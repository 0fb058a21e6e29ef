"""Compile lfm2_moe_train_8k's 8-step epoch program for a described v5e; print memory.
usage: JAX_PLATFORMS=cpu python compile_epoch.py [name,name,...|none|all|nopolicy] [--text out.txt]
Names are tags of hybrid_lm.KEPT ("all": the module's constant; "nopolicy": jax.checkpoint(fn) as
before PR 29). PR 29's 15,097,541,632-byte reading also kept W3 u, under a tag "ff_up" beside
"ff_gate" in dense_ff that the final tree does not carry. A compile, not a chip run."""
import os, sys, time, json
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.environ.get("TREE", os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))))
import jax, jax.numpy as jnp, numpy as np
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from csed_514_project_distributed_training_using_pytorch_tpu import ops
from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
from csed_514_project_distributed_training_using_pytorch_tpu.ops import moe, optim, pallas_attention
from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
    create_train_state, make_train_step, make_epoch_from_step)

kept = sys.argv[1] if len(sys.argv) > 1 else "all"
policy = True
if kept == "nopolicy":
    policy = False
elif kept == "none":
    hybrid_lm.KEPT = ()
elif kept != "all":
    hybrid_lm.KEPT = tuple(kept.split(","))
if not policy:
    jax.checkpoint_policies.save_only_these_names = lambda *n: None
B, S, STEPS = int(os.environ.get("B", 4)), 8192, int(os.environ.get("STEPS", 8))
jax.config.update("jax_enable_compilation_cache", False)
moe._interpret = lambda: False
pallas_attention._interpret = lambda: False
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])
tree = sys.path[0]
model = hybrid_lm.from_config_file(f"{tree}/benchmark/configs/lfm2-24b-a2b-ep8.json",
    vocab_size=8192, seq_len=S, dtype=jnp.bfloat16, remat=True,
    attention_fn=ops.dispatch_attention)
opt = optim.freeze(optim.make_optimizer("adamw", learning_rate=1e-6, momentum=0.5,
                                        weight_decay=0.01), hybrid_lm.is_frozen)
state = jax.eval_shape(lambda: create_train_state(model, jax.random.PRNGKey(0),
        sample_input_shape=(1, S), optimizer=opt))
def lm_loss(params, xs, ys, rng):
    return model.loss(params, xs)
step = make_train_step(model, learning_rate=1e-6, momentum=0.5, optimizer=opt,
                       clip_grad_norm=1.0, loss_fn=lm_loss, loss_has_aux=True)
epoch = jax.jit(make_epoch_from_step(step, aux=True), donate_argnums=(0,))
on = lambda t: jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), t)
n = B * STEPS
args = (on(state), on(jax.ShapeDtypeStruct((n, S), jnp.int32)),
        on(jax.ShapeDtypeStruct((n,), jnp.int32)),
        on(jax.ShapeDtypeStruct((STEPS, B), jnp.int32)),
        on(jax.eval_shape(lambda: jax.random.PRNGKey(1))))
t0 = time.time()
lowered = epoch.lower(*args)
t1 = time.time()
compiled = lowered.compile()
t2 = time.time()
m = compiled.memory_analysis()
text = compiled.as_text()
import re
calls = {k: len(re.findall(rf'custom_call_target="tpu_custom_call".*kernel_name.*?"{k}"|%{k}[.\d]* = ', text)) for k in
         ("flash_fwd", "flash_dq", "flash_dkv", "moe_ffn_fwd", "moe_pack", "moe_gather", "moe_combine")}
out = {"kept": list(hybrid_lm.KEPT) if policy else "nopolicy", "args": m.argument_size_in_bytes, "temp": m.temp_size_in_bytes,
       "out": m.output_size_in_bytes, "alias": m.alias_size_in_bytes,
       "args+temp": m.argument_size_in_bytes + m.temp_size_in_bytes,
       "lower_s": round(t1 - t0, 1), "compile_s": round(t2 - t1, 1), "calls": calls,
       "sorts": len(re.findall(r" sort\(", text)), "topk": len(re.findall(r"topk|TopK|top_k", text))}
print(json.dumps(out))
if "--text" in sys.argv:
    open(sys.argv[sys.argv.index("--text") + 1], "w").write(text)
