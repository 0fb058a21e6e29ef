"""Compile the falcon_h1 cell's 8-step epoch program (1 x 8192 tokens a step) for a described
v5e and print its memory; bench_results/hw_pr43/compile_epoch.py with this cell.
usage: JAX_PLATFORMS=cpu [LAYERS=4] [KEPT=flash_out,flash_lse,...] python compile_epoch.py [--text out.txt]
A compile, not a chip run."""
import dataclasses, json, os, sys, time
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.realpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from csed_514_project_distributed_training_using_pytorch_tpu import ops
from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
from csed_514_project_distributed_training_using_pytorch_tpu.ops import optim, pallas_attention, ssm
from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
    create_train_state, make_train_step, make_epoch_from_step)

S, STEPS, B = int(os.environ.get("SEQ", 8192)), 8, int(os.environ.get("BATCH", 1))
jax.config.update("jax_enable_compilation_cache", False)
for module in (ssm, pallas_attention):
    module._interpret = lambda: False
with open(f"{ROOT}/benchmark/configs/falcon-h1-34b-tp4.json") as fh:
    config = json.load(fh)
config["num_hidden_layers"] = int(os.environ.get("LAYERS", config["num_hidden_layers"]))
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])
model = hybrid_lm.from_config(config, vocab_size=config["vocab_size"], seq_len=S, dtype=jnp.bfloat16,
                              remat=True, attention_fn=ops.dispatch_attention)
if "KEPT" in os.environ:
    model = dataclasses.replace(model, kept=tuple(filter(None, os.environ["KEPT"].split(","))))
opt = optim.freeze(optim.make_optimizer("adamw", learning_rate=1e-6, momentum=0.5, weight_decay=0.01),
                   hybrid_lm.is_frozen)
state = jax.eval_shape(lambda: create_train_state(model, jax.random.PRNGKey(0), sample_input_shape=(1, S),
                                                  optimizer=opt))
step = make_train_step(model, learning_rate=1e-6, momentum=0.5, optimizer=opt, clip_grad_norm=1.0,
                       loss_fn=lambda params, xs, ys, rng: model.loss(params, xs), loss_has_aux=True,
                       after_update=None)
epoch = jax.jit(make_epoch_from_step(step, aux=True), donate_argnums=(0,))
on = lambda t: jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), t)
n = B * STEPS
args = (on(state), on(jax.ShapeDtypeStruct((n, S), jnp.int32)), on(jax.ShapeDtypeStruct((n,), jnp.int32)),
        on(jax.ShapeDtypeStruct((STEPS, B), jnp.int32)), on(jax.eval_shape(lambda: jax.random.PRNGKey(1))))
t0 = time.time()
lowered = epoch.trace(*args).lower()
t1 = time.time()
compiled = lowered.compile()
t2 = time.time()
m = compiled.memory_analysis()
print(json.dumps({"layers": config["num_hidden_layers"], "kept": list(model.kept), "seq": S,
                  "args": m.argument_size_in_bytes, "temp": m.temp_size_in_bytes,
                  "args+temp": m.argument_size_in_bytes + m.temp_size_in_bytes,
                  "out": m.output_size_in_bytes, "alias": m.alias_size_in_bytes,
                  "lower_s": round(t1 - t0, 1), "compile_s": round(t2 - t1, 1)}))
if "--text" in sys.argv:
    with open(sys.argv[sys.argv.index("--text") + 1], "w") as fh:
        fh.write(compiled.as_text())
