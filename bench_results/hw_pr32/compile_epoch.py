"""Compile an expert cell's 8-step epoch program for a described v5e; print its memory
and how many products touch the head's [T, vocab] logits (HybridLM.head_products on
the jaxpr, where the tree has it) and carry the scope head_loss in the compiled text.
usage: JAX_PLATFORMS=cpu [TREE=<repo root to import from>] python compile_epoch.py <kimi|lfm2|nemotron> [--text out.txt] [--dump dir]
bench_results/hw_pr31/compile_epoch.py with the kimi cell (BATCH=<n> sets its batch). A compile, not a chip run."""
import json, os, re, sys, time
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.realpath(os.environ.get("TREE", os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))))
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from csed_514_project_distributed_training_using_pytorch_tpu import ops
from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
from csed_514_project_distributed_training_using_pytorch_tpu.ops import moe, optim, pallas_attention, ssm
try:
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import kda
except ImportError:
    kda = None
from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
    create_train_state, make_train_step, make_epoch_from_step)

CELLS = {"kimi": ("kimi-linear-48b-a3b-ep32.json", 20480, int(os.environ.get("BATCH", 2))), "lfm2": ("lfm2-24b-a2b-ep8.json", 8192, 4), "nemotron": ("nemotron3-super-120b-tp8-ep64.json", 16384, 2)}
file, vocab, B = CELLS[sys.argv[1]]
S, STEPS = 8192, 8
tree = sys.path[0]
assert os.path.realpath(hybrid_lm.__file__).startswith(tree), hybrid_lm.__file__
jax.config.update("jax_enable_compilation_cache", False)
for module in filter(None, (moe, pallas_attention, ssm, kda)):
    module._interpret = lambda: False
hybrid_lm.KEPT = tuple(n for n in hybrid_lm.KEPT if n not in os.environ.get("DROP", "").split(","))  # DROP=a,b: compile without these kept tags
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])
model = hybrid_lm.from_config_file(f"{tree}/benchmark/configs/{file}", vocab_size=vocab, seq_len=S,
                                   dtype=jnp.bfloat16, remat=True, attention_fn=ops.dispatch_attention)
opt = optim.freeze(optim.make_optimizer("adamw", learning_rate=1e-6, momentum=0.5, weight_decay=0.01),
                   hybrid_lm.is_frozen)
state = jax.eval_shape(lambda: create_train_state(model, jax.random.PRNGKey(0), sample_input_shape=(1, S),
                                                  optimizer=opt))
extra = {"after_update": model.rebalance} if model.router_bias_update_rate else {}
step = make_train_step(model, learning_rate=1e-6, momentum=0.5, optimizer=opt, clip_grad_norm=1.0,
                       loss_fn=lambda params, xs, ys, rng: model.loss(params, xs), loss_has_aux=True, **extra)
epoch = jax.jit(make_epoch_from_step(step, aux=True), donate_argnums=(0,))
on = lambda t: jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), t)
n = B * STEPS
args = (on(state), on(jax.ShapeDtypeStruct((n, S), jnp.int32)), on(jax.ShapeDtypeStruct((n,), jnp.int32)),
        on(jax.ShapeDtypeStruct((STEPS, B), jnp.int32)), on(jax.eval_shape(lambda: jax.random.PRNGKey(1))))
t0 = time.time()
traced = epoch.trace(*args)
lowered = traced.lower()
t1 = time.time()
dump = {"xla_dump_to": sys.argv[sys.argv.index("--dump") + 1], "xla_dump_hlo_as_text": True} if "--dump" in sys.argv else None
compiled = lowered.compile(compiler_options=dump)
t2 = time.time()
m, text = compiled.memory_analysis(), compiled.as_text()
T = B * S
products = [l for l in text.splitlines() if re.search(r" (convolution|dot)\(", l)]
head = [l for l in products if "head_loss" in l]
count = getattr(model, "head_products", None)
out = {"cell": sys.argv[1], "batch": B, "dropped": os.environ.get("DROP", ""), "tree": tree, "args": m.argument_size_in_bytes, "temp": m.temp_size_in_bytes,
       "args+temp": m.argument_size_in_bytes + m.temp_size_in_bytes, "out": m.output_size_in_bytes,
       "alias": m.alias_size_in_bytes, "lower_s": round(t1 - t0, 1), "compile_s": round(t2 - t1, 1),
       "head_products_jaxpr": count(traced.jaxpr, T) if count else None,
       "products_in_text": len(products), "head_loss_products_in_text": len(head)}
print(json.dumps(out))
if "--text" in sys.argv:
    with open(sys.argv[sys.argv.index("--text") + 1], "w") as fh:
        fh.write(text)
