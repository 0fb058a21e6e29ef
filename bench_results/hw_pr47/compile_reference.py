"""Compile the plain reference's training step (benchmark/reference/falcon_h1.py through
reference/train.py's make_step, as drivers/train_corpus_ssm.py's reference_follow drives it)
at the cell's size for a described v5e and print its memory: the reference has to fit the
chip beside nothing else. A compile, not a chip run.
usage: JAX_PLATFORMS=cpu [LAYERS=4] [PRECISION=fp8] python compile_reference.py"""
import json, os, sys, time
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.realpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
import harness
jax.config.update("jax_enable_compilation_cache", False)
bench = os.path.join(ROOT, "benchmark")
ref, ref_train = harness.load_reference(bench, "falcon_h1"), harness.load_reference(bench, "train")
for name in ("SCORE_BLOCK", "ROW_BLOCK", "TIME_BLOCK"):
    setattr(ref, name, int(os.environ.get(name, getattr(ref, name))))
config = harness.read_json(os.path.join(bench, "configs", "falcon-h1-34b-tp4.json"))
config["num_hidden_layers"] = int(os.environ.get("LAYERS", config["num_hidden_layers"]))
model = {k: v for k, v in config.items() if k not in ("train", "model")}
chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
on = lambda t: jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), t)
params = ref.param_shapes(model)
precision = os.environ.get("PRECISION", "highest")         # PRECISION=fp8: the control
step = ref_train.make_step(lambda p, b: ref.loss(p, b, model, precision=precision),
                           config["train"]["optimizer"])
t0 = time.time()
with jax.default_matmul_precision("highest"):
    compiled = step.lower(on(params), on({"m": params, "v": params}),
                          on(jax.ShapeDtypeStruct((1, 8192), jnp.int32)),
                          on(jax.ShapeDtypeStruct((), jnp.int32))).compile()
m = compiled.memory_analysis()
print(json.dumps({"what": "reference step, batch 1 x 8192", "layers": config["num_hidden_layers"],
                  "precision": precision, "args": m.argument_size_in_bytes,
                  "temp": m.temp_size_in_bytes, "args+temp": m.argument_size_in_bytes + m.temp_size_in_bytes,
                  "alias": m.alias_size_in_bytes, "blocks": [ref.SCORE_BLOCK, ref.ROW_BLOCK, ref.TIME_BLOCK],
                  "compile_s": round(time.time() - t0, 1)}))
