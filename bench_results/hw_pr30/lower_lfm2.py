"""Dump the lowered text of the lfm2-24b-a2b-ep8 train step (value, gradient, clip,
AdamW; bf16, remat) at the cell's batch, lowered for the tpu platform with the
kernels' interpret switch forced off (a rehearsal: nothing runs).

usage: python lower_lfm2.py <repo root to import from> <out dir>
Two trees give the same program when `summary.json` agrees on both hashes: the
stablehlo text with each Mosaic body blanked and private function numbers dropped, and
the Mosaic modules printed without source locations.
"""
import hashlib, json, os, re, sys
root, out = os.path.realpath(sys.argv[1]), sys.argv[2]
sys.path.insert(0, root)
os.makedirs(out, exist_ok=True)
import jax, jax.numpy as jnp
from csed_514_project_distributed_training_using_pytorch_tpu import ops
from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
from csed_514_project_distributed_training_using_pytorch_tpu.ops import moe, optim, pallas_attention
from csed_514_project_distributed_training_using_pytorch_tpu.train.step import create_train_state, make_train_step
assert os.path.realpath(hybrid_lm.__file__).startswith(root), hybrid_lm.__file__
for module in (moe, pallas_attention):
    module._interpret = lambda: False
import jax._src.tpu_custom_call as tcc
_orig, mosaic = tcc._lower_mosaic_module_to_asm, []
def _rec(module, **kw):
    mosaic.append(module.operation.get_asm(enable_debug_info=False))
    return _orig(module, **kw)
tcc._lower_mosaic_module_to_asm = _rec
batch, seq = 4, 8192
model = hybrid_lm.from_config_file(os.path.join(root, "benchmark/configs/lfm2-24b-a2b-ep8.json"),
                                   vocab_size=8192, seq_len=seq, dtype=jnp.bfloat16, remat=True,
                                   attention_fn=ops.dispatch_attention)
optimizer = optim.freeze(optim.make_optimizer("adamw", learning_rate=1e-6, momentum=0.0, weight_decay=0.01), hybrid_lm.is_frozen)
step = make_train_step(model, learning_rate=1e-6, momentum=0.0, optimizer=optimizer, clip_grad_norm=1.0,
                       loss_fn=lambda params, xs, ys, rng: model.loss(params, xs), loss_has_aux=True)
state = jax.eval_shape(lambda: create_train_state(model, jax.random.PRNGKey(0), sample_input_shape=(1, seq), optimizer=optimizer))
args = (state, jax.ShapeDtypeStruct((batch, seq), jnp.int32), jax.ShapeDtypeStruct((batch,), jnp.int32),
        jax.eval_shape(lambda: jax.random.PRNGKey(0)))
text = jax.jit(step).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', 'BODY', text)
with open(os.path.join(out, "step.nobody.mlir"), "w") as fh:
    fh.write(text)
with open(os.path.join(out, "step.mosaic.mlir"), "w") as fh:
    fh.write("\n// -----\n".join(mosaic))
numbered = re.sub(r'(@[A-Za-z_][\w.]*?)_\d+\b', r'\1_N', text)
res = {"root": root, "stablehlo_sha256": hashlib.sha256(text.encode()).hexdigest(),
       "stablehlo_unnumbered_sha256": hashlib.sha256(numbered.encode()).hexdigest(), "bytes": len(text),
       "tpu_custom_calls": text.count("tpu_custom_call"), "mosaic_modules": len(mosaic),
       "mosaic_sha256": hashlib.sha256("".join(mosaic).encode()).hexdigest()}
print(json.dumps(res))
with open(os.path.join(out, "summary.json"), "w") as fh:
    json.dump(res, fh)
