"""The 8-step epoch program of an expert cell, lowered for the tpu platform with the kernels'
interpret switch off (a rehearsal: nothing runs), as two hashes: the stablehlo text with each
Mosaic body blanked and private function numbers dropped, and the Mosaic modules printed
without source locations. Two trees whose hashes agree run the same program in that cell
(PR 28's / PR 30's lower_*.py, on bench_results/hw_pr32/compile_epoch.py's program).
usage: JAX_PLATFORMS=cpu python lower_epoch.py <repo root to import from> <lfm2|nemotron|kimi>"""
import hashlib, json, os, re, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
root, cell = os.path.realpath(sys.argv[1]), sys.argv[2]
sys.path.insert(0, root)
import jax, jax.numpy as jnp
import jax._src.tpu_custom_call as tcc
from csed_514_project_distributed_training_using_pytorch_tpu import ops
from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
from csed_514_project_distributed_training_using_pytorch_tpu.ops import kda, moe, optim, pallas_attention, ssm
from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
    create_train_state, make_train_step, make_epoch_from_step)
assert os.path.realpath(hybrid_lm.__file__).startswith(root), hybrid_lm.__file__
for module in (moe, pallas_attention, ssm, kda):
    module._interpret = lambda: False
_orig, mosaic = tcc._lower_mosaic_module_to_asm, []
def _rec(module, **kw):
    mosaic.append(module.operation.get_asm(enable_debug_info=False))
    return _orig(module, **kw)
tcc._lower_mosaic_module_to_asm = _rec
file, vocab, B = {"kimi": ("kimi-linear-48b-a3b-ep32.json", 20480, 2), "lfm2": ("lfm2-24b-a2b-ep8.json", 8192, 4),
                  "nemotron": ("nemotron3-super-120b-tp8-ep64.json", 16384, 2)}[cell]
S, STEPS = 8192, 8
model = hybrid_lm.from_config_file(f"{root}/benchmark/configs/{file}", vocab_size=vocab, seq_len=S,
                                   dtype=jnp.bfloat16, remat=True, attention_fn=ops.dispatch_attention)
opt = optim.freeze(optim.make_optimizer("adamw", learning_rate=1e-6, momentum=0.5, weight_decay=0.01), hybrid_lm.is_frozen)
state = jax.eval_shape(lambda: create_train_state(model, jax.random.PRNGKey(0), sample_input_shape=(1, S), optimizer=opt))
extra = {"after_update": model.rebalance} if model.router_bias_update_rate else {}
step = make_train_step(model, learning_rate=1e-6, momentum=0.5, optimizer=opt, clip_grad_norm=1.0,
                       loss_fn=lambda params, xs, ys, rng: model.loss(params, xs), loss_has_aux=True, **extra)
epoch = jax.jit(make_epoch_from_step(step, aux=True), donate_argnums=(0,))
n = B * STEPS
args = (state, jax.ShapeDtypeStruct((n, S), jnp.int32), jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((STEPS, B), jnp.int32), jax.eval_shape(lambda: jax.random.PRNGKey(1)))
text = epoch.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', 'BODY', text)
numbered = re.sub(r'(@[A-Za-z_][\w.]*?)_\d+\b', r'\1_N', text)
print(json.dumps({"root": root, "cell": cell, "bytes": len(text), "tpu_custom_calls": text.count("tpu_custom_call"),
                  "stablehlo_unnumbered_sha256": hashlib.sha256(numbered.encode()).hexdigest(),
                  "mosaic_modules": len(mosaic),
                  "mosaic_sha256": hashlib.sha256("".join(mosaic).encode()).hexdigest()}))
