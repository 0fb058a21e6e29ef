"""The epoch program of each of the four cells, lowered for the tpu platform with the kernels'
interpret switch off (a rehearsal: nothing runs), as two hashes: the stablehlo text (printed
without locations, which is where op_names live) with each Mosaic body blanked and private
function numbers dropped (those of the quoted `@"<unknown>_100"` too, which PR 38's tool left: a
tag more in a block's jaxpr moves that counter and nothing else), and the Mosaic modules printed without source locations. Two trees
whose hashes agree run the same program in that cell but for locations and op_name metadata
(bench_results/hw_pr38/lower_epoch.py with the kanana cell beside the five, the forward pass alone
(the loss, which is what the eval program and the checks' one-row programs run of the model) hashed
beside the epoch program).
usage: JAX_PLATFORMS=cpu python lower_epoch.py <repo root to import from> <lm|lfm2|nemotron|kimi|evabyte|kanana|qwen>
(qwen: added by PR 44)"""
import hashlib, json, os, re, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
root, cell = os.path.realpath(sys.argv[1]), sys.argv[2]
sys.path.insert(0, root)
import jax, jax.numpy as jnp
import jax._src.tpu_custom_call as tcc
from csed_514_project_distributed_training_using_pytorch_tpu import ops
from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm, lm as lm_mod
from csed_514_project_distributed_training_using_pytorch_tpu.ops import eva, kda, moe, optim, pallas_attention, ssm
from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
    create_train_state, make_train_step, make_epoch_from_step)
assert os.path.realpath(hybrid_lm.__file__).startswith(root), hybrid_lm.__file__
for module in (moe, pallas_attention, ssm, kda, eva):
    module._interpret = lambda: False
_orig, mosaic = tcc._lower_mosaic_module_to_asm, []
def _rec(module, **kw):
    mosaic.append(module.operation.get_asm(enable_debug_info=False))
    return _orig(module, **kw)
tcc._lower_mosaic_module_to_asm = _rec
if cell == "lm":        # train/lm.py's program at benchmark/configs/pixel-lm-d1024.json + train_b16
    S, STEPS, B = 784, 32, 16
    model = lm_mod.TransformerLM(vocab_size=17, seq_len=S, embed_dim=1024, num_layers=8, num_heads=8,
                                 dropout_rate=0.0, num_kv_heads=2, attention_window=0, rope=True,
                                 dtype=jnp.bfloat16, remat=False, attention_fn=ops.dispatch_attention)
    opt = optim.make_optimizer("adamw", learning_rate=3e-4, momentum=0.5, weight_decay=0.01)
    loss = lambda params, xs, ys, rng: lm_mod.next_token_loss(model, params, xs, rng, deterministic=True,
                                                              label_smoothing=0.0)
    extra, aux, lr = {}, False, 3e-4
else:
    file, vocab, B = {"kimi": ("kimi-linear-48b-a3b-ep32.json", 20480, 2), "lfm2": ("lfm2-24b-a2b-ep8.json", 8192, 4),
                      "nemotron": ("nemotron3-super-120b-tp8-ep64.json", 16384, 2),
                      "evabyte": ("evabyte-6.5b-tp2.json", 320, 1),
                      "kanana": ("kanana-2-30b-a3b-ep8.json", 16032, 2),
                      "qwen": ("qwen3-next-80b-a3b-ep16.json", 18992, 2)}[cell]
    S, STEPS = (32768, 4) if cell == "evabyte" else (8192, 8)
    model = hybrid_lm.from_config_file(f"{root}/benchmark/configs/{file}", vocab_size=vocab, seq_len=S,
                                       dtype=jnp.bfloat16, remat=True, attention_fn=ops.dispatch_attention)
    opt = optim.freeze(optim.make_optimizer("adamw", learning_rate=1e-6, momentum=0.5, weight_decay=0.01), hybrid_lm.is_frozen)
    loss = lambda params, xs, ys, rng: model.loss(params, xs)
    extra = {"after_update": model.rebalance} if model.router_bias_update_rate else {}
    aux, lr = True, 1e-6
state = jax.eval_shape(lambda: create_train_state(model, jax.random.PRNGKey(0), sample_input_shape=(1, S), optimizer=opt))
step = make_train_step(model, learning_rate=lr, momentum=0.5, optimizer=opt, clip_grad_norm=1.0,
                       loss_fn=loss, loss_has_aux=aux, **extra)
epoch = jax.jit(make_epoch_from_step(step, aux=aux), donate_argnums=(0,))
n = B * STEPS
args = (state, jax.ShapeDtypeStruct((n, S), jnp.int32), jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((STEPS, B), jnp.int32), jax.eval_shape(lambda: jax.random.PRNGKey(1)))
def unnumbered(lowered):
    text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', 'BODY', lowered.as_text())
    return text, re.sub(r'(@"?[A-Za-z_<][\w.<>]*?)_\d+\b', r'\1_N', text)
lowered = epoch.trace(*args).lower(lowering_platforms=("tpu",))
with_names = lowered.as_text(debug_info=True)
text, numbered = unnumbered(lowered)
if cell == "lm":
    forward = lambda params, xs: loss(params, xs, None, None)
else:
    forward = lambda params, xs: model.loss(params, xs)[0]
_, forward_text = unnumbered(jax.jit(forward).trace(
    state.params, jax.ShapeDtypeStruct((B, S), jnp.int32)).lower(lowering_platforms=("tpu",)))
print(json.dumps({"root": root, "cell": cell, "bytes": len(text), "tpu_custom_calls": text.count("tpu_custom_call"),
                  "stablehlo_unnumbered_sha256": hashlib.sha256(numbered.encode()).hexdigest(),
                  "forward_stablehlo_unnumbered_sha256": hashlib.sha256(forward_text.encode()).hexdigest(),
                  "mosaic_modules": len(mosaic),
                  "mosaic_sha256": hashlib.sha256("".join(mosaic).encode()).hexdigest(),
                  "locations_naming_optimizer": with_names.count("optimizer")}))
