"""Dump the lowered text of grad(flash_attention) at both cells' calls.

usage: python lower_flash.py <repo root to import from> <out dir> [--force-tpu-lowering]
On the chip the default backend is the TPU. Off it, --force-tpu-lowering lowers for the
tpu platform with the kernels' interpret switch forced off (a rehearsal: nothing runs).
"""
import hashlib, json, os, re, sys
root, out = sys.argv[1], sys.argv[2]
force = "--force-tpu-lowering" in sys.argv
sys.path.insert(0, root)
os.makedirs(out, exist_ok=True)
import jax, jax.numpy as jnp
from csed_514_project_distributed_training_using_pytorch_tpu.ops import pallas_attention as pa
assert os.path.realpath(pa.__file__).startswith(os.path.realpath(root)), pa.__file__
if force:
    pa._interpret = lambda: False
# The Mosaic module rides in the custom call as bytecode WITH source locations, so the
# stablehlo text differs between two trees by line numbers alone. Record each kernel's
# module as text without locations, in lowering order, and blank the bytecode.
import jax._src.tpu_custom_call as tcc
_orig = tcc._lower_mosaic_module_to_asm
mosaic = []
def _rec(module, **kw):
    mosaic.append(module.operation.get_asm(enable_debug_info=False))
    return _orig(module, **kw)
tcc._lower_mosaic_module_to_asm = _rec
res = {"root": root, "backend": jax.default_backend(),
       "device_kind": jax.devices()[0].device_kind, "forced": force}
for name, shape in (("lm_train_b16", (16, 784, 8, 128)), ("lfm2_moe_train_8k", (4, 8192, 32, 64))):
    f = jax.jit(jax.grad(lambda q, k, v: pa.flash_attention(q, k, v, causal=True).sum(), (0, 1, 2)))
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    traced = f.trace(x, x, x)
    lowered = traced.lower(lowering_platforms=("tpu",)) if force else traced.lower()
    for tag, text in (("plain", lowered.as_text()), ("debug", lowered.as_text(debug_info=True))):
        with open(os.path.join(out, f"{name}.{tag}.mlir"), "w") as fh:
            fh.write(text)
    text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', 'BODY', lowered.as_text())
    with open(os.path.join(out, f"{name}.nobody.mlir"), "w") as fh:
        fh.write(text)
    with open(os.path.join(out, f"{name}.mosaic.mlir"), "w") as fh:
        fh.write("\n// -----\n".join(mosaic))
    res[name + "_mosaic"] = {"modules": len(mosaic), "sha256": hashlib.sha256("".join(mosaic).encode()).hexdigest()}
    del mosaic[:]
    res[name] = {"sha256": hashlib.sha256(text.encode()).hexdigest(), "bytes": len(text),
                 "tpu_custom_calls": text.count("tpu_custom_call"),
                 "kernels": sorted(set(re.findall(r"flash_(?:fwd|dq|dkv)", text)))}
    if not force and jax.default_backend() == "tpu":
        c = lowered.compile()
        h = c.as_text()
        res[name]["compiled_sha256"] = hashlib.sha256(re.sub(r'metadata=\{[^}]*\}', '', h).encode()).hexdigest()
print(json.dumps(res))
with open(os.path.join(out, "summary.json"), "w") as fh:
    json.dump(res, fh)
