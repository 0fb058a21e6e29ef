"""A Pallas kernel lowered for the TPU carries, in its serialized Mosaic module, the name and the file
of the function that called it: two callers, one kernel, two bodies. It is why the optimized HLO
of a cell differs between two trees inside its kernels' `body` strings alone when the loss closure
moved from `train/lm.py::main.<locals>.lm_loss` to `TransformerLM.trainee.<locals>.<lambda>`, and why
a compile cache one tree filled misses for the other (hlo_hash.py, pairs.sh). CPU, seconds."""
import os, re, base64
os.environ["JAX_PLATFORMS"]="cpu"
import jax, jax.numpy as jnp
from jax.experimental import pallas as pl
def kernel(x_ref, o_ref): o_ref[...] = x_ref[...] * 2
def call(x): return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)
def lm_loss(x): return call(x).sum()
def trainee_lambda_with_a_longer_name(x): return call(x).sum()
x = jax.ShapeDtypeStruct((8,128), jnp.float32)
bodies=[]
for f in (lm_loss, trainee_lambda_with_a_longer_name):
    t = jax.jit(f).trace(x).lower(lowering_platforms=("tpu",)).as_text()
    m = re.search(r'\\22body\\22: \\22([^\\]*)\\22', t) or re.search(r'"body": "([^"]*)"', t)
    raw = base64.b64decode(m.group(1)); bodies.append(raw)
    print(f.__name__, len(raw), f.__name__.encode() in raw, os.path.basename(__file__).encode() in raw)
print("equal", bodies[0]==bodies[1])
