"""kda_fwd / kda_bwd as Mosaic compiles them, on the chip: (a) against the token-by-token
recurrence with the norms, β and the statistic in plain jnp (tests/test_kimi_linear.py's
own helpers) at the published tile, float32 and bfloat16 operands; (b) at the cell's shapes
(2 x 8192 tokens, 32 heads of 128 x 128, bf16) run twice for the same bits, then timed alone.
usage (chip only): python3 bench_results/hw_pr34/kernels_on_chip.py"""
import functools, json, os, sys, time
root = os.path.realpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path[:0] = [root, os.path.join(root, "tests")]
import jax, jax.numpy as jnp
import test_kimi_linear as t
from csed_514_project_distributed_training_using_pytorch_tpu.ops import kda

assert jax.default_backend() == "tpu", jax.default_backend()
rel = lambda a, b: float(jnp.abs(a.astype(jnp.float32) - b).max() / jnp.abs(b).max())
for dtype in (jnp.float32, jnp.bfloat16):
    for decay in (1.0, 8.0):
        q, k, v, g, beta = t.scan_inputs(1, 640, 2, 128, 128, decay, seed=11)
        low = tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)
        w = jax.random.normal(jax.random.PRNGKey(9), v.shape)
        got, grads = t.scan_and_gradients(lambda *a: t.flat_scan(*a).astype(jnp.float32), low, w)
        with jax.default_matmul_precision("highest"):
            want, wants = t.scan_and_gradients(
                t.normed_recurrence, tuple(x.astype(jnp.float32) for x in low), w)
        print(json.dumps({"check": jnp.dtype(dtype).name, "decay": decay, "out": rel(got, want),
                          **{n: rel(a, b) for n, a, b in zip(t.OPERANDS, grads, wants)},
                          "finite": bool(all(jnp.isfinite(x.astype(jnp.float32)).all() for x in grads))}))

b, s, h, d = 2, 8192, 32, 128
ks = jax.random.split(jax.random.PRNGKey(0), 5)
x = [jax.nn.silu(jax.random.normal(key, (b, s, h * d))).astype(jnp.bfloat16) for key in ks[:3]]
g = -jax.nn.softplus(jax.random.normal(ks[3], (b, s, h * d)))
beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
scan = functools.partial(kda.kda_scan, eps=1e-5)
forward = jax.jit(scan)
both = jax.jit(jax.grad(lambda *a: jnp.sum(scan(*a).astype(jnp.float32)), argnums=(0, 1, 2, 3, 4)))
for name, fn in (("kda_fwd", forward), ("kda_fwd+kda_bwd", both)):
    first, again = (jax.tree.leaves(fn(*x, g, beta)) for _ in range(2))
    print(json.dumps({"repeated": name,      # one program run twice: the same bits
                      "same_bits": [bool(jnp.array_equal(a, b)) for a, b in zip(first, again)]}))
    times = []
    for _ in range(5):
        t0 = time.perf_counter(); jax.block_until_ready(fn(*x, g, beta)); times.append(time.perf_counter() - t0)
    chunks = b * h * s // kda.CHUNK
    print(json.dumps({"timed": name, "ms": [round(1e3 * x, 3) for x in times],
                      "us_a_chunk_and_head": round(1e6 * min(times) / chunks, 3)}))
