"""A CPU rehearsal (the Pallas interpreter; a result, no time): one head of 128 x 128 over 1024 tokens,
bf16 operands, the per-channel scan at the parent's tiling and at chunks of 128 against the float32
recurrence token by token (tests/test_kimi_linear.py's own inputs and reference), output and the five
gradients: the largest gap over the largest entry, and the gap's norm over the norm. Says how far a
chunk of 128 moves the kernels from the recurrence beside what bf16 operands already do.
``JAX_PLATFORMS=cpu python bench_results/hw_pr45/chunk_gaps_cpu.py``"""
import functools
import os
import sys
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT); sys.path.insert(0, os.path.join(ROOT, "tests"))
import jax, jax.numpy as jnp, numpy as np
import test_kimi_linear as t
from csed_514_project_distributed_training_using_pytorch_tpu.ops import kda
for decay in (1.0, 8.0):
    q, k, v, g, beta = t.scan_inputs(1, 1024, 1, 128, 128, decay)
    low = tuple(x.astype(jnp.bfloat16) for x in (q, k, v)) + (g, beta)
    w = jax.random.normal(jax.random.PRNGKey(9), low[2].shape)
    with jax.default_matmul_precision("highest"):
        want, wants = t.scan_and_gradients(t.normed_recurrence, tuple(x.astype(jnp.float32) for x in low), w)
    for tile in ((64, 4, 4), (128, 4, 4), (128, 8, 4)):
        got, grads = t.scan_and_gradients(functools.partial(t.flat_scan, chunk=tile[0], sub=tile[1], group=tile[2]), low, w)
        rel = lambda a, b: float(jnp.abs(a.astype(jnp.float32) - b).max() / jnp.abs(b).max())
        l2 = lambda a, b: float(jnp.linalg.norm((a.astype(jnp.float32) - b).ravel()) / jnp.linalg.norm(b.ravel()))
        print(decay, tile, "max-gap out", round(rel(got, want), 5), {n: round(rel(a, b), 5) for n, a, b in zip(t.OPERANDS, grads, wants)},
              "l2 out", round(l2(got, want), 5), {n: round(l2(a, b), 5) for n, a, b in zip(t.OPERANDS, grads, wants)}, flush=True)
