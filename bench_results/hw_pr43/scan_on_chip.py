"""The scalar-decay scan alone on the chip, at the cell's shapes (2 x 8192 tokens, 32 value
heads on 16 key heads of 128), by tiling (chunk, sub-block, group): forward and forward +
backward, ms a call and µs a chunk and value head, each tiling's output and gradients held
to the first's. `python3 bench_results/hw_pr43/scan_on_chip.py [out.jsonl]` through chiprun."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
from csed_514_project_distributed_training_using_pytorch_tpu.ops import kda  # noqa: E402

B, S, KH, H, D = 2, 8192, 16, 32, 128
TILES = [tuple(map(int, t.split(","))) for t in os.environ.get(
    "TILES", "64,4,4;64,8,4;64,16,4;64,64,4;64,4,8;64,8,8;128,8,2;128,16,2;32,4,8").split(";")]
keys = jax.random.split(jax.random.PRNGKey(0), 6)
q, k = (jax.random.normal(x, (B, S, KH * D), jnp.bfloat16) for x in keys[:2])
v, w = (jax.random.normal(x, (B, S, H * D), jnp.bfloat16) for x in keys[2:4])
g = -jax.nn.softplus(jax.random.normal(keys[4], (B, S, H)))
beta = jax.nn.sigmoid(jax.random.normal(keys[5], (B, S, H)))
if len(sys.argv) > 1:
    os.makedirs(os.path.dirname(os.path.abspath(sys.argv[1])), exist_ok=True)
out = open(sys.argv[1], "w") if len(sys.argv) > 1 else sys.stdout
first = None
for chunk, sub, group in TILES:
    scan = lambda *a: kda.gdn_scan(*a, key_heads=KH, eps=1e-6, chunk=chunk, sub=sub, group=group)
    fwd = jax.jit(scan)
    both = jax.jit(jax.grad(lambda *a: jnp.sum((scan(*a) * w).astype(jnp.float32)),
                            argnums=(0, 1, 2, 3, 4)))
    row = {"chunk": chunk, "sub": sub, "group": group, "device": jax.devices()[0].device_kind}
    try:
        results = {}
        for name, fn in (("forward", fwd), ("forward_backward", both)):
            t0 = time.perf_counter()
            results[name] = jax.block_until_ready(fn(q, k, v, g, beta))
            row[name + "_compile_s"] = round(time.perf_counter() - t0, 2)
            t0 = time.perf_counter()
            for _ in range(10):
                r = fn(q, k, v, g, beta)
            jax.block_until_ready(r)
            row[name + "_ms"] = (time.perf_counter() - t0) / 10 * 1e3
        row["us_per_chunk64_head"] = row["forward_backward_ms"] * 1e3 / (B * H * S // 64)
        flat = [results["forward"], *results["forward_backward"]]
        if first is None:
            first = flat
        row["worst_gap_to_first"] = max(
            float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()
                  / (jnp.abs(b.astype(jnp.float32)).max() + 1e-9)) for a, b in zip(flat, first))
    except Exception as e:      # a tiling Mosaic refuses (fast memory): say so and go on
        row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
    print(json.dumps(row), file=out, flush=True)
