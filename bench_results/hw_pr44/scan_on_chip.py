"""Both branches of the delta-rule scan alone on the chip, at the cells' shapes (2 x 8192
tokens; gdn: 32 value heads on 16 key heads of 128, a scalar decay; kda: 32 heads of 128 / 128,
a decay a channel), by tiling (chunk, sub-block, group): forward and forward + backward, ms a
call and µs a chunk of 64 and value head, and each tiling's worst gap to the branch's first
(the parent's 64, 4, 4) in the value and in each of the five gradients. A tiling Mosaic
refuses is written down as refused. bench_results/hw_pr43/scan_on_chip.py for two branches.
``python3 bench_results/hw_pr44/scan_on_chip.py [out.jsonl]`` through chiprun; ``TILES=`` and
``BRANCHES=`` choose; off the chip a tiny size of both, a rehearsal and no measurement."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
from csed_514_project_distributed_training_using_pytorch_tpu.ops import kda  # noqa: E402

ON_CHIP = jax.default_backend() == "tpu"
B, S, KH, H, D = (2, 8192, 16, 32, 128) if ON_CHIP else (1, 64, 1, 2, 16)
REPS = 10 if ON_CHIP else 1
TILES = [tuple(map(int, t.split(","))) for t in os.environ.get(
    "TILES", "64,4,4;64,4,8;64,4,16;128,8,2;128,8,4;128,4,4;128,16,4;256,8,1;128,8,8;64,4,4"
    if ON_CHIP else "8,4,2;16,4,2;8,4,4").split(";")]
NAMES = ("out", "dq", "dk", "dv", "dg", "dbeta")


def operands(branch):
    """gdn: PR 43's (normal q̃, k̃, v; one log-decay a token and head). kda: PR 38's (silu of
    normal, as the convolutions' silu writes them; a log-decay a channel)."""
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    normal = lambda key, width: jax.random.normal(key, (B, S, width), jnp.bfloat16)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (B, S, H)))
    w = normal(keys[3], H * D)
    if branch == "gdn":
        q, k, v = normal(keys[0], KH * D), normal(keys[1], KH * D), normal(keys[2], H * D)
        g = -jax.nn.softplus(jax.random.normal(keys[4], (B, S, H)))
    else:
        q, k, v = (jax.nn.silu(normal(key, H * D).astype(jnp.float32)).astype(jnp.bfloat16)
                   for key in keys[:3])
        g = -jax.nn.softplus(jax.random.normal(keys[4], (B, S, H * D)))
    return (q, k, v, g, beta), w


PARAMS = kda._params


def raised(mib):
    """A fourth number of a tiling is Mosaic's scoped limit of fast memory in MiB (16 when the
    kernels ask for none, which is what ``ops/kda.py`` does; scan_tilings.jsonl's rows read null
    there): only to price what 16 MiB refuse."""
    kda._params = PARAMS if mib is None else lambda: kda.pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=mib << 20)
    kda._make_op.cache_clear()


if len(sys.argv) > 1:
    os.makedirs(os.path.dirname(os.path.abspath(sys.argv[1])), exist_ok=True)
out = open(sys.argv[1], "a") if len(sys.argv) > 1 else sys.stdout
for branch in os.environ.get("BRANCHES", "gdn,kda").split(","):
    args, w = operands(branch)
    first = None
    for chunk, sub, group, *limit in TILES:
        tile = dict(chunk=chunk, sub=sub, group=group)
        raised(limit[0] if limit else None)
        scan = ((lambda *a: kda.gdn_scan(*a, key_heads=KH, eps=1e-6, **tile)) if branch == "gdn"
                else (lambda *a: kda.kda_scan(*a, eps=1e-5, **tile)))
        fwd = jax.jit(scan)
        both = jax.jit(jax.grad(lambda *a: jnp.sum((scan(*a) * w).astype(jnp.float32)),
                                argnums=(0, 1, 2, 3, 4)))
        row = {"branch": branch, **tile,
               "vmem_limit_mib": limit[0] if limit else getattr(kda, "VMEM_LIMIT", 16 << 20) >> 20,
               "device": jax.devices()[0].device_kind}
        try:
            results = {}
            for name, fn in (("forward", fwd), ("forward_backward", both)):
                t0 = time.perf_counter()
                results[name] = jax.block_until_ready(fn(*args))
                row[name + "_compile_s"] = round(time.perf_counter() - t0, 2)
                t0 = time.perf_counter()
                for _ in range(REPS):
                    r = fn(*args)
                jax.block_until_ready(r)
                row[name + "_ms"] = (time.perf_counter() - t0) / REPS * 1e3
            row["backward_ms"] = row["forward_backward_ms"] - row["forward_ms"]
            row["us_per_chunk64_head"] = row["forward_backward_ms"] * 1e3 / (B * H * S // 64)
            flat = [x.astype(jnp.float32)
                    for x in (results["forward"], *results["forward_backward"])]
            if first is None:
                first = flat
            row["gap_to_first"] = {
                name: float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
                for name, a, b in zip(NAMES, flat, first)}
            row["worst_gap_to_first"] = max(row["gap_to_first"].values())
            row["finite"] = bool(all(jnp.isfinite(x).all() for x in flat))
        except Exception as e:      # a tiling Mosaic refuses (fast memory): say so and go on
            row["refused"] = f"{type(e).__name__}: {str(e)[-400:]}"
        print(json.dumps(row), file=out, flush=True)
