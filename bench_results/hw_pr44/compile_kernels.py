"""Both branches of the delta-rule scan (``gdn_fwd`` / ``gdn_bwd``, ``kda_fwd`` / ``kda_bwd``)
compiled at the cells' shapes for a described v5e, a tiling at a time: no chip, no time, only what
Mosaic refuses, how long it takes to say so, and the kept states' bytes.
``JAX_PLATFORMS=cpu python bench_results/hw_pr44/compile_kernels.py [out.jsonl]``; ``TILES=`` and
``BRANCHES=`` (``gdn,kda``) choose; no ``TILES`` entry: each branch's committed default."""
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
from jax.experimental import topologies                         # noqa: E402
from jax.sharding import SingleDeviceSharding                   # noqa: E402
from csed_514_project_distributed_training_using_pytorch_tpu.ops import kda  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
chip = SingleDeviceSharding(
    topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
kda._interpret = lambda: False
B, S, KH, H, D = 2, 8192, 16, 32, 128
spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
tiles = [dict(zip(("chunk", "sub", "group", "vmem_limit_mib"), map(int, t.split(","))))
         for t in os.environ.get("TILES", "").split(";") if t] or [{}]
PARAMS = kda._params


def raised(mib):
    """A fourth number of a tiling: Mosaic's scoped limit of fast memory in MiB (16 unsaid)."""
    kda._params = PARAMS if mib is None else lambda: kda.pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=mib << 20)
    kda._make_op.cache_clear()


out = open(sys.argv[1], "a") if len(sys.argv) > 1 else sys.stdout
for branch in os.environ.get("BRANCHES", "gdn,kda").split(","):
    for tile in tiles:
        tile = dict(tile)
        raised(tile.pop("vmem_limit_mib", None))
        if branch == "gdn":
            scan = lambda *a: kda.gdn_scan(*a, key_heads=KH, eps=1e-6, **tile)
            args = (spec((B, S, KH * D)), spec((B, S, KH * D)), spec((B, S, H * D)),
                    spec((B, S, H), jnp.float32), spec((B, S, H), jnp.float32))
        else:
            scan = lambda *a: kda.kda_scan(*a, eps=1e-5, **tile)
            args = (spec((B, S, H * D)),) * 3 + (spec((B, S, H * D), jnp.float32),
                                                 spec((B, S, H), jnp.float32))
        loss = lambda *a: jnp.sum(scan(*a).astype(jnp.float32))
        limit = kda._params().vmem_limit_bytes     # None: Mosaic's own 16 MiB
        row, t0 = {"branch": branch, **tile, "vmem_limit_mib": limit and limit >> 20}, time.time()
        try:
            compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
            m = compiled.memory_analysis()
            row.update(compile_s=round(time.time() - t0, 1), temporaries=m.temp_size_in_bytes,
                       kept_states=[w for w in set(compiled.as_text().replace("{", " ").split())
                                    if w.startswith(f"f32[{B},") and w.endswith(f",{H},{D},{D}]")])
        except Exception as e:      # what Mosaic refuses, in its own words
            row.update(compile_s=round(time.time() - t0, 1),
                       refused=f"{type(e).__name__}: {str(e)[-400:]}")
        print(json.dumps(row), file=out, flush=True)
