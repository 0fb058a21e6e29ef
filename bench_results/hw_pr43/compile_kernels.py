"""The scalar-decay scan (``gdn_fwd``, ``gdn_bwd``) and the flash kernels at 256 / 256,
compiled at the cell's shapes for a described v5e: no chip, no time, only what Mosaic
refuses and the bytes. ``JAX_PLATFORMS=cpu python bench_results/hw_pr43/compile_kernels.py``."""
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
from jax.experimental import topologies                         # noqa: E402
from jax.sharding import SingleDeviceSharding                   # noqa: E402
from csed_514_project_distributed_training_using_pytorch_tpu.ops import (  # noqa: E402
    kda, pallas_attention,
)

jax.config.update("jax_enable_compilation_cache", False)
chip = SingleDeviceSharding(
    topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
kda._interpret = pallas_attention._interpret = lambda: False
B, S = 2, 8192
spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def report(name, lowered):
    t0 = time.time()
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    print(name, f"{time.time() - t0:.1f} s", "arguments", m.argument_size_in_bytes,
          "temporaries", m.temp_size_in_bytes, "outputs", m.output_size_in_bytes,
          "kernels", sorted({w.split("(")[0] for w in text.split() if w.startswith("%gdn_")
                             or w.startswith("%flash_")}))


tiles = [tuple(map(int, t.split(","))) for t in
         os.environ.get("TILES", "64,4,4").split(";")]
for chunk, sub, group in tiles:
    loss = lambda q, k, v, g, b: jnp.sum(kda.gdn_scan(
        q, k, v, g, b, key_heads=16, eps=1e-6, chunk=chunk, sub=sub,
        group=group).astype(jnp.float32))
    report(f"gdn scan {chunk},{sub},{group}", jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        spec((B, S, 2048)), spec((B, S, 2048)), spec((B, S, 4096)),
        spec((B, S, 32), jnp.float32), spec((B, S, 32), jnp.float32)))
attend = lambda q, k, v: jnp.sum(pallas_attention.flash_attention(
    q, k, v, causal=True).astype(jnp.float32))
x = spec((B, S, 16, 256))
print(pallas_attention.dispatch_plan((B, S, 16, 256), causal=True))
report("flash 256/256", jax.jit(jax.grad(attend, argnums=(0, 1, 2))).lower(x, x, x))
