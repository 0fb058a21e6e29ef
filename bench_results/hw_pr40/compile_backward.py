"""Compile flash_backward_blocks for a described v5e at the cells' shapes, the budget's edge and
the banded / traced-offset walks, fused and split. A compile, not a chip run.
usage: JAX_PLATFORMS=cpu python bench_results/hw_pr40/compile_backward.py"""
import json, os, sys, time
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.realpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from csed_514_project_distributed_training_using_pytorch_tpu.ops import pallas_attention as pa

jax.config.update("jax_enable_compilation_cache", False)
pa._interpret = lambda: False
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])

# name, BH, S, D, Dv, block, causal, window, q_offset, traced offset
CASES = [
    ("kanana2/kimi", 64, 8192, 192, 128, 1024, True, 0, 0, False),
    ("lfm2", 128, 8192, 64, 64, 1024, True, 0, 0, False),
    ("lm_b16", 128, 896, 128, 128, 896, True, 0, 0, False),
    ("eva_windows", 256, 2048, 128, 128, 1024, True, 0, 0, False),
    ("budget_edge", 2, 32768, 128, 128, 1024, True, 0, 0, False),
    ("non_causal", 8, 4096, 128, 128, 1024, False, 0, 0, False),
    ("banded_causal", 8, 8192, 128, 128, 512, True, 256, 0, False),
    ("banded_hop", 8, 8192, 128, 128, 512, False, 256, 1024, False),
    ("traced_banded", 8, 8192, 128, 128, 512, False, 256, 0, True),
    ("traced_full", 8, 2048, 128, 128, 512, False, 0, 0, True),
]
only = sys.argv[1:]
for name, bh, s, d, dv, block, causal, window, q_offset, traced in CASES:
    if only and name not in only:
        continue
    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    stat = sds((bh, s // block, 1, block), jnp.float32)
    args = [sds((bh, s, d)), sds((bh, s, d)), sds((bh, s, dv)), sds((bh, s, dv)), stat, stat]
    if traced:
        fn = lambda q, k, v, g, lse, delta, off: pa.flash_backward_blocks(
            q, k, v, g, lse, delta, causal=causal, block=block, window=window, q_offset_dyn=off)
        args.append(sds((), jnp.int32))
    else:
        fn = lambda *a: pa.flash_backward_blocks(*a, causal=causal, block=block, window=window,
                                                 q_offset=q_offset)
    for budget in (pa.FUSED_DQ_MAX_BYTES, 0):
        saved, pa.FUSED_DQ_MAX_BYTES = pa.FUSED_DQ_MAX_BYTES, budget
        row = {"case": name, "backward": "fused" if pa.backward_fused(s, d) else "split"}
        t0 = time.time()
        try:
            compiled = jax.jit(lambda *a: fn(*a)).lower(*args).compile()   # a new trace a budget
            m = compiled.memory_analysis()
            row.update(ok=True, temp=m.temp_size_in_bytes, compile_s=round(time.time() - t0, 1))
        except Exception as e:   # the compiler's refusal is the finding
            row.update(ok=False, error=str(e)[-600:])
        pa.FUSED_DQ_MAX_BYTES = saved
        print(json.dumps(row), flush=True)
