"""Kernels of the main path compiled, at their real widths, for the chip the
benchmark runs on: a described ``v5e``, not an attached one, so this costs no
chip time and needs none. Mosaic refuses here what it would refuse there (block
shapes against the tiling, fast memory a kernel may use); nothing runs, so
nothing is said about results or times.

All such tests live in this one file: the worker that gets it loads the TPU's
library once, inside the fixture, after collection.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_expert_layer_kernels_compile_for_the_v5e_at_published_widths(one_chip, monkeypatch):
    """``ops/moe.py`` forward and backward at LFM2-24B-A2B's widths (d 2048, expert
    width 1536, 8 of 64 experts held, top-4): the three Pallas kernels are in the
    program, with the whole of one expert's weights resident in VMEM."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import moe
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    tokens, d, f, router, held, k = 4096, 2048, 1536, 64, 8, 4
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(u, router_kernel, bias, w1, w3, w2):
        weights, experts = moe.route(u, router_kernel, bias, top_k=k)
        out, counts = moe.held_experts_ffn(u, weights, experts, w1, w3, w2,
                                           held=(0, held))
        return jnp.sum(out.astype(jnp.float32)), counts

    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(jax.value_and_grad(layer, argnums=(0, 1, 3, 4, 5),
                                              has_aux=True)).lower(
            spec((tokens, d), jnp.bfloat16), spec((d, router), jnp.float32),
            spec((router,), jnp.float32), spec((d, held * f), jnp.float32),
            spec((d, held * f), jnp.float32), spec((f, held * d), jnp.float32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    text = compiled.as_text()
    for name in ("moe_ffn_fwd", "moe_ffn_bwd", "moe_ffn_dw"):
        assert f"%{name}" in text and "tpu_custom_call" in text, name
    plan = moe.expert_plan(tokens, top_k=k, held=(0, held))
    assert plan["rows_buffer"] == tokens * k + held * moe.ROW_TILE
