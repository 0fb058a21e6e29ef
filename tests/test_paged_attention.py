"""ops/paged_attention.py: kernel vs gather reference vs dense contiguous.

The reference must match the contiguous decode attention bitwise on a
contiguously-mapped table (same einsum structure); the Pallas kernel
(interpret mode on CPU) must match the reference allclose-tight — its
online softmax reorders the reduction, so bitwise is not the contract.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from csed_514_project_distributed_training_using_pytorch_tpu.ops import (
    quant as quant_ops,
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops.attention import (
    MASK_VALUE,
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops.paged_attention import (
    paged_attend,
    paged_attend_reference,
)


def _tol():
    """Interpret mode (CPU) is exact to f32 round-off; compiled on the chip both
    the kernel and the reference run their f32 matmuls as bf16 MXU passes and
    differ at ~4e-3 (PR 21 chip run) — the TPU tolerance of
    tests/test_pallas_attention.py."""
    if jax.default_backend() == "tpu":
        return dict(rtol=2e-2, atol=2e-2)
    return dict(rtol=1e-5, atol=1e-5)


def _setup(seed, *, b=3, g=2, rep=2, d=8, ps=4, s=16, quantized=False,
           shuffle=True):
    """Random pool + per-slot table covering the full context, with free
    pages poisoned so any out-of-reservation read shows up."""
    rng = np.random.default_rng(seed)
    p_max = s // ps
    num_pages = 1 + b * p_max + 2          # null + slots + poisoned spares
    kd = np.float32
    k_pool = rng.normal(size=(num_pages, ps, g, d)).astype(kd)
    v_pool = rng.normal(size=(num_pages, ps, g, d)).astype(kd)
    scales = {}
    if quantized:
        kq, ks = quant_ops.quantize_rows(jnp.asarray(k_pool), jnp.int8)
        vq, vs = quant_ops.quantize_rows(jnp.asarray(v_pool), jnp.int8)
        k_pool, v_pool = np.asarray(kq), np.asarray(vq)
        scales = dict(k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
    ids = np.arange(1, 1 + b * p_max)
    if shuffle:
        rng.shuffle(ids)                   # non-contiguous page assignment
    table = ids.reshape(b, p_max).astype(np.int32)
    q = rng.normal(size=(b, g, rep, d)).astype(np.float32)
    t = rng.integers(0, s, size=b).astype(np.int32)
    return (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(table), jnp.asarray(t), scales)


def _dense_oracle(q, k_pool, v_pool, table, t, *, s, window=0, scales=None):
    """decode_step_slots' attention block on the explicitly gathered view."""
    b, g, rep, d = q.shape
    ps = k_pool.shape[1]
    view = lambda pool: pool[table].reshape(
        (b, table.shape[1] * ps) + pool.shape[2:])[:, :s]
    k_read, v_read = view(k_pool), view(v_pool)
    if scales:
        k_read = quant_ops.dequantize_rows(k_read, view(scales["k_scale"]))
        v_read = quant_ops.dequantize_rows(v_read, view(scales["v_scale"]))
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    pos = jnp.arange(s)[None]
    visible = pos <= t[:, None]
    if window:
        visible &= t[:, None] - pos < window
    scores = jnp.einsum("bgrd,bsgd->bgrs", q * scale, k_read)
    scores = jnp.where(visible[:, None, None, :], scores, MASK_VALUE)
    return jnp.einsum("bgrs,bsgd->bgrd", jax.nn.softmax(scores, -1), v_read)


@pytest.mark.parametrize("window", [0, 5], ids=["full", "window"])
@pytest.mark.parametrize("quantized", [False, True], ids=["fp32", "int8"])
def test_reference_matches_dense_bitwise(window, quantized):
    q, k_pool, v_pool, table, t, scales = _setup(0, quantized=quantized)
    ref = paged_attend_reference(q, k_pool, v_pool, table, t, seq_len=16,
                                 window=window, **scales)
    dense = _dense_oracle(q, k_pool, v_pool, table, t, s=16, window=window,
                          scales=scales or None)
    assert np.array_equal(np.asarray(ref), np.asarray(dense))


@pytest.mark.parametrize("window", [0, 5], ids=["full", "window"])
@pytest.mark.parametrize("quantized", [False, True], ids=["fp32", "int8"])
@pytest.mark.parametrize("rep", [1, 2], ids=["mha", "gqa"])
def test_kernel_matches_reference(window, quantized, rep):
    q, k_pool, v_pool, table, t, scales = _setup(1, rep=rep,
                                                 quantized=quantized)
    ref = paged_attend_reference(q, k_pool, v_pool, table, t, seq_len=16,
                                 window=window, **scales)
    out = paged_attend(q, k_pool, v_pool, table, t, window=window, **scales)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **_tol())


def test_kernel_ignores_unmapped_pages():
    """Poison every page a slot does NOT own (including the spares) with huge
    values: output must be unchanged — the mask plus the reservation
    invariant keep unowned pages invisible."""
    q, k_pool, v_pool, table, t, _ = _setup(2, shuffle=True)
    out = paged_attend(q, k_pool, v_pool, table, t)
    owned = set(np.asarray(table).ravel().tolist())
    poison_ids = [p for p in range(k_pool.shape[0]) if p not in owned]
    k_np, v_np = np.asarray(k_pool).copy(), np.asarray(v_pool).copy()
    k_np[poison_ids] = 1e9
    v_np[poison_ids] = 1e9
    out2 = paged_attend(q, jnp.asarray(k_np), jnp.asarray(v_np), table, t)
    assert np.array_equal(np.asarray(out), np.asarray(out2))


def test_kernel_t_zero_and_t_max():
    """Edge positions: a slot at t=0 attends over exactly one row; a slot at
    t=S-1 over all of them."""
    q, k_pool, v_pool, table, _, _ = _setup(3, b=2)
    t = jnp.asarray([0, 15], jnp.int32)
    ref = paged_attend_reference(q, k_pool, v_pool, table, t, seq_len=16)
    out = paged_attend(q, k_pool, v_pool, table, t)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **_tol())
