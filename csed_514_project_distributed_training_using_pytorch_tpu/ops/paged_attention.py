"""Paged-attention decode: attend through a page table, dequant fused in.

The TPU half of the paged KV cache (DESIGN.md §27). The gather adapters in
``models/lm.py`` materialize each slot's logical ``[S]`` view from the page
pool and run the contiguous attention on it — bitwise-exact, but the gather
writes the whole view back through HBM before attention reads it again. This
module's kernel fuses the two passes: a Pallas grid walks each slot's pages
with the PAGE TABLE as a scalar-prefetch operand (the index map reads
``table[b, j]`` to address the pool block directly, the
``PrefetchScalarGridSpec`` pattern from ``ops/pallas_attention.py``'s traced
ring offsets), streaming each page HBM→VMEM exactly once into an
online-softmax accumulator — and for int8/fp8 pools the per-head dequant
scale multiplies inside the kernel, so HBM streams the NARROW codes.

Two implementations, one contract:

- ``paged_attend_reference`` — pure-XLA gather-attend, the exact einsum/mask
  structure of ``decode_step_slots``'s attention block. The CPU/tier-1 path
  and the numerics oracle.
- ``paged_attend`` — the Pallas kernel (compiled on TPU, interpret mode
  elsewhere, same ``_interpret`` gate as the flash kernels; first compiled by
  Mosaic in PR 21 — v5e, jax 0.9.0 — at the engine's G=2, R=4, D=128, page 64
  geometry, fp32 and int8+scales, which ``chip_smoke.py`` re-checks). Online
  softmax changes the reduction ORDER, so the kernel is pinned allclose-tight
  (not bitwise) against the reference in ``tests/test_paged_attention.py``;
  the engine's default paged path stays on the gather adapters, which ARE
  bitwise, and opts into the kernel per-platform.

Layouts (decode-time, one query token per slot): ``q [B, G, R, D]`` (query
heads grouped by their shared KV head — GQA-ready; ``R == 1`` plain MHA is a
degenerate grouping), pools ``[num_pages, page_size, G, D]`` with optional
f32 scale pools ``[num_pages, page_size, G]`` (``ops.quant`` quantize-on-
write), ``table [B, P_max]`` int32, positions ``t [B]`` int32. Every
position ``<= t[b]`` must be mapped (the engine's reservation invariant);
unmapped entries point at the allocator's null page, whose junk the
``pos <= t`` (and sliding-window) mask hides exactly as in the dense path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from csed_514_project_distributed_training_using_pytorch_tpu.ops.attention import (
    MASK_VALUE as NEG,
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops.pallas_attention import (
    _interpret,
)


def paged_attend_reference(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           table: jax.Array, t: jax.Array, *,
                           seq_len: int, window: int = 0,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None) -> jax.Array:
    """Gather-attend oracle: ``[B, G, R, D]`` out, ``decode_step_slots``'s
    exact attention math on the table's gathered view."""
    b, g, r, d = q.shape
    ps = k_pool.shape[1]
    p_max = table.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))

    def view(pool):
        return pool[table].reshape((b, p_max * ps) + pool.shape[2:])[:, :seq_len]

    k_read, v_read = view(k_pool), view(v_pool)
    if k_scale is not None:
        k_read = k_read.astype(jnp.float32) * view(k_scale)[..., None]
        v_read = v_read.astype(jnp.float32) * view(v_scale)[..., None]
    pos = jnp.arange(seq_len)[None]                              # [1, S]
    tb = t[:, None]
    visible = pos <= tb
    if window:
        visible &= tb - pos < window
    visible = visible[:, None, None, :]                          # [B, 1, 1, S]
    scores = jnp.einsum("bgrd,bsgd->bgrs", q * scale, k_read)
    scores = jnp.where(visible, scores, NEG)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bgrs,bsgd->bgrd", weights, v_read)


def _paged_kernel(*refs, groups, rep, head_dim, page_size, p_max, window,
                  quantized):
    # Scalar-prefetch operands come first: the page table [B, P_max] and
    # the positions t [B]. Then q [1, H, D] (H = G·R), the pool page blocks
    # [ps, G·D] (k, v[, k_scale, v_scale [ps, G]]), the out ref [1, H, D],
    # and the online-softmax scratch (acc [H, D], m [H, 1], l [H, 1] — f32
    # VMEM persisting across the page walk, exactly the flash forward's
    # accumulator discipline).
    table_ref, t_ref = refs[0], refs[1]
    if quantized:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs[2:]
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs[2:]
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    j = pl.program_id(1)
    scale = 1.0 / (head_dim ** 0.5)
    t_b = t_ref[b]

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    # A page whose first position is already past t holds no visible row —
    # skip its FLOPs (its fetch was aliased onto a live page by the index
    # map's clamp, so it costs no copy either).
    @pl.when(j * page_size <= t_b)
    def _():
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)                        # [1, ps]
        vis = pos <= t_b
        if window:
            vis &= t_b - pos < window
        for g in range(groups):
            kg = k_ref[:, g * head_dim:(g + 1) * head_dim]       # [ps, D]
            vg = v_ref[:, g * head_dim:(g + 1) * head_dim]
            if quantized:
                kg = kg.astype(jnp.float32) * ks_ref[:, g:g + 1]
                vg = vg.astype(jnp.float32) * vs_ref[:, g:g + 1]
            else:
                kg = kg.astype(jnp.float32)
                vg = vg.astype(jnp.float32)
            qg = q_ref[0, g * rep:(g + 1) * rep, :].astype(jnp.float32)  # [R, D]
            s = jax.lax.dot_general(
                qg, kg, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale      # [R, ps]
            s = jnp.where(vis, s, NEG)
            rows = slice(g * rep, (g + 1) * rep)
            m = m_ref[rows]
            l = l_ref[rows]
            m_blk = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m, m_blk)
            p = jnp.exp(s - m_new)
            p = jnp.where(vis, p, 0.0)
            corr = jnp.exp(m - m_new)
            acc_ref[rows] = acc_ref[rows] * corr + jnp.dot(
                p, vg, preferred_element_type=jnp.float32)
            l_ref[rows] = l * corr + jnp.sum(p, axis=1, keepdims=True)
            m_ref[rows] = m_new

    @pl.when(j == p_max - 1)
    def _():
        l_safe = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def paged_attend(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                 table: jax.Array, t: jax.Array, *, window: int = 0,
                 k_scale: jax.Array | None = None,
                 v_scale: jax.Array | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """Fused page-walk attention: ``[B, G, R, D]`` out without ever
    materializing the gathered ``[B, S]`` view. Grid ``(B, P_max)`` — the
    inner axis walks slot ``b``'s pages, the table (scalar prefetch) steers
    each step's pool block, dead pages (wholly past ``t[b]``) alias onto the
    last live one so they cost neither copy nor FLOPs."""
    b, g, rep, d = q.shape
    num_pages, ps = k_pool.shape[:2]
    p_max = table.shape[1]
    h = g * rep
    quantized = k_scale is not None
    if interpret is None:
        interpret = _interpret()

    q3 = q.reshape(b, h, d)
    kf = k_pool.reshape(num_pages, ps, g * d)
    vf = v_pool.reshape(num_pages, ps, g * d)
    # Dead steps clamp onto the newest live page (same fetch-elision trick as
    # the flash kernels' _elided_key_idx): consecutive steps requesting the
    # same block skip the copy.
    def page_idx(bb, jj, tbl, tt):
        live = jnp.maximum(tt[bb] // ps, 0)
        return tbl[bb, jnp.minimum(jj, live)]

    in_specs = [
        pl.BlockSpec((1, h, d), lambda bb, jj, tbl, tt: (bb, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((None, ps, g * d),
                     lambda bb, jj, tbl, tt: (page_idx(bb, jj, tbl, tt), 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((None, ps, g * d),
                     lambda bb, jj, tbl, tt: (page_idx(bb, jj, tbl, tt), 0, 0),
                     memory_space=pltpu.VMEM),
    ]
    args = [q3, kf, vf]
    if quantized:
        for sc in (k_scale, v_scale):
            in_specs.append(pl.BlockSpec(
                (None, ps, g),
                lambda bb, jj, tbl, tt: (page_idx(bb, jj, tbl, tt), 0, 0),
                memory_space=pltpu.VMEM))
            args.append(sc)
    kernel = functools.partial(
        _paged_kernel, groups=g, rep=rep, head_dim=d, page_size=ps,
        p_max=p_max, window=window, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, p_max),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, h, d),
                                   lambda bb, jj, tbl, tt: (bb, 0, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((h, d), jnp.float32),    # acc
                pltpu.VMEM((h, 1), jnp.float32),    # running max m
                pltpu.VMEM((h, 1), jnp.float32),    # running normalizer l
            ]),
        out_shape=jax.ShapeDtypeStruct((b, h, d), jnp.float32),
        interpret=interpret,
    )(table, t.astype(jnp.int32), *args)
    return out.reshape(b, g, rep, d)
