"""Flash attention as first-party Pallas TPU kernels (forward + full backward).

The single-chip long-context hot path: dense attention materializes the ``[S, S]`` score
matrix in HBM (O(S²) memory and bandwidth); these kernels stream K/V blocks through VMEM
with the online-softmax recurrence, so HBM traffic is O(S·D) and the score matrix never
exists. This is the intra-chip complement of the cross-chip ring attention in
``parallel/ring_attention.py`` (same math, different memory wall).

Kernel layout (FlashAttention-2 style, in the canonical Pallas-TPU grid formulation):

- **Forward**: grid ``(B·H, S/BLOCK, S/BLOCK)`` over operands packed ``[BH, S, D]``, the
  one layout (two forms that fed the model's ``[B, S, H, D]`` viewed flat lost to it on
  the chip at every recorded shape and are gone: DESIGN.md §9) — the innermost
  (fastest-varying) axis walks K/V blocks while the query block and the online-softmax
  accumulators ``(acc, m, l)`` persist in **VMEM scratch** across those steps
  (``@pl.when`` on the first/last K/V step initializes/finalizes them). Streaming and
  double-buffering come from Pallas's automatic grid pipelining — each operand's
  ``index_map`` names the block the step needs and the next block's copy overlaps the
  current block's math. VMEM residency is a handful of ``[128, D]`` blocks regardless
  of S, so sequence length is HBM-bound: an earlier full-K/V-in-VMEM variant hit the
  16 MB scoped-vmem wall at S=16k, and a hand-rolled in-kernel DMA variant
  (``run_scoped`` + ``make_async_copy`` double buffering) wedged this environment's AOT
  Mosaic compile helper the same way the (since-retired) whole-model fused CNN kernel
  did — the grid formulation compiles in seconds.
- **Backward**: the recompute formulation — no O(S²) residuals, only
  ``(out, lse = m + log l)`` — as ONE kernel (``flash_dkv`` on a trace): the grid walks
  query/dout blocks per key block, recomputes ``p = exp(q·kᵀ·scale − lse)`` and
  ``ds = p ∘ (dout·vᵀ − Δ)`` once a block pair, and feeds all three gradients from them
  (five products a pair). dk and dv accumulate in a key block's float32 scratch; dq of
  the program's whole (batch, head) stays resident in VMEM, ``[S, D]`` float32 (6.3 MB
  at S 8192 × D 192) beside its ``[S, D]`` output block in the operands' dtype, each
  live pair adding ``ds · k`` at its query block's rows; it is zeroed at the program's
  first step and leaves scaled and narrowed at its last, so no float32 dq passes
  through HBM. ``Δ = rowsum(dout ∘ out)`` is computed once outside (XLA fuses it).
  Past ``FUSED_DQ_MAX_BYTES`` of resident dq (S·D·4 > 16 MiB: S 32768 at D 192, S 65536
  at D 128) the two split kernels run instead, whatever the walk: ``flash_dq`` re-walks
  K/V blocks per query block and ``flash_dkv`` makes dk and dv alone, each computing
  the scores again (seven products a pair); ``backward_fused`` is the one predicate.
- **Causal/banded dead blocks** cost no FLOPs (``@pl.when`` skip) and — r5 — no fetch
  either: the full walks clamp their index maps onto the nearest live block
  (``_elided_key_idx``), and Pallas skips the copy when consecutive steps request the
  same block; fully-visible interior blocks also skip the mask's iota/select chain
  (``_block_interior``). Static offsets get band-compressed grids; TRACED (zig-zag)
  offsets steer the band through scalar-prefetch index maps (``_dyn_band_reach``).

All matmuls request ``preferred_element_type=float32`` (MXU accumulation), block shapes
are lane-aligned (any multiple of 128 rows via the ``block`` parameter, default
``BLOCK = 128``; head dim on the lane axis), masks use 2-D ``broadcasted_iota``, and the
only in-kernel reshapes drop/add leading unit dims — every construct from the
v5e-probe-verified Mosaic lowering list (DESIGN.md §9). ``block`` is a pure
performance knob (numerics are block-invariant — pinned in tests): larger blocks
amortize grid/pipeline overhead per step against more VMEM per block; tune with
``bench_attention.py --block-sweep``.

Like the other Pallas modules: compiled on TPU, interpret mode elsewhere (the CPU test
platform), numerics pinned against ``ops.attention.full_attention`` in
``tests/test_pallas_attention.py`` (hardware-gated Mosaic re-check included). Sequences
must divide by the chosen ``block``; ``flash_attention`` zero-pads a CAUSAL call of any
other length at the tail (exact under the mask), a non-causal one takes the dense path
(``dispatch_attention``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from csed_514_project_distributed_training_using_pytorch_tpu.ops.attention import (
    MASK_VALUE as NEG,
    full_attention,
    validate_window,
)

BLOCK = 128            # base block rows (lane-aligned, MXU-shaped): the layout unit the
                       # ring merges are written against; every kernel accepts ``block``
                       # (a multiple of 128) for tuning — larger blocks amortize
                       # grid/pipeline overhead per step at the cost of more VMEM per
                       # block (see bench_attention.py --block)

MAX_AUTO_BLOCK = 1024  # r4 v5e sweep (bench_results/hw_r4/bench_attention_blocktune
                       # .jsonl): per-op time falls monotonically 128→1024 at every
                       # S >= 1024 (3.3× at S=2048), and 2048 hits the Mosaic
                       # VMEM/compile wall — 1024 is the measured sweet spot. Below
                       # it the same holds down to one whole-sequence block (PR 25,
                       # bench_results/hw_pr25/bench_attention_dispatch_tpu.jsonl,
                       # B16·H8·D128 bf16 causal fwd+bwd): at 784→pad, 896 at 896
                       # 1.86 ms, 1024 at 1024 2.29, 512 at 1024 2.72, 256 at 1024
                       # 4.97, 128 at 896 9.00; 640 1.06 against its divisor 128's
                       # 3.99, 768 1.43 against 256's 2.76, 384 0.59 against 1.54;
                       # under a window of 256 too (896 1.90; 512 2.89, 128 5.38)

MAX_AUTO_BLOCK_WINDOWED = 512  # banded grids do O(S·(W+block)) work, so oversize
                               # blocks defeat NARROW bands: b512 beats b1024
                               # 1.6× at S=8192 W=256 on v5e (r4 capture). WIDE
                               # bands amortize like the full walk — b1024 beats
                               # b512 12-13% at W=4096, S=8192/32768 under the
                               # r5 elision kernels (hw_r5/bench_attention_
                               # windowtune.jsonl) — so the cap is W-dependent
                               # (WIDE_WINDOW below)

WIDE_WINDOW = 4096             # smallest window the full MAX_AUTO_BLOCK cap is
                               # MEASURED to win at; narrower windows keep the
                               # windowed cap (the crossover lies somewhere in
                               # (256, 4096) — untested widths take the
                               # conservative side)

# The dense/flash crossover on TPU v5e (bf16 causal fwd+bwd; hw_r4/bench_attention_tpu
# .jsonl at B = 1 and PR 25's bench_results/hw_pr25/bench_attention_dispatch_tpu.jsonl;
# the table is in PERF.md §6). What dense costs follows the BYTES of its float32 score
# tensor whatever S and D, in two steps as they stop fitting on-chip (128 MiB of VMEM):
# 2.6-3.4 ns/KB at 33.5-39 MB, 5.4-6.5 at 59-100 MB (0.36-0.38 ms at 67 MB with S = 256,
# 512 and 1024 alike), 14-19 at 134-315 MB. What flash costs is 3-4 µs a (batch, head)
# program and kernel plus its S² work (4.0 µs at S = 256 with D = 32, 64 and 128 alike,
# 5.7 at 512, 14.6 at 896).

FLASH_MIN_SCORE_BYTES = 64 << 20        # B·H·S_q·S_k·4 per device: the smallest size
                       # measured to win at every S from 512 up (1.05-1.36× at 67 MB,
                       # 1.3× at 79-100, 2.6-4.8× at 134, 2.8-3.2× at 164-315, the
                       # benchmark's cell); it loses at 33.5 and 39 MB (0.67×) and wins
                       # at 59 (1.29×, S = 784): the crossover lies between

FLASH_MIN_HEAD_SCORE_BYTES = 1 << 20    # S_q·S_k·4 of ONE (batch, head), = S 512: a
                       # smaller tile never pays a program's fixed cost back (S = 256:
                       # 0.39-0.55× at 67 MB, 0.85-1.05× at 134-268 MB, the r3 trainer's
                       # shape; S = 384: 0.86× at 75 MB; S = 512: level at 67 MB,
                       # 2.6-2.7× at 134). S = 384 above 128 MiB is untested and takes
                       # the dense side


FUSED_DQ_MAX_BYTES = 16 << 20   # S·D·4 of one (batch, head): the float32 dq the fused
                       # backward keeps in VMEM across its whole walk. 16 MiB is S 32768
                       # at D 128; the cells hold 6.3 MB (S 8192, D 192), 2.1 (S 8192,
                       # D 64), 1 (EVA's windows of 2048 at 128) and 0.46 (S 896, D 128)

FUSED_VMEM_LIMIT = 100 << 20    # scoped VMEM of the fused backward, of the chip's 128 MiB
                       # (as ops/moe.py's): at the budget the resident dq, its output
                       # block twice and a block pair's float32 tiles are 56 MB


def auto_block(s: int, window: int = 0) -> int:
    """``s`` itself when one block holds it (``MAX_AUTO_BLOCK``), else the largest
    power-of-two block ≤ the measured per-regime cap that tiles ``s`` evenly — the
    measured-fastest choice per shape (see ``MAX_AUTO_BLOCK`` /
    ``MAX_AUTO_BLOCK_WINDOWED``)."""
    cap = (MAX_AUTO_BLOCK_WINDOWED if 0 < window < WIDE_WINDOW
           else MAX_AUTO_BLOCK)
    if s % BLOCK == 0 and s <= MAX_AUTO_BLOCK:
        # A sequence that fits one block takes one, banded or not: that short, a
        # grid step's fixed cost outweighs what a band or a causal skip saves.
        return s
    for b in (1024, 512, 256, 128):
        if b <= min(s, cap) and s % b == 0:
            return b
    raise ValueError(
        f"flash attention requires sequence length divisible by 128, got {s} "
        f"(use ops.full_attention for odd lengths)")


def _interpret() -> bool:
    """Compiled on TPU; interpret mode on CPU/GPU (the test platforms)."""
    return jax.default_backend() != "tpu"


def _check_block(s: int, block: int) -> None:
    """Sequence/block compatibility: lane-aligned block, evenly tiled sequence."""
    if block < 128 or block % 128:
        raise ValueError(f"flash block must be a positive multiple of 128, got {block}")
    if s % block:
        raise ValueError(
            f"flash attention requires sequence length divisible by block={block}, "
            f"got {s} (use ops.full_attention for odd lengths)")


def _check_offset(q_offset: int, block: int) -> None:
    """Hop offsets must be block-quantized: the banded grids shift whole blocks
    (ring shard lengths are multiples of BLOCK, so this holds by construction)."""
    if q_offset % block:
        raise ValueError(
            f"q_offset must be a multiple of block={block}, got {q_offset}")


def _visibility_mask(iq, ik, bq, bk, *, causal: bool, window: int = 0,
                     q_offset: int = 0):
    """[bq, bk] visibility mask for query block iq vs key block ik (global positions):
    causal lower-triangle and/or the sliding-window band (distance < window).

    ``q_offset`` (static) shifts the QUERY positions by a global amount relative to
    the keys — the ring hop offset: a ring caller whose local K/V block originated
    ``delta`` shards away passes ``q_offset = delta · shard_len`` so the band/causal
    masks act on true global positions while both operands index locally."""
    q_pos = q_offset + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= (q_pos - k_pos < window) & (k_pos - q_pos < window)
    return mask


def _block_live(iq, j, bq, bk, *, causal: bool, window: int = 0,
                q_offset: int = 0):
    """Whether (query block iq, key block j) holds ANY visible pair — the grid-step
    skip predicate (skipped blocks cost no FLOPs; their fetch still pipelines).
    Same expression serves the dkv kernel with (i, ik) in the (iq, j) roles.
    ``q_offset`` shifts query positions globally (see ``_visibility_mask``)."""
    live = jnp.bool_(True)
    if causal:
        live &= j * bk <= q_offset + iq * bq + bq - 1     # not entirely future
    if window:
        # Not entirely older than the window: youngest key vs oldest query.
        live &= q_offset + iq * bq - (j * bk + bk - 1) < window
        if not causal:
            # Bidirectional band: not entirely newer either.
            live &= j * bk - (q_offset + iq * bq + bq - 1) < window
    return live


def _block_interior(iq, j, bq, bk, *, causal: bool, window: int = 0,
                    q_offset: int = 0):
    """Whether EVERY pair of (query block iq, key block j) is visible — such
    blocks skip the mask's iota/compare/select chain entirely (r5: the VPU work
    per element of that chain rivals the softmax exp, and at large S interior
    blocks dominate). Extreme-position arithmetic mirrors ``_visibility_mask``."""
    interior = jnp.bool_(True)
    if causal:
        interior &= q_offset + iq * bq >= j * bk + bk - 1   # oldest q ≥ youngest k
    if window:
        interior &= q_offset + iq * bq + bq - 1 - j * bk < window
        interior &= j * bk + bk - 1 - (q_offset + iq * bq) < window
    return interior


def _elided_key_idx(nq: int, off_blocks: int, reach, *, causal: bool):
    """Key-walk block index ``idx(i, j)`` for the FULL (non-banded) grid that
    aliases DEAD steps onto the nearest live block: Pallas skips the HBM→VMEM copy
    when consecutive grid steps request the same block, so the upper-triangle
    (causal) / out-of-band (windowed) fetches that previously still streamed now
    cost nothing (r5 — at S ≥ 8k causal the dead fetches made the kernels
    HBM-bound). Dead steps remain grid iterations; ``@pl.when`` already skips
    their FLOPs. The clamp is the identity for every LIVE step, so numerics are
    untouched."""

    def idx(i, j):
        lo = i + off_blocks - reach if reach is not None else 0
        hi = i + off_blocks if causal else (
            i + off_blocks + reach if reach is not None else nq - 1)
        return jnp.clip(jnp.clip(j, lo, hi), 0, nq - 1)

    return idx


def _elided_query_idx(nq: int, off_blocks: int, reach, *, causal: bool):
    """``_elided_key_idx``'s mirror for the dkv kernel, whose step axis walks QUERY
    blocks around key block ``i``: causal bounds queries from BELOW (only queries
    at/after the key see it), the window from above."""

    def idx(i, j):
        lo = i - off_blocks if causal else (
            i - off_blocks - reach if reach is not None else 0)
        hi = i - off_blocks + reach if reach is not None else nq - 1
        return jnp.clip(jnp.clip(j, lo, hi), 0, nq - 1)

    return idx


def _spec(tail: tuple, idx_fn, dyn: bool):
    """The VMEM block ``tail`` of one (batch, head) of a packed operand at S-block
    ``idx_fn(i, j, *scalars)``: ``(block, D)`` of a ``[BH, S, D]`` array, or the
    statistics' ``(1, 1, block)`` of ``[BH, S/block, 1, block]`` (trailing block dims
    equal to the array's, which Mosaic's last-two-dims rule admits). With ``dyn`` the
    index map takes the scalar-prefetch ref as a trailing argument (the
    ``PrefetchScalarGridSpec`` convention) — how a TRACED hop offset steers a banded
    walk."""
    zeros = (0,) * (len(tail) - 1)
    if dyn:
        return pl.BlockSpec((None,) + tail,
                            lambda b, i, j, off: (b, idx_fn(i, j, off)) + zeros,
                            memory_space=pltpu.VMEM)
    return pl.BlockSpec((None,) + tail,
                        lambda b, i, j: (b, idx_fn(i, j)) + zeros,
                        memory_space=pltpu.VMEM)


def _row_idx(i, j, *_):
    """The grid's own block axis: the operand a kernel holds across its walk."""
    return i


def _dyn_band_reach(window: int, block: int) -> int:
    """Band reach for TRACED offsets: one block wider than the static reach, so
    the steered band stays correct for ANY offset value — the index maps steer by
    ``off // block``, and the discarded sub-block remainder can push visible
    pairs one block outside the quantized band. (In-repo zig-zag callers pass
    block-quantized offsets, but the kernels' correctness must not depend on
    that.)"""
    return _band_reach(window, block) + 1


def _dyn_banded(window: int, nq: int, block: int) -> bool:
    """Whether the traced-offset banded walk is narrower than the full walk."""
    return bool(window) and 2 * _dyn_band_reach(window, block) + 1 < nq


def _pallas_dispatch(kernel, grid: tuple, in_specs, out_specs, out_shape,
                     scratch_shapes, dyn: bool, vmem_limit: int | None = None):
    """One owner for the dyn/static ``pallas_call`` shape (fwd, dq, and dkv all
    dispatch through here): traced offsets ride scalar prefetch
    (``PrefetchScalarGridSpec`` — the scalar is the first operand and reaches the
    index maps as their trailing arg), static paths use the plain grid.
    ``vmem_limit`` raises the kernel's scoped VMEM above the compiler's default (the
    fused backward's resident dq)."""
    # The kernel's name on a device trace: flash_fwd, flash_dq, flash_dkv.
    name = "flash" + kernel.func.__name__.removesuffix("_kernel")
    kw = dict(out_shape=out_shape, interpret=_interpret(), name=name)
    if vmem_limit is not None:
        kw["compiler_params"] = pltpu.CompilerParams(vmem_limit_bytes=vmem_limit)
    if dyn:
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid,
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch_shapes), **kw)
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch_shapes, **kw)


def _dispatch_block(body, qi, ki, bq, bk, in_range, *, causal: bool,
                    window: int, q_offset):
    """Shared liveness/interior gating for all the kernels (fwd/dq/dkv, fused or not):
    ``body(masked)`` runs only for live blocks, and fully-visible interior blocks
    take the mask-free specialization. One owner — an edit to the gating cannot
    desynchronize forward and backward masking."""
    live = in_range & _block_live(qi, ki, bq, bk, causal=causal, window=window,
                                  q_offset=q_offset)
    if causal or window:
        interior = _block_interior(qi, ki, bq, bk, causal=causal, window=window,
                                   q_offset=q_offset)
        pl.when(live & interior)(lambda: body(False))
        pl.when(live & ~interior)(lambda: body(True))
    else:
        pl.when(live)(lambda: body(False))


def _band_reach(window: int, block: int) -> int:
    """Max |query block − key block| with any in-window pair: the banded grid walks
    key-block offsets ``[-reach, +reach]`` (``[-reach, 0]`` causal) instead of all
    ``S/block`` key blocks, making grid overhead O(S·W/B²) rather than O((S/B)²) —
    at S=128k, W=4k, B=128 that is 33 steps per query block instead of 1024."""
    return (window + block - 2) // block


def _banded(window: int, causal: bool, nq: int, block: int) -> bool:
    """Use the band-compressed grid when it is actually narrower than the full walk."""
    if not window:
        return False
    reach = _band_reach(window, block)
    return (reach + 1 if causal else 2 * reach + 1) < nq


# =========================================================================================
# Forward
# =========================================================================================


def _fwd_kernel(*refs, scale, causal, num_steps, num_blocks,
                band_base=None, window=0, q_offset=0, dyn_offset=False):
    # ``dyn_offset``: the hop offset arrives as a TRACED int32 scalar via scalar
    # prefetch (the first operand) instead of the static ``q_offset`` — the
    # zig-zag schedules' chunk-pair offsets are device-dependent. r5: scalar-
    # prefetch index maps let the SAME traced offset steer a banded walk
    # (``band_base`` set), so dynamic windowed callers no longer pay the full
    # O((S/block)²) grid. Refs are [block, D], scratch [block, ...].
    if dyn_offset:
        off_ref, refs = refs[0], refs[1:]
        q_offset = off_ref[0]
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    iq = pl.program_id(1)
    step = pl.program_id(2)
    bq = q_ref.shape[0]
    # Band-compressed grid: the step axis walks key-block OFFSETS around the query
    # block (shifted by the hop offset when the caller's queries live q_offset
    # positions past the keys); out-of-range offsets (clamped to a real block by
    # the index_map) are dead.
    if band_base is None:
        j, in_range = step, jnp.bool_(True)
    else:
        j = iq + q_offset // bq + step - band_base
        in_range = (j >= 0) & (j < num_blocks)

    @pl.when(step == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    def body(masked: bool):
        # Matmul operands keep the INPUT dtype (bf16 runs at the MXU's native
        # rate; f32 inputs behave as before) with f32 accumulation; the softmax
        # scale is applied to the f32 product, not the narrow operand.
        visible = (_visibility_mask(iq, j, bq, k_ref.shape[0], causal=causal,
                                    window=window, q_offset=q_offset)
                   if masked else None)
        q = q_ref[:]                                                       # [bq, D]
        k_blk = k_ref[:]                                                   # [bk, D]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(visible, s, NEG)
        m = m_ref[:]
        l = l_ref[:]
        m_blk = jnp.max(s, axis=1, keepdims=True)                          # [bq, 1]
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(visible, p, 0.0)
        corr = jnp.exp(m - m_new)
        v_blk = v_ref[:]
        acc_new = acc_ref[:] * corr + jnp.dot(p.astype(v_blk.dtype), v_blk,
                                              preferred_element_type=jnp.float32)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:], m_ref[:], l_ref[:] = acc_new, m_new, l_new

    # Causal/banded: key blocks with no visible pair contribute nothing — no FLOPs
    # (and with the elided walks, no fetch either). Fully-visible INTERIOR blocks
    # skip the mask chain — per element it costs iota+compare+2 selects of VPU
    # work, which rivals the softmax exp (r5).
    _dispatch_block(body, iq, j, bq, k_ref.shape[0], in_range, causal=causal,
                    window=window, q_offset=q_offset)

    @pl.when(step == num_steps - 1)
    def _():
        l_cur = l_ref[:]
        l_safe = jnp.where(l_cur == 0.0, 1.0, l_cur)
        acc = acc_ref[:]
        lse = jnp.transpose(m_ref[:] + jnp.log(l_safe))                    # [1, bq]
        o_ref[:] = (acc / l_safe).astype(o_ref.dtype)
        lse_ref[:] = lse.reshape(1, 1, bq)


def _flash_forward(qx, kx, vx, *, causal: bool, block: int = BLOCK,
                   window: int = 0, q_offset: int = 0, q_offset_dyn=None):
    """Packed q, k ``[BH, S, D]`` and v ``[BH, S, Dv]`` → (out [BH, S, Dv], lse [BH,
    S/block, 1, block]); the scores' scale is ``D^-½``, the key width's.
    ``q_offset`` (static, a multiple of ``block``) shifts query positions globally
    relative to the keys — the ring hop offset (see ``_visibility_mask``).
    ``q_offset_dyn`` (a traced int32 scalar, mutually exclusive with a nonzero
    ``q_offset``) carries a DEVICE-DEPENDENT offset into the kernels via scalar
    prefetch — the zig-zag schedules' chunk-pair offsets. r5: the traced offset
    also STEERS the banded walk through scalar-prefetch index maps, so windowed
    dynamic callers pay O(S·W/block²) grid steps like the static path instead of
    the full O((S/block)²) walk. Unlike the static ``q_offset``, the traced
    offset need NOT be block-quantized: the dynamic band is one block wider
    (``_dyn_band_reach``) to absorb the sub-block remainder its floor-division
    steering discards."""
    bh, s, d = qx.shape
    dv = vx.shape[-1]
    _check_block(s, block)
    _check_offset(q_offset, block)
    dyn = q_offset_dyn is not None
    if dyn and q_offset:
        raise ValueError("q_offset and q_offset_dyn are mutually exclusive")
    scale = 1.0 / (d ** 0.5)
    nq = s // block
    off_blocks = q_offset // block
    # The dynamic-offset banded walk is bidirectional only: the causal one-sided
    # narrowing needs offset 0, and the zig-zag's dynamic pairs are non-causal.
    if not dyn and _banded(window, causal and not q_offset, nq, block):
        base = _band_reach(window, block)
        # A nonzero hop offset can put the whole band on one side of the local
        # diagonal, so the causal one-sided walk applies only at offset 0.
        num_steps = base + 1 if causal and not q_offset else 2 * base + 1
        key_idx = lambda i, o: jnp.clip(i + off_blocks + o - base, 0, nq - 1)
    elif dyn and not causal and _dyn_banded(window, nq, block):
        base = _dyn_band_reach(window, block)
        num_steps = 2 * base + 1
        key_idx = lambda i, o, off: jnp.clip(i + off[0] // block + o - base,
                                             0, nq - 1)
    else:
        base, num_steps = None, nq
        if not dyn and (causal or window):
            # Full walk with dead-step fetch elision (see _elided_key_idx).
            key_idx = _elided_key_idx(
                nq, off_blocks, _band_reach(window, block) if window else None,
                causal=causal)
        else:
            key_idx = lambda i, j, *_: j
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               num_steps=num_steps, num_blocks=nq, band_base=base,
                               window=window, q_offset=q_offset, dyn_offset=dyn)
    out_spec = _spec((block, dv), _row_idx, dyn)
    out_shape = [
        jax.ShapeDtypeStruct((bh, s, dv), qx.dtype),
        jax.ShapeDtypeStruct((bh, nq, 1, block), jnp.float32),
    ]
    scratch_shapes = [
        pltpu.VMEM((block, dv), jnp.float32),   # acc
        pltpu.VMEM((block, 1), jnp.float32),    # running max m
        pltpu.VMEM((block, 1), jnp.float32),    # running normalizer l
    ]
    dyn_args = ((jnp.asarray(q_offset_dyn, jnp.int32).reshape(1),) if dyn else ())
    out, lse = _pallas_dispatch(
        kernel, (bh, nq, num_steps),
        [_spec((block, d), _row_idx, dyn), _spec((block, d), key_idx, dyn),
         _spec((block, dv), key_idx, dyn)],
        [out_spec, _spec((1, 1, block), _row_idx, dyn)], out_shape,
        scratch_shapes, dyn)(*dyn_args, qx, kx, vx)
    return out, lse


# =========================================================================================
# Backward (recompute formulation: residuals are out + lse only). One kernel,
# ``_dkv_kernel(fused=True)``, makes dq, dk and dv from one set of scores a block pair;
# dq's [S, D] float32 of a (batch, head) is resident in VMEM (``FUSED_DQ_MAX_BYTES``).
# Past that budget ``_dq_kernel`` and the plain ``_dkv_kernel`` run as two calls.
# =========================================================================================


def _dq_kernel(*refs, scale, causal, num_steps, num_blocks,
               band_base=None, window=0, q_offset=0, dyn_offset=False):
    if dyn_offset:                      # traced hop offset (see _fwd_kernel)
        off_ref, refs = refs[0], refs[1:]
        q_offset = off_ref[0]
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
     dq_acc_ref) = refs
    iq = pl.program_id(1)
    step = pl.program_id(2)
    bq = q_ref.shape[0]
    if band_base is None:
        j, in_range = step, jnp.bool_(True)
    else:
        j = iq + q_offset // bq + step - band_base
        in_range = (j >= 0) & (j < num_blocks)

    @pl.when(step == 0)
    def _():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    def body(masked: bool):
        # Matmul operands keep the INPUT dtype (bf16 at the MXU's native rate),
        # f32 accumulation; softmax statistics and ds stay f32, narrowed only at
        # the matmul boundary (the standard TPU flash-backward precision split).
        visible = (_visibility_mask(iq, j, bq, k_ref.shape[0], causal=causal,
                                    window=window, q_offset=q_offset)
                   if masked else None)
        q = q_ref[:]                                              # [bq, D]
        do = do_ref[:]                                            # [bq, D]
        lse = jnp.transpose(lse_ref[0])                           # [bq, 1]
        delta = jnp.transpose(delta_ref[0])                       # [bq, 1]
        k_blk = k_ref[:]
        v_blk = v_ref[:]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(visible, s, NEG)
        p = jnp.exp(s - lse)                                      # [bq, bk]
        if masked:
            p = jnp.where(visible, p, 0.0)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        upd = jnp.dot(ds.astype(k_blk.dtype), k_blk,
                      preferred_element_type=jnp.float32)
        dq_acc_ref[:] = dq_acc_ref[:] + upd

    _dispatch_block(body, iq, j, bq, k_ref.shape[0], in_range, causal=causal,
                    window=window, q_offset=q_offset)

    @pl.when(step == num_steps - 1)
    def _():
        dq_ref[:] = (dq_acc_ref[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, causal, num_steps, num_blocks,
                band_base=None, window=0, q_offset=0, dyn_offset=False, fused=False):
    # ``fused``: the one-kernel backward. dq of the program's whole (batch, head)
    # stays in VMEM beside dk and dv (``dq_acc_ref`` [S, D] float32, ``dq_ref`` its
    # [S, D] output block, whose index the two inner grid axes do not move), and
    # each live block pair adds ``ds · k`` at its query block's rows; without it
    # ``_dq_kernel`` makes dq in a walk of its own.
    if dyn_offset:                      # traced hop offset (see _fwd_kernel)
        off_ref, refs = refs[0], refs[1:]
        q_offset = off_ref[0]
    if fused:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
         dq_acc_ref, dk_acc_ref, dv_acc_ref) = refs
        rows = lambda r: pl.ds(pl.multiple_of(r * bq, bq), bq)   # query block r of [S, D]
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
         dk_acc_ref, dv_acc_ref) = refs
    ik = pl.program_id(1)
    step = pl.program_id(2)
    bk = k_ref.shape[0]
    bq = q_ref.shape[0]
    # Banded: the step axis walks QUERY-block offsets around this key block
    # (causal keys are only visible to queries at or after them, so offsets start
    # at the diagonal: band_base == 0). A hop offset shifts the visible query
    # range the OPPOSITE way: queries near global key position sit off_blocks
    # EARLIER in their local index space.
    if band_base is None:
        i, in_range = step, jnp.bool_(True)
    else:
        i = ik - q_offset // bk + step - band_base
        in_range = (i >= 0) & (i < num_blocks)

    @pl.when(step == 0)
    def _():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    if fused:
        @pl.when((ik == 0) & (step == 0))
        def _():
            @pl.loop(0, num_blocks)
            def _(r):
                dq_acc_ref[rows(r), :] = jnp.zeros((bq, dq_acc_ref.shape[1]),
                                                   jnp.float32)

    def body(masked: bool):
        # Same precision split as the dq kernel: operands in the input dtype,
        # f32 accumulation, p/ds narrowed only at the matmul boundary.
        visible = (_visibility_mask(i, ik, bq, bk, causal=causal,
                                    window=window, q_offset=q_offset)
                   if masked else None)
        k = k_ref[:]                                              # [bk, D]
        v = v_ref[:]                                              # [bk, D]
        q_blk = q_ref[:]                                          # [bq, D]
        do_blk = do_ref[:]
        lse_blk = jnp.transpose(lse_ref[0])                       # [bq, 1]
        delta_blk = jnp.transpose(delta_ref[0])                   # [bq, 1]
        s = jax.lax.dot_general(q_blk, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(visible, s, NEG)
        p = jnp.exp(s - lse_blk)                                  # [bq, bk]
        if masked:
            p = jnp.where(visible, p, 0.0)
        # dv += pᵀ · do ; dk += dsᵀ · q
        dv_upd = jax.lax.dot_general(
            p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                   # [bk, D]
        dp = jax.lax.dot_general(do_blk, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk)
        dk_upd = jax.lax.dot_general(
            ds.astype(q_blk.dtype), q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dv_acc_ref[:] = dv_acc_ref[:] + dv_upd
        dk_acc_ref[:] = dk_acc_ref[:] + dk_upd
        if fused:
            # dq[i] += ds · k: key blocks arrive in ascending order, as the dq
            # kernel's walk sums them.
            dq_acc_ref[rows(i), :] = dq_acc_ref[rows(i), :] + jnp.dot(
                ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    # Causal/banded: query blocks with no visible pair against this key block skip;
    # fully-visible interior blocks skip the mask chain (see _fwd_kernel).
    _dispatch_block(body, i, ik, bq, bk, in_range, causal=causal,
                    window=window, q_offset=q_offset)

    @pl.when(step == num_steps - 1)
    def _():
        dk_ref[:] = (dk_acc_ref[:] * scale).astype(dk_ref.dtype)
        dv_ref[:] = dv_acc_ref[:].astype(dv_ref.dtype)

    if fused:
        @pl.when((ik == num_blocks - 1) & (step == num_steps - 1))
        def _():
            @pl.loop(0, num_blocks)
            def _(r):
                dq_ref[rows(r), :] = (dq_acc_ref[rows(r), :] * scale).astype(
                    dq_ref.dtype)


def backward_fused(s: int, d: int) -> bool:
    """Whether the backward of ``[S, D]`` queries a (batch, head) is the one fused
    kernel: its float32 dq fits the resident budget (``FUSED_DQ_MAX_BYTES``). The one
    predicate: ``flash_backward_blocks`` runs what this says and ``dispatch_plan``
    reports it."""
    return 4 * s * d <= FUSED_DQ_MAX_BYTES


def _flash_backward(res, g, *, causal: bool, block: int = BLOCK,
                    window: int = 0):
    qx, kx, vx, out, lse = res
    gsz, s = qx.shape[0], qx.shape[1]
    nq = s // block
    # Δ = rowsum(dout ∘ out), reshaped to the lse layout — XLA fuses this small
    # pass.
    prod = g.astype(jnp.float32) * out.astype(jnp.float32)
    delta = jnp.sum(prod, axis=-1).reshape(gsz, nq, 1, block)
    return flash_backward_blocks(qx, kx, vx, g, lse, delta, causal=causal,
                                 block=block, window=window)


def flash_backward_blocks(qx, kx, vx, g, lse, delta, *, causal: bool,
                          block: int = BLOCK, window: int = 0,
                          q_offset: int = 0, q_offset_dyn=None):
    """One flash-backward pass of a query-block set against a key/value-block set,
    given the GLOBAL softmax statistics: ``(dq, dk, dv)`` contributions.

    Packed layout (the ring schedules' shard form): ``qx: [BH, Sq, D]``, ``kx: [BH,
    Sk, D]``, ``vx: [BH, Sk, Dv]`` and ``g: [BH, Sq, Dv]`` with ``Sq == Sk``,
    ``lse/delta: [BH, Sq/BLOCK, 1, BLOCK]``. The statistics are of the FULL attention row (all
    keys, not just this block set): ``p = exp(q·kᵀ·scale − lse)`` then yields the
    true softmax coefficients restricted to these keys, so the returned
    contributions sum exactly over block sets — the per-hop building block of the
    trainable ring-of-flash (``parallel.ring_attention.ring_flash_attention``),
    where dk/dv ride the ring with their K/V blocks. ``causal=True`` masks with
    LOCAL block indices, i.e. it assumes q and k share a global origin — ring
    callers use it only for the diagonal hop."""
    bh, s, d = qx.shape
    dv = vx.shape[-1]
    if kx.shape != qx.shape or vx.shape[:2] != qx.shape[:2]:
        raise ValueError(
            f"flash_backward_blocks needs equal q/k block sets, got {qx.shape} vs "
            f"{kx.shape} and values {vx.shape}")
    _check_block(s, block)
    _check_offset(q_offset, block)
    dyn = q_offset_dyn is not None
    if dyn and q_offset:
        raise ValueError("q_offset and q_offset_dyn are mutually exclusive")
    scale = 1.0 / (d ** 0.5)
    nq = s // block
    off_blocks = q_offset // block
    one_sided = causal and not q_offset
    # The dynamic-offset banded walk (r5, scalar-prefetch index maps) is
    # bidirectional only, like the forward's.
    dyn_banded = dyn and not causal and _dyn_banded(window, nq, block)
    if not dyn and _banded(window, one_sided, nq, block):
        reach = _band_reach(window, block)
        # dq walks key blocks around the query block (causal: only the past side);
        # dkv walks query blocks around the key block (causal: only the future
        # side). A hop offset shifts the dq walk's center forward and the dkv
        # walk's center backward in local index space.
        dq_base, dq_steps = reach, (reach + 1 if one_sided else 2 * reach + 1)
        kv_base = 0 if one_sided else reach
        kv_steps = reach + 1 if one_sided else 2 * reach + 1
    elif dyn_banded:
        reach = _dyn_band_reach(window, block)
        dq_base = kv_base = reach
        dq_steps = kv_steps = 2 * reach + 1
    else:
        dq_base = kv_base = None
        dq_steps = kv_steps = nq

    # Full (non-banded) walks elide dead-step fetches by aliasing onto the nearest
    # live block (see _elided_key_idx); traced offsets steer banded walks through
    # scalar prefetch when a window permits, else take the plain walk.
    full_reach = _band_reach(window, block) if window else None
    elide = not dyn and (causal or window)

    def _walk_idx(base, center_off=0, kv=False):
        if base is None:
            if elide:
                mk = _elided_query_idx if kv else _elided_key_idx
                return mk(nq, off_blocks, full_reach, causal=causal)
            return lambda i, j, *_: j
        if dyn:
            sign = -1 if kv else 1
            return lambda i, o, off: jnp.clip(
                i + sign * (off[0] // block) + o - base, 0, nq - 1)
        return lambda i, o: jnp.clip(i + center_off + o - base, 0, nq - 1)

    # key-width operands (q, k and their gradients) and value-width ones (v, dout)
    row_spec, row_spec_v = (_spec((block, w), _row_idx, dyn) for w in (d, dv))
    lse_row_spec = _spec((1, 1, block), _row_idx, dyn)
    out_like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    acc, acc_v = (pltpu.VMEM((block, w), jnp.float32) for w in (d, dv))
    dyn_args = ((jnp.asarray(q_offset_dyn, jnp.int32).reshape(1),) if dyn else ())

    def call(kernel_fn, base, steps, in_specs, out_specs, out_shape, scratch,
             vmem_limit=None):
        kernel = functools.partial(kernel_fn, scale=scale, causal=causal,
                                   num_steps=steps, num_blocks=nq, band_base=base,
                                   window=window, q_offset=q_offset,
                                   dyn_offset=dyn)
        return _pallas_dispatch(kernel, (bh, nq, steps), in_specs, out_specs,
                                out_shape, scratch, dyn, vmem_limit)(
            *dyn_args, qx, kx, vx, g, lse, delta)

    # dkv grid: the query-block axis walks (accumulators persist per key block).
    kv_idx = _walk_idx(kv_base, -off_blocks, kv=True)
    kv_lse_walk = _spec((1, 1, block), kv_idx, dyn)
    kv_in = [_spec((block, d), kv_idx, dyn), row_spec, row_spec_v,
             _spec((block, dv), kv_idx, dyn), kv_lse_walk, kv_lse_walk]
    if backward_fused(s, d):
        # One kernel: dq's whole [S, D] of a (batch, head) rides the dkv walk.
        return call(functools.partial(_dkv_kernel, fused=True), kv_base, kv_steps,
                    kv_in, [_spec((s, d), lambda *_: 0, dyn), row_spec, row_spec_v],
                    [out_like(qx), out_like(kx), out_like(vx)],
                    [pltpu.VMEM((s, d), jnp.float32), acc, acc_v], FUSED_VMEM_LIMIT)

    dq_idx = _walk_idx(dq_base, off_blocks)
    dq = call(_dq_kernel, dq_base, dq_steps,
              [row_spec, _spec((block, d), dq_idx, dyn), _spec((block, dv), dq_idx, dyn),
               row_spec_v, lse_row_spec, lse_row_spec],
              [row_spec], [out_like(qx)], [acc])[0]
    dk, dvx = call(_dkv_kernel, kv_base, kv_steps, kv_in,
                   [row_spec, row_spec_v], [out_like(kx), out_like(vx)], [acc, acc_v])
    return dq, dk, dvx


# =========================================================================================
# Public API: custom-vjp op on [B, S, H, D], ops.full_attention-compatible
# =========================================================================================


@functools.lru_cache(maxsize=None)
def _make_op(causal: bool, block: int = BLOCK, window: int = 0):
    # The two halves are jitted, and this factory is cached: every layer of a
    # model calls the same two functions, so a program traces and lowers the three
    # kernels once, not once a layer (PR 25: the 24 Pallas calls of the 8-layer LM,
    # lowered in each of three programs, put 10 s on a 42 s warm start).
    kw = dict(causal=causal, block=block, window=window)

    @jax.jit
    def flash_forward(q3, k3, v3):
        return _flash_forward(q3, k3, v3, **kw)

    @jax.jit
    def flash_backward(res, g):
        return _flash_backward(res, g, **kw)

    @jax.custom_vjp
    def op(q3, k3, v3):
        return flash_forward(q3, k3, v3)[0]

    def fwd(q3, k3, v3):
        # Named here, outside the jitted half, as the VJP's residuals: a caller's
        # ``jax.checkpoint`` whose policy keeps these names does not run the forward
        # kernel again in its backward pass (an identity under no such policy).
        out, lse = flash_forward(q3, k3, v3)
        out, lse = checkpoint_name(out, "flash_out"), checkpoint_name(lse, "flash_lse")
        return out, (q3, k3, v3, out, lse)

    bwd = flash_backward

    op.defvjp(fwd, bwd)
    return op


def flash_forward_with_lse(q3: jax.Array, k3: jax.Array, v3: jax.Array, *,
                           causal: bool = False, window: int = 0,
                           q_offset: int = 0, q_offset_dyn=None):
    """Forward-only flash attention that also returns the per-row log-sum-exp:
    ``[BH, S, D]³ → (out [BH, S, D], lse [BH, S/BLOCK, 1, BLOCK])``.

    The lse rows are what blockwise/ring merges need to combine partial attention
    results exactly (``parallel.ring_attention.ring_flash_attention``). Not wrapped in
    the custom VJP — differentiate through ``flash_attention`` instead. Always the
    default BLOCK: the ring merge layouts are written against it. ``window`` /
    ``q_offset`` bind the sliding band and the ring hop offset into the kernels'
    masks (``_visibility_mask``) — the windowed ring-of-flash building block.
    """
    return _flash_forward(q3, k3, v3, causal=causal, window=window,
                          q_offset=q_offset, q_offset_dyn=q_offset_dyn)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False, block: int | None = None,
                    window: int | None = None) -> jax.Array:
    """Drop-in for ``ops.full_attention``: ``[B, S, H, D]`` → ``[B, S, H, D]``; ``v`` may
    be of another width than ``q`` and ``k`` (``[B, S, H, Dv]`` → ``[B, S, H, Dv]``: latent
    attention's 192 / 128), the scores' scale being the key width's ``D^-½``.

    Requires ``S % block == 0`` with ``block`` a multiple of 128 (lane-aligned), or
    ``causal=True``: a causal call of any other length is zero-padded at the tail to
    the next multiple of ``block``, run through the kernels and sliced, which the
    mask makes exact. ``block=None`` (the default) picks the measured-fastest size
    for the shape via ``auto_block``. Differentiable via the flash backward kernel; usable as the
    transformer family's ``attention_fn``. ``block`` is a pure performance knob
    (numerics are block-invariant — pinned in tests); tune it with
    ``bench_attention.py --block``. The kernels take operands packed
    ``[B·H, S, D]``: the two S↔H transposes around them cost less on the chip than
    either way of reading the model's layout in place (DESIGN.md §9).

    ``window=W`` is sliding-window/local attention with ``full_attention``'s exact
    semantics (distance < W; causal restricts to the past side) — and a BANDED grid:
    the step axis walks only key-block offsets within the band (``_band_reach``), so
    both compute AND grid/pipeline overhead are O(S·W) rather than O(S²) — the r2
    full-grid + ``@pl.when``-skip formulation still paid (S/B)² grid steps, which
    dominated at S ≥ 64k. Out-of-band blocks cost nothing: they are never stepped.
    """
    b, s, h, d = q.shape
    validate_window(window)
    padded, block = _flash_plan(s, causal=causal, window=window, block=block)
    if padded != s:
        # Exact under the causal mask: padded keys lie after every real query and
        # are masked; padded query rows are sliced away, so their dout and Δ are
        # zero and they add nothing to dk/dv. jnp.pad and the slice differentiate
        # as themselves around the custom-VJP op.
        q, k, v = (jnp.pad(x, ((0, 0), (0, padded - s), (0, 0), (0, 0)))
                   for x in (q, k, v))
    op = _make_op(bool(causal), block, int(window or 0))
    to3 = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, padded, x.shape[-1])
    out = jnp.transpose(op(to3(q), to3(k), to3(v)).reshape(b, h, padded, v.shape[-1]),
                        (0, 2, 1, 3))
    return out[:, :s] if padded != s else out


def _flash_plan(s: int, *, causal: bool, window: int | None,
                block: int | None = None) -> tuple[int, int]:
    """``(padded length, block)`` of a ``flash_attention`` call: the one place its
    tiling is decided, for the op itself and for ``dispatch_plan``. The padded length
    is ``s`` itself when it is lane-aligned or the call is not causal (only a causal
    mask makes tail padding exact; ``_check_block`` refuses the rest), else the next
    multiple of ``block`` (of 128 when ``auto_block`` is to choose)."""
    pad_to = block or BLOCK
    padded = s if s % BLOCK == 0 or not causal else -(-s // pad_to) * pad_to
    if block is None:
        block = auto_block(padded, int(window or 0))
    _check_block(padded, block)
    return padded, int(block)


def dispatch_plan(shape, *, causal: bool = False, window: int | None = None,
                  k_len: int | None = None, value_dim: int | None = None) -> dict:
    """What ``dispatch_attention`` does with a per-device ``[B, S, H, D]`` call (values
    of ``value_dim`` channels where that is not ``D``), from its shapes alone: ``{impl,
    score_bytes, seq_padded, block, key_dim, value_dim, backward}``. The one
    routing predicate: the dispatcher runs what this returns, and callers that label
    a measurement or a telemetry event (``train/lm.py``'s ``compile`` event,
    ``bench_transformer.py``, ``chip_smoke.py``) read the same dict, so a label
    cannot desync from the dispatch.

    ``impl`` is ``"flash"`` when the float32 score tensor the dense core would
    materialise (``B·H·S_q·S_k·4`` bytes, forward and again backward) reaches
    ``FLASH_MIN_SCORE_BYTES``, one (batch, head)'s tile of it reaches
    ``FLASH_MIN_HEAD_SCORE_BYTES``, and the kernels can run the call:
    self-attention (``S_q == S_k``) at a 128-aligned S, or any S under a causal
    mask (padded at the tail, ``seq_padded``). Everything else is ``"dense"``.
    Under ``jit`` over a mesh the shapes a trace sees are global: callers there
    hand the dispatcher per-device calls (``shard_map``) or keep the dense core
    (``train/lm.py``). ``backward`` is ``"fused"`` where the flash backward is its one
    kernel, ``"split"`` where the padded ``[S, D]`` is past the resident dq's budget
    (``backward_fused``) and ``None`` for a dense plan."""
    b, s, h, d = shape
    s_k = s if k_len is None else k_len
    plan = {"impl": "dense", "score_bytes": 4 * b * h * s * s_k,
            "seq_padded": None, "block": None, "key_dim": d,
            "value_dim": d if value_dim is None else value_dim, "backward": None}
    if (plan["score_bytes"] >= FLASH_MIN_SCORE_BYTES
            and 4 * s * s_k >= FLASH_MIN_HEAD_SCORE_BYTES
            and s_k == s and (causal or s % BLOCK == 0)):
        padded, block = _flash_plan(s, causal=causal, window=window)
        plan.update(impl="flash", seq_padded=padded, block=block,
                    backward="fused" if backward_fused(padded, d) else "split")
    return plan


def dispatch_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool = False,
                       window: int | None = None) -> jax.Array:
    """``full_attention``-compatible attention that picks the measured-faster
    implementation per call (``dispatch_plan``): XLA's dense path while the float32
    scores are small enough to stay on-chip, the flash kernels once they would go
    through HBM — so a caller that passes this as its ``attention_fn`` can never
    regress throughput the way the r3 trainer capture did with the kernels forced
    on (45.96 vs 86.09 steps/s at S=256,
    ``bench_results/hw_r3/bench_transformer_flash_tpu.json``). Calls the kernels
    cannot run (cross-attention, a non-causal S that is not a multiple of 128)
    take the dense path."""
    plan = dispatch_plan(q.shape, causal=causal, window=window, k_len=k.shape[1],
                         value_dim=v.shape[-1])
    if plan["impl"] == "dense":
        return full_attention(q, k, v, causal=causal, window=window)
    return flash_attention(q, k, v, causal=causal, window=window)
