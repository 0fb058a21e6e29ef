"""The gated delta rule with a per-channel decay (Kimi Delta Attention) as a chunked
scan, forward and backward, in Pallas.

Per head (keys of ``K`` channels, values of ``V``, a state of ``K x V``) and token ``t``::

    S_t = (I − β_t k_t k_tᵀ) Diag(exp(g_t)) S_{t−1} + β_t k_t v_tᵀ        S_0 = 0
    o_t = S_tᵀ q_t

``g_t <= 0`` is a vector of ``K`` log-decays, ``β_t`` a scalar. The convolutions,
softplus, the sigmoid, the output norm's learned scale and the output gate are the
caller's (``models/hybrid_lm.py``), all on the flat ``[B, S, H·width]`` layout the
projections write. What is one number a token and head is computed here, inside the
kernels, where a head's channels are the 128 lanes of the block a program holds and
such a number is a column that broadcasts along them: the unit norms (``q = q̃ ·
rsqrt(Σ_c q̃² + 1e-6) · K^-½``, ``k = k̃ · rsqrt(Σ_c k̃² + 1e-6)``, float32, from the operands
as silu wrote them), ``β`` (a program reads its head's column of the ``[rows, H]`` block
and forms ``βk``, ``βv``) and the output norm's statistic (``o · rsqrt(mean_c o² + eps)``
is what the kernel writes). Outside, each would be a ``[B, S, H, 1]`` factor broadcast
to 128 lanes and rewritten flat, with the heads moved between sublanes and lanes
(PERF.md §6, PR 34). Their gradients are autodiff's, inside ``kda_bwd``; ``dβ`` leaves as
a row a program.

The scan walks a sequence in chunks of ``C`` tokens (a ``Tiling``'s ``chunk``). With ``G`` the
running sum of ``g`` from the chunk's start (inclusive) and ``P(a, b)_ij = Σ_c a_ic b_jc
exp(G_ic − G_jc)``::

    A = strict_lower(P(βk, k))        Ũ = (I + A)⁻¹ (βv − (βk ⊙ e^G) S)
    o = (q ⊙ e^G) S + lower(P(q, k)) Ũ
    S' = Diag(e^{G_C}) S + (k ⊙ e^{G_C − G})ᵀ Ũ

``exp(−G)`` is never formed: a trained decay spans a few units a chunk, a seeded one
hundreds, and ``exp(G_i − G_j)`` is wanted only where ``i >= j``, where it is at most
one. ``P`` is built in sub-blocks of ``sub`` rows (4: chosen on the chip, PERF.md §6, PR
38). A pair inside one sub-block is computed exactly, ``Σ_c a_ic b_jc exp(G_ic − G_jc)``
a diagonal of the sub-block at a time (the rows shifted by their distance, on the VPU:
three diagonals a chunk). Every other pair is in the later half's rows against the
earlier half's columns of one block of ``2·sub``, ``4·sub``, … ``C`` rows, and each doubling
is one product of two operands rescaled against the later half's first row ``m``,
``a ⊙ exp(G − G_m)`` below it and ``b ⊙ exp(G_m − G)`` above, both factors at most one,
so a decay too small for float32 reads zero and never infinity.
``(I + A)⁻¹`` is exact elimination in products: inside the diagonal sub-blocks
``(I − D)(I + D²)(I + D⁴)…`` (``D`` is nilpotent), then ``(I − N)(I + N²)…`` over the
sub-blocks with ``N = (I + D)⁻¹(A − D)``, so no power of the whole ``A`` is taken.

``kda_fwd`` takes ``group`` chunks a grid step, the groups of one (batch, head) along a
sequential grid axis with the state carried in VMEM (held transposed, ``[V, K]``, so
that a channel's decay is a lane's), and writes the state that entered every group
(``[B, S/(group·C), H, V, K]`` float32: a state is 64 KiB at the published 128 x 128, so
every 64 tokens' would be 0.27 GB a layer and sequence of 8192, and a group's of 512
or 1024 tokens is an eighth or a sixteenth of that). ``kda_bwd`` walks the groups in
reverse carrying the state's gradient: a step runs its group's chunks again from the kept state and then their
transpose, which is ``jax.vjp`` of the very function the forward kernel runs, traced
into the kernel (so the two cannot drift apart). Within a grid step, what no state
enters (running sums, ``P``, the inverses, ``(I + A)⁻¹βk e^G`` and ``(I + A)⁻¹βv``) is
computed for all ``group`` chunks first, the inverses' products a step at a time across
the chunks, and the states follow: a chunk's small products wait for one another,
and chunk by chunk the MXU stood idle in the waits. Decays, masks, the running sums
(one float32 product with a triangle of ones, at ``highest``) and the state are
float32; every other product runs on the MXU in the model's dtype.

What a grid step holds (``Tiling``: the tokens of a chunk, the rows of a sub-block, the
chunks a step) changes no result beyond the products' rounding, since the chunked algorithm
is the recurrence for any chunk, and is no key of any model's file: it is sized on the
chip, one tiling a decay kind, because what bounds these kernels is the waits of small
dependent products and those regroup with the tiling (alone at the cells' shapes, forward
+ backward of a layer, bench_results/hw_pr44/ and hw_pr45/scan_tilings.jsonl: the scalar pair
26.5 ms at (64, 4, 4), 18.7 at (128, 8, 4), 17.1 at (128, 8, 8): half the grid steps and the
state walk's products at the MXU's full depth; the per-channel pair 25.5, 23.7 at (64, 4, 8),
21.7 at (128, 8, 4), 20.2 at (128, 4, 4), 18.3 at (128, 4, 8), which compiles in 43 s for 27
and was left for that, 20.5 at (128, 4, 16); a chunk of 256 is slower (per-channel) or dies
in the compiler (scalar)). ``kda_scan`` and ``gdn_scan`` default to their own (``KDA_TILING``,
``GDN_TILING``). The backward kernel holds every chunk's ``[C, 128]`` and ``[C, C]``
intermediates of a step for ``jax.vjp``, 17.15 MiB at the per-channel tiling and 17.61 at the
scalar one, where Mosaic gives a kernel that asks for none 16 MiB of scoped fast memory and
refuses the rest at compile time: ``_params`` asks for ``VMEM_LIMIT``, one limit for both
kinds (the limit alone moves no kernel's time: 18.748 ms for 18.752), and ``scan_plan``
reports it. In the cells, parent and change in one call (bench_results/hw_pr45/cells_tpu.jsonl,
PERF.md §6, PR 45): ``kda_fwd`` + ``kda_bwd`` 105.3 -> 82.9 ms a step and 3.577 -> 3.717
examples/s in ``kimi_linear_train_8k`` (+3.9 % on three seeds, every check ``correct``);
``gdn_fwd`` + ``gdn_bwd`` 48.3 -> 43.5 ms a step in ``qwen3_next_train_8k``.

A sequence whose length is not a multiple of ``group·C`` is padded at its end with
tokens that decay nothing and write nothing (``g = 0``, ``β = 0``, zero ``q̃``, ``k̃`` and ``v``,
which the ``1e-6`` under the norms' roots leaves zeros), and the result sliced.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


class Tiling(NamedTuple):
    """What one grid step of the scan holds. The recurrence is the same for any of them."""
    chunk: int      # tokens of a chunk, and the side of (I + A)⁻¹
    sub: int        # rows of a sub-block: pairs inside one are computed exactly
    group: int      # chunks a grid step, side by side, and between two kept states


# One tiling a decay kind, from its sweep on the chip at the cells' shapes: the fastest
# forward + backward of each kind that compiles in little more time than the one before it
# (bench_results/hw_pr44/scan_tilings.jsonl, bench_results/hw_pr45/; PERF.md §6, PR 44 and 45).
KDA_TILING = Tiling(128, 4, 4)      # a decay a channel (``kda_fwd``, ``kda_bwd``)
GDN_TILING = Tiling(128, 8, 8)      # one decay a token and head (``gdn_fwd``, ``gdn_bwd``)
# The scoped fast memory both kinds' kernels ask Mosaic for (its own 16 MiB refuse either
# tiling's backward kernel; the chip has 128).
VMEM_LIMIT = 32 << 20

NN, NT, TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _interpret() -> bool:
    """Compiled on TPU; interpret mode on CPU (the test platform)."""
    return jax.default_backend() != "tpu"


def _dot(a, b, contract, dtype):
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), (contract, ((), ())),
                               preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rolled(x, by: int):
    return pltpu.roll(x, by, 0)


_rolled.defvjp(lambda x, by: (pltpu.roll(x, by, 0), None),
               lambda by, _, d: (pltpu.roll(d, d.shape[0] - by, 0),))


def _shifted(x, by: int):
    """Row ``i`` of the result is row ``i − by`` of ``x`` (cyclic; callers mask the wrap)."""
    return jnp.roll(x, by, axis=0) if _interpret() else _rolled(x, by)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _pair_scores(q, k, kb, cum, sub: int, dtype):
    """``(strict_lower P(kb, k), lower P(q, k))`` of one chunk, ``[C, C]`` float32; the
    operands float32 ``[C, K]``."""
    c, d = k.shape
    row, col, token = _iota((c, c), 0), _iota((c, c), 1), _iota((c, d), 0)
    rowsum = lambda x: jnp.sum(x, axis=1, keepdims=True)
    # inside a sub-block, a diagonal at a time: exp(G_i − G_{i−by}) itself
    kk = jnp.zeros((c, c), jnp.float32)
    qk = jnp.where(row == col, rowsum(q * k), 0.0)
    for by in range(1, sub):
        near = token % sub >= by
        decay = jnp.exp(jnp.where(near, cum - _shifted(cum, by), 0.0))
        kd = jnp.where(near, _shifted(k, by) * decay, 0.0)
        at = row - col == by
        kk = jnp.where(at, rowsum(kb * kd), kk)
        qk = jnp.where(at, rowsum(q * kd), qk)
    # a doubling of the block at a time: the later half's rows against the earlier half's
    # columns, every row rescaled against the later half's first (a row is read in one
    # half only, so one exponential a doubling serves both operands)
    half = sub
    while half < c:
        middle = jnp.concatenate([jnp.broadcast_to(cum[n:n + 1], (2 * half, d))
                                  for n in range(half, c, 2 * half)])
        later = token % (2 * half) >= half
        scaled = jnp.exp(jnp.where(later, cum - middle, middle - cum))
        both = _dot(jnp.concatenate([kb * scaled, q * scaled]), k * scaled, NT, dtype)
        at = (row // (2 * half) == col // (2 * half)) & (col // half < row // half)
        kk, qk = jnp.where(at, both[:c], kk), jnp.where(at, both[c:], qk)
        half *= 2
    return kk, qk


def _scalar_pair_scores(q, k, kb, g, dtype):
    """``_pair_scores`` where a token's decay is one number (``g [C, K]`` holds it on every
    lane): ``exp(G_i − G_j)`` leaves the sum over channels, so both score matrices are ONE
    product ``[βk; q] kᵀ`` times a ``[C, C]`` mask. ``G_i − G_j = Σ_{j<t≤i} g_t`` is itself a
    product with the triangle of ones (float32 at ``highest``; no column of running sums
    is turned into a row), at most zero where ``i >= j``: no factor passes one, a decay
    too small for float32 reads zero, and ``exp(−G)`` is never formed."""
    c = k.shape[0]
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    after = jnp.where(row > col, jnp.broadcast_to(g[:, :1], (c, c)), 0.0)   # g_t, t > j
    span = jax.lax.dot_general((row >= col).astype(jnp.float32), after, (NN, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    decay = jnp.where(row >= col, jnp.exp(span), 0.0)
    both = _dot(jnp.concatenate([kb, q]), k, NT, dtype)
    return jnp.where(row > col, both[:c] * decay, 0.0), both[c:] * decay


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _unit_lower_inverses(mats, sub: int, dtype):
    """``(I + a)⁻¹`` of every strictly lower triangular ``a [C, C]`` of the tuple ``mats``,
    each product taken for all of them before the next: one matrix's products wait
    for one another, those of different matrices do not."""
    c = mats[0].shape[0]
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    eye = (row == col).astype(jnp.float32)
    mm = lambda xs, ys: [_dot(x, y, NN, dtype) for x, y in zip(xs, ys)]

    def nilpotent_inverses(ns, index: int):
        """``(I + n)⁻¹ = (I − n)(I + n²)(I + n⁴)…`` where ``n`` to the ``index`` is zero."""
        inverses, powers, reach = [eye - n for n in ns], ns, 2
        while reach < index:
            powers = mm(powers, powers)
            inverses = [x + y for x, y in zip(inverses, mm(inverses, powers))]
            reach *= 2
        return inverses

    inside = [jnp.where(row // sub == col // sub, a, 0.0) for a in mats]
    blocks = nilpotent_inverses(inside, sub)
    if c == sub:
        return tuple(blocks)
    across = mm(blocks, [a - d for a, d in zip(mats, inside)])
    return tuple(mm(nilpotent_inverses(across, c // sub), blocks))


def _inverses_fwd(mats, sub, dtype):
    inverses = _unit_lower_inverses(mats, sub, dtype)
    return inverses, inverses


def _inverses_bwd(sub, dtype, inverses, ds):
    # d(M⁻¹) = −M⁻¹ dM M⁻¹
    return (tuple(-_dot(_dot(inverse, d, TN, dtype), inverse, NT, dtype)
                  for inverse, d in zip(inverses, ds)),)


_unit_lower_inverses.defvjp(_inverses_fwd, _inverses_bwd)


def _chunks(q, k, kb, vb, g, state, chunk: int, sub: int, dtype, scalar: bool = False):
    """The chunks of one grid step of one head: ``(o [R, V] float32, the state after
    them)``. ``state`` is ``Sᵀ [V, K]``; ``q``, ``k``, ``kb = βk`` ``[R, K]``, ``vb = βv [R, V]`` and
    ``g [R, K]`` float32; the products run in ``dtype``. ``scalar``: a row of ``g`` is one
    number on every lane, and the pairs' scores are ``_scalar_pair_scores``'."""
    c = chunk
    ones = (_iota((c, c), 0) >= _iota((c, c), 1)).astype(jnp.float32)
    by_chunk = lambda x: [x[at:at + c] for at in range(0, x.shape[0], c)]
    # What no state enters, for every chunk before the first state: a chunk's products
    # are small and wait for one another (the inverse's above all), and written chunk
    # after chunk they ran so, the MXU idle in the waits (PERF.md §6, PR 38: −38 %).
    qs, ks, kbs, vbs = map(by_chunk, (q, k, kb, vb))
    cums = [jax.lax.dot_general(ones, x, (NN, ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32) for x in by_chunk(g)]
    growns = [jnp.exp(cum) for cum in cums]
    scores = ([_scalar_pair_scores(*x, dtype) for x in zip(qs, ks, kbs, by_chunk(g))]
              if scalar else [_pair_scores(*x, sub, dtype) for x in zip(qs, ks, kbs, cums)])
    inverses = _unit_lower_inverses(tuple(a_kk for a_kk, _ in scores), sub, dtype)
    ws = [_dot(inverse, kb * grown, NN, dtype)
          for inverse, kb, grown in zip(inverses, kbs, growns)]
    us = [_dot(inverse, vb, NN, dtype) for inverse, vb in zip(inverses, vbs)]
    out = []
    for q, k, cum, grown, (_, a_qk), w, u in zip(qs, ks, cums, growns, scores, ws, us):
        total = cum[c - 1:c]
        fresh = u - _dot(w, state, NT, dtype)                       # Ũ [C, V]
        out.append(_dot(q * grown, state, NT, dtype) + _dot(a_qk, fresh, NN, dtype))
        state = state * jnp.exp(total) + _dot(fresh, k * jnp.exp(total - cum), TN, dtype)
    return jnp.concatenate(out), state


def _unit(x, scale: float = 1.0):
    """A row's channels to length ``scale`` (``1e-6`` under the root: a row of zeros
    stays zeros)."""
    return x * (jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + 1e-6) * scale)


def _group(q, k, v, g, beta, state, chunk: int, sub: int, eps: float,
           scalar: bool = False):
    """The chunks of one grid step, one after the other: ``(o, the state after)``, with
    everything that is one number a token of this head: ``q``, ``k`` ``[R, K]`` and ``v
    [R, V]`` as the projections wrote them (the model's dtype) are brought to unit
    length and to ``βv`` here, ``beta [R, 1]`` float32 a column that broadcasts along
    the lanes, and a row of ``o`` leaves divided by its root mean square. ``scalar``: ``g``
    is such a column too, one log-decay a token."""
    dtype = q.dtype
    if scalar:
        g = jnp.broadcast_to(g, k.shape)
    q = _unit(q.astype(jnp.float32), q.shape[1] ** -0.5)
    k = _unit(k.astype(jnp.float32))
    kb, vb = beta * k, beta * v.astype(jnp.float32)
    o, state = _chunks(q, k, kb, vb, g, state, chunk, sub, dtype, scalar)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps)
    return o.astype(v.dtype), state


def _head_column(block):
    """This program's head of a ``[R, H]`` block of per-head scalars, ``[R, 1]``."""
    head = _iota(block.shape, 1) == pl.program_id(1)
    return jnp.sum(jnp.where(head, block, 0.0), axis=1, keepdims=True)


def _as_row(column, width: int):
    """``[R, 1]`` to ``[1, R]`` without a sublane-to-lane transpose: an ``NT`` product with
    rows (a sublane tile of them) that read the first lane, exact at ``highest``."""
    first = (_iota((8, width), 1) == 0).astype(jnp.float32)
    return jax.lax.dot_general(
        first, jnp.broadcast_to(column, (column.shape[0], width)), (NT, ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)[:1]


def _decay(g_ref, static: dict):
    """A program's log-decays: its ``[R, K]`` block, or with ``scalar`` its head's column of
    the ``[R, H]`` block of every head."""
    return _head_column(g_ref[...]) if static.get("scalar") else g_ref[...]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, entered_ref, state, **static):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    entered_ref[...] = state[...]
    o_ref[...], state[...] = _group(q_ref[...], k_ref[...], v_ref[...], _decay(g_ref, static),
                                    _head_column(beta_ref[...]), state[...], **static)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, entered_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate, **static):
    """The same group's transpose, the groups taken last to first; ``dstate``: the
    gradient of the state this group hands on."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    _, pull = jax.vjp(functools.partial(_group, **static),
                      q_ref[...], k_ref[...], v_ref[...], _decay(g_ref, static),
                      _head_column(beta_ref[...]), entered_ref[...])
    dq_ref[...], dk_ref[...], dv_ref[...], dg, dbeta, handed = pull((do_ref[...], dstate[...]))
    dg_ref[...] = _as_row(dg, q_ref.shape[1]) if static.get("scalar") else dg
    dstate[...] = handed
    dbeta_ref[...] = _as_row(dbeta, q_ref.shape[1])


def _specs(rows: int, heads: int, k: int, v: int, at, rep: int = 1):
    """Block specs by operand; ``at(s)`` is the group that step ``s`` of the sequential
    grid axis works on (the backward pass walks them in reverse). A head's channels
    are a block of lanes of the ``[B, S, H·width]`` arrays: no operand is transposed.
    ``beta`` comes as the ``[R, H]`` block of every head, of which a program reads its
    column; its gradient leaves as a row of ``[B, H, S/R, 1, R]``, so that the programs
    of one block of tokens write blocks of their own. ``shared``: the queries and keys of
    ``rep`` value heads are one key head's, ``[B, S, (H/rep)·K]``, and program ``h`` reads
    block ``h // rep``."""
    tokens = lambda width: pl.BlockSpec((None, rows, width),
                                        lambda b, h, s: (b, at(s), h))
    return {"k": tokens(k), "v": tokens(v),
            "shared": pl.BlockSpec((None, rows, k), lambda b, h, s: (b, at(s), h // rep)),
            "beta": pl.BlockSpec((None, rows, heads), lambda b, h, s: (b, at(s), 0)),
            "dbeta": pl.BlockSpec((None, None, None, 1, rows),
                                  lambda b, h, s: (b, h, at(s), 0, 0)),
            "state": pl.BlockSpec((None, None, None, v, k),
                                  lambda b, h, s: (b, at(s), h, 0, 0))}


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _scan_fwd(q, k, v, g, beta, chunk: int, sub: int, group: int, eps: float,
              scalar: bool = False, rep: int = 1):
    """``kda_fwd``; with ``scalar`` ``gdn_fwd``: ``g [B, S, H]`` like ``beta``, and ``q``, ``k`` of
    ``H / rep`` key heads."""
    bsz, s, heads = beta.shape
    dk, dv, rows = q.shape[2] // (heads // rep), v.shape[2] // heads, group * chunk
    sp = _specs(rows, heads, dk, dv, lambda step: step, rep)
    keys, decay = (sp["shared"], sp["beta"]) if scalar else (sp["k"], sp["k"])
    static = dict(chunk=chunk, sub=sub, eps=eps, **({"scalar": True} if scalar else {}))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **static), name="gdn_fwd" if scalar else "kda_fwd",
        interpret=_interpret(), grid=(bsz, heads, s // rows),
        in_specs=[keys, keys, sp["v"], decay, sp["beta"]],
        out_specs=[sp["v"], sp["state"]],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((bsz, s // rows, heads, dv, dk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_params(),
    )(q, k, v, g, beta)


def _scan_bwd(q, k, v, g, beta, entered, do, chunk: int, sub: int, group: int, eps: float,
              scalar: bool = False, rep: int = 1):
    """``kda_bwd``; with ``scalar`` ``gdn_bwd``: ``dg`` leaves as ``dbeta`` does, and a value
    head's program writes its own ``dq``, ``dk`` block, which the ``rep`` heads of a key head
    sum outside (two programs may not write one block)."""
    bsz, s, heads = beta.shape
    dk, dv, rows = q.shape[2] // (heads // rep), v.shape[2] // heads, group * chunk
    groups = s // rows
    sp = _specs(rows, heads, dk, dv, lambda step: groups - 1 - step, rep)
    keys, decay, ddecay = ((sp["shared"], sp["beta"], sp["dbeta"]) if scalar
                           else (sp["k"], sp["k"], sp["k"]))
    like = lambda x, width=None: jax.ShapeDtypeStruct(
        x.shape[:2] + (width or x.shape[2],), x.dtype)
    row = jax.ShapeDtypeStruct((bsz, heads, groups, 1, rows), jnp.float32)
    static = dict(chunk=chunk, sub=sub, eps=eps, **({"scalar": True} if scalar else {}))
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, **static), name="gdn_bwd" if scalar else "kda_bwd",
        interpret=_interpret(), grid=(bsz, heads, groups),
        in_specs=[keys, keys, sp["v"], decay, sp["beta"], sp["state"], sp["v"]],
        out_specs=[sp["k"], sp["k"], sp["v"], ddecay, sp["dbeta"]],
        out_shape=[like(q, heads * dk), like(k, heads * dk), like(v),
                   row if scalar else like(g), row],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=_params(),
    )(q, k, v, g, beta, entered, do.astype(v.dtype))
    by_token = lambda x: jnp.swapaxes(x.reshape(bsz, heads, s), 1, 2)
    if scalar:
        dg = by_token(dg)
        if rep > 1:
            dq, dk_ = (x.reshape(bsz, s, heads // rep, rep, dk).astype(jnp.float32).sum(3)
                       .reshape(q.shape).astype(q.dtype) for x in (dq, dk_))
    return dq, dk_, dv_, dg, by_token(dbeta)


@functools.lru_cache(maxsize=None)
def _make_op(chunk: int, sub: int, group: int, eps: float, scalar: bool = False,
             rep: int = 1):
    # Jitted halves behind a cached factory, as ``ssm._make_op``: every KDA layer of a
    # model calls the same two functions, lowered once a program.
    kw = dict(chunk=chunk, sub=sub, group=group, eps=eps)
    if scalar:
        kw.update(scalar=True, rep=rep)
    forward = jax.jit(functools.partial(_scan_fwd, **kw))
    backward = jax.jit(functools.partial(_scan_bwd, **kw))

    @jax.custom_vjp
    def op(q, k, v, g, beta):
        return forward(q, k, v, g, beta)[0]

    def fwd(q, k, v, g, beta):
        # Named as the VJP's residuals: a caller's ``jax.checkpoint`` whose policy
        # keeps these names does not run ``kda_fwd`` again in its backward pass.
        o, entered = forward(q, k, v, g, beta)
        o, entered = checkpoint_name(o, "kda_out"), checkpoint_name(entered, "kda_state")
        return o, (q, k, v, g, beta, entered)

    def bwd(residuals, do):
        return backward(*residuals, do)

    op.defvjp(fwd, bwd)
    return op


def _check_tiling(chunk: int, sub: int) -> None:
    if chunk % sub or (chunk // sub) & (chunk // sub - 1) or sub & (sub - 1):
        raise ValueError(f"sub-blocks of {sub} rows do not halve a chunk of {chunk}")


def _padded_scan(scope: str, op, operands, s: int, rows: int):
    """``op`` over ``operands [B, S, ·]`` padded at the end to whole groups of ``rows``
    tokens (zeros decay nothing and write nothing), the result sliced back to ``S``."""
    short = -s % rows
    padded = [jnp.pad(x, ((0, 0), (0, short), (0, 0))) for x in operands]
    with jax.named_scope(scope):
        o = op(*padded)
    return o[:, :s]


def kda_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array, *,
             eps: float, chunk: int = KDA_TILING.chunk, sub: int = KDA_TILING.sub,
             group: int = KDA_TILING.group) -> jax.Array:
    """``RMSNorm_head(o) [B, S, H·V]`` (no learned scale) of the recurrence above, on the
    flat layout the projections write: ``q``, ``k`` ``[B, S, H·K]`` and ``v [B, S, H·V]`` in
    the model's dtype, ``q`` and ``k`` before their unit norms; ``g [B, S, H·K]`` (log-decays,
    ``<= 0``) and ``beta [B, S, H]`` float32, whose last axis says how many heads there
    are. Differentiable in all five. Any ``S``: the tail of a sequence is padded to a
    whole group of chunks."""
    _check_tiling(chunk, sub)
    return _padded_scan("kda", _make_op(chunk, sub, group, eps),
                        (q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32)),
                        q.shape[1], group * chunk)


def gdn_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array, *,
             key_heads: int, eps: float, chunk: int = GDN_TILING.chunk,
             sub: int = GDN_TILING.sub, group: int = GDN_TILING.group) -> jax.Array:
    """``kda_scan`` for a decay that is one number a token and head (the gated delta rule:
    ``Diag(exp g) = e^g I``), computed as such: ``g [B, S, H]`` like ``beta``, a chunk's scores
    one product and a ``[C, C]`` mask (``_scalar_pair_scores``), everything else the same
    kernels' (``gdn_fwd``, ``gdn_bwd`` on a trace). ``q``, ``k`` ``[B, S, key_heads·K]`` may hold
    fewer heads than ``v [B, S, H·V]``: value head ``h`` reads key head ``h // (H / key_heads)``
    through its block spec, and the key heads' gradients are summed over their value
    heads outside the kernel."""
    _check_tiling(chunk, sub)
    heads = beta.shape[2]
    if heads % key_heads or q.shape[2] % key_heads or q.shape != k.shape:
        raise ValueError(f"{key_heads} key heads do not divide {heads} value heads, or "
                         f"the channels of q {q.shape} and k {k.shape}")
    return _padded_scan("gdn", _make_op(chunk, sub, group, eps, True, heads // key_heads),
                        (q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32)),
                        q.shape[1], group * chunk)


def scan_plan(*, heads: int, key_dim: int, value_dim: int, seq_len: int,
              chunk: int | None = None, sub: int | None = None, group: int | None = None,
              kept: tuple[str, ...] = (), key_heads: int | None = None) -> dict:
    """The ``compile`` event's ``kda`` field: what a KDA layer asks of a step. A state is
    kept a group of chunks, not a chunk. With ``key_heads`` the ``gdn`` field: ``heads``
    value heads over that many key heads, the decay one number a token and head. What of
    the tiling is not given is the decay kind's own, as the scans default to it."""
    own = KDA_TILING if key_heads is None else GDN_TILING
    chunk, sub, group = (mine if given is None else given
                         for given, mine in zip((chunk, sub, group), own))
    rows = group * chunk
    groups = -(-seq_len // rows)
    plan = {"heads": heads, "key_dim": key_dim, "value_dim": value_dim, "chunk": chunk,
            "sub_block": sub, "group": group, "chunks_per_sequence": groups * group,
            "states_per_sequence": groups,
            "state_bytes_per_sequence": groups * heads * key_dim * value_dim * 4,
            "kept": [name for name in ("kda_out", "kda_state") if name in kept],
            "in_kernel": ["q_norm", "k_norm", "beta", "out_norm"],
            "vmem_limit_bytes": _params().vmem_limit_bytes}
    if key_heads is not None:
        plan.update(key_heads=key_heads, decay="scalar")
    return plan
