"""Ring attention: sequence/context parallelism over a mesh axis.

Beyond-parity capability (the reference is DP-only — SURVEY.md §2c — and has no attention
op at all): self-attention over a sequence that is **sharded across devices along the
sequence axis**, so context length scales with the number of chips instead of being
bounded by one chip's HBM.

Design (TPU-first, the blockwise/ring formulation):

- Each device holds its local ``S/n`` slice of Q, K, V. K/V blocks rotate around the mesh
  axis ring with ``lax.ppermute`` — on hardware these hops ride **ICI** neighbor links,
  and XLA overlaps the permute with the block's attention math.
- Attention is accumulated with the **online softmax** recurrence (running max ``m``,
  running normalizer ``l``, running numerator ``acc``) in float32, so the sharded result
  equals the dense softmax to float32 round-off — pinned against
  ``ops.attention.full_attention`` in ``tests/test_ring_attention.py``.
- The hop loop is a ``lax.scan`` (not ``fori_loop``) so the whole thing is **reverse-mode
  differentiable**: ``ppermute`` transposes to the inverse permutation, and the scan gives
  XLA a static, compiler-friendly loop. Gradients are likewise parity-tested.
- Causal masking uses *global* positions reconstructed from ``lax.axis_index`` and the hop
  count, so decoder-style attention works identically under sharding.

No backend strings, no explicit sends: the collective schedule is the compiler's job
(same philosophy as ``parallel/collectives.py``).
"""

from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from csed_514_project_distributed_training_using_pytorch_tpu.ops.attention import (
    MASK_VALUE,
)


def _online_softmax_update(carry, q_scaled, k_blk, v_blk, visible):
    """Fold one K/V block into the online-softmax accumulators.

    ``carry = (acc [B,Sq,H,D] f32, m [B,H,Sq] f32, l [B,H,Sq] f32)``;
    ``q_scaled`` is the f32, pre-scaled query block; ``visible`` is a ``[Sq, Sk]``
    bool mask or ``None`` for a fully-visible block. Shared by the einsum ring and
    the zig-zag schedule — the numerically delicate part (running max, masked-row
    normalizer hygiene, correction factors) lives once."""
    acc, m, l = carry
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_scaled,
                        k_blk.astype(jnp.float32))    # [B,H,Sq,Sk]
    if visible is not None:
        scores = jnp.where(visible[None, None], scores, MASK_VALUE)
    m_block = jnp.max(scores, axis=-1)                # [B,H,Sq]
    m_new = jnp.maximum(m, m_block)
    p = jnp.exp(scores - m_new[..., None])            # [B,H,Sq,Sk]
    if visible is not None:
        # A fully-masked row leaves m_new at MASK_VALUE; exp(0)=1 entries must not
        # leak into the normalizer.
        p = jnp.where(visible[None, None], p, 0.0)
    correction = jnp.exp(m - m_new)                   # [B,H,Sq]
    l_new = l * correction + jnp.sum(p, axis=-1)
    acc_corr = jnp.transpose(correction, (0, 2, 1))[..., None]  # [B,Sq,H,1]
    acc_new = acc * acc_corr + jnp.einsum("bhqk,bkhd->bqhd", p,
                                          v_blk.astype(jnp.float32))
    return acc_new, m_new, l_new


def _case_index(origin, my_index):
    """Causal-hop classification for equal shards arriving whole:
    0 = entirely future (skip), 1 = entirely past (unmasked), 2 = diagonal (masked).
    Shared by the einsum ring and ring-of-flash — the switch branch order in both
    depends on this encoding."""
    return jnp.where(origin == my_index, 2,
                     jnp.where(origin < my_index, 1, 0))


def _zigzag_case(q_chunk, k_chunk, c, window):
    """Chunk-pair classification for the zig-zag flash schedule, same branch
    encoding as ``_case_index`` with the key chunk in the ``origin`` role —
    plus band liveness when windowed: a past pair whose CLOSEST elements sit
    ``(delta−1)·c + 1 ≥ W`` apart is dead (branch 0)."""
    if not window:
        return _case_index(k_chunk, q_chunk)
    delta = q_chunk - k_chunk
    live_past = (delta > 0) & ((delta - 1) * c + 1 < window)
    return jnp.where(delta == 0, 2, jnp.where(live_past, 1, 0))


def _ring_attention_local(ql: jax.Array, kl: jax.Array, vl: jax.Array, *,
                          axis_name: str, num_shards: int,
                          causal: bool, window: int = 0) -> jax.Array:
    """Per-device body: local Q block stays put; K/V blocks arrive via the ring.

    ``ql, kl, vl: [B, S/n, H, D]`` (this device's shard). Runs inside ``shard_map``.
    ``window=W`` restricts attention to the sliding band (``full_attention``'s
    semantics: distance < W; causal keeps the past side) — hops whose block lies
    entirely outside the band skip the einsums, so per-device work is O(W·C) once
    W ≲ a few chunks, regardless of the total ring length.
    """
    b, s_q, h, d = ql.shape
    s_k = kl.shape[1]
    my_index = lax.axis_index(axis_name)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    qf = ql.astype(jnp.float32) * scale

    # K/V move one step "forward" per hop: after hop t, the block sitting on device i
    # originated on device (i - t) mod n — that origin gives the block's global positions.
    perm = [(j, (j + 1) % num_shards) for j in range(num_shards)]
    q_pos = my_index * s_q + jnp.arange(s_q)  # global query positions [S/n]

    def update(carry, k_blk, v_blk, origin, masked: bool):
        """One block fold; ``masked`` is static — the diagonal hop (causal) and every
        live hop (windowed) apply a mask built from global positions."""
        visible = None
        if masked:
            k_pos = origin * s_k + jnp.arange(s_k)
            rel = q_pos[:, None] - k_pos[None, :]       # [Sq,Sk] signed distance
            visible = rel >= 0 if causal else jnp.ones_like(rel, bool)
            if window:
                visible &= (rel < window) & (rel > -window)
        return _online_softmax_update(carry, qf, k_blk, v_blk, visible)

    def fold(carry, k_blk, v_blk, origin):
        """One hop's block math. Causal hops decompose by the block's position
        relative to the local queries (equal shards arrive whole): entirely past →
        unmasked math, diagonal → masked math, entirely future → skipped outright
        (r3: previously every hop paid full einsums plus masking). Windowed hops
        additionally skip blocks entirely outside the band; live windowed blocks
        always take the masked path (the band may cut anywhere inside them)."""
        if window:
            # Block live iff its closest pair is inside the band: min distance
            # between distinct blocks delta apart is (delta-1)·C + 1.
            delta = jnp.abs(my_index - origin)
            live = (delta - 1) * s_k + 1 < window
            if causal:
                live &= origin <= my_index
            return lax.cond(
                live,
                lambda c, kb, vb, o: update(c, kb, vb, o, masked=True),
                lambda c, kb, vb, o: c,
                carry, k_blk, v_blk, origin)
        if not causal:
            return update(carry, k_blk, v_blk, origin, masked=False)
        return lax.switch(
            _case_index(origin, my_index),
            [lambda c, kb, vb, o: c,
             lambda c, kb, vb, o: update(c, kb, vb, o, masked=False),
             lambda c, kb, vb, o: update(c, kb, vb, o, masked=True)],
            carry, k_blk, v_blk, origin)

    def hop(carry, t):
        acc, m, l, k_cur, v_cur = carry
        acc, m, l = fold((acc, m, l), k_cur, v_cur,
                         (my_index - t) % num_shards)
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (acc, m, l, k_next, v_next), None

    acc0 = jnp.zeros((b, s_q, h, d), jnp.float32)
    m0 = jnp.full((b, h, s_q), MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((b, h, s_q), jnp.float32)
    # Scan the first n-1 hops (each: block math, then rotate K/V); the last arriving
    # block is folded in outside the scan so no ppermute is issued whose result is
    # discarded (XLA cannot DCE collectives inside a scan — that would otherwise cost an
    # extra round of ICI transfers per call).
    (acc, m, l, k_last, v_last), _ = lax.scan(
        hop, (acc0, m0, l0, kl, vl), jnp.arange(num_shards - 1))
    acc, _, l = fold((acc, m, l), k_last, v_last,
                     (my_index - (num_shards - 1)) % num_shards)

    # Under causal masking every query sees at least itself, so l > 0; the guard only
    # protects pathological all-masked rows from dividing by zero.
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / jnp.transpose(l_safe, (0, 2, 1))[..., None]
    return out.astype(ql.dtype)


def _qkv_spec(mesh: Mesh, shape: tuple, axis_name: str) -> P:
    """shard_map partition spec for a ``[B, S, H, D]`` operand on a composed mesh.

    The sequence dim always shards over ``axis_name``; the batch dim additionally
    shards over ``data`` and the head dim over ``model`` whenever those axes exist in
    the mesh and divide the corresponding dimension — attention is independent per
    batch element and per head, so the ring body is unchanged and each (data, model)
    coordinate works only its own slice instead of redundantly recomputing the full
    batch/all heads (the replication cost flagged in the round-2 advisor review)."""
    b, _, h, _ = shape

    def axis_if(name: str, dim: int):
        size = mesh.shape.get(name, 1)
        return name if (name != axis_name and size > 1 and dim % size == 0) else None

    return P(axis_if("data", b), axis_name, axis_if("model", h), None)


def ring_attention(mesh: Mesh, q: jax.Array, k: jax.Array, v: jax.Array, *,
                   axis_name: str = "seq", causal: bool = False,
                   window: int = 0) -> jax.Array:
    """Sequence-parallel attention: ``[B, S, H, D]`` with S sharded over ``axis_name``.

    Drop-in equivalent of ``ops.full_attention`` (same signature modulo the mesh);
    callable under ``jax.jit`` (the mesh is static). The sequence length must divide by
    the mesh axis size. On a composed mesh the batch/head dims co-shard over the
    ``data``/``model`` axes (see ``_qkv_spec``). ``window=W`` is sliding-window
    attention over the sharded sequence (``full_attention``'s band semantics):
    out-of-band hops skip their einsums, so long-context local attention scales as
    O(W·C) per device instead of O(S·C).
    """
    n = mesh.shape[axis_name]
    if q.shape[1] % n:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by mesh axis "
            f"{axis_name!r} size {n} — ring attention shards the sequence evenly")
    if window < 0:
        raise ValueError(f"window must be >= 0 (0 = full attention), got {window}")
    spec = _qkv_spec(mesh, q.shape, axis_name)

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
             check_vma=False)
    def _ring(ql, kl, vl):
        return _ring_attention_local(ql, kl, vl, axis_name=axis_name,
                                     num_shards=n, causal=causal, window=window)

    return _ring(q, k, v)


def make_ring_attention_fn(mesh: Mesh, *, axis_name: str = "seq",
                           use_flash: bool = False, use_zigzag: bool = False,
                           window: int = 0):
    """Bind a mesh into a ``(q, k, v, *, causal) -> out`` callable with
    ``ops.full_attention``'s exact signature — the injection point for
    ``models/transformer.py``'s pluggable ``attention_fn``.

    ``use_flash=True`` routes every hop's block math through the Pallas flash kernels
    (``ring_flash_attention`` — trainable, causal-capable); the per-device sequence
    shard must then divide by the flash ``BLOCK`` (128). ``use_zigzag=True`` uses the
    load-balanced zig-zag causal schedule (``zigzag_ring_attention``; causal-only).
    Both together select ``zigzag_ring_flash_attention`` — the full long-context
    causal training composition. ``window=W`` (r4) binds sliding-window masking into
    EVERY schedule: the einsum ring and the ring-of-flash skip out-of-band hops
    (the flash ring truncates its rotations to the band's reach), the einsum
    zig-zag band-masks each chunk pair from global positions, and the flash
    zig-zag carries its device-dependent chunk-pair offsets into the kernels as
    traced SMEM scalars (``q_offset_dyn``)."""

    def attention_fn(q, k, v, *, causal: bool = False):
        if use_zigzag:
            if not causal:
                raise ValueError("the zig-zag schedule is causal-only — use "
                                 "ring_attention for bidirectional attention")
            if use_flash:
                return zigzag_ring_flash_attention(mesh, q, k, v,
                                                   axis_name=axis_name,
                                                   window=window)
            return zigzag_ring_attention(mesh, q, k, v, axis_name=axis_name,
                                         window=window)
        if use_flash:
            return ring_flash_attention(mesh, q, k, v, axis_name=axis_name,
                                        causal=causal, window=window)
        return ring_attention(mesh, q, k, v, axis_name=axis_name, causal=causal,
                              window=window)

    return attention_fn


def _zigzag_order(n: int) -> tuple[list, list]:
    """Chunk permutation for the zig-zag layout and its inverse: 2n chunks laid out so
    shard_map's n contiguous slices are the pairs (i, 2n-1-i)."""
    order = []
    for i in range(n):
        order += [i, 2 * n - 1 - i]
    inv = [0] * (2 * n)
    for pos, chunk in enumerate(order):
        inv[chunk] = pos
    return order, inv


def zigzag_ring_attention(mesh: Mesh, q: jax.Array, k: jax.Array, v: jax.Array, *,
                          axis_name: str = "seq", window: int = 0) -> jax.Array:
    """Load-balanced CAUSAL ring attention via zig-zag chunk pairing.

    The naive causal ring leaves device ``i`` with ``i+1`` live hops out of ``n`` —
    utilization ≈ 50% at scale, the critical path being the last device. Zig-zag
    (the Megatron-CP / zigzag-ring schedule) splits the sequence into ``2n`` chunks
    and assigns device ``i`` the PAIR ``(i, 2n-1-i)`` — one early chunk, one late
    chunk. Per hop the K/V pair originating on device ``o`` meets the local query
    pair in 4 chunk-pair combinations, of which exactly TWO are live on every device
    at every non-diagonal hop (early-vs-early when ``my > o``, or late-vs-late when
    ``o > my``; the late-vs-early pair is always live, the early-vs-late never) and
    THREE on the diagonal hop — uniform load by construction. Each live pair is
    folded with the same online-softmax math as the plain ring; the within-chunk
    diagonal mask is the ordinary lower-triangular one, so no global-position
    plumbing is needed.

    The wrapper permutes chunks into the zig-zag layout before the shard_map and
    inverts it after, so the call is a drop-in for ``ring_attention(..., causal=
    True)`` (pinned equal to the dense causal oracle in tests); on hardware the
    boundary permutes are two collective-permutes that a long-context trainer can
    amortize by keeping activations in the zig-zag layout between layers.
    ``S % (2n) == 0`` required. Differentiable through scan/switch/ppermute — no
    custom VJP needed (einsum formulation).

    ``window=W`` (r4) binds the sliding causal band: every chunk-pair combination
    masks with GLOBAL positions rebuilt from the (traced) chunk ids, and pairs whose
    closest elements sit outside the band skip their einsums via ``lax.cond`` — the
    windowed-context-parallelism hop-skipping, applied per chunk pair (a device's
    work falls to the O(W) live pairs once W ≲ a few chunks).
    """
    n = mesh.shape[axis_name]
    b, s, h, d = q.shape
    if s % (2 * n):
        raise ValueError(
            f"zigzag ring attention needs sequence length divisible by 2·shards = "
            f"{2 * n}, got {s}")
    c = s // (2 * n)
    order, inv = _zigzag_order(n)
    spec = _qkv_spec(mesh, q.shape, axis_name)

    def to_zigzag(x):
        return x.reshape(b, 2 * n, c, h, d)[:, jnp.asarray(order)].reshape(
            b, s, h, d)

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
             check_vma=False)
    def _ring(ql, kl, vl):
        # LOCAL shapes: batch/head dims may be sharded over data/model (_qkv_spec).
        lb, ls, lh, ld = ql.shape
        my_index = lax.axis_index(axis_name)
        scale = 1.0 / jnp.sqrt(jnp.asarray(ld, jnp.float32))
        qf = ql.astype(jnp.float32) * scale
        qa, qb = qf[:, :c], qf[:, c:]                 # chunks (my, 2n-1-my)
        perm = [(j, (j + 1) % n) for j in range(n)]
        tri = (jnp.arange(c)[:, None] >= jnp.arange(c)[None, :])  # within-chunk diag

        def pair_fold(carry, qx, k_blk, v_blk, q_chunk, k_chunk):
            """Fold one (query-chunk, key-chunk) pair whose case varies by hop:
            future → skip, past → unmasked, equal → within-chunk diagonal mask.
            Windowed: global positions rebuilt from the chunk ids drive the band
            mask, and band-dead pairs skip their einsums via ``lax.cond``."""
            if window:
                rel = ((q_chunk * c + jnp.arange(c))[:, None]
                       - (k_chunk * c + jnp.arange(c))[None, :])
                visible = (rel >= 0) & (rel < window)
                delta = q_chunk - k_chunk
                live = (delta >= 0) & ((delta - 1) * c + 1 < window)
                return lax.cond(
                    live,
                    lambda a: _online_softmax_update(a[:3], qx, a[3], a[4],
                                                     visible),
                    lambda a: a[:3],
                    (*carry, k_blk, v_blk))
            return lax.switch(
                _case_index(k_chunk, q_chunk),
                [lambda a: a[:3],
                 lambda a: _online_softmax_update(a[:3], qx, a[3], a[4], None),
                 lambda a: _online_softmax_update(a[:3], qx, a[3], a[4], tri)],
                (*carry, k_blk, v_blk))

        def hop(carry, t):
            ca, cb, k_cur, v_cur = carry
            o = (my_index - t) % n
            ko, k2 = k_cur[:, :c], k_cur[:, c:]       # chunks (o, 2n-1-o)
            vo, v2 = v_cur[:, :c], v_cur[:, c:]
            # Of the 4 chunk-pair combinations, two are statically decided: the early
            # query chunk never sees the late key chunk (my ≤ n-1 < n ≤ 2n-1-o —
            # skipped outright, no switch), and the late query chunk always sees the
            # early key chunk in full (2n-1-my ≥ n > o) — unless a window bands it,
            # in which case it routes through pair_fold like the varying pairs.
            ca = pair_fold(ca, qa, ko, vo, my_index, o)
            if window:
                cb = pair_fold(cb, qb, ko, vo, 2 * n - 1 - my_index, o)
            else:
                cb = _online_softmax_update(cb, qb, ko, vo, None)
            cb = pair_fold(cb, qb, k2, v2, 2 * n - 1 - my_index, 2 * n - 1 - o)
            return (ca, cb, lax.ppermute(k_cur, axis_name, perm),
                    lax.ppermute(v_cur, axis_name, perm)), None

        def init():
            return (jnp.zeros((lb, c, lh, ld), jnp.float32),
                    jnp.full((lb, lh, c), MASK_VALUE, jnp.float32),
                    jnp.zeros((lb, lh, c), jnp.float32))

        (ca, cb, k_last, v_last), _ = lax.scan(
            hop, (init(), init(), kl, vl), jnp.arange(n - 1))
        o = (my_index - (n - 1)) % n
        ko, k2 = k_last[:, :c], k_last[:, c:]
        vo, v2 = v_last[:, :c], v_last[:, c:]
        ca = pair_fold(ca, qa, ko, vo, my_index, o)
        if window:
            cb = pair_fold(cb, qb, ko, vo, 2 * n - 1 - my_index, o)
        else:
            cb = _online_softmax_update(cb, qb, ko, vo, None)
        cb = pair_fold(cb, qb, k2, v2, 2 * n - 1 - my_index, 2 * n - 1 - o)

        def finish(carry):
            acc, _, l = carry
            l_safe = jnp.where(l == 0.0, 1.0, l)
            return acc / jnp.transpose(l_safe, (0, 2, 1))[..., None]

        return jnp.concatenate([finish(ca), finish(cb)], axis=1).astype(ql.dtype)

    out = _ring(to_zigzag(q), to_zigzag(k), to_zigzag(v))
    return out.reshape(b, 2 * n, c, h, d)[:, jnp.asarray(inv)].reshape(b, s, h, d)


def _apply_in_kernel_layout(op, ql, kl, vl):
    """Run a ``[BH, S_local, D]`` kernel-layout op on ``[B, S, H, D]`` local shards.

    Converts to the kernel layout ONCE and promotes to f32 at entry: the flash kernel
    emits its output in the input dtype, and merging n bf16-rounded partials would
    lose precision the f32 merge math cannot recover. K/V then ride the ring in 3-D
    form (ppermute is shape-agnostic) — no per-hop relayout. Uses LOCAL (not global)
    b/h sizes: the batch/head dims may be sharded over data/model (``_qkv_spec``).
    Shared by both ring-of-flash shard_map bodies."""
    lb, ls, lh, ld = ql.shape
    to3 = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(
        lb * lh, ls, ld).astype(jnp.float32)
    out3 = op(to3(ql), to3(kl), to3(vl))
    return jnp.transpose(out3.reshape(lb, lh, ls, ld),
                         (0, 2, 1, 3)).astype(ql.dtype)


def _flash_merge(carry, out3, lse4):
    """Merge one flash-kernel partial — ``out3 [BH, S, D]`` plus its log-sum-exp in
    the kernels' ``[BH, S/BLOCK, 1, BLOCK]`` statistics layout — into the blockwise-
    softmax accumulators ``(acc [BH,S,D], m [BH,S,1], l [BH,S,1])``. The exact
    combination ``lse = logsumexp_t(lse_t), out = Σ_t exp(lse_t − lse)·out_t``,
    shared by both ring-of-flash variants — the numerically delicate part lives
    once (as ``_online_softmax_update`` does for the einsum rings)."""
    acc, m, l = carry
    bh, srows, _ = out3.shape
    lse_rows = jnp.transpose(lse4, (0, 1, 3, 2)).reshape(bh, srows, 1)
    m_new = jnp.maximum(m, lse_rows)
    corr = jnp.exp(m - m_new)
    w = jnp.exp(lse_rows - m_new)
    return acc * corr + out3 * w, m_new, l * corr + w


def _flash_finish(carry):
    """Normalize blockwise-softmax accumulators: ``(out [BH,S,D], lse [BH,S,1])``.
    The guard only protects pathological all-masked rows from dividing by zero."""
    acc, m, l = carry
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return acc / l_safe, m + jnp.log(l_safe)


def _window_hop_reach(window: int, shard_len: int) -> int:
    """Max |shard delta| with any in-band pair: blocks ``delta`` shards apart have
    closest-pair distance ``(delta-1)·C + 1``, so the ring only needs
    ``min(reach, n-1)`` hops per direction — compute AND communication are O(W·C)."""
    if window <= 1:
        return 0
    return (window - 2) // shard_len + 1


@functools.lru_cache(maxsize=None)
def _make_windowed_ring_flash_op(axis_name: str, n: int, causal: bool,
                                 window: int, shard_len: int):
    """Per-device WINDOWED ring-of-flash op on ``[BH, C, D]`` (f32) operands, with a
    custom VJP — sliding-band attention over a sequence sharded across the ring.

    Each hop's K/V block originated a STATIC shard delta away (the hop loop is
    unrolled — ``n`` is static), so its global offset ``delta·C`` enters the flash
    kernels' band masks as the static ``q_offset`` (``ops.pallas_attention``), and
    band-dead deltas are skipped at trace time. The ring is TRUNCATED to the band's
    hop reach and runs BIDIRECTIONALLY for non-causal windows (forward hops cover
    past-side blocks, reverse hops future-side), so both compute and ICI traffic
    are O(W·C) per device instead of O(S·C) — the flash counterpart of the einsum
    ring's windowed hop-skipping. Per-device wraparound (a hop whose block sits on
    the sequence's other end) switches to the wrapped delta's offset via
    ``lax.cond``; under a causal window wrapped forward blocks are future and skip.

    Backward mirrors the truncated schedule: per live hop the blockwise backward
    runs with the same static offset, dk/dv accumulators ride with their K/V
    blocks, and after the truncated walk they rotate straight home (``reach``
    reverse hops) instead of completing the full circle.
    """
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import (
        pallas_attention as pa,
    )

    fwd_perm = [(j, (j + 1) % n) for j in range(n)]
    rev_perm = [(j, (j - 1) % n) for j in range(n)]
    reach = _window_hop_reach(window, shard_len)
    hops_fwd = min(reach, n - 1)
    hops_rev = 0 if causal else min(reach, n - 1 - hops_fwd)

    def _live(delta: int) -> bool:
        return delta == 0 or (abs(delta) - 1) * shard_len + 1 < window

    def _hop_deltas(t: int, reverse: bool):
        """(no-wrap delta, wrap delta) for hop t in the given direction."""
        return (-t, n - t) if reverse else (t, t - n)

    def _forward(q3, k3, v3):
        bh, sq, d = q3.shape
        nq = sq // pa.BLOCK
        my_index = lax.axis_index(axis_name)

        def merge(carry, k_blk, v_blk, *, flag, off):
            return _flash_merge(carry, *pa.flash_forward_with_lse(
                q3, k_blk, v_blk, causal=flag, window=window,
                q_offset=off * shard_len))

        def fold(carry, k_blk, v_blk, t: int, reverse: bool):
            d_nw, d_w = _hop_deltas(t, reverse)
            live_nw = _live(d_nw) and not (causal and d_nw < 0)
            live_w = _live(d_w) and not (causal and d_w < 0)
            br_nw = ((lambda c, kb, vb: merge(c, kb, vb, flag=False, off=d_nw))
                     if live_nw else (lambda c, kb, vb: c))
            br_w = ((lambda c, kb, vb: merge(c, kb, vb, flag=False, off=d_w))
                    if live_w else (lambda c, kb, vb: c))
            wrapped = (my_index + t >= n) if reverse else (my_index < t)
            return lax.cond(wrapped, br_w, br_nw, carry, k_blk, v_blk)

        acc0 = jnp.zeros((bh, sq, d), jnp.float32)
        m0 = jnp.full((bh, sq, 1), MASK_VALUE, jnp.float32)
        l0 = jnp.zeros((bh, sq, 1), jnp.float32)
        # Diagonal block: local origin, ordinary causal/band masking.
        carry = _flash_merge((acc0, m0, l0), *pa.flash_forward_with_lse(
            q3, k3, v3, causal=causal, window=window))
        k_cur, v_cur = k3, v3
        for t in range(1, hops_fwd + 1):       # unrolled: offsets are static
            k_cur = lax.ppermute(k_cur, axis_name, fwd_perm)
            v_cur = lax.ppermute(v_cur, axis_name, fwd_perm)
            carry = fold(carry, k_cur, v_cur, t, reverse=False)
        k_cur, v_cur = k3, v3
        for t in range(1, hops_rev + 1):
            k_cur = lax.ppermute(k_cur, axis_name, rev_perm)
            v_cur = lax.ppermute(v_cur, axis_name, rev_perm)
            carry = fold(carry, k_cur, v_cur, t, reverse=True)
        out3, lse_rows = _flash_finish(carry)
        return out3, lse_rows.reshape(bh, nq, pa.BLOCK)[:, :, None, :]

    @jax.custom_vjp
    def op(q3, k3, v3):
        return _forward(q3, k3, v3)[0]

    def fwd(q3, k3, v3):
        out3, lse4 = _forward(q3, k3, v3)
        return out3, (q3, k3, v3, out3, lse4)

    def bwd(res, g):
        q3, k3, v3, out3, lse4 = res
        bh, sq, d = q3.shape
        nq = sq // pa.BLOCK
        my_index = lax.axis_index(axis_name)
        g = g.astype(jnp.float32)
        delta4 = jnp.sum(g * out3, axis=-1).reshape(bh, nq, pa.BLOCK)[:, :, None, :]

        def contrib(k_blk, v_blk, *, flag, off):
            return pa.flash_backward_blocks(
                q3, k_blk, v_blk, g, lse4, delta4, causal=flag, window=window,
                q_offset=off * shard_len)

        zeros3 = lambda a: (jnp.zeros_like(q3), jnp.zeros_like(a),
                            jnp.zeros_like(a))

        def hop_contrib(k_blk, v_blk, t: int, reverse: bool):
            d_nw, d_w = _hop_deltas(t, reverse)
            live_nw = _live(d_nw) and not (causal and d_nw < 0)
            live_w = _live(d_w) and not (causal and d_w < 0)
            br_nw = ((lambda kb, vb: contrib(kb, vb, flag=False, off=d_nw))
                     if live_nw else (lambda kb, vb: zeros3(kb)))
            br_w = ((lambda kb, vb: contrib(kb, vb, flag=False, off=d_w))
                    if live_w else (lambda kb, vb: zeros3(kb)))
            wrapped = (my_index + t >= n) if reverse else (my_index < t)
            return lax.cond(wrapped, br_w, br_nw, k_blk, v_blk)

        # Diagonal.
        dq, dk_d, dv_d = pa.flash_backward_blocks(
            q3, k3, v3, g, lse4, delta4, causal=causal, window=window)

        def walk(perm_out, perm_home, hops, reverse):
            """One direction's truncated walk: K/V and their dk/dv accumulators
            rotate together; after the walk the accumulators rotate straight home."""
            nonlocal dq
            k_cur, v_cur = k3, v3
            dk_t = jnp.zeros_like(k3)
            dv_t = jnp.zeros_like(v3)
            for t in range(1, hops + 1):
                k_cur = lax.ppermute(k_cur, axis_name, perm_out)
                v_cur = lax.ppermute(v_cur, axis_name, perm_out)
                dk_t = lax.ppermute(dk_t, axis_name, perm_out)
                dv_t = lax.ppermute(dv_t, axis_name, perm_out)
                dq_h, dk_h, dv_h = hop_contrib(k_cur, v_cur, t, reverse)
                dq, dk_t, dv_t = dq + dq_h, dk_t + dk_h, dv_t + dv_h
            for _ in range(hops):
                dk_t = lax.ppermute(dk_t, axis_name, perm_home)
                dv_t = lax.ppermute(dv_t, axis_name, perm_home)
            return dk_t, dv_t

        dk_f, dv_f = walk(fwd_perm, rev_perm, hops_fwd, reverse=False)
        dk_r, dv_r = walk(rev_perm, fwd_perm, hops_rev, reverse=True)
        return dq, dk_d + dk_f + dk_r, dv_d + dv_f + dv_r

    op.defvjp(fwd, bwd)
    return op


@functools.lru_cache(maxsize=None)
def _make_ring_flash_op(axis_name: str, n: int, causal: bool):
    """Per-device ring-of-flash op on kernel-layout operands ``[BH, S/n, D]`` (f32),
    with a custom VJP so the composition TRAINS.

    Causal structure: because shards are equal-sized and K/V blocks arrive whole, every
    hop's block is (relative to the local queries) entirely in the past, on the
    diagonal, or entirely in the future — so per hop a ``lax.switch`` picks the
    non-causal flash kernel, the causal flash kernel, or skips the block outright
    (future hops cost no kernel launch; their fetch already rode the ring). No
    per-offset masks enter the kernels. The naive ring order leaves device i with
    ``i+1`` live hops of ``n`` — the inherent load imbalance of causal ring attention;
    ``zigzag_ring_flash_attention`` is the leveled schedule.

    Backward: the saved residuals are the inputs plus the MERGED ``(out, lse)`` only —
    O(S·D) per device, no score matrix. Each reverse hop recomputes the block's softmax
    coefficients from the GLOBAL lse via ``ops.pallas_attention.flash_backward_blocks``
    (``p = exp(q·kᵀ·scale − lse)`` restricted to the block is exactly the true
    coefficient set), accumulates dq locally, and accumulates dk/dv into buffers that
    RIDE THE RING with their K/V blocks; after the last hop one extra ppermute delivers
    every dk/dv block back to its home device.
    """
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import (
        pallas_attention as pa,
    )

    perm = [(j, (j + 1) % n) for j in range(n)]

    def rot(x):
        return lax.ppermute(x, axis_name, perm)

    def _forward(q3, k3, v3):
        bh, sq, d = q3.shape
        nq = sq // pa.BLOCK
        my_index = lax.axis_index(axis_name)

        def fold(carry, k_blk, v_blk, origin):
            acc, m, l = carry

            def apply(flag):
                def f(args):
                    kb, vb = args[3], args[4]
                    return _flash_merge(
                        args[:3], *pa.flash_forward_with_lse(q3, kb, vb,
                                                             causal=flag))
                return f

            args = (acc, m, l, k_blk, v_blk)
            if not causal:
                return apply(False)(args)
            return lax.switch(_case_index(origin, my_index),
                              [lambda a: a[:3], apply(False), apply(True)], args)

        def hop(carry, t):
            acc, m, l, k_cur, v_cur = carry
            acc, m, l = fold((acc, m, l), k_cur, v_cur, (my_index - t) % n)
            return (acc, m, l, rot(k_cur), rot(v_cur)), None

        acc0 = jnp.zeros((bh, sq, d), jnp.float32)
        m0 = jnp.full((bh, sq, 1), MASK_VALUE, jnp.float32)
        l0 = jnp.zeros((bh, sq, 1), jnp.float32)
        # n-1 permuting hops, then fold the last arriving block without rotating —
        # no discarded collective (same structure as _ring_attention_local above).
        (acc, m, l, k_last, v_last), _ = lax.scan(
            hop, (acc0, m0, l0, k3, v3), jnp.arange(n - 1))
        acc, m, l = fold((acc, m, l), k_last, v_last,
                         (my_index - (n - 1)) % n)
        out3, lse_rows = _flash_finish((acc, m, l))
        lse4 = lse_rows.reshape(bh, nq, pa.BLOCK)[:, :, None, :]
        return out3, lse4

    @jax.custom_vjp
    def op(q3, k3, v3):
        return _forward(q3, k3, v3)[0]

    def fwd(q3, k3, v3):
        out3, lse4 = _forward(q3, k3, v3)
        return out3, (q3, k3, v3, out3, lse4)

    def bwd(res, g):
        q3, k3, v3, out3, lse4 = res
        bh, sq, d = q3.shape
        nq = sq // pa.BLOCK
        my_index = lax.axis_index(axis_name)
        g = g.astype(jnp.float32)
        # Δ = rowsum(dout ∘ out) over the FULL row — constant across hops, in the
        # kernels' [BH, nq, 1, BLOCK] statistics layout.
        delta4 = jnp.sum(g * out3, axis=-1).reshape(bh, nq, pa.BLOCK)[:, :, None, :]

        def contrib(k_blk, v_blk, origin):
            args = (q3, k_blk, v_blk, g, lse4, delta4)
            if not causal:
                return pa.flash_backward_blocks(*args, causal=False)
            return lax.switch(
                _case_index(origin, my_index),
                [lambda a: (jnp.zeros_like(q3), jnp.zeros_like(a[1]),
                            jnp.zeros_like(a[2])),
                 lambda a: pa.flash_backward_blocks(*a, causal=False),
                 lambda a: pa.flash_backward_blocks(*a, causal=True)], args)

        def hop(carry, t):
            dq, dk_cur, dv_cur, k_cur, v_cur = carry
            dq_h, dk_h, dv_h = contrib(k_cur, v_cur, (my_index - t) % n)
            # dk/dv accumulators travel WITH their K/V blocks around the ring.
            return (dq + dq_h, rot(dk_cur + dk_h), rot(dv_cur + dv_h),
                    rot(k_cur), rot(v_cur)), None

        init = (jnp.zeros_like(q3), jnp.zeros_like(k3), jnp.zeros_like(v3), k3, v3)
        (dq, dk_t, dv_t, k_last, v_last), _ = lax.scan(hop, init, jnp.arange(n - 1))
        dq_h, dk_h, dv_h = contrib(k_last, v_last, (my_index - (n - 1)) % n)
        # After n-1 rotations the accumulators sit one hop short of home.
        return dq + dq_h, rot(dk_t + dk_h), rot(dv_t + dv_h)

    op.defvjp(fwd, bwd)
    return op


def ring_flash_attention(mesh: Mesh, q: jax.Array, k: jax.Array, v: jax.Array, *,
                         axis_name: str = "seq", causal: bool = False,
                         window: int = 0) -> jax.Array:
    """Ring-of-flash: sequence-parallel attention whose per-hop block math runs through
    the Pallas flash kernels (``ops/pallas_attention.py``) instead of dense einsums.

    The true long-context composition on TPU: the ring shards the sequence across chips
    (K/V hops on ICI), and within each hop the arriving block attends via the
    O(block·D)-VMEM flash kernel, so neither level ever materializes a score matrix.
    Per-hop partial results carry their log-sum-exp rows and are merged with the
    standard blockwise-softmax combination

        lse = logsumexp_t(lse_t),   out = Σ_t exp(lse_t − lse) · out_t

    which is exact (pinned against the dense oracle in ``tests/test_ring_attention.py``).

    Trainable AND causal (round-3; previously forward-only, non-causal): gradients flow
    through a custom VJP whose reverse pass runs the flash backward kernels per hop with
    the merged global softmax statistics, dk/dv riding the ring home with their blocks —
    see ``_make_ring_flash_op``. Causal masking decomposes per hop into
    past/diagonal/future cases (non-causal kernel / causal kernel / skipped), so decoder
    training composes with sequence parallelism. Per-device sequence shard must divide
    by the flash BLOCK (128), i.e. ``S % (shards · 128) == 0``. On a composed mesh the
    batch/head dims co-shard over ``data``/``model`` (``_qkv_spec``).

    ``window=W`` (r4) selects the WINDOWED ring-of-flash: each hop's static shard
    offset enters the kernels' band masks (``q_offset``), and the ring truncates to
    the band's hop reach — bidirectional for non-causal windows — so compute and
    ICI traffic are O(W·C) per device (``_make_windowed_ring_flash_op``).
    """
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import (
        pallas_attention as pa,
    )

    n = mesh.shape[axis_name]
    b, s, h, d = q.shape
    if s % (n * pa.BLOCK):
        raise ValueError(
            f"ring_flash_attention needs sequence length divisible by "
            f"shards·BLOCK = {n}·{pa.BLOCK}, got {s}")
    if window < 0:
        raise ValueError(f"window must be >= 0 (0 = full attention), got {window}")
    spec = _qkv_spec(mesh, q.shape, axis_name)
    if window:
        op = _make_windowed_ring_flash_op(axis_name, n, bool(causal),
                                          int(window), s // n)
    else:
        op = _make_ring_flash_op(axis_name, n, bool(causal))

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
             check_vma=False)
    def _ring(ql, kl, vl):
        return _apply_in_kernel_layout(op, ql, kl, vl)

    return _ring(q, k, v)


@functools.lru_cache(maxsize=None)
def _make_zigzag_flash_op(axis_name: str, n: int, window: int = 0):
    """Per-device zig-zag ring-of-flash op on ``[BH, 2c, D]`` f32 chunk pairs, with a
    custom VJP — the load-balanced causal schedule with Pallas flash kernels on every
    live chunk pair.

    Same structure as ``_make_ring_flash_op`` (separate online-softmax carries per
    local chunk, global-lse blockwise backward, dk/dv riding the ring), with the
    zig-zag case analysis of ``zigzag_ring_attention``: per hop the early-vs-late
    pair is statically skipped, the late-vs-early pair always runs the non-causal
    kernel, and the two same-parity pairs switch between skip / non-causal / causal
    (the diagonal needs only the kernels' LOCAL blockwise causal masking, since a
    chunk pair on the diagonal shares its global offset).

    ``window=W`` (r4 — the final cell of the schedule × masking matrix): the
    chunk-pair offsets are DEVICE-DEPENDENT (``(q_chunk − k_chunk)·c`` with traced
    chunk ids), so live past pairs route through the flash kernels' dynamic-offset
    path (``q_offset_dyn`` — the offset rides into the kernels as an SMEM scalar,
    verified bit-equal to the static path on-chip), the diagonal keeps the static
    causal+window kernel, band-dead pairs (closest elements ≥ W apart) skip at the
    switch — including the late-vs-early pair, which is always live without a
    window. A past pair needs no causal term: its minimum distance is ≥ 1, so the
    symmetric band mask is exact. ONE factory owns the delicate ring bookkeeping
    for both maskings."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import (
        pallas_attention as pa,
    )

    perm = [(j, (j + 1) % n) for j in range(n)]

    def rot(x):
        return lax.ppermute(x, axis_name, perm)

    def _lse4(rows, nq):
        """[BH, c] rows → the kernels' [BH, nq, 1, BLOCK] statistics layout."""
        bh = rows.shape[0]
        return rows.reshape(bh, nq, pa.BLOCK)[:, :, None, :]

    def _forward(q3, k3, v3):
        bh, s2, d = q3.shape
        c = s2 // 2
        my_index = lax.axis_index(axis_name)
        qa, qb = q3[:, :c], q3[:, c:]

        def pair(carry, qx, k_blk, v_blk, q_chunk, k_chunk):
            off = (q_chunk - k_chunk) * c

            def past(a):
                return _flash_merge(a[:3], *pa.flash_forward_with_lse(
                    qx, a[3], a[4], causal=False, window=window,
                    q_offset_dyn=off if window else None))

            def diag(a):
                return _flash_merge(a[:3], *pa.flash_forward_with_lse(
                    qx, a[3], a[4], causal=True, window=window))

            return lax.switch(_zigzag_case(q_chunk, k_chunk, c, window),
                              [lambda a: a[:3], past, diag],
                              (*carry, k_blk, v_blk))

        def fold(ca, cb, k_cur, v_cur, o):
            ko, k2 = k_cur[:, :c], k_cur[:, c:]
            vo, v2 = v_cur[:, :c], v_cur[:, c:]
            # Static pair outcomes as in zigzag_ring_attention: early-vs-late never
            # fires; late-vs-early is always fully visible WITHOUT a window (the
            # band can kill it, so windowed runs route it through the switch too).
            ca = pair(ca, qa, ko, vo, my_index, o)
            if window:
                cb = pair(cb, qb, ko, vo, 2 * n - 1 - my_index, o)
            else:
                cb = _flash_merge(cb, *pa.flash_forward_with_lse(
                    qb, ko, vo, causal=False))
            cb = pair(cb, qb, k2, v2, 2 * n - 1 - my_index, 2 * n - 1 - o)
            return ca, cb

        def hop(carry, t):
            ca, cb, k_cur, v_cur = carry
            ca, cb = fold(ca, cb, k_cur, v_cur, (my_index - t) % n)
            return (ca, cb, rot(k_cur), rot(v_cur)), None

        def init():
            return (jnp.zeros((bh, c, d), jnp.float32),
                    jnp.full((bh, c, 1), MASK_VALUE, jnp.float32),
                    jnp.zeros((bh, c, 1), jnp.float32))

        (ca, cb, k_last, v_last), _ = lax.scan(
            hop, (init(), init(), k3, v3), jnp.arange(n - 1))
        ca, cb = fold(ca, cb, k_last, v_last, (my_index - (n - 1)) % n)

        out_a, lse_a = _flash_finish(ca)
        out_b, lse_b = _flash_finish(cb)
        lse_a, lse_b = lse_a[..., 0], lse_b[..., 0]              # rows [BH, c]
        return (jnp.concatenate([out_a, out_b], axis=1),
                jnp.concatenate([lse_a, lse_b], axis=1))         # lse rows [BH, 2c]

    @jax.custom_vjp
    def op(q3, k3, v3):
        return _forward(q3, k3, v3)[0]

    def fwd(q3, k3, v3):
        out3, lse_rows = _forward(q3, k3, v3)
        return out3, (q3, k3, v3, out3, lse_rows)

    def bwd(res, g):
        q3, k3, v3, out3, lse_rows = res
        bh, s2, d = q3.shape
        c = s2 // 2
        nq = c // pa.BLOCK
        my_index = lax.axis_index(axis_name)
        g = g.astype(jnp.float32)
        qa, qb = q3[:, :c], q3[:, c:]
        ga, gb = g[:, :c], g[:, c:]
        delta_rows = jnp.sum(g * out3, axis=-1)                  # [BH, 2c]
        stats_a = (_lse4(lse_rows[:, :c], nq), _lse4(delta_rows[:, :c], nq))
        stats_b = (_lse4(lse_rows[:, c:], nq), _lse4(delta_rows[:, c:], nq))

        def contrib(qx, gx, stats, k_blk, v_blk, q_chunk, k_chunk):
            off = (q_chunk - k_chunk) * c
            args = (qx, k_blk, v_blk, gx, *stats)
            return lax.switch(
                _zigzag_case(q_chunk, k_chunk, c, window),
                [lambda a: (jnp.zeros_like(qx), jnp.zeros_like(a[1]),
                            jnp.zeros_like(a[2])),
                 lambda a: pa.flash_backward_blocks(
                     *a, causal=False, window=window,
                     q_offset_dyn=off if window else None),
                 lambda a: pa.flash_backward_blocks(*a, causal=True,
                                                    window=window)], args)

        def fold(dqa, dqb, dk_cur, dv_cur, k_cur, v_cur, o):
            ko, k2 = k_cur[:, :c], k_cur[:, c:]
            vo, v2 = v_cur[:, :c], v_cur[:, c:]
            d1q, d1k, d1v = contrib(qa, ga, stats_a, ko, vo, my_index, o)
            if window:
                d2q, d2k, d2v = contrib(qb, gb, stats_b, ko, vo,
                                        2 * n - 1 - my_index, o)
            else:
                d2q, d2k, d2v = pa.flash_backward_blocks(qb, ko, vo, gb,
                                                         *stats_b, causal=False)
            d3q, d3k, d3v = contrib(qb, gb, stats_b, k2, v2,
                                    2 * n - 1 - my_index, 2 * n - 1 - o)
            dqa = dqa + d1q
            dqb = dqb + d2q + d3q
            dk_cur = dk_cur + jnp.concatenate([d1k + d2k, d3k], axis=1)
            dv_cur = dv_cur + jnp.concatenate([d1v + d2v, d3v], axis=1)
            return dqa, dqb, dk_cur, dv_cur

        def hop(carry, t):
            dqa, dqb, dk_cur, dv_cur, k_cur, v_cur = carry
            dqa, dqb, dk_cur, dv_cur = fold(dqa, dqb, dk_cur, dv_cur,
                                            k_cur, v_cur, (my_index - t) % n)
            return (dqa, dqb, rot(dk_cur), rot(dv_cur),
                    rot(k_cur), rot(v_cur)), None

        init = (jnp.zeros_like(qa), jnp.zeros_like(qb),
                jnp.zeros_like(k3), jnp.zeros_like(v3), k3, v3)
        (dqa, dqb, dk_t, dv_t, k_last, v_last), _ = lax.scan(
            hop, init, jnp.arange(n - 1))
        dqa, dqb, dk_t, dv_t = fold(dqa, dqb, dk_t, dv_t, k_last, v_last,
                                    (my_index - (n - 1)) % n)
        # After n-1 rotations the traveling dk/dv sit one hop short of home.
        return jnp.concatenate([dqa, dqb], axis=1), rot(dk_t), rot(dv_t)

    op.defvjp(fwd, bwd)
    return op


def zigzag_ring_flash_attention(mesh: Mesh, q: jax.Array, k: jax.Array,
                                v: jax.Array, *,
                                axis_name: str = "seq",
                                window: int = 0) -> jax.Array:
    """Zig-zag ring-of-flash: the full long-context causal training composition —
    load-balanced zig-zag scheduling across chips (uniform per-hop work), Pallas
    flash kernels within every live chunk pair (no score matrix anywhere), and a
    custom VJP so it TRAINS. Causal-only, like the schedule itself.

    Requires ``S % (2·shards·BLOCK) == 0`` (each zig-zag chunk must be flash-block
    aligned). Drop-in for ``ring_flash_attention(..., causal=True)``; pinned to the
    dense causal oracle — forward and gradients — in ``tests/test_ring_attention.py``.

    ``window=W`` (r4) selects the WINDOWED variant: chunk-pair offsets ride into
    the flash kernels as traced SMEM scalars (``q_offset_dyn``) and band-dead
    pairs skip — see ``_make_zigzag_flash_op``.
    """
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import (
        pallas_attention as pa,
    )

    n = mesh.shape[axis_name]
    b, s, h, d = q.shape
    if s % (2 * n * pa.BLOCK):
        raise ValueError(
            f"zigzag ring-of-flash needs sequence length divisible by "
            f"2·shards·BLOCK = 2·{n}·{pa.BLOCK}, got {s}")
    if window < 0:
        raise ValueError(f"window must be >= 0 (0 = full attention), got {window}")
    c = s // (2 * n)
    order, inv = _zigzag_order(n)
    spec = _qkv_spec(mesh, q.shape, axis_name)
    op = _make_zigzag_flash_op(axis_name, n, int(window))

    def to_zigzag(x):
        return x.reshape(b, 2 * n, c, h, d)[:, jnp.asarray(order)].reshape(
            b, s, h, d)

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
             check_vma=False)
    def _ring(ql, kl, vl):
        return _apply_in_kernel_layout(op, ql, kl, vl)

    out = _ring(to_zigzag(q), to_zigzag(k), to_zigzag(v))
    return out.reshape(b, 2 * n, c, h, d)[:, jnp.asarray(inv)].reshape(b, s, h, d)
