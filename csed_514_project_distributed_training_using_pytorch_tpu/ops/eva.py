"""EVA attention: exact keys inside the query's own window, one learned summary a chunk
before it, one softmax over both (Zheng et al., "Efficient Attention via Control
Variates", arXiv:2302.04542, with learned per-head vectors in place of sampled ones, as
the EvaByte release uses it).

One head of width ``d``, scores scaled by ``d^-½``, window ``W``, chunk ``c``, ``M = W/c``
summaries a window; ``k`` arrives rotated:

    summaries   chunk j holds tokens c·j … c·j + c − 1;  a_jm = softmax_m(φ · k_m) over the
                chunk;  k̃_j = Σ_m a_jm k_m + μ,  ṽ_j = Σ_m a_jm v_m   (``chunk_summaries``,
                plain ``jax.numpy`` in float32: 6 d FLOPs a token)
    attention   query t of window w = ⌊t/W⌋ sees the keys m of its window with m ≤ t and
                the summaries j < M·w (every chunk of every earlier window, none of its
                own):  o_t = (Σ_m e^{s q·k_m} v_m + Σ_j e^{s q·k̃_j} ṽ_j) / Z_t,  Z_t the sum
                of both kinds of weight; scores and statistics in float32

On the chip the two halves of the softmax are computed apart and joined by its
statistics. The local half is causal attention over ``[B·H·S/W, W, d]``, which is what
the flash kernels compute (``ops/pallas_attention.py``: ``flash_fwd`` hands out each
row's log-sum-exp). The remote half is a second score block ``[S, S/c]`` whose mask is
by window, so a block of it is whole, cut at one column or dead, never triangular:
``eva_fwd`` walks the summaries a query block sees with the online softmax. The two
outputs are joined by ``lse = logaddexp(lse_local, lse_remote)``. The backward pass takes
no cotangent on a statistic: the op has one rule (``_make_op``) whose backward hands the
joint ``lse`` and ``Δ = rowsum(do ∘ o)`` to ``flash_backward_blocks`` (written for the ring
schedules: the statistics are the whole row's, the keys a part of it; one fused kernel
at a window's size, ``flash_dkv`` on a trace) and to ``eva_dq`` / ``eva_dkv``, the
two-kernel recompute formulation over the summaries. The summaries'
own gradient (φ, μ, and k, v through the pooling) is autodiff's, outside the kernels.

A window that is no multiple of 128 (the CPU tests' sizes) takes ``dense_attention``,
the same mathematics with both score blocks materialised.
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
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops.pallas_attention import (
    BLOCK,
    _flash_forward,
    _interpret,
    auto_block,
    flash_backward_blocks,
)

SUMMARY_BLOCK = 512     # summaries a kernel step holds: a [1024, 512] float32 score tile


def chunk_summaries(k, v, phi, mu, *, chunk: int):
    """``k``, ``v`` ``[N, S, d]`` (a row of N is one batch entry's head; ``k`` rotated),
    ``phi``, ``mu`` ``[N, d]`` -> ``(k̃, ṽ) [N, S/chunk, d]`` in the operands' dtype,
    pooled in float32."""
    n, s, d = k.shape
    f32 = jnp.float32
    kc, vc = (x.astype(f32).reshape(n, s // chunk, chunk, d) for x in (k, v))
    a = jax.nn.softmax(jnp.einsum("njmd,nd->njm", kc, phi.astype(f32)), axis=-1)
    k_sum = jnp.einsum("njm,njmd->njd", a, kc) + mu.astype(f32)[:, None, :]
    v_sum = jnp.einsum("njm,njmd->njd", a, vc)
    return k_sum.astype(k.dtype), v_sum.astype(v.dtype)


def dense_attention(q, k, v, ks, vs, *, window: int, chunk: int):
    """The attention above with both score blocks whole: ``q``, ``k``, ``v`` ``[N, S, d]``,
    ``ks``, ``vs`` ``[N, S/chunk, d]`` -> ``[N, S, d]``. Float32 throughout."""
    n, s, d = q.shape
    f32 = jnp.float32
    q, k, v, ks, vs = (x.astype(f32) for x in (q, k, v, ks, vs))
    scale = d ** -0.5
    t, j = jnp.arange(s), jnp.arange(s // chunk)
    near = (t[:, None] // window == t[None, :] // window) & (t[:, None] >= t[None, :])
    far = j[None, :] < (window // chunk) * (t[:, None] // window)
    scores = jnp.concatenate(
        [jnp.where(near, jnp.einsum("nqd,nkd->nqk", q, k) * scale, NEG),
         jnp.where(far, jnp.einsum("nqd,njd->nqj", q, ks) * scale, NEG)], axis=-1)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("nqk,nkd->nqd", weights, jnp.concatenate([v, vs], axis=1))


# ==========================================================================================
# The remote half: queries [N, S, d] against summaries [N, S/c, d], masked by window
# ==========================================================================================


def _seen(iq, bq: int, window: int, per_window: int):
    """How many summaries the queries of block ``iq`` see: ``M · ⌊t/W⌋``, one number a
    block because a block lies inside a window."""
    return (iq * bq // window) * per_window


def _walk(body, j, bk: int, seen):
    """``body(masked)`` for summary block ``j`` of a row of blocks that sees the first
    ``seen`` summaries: whole, cut at a column, or not at all."""
    live = j * bk < seen
    whole = (j + 1) * bk <= seen
    pl.when(live & whole)(lambda: body(False))
    pl.when(live & ~whole)(lambda: body(True))


def _scores(q, ks, j, seen, masked: bool, scale: float):
    s = jax.lax.dot_general(q, ks, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if not masked:
        return s, None
    visible = j * ks.shape[0] + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < seen
    return jnp.where(visible, s, NEG), visible


def _fwd_kernel(q_ref, ks_ref, vs_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                scale, window, per_window, num_steps):
    iq, j = pl.program_id(1), pl.program_id(2)
    bq, bk = q_ref.shape[0], ks_ref.shape[0]
    seen = _seen(iq, bq, window, per_window)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    def body(masked: bool):
        s, visible = _scores(q_ref[:], ks_ref[:], j, seen, masked, scale)
        m = m_ref[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(visible, p, 0.0)
        corr = jnp.exp(m - m_new)
        vs = vs_ref[:]
        acc_ref[:] = acc_ref[:] * corr + jnp.dot(p.astype(vs.dtype), vs,
                                                 preferred_element_type=jnp.float32)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_new

    _walk(body, j, bk, seen)

    @pl.when(j == num_steps - 1)
    def _():
        # a row of the first window sees no summary: l = 0, o = 0 and lse = NEG, which
        # the join reads as a weight of zero
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[:] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[:] = jnp.transpose(m_ref[:] + jnp.log(l_safe)).reshape(1, 1, bq)


def _dq_kernel(q_ref, ks_ref, vs_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref, *,
               scale, window, per_window, num_steps):
    iq, j = pl.program_id(1), pl.program_id(2)
    bq, bk = q_ref.shape[0], ks_ref.shape[0]
    seen = _seen(iq, bq, window, per_window)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def body(masked: bool):
        ks, vs, do = ks_ref[:], vs_ref[:], do_ref[:]
        s, visible = _scores(q_ref[:], ks, j, seen, masked, scale)
        p = jnp.exp(s - jnp.transpose(lse_ref[0]))
        if masked:
            p = jnp.where(visible, p, 0.0)
        dp = jax.lax.dot_general(do, vs, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.transpose(delta_ref[0]))
        acc_ref[:] = acc_ref[:] + jnp.dot(ds.astype(ks.dtype), ks,
                                          preferred_element_type=jnp.float32)

    _walk(body, j, bk, seen)

    @pl.when(j == num_steps - 1)
    def _():
        dq_ref[:] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, ks_ref, vs_ref, do_ref, lse_ref, delta_ref, dks_ref, dvs_ref,
                dk_acc_ref, dv_acc_ref, *, scale, window, per_window, num_steps):
    j, iq = pl.program_id(1), pl.program_id(2)      # the step axis walks query blocks
    bq, bk = q_ref.shape[0], ks_ref.shape[0]
    seen = _seen(iq, bq, window, per_window)

    @pl.when(iq == 0)
    def _():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    def body(masked: bool):
        q, do = q_ref[:], do_ref[:]
        s, visible = _scores(q, ks_ref[:], j, seen, masked, scale)
        p = jnp.exp(s - jnp.transpose(lse_ref[0]))
        if masked:
            p = jnp.where(visible, p, 0.0)
        dv_acc_ref[:] = dv_acc_ref[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, vs_ref[:], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.transpose(delta_ref[0]))
        dk_acc_ref[:] = dk_acc_ref[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _walk(body, j, bk, seen)

    @pl.when(iq == num_steps - 1)
    def _():
        dks_ref[:] = (dk_acc_ref[:] * scale).astype(dks_ref.dtype)
        dvs_ref[:] = dv_acc_ref[:].astype(dvs_ref.dtype)


def _blocks(s: int, window: int, chunk: int) -> tuple[int, int]:
    """``(query block, summary block)``: the local half's flash block, which divides the
    window, and the largest power of two up to ``SUMMARY_BLOCK`` that divides the
    sequence's summaries (all of them where none does)."""
    bq = auto_block(window)
    count = s // chunk
    bk = next((b for b in (SUMMARY_BLOCK, 256, 128) if count % b == 0), count)
    return bq, bk


def _specs(bq: int, bk: int, d: int, window: int, per_window: int, nq: int):
    """The operands' blocks under grid ``(row, i, j)``. A query-side operand is held
    across its walk; a walked operand's dead steps ask for the nearest live block again,
    which costs no copy (``pallas_attention._elided_key_idx``)."""
    vmem = dict(memory_space=pltpu.VMEM)
    # forward and dq: i a query block, j the summary block walked
    last_seen = lambda i: jnp.maximum((_seen(i, bq, window, per_window) - 1) // bk, 0)
    # dkv: i a summary block, j the query block walked; the first that sees summary
    # i·bk is the first of window ⌊i·bk / M⌋ + 1
    first_seeing = lambda i: jnp.minimum((i * bk // per_window + 1) * (window // bq), nq - 1)
    return {
        "q": pl.BlockSpec((None, bq, d), lambda n, i, j: (n, i, 0), **vmem),
        "stat": pl.BlockSpec((None, 1, 1, bq), lambda n, i, j: (n, i, 0, 0), **vmem),
        "walked_s": pl.BlockSpec(
            (None, bk, d), lambda n, i, j: (n, jnp.minimum(j, last_seen(i)), 0), **vmem),
        "s": pl.BlockSpec((None, bk, d), lambda n, i, j: (n, i, 0), **vmem),
        "walked_q": pl.BlockSpec(
            (None, bq, d), lambda n, i, j: (n, jnp.maximum(j, first_seeing(i)), 0), **vmem),
        "walked_stat": pl.BlockSpec(
            (None, 1, 1, bq), lambda n, i, j: (n, jnp.maximum(j, first_seeing(i)), 0, 0),
            **vmem),
    }


def _remote_forward(q, ks, vs, *, window: int, chunk: int):
    """``(o [N, S, d], lse [N, S/bq, 1, bq])`` of the summaries alone."""
    n, s, d = q.shape
    bq, bk = _blocks(s, window, chunk)
    nq, steps, per_window = s // bq, ks.shape[1] // bk, window // chunk
    sp = _specs(bq, bk, d, window, per_window, nq)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=d ** -0.5, window=window,
                          per_window=per_window, num_steps=steps),
        name="eva_fwd", interpret=_interpret(), grid=(n, nq, steps),
        in_specs=[sp["q"], sp["walked_s"], sp["walked_s"]],
        out_specs=[sp["q"], sp["stat"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((n, nq, 1, bq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32), pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
    )(q, ks, vs)


def _remote_backward(q, ks, vs, do, lse, delta, *, window: int, chunk: int):
    """``(dq, dk̃, dṽ)`` of the summaries' part of the row, given the whole row's
    statistics."""
    n, s, d = q.shape
    bq, bk = _blocks(s, window, chunk)
    nq, nk, per_window = s // bq, ks.shape[1] // bk, window // chunk
    sp = _specs(bq, bk, d, window, per_window, nq)
    kw = dict(scale=d ** -0.5, window=window, per_window=per_window)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, num_steps=nk, **kw),
        name="eva_dq", interpret=_interpret(), grid=(n, nq, nk),
        in_specs=[sp["q"], sp["walked_s"], sp["walked_s"], sp["q"], sp["stat"], sp["stat"]],
        out_specs=[sp["q"]], out_shape=[like(q)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
    )(q, ks, vs, do, lse, delta)[0]
    dks, dvs = pl.pallas_call(
        functools.partial(_dkv_kernel, num_steps=nq, **kw),
        name="eva_dkv", interpret=_interpret(), grid=(n, nk, nq),
        in_specs=[sp["walked_q"], sp["s"], sp["s"], sp["walked_q"], sp["walked_stat"],
                  sp["walked_stat"]],
        out_specs=[sp["s"], sp["s"]], out_shape=[like(ks), like(vs)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32)] * 2,
    )(q, ks, vs, do, lse, delta)
    return dq, dks, dvs


# ==========================================================================================
# The op: local half by the flash kernels, remote half above, one differentiation rule
# ==========================================================================================


def _forward(q, k, v, ks, vs, *, window: int, chunk: int):
    """``(o [N, S, d], lse [N, S/bq, 1, bq])`` of the whole row."""
    n, s, d = q.shape
    bq, _ = _blocks(s, window, chunk)
    windows = lambda x: x.reshape(n * (s // window), window, d)
    o, lse = _flash_forward(windows(q), windows(k), windows(v), causal=True, block=bq)
    o, lse = o.reshape(n, s, d), lse.reshape(n, s // bq, 1, bq)
    if s == window:         # one window: no summary is seen
        return o, lse
    far, far_lse = _remote_forward(q, ks, vs, window=window, chunk=chunk)
    joint = jnp.logaddexp(lse, far_lse)
    share = lambda part: jnp.exp(part - joint).reshape(n, s, 1)
    out = share(lse) * o.astype(jnp.float32) + share(far_lse) * far.astype(jnp.float32)
    return out.astype(q.dtype), joint


def _backward(residuals, do, *, window: int, chunk: int):
    q, k, v, ks, vs, out, lse = residuals
    n, s, d = q.shape
    bq, _ = _blocks(s, window, chunk)
    do = do.astype(q.dtype)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1) \
        .reshape(n, s // bq, 1, bq)
    windows = lambda x: x.reshape(n * (s // window), window, d)
    stat = lambda x: x.reshape(n * (s // window), window // bq, 1, bq)
    dq, dk, dv = flash_backward_blocks(*map(windows, (q, k, v, do)), stat(lse),
                                       stat(delta), causal=True, block=bq)
    dq, dk, dv = (x.reshape(n, s, d) for x in (dq, dk, dv))
    if s == window:
        return dq, dk, dv, jnp.zeros_like(ks), jnp.zeros_like(vs)
    far_dq, dks, dvs = _remote_backward(q, ks, vs, do, lse, delta,
                                        window=window, chunk=chunk)
    return dq + far_dq, dk, dv, dks, dvs


@functools.lru_cache(maxsize=None)
def _make_op(window: int, chunk: int):
    # Jitted halves behind a cached factory, as the flash kernels': every layer calls
    # the same two functions, lowered once a program.
    kw = dict(window=window, chunk=chunk)
    forward = jax.jit(functools.partial(_forward, **kw))
    backward = jax.jit(functools.partial(_backward, **kw))

    @jax.custom_vjp
    def op(q, k, v, ks, vs):
        return forward(q, k, v, ks, vs)[0]

    def fwd(q, k, v, ks, vs):
        # Named as the rule's residuals: a ``jax.checkpoint`` whose policy keeps these
        # names does not run the forward kernels again in its backward pass.
        out, lse = forward(q, k, v, ks, vs)
        out, lse = checkpoint_name(out, "eva_out"), checkpoint_name(lse, "eva_lse")
        return out, (q, k, v, ks, vs, out, lse)

    op.defvjp(fwd, backward)
    return op


def kernel_attention(q, k, v, ks, vs, *, window: int, chunk: int):
    """``dense_attention`` by the kernels, in the operands' dtype; differentiable in all
    five. ``window`` a multiple of 128 that divides ``S``."""
    return _make_op(int(window), int(chunk))(q, k, v, ks, vs)


def uses_kernels(window: int) -> bool:
    """Whether ``eva_attention`` runs the kernels: a window of whole 128-row blocks."""
    return window % BLOCK == 0


def eva_attention(q, k, v, phi, mu, *, window: int, chunk: int):
    """``q``, ``k``, ``v`` ``[B, S, H, d]`` (q and k rotated), ``phi``, ``mu`` ``[H, d]`` ->
    ``[B, S, H, d]``: the chunk summaries and the attention over both kinds of key, each
    under a scope of its own. The kernels take operands packed ``[B·H, S, d]``, as the
    flash kernels do."""
    b, s, h, d = q.shape
    if window % chunk or s % window:
        raise ValueError(f"a window of {window} is not whole chunks of {chunk}, or a "
                         f"sequence of {s} not whole windows")
    pack = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, s, d)
    q, k, v = pack(q), pack(k), pack(v)
    with jax.named_scope("eva/summaries"):
        ks, vs = chunk_summaries(k, v, jnp.tile(phi, (b, 1)), jnp.tile(mu, (b, 1)),
                                 chunk=chunk)
    with jax.named_scope("eva/attention"):
        core = kernel_attention if uses_kernels(window) else dense_attention
        out = core(q, k, v, ks, vs, window=window, chunk=chunk).astype(q.dtype)
    return jnp.transpose(out.reshape(b, h, s, d), (0, 2, 1, 3))


def attention_plan(*, heads: int, head_dim: int, seq_len: int, window: int, chunk: int,
                   kept: tuple[str, ...] = ()) -> dict:
    """The ``compile`` event's ``eva`` field: what an EVA layer asks of a step."""
    kernels = uses_kernels(window)
    bq, bk = _blocks(seq_len, window, chunk) if kernels else (None, None)
    return {"impl": "kernels" if kernels else "dense", "heads": heads,
            "head_dim": head_dim, "window": window, "chunk": chunk,
            "windows_per_sequence": seq_len // window,
            "summaries_per_window": window // chunk,
            "summaries_per_sequence": seq_len // chunk,
            "query_block": bq, "summary_block": bk,
            "kept": [name for name in ("eva_out", "eva_lse") if name in kept]}
