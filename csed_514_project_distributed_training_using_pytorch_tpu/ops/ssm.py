"""The Mamba-2 recurrence as a chunked scan (SSD), forward and backward, in Pallas.

Per head (``P`` channels, a state of ``P x N``) and token ``t``::

    S_t = exp(a_t) · S_{t-1} + dt_t · x_t ⊗ b_t        S_0 = 0
    y_t = S_t · c_t

``a = dt · A`` is the step's log-decay (``<= 0``), ``b`` and ``c`` belong to the head's
group (``H / G`` heads read one group's). ``+ D · x``, the convolution, softplus, the
gate and the grouped norm are the caller's (``models/hybrid_lm.py``).

The scan walks a sequence in chunks of ``Q`` tokens (``chunk``, 128 as published), the
chunks of one (batch, group) along a sequential grid axis with the heads' states
carried in VMEM, as the flash kernels carry their statistics. Inside a chunk, with
``cum`` the running sum of ``a`` from the chunk's start::

    y     = (c bᵀ ⊙ L) (dt ⊙ x) + exp(cum) ⊙ (c · S_in)    L_ij = exp(cum_i − cum_j), i >= j
    S_out = exp(cum_Q) · S_in + ((exp(cum_Q − cum) ⊙ dt ⊙ x)ᵀ b)

``c bᵀ`` is computed once a group and chunk; decays, masks and the state are float32,
the products run on the MXU in the model's dtype. ``ssd_fwd`` also writes the state
that entered every chunk (``[B, S/Q, H, P, N]`` float32: 64 KiB a head and chunk at the
published sizes); ``ssd_bwd`` walks the chunks in reverse carrying the state's
gradient, recomputes ``L`` and ``y`` from those, and returns the gradients of ``x``,
``dt``, ``a``, ``b``, ``c``. The gradient of ``a`` comes from one identity: with ``W = dM ⊙
M`` (``M = c bᵀ ⊙ L``), ``d cum_i = Σ_j W_ij − Σ_j W_ji`` and the other paths through
``cum`` fold into ``d cum = rowsum(dy ⊙ y) − rowsum(xd ⊙ d xd)``, plus ``<dS_out, S_out>`` on
the chunk's last token; ``d a`` is its reverse running sum inside the chunk. Both
rowsums take ``xd = dt ⊙ x`` as the products took it, rounded to the model's dtype: the
diagonal ``W_ii`` stands in both and cancels only then.

A sequence whose length is not a multiple of the chunk is padded at its end with
steps that decay nothing and add nothing (``a = dt = 0``), and the result sliced.
Per-head scalars of a token (``dt``, ``cum``) enter the kernels twice over: as columns
``[Q, heads]`` and, for ``L``, as rows ``[heads, Q]``, so that no kernel transposes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128


def _interpret() -> bool:
    """Compiled on TPU; interpret mode on CPU (the test platform)."""
    return jax.default_backend() != "tpu"


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


NN, NT, TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _decays(cumc_ref, cumr_ref, tot_ref, h: int, causal):
    """Of head ``h`` in this chunk: ``cum`` as a column, ``L``, ``exp(cum_Q − cum)`` as a
    column, and ``exp(cum_Q)`` (a scalar)."""
    heads = cumr_ref.shape[0]
    cc = cumc_ref[:, h:h + 1]
    lower = jnp.exp(jnp.where(causal, cc - cumr_ref[h:h + 1, :], -jnp.inf))
    return cc, lower, jnp.exp(tot_ref[0, 0, h] - cc), tot_ref[0, 0, heads + h]


def _causal(q: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _fwd_kernel(x_ref, dt_ref, cumc_ref, cumr_ref, tot_ref, b_ref, c_ref,
                y_ref, entered_ref, state):
    """One chunk of one (batch, group): every head's output, and the states as they
    entered (what the backward pass starts from)."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    b, c = b_ref[...], c_ref[...]
    dtype = b.dtype
    scores = _dot(c, b, NT)                                   # [Q, Q], once a group
    causal = _causal(scores.shape[0])
    for h in range(x_ref.shape[0]):
        cc, lower, to_end, total = _decays(cumc_ref, cumr_ref, tot_ref, h, causal)
        xd = x_ref[h].astype(jnp.float32) * dt_ref[:, h:h + 1]          # [Q, P]
        entered = state[h]                                              # [P, N]
        entered_ref[h] = entered
        y = _dot((scores * lower).astype(dtype), xd.astype(dtype), NN) \
            + jnp.exp(cc) * _dot(c, entered.astype(dtype), NT)
        y_ref[h] = y.astype(y_ref.dtype)
        state[h] = total * entered + _dot((to_end * xd).astype(dtype), b, TN)


def _place(acc, column, h: int):
    """``acc [rows, heads]`` with column ``h`` set to ``column [rows, 1]``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
    return jnp.where(lane == h, column, acc)


def _bwd_kernel(x_ref, dt_ref, cumc_ref, cumr_ref, tot_ref, b_ref, c_ref, entered_ref,
                dy_ref, dx_ref, ddt_ref, dcum_ref, edge_ref, db_ref, dc_ref, dstate):
    """The same chunk's backward, the chunks taken last to first; ``dstate``: the
    gradient of the state this chunk hands on. ``edge``: ``<dS_in, S_in>`` by state row,
    which the chunk BEFORE this one adds to ``d cum`` of its last token."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    b, c = b_ref[...], c_ref[...]
    dtype = b.dtype
    scores = _dot(c, b, NT)
    q = scores.shape[0]
    causal = _causal(q)
    dscores = jnp.zeros((q, q), jnp.float32)
    db = jnp.zeros(b.shape, jnp.float32)
    dc = jnp.zeros(c.shape, jnp.float32)
    ddt, dcum, edge = (jnp.zeros(ref.shape, jnp.float32)
                       for ref in (ddt_ref, dcum_ref, edge_ref))
    for h in range(x_ref.shape[0]):
        cc, lower, to_end, total = _decays(cumc_ref, cumr_ref, tot_ref, h, causal)
        x, dt = x_ref[h].astype(jnp.float32), dt_ref[:, h:h + 1]
        xd = x * dt
        xd_lo = xd.astype(dtype)        # what the products take: rounded once, here
        entered, dout = entered_ref[h], dstate[h]                       # [P, N]
        mixed = (scores * lower).astype(dtype)
        from_start = jnp.exp(cc)
        dy = dy_ref[h]
        y = _dot(mixed, xd_lo, NN) + from_start * _dot(c, entered.astype(dtype), NT)
        dxd = _dot(mixed, dy, TN) + to_end * _dot(b, dout.astype(dtype), NT)
        dscores += _dot(dy, xd_lo, NT) * lower
        dy_in = (from_start * dy.astype(jnp.float32)).astype(dtype)
        dc += _dot(dy_in, entered.astype(dtype), NN)
        db += _dot((to_end * xd).astype(dtype), dout.astype(dtype), NN)
        dentered = total * dout + _dot(dy_in, c, TN)
        dstate[h] = dentered
        dx_ref[h] = (dxd * dt).astype(dx_ref.dtype)
        ddt = _place(ddt, jnp.sum(dxd * x, axis=1, keepdims=True), h)
        # the identity's diagonal terms cancel only if both sides saw the same ``xd``:
        # the rounded one ``y`` was made from
        dcum = _place(dcum, jnp.sum(dy.astype(jnp.float32) * y
                                    - xd_lo.astype(jnp.float32) * dxd, axis=1,
                                    keepdims=True), h)
        edge = _place(edge, jnp.sum(dentered * entered, axis=1, keepdims=True), h)
    ddt_ref[...], dcum_ref[...], edge_ref[...] = ddt, dcum, edge
    dscores = dscores.astype(dtype)
    dc_ref[...] = (dc + _dot(dscores, b, NN)).astype(dc_ref.dtype)
    db_ref[...] = (db + _dot(dscores, c, TN)).astype(db_ref.dtype)


def _specs(heads: int, q: int, p: int, n: int, chunks: int, groups: int, at):
    """Block specs of a kernel's operands; ``at(c)`` is the chunk that step ``c`` of
    the sequential grid axis works on (the backward pass walks them in reverse)."""
    def spec(block, index):
        return pl.BlockSpec(block, lambda b, g, c: index(b, g, at(c)))

    return {
        "x": spec((None, heads, q, p), lambda b, g, c: (b, g, c, 0)),
        "col": spec((None, None, None, q, heads), lambda b, g, c: (b, c, g, 0, 0)),
        "row": spec((None, None, None, heads, q), lambda b, g, c: (b, c, g, 0, 0)),
        "tot": pl.BlockSpec((1, 1, 2 * heads),
                            lambda b, g, c: ((b * chunks + at(c)) * groups + g, 0, 0),
                            memory_space=pltpu.SMEM),
        "bc": spec((None, None, q, n), lambda b, g, c: (b, g, c, 0)),
        "state": spec((None, None, heads, p, n), lambda b, g, c: (b, c, g, 0, 0)),
        "edge": spec((None, None, None, p, heads), lambda b, g, c: (b, c, g, 0, 0)),
    }


def _layout(x, dt, a, b, c, q: int):
    """The kernels' operands from ``x [B, S, H, P]``, ``dt``, ``a`` ``[B, S, H]``, ``b``,
    ``c`` ``[B, S, G, N]`` (``S`` a multiple of ``q``)."""
    bsz, s, h, _ = x.shape
    g = b.shape[2]
    nc, hg = s // q, h // g
    by_chunk = lambda v: v.astype(jnp.float32).reshape(bsz, nc, q, g, hg).transpose(0, 1, 3, 2, 4)
    cum = jnp.cumsum(by_chunk(a), axis=3)                     # [B, nc, G, Q, Hg]
    last = cum[:, :, :, -1]                                   # [B, nc, G, Hg]
    tot = jnp.concatenate([last, jnp.exp(last)], axis=-1).reshape(bsz * nc * g, 1, 2 * hg)
    heads_first = lambda v: v.transpose(0, 2, 1, 3)
    return (heads_first(x), by_chunk(dt), cum, cum.transpose(0, 1, 2, 4, 3), tot,
            heads_first(b), heads_first(c))


def _scan_fwd(x, dt, a, b, c, q: int):
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    nc, hg = s // q, h // g
    sp = _specs(hg, q, p, n, nc, g, lambda ci: ci)
    y, entered = pl.pallas_call(
        _fwd_kernel, name="ssd_fwd", interpret=_interpret(), grid=(bsz, g, nc),
        in_specs=[sp["x"], sp["col"], sp["col"], sp["row"], sp["tot"], sp["bc"], sp["bc"]],
        out_specs=[sp["x"], sp["state"]],
        out_shape=[jax.ShapeDtypeStruct((bsz, h, s, p), x.dtype),
                   jax.ShapeDtypeStruct((bsz, nc, h, p, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hg, p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*_layout(x, dt, a, b, c, q))
    return y.transpose(0, 2, 1, 3), entered


def _scan_bwd(x, dt, a, b, c, entered, dy, q: int):
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    nc, hg = s // q, h // g
    sp = _specs(hg, q, p, n, nc, g, lambda ci: nc - 1 - ci)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    dx, ddt, dcum, edge, db, dc = pl.pallas_call(
        _bwd_kernel, name="ssd_bwd", interpret=_interpret(), grid=(bsz, g, nc),
        in_specs=[sp["x"], sp["col"], sp["col"], sp["row"], sp["tot"], sp["bc"], sp["bc"],
                  sp["state"], sp["x"]],
        out_specs=[sp["x"], sp["col"], sp["col"], sp["edge"], sp["bc"], sp["bc"]],
        out_shape=[jax.ShapeDtypeStruct((bsz, h, s, p), x.dtype),
                   f32(bsz, nc, g, q, hg), f32(bsz, nc, g, q, hg), f32(bsz, nc, g, p, hg),
                   jax.ShapeDtypeStruct((bsz, g, s, n), b.dtype),
                   jax.ShapeDtypeStruct((bsz, g, s, n), c.dtype)],
        scratch_shapes=[pltpu.VMEM((hg, p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*_layout(x, dt, a, b, c, q), entered, dy.astype(x.dtype).transpose(0, 2, 1, 3))
    # <dS_in, S_in> of chunk k + 1 is <dS_out, S_out> of chunk k: d cum of its last token
    edge = jnp.pad(edge.sum(axis=3)[:, 1:], ((0, 0), (0, 1), (0, 0), (0, 0)))
    dcum = dcum.at[:, :, :, -1].add(edge)
    da = jnp.flip(jnp.cumsum(jnp.flip(dcum, axis=3), axis=3), axis=3)
    tokens = lambda v: v.transpose(0, 1, 3, 2, 4).reshape(bsz, s, h)
    back = lambda v: v.transpose(0, 2, 1, 3)
    return back(dx), tokens(ddt), tokens(da), back(db), back(dc)


@functools.lru_cache(maxsize=None)
def _make_op(q: int):
    # Jitted halves behind a cached factory, as ``pallas_attention._make_op``: every
    # Mamba-2 layer of a model calls the same two functions, lowered once a program.
    forward = jax.jit(functools.partial(_scan_fwd, q=q))
    backward = jax.jit(functools.partial(_scan_bwd, q=q))

    @jax.custom_vjp
    def op(x, dt, a, b, c):
        return forward(x, dt, a, b, c)[0]

    def fwd(x, dt, a, b, c):
        # Named as the VJP's residuals: a caller's ``jax.checkpoint`` whose policy
        # keeps these names does not run ``ssd_fwd`` again in its backward pass.
        y, entered = forward(x, dt, a, b, c)
        y, entered = checkpoint_name(y, "ssd_out"), checkpoint_name(entered, "ssd_state")
        return y, (x, dt, a, b, c, entered)

    def bwd(residuals, dy):
        x, dt, a, b, c, entered = residuals
        dx, ddt, da, db, dc = backward(x, dt, a, b, c, entered, dy)
        return dx, ddt.astype(dt.dtype), da.astype(a.dtype), db, dc

    op.defvjp(fwd, bwd)
    return op


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array, *,
             chunk: int = CHUNK) -> jax.Array:
    """``y [B, S, H, P]`` of the recurrence above. ``x [B, S, H, P]`` and ``b``, ``c``
    ``[B, S, G, N]`` in the model's dtype (``G`` divides ``H``); ``dt`` (the step) and
    ``a`` (``dt · A``, the step's log-decay) ``[B, S, H]`` float32. Differentiable in
    all five. Any ``S``: the tail of a sequence is padded to a whole chunk."""
    s = x.shape[1]
    if x.shape[2] % b.shape[2]:
        raise ValueError(f"{b.shape[2]} groups do not divide {x.shape[2]} heads")
    short = -s % chunk
    if short:
        x, dt, a, b, c = (jnp.pad(v, ((0, 0), (0, short)) + ((0, 0),) * (v.ndim - 2))
                          for v in (x, dt, a, b, c))
    with jax.named_scope("ssd"):
        y = _make_op(chunk)(x, dt.astype(jnp.float32), a.astype(jnp.float32),
                            b.astype(x.dtype), c.astype(x.dtype))
    return y[:, :s] if short else y


def scan_plan(*, heads: int, groups: int, head_dim: int, state: int, seq_len: int,
              chunk: int = CHUNK, kept: tuple[str, ...] = ()) -> dict:
    """The ``compile`` event's ``ssm`` field: what a state-space layer asks of a step."""
    chunks = -(-seq_len // chunk)
    return {"heads": heads, "groups": groups, "head_dim": head_dim, "state": state,
            "chunk": chunk, "chunks_per_sequence": chunks,
            "state_bytes_per_sequence": chunks * heads * head_dim * state * 4,
            "kept": [name for name in ("ssd_out", "ssd_state") if name in kept]}
