"""Dropless sparse expert layer, as expert parallelism needs it: this chip's share.

The layer is told which experts it holds (``held``: a contiguous range of expert
ids). It routes every token over ALL experts, keeps the assignments that land on a
held expert, and returns the held experts' part of the layer's result: what the
other chips' experts would add is theirs to compute, and on one chip there is no
exchange. No token is dropped and there is no capacity: every assignment to a held
expert is computed, at any imbalance.

    route      s = sigmoid(u · W_r) in float32; the experts are the top-k of s + b
               (b enters the selection only); their weights are s_e / (Σ s_e + 1e-6)
    sort       the held assignments, grouped by expert; each expert's rows start on a
               row tile, so a tile belongs to one expert; rows of the token array are
               gathered into that order
    experts    the grouped product, three Pallas kernels whose grid is the number of
               row tiles that ARRIVED (a scalar the sort hands them), not the static
               bound of k·T rows: ``moe_ffn_fwd`` (W2 · (silu(W1 x) ⊙ W3 x) per tile,
               the hidden tile never leaving VMEM), ``moe_ffn_bwd`` (the same tile's
               input and routing-weight gradients) and ``moe_ffn_dw`` (the three
               weight gradients, accumulated over an expert's tiles)
    combine    each token gathers its held assignments' rows, weighted

Only gathers cross between token order and expert order, forward and backward (a
TPU scatter-add walks its rows one by one): the backward of the combine is the
gather of the sort, and the other way round. Buffers in expert order are sized for
the bound (``k·T`` rows and a tile a held expert); what is computed and what the
kernels read and write follows the rows that arrived.

Expert weights are three leaves a layer, column-blocked by held expert: ``w1``,
``w3`` ``[d, n_held·f]`` and ``w2`` ``[f, n_held·d]``, so that a kernel's block index
is the expert and a leaf's fan-in is its first axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 256          # rows of one expert a kernel step multiplies
HIDDEN_TILE = 512       # columns of the hidden width a weight-gradient step owns
VMEM_LIMIT = 100 * 2 ** 20   # the resident expert's three bf16 matrices, twice


def _interpret() -> bool:
    """Compiled on TPU; interpret mode on CPU (the test platform)."""
    return jax.default_backend() != "tpu"


def route(u: jax.Array, router_kernel: jax.Array, select_bias: jax.Array, *,
          top_k: int, scaling: float = 1.0) -> tuple[jax.Array, jax.Array]:
    """``u [T, d]`` -> ``(weights [T, k] float32, experts [T, k] int32)`` over all the
    router's experts. Matmul (at ``highest``: one bf16 pass would move near-tied
    selections), sigmoid and top-k in float32; ``select_bias`` moves the selection
    and not the weights, and gets no gradient."""
    with jax.named_scope("moe/route"):
        scores = jax.nn.sigmoid(jnp.dot(
            u.astype(jnp.float32), router_kernel.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, experts = jax.lax.top_k(
            scores + jax.lax.stop_gradient(select_bias.astype(jnp.float32)), top_k)
        picked = jnp.take_along_axis(scores, experts, axis=-1)
        weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
        return weights * scaling, experts.astype(jnp.int32)


def expert_plan(tokens: int, *, top_k: int, held: tuple[int, int],
                block: int | None = None) -> dict:
    """The ``compile`` event's ``experts`` field: the held range, the static bound
    on rows (every token sending all of its ``top_k`` rows here), the rows of the
    expert-order buffers (the bound and a tile a held expert) and the row tile."""
    tm = block or ROW_TILE
    bound = tokens * top_k
    return {"held": [held[0], held[0] + held[1]], "row_bound": bound,
            "rows_buffer": (-(-bound // tm) + held[1]) * tm, "block": tm}


def _sort(experts: jax.Array, held: tuple[int, int], tm: int) -> dict:
    """Expert order from the router's choice. ``counts [n_held]``: rows that arrived
    at each held expert. Rows of expert ``e`` sit at ``seg_start[e] + rank``, each
    segment a whole number of tiles (an empty expert keeps one, all invalid, so
    that its weight gradient is written)."""
    first, n = held
    t, k = experts.shape
    a = t * k
    local = experts.reshape(a) - first
    is_held = (local >= 0) & (local < n)
    key = jnp.where(is_held, local, n)
    running = jnp.cumsum((key[:, None] == jnp.arange(n)[None]).astype(jnp.int32), axis=0)
    counts = running[-1]
    slot = jnp.minimum(key, n - 1)
    rank = jnp.take_along_axis(running, slot[:, None], axis=1)[:, 0] - 1
    tiles = jnp.maximum(1, -(-counts // tm))
    tile_end = jnp.cumsum(tiles)
    seg_start = (tile_end - tiles) * tm
    pos = jnp.where(is_held, seg_start[slot] + rank, 0)
    order = jnp.argsort(key, stable=True)               # held first, by expert
    unaligned = jnp.cumsum(counts) - counts
    n_tiles = -(-a // tm) + n
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles), side="right"), n - 1)
    rows = jnp.arange(n_tiles * tm)
    of_row = tile_expert[rows // tm]
    offset = rows - seg_start[of_row]
    valid = (offset < counts[of_row]) & (rows // tm < tile_end[-1])
    source = order[jnp.clip(unaligned[of_row] + offset, 0, a - 1)]
    return {"counts": counts, "num_tiles": tile_end[-1].astype(jnp.int32),
            "tile_expert": tile_expert.astype(jnp.int32),
            "assignment_of_row": jnp.where(valid, source, 0).astype(jnp.int32),
            "valid_row": valid, "pos": pos.reshape(t, k).astype(jnp.int32),
            "is_held": is_held.reshape(t, k)}


# --------------------------------------------------------------------------------------
# The grouped product. ``te_ref``: the expert of each row tile (scalar prefetch).
# --------------------------------------------------------------------------------------


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _fwd_kernel(te_ref, x_ref, w1_ref, w3_ref, w2_ref, y_ref):
    del te_ref
    x = x_ref[...]
    gate = _dot(x, w1_ref[...], ((1,), (0,)))
    up = _dot(x, w3_ref[...], ((1,), (0,)))
    hidden = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
    y_ref[...] = _dot(hidden, w2_ref[...], ((1,), (0,))).astype(y_ref.dtype)


def _bwd_kernel(te_ref, x_ref, g_ref, wr_ref, w1_ref, w3_ref, w2_ref,
                dx_ref, dwr_ref, dgate_ref, dup_ref, hw_ref):
    """One row tile's backward. ``wr``: the rows' routing weights (0 on an invalid
    row, which therefore adds nothing to any weight gradient). Writes the input
    gradient, the routing weights' gradient, and what ``moe_ffn_dw`` multiplies."""
    del te_ref
    x, g, wr = x_ref[...], g_ref[...], wr_ref[...]
    gate = _dot(x, w1_ref[...], ((1,), (0,)))
    up = _dot(x, w3_ref[...], ((1,), (0,)))
    sig = jax.nn.sigmoid(gate)
    act = gate * sig
    hidden = act * up
    dhidden = _dot(g, w2_ref[...], ((1,), (1,)))                 # [tm, f]
    dwr_ref[...] = jnp.sum(hidden * dhidden, axis=1, keepdims=True)
    dhidden = wr * dhidden
    dgate = (dhidden * up * (sig * (1.0 + gate * (1.0 - sig)))).astype(x.dtype)
    dup = (dhidden * act).astype(x.dtype)
    dgate_ref[...] = dgate
    dup_ref[...] = dup
    hw_ref[...] = (wr * hidden).astype(x.dtype)
    dx_ref[...] = (_dot(dgate, w1_ref[...], ((1,), (1,)))
                   + _dot(dup, w3_ref[...], ((1,), (1,)))).astype(dx_ref.dtype)


def _dw_kernel(te_ref, x_ref, g_ref, dgate_ref, dup_ref, hw_ref,
               dw1_ref, dw3_ref, dw2_ref):
    """Weight gradients of one hidden-column block, accumulated in the output
    block over the consecutive row tiles of one expert."""
    i = pl.program_id(1)

    @pl.when((i == 0) | (te_ref[i] != te_ref[jnp.maximum(i - 1, 0)]))
    def _():
        dw1_ref[...] = jnp.zeros_like(dw1_ref)
        dw3_ref[...] = jnp.zeros_like(dw3_ref)
        dw2_ref[...] = jnp.zeros_like(dw2_ref)

    x, g = x_ref[...], g_ref[...]
    dw1_ref[...] += _dot(x, dgate_ref[...], ((0,), (0,)))
    dw3_ref[...] += _dot(x, dup_ref[...], ((0,), (0,)))
    dw2_ref[...] += _dot(hw_ref[...], g, ((0,), (0,)))


def _call(kernel, name, grid, in_specs, out_specs, out_shape):
    return pl.pallas_call(
        kernel, name=name, out_shape=out_shape, interpret=_interpret(),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs, out_specs=out_specs),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=VMEM_LIMIT))


def _of_expert(shape):
    """The column block of a ``[rows, n_held·width]`` leaf that is the tile's expert."""
    return pl.BlockSpec(shape, lambda i, te: (0, te[i]))


def _experts_fwd(sort, x_sorted, w1, w3, w2, tm):
    d, f = x_sorted.shape[1], w2.shape[0]
    row = lambda width: pl.BlockSpec((tm, width), lambda i, te: (i, 0))
    return _call(
        _fwd_kernel, "moe_ffn_fwd", (sort["num_tiles"],),
        [row(d), _of_expert((d, f)), _of_expert((d, f)), _of_expert((f, d))], row(d),
        jax.ShapeDtypeStruct(x_sorted.shape, x_sorted.dtype),
    )(sort["tile_expert"], x_sorted, w1, w3, w2)


def _experts_bwd(sort, x_sorted, g_sorted, w_row, w1, w3, w2, tm):
    d, f = x_sorted.shape[1], w2.shape[0]
    row = lambda width: pl.BlockSpec((tm, width), lambda i, te: (i, 0))
    m, dt = x_sorted.shape[0], x_sorted.dtype
    hidden = jax.ShapeDtypeStruct((m, f), dt)
    dx, dwr, dgate, dup, hw = _call(
        _bwd_kernel, "moe_ffn_bwd", (sort["num_tiles"],),
        [row(d), row(d), row(1), _of_expert((d, f)), _of_expert((d, f)),
         _of_expert((f, d))],
        [row(d), row(1), row(f), row(f), row(f)],
        [jax.ShapeDtypeStruct((m, d), dt), jax.ShapeDtypeStruct((m, 1), jnp.float32),
         hidden, hidden, hidden],
    )(sort["tile_expert"], x_sorted, g_sorted, w_row, w1, w3, w2)
    fb = HIDDEN_TILE if f % HIDDEN_TILE == 0 else f
    nf = f // fb
    rows = lambda width: pl.BlockSpec((tm, width), lambda j, i, te: (i, 0))
    cols = pl.BlockSpec((tm, fb), lambda j, i, te: (i, j))
    dw13 = pl.BlockSpec((d, fb), lambda j, i, te: (0, te[i] * nf + j))
    dw1, dw3, dw2 = _call(
        _dw_kernel, "moe_ffn_dw", (nf, sort["num_tiles"]),
        [rows(d), rows(d), cols, cols, cols],
        [dw13, dw13, pl.BlockSpec((fb, d), lambda j, i, te: (j, te[i]))],
        [jax.ShapeDtypeStruct(w1.shape, jnp.float32)] * 2
        + [jax.ShapeDtypeStruct(w2.shape, jnp.float32)],
    )(sort["tile_expert"], x_sorted, g_sorted, dgate, dup, hw)
    return dx, dwr[:, 0], dw1, dw3, dw2


def _from_rows(rows: jax.Array, sort: dict, weights: jax.Array | None) -> jax.Array:
    """Token order from expert order: each token's sum over its held assignments of
    their row (times the assignment's weight, if given). One gather an assignment."""
    out = 0.0
    for j in range(sort["pos"].shape[1]):
        row = jnp.take(rows, sort["pos"][:, j], axis=0).astype(jnp.float32)
        keep = sort["is_held"][:, j]
        scale = keep if weights is None else jnp.where(keep, weights[:, j], 0.0)
        out = out + jnp.where(keep[:, None], row, 0.0) * scale[:, None]
    return out


def _to_rows(tokens: jax.Array, sort: dict, k: int) -> jax.Array:
    return jnp.take(tokens, sort["assignment_of_row"] // k, axis=0)


@functools.lru_cache(maxsize=None)
def _grouped_ffn(tm: int):
    """``ffn(x, weights, w1, w3, w2, sort) -> [T, d]`` with its hand-written
    backward: recomputes the hidden tile instead of keeping ``[rows, f]``."""

    @jax.custom_vjp
    def ffn(x, weights, w1, w3, w2, sort):
        return forward(x, weights, w1, w3, w2, sort)[0]

    def forward(x, weights, w1, w3, w2, sort):
        cast = lambda w: w.astype(x.dtype)
        with jax.named_scope("moe/sort"):
            x_sorted = _to_rows(x, sort, weights.shape[1])
        with jax.named_scope("moe/experts"):
            y_sorted = _experts_fwd(sort, x_sorted, cast(w1), cast(w3), cast(w2), tm)
        with jax.named_scope("moe/combine"):
            out = _from_rows(y_sorted, sort, weights).astype(x.dtype)
        return out, (x, weights, w1, w3, w2, sort)

    def backward(residuals, dout):
        x, weights, w1, w3, w2, sort = residuals
        cast = lambda w: w.astype(x.dtype)
        k = weights.shape[1]
        with jax.named_scope("moe/combine"):
            x_sorted = _to_rows(x, sort, k)
            g_sorted = _to_rows(dout.astype(x.dtype), sort, k)
            w_row = jnp.where(sort["valid_row"],
                              weights.reshape(-1)[sort["assignment_of_row"]], 0.0)
        with jax.named_scope("moe/experts"):
            dx_sorted, dw_row, dw1, dw3, dw2 = _experts_bwd(
                sort, x_sorted, g_sorted, w_row[:, None].astype(jnp.float32),
                cast(w1), cast(w3), cast(w2), tm)
        with jax.named_scope("moe/sort"):
            dx = _from_rows(dx_sorted, sort, None).astype(x.dtype)
            dweights = jnp.where(sort["is_held"],
                                 jnp.take(dw_row, sort["pos"], axis=0), 0.0)
        return (dx, dweights.astype(weights.dtype), dw1.astype(w1.dtype),
                dw3.astype(w3.dtype), dw2.astype(w2.dtype), None)

    ffn.defvjp(forward, backward)
    return ffn


def held_experts_ffn(x: jax.Array, weights: jax.Array, experts: jax.Array,
                     w1: jax.Array, w3: jax.Array, w2: jax.Array, *,
                     held: tuple[int, int], block: int | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """The held experts' part of ``Σ_e w_e · W2_e (silu(W1_e x) ⊙ W3_e x)``.

    ``x [T, d]``; ``weights``, ``experts`` ``[T, k]`` as ``route`` gives them (ids over
    all experts); ``held = (first id, how many)``; ``w1``, ``w3`` ``[d, n_held·f]``,
    ``w2`` ``[f, n_held·d]``. Returns ``(out [T, d], counts [n_held] int32)``: the rows
    that arrived at each held expert, every one of them computed."""
    tm = block or ROW_TILE
    with jax.named_scope("moe/sort"):
        sort = _sort(experts, held, tm)
    counts = sort.pop("counts")
    return _grouped_ffn(tm)(x, weights, w1, w3, w2, sort), counts
