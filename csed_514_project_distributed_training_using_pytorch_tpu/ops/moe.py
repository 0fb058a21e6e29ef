"""Dropless sparse expert layer, as expert parallelism needs it: this chip's share.

The layer is told which experts it holds (``held``: a contiguous range of expert
ids). It routes every token over ALL experts, keeps the assignments that land on a
held expert, and returns the held experts' part of the layer's result: what the
other chips' experts would add is theirs to compute, and on one chip there is no
exchange. No token is dropped and there is no capacity: every assignment to a held
expert is computed, at any imbalance.

    route      s = sigmoid(u · W_r) in float32; the experts are the top-k of s + b
               (b enters the selection only); their weights are s_e / (Σ s_e + eps),
               times the model's scaling. Or s = softmax(u · W_r) over all the
               experts, the top-k of s, no b, the same renormalisation
    sort       a token can send a held expert at most one row, so of its k assignments
               at most min(k, n_held) land here: where k is the larger, each token's
               held assignments are moved to the front and the rest cut off (each takes
               the slot its place among the held ones gives it: a select and a sum over
               the k candidates of a slot), and everything below sees min(k, n_held)
               assignments a token. Then the held assignments, grouped by expert; each
               expert's rows start on a row tile, so a tile belongs to one expert. What
               an assignment needs of its expert (its rank there, the expert's first
               row) is a sum over its one-hot over the held experts; what a row needs
               (its expert, the expert's first row and count) is its tile's, repeated;
               the assignment of a row is a stable ``argsort`` with each expert's run
               shifted to where the expert's rows start. ``moe_pack`` writes the token
               array once as row-major 32-bit words (a row of a tiled ``[T, d]`` array
               is not contiguous in HBM, a row of that copy is one DMA), and
               ``moe_gather`` copies, tile by tile, the rows of the tiles that ARRIVED
               into expert order
    experts    the grouped product, three Pallas kernels whose grid is the number of
               row tiles that arrived (a scalar the sort hands them), not the static
               bound of min(k, n_held)·T rows: ``moe_ffn_fwd`` (per tile W2 · (silu(W1
               x) ⊙ W3 x), or W2 · relu(W1 x)² for an expert of two matrices, the
               hidden tile never leaving VMEM), ``moe_ffn_bwd`` (the same tile's
               input and routing-weight gradients) and ``moe_ffn_dw`` (the weight
               gradients, accumulated over an expert's tiles)
    combine    the product kernels write their rows row-major too; ``moe_combine``
               copies, for a tile of tokens at a time, the rows that ARRIVED for them
               (an expert's rows are in token order, so those of a tile of tokens are
               a range, which the sort hands over), sums each token's in float32,
               weighted, and rounds once to the model's dtype

Only row copies cross between token order and expert order, forward and backward (a
scatter-add would read and write every float32 sum once a row): the backward of the
combine is the gather of the sort, and the other way round. The scalars that drive
them cross with no gather or scatter: over the ``T·k`` assignments nothing is fetched or
stored by index (one scalar at a time, that costs 10 ns an element on the chip where
the bytes need microseconds) in ``_held_first``, in ``_sort``, or in the router's
picked scores and their gradient; each is a select and a sum over the few candidates
(the k of a token, the held experts, the router's experts) or a shift of a contiguous
run. (Two fetches by index are left, both in the layer's backward pass: the routing
weight of a row, and a row's weight gradient back at its assignment.) What a crossing copies
follows the rows that arrived (``num_tiles``, ``rows_of_tokens``), as the products do;
what does not is one pass over the ``[T, d]`` token array on either side (``moe_pack``
going in, the tiles ``moe_combine`` writes coming back). Every per-row and per-token
fact a crossing needs is a scalar in SMEM (the token of a row, the assignment of a
row, a tile's weights): a ``[T, k]`` operand of a kernel is padded to 128 lanes in HBM,
and so is everything upstream that XLA gives its layout. Buffers in expert order are
still sized for the bound (``min(k, n_held)·T`` rows and a tile a held expert); their tiles past
``num_tiles`` are never written and never read, a padding row inside an arrived tile
is token 0's row going in, and a slot of a token no row came into is left out by a
select, never by a product with 0.

Expert weights are three leaves a layer (two for the relu² expert, which has no
``w3``), column-blocked by held expert: ``w1``, ``w3`` ``[d, n_held·f]`` and ``w2``
``[f, n_held·d]``, so that a kernel's block index is the expert and a leaf's fan-in
is its first axis. ``d`` is the width of the rows the layer is given: the model's, or
a latent one the caller projects to and from.
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 256          # rows of one expert a kernel step multiplies
HIDDEN_TILE = 512       # columns of the hidden width a weight-gradient step owns
VMEM_LIMIT = 100 * 2 ** 20   # the resident expert's three bf16 matrices, twice
LANES, SUBLANES = 128, 8     # one tile of 32-bit words: what a row copy is aligned to


def _interpret() -> bool:
    """Compiled on TPU; interpret mode on CPU (the test platform)."""
    return jax.default_backend() != "tpu"


SCORINGS = ("sigmoid", "softmax")    # what ``route`` makes of the router's logits


def route(u: jax.Array, router_kernel: jax.Array, select_bias: jax.Array | None, *,
          top_k: int, scaling: float = 1.0, eps: float = 1e-6, load: bool = False,
          scoring: str = "sigmoid") -> tuple[jax.Array, ...]:
    """``u [T, d]`` -> ``(weights [T, k] float32, experts [T, k] int32)`` over all the
    router's experts. Matmul (at ``highest``: one bf16 pass would move near-tied
    selections), sigmoid and top-k in float32; ``select_bias`` moves the selection
    and not the weights, and gets no gradient. The selected experts' scores are picked
    by k masked sums over ``[T, experts]`` (``_pick``), whose transpose is k selects:
    no gather of ``T·k`` scalars going forward, no scatter-add of them coming back.
    ``load=True`` adds a third result, ``[experts] int32``: the tokens that selected
    each of the router's experts, held here or not (what ``rebalanced_bias`` reads),
    counted as the biased scores at or over a token's ``top_k``-th: one pass over
    ``[T, experts]``. ``scoring="softmax"``: the scores are the softmax over all the
    router's logits (the selection is the logits' own; ``select_bias`` None: such a router
    has none), and the selected ones are renormalised as the sigmoid's are."""
    with jax.named_scope("moe/route"):
        # Named: what a caller's ``jax.checkpoint`` may keep of the router (a policy
        # over names; an identity otherwise). The logits and not the scores, because
        # the sigmoid's derivative reads the variable the sigmoid wrote.
        logits = checkpoint_name(jnp.dot(
            u.astype(jnp.float32), router_kernel.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), "moe_route")
        scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        biased = scores if select_bias is None \
            else scores + jax.lax.stop_gradient(select_bias.astype(jnp.float32))
        top, experts = jax.lax.top_k(biased, top_k)
        experts = checkpoint_name(experts.astype(jnp.int32), "moe_route")
        picked = checkpoint_name(_pick(scores, experts), "moe_route")
        weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps)
        if not load:
            return weights * scaling, experts
        selected = biased >= top[:, -1:]
        return weights * scaling, experts, jnp.sum(selected, axis=0, dtype=jnp.int32)


@jax.custom_vjp
def _cotangent_written(x: jax.Array) -> jax.Array:
    """``x``, whose cotangent is written out before anything reads it."""
    return x


_cotangent_written.defvjp(lambda x: (x, None),
                          lambda _, g: (jax.lax.optimization_barrier(g),))


def _pick(scores: jax.Array, experts: jax.Array) -> jax.Array:
    """``scores [T, E]`` at ``experts [T, k]``, what ``take_along_axis`` gives, as k
    masked sums over ``[T, E]`` (one term of a sum is not zero, so they are exact, and
    no ``[T, k, E]`` array is formed: a pass a candidate). Autodiff transposes them into
    k selects; ``take_along_axis`` fetches ``T·k`` scalars and its transpose scatters
    them, 3.8 and 3.5 ms at 16,384 × 22 of 512 where this takes under 0.3 either way
    (``PERF.md`` §6, PR 36). At 10 ns a fetched scalar against the 1.5 ps a compared one
    cost there, the fetch would win from about 7,000 experts. The gradient is written
    once as ``[T, E]``: left to the compiler, the k selects fuse into both of the
    router's backward products as an operand computed again a tile (1.3 ms a layer at
    22 of 512, nothing at 8 of 256 or 4 of 64)."""
    of_expert = jnp.arange(scores.shape[-1], dtype=experts.dtype)[None]
    scores = _cotangent_written(scores)
    return jnp.stack(
        [jnp.sum(jnp.where(experts[:, j:j + 1] == of_expert, scores, 0), axis=1)
         for j in range(experts.shape[1])], axis=1)


def rebalanced_bias(select_bias: jax.Array, load: jax.Array, rate: float) -> jax.Array:
    """The selection's bias after a step whose tokens chose the experts ``load
    [experts]`` times: up by ``rate`` where an expert drew fewer tokens than the mean,
    down where more (the auxiliary-loss-free balancing of Wang et al. 2024, which
    DeepSeek-V3 and Megatron-LM's ``moe_router_enable_expert_bias`` train with). No
    gradient is involved: the rule reads counts."""
    load = load.astype(jnp.float32)
    return select_bias + rate * jnp.sign(jnp.mean(load) - load).astype(select_bias.dtype)


def expert_plan(tokens: int, *, top_k: int, held: tuple[int, int],
                block: int | None = None) -> dict:
    """The ``compile`` event's ``experts`` field: the held range, the static bound
    on rows (every token sending here all the rows it can: one a held expert, of its
    ``top_k``), the rows of the expert-order buffers (the bound and a tile a held
    expert), the row tile, and what a crossing between the two orders moves: the row
    tiles that arrived."""
    tm = block or ROW_TILE
    bound = tokens * min(top_k, held[1])
    return {"held": [held[0], held[0] + held[1]], "row_bound": bound,
            "rows_buffer": (-(-bound // tm) + held[1]) * tm, "block": tm,
            "rows_moved": "arrived"}


def _sort(experts: jax.Array, held: tuple[int, int], tm: int) -> dict:
    """Expert order from the router's choice. ``counts [n_held]``: rows that arrived
    at each held expert. Rows of expert ``e`` sit at ``seg_start[e] + rank``, each
    segment a whole number of tiles (an empty expert keeps one, all invalid, so
    that its weight gradient is written). ``rows_of_tokens [n_held, tiles + 1]``:
    expert ``e``'s rows of the ``i``-th tile of ``tm`` tokens are ``[e, i]`` to
    ``[e, i + 1]``.

    Nothing here is fetched by index over the ``T·k`` assignments. What an assignment
    needs of its expert is a sum over ``lands``, its one-hot over the held experts; what
    a row needs is its tile's, repeated ``tm`` times; and the assignment of a row is
    ``order`` with each expert's run of it moved to where the expert's rows start."""
    first, n = held
    t, k = experts.shape
    a = t * k
    local = experts.reshape(a) - first
    is_held = (local >= 0) & (local < n)
    key = jnp.where(is_held, local, n)
    lands = (jnp.arange(n)[:, None] == key[None]).astype(jnp.int32)        # [n, a]
    running = jnp.cumsum(lands, axis=1)
    counts = running[:, -1]
    tiles = jnp.maximum(1, -(-counts // tm))
    tile_end = jnp.cumsum(tiles)
    seg_start = (tile_end - tiles) * tm
    # its expert's first row and its rank there; of an assignment that is not held, 0
    pos = jnp.sum(lands * (seg_start[:, None] + running - 1), axis=0)
    order = jnp.argsort(key, stable=True)               # held first, by expert
    unaligned = jnp.cumsum(counts) - counts
    n_tiles = -(-a // tm) + n
    tile = jnp.arange(n_tiles)
    tile_expert = jnp.minimum(jnp.sum(tile[:, None] >= tile_end[None], axis=1), n - 1)
    of_expert = tile_expert[:, None] == jnp.arange(n)[None]                # [n_tiles, n]
    start, count = (jnp.sum(jnp.where(of_expert, per_expert[None], 0), axis=1)
                    for per_expert in (seg_start, counts))
    offset = (tile * tm - start)[:, None] + jnp.arange(tm)[None]          # [n_tiles, tm]
    valid = (offset < count[:, None]) & (tile < tile_end[-1])[:, None]
    # expert e's rows are order[unaligned[e]:][:counts[e]] at seg_start[e]: a shift
    room = n * tm                       # an expert's rows start at most this far along
    padded = jnp.pad(order, (room, n_tiles * tm - a))
    source = jnp.zeros((n_tiles, tm), order.dtype)
    for e in range(n):
        moved = jax.lax.dynamic_slice(padded, (room - (seg_start[e] - unaligned[e]),),
                                      (n_tiles * tm,))
        source = jnp.where(of_expert[:, e:e + 1], moved.reshape(n_tiles, tm), source)
    source, valid = source.reshape(-1), valid.reshape(-1)
    # an expert's rows are in token order: those of one tile of ``tm`` tokens are a range
    token_tiles = -(-t // tm)
    of_tile = jnp.pad(lands, ((0, 0), (0, token_tiles * tm * k - a))).reshape(
        n, token_tiles, tm * k).sum(axis=2)
    before = jnp.concatenate([jnp.zeros((n, 1), jnp.int32), jnp.cumsum(of_tile, axis=1)],
                             axis=1)
    return {"counts": counts, "num_tiles": tile_end[-1].astype(jnp.int32),
            "rows_of_tokens": (seg_start[:, None] + before).astype(jnp.int32),
            "tile_expert": tile_expert.astype(jnp.int32),
            "assignment_of_row": jnp.where(valid, source, 0).astype(jnp.int32),
            "token_of_row": jnp.where(valid, source // k, -1).astype(jnp.int32),
            "pos": pos.reshape(t, k).astype(jnp.int32),
            "is_held": is_held.reshape(t, k)}


# --------------------------------------------------------------------------------------
# Row-major rows. A row of a ``[rows, d]`` array is not contiguous in HBM (tiles of 8 or
# 16 rows), so what a crossing copies row by row is kept as 32-bit words ``[rows · r,
# 128]`` in which row ``t`` is the ``r`` consecutive word rows from ``t · r``: whole
# tiles, one DMA. bf16 packs two column blocks a word.
# --------------------------------------------------------------------------------------


def _word_rows(d: int, dtype) -> int:
    """Word rows (of 128 lanes) that one row of ``d`` columns takes: whole tiles."""
    words = -(-d // LANES) * LANES * jnp.dtype(dtype).itemsize // 4
    return -(-words // (LANES * SUBLANES)) * SUBLANES


def _column_blocks(d: int, dtype):
    """``(word row, half, first column, columns)`` of every 128-column block."""
    per = 4 // jnp.dtype(dtype).itemsize
    return [(c // per, c % per, c * LANES, min(LANES, d - c * LANES))
            for c in range(-(-d // LANES))]


def _to_words(block, half, dtype):
    """A ``[tm, 128]`` block's bits where its half of the 32-bit word is."""
    if jnp.dtype(dtype).itemsize == 4:
        return pltpu.bitcast(block, jnp.uint32)
    bits = pltpu.bitcast(block.astype(jnp.float32), jnp.uint32)   # bf16: the high half
    return bits & jnp.uint32(0xFFFF0000) if half else bits >> 16


def _from_words(words, half, dtype):
    if jnp.dtype(dtype).itemsize == 4:
        return pltpu.bitcast(words, dtype)
    bits = words & jnp.uint32(0xFFFF0000) if half else words << 16
    return pltpu.bitcast(bits, jnp.float32).astype(dtype)


def _lanes(block):
    short = LANES - block.shape[1]
    return jnp.pad(block, ((0, 0), (0, short))) if short else block


def _store_row_major(o_ref, value, r: int):
    """``value [tm, d]`` into ``o_ref [tm · r, 128]`` words, each row's ``r`` together."""
    tm, d = value.shape
    words = {}
    for s, half, c0, n in _column_blocks(d, value.dtype):
        w = _to_words(_lanes(value[:, c0:c0 + n]), half, value.dtype)
        words[s] = words[s] | w if s in words else w
    for s, w in words.items():
        o_ref[pl.ds(s, tm, stride=r), :] = w


def _each(lo, hi, body, by: int):
    """``body(j)`` for ``lo <= j < hi``, ``by`` of them a turn of the loop and the rest
    one by one (Mosaic unrolls a loop whole or not at all, and a turn's scalar work
    does not overlap the next's)."""
    whole = (hi - lo) // by

    def turn(g, carry):
        for u in range(by):
            body(lo + g * by + u)
        return carry

    def one(j, carry):
        body(j)
        return carry

    jax.lax.fori_loop(0, whole, turn, 0)
    jax.lax.fori_loop(lo + whole * by, hi, one, 0)


# --------------------------------------------------------------------------------------
# The grouped product. ``te_ref``: the expert of each row tile (scalar prefetch).
# --------------------------------------------------------------------------------------


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _hidden(x, w_in_refs):
    """A row tile's hidden tile from its pre-activations, ``silu(W1 x) ⊙ W3 x`` of
    two or ``relu(W1 x)²`` of one, float32, and a function from the hidden tile's
    gradient to theirs, rounded to ``x``'s dtype."""
    pre = [_dot(x, w_ref[...], ((1,), (0,))) for w_ref in w_in_refs]
    if len(pre) == 2:
        gate, up = pre
        sig = jax.nn.sigmoid(gate)
        act = gate * sig
        return act * up, lambda dhidden: [
            (dhidden * up * (sig * (1.0 + gate * (1.0 - sig)))).astype(x.dtype),
            (dhidden * act).astype(x.dtype)]
    positive = jnp.maximum(pre[0], 0.0)
    return positive * positive, lambda dhidden: [
        (dhidden * (2.0 * positive)).astype(x.dtype)]


def _fwd_kernel(te_ref, x_ref, *refs, r):
    """One row tile's result, written row-major: only the combine reads it.
    ``refs``: W1 (and W3), W2, the output."""
    del te_ref
    *w_in_refs, w2_ref, y_ref = refs
    x = x_ref[...]
    hidden = _hidden(x, w_in_refs)[0].astype(x.dtype)
    _store_row_major(y_ref, _dot(hidden, w2_ref[...], ((1,), (0,))).astype(x.dtype), r)


def _bwd_kernel(te_ref, x_ref, g_ref, wr_ref, *refs, r, n_in):
    """One row tile's backward. ``wr``: the rows' routing weights (0 on an invalid
    row, which therefore adds nothing to any weight gradient). ``refs``: the
    ``n_in`` input matrices (W1, and W3 of a gated expert), W2; then the outputs: the
    input gradient (row-major, for the combine), the routing weights' gradient, and
    what ``moe_ffn_dw`` multiplies (the pre-activations' gradients, the weighted
    hidden tile)."""
    del te_ref
    w_in_refs, w2_ref = refs[:n_in], refs[n_in]
    dx_ref, dwr_ref, *dpre_refs, hw_ref = refs[n_in + 1:]
    x, g, wr = x_ref[...], g_ref[...], wr_ref[...]
    hidden, pre_gradients = _hidden(x, w_in_refs)
    dhidden = _dot(g, w2_ref[...], ((1,), (1,)))                 # [tm, f]
    dwr_ref[...] = jnp.sum(hidden * dhidden, axis=1, keepdims=True)
    dpre = pre_gradients(wr * dhidden)
    for ref, d in zip(dpre_refs, dpre):
        ref[...] = d
    hw_ref[...] = (wr * hidden).astype(x.dtype)
    dx = functools.reduce(operator.add, (_dot(d, w_ref[...], ((1,), (1,)))
                                         for d, w_ref in zip(dpre, w_in_refs)))
    _store_row_major(dx_ref, dx.astype(x.dtype), r)


def _dw_kernel(te_ref, x_ref, g_ref, *refs):
    """Weight gradients of one hidden-column block, accumulated in the output
    blocks over the consecutive row tiles of one expert. ``refs``: the
    pre-activations' gradients and the weighted hidden tile, then the outputs, the
    input matrices' gradients and W2's."""
    n_in = len(refs) // 2 - 1
    dpre_refs, hw_ref = refs[:n_in], refs[n_in]
    *dw_in_refs, dw2_ref = refs[n_in + 1:]
    i = pl.program_id(1)

    @pl.when((i == 0) | (te_ref[i] != te_ref[jnp.maximum(i - 1, 0)]))
    def _():
        for ref in (*dw_in_refs, dw2_ref):
            ref[...] = jnp.zeros_like(ref)

    x, g = x_ref[...], g_ref[...]
    for dw_ref, dpre_ref in zip(dw_in_refs, dpre_refs):
        dw_ref[...] += _dot(x, dpre_ref[...], ((0,), (0,)))
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


def _row_major(tm: int, r: int):
    """A row tile of an expert-order array kept row-major."""
    return pl.BlockSpec((tm * r, LANES), lambda i, te: (i, 0))


def _experts_fwd(sort, x_sorted, w_in, w2, tm):
    (m, d), f = x_sorted.shape, w2.shape[0]
    r = _word_rows(d, x_sorted.dtype)
    row = lambda width: pl.BlockSpec((tm, width), lambda i, te: (i, 0))
    return _call(
        functools.partial(_fwd_kernel, r=r), "moe_ffn_fwd", (sort["num_tiles"],),
        [row(d)] + [_of_expert((d, f))] * len(w_in) + [_of_expert((f, d))],
        _row_major(tm, r), jax.ShapeDtypeStruct((m * r, LANES), jnp.uint32),
    )(sort["tile_expert"], x_sorted, *w_in, w2)


def _experts_bwd(sort, x_sorted, g_sorted, w_row, w_in, w2, tm):
    d, f, n_in = x_sorted.shape[1], w2.shape[0], len(w_in)
    row = lambda width: pl.BlockSpec((tm, width), lambda i, te: (i, 0))
    m, dt = x_sorted.shape[0], x_sorted.dtype
    r = _word_rows(d, dt)
    hidden = jax.ShapeDtypeStruct((m, f), dt)
    dx, dwr, *dpre_and_hw = _call(
        functools.partial(_bwd_kernel, r=r, n_in=n_in), "moe_ffn_bwd",
        (sort["num_tiles"],),
        [row(d), row(d), row(1)] + [_of_expert((d, f))] * n_in + [_of_expert((f, d))],
        [_row_major(tm, r), row(1)] + [row(f)] * (n_in + 1),
        [jax.ShapeDtypeStruct((m * r, LANES), jnp.uint32),
         jax.ShapeDtypeStruct((m, 1), jnp.float32)] + [hidden] * (n_in + 1),
    )(sort["tile_expert"], x_sorted, g_sorted, w_row, *w_in, w2)
    fb = HIDDEN_TILE if f % HIDDEN_TILE == 0 else f
    nf = f // fb
    rows = lambda width: pl.BlockSpec((tm, width), lambda j, i, te: (i, 0))
    cols = pl.BlockSpec((tm, fb), lambda j, i, te: (i, j))
    dw_in_block = pl.BlockSpec((d, fb), lambda j, i, te: (0, te[i] * nf + j))
    *dw_in, dw2 = _call(
        _dw_kernel, "moe_ffn_dw", (nf, sort["num_tiles"]),
        [rows(d), rows(d)] + [cols] * (n_in + 1),
        [dw_in_block] * n_in + [pl.BlockSpec((fb, d), lambda j, i, te: (j, te[i]))],
        [jax.ShapeDtypeStruct(w.shape, jnp.float32) for w in (*w_in, w2)],
    )(sort["tile_expert"], x_sorted, g_sorted, *dpre_and_hw)
    return dx, dwr[:, 0], tuple(dw_in), dw2


# --------------------------------------------------------------------------------------
# The crossings between token order and expert order: row copies, a row tile or a tile
# of tokens a grid step, driven by the sort's scalars (scalar prefetch).
# --------------------------------------------------------------------------------------


def _pack_kernel(x_ref, o_ref, *, r):
    _store_row_major(o_ref, x_ref[...], r)


def _gather_kernel(tok_ref, *refs, r):
    """One row tile of expert order: its rows' tokens, copied row by row, from each of
    ``n`` token-order arrays (row-major) into its own output."""
    n = len(refs) // 3
    sources, outs, bufs, sem = refs[:n], refs[n:2 * n], refs[2 * n:3 * n], refs[-1]
    tm, d = outs[0].shape
    i = pl.program_id(0)

    def copies(j, token):
        return [pltpu.make_async_copy(
            x_hbm.at[pl.ds(pl.multiple_of(token * r, SUBLANES), r)],
            buf.at[pl.ds(pl.multiple_of(j * r, SUBLANES), r)], sem)
            for x_hbm, buf in zip(sources, bufs)]

    _each(0, tm, lambda j: [c.start() for c in copies(j, tok_ref[i * tm + j])], by=8)
    _each(0, tm, lambda j: [c.wait() for c in copies(j, 0)], by=8)
    for o_ref, buf in zip(outs, bufs):
        for s, half, c0, w in _column_blocks(d, o_ref.dtype):
            block = _from_words(buf[pl.ds(s, tm, stride=r), :], half, o_ref.dtype)
            o_ref[:, c0:c0 + w] = block[:, :w]


def _combine_kernel(range_ref, assignment_ref, *refs, r, k, weighted, dtype):
    """One tile of tokens: the rows of its held assignments, copied from expert order
    row by row (every held expert's rows of these tokens are a range, ``range_ref``;
    ``assignment_ref``: the assignment ``token · k + j`` of a row; ``weight_ref``: the
    weights of this tile's assignments), and per token their float32 sum, weighted,
    rounded once. ``came`` marks the slots a row came into: the others are left out by
    a select."""
    weight_ref = refs[1] if weighted else None       # this tile's [1, 1, tm · k], in SMEM
    rows_hbm, (o_ref, buf, came, scale, sem) = refs[0], refs[-5:]
    tm, d = o_ref.shape
    i, steps = pl.program_id(0), pl.num_programs(0)
    came[...] = jnp.zeros_like(came)

    def copy(row, slot):
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(pl.multiple_of(row * r, SUBLANES), r)],
            buf.at[pl.ds(pl.multiple_of(slot * r, SUBLANES), r)], sem)

    def of_expert(e, started):
        lo = range_ref[e * (steps + 1) + i]
        hi = range_ref[e * (steps + 1) + i + 1]

        def start(row):
            a = assignment_ref[row]
            slot = (a % k) * tm + a // k - i * tm
            copy(row, slot).start()
            came[pl.ds(slot, 1), :] = jnp.ones((1, LANES), jnp.float32)
            if weighted:
                scale[pl.ds(slot, 1), :] = jnp.full(
                    (1, LANES), weight_ref[0, 0, a - i * tm * k], jnp.float32)

        _each(lo, hi, start, by=4)
        return started + hi - lo

    experts = range_ref.shape[0] // (steps + 1)
    started = jax.lax.fori_loop(0, experts, of_expert, 0)
    _each(0, started, lambda _: copy(0, 0).wait(), by=4)
    for s, half, c0, n in _column_blocks(d, dtype):
        total = jnp.zeros((tm, LANES), jnp.float32)
        for a in range(k):
            row = _from_words(buf[pl.ds(a * tm * r + s, tm, stride=r), :], half,
                              dtype).astype(jnp.float32)
            if weighted:
                row = row * scale[a * tm:(a + 1) * tm, :]
            total = total + jnp.where(came[a * tm:(a + 1) * tm, :] > 0, row, 0.0)
        o_ref[:, c0:c0 + n] = total[:, :n].astype(o_ref.dtype)


def _pack(x: jax.Array, tm: int) -> jax.Array:
    """``[T, d]`` -> its row-major words ``[T · r, 128]``: one pass over ``x``."""
    t, d = x.shape
    r = _word_rows(d, x.dtype)
    return pl.pallas_call(
        functools.partial(_pack_kernel, r=r), name="moe_pack", interpret=_interpret(),
        out_shape=jax.ShapeDtypeStruct((t * r, LANES), jnp.uint32), grid=(-(-t // tm),),
        in_specs=[pl.BlockSpec((tm, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tm * r, LANES), lambda i: (i, 0)))(x)


def _to_rows(tokens: tuple, sort: dict, tm: int) -> list:
    """Expert order from token order, for each of ``tokens`` (``[T, d]`` arrays of one
    shape and dtype): ``moe_pack`` makes them row-major, ``moe_gather`` writes the row
    tiles that arrived (a padding row of theirs gets token 0's row); the buffers' other
    tiles stay unwritten, and nothing reads them."""
    (_, d), dtype, n = tokens[0].shape, tokens[0].dtype, len(tokens)
    r = _word_rows(d, dtype)
    rows = sort["token_of_row"].shape[0]
    return pl.pallas_call(
        functools.partial(_gather_kernel, r=r), name="moe_gather",
        interpret=_interpret(),
        out_shape=[jax.ShapeDtypeStruct((rows, d), dtype)] * n,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(sort["num_tiles"],),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n,
            out_specs=[pl.BlockSpec((tm, d), lambda i, tok: (i, 0))] * n,
            scratch_shapes=[pltpu.VMEM((tm * r, LANES), jnp.uint32)] * n
            + [pltpu.SemaphoreType.DMA(())]),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(jnp.maximum(sort["token_of_row"], 0), *[_pack(x, tm) for x in tokens])


def _from_rows(rows: jax.Array, sort: dict, weights: jax.Array | None, d: int, dtype,
               tm: int) -> jax.Array:
    """Token order from expert order: each token's sum over its held assignments of
    their row (times the assignment's weight, if given), in float32, rounded once.
    ``rows``: ``[·, d]`` of ``dtype`` kept row-major, as the product kernels write
    it; ``moe_combine`` copies, for a tile of tokens at a time, the rows that arrived
    for them. Everything it is told about a token is a scalar (a ``[T, k]`` operand
    would have to be padded to 128 lanes in HBM, and its producers' with it)."""
    tokens, k = sort["pos"].shape
    r = _word_rows(d, dtype)
    weighted = weights is not None
    steps = -(-tokens // tm)
    slot_rows = pltpu.VMEM((k * tm, LANES), jnp.float32)
    of_tile = []
    if weighted:        # a tile's weights: scalars, a step's at a time
        flat = weights.astype(jnp.float32).reshape(-1)
        of_tile = [jnp.pad(flat, (0, steps * tm * k - flat.shape[0])).reshape(steps, 1, tm * k)]
    return pl.pallas_call(
        functools.partial(_combine_kernel, r=r, k=k, weighted=weighted, dtype=dtype),
        name="moe_combine", interpret=_interpret(),
        out_shape=jax.ShapeDtypeStruct((tokens, d), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(steps,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)]
            + [pl.BlockSpec((1, 1, tm * k), lambda i, *_: (i, 0, 0),
                            memory_space=pltpu.SMEM)] * weighted,
            out_specs=pl.BlockSpec((tm, d), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((k * tm * r, LANES), jnp.uint32), slot_rows,
                            slot_rows, pltpu.SemaphoreType.DMA(())]),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             vmem_limit_bytes=VMEM_LIMIT),
    )(sort["rows_of_tokens"].reshape(-1), sort["assignment_of_row"], rows, *of_tile)


@functools.lru_cache(maxsize=None)
def _grouped_ffn(tm: int):
    """``ffn(x, weights, w_in, w2, sort) -> [T, d]`` with its hand-written backward
    (``w_in``: ``(w1, w3)`` of a gated expert, ``(w1,)`` of a relu² one): recomputes
    the hidden tile instead of keeping ``[rows, f]``. The two halves are jitted and
    this factory is cached, as ``pallas_attention._make_op``'s are: every sparse layer
    of a model calls the same two functions, so a program traces and lowers the six
    kernels once, not once a layer and pass."""

    @jax.custom_vjp
    def ffn(x, weights, w_in, w2, sort):
        return forward(x, weights, w_in, w2, sort)[0]

    @jax.jit
    def forward(x, weights, w_in, w2, sort):
        cast = lambda w: w.astype(x.dtype)
        with jax.named_scope("moe/sort"):
            x_sorted, = _to_rows((x,), sort, tm)
        with jax.named_scope("moe/experts"):
            y_sorted = _experts_fwd(sort, x_sorted, tuple(map(cast, w_in)), cast(w2), tm)
        with jax.named_scope("moe/combine"):
            out = _from_rows(y_sorted, sort, weights, x.shape[1], x.dtype, tm)
        return out, (x, weights, w_in, w2, sort)

    @jax.jit
    def backward(residuals, dout):
        x, weights, w_in, w2, sort = residuals
        dtype, d = x.dtype, x.shape[1]
        cast = lambda w: w.astype(dtype)
        with jax.named_scope("moe/combine"):
            x_sorted, g_sorted = _to_rows((x, dout.astype(dtype)), sort, tm)
            w_row = jnp.where(sort["token_of_row"] >= 0,
                              weights.reshape(-1)[sort["assignment_of_row"]], 0.0)
        with jax.named_scope("moe/experts"):
            dx_sorted, dw_row, dw_in, dw2 = _experts_bwd(
                sort, x_sorted, g_sorted, w_row[:, None].astype(jnp.float32),
                tuple(map(cast, w_in)), cast(w2), tm)
        with jax.named_scope("moe/sort"):
            dx = _from_rows(dx_sorted, sort, None, d, dtype, tm)
            dweights = jnp.where(sort["is_held"], dw_row.at[sort["pos"]].get(
                mode="promise_in_bounds"), 0.0)
        return (dx, dweights.astype(weights.dtype),
                tuple(dw.astype(w.dtype) for dw, w in zip(dw_in, w_in)),
                dw2.astype(w2.dtype), None)

    ffn.defvjp(forward, backward)
    return ffn


def _held_first(weights: jax.Array, experts: jax.Array, held: tuple[int, int]):
    """``weights``, ``experts`` ``[T, k]`` cut to ``[T, min(k, n_held)]``: a token's
    assignments to held experts first, in the router's order. The router's experts of
    a token are distinct, so none that is held is cut off. An assignment's slot is a
    count of those before it, and a slot's assignment a select and a sum over the k
    candidates: nothing is sorted or fetched by index, and the transpose selects too."""
    k, keep = experts.shape[1], min(experts.shape[1], held[1])
    if keep == k:
        return weights, experts
    local = experts - held[0]
    is_held = (local >= 0) & (local < held[1])
    ahead = jnp.cumsum(is_held, axis=1, dtype=jnp.int32)     # held ones, up to and with j
    others = jnp.arange(1, k + 1)[None] - ahead              # the rest, the same
    slot = jnp.where(is_held, ahead, ahead[:, -1:] + others) - 1
    front = lambda x: jnp.stack(
        [jnp.sum(jnp.where(slot == s, x, 0), axis=1) for s in range(keep)], axis=1)
    return front(weights), front(experts)


def held_experts_ffn(x: jax.Array, weights: jax.Array, experts: jax.Array,
                     w1: jax.Array, w3: jax.Array | None, w2: jax.Array, *,
                     held: tuple[int, int], block: int | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """The held experts' part of ``Σ_e w_e · W2_e (silu(W1_e x) ⊙ W3_e x)``, or, with
    ``w3`` None, of ``Σ_e w_e · W2_e relu(W1_e x)²``.

    ``x [T, d]``; ``weights``, ``experts`` ``[T, k]`` as ``route`` gives them (ids over
    all experts); ``held = (first id, how many)``; ``w1``, ``w3`` ``[d, n_held·f]``,
    ``w2`` ``[f, n_held·d]``. Returns ``(out [T, d], counts [n_held] int32)``: the rows
    that arrived at each held expert, every one of them computed."""
    tm = block or ROW_TILE
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        raise TypeError(f"the row-major copies pack float32 or bfloat16, not {x.dtype}")
    with jax.named_scope("moe/sort"):
        weights, experts = _held_first(weights, experts, held)
        sort = _sort(experts, held, tm)
    counts = sort.pop("counts")
    # Residuals of the VJP below, named before they enter it (see ``route``).
    sort = jax.tree.map(lambda leaf: checkpoint_name(leaf, "moe_sort"), sort)
    w_in = (w1,) if w3 is None else (w1, w3)
    return _grouped_ffn(tm)(x, weights, w_in, w2, sort), counts
