"""Plain DeepSeek-V3-shaped decoder (``model_type`` ``deepseek_v3``), or one chip's share
of it.

Written from the architecture's description and the published ``deepseek_v3`` modelling
code its keys are read by, float32 ``jax.numpy``; imports nothing of the program. ``m`` is
the configuration file itself: the published keys at its top level, with the keys that
count layers, routed experts and ids as held here, ``published.n_routed_experts`` the
router's width, and ``share`` = ``{first_layer (numbered from 0), first_expert}``.

    layer       h = x + mla(rms(x));  y = h + ff(rms(h));  rms(x) = x/sqrt(mean x² + eps)·w
    mla         q = W_q u, a head [q_nope | q_pe];  [c | k_pe] = W_kva u;  [k_nope | v] =
                W_kvb rms(c) a head;  q_pe of every head and the one k_pe turn by their
                position t: where ``rope_interleave``, the pairs (2i, 2i + 1) are first moved
                apart (x[0::2] | x[1::2]), then x·cos + rotate_half(x)·sin with angles
                t · theta^(−2i/pe) over both halves, as the published code writes it;  a
                head's key is [k_nope | k_pe], k_pe the same for every head;
                causal softmax(q·k/√(nope + pe))·v;  W_o
    dense ff    W2 (silu(W1 u) ⊙ W3 u), the first ``first_k_dense_replace`` layers
    experts     s = sigmoid(W_r u); the k experts are the top-k of s + b (no groups);
                weights scaling · s_e / (Σ s_e + 1e-20);
                Σ_{e held} w_e W2_e (silu(W1_e u) ⊙ W3_e u)  +  W_s2 (silu(W_s1 u) ⊙ W_s3 u),
                the shared experts one gated expert of ``n_shared_experts`` × the expert
                width, unweighted: a loop over the held experts with masks, no sort, no
                capacity, no token dropped. What the experts held elsewhere would add is
                left out.
    head        its own matrix over the held slice of the vocabulary, after a last
                rms; loss = mean next-token NLL over the S-1 targets a sequence

Departures, for memory alone: layers are recomputed in the backward pass, and attention,
the feed-forwards and the head walk a sequence's rows in blocks (``ROW_BLOCK``; attention's
scores in ``SCORE_BLOCK`` query rows against every key, so that 8192 x 8192 x 32 heads
never stands whole). The batch's sequences go side by side (``vmap`` of the one-sequence
model): one after the other, each sequence's gradient of every parameter would stand
beside the running sum, 2.75 GB twice more than the chip has beside the reference's state.
``b`` (``expert_bias_b``) gets no gradient: where the file gives
``moe_router_bias_update_rate`` it moves after a step by that rate, up for an expert that
fewer tokens chose than the mean over the router's experts and down for one that more did
(``loss(..., with_load=True)`` hands out the counts, ``rebalanced`` moves the biases).

Leaf names are those of the program's tree (``embed_tokens``, ``lm_head_kernel``,
``final_norm_scale``, ``layer_<i>/{mixer_norm_scale, ff_norm_scale}``,
``layer_<i>/mla/{q,kv_a,kv_b,out}_kernel``, ``…/mla/kv_a_norm_scale``,
``layer_<i>/ff/w{1,2,3}_kernel``, ``layer_<i>/moe/{router_kernel, expert_bias_b,
shared_w{1,2,3}_kernel, experts_w{1,2,3}_kernel}``); expert matrices are column-blocked by
held expert: ``experts_w1_kernel [d, n·f]``, ``experts_w2_kernel [f, n·d]``. The forty lines
this file shares with ``kimi_linear.py`` are copied, not imported: one model's reference
does not follow another's edits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import precision as prec

MASK = -1e30
ROW_BLOCK = 512         # rows of a sequence a row-wise stage holds at once
SCORE_BLOCK = 256       # query rows whose scores against every key stand at once


def _by_rows(fn, x, *more, rows=ROW_BLOCK):
    """``fn`` over blocks of ``rows`` rows of ``x`` (and of ``more``), one block at a
    time and recomputed in the backward pass. ``fn(block, *more_blocks, first_row)``."""
    s = x.shape[0]
    rows = rows if s % rows == 0 else s
    cut = lambda a: a.reshape((s // rows, rows) + a.shape[1:])
    starts = jnp.arange(0, s, rows)
    out = jax.lax.map(lambda args: jax.checkpoint(fn)(*args),
                      (cut(x), *map(cut, more), starts))
    return out.reshape((s,) + out.shape[2:])


def sparse(m: dict) -> list[bool]:
    """Whether each kept layer's feed-forward is the expert layer."""
    first = m.get("share", {}).get("first_layer", 0)
    return [i >= m["first_k_dense_replace"]
            for i in range(first, first + m["num_hidden_layers"])]


def held(m: dict) -> tuple[int, int, int]:
    """(first held expert, how many are held, the router's width)."""
    return (m.get("share", {}).get("first_expert", 0), m["n_routed_experts"],
            m.get("published", {}).get("n_routed_experts", m["n_routed_experts"]))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def rotated(x, m: dict):
    """``x [S, heads, pe]`` turned by each row's position, the published way."""
    s, heads, pe = x.shape
    if m.get("rope_interleave"):        # pairs moved apart: [x0 x2 … | x1 x3 …]
        x = x.reshape(s, heads, pe // 2, 2).swapaxes(-1, -2).reshape(s, heads, pe)
    inv_freq = m["rope_theta"] ** (-jnp.arange(0, pe, 2, dtype=jnp.float32) / pe)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    rotate_half = jnp.concatenate([-x[..., pe // 2:], x[..., :pe // 2]], axis=-1)
    return x * jnp.cos(angles) + rotate_half * jnp.sin(angles)


def mla_mixer(p, u, m, mm, es):
    s = u.shape[0]
    heads, rank = m["num_attention_heads"], m["kv_lora_rank"]
    nope, pe, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    q_nope, q_pe = jnp.split(mm(u, p["q_kernel"]).reshape(s, heads, nope + pe), [nope], axis=-1)
    latent, k_pe = jnp.split(mm(u, p["kv_a_kernel"]), [rank], axis=-1)
    kv = mm(_rms(latent, p["kv_a_norm_scale"], m["rms_norm_eps"]), p["kv_b_kernel"])
    k_nope, v = jnp.split(kv.reshape(s, heads, nope + vd), [nope], axis=-1)
    q = jnp.concatenate([q_nope, rotated(q_pe, m)], axis=-1)
    k_pe = rotated(k_pe[:, None, :], m)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (s, heads, pe))], axis=-1)

    def rows(q_blk, start):
        scores = es("qhd,khd->hqk", q_blk, k) / jnp.sqrt(jnp.float32(nope + pe))
        seen = (start + jnp.arange(q_blk.shape[0]))[:, None] >= jnp.arange(s)[None]
        w = jax.nn.softmax(jnp.where(seen[None], scores, MASK), axis=-1)
        return es("hqk,khd->qhd", w, v)

    return mm(_by_rows(rows, q, rows=SCORE_BLOCK).reshape(s, heads * vd), p["out_kernel"])


def _gated(u, w1, w3, w2, mm):
    return mm(jax.nn.silu(mm(u, w1)) * mm(u, w3), w2)


def dense_ff(p, u, mm):
    return _by_rows(lambda rows, _: _gated(rows, p["w1_kernel"], p["w3_kernel"],
                                           p["w2_kernel"], mm), u)


def route(p, u, m, mm):
    """``(weights [S, k], experts [S, k])`` over all the router's experts."""
    scores = jax.nn.sigmoid(mm(u, p["router_kernel"]))
    _, experts = jax.lax.top_k(scores + jax.lax.stop_gradient(p["expert_bias_b"]),
                               m["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return weights * m["routed_scaling_factor"], experts


def experts_ff(p, u, m, mm):
    first, count, _ = held(m)
    weights, experts = route(p, u, m, mm)
    width = {name: p[name].shape[1] // count for name in
             ("experts_w1_kernel", "experts_w3_kernel", "experts_w2_kernel")}

    @jax.checkpoint
    def expert(e, u, weights, w1, w3, w2):
        """What held expert ``e`` adds: its weight a token (zero where the token did
        not choose it) times its gated feed-forward, of every token."""
        w_e = jnp.sum(jnp.where(experts == first + e, weights, 0.0), axis=-1)
        return w_e[:, None] * _gated(u, w1, w3, w2, mm)

    routed = jnp.zeros_like(u)
    for e in range(count):      # column block e of each matrix is held expert e's
        routed = routed + expert(e, u, weights, *(
            p[name][:, e * width[name]:(e + 1) * width[name]] for name in width))
    chosen = jnp.sum(experts[..., None] == jnp.arange(p["router_kernel"].shape[1]),
                     axis=(0, 1), dtype=jnp.int32)
    shared = _gated(u, p["shared_w1_kernel"], p["shared_w3_kernel"],
                    p["shared_w2_kernel"], mm)
    return routed + shared, chosen


def _mixed(p, x, m, mm, es):
    """``x + mla(rms(x))``: the first half of a layer."""
    return x + mla_mixer(p["mla"], _rms(x, p["mixer_norm_scale"], m["rms_norm_eps"]),
                         m, mm, es)


def _layer(p, x, m, is_sparse, mm, es):
    """``(the layer's output, how often each of the router's experts was chosen)``, the
    second None for a dense layer."""
    h = _mixed(p, x, m, mm, es)
    u = _rms(h, p["ff_norm_scale"], m["rms_norm_eps"])
    out, chosen = experts_ff(p["moe"], u, m, mm) if is_sparse \
        else (dense_ff(p["ff"], u, mm), None)
    return h + out, chosen


def _forward(params, ids, m: dict, precision: str, layers: int | None):
    """One sequence ``ids [S]`` -> ``([S, d] after ``layers`` layers (all, and the last
    norm, when None), [the expert layers' ``chosen``])``."""
    mm, es = prec.matmul(precision), prec.einsum(precision)
    x = params["embed_tokens"][ids]
    load = []
    for i, is_sparse in list(enumerate(sparse(m)))[:layers]:
        x, chosen = jax.checkpoint(
            lambda p, x, is_sparse=is_sparse: _layer(p, x, m, is_sparse, mm, es))(
                params[f"layer_{i}"], x)
        load += [] if chosen is None else [chosen]
    if layers is None:
        x = _rms(x, params["final_norm_scale"], m["rms_norm_eps"])
    return x, load


def hidden_states(params, ids, m: dict, *, precision: str = "highest",
                  layers: int | None = None):
    """One sequence ``ids [S]`` -> ``[S, d]`` after ``layers`` layers (all, and the
    last norm, when None)."""
    return _forward(params, ids, m, precision, layers)[0]


def logits(params, ids, m: dict, *, precision: str = "highest"):
    """``ids [S]`` -> ``[S, vocab]`` float32 logits of the next token."""
    x = hidden_states(params, ids, m, precision=precision)
    return prec.matmul(precision)(x, params["lm_head_kernel"])


def router_choice(params, ids, m: dict, layer: int, *, precision: str = "highest"):
    """The experts ``[S, k]`` that the expert layer of kept layer ``layer`` selects for
    one sequence."""
    mm, es = prec.matmul(precision), prec.einsum(precision)
    x = hidden_states(params, ids, m, precision=precision, layers=layer)
    p = params[f"layer_{layer}"]
    h = _mixed(p, x, m, mm, es)
    return route(p["moe"], _rms(h, p["ff_norm_scale"], m["rms_norm_eps"]), m, mm)[1]


def loss(params, tokens, m: dict, *, precision: str = "highest", with_load: bool = False):
    """Mean next-token NLL over the B·(S-1) targets of ``tokens`` [B, S]; with
    ``with_load`` also ``[expert layers, router's experts] int32``, how many of the
    batch's tokens chose each expert."""
    mm = prec.matmul(precision)

    def one(ids):
        x, load = _forward(params, ids, m, precision, None)

        def rows(x_blk, target, start):
            lp = jax.nn.log_softmax(mm(x_blk, params["lm_head_kernel"]), axis=-1)
            return -jnp.take_along_axis(lp, target[:, None], axis=-1)[:, 0]

        # row t's target is token t + 1; the last row has none
        return jnp.sum(_by_rows(rows, x, jnp.roll(ids, -1))[:-1]), jnp.stack(load)

    totals, load = jax.vmap(jax.checkpoint(one))(tokens)
    value = jnp.sum(totals) / (tokens.shape[0] * (tokens.shape[1] - 1))
    return (value, jnp.sum(load, axis=0)) if with_load else value


def rebalanced(params, load, m: dict):
    """``params`` with each expert layer's ``expert_bias_b`` moved by the file's
    ``moe_router_bias_update_rate`` toward balance: ``b + rate · sign(mean(load) − load)``
    over the router's experts, ``load`` a step's counts from ``loss``."""
    rate = m["moe_router_bias_update_rate"]
    moved = dict(params)
    for row, i in zip(load, (i for i, is_sparse in enumerate(sparse(m)) if is_sparse)):
        row = row.astype(jnp.float32)
        moe = dict(params[f"layer_{i}"]["moe"])
        moe["expert_bias_b"] = moe["expert_bias_b"] + rate * jnp.sign(jnp.mean(row) - row)
        moved[f"layer_{i}"] = dict(params[f"layer_{i}"], moe=moe)
    return moved


def param_shapes(m: dict) -> dict:
    """The parameter tree's paths and shapes, float32, from the widths alone."""
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    d, heads = m["hidden_size"], m["num_attention_heads"]
    nope, pe, vd, rank = (m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"],
                          m["kv_lora_rank"])
    _, count, router = held(m)
    f, shared = m["moe_intermediate_size"], m["moe_intermediate_size"] * m["n_shared_experts"]
    mla = lambda: {
        "q_kernel": f32(d, heads * (nope + pe)), "kv_a_kernel": f32(d, rank + pe),
        "kv_a_norm_scale": f32(rank), "kv_b_kernel": f32(rank, heads * (nope + vd)),
        "out_kernel": f32(heads * vd, d)}
    dense = lambda: {"w1_kernel": f32(d, m["intermediate_size"]),
                     "w3_kernel": f32(d, m["intermediate_size"]),
                     "w2_kernel": f32(m["intermediate_size"], d)}
    experts = lambda: {
        "router_kernel": f32(d, router), "expert_bias_b": f32(router),
        "shared_w1_kernel": f32(d, shared), "shared_w3_kernel": f32(d, shared),
        "shared_w2_kernel": f32(shared, d),
        "experts_w1_kernel": f32(d, count * f), "experts_w3_kernel": f32(d, count * f),
        "experts_w2_kernel": f32(f, count * d)}
    tree = {"embed_tokens": f32(m["vocab_size"], d), "lm_head_kernel": f32(d, m["vocab_size"]),
            "final_norm_scale": f32(d)}
    for i, is_sparse in enumerate(sparse(m)):
        tree[f"layer_{i}"] = {"mixer_norm_scale": f32(d), "ff_norm_scale": f32(d),
                              "mla": mla(),
                              **({"moe": experts()} if is_sparse else {"ff": dense()})}
    return tree


def batch_of(split: dict, rows):
    """The reference's view of one training batch: the rows' token sequences."""
    return jnp.asarray(split["tokens"][rows])
