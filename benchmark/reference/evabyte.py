"""Plain EvaByte decoder (``model_type`` ``evabyte``), or one chip's share of it.

Written from the architecture's description, float32 ``jax.numpy``; imports nothing
of the program. ``m`` is the configuration file itself: the published keys at its top
level, with ``num_hidden_layers`` and ``num_attention_heads`` as held here,
``published.num_attention_heads`` the whole layer's heads (a head's width is
``hidden_size`` over that) and ``share.mlp_columns`` the held columns of the feed-forward.

    layer       h = x + W_o eva(rms(x));  y = h + W_2 (silu(W_1 u) ⊙ W_3 u), u = rms(h);
                rms(x) = x / sqrt(mean x² + eps) · (1 + g), g the leaf (norm_add_unit_offset)
    eva         q_t = R_t(W_q u_t), k_t = R_t(W_k u_t), v_t = W_v u_t; R_t the rotation at
                rope_theta over a head's whole width, half-split pairing; no bias.
                Chunk j holds tokens c·j … c·j + c − 1 (c = chunk_size): a_jm = softmax_m
                (φ · k_m) over the chunk, k̃_j = Σ_m a_jm k_m + μ, ṽ_j = Σ_m a_jm v_m, with φ, μ
                the head's learned vectors (the summaries: a plain reshape to [S/c, c, d]).
                Query t of window w = ⌊t/W⌋ (W = window_size, M = W/c) sees the keys m of
                its window with m ≤ t and the summaries j < M·w: one softmax, scale d^-½:
                o_t = (Σ_m e^{s q·k_m} v_m + Σ_j e^{s q·k̃_j} ṽ_j) / (Σ_m e^{s q·k_m} + Σ_j e^{s q·k̃_j})
    head        logits_t = W_head rms(x_t), [P · vocab] with P = num_pred_heads; head i of
                place t predicts token t + 1 + i; loss = mean cross-entropy over the heads
                and places with t + 1 + i < S

A share's out-projection and down-projection sum over the held heads and columns;
what the other chip would add is left out, as in the program.

Departures, for memory alone (none changes a number): the loss runs one sequence at a
time under ``jax.checkpoint``; a layer is recomputed in the backward pass, and inside it
the mixer runs ``HEAD_GROUP`` heads at a time (their columns of W_q, W_k, W_v and rows of
W_o), each group recomputed too; the scores are materialised ``SCORE_BLOCK`` query rows at
a time, against the ``W`` keys of the rows' own window (a slice) and all ``S/c`` summaries
(masked by window); the feed-forward and the head walk a sequence's rows in blocks of
``ROW_BLOCK``.

Leaf names are those of the program's tree (``embed_tokens``, ``lm_head_kernel``,
``final_norm_offset``, ``layer_<i>/{mixer_norm_offset, ff_norm_offset}``,
``layer_<i>/eva/{q,k,v,out}_kernel``, ``layer_<i>/eva/{adaptive_phi, adaptive_mu_k}``,
``layer_<i>/ff/w{1,2,3}_kernel``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import precision as prec

MASK = -1e30
ROW_BLOCK = 512         # rows of a sequence a row-wise stage holds at once
SCORE_BLOCK = 256       # query rows whose scores stand at once
HEAD_GROUP = 4          # heads of a mixer computed together


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


def head_dim(m: dict) -> int:
    heads = m.get("published", {}).get("num_attention_heads", m["num_attention_heads"])
    return m["hidden_size"] // heads


def mlp_columns(m: dict) -> int:
    return m.get("share", {}).get("mlp_columns", m["intermediate_size"])


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + g)


def rotate(x, theta: float):
    """``x [S, H, d]`` rotated by its row's position: channel i pairs with i + d/2."""
    s, _, d = x.shape
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def summaries(k, v, phi, mu, chunk: int, es):
    """``k``, ``v`` ``[S, H, d]``, ``phi``, ``mu`` ``[H, d]`` -> ``(k̃, ṽ) [S/chunk, H, d]``."""
    s, heads, d = k.shape
    kc, vc = (x.reshape(s // chunk, chunk, heads, d) for x in (k, v))
    a = jax.nn.softmax(es("jmhd,hd->jmh", kc, phi), axis=1)
    return es("jmh,jmhd->jhd", a, kc) + mu, es("jmh,jmhd->jhd", a, vc)


def eva_attention(q, k, v, ks, vs, window: int, chunk: int, es):
    """``q``, ``k``, ``v`` ``[S, H, d]``, ``ks``, ``vs`` ``[S/chunk, H, d]`` -> ``[S, H, d]``."""
    s, _, d = q.shape
    per_window, scale = window // chunk, d ** -0.5
    block = SCORE_BLOCK if window % SCORE_BLOCK == 0 else window

    def rows(q_blk, start):
        first = (start // window) * window          # the rows' window starts here
        k_w = jax.lax.dynamic_slice_in_dim(k, first, window)
        v_w = jax.lax.dynamic_slice_in_dim(v, first, window)
        t = start + jnp.arange(q_blk.shape[0])
        near = jnp.where(first + jnp.arange(window)[None, :] <= t[:, None],
                         es("qhd,khd->hqk", q_blk, k_w) * scale, MASK)
        far = jnp.where(jnp.arange(s // chunk)[None, :] < per_window * (start // window),
                        es("qhd,jhd->hqj", q_blk, ks) * scale, MASK)
        w = jax.nn.softmax(jnp.concatenate([near, far], axis=-1), axis=-1)
        return es("hqk,khd->qhd", w, jnp.concatenate([v_w, vs], axis=0))

    return _by_rows(rows, q, rows=block)


def eva_mixer(p, u, m, mm, es):
    """``W_o`` over the held heads' attention, a group of heads at a time."""
    s, d = u.shape[0], head_dim(m)
    heads, theta = m["num_attention_heads"], float(m["rope_theta"])
    group = HEAD_GROUP if heads % HEAD_GROUP == 0 else heads

    @jax.checkpoint
    def some_heads(u, wq, wk, wv, wo, phi, mu):
        shaped = lambda w: mm(u, w).reshape(s, group, d)
        q, k, v = rotate(shaped(wq), theta), rotate(shaped(wk), theta), shaped(wv)
        ks, vs = summaries(k, v, phi, mu, m["chunk_size"], es)
        o = eva_attention(q, k, v, ks, vs, m["window_size"], m["chunk_size"], es)
        return mm(o.reshape(s, group * d), wo)

    out = jnp.zeros_like(u)
    for g in range(heads // group):
        cols = slice(g * group * d, (g + 1) * group * d)
        held = slice(g * group, (g + 1) * group)
        out = out + some_heads(u, p["q_kernel"][:, cols], p["k_kernel"][:, cols],
                               p["v_kernel"][:, cols], p["out_kernel"][cols],
                               p["adaptive_phi"][held], p["adaptive_mu_k"][held])
    return out


def dense_ff(p, u, mm):
    return _by_rows(lambda rows, _: mm(jax.nn.silu(mm(rows, p["w1_kernel"]))
                                       * mm(rows, p["w3_kernel"]), p["w2_kernel"]), u)


def _layer(p, x, m, mm, es):
    eps = m["rms_norm_eps"]
    h = x + eva_mixer(p["eva"], _rms(x, p["mixer_norm_offset"], eps), m, mm, es)
    return h + dense_ff(p["ff"], _rms(h, p["ff_norm_offset"], eps), mm)


def hidden_states(params, ids, m: dict, *, precision: str = "highest",
                  layers: int | None = None):
    """One sequence ``ids [S]`` -> ``[S, hidden]`` after ``layers`` layers (all, and the
    last norm, when None)."""
    mm, es = prec.matmul(precision), prec.einsum(precision)
    x = params["embed_tokens"][ids]
    for i in range(m["num_hidden_layers"])[:layers]:
        x = jax.checkpoint(lambda p, x: _layer(p, x, m, mm, es))(params[f"layer_{i}"], x)
    if layers is None:
        x = _rms(x, params["final_norm_offset"], m["rms_norm_eps"])
    return x


def logits(params, ids, m: dict, *, precision: str = "highest"):
    """``ids [S]`` -> ``[S, P, vocab]`` float32: head i's logits of token t + 1 + i."""
    x = hidden_states(params, ids, m, precision=precision)
    return prec.matmul(precision)(x, params["lm_head_kernel"]).reshape(
        ids.shape[0], m["num_pred_heads"], m["vocab_size"])


def targets_per_sequence(m: dict, seq_len: int) -> int:
    heads = m["num_pred_heads"]
    return heads * (seq_len - 1) - heads * (heads - 1) // 2


def loss(params, tokens, m: dict, *, precision: str = "highest"):
    """Mean cross-entropy over the batch's (place t, head i) with t + 1 + i < S."""
    mm = prec.matmul(precision)
    heads, vocab, s = m["num_pred_heads"], m["vocab_size"], tokens.shape[1]

    def one(ids):
        x = hidden_states(params, ids, m, precision=precision)
        # targets[t, i] = ids[t + 1 + i]; the rolled-in ids past the end are masked
        targets = jnp.stack([jnp.roll(ids, -(1 + i)) for i in range(heads)], axis=-1)

        def rows(x_blk, target, start):
            lp = jax.nn.log_softmax(
                mm(x_blk, params["lm_head_kernel"]).reshape(-1, heads, vocab), axis=-1)
            picked = jnp.take_along_axis(lp, target[..., None], axis=-1)[..., 0]
            t = start + jnp.arange(x_blk.shape[0])
            return -jnp.sum(jnp.where(t[:, None] + 1 + jnp.arange(heads)[None, :] < s,
                                      picked, 0.0), axis=-1)

        return jnp.sum(_by_rows(rows, x, targets))

    totals = jax.lax.map(jax.checkpoint(one), tokens)
    return jnp.sum(totals) / (tokens.shape[0] * targets_per_sequence(m, s))


def param_shapes(m: dict) -> dict:
    """The parameter tree's paths and shapes, float32, from the widths alone."""
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    d, heads, hd, f = m["hidden_size"], m["num_attention_heads"], head_dim(m), mlp_columns(m)
    tree = {"embed_tokens": f32(m["vocab_size"], d),
            "lm_head_kernel": f32(d, m["num_pred_heads"] * m["vocab_size"]),
            "final_norm_offset": f32(d)}
    for i in range(m["num_hidden_layers"]):
        tree[f"layer_{i}"] = {
            "mixer_norm_offset": f32(d), "ff_norm_offset": f32(d),
            "eva": {"q_kernel": f32(d, heads * hd), "k_kernel": f32(d, heads * hd),
                    "v_kernel": f32(d, heads * hd), "out_kernel": f32(heads * hd, d),
                    "adaptive_phi": f32(heads, hd), "adaptive_mu_k": f32(heads, hd)},
            "ff": {"w1_kernel": f32(d, f), "w3_kernel": f32(d, f), "w2_kernel": f32(f, d)}}
    return tree


def batch_of(split: dict, rows):
    """The reference's view of one training batch: the rows' token sequences."""
    return jnp.asarray(split["tokens"][rows])
