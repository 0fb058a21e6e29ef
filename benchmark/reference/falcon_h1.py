"""Plain Falcon-H1 decoder (``model_type`` ``falcon_h1``), or one chip's share of it.

Written from the architecture's description and the published ``falcon_h1`` modelling code
its keys are read by, float32 ``jax.numpy``; imports nothing of the program. ``m`` is the
configuration file itself: the published keys at its top level, with the keys that count
layers, attention heads, Mamba-2 heads, groups and channels and ids as held here, and
``share.mlp_columns`` the held columns of the feed-forward. The forward multipliers
(fourteen numbers under eleven keys) are the file's, each applied to the activation the
published code applies it to.

    embedding   x = embedding_multiplier · E[ids]
    layer       u = rms(x; w_mixer);  x ← x + ssm_out_multiplier · mamba(u)
                + attention_out_multiplier · attention(attention_in_multiplier · u);
                v = rms(x; w_ff);  x ← x + ff(v);   rms(x; w) = x/sqrt(mean x² + eps) · w
                (ONE norm feeds both mixers, which run side by side on the same input)
    mamba-2     p = (W_in (ssm_in_multiplier · u)) ⊙ μ, μ constant on each of the five
                segments [z | x | B | C | dt] of the held columns: ssm_multipliers[0..4];
                [x | B | C] ← silu(conv4([x | B | C]) + b) (depthwise, causal, zeros before
                the start);  Δ = softplus(dt + dt_bias), no clamp;  A = −exp(A_log);  per
                head and TOKEN, one after the other: S_t = exp(Δ_t A) S_{t−1} + Δ_t x_t ⊗ B_t
                (S_0 = 0), y_t = S_t C_t + D x_t (heads of group g read B, C of group g);
                W_out (w_n ⊙ rms_group(y ⊙ silu(z))): gated, then normed over the HELD
                channels of a group (``mamba_rms_norm`` with ``mamba_norm_before_gate`` false)
    attention   q: H heads, k, v: KV heads of head_dim;  k ← key_multiplier · k;  q and k
                turn by position the published way, x·cos + rotate_half(x)·sin over all of
                a head's channels, angles t · theta^(−2j/D) on both halves;  the KV heads
                repeated to the query heads;  causal softmax(q·k/√D)·v;  W_o
    ff          down_mult · W_down (W_up v ⊙ silu(gate_mult · W_gate v)) over the held columns,
                (gate_mult, down_mult) = mlp_multipliers
    head        lm_head_multiplier · W_head rms(x; w_final), its own matrix over the held slice
                of the vocabulary; loss = mean next-token NLL over the S−1 targets a sequence

What the heads, channels and columns held elsewhere would add to an out-, o- or
down-projection is left out. The recurrence is the definition, a ``lax.scan`` over time: no
chunks, no decay matrices. Departures, for memory alone: the loss runs one sequence at a
time, layers are recomputed in the backward pass, the scan over time is checkpointed every
``TIME_BLOCK`` tokens (a sequence's 8192 states of 8 x 128 x 256 floats would be 8.6 GB a
layer), attention's scores stand ``SCORE_BLOCK`` query rows at a time against every key,
and the head walks a sequence's rows in blocks of ``ROW_BLOCK``.

Leaf names are those of the program's tree (``embed_tokens``, ``lm_head_kernel``,
``final_norm_scale``, ``layer_<i>/{mixer_norm_scale, ff_norm_scale}``,
``layer_<i>/mamba/{in_proj_kernel, conv_kernel, conv_bias, dt_bias, A_log, D_scale,
gate_norm_scale, out_proj_kernel}``, ``layer_<i>/attn/{q,k,v,out}_kernel``,
``layer_<i>/ff/{w1 (gate), w3 (up), w2 (down)}_kernel``). The lines this file shares with
``nemotron_h.py`` and ``qwen3_next.py`` are copied, not imported: one model's reference does
not follow another's edits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import precision as prec

MASK = -1e30
ROW_BLOCK = 512         # rows of a sequence the head holds at once
SCORE_BLOCK = 256       # query rows whose scores against every key stand at once
TIME_BLOCK = 128        # tokens of the recurrence between two kept states


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


def mlp_columns(m: dict) -> int:
    return m.get("share", {}).get("mlp_columns", m["intermediate_size"])


def segments(m: dict) -> list[int]:
    """Widths of the in-projection's five segments ``[z | x | B | C | dt]`` as held."""
    inner = m["mamba_n_heads"] * m["mamba_d_head"]
    bc = m["mamba_n_groups"] * m["mamba_d_state"]
    return [inner, inner, bc, bc, m["mamba_n_heads"]]


def mup_vector(m: dict):
    """μ: ``ssm_multipliers[i]`` on every column of segment ``i``."""
    return jnp.concatenate([jnp.full((width,), value, jnp.float32)
                            for width, value in zip(segments(m), m["ssm_multipliers"])])


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def recurrence(x, dt, a, b, c, es):
    """``x [S, H, P]``, ``dt [S, H]``, ``a [H]``, ``b``, ``c`` ``[S, H, N]`` -> ``y [S, H, P]``,
    token by token from a zero state."""
    s, heads, p = x.shape

    def token(state, now):
        x_t, dt_t, b_t, c_t = now
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, es("hpn,hn->hp", state, c_t)

    steps = TIME_BLOCK if s % TIME_BLOCK == 0 else s
    cut = lambda v: v.reshape((s // steps, steps) + v.shape[1:])
    block = jax.checkpoint(lambda state, nows: jax.lax.scan(token, state, nows))
    _, y = jax.lax.scan(block, jnp.zeros((heads, p, b.shape[-1]), jnp.float32),
                        tuple(map(cut, (x, dt, b, c))))
    return y.reshape(s, heads, p)


def mamba_mixer(p, u, m, mm, es):
    s = u.shape[0]
    heads, groups = m["mamba_n_heads"], m["mamba_n_groups"]
    hd, n, taps = m["mamba_d_head"], m["mamba_d_state"], m["mamba_d_conv"]
    inner, bc = heads * hd, groups * n
    projected = mm(u * m["ssm_in_multiplier"], p["in_proj_kernel"]) * mup_vector(m)
    z, xbc, dt = jnp.split(projected, [inner, 2 * inner + 2 * bc], axis=-1)
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(p["conv_kernel"][j] * padded[j:j + s] for j in range(taps))
                      + p["conv_bias"])
    x, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)
    x = x.reshape(s, heads, hd)
    of_head = lambda v: jnp.repeat(v.reshape(s, groups, n), heads // groups, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), of_head(b), of_head(c), es) \
        + p["D_scale"][:, None] * x
    gated = (y.reshape(s, inner) * jax.nn.silu(z)).reshape(s, groups, inner // groups)
    normed = _rms(gated, 1.0, m["rms_norm_eps"]).reshape(s, inner) * p["gate_norm_scale"]
    return mm(normed, p["out_proj_kernel"])


def _rotated(x, m):
    """``x [S, heads, D]``: every channel turns by the row's position, the published way."""
    s, _, d = x.shape
    inv_freq = 1.0 / (float(m["rope_theta"]) ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    rotate_half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(angles) + rotate_half * jnp.sin(angles)


def attention_mixer(p, a, m, mm, es):
    s = a.shape[0]
    heads, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q = mm(a, p["q_kernel"]).reshape(s, heads, hd)
    k = (mm(a, p["k_kernel"]) * m["key_multiplier"]).reshape(s, kv, hd)
    v = mm(a, p["v_kernel"]).reshape(s, kv, hd)
    q, k = _rotated(q, m), _rotated(k, m)
    k, v = (jnp.repeat(x, heads // kv, axis=1) for x in (k, v))

    def rows(q_blk, start):
        scores = es("qhd,khd->hqk", q_blk, k) / jnp.sqrt(jnp.float32(hd))
        seen = (start + jnp.arange(q_blk.shape[0]))[:, None] >= jnp.arange(s)[None]
        w = jax.nn.softmax(jnp.where(seen[None], scores, MASK), axis=-1)
        return es("hqk,khd->qhd", w, v)

    out = _by_rows(rows, q, rows=SCORE_BLOCK).reshape(s, heads * hd)
    return mm(out, p["out_kernel"])


def mixers(p, u, m, mm, es):
    """What the two mixers add to the stream, from their one normed input ``u``."""
    return m["ssm_out_multiplier"] * mamba_mixer(p["mamba"], u, m, mm, es) \
        + m["attention_out_multiplier"] * attention_mixer(
            p["attn"], u * m["attention_in_multiplier"], m, mm, es)


def dense_ff(p, v, m, mm):
    gate_mult, down_mult = m["mlp_multipliers"]
    hidden = mm(v, p["w3_kernel"]) * jax.nn.silu(mm(v, p["w1_kernel"]) * gate_mult)
    return mm(hidden, p["w2_kernel"]) * down_mult


def _layer(p, x, m, mm, es):
    eps = m["rms_norm_eps"]
    x = x + mixers(p, _rms(x, p["mixer_norm_scale"], eps), m, mm, es)
    return x + dense_ff(p["ff"], _rms(x, p["ff_norm_scale"], eps), m, mm)


def hidden_states(params, ids, m: dict, *, precision: str = "highest"):
    """One sequence ``ids [S]`` -> ``[S, d]`` after every layer and the last norm."""
    mm, es = prec.matmul(precision), prec.einsum(precision)
    x = params["embed_tokens"][ids] * m["embedding_multiplier"]
    for i in range(m["num_hidden_layers"]):
        x = jax.checkpoint(lambda p, x: _layer(p, x, m, mm, es))(params[f"layer_{i}"], x)
    return _rms(x, params["final_norm_scale"], m["rms_norm_eps"])


def logits(params, ids, m: dict, *, precision: str = "highest"):
    """``ids [S]`` -> ``[S, vocab]`` float32 logits of the next token."""
    x = hidden_states(params, ids, m, precision=precision)
    return prec.matmul(precision)(x, params["lm_head_kernel"]) * m["lm_head_multiplier"]


def loss(params, tokens, m: dict, *, precision: str = "highest"):
    """Mean next-token NLL over the B·(S-1) targets of ``tokens`` [B, S]."""
    mm = prec.matmul(precision)

    def one(ids):
        x = hidden_states(params, ids, m, precision=precision)

        def rows(x_blk, target, start):
            lp = jax.nn.log_softmax(
                mm(x_blk, params["lm_head_kernel"]) * m["lm_head_multiplier"], axis=-1)
            return -jnp.take_along_axis(lp, target[:, None], axis=-1)[:, 0]

        # row t's target is token t + 1; the last row has none
        return jnp.sum(_by_rows(rows, x, jnp.roll(ids, -1))[:-1])

    totals = jax.lax.map(jax.checkpoint(one), tokens)
    return jnp.sum(totals) / (tokens.shape[0] * (tokens.shape[1] - 1))


def param_shapes(m: dict) -> dict:
    """The parameter tree's paths and shapes, float32, from the widths alone."""
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    d, heads, kvh, hd = (m["hidden_size"], m["num_attention_heads"],
                         m["num_key_value_heads"], m["head_dim"])
    widths = segments(m)
    inner, conv, columns = widths[0], sum(widths[1:4]), mlp_columns(m)
    layer = lambda: {
        "mixer_norm_scale": f32(d), "ff_norm_scale": f32(d),
        "mamba": {"in_proj_kernel": f32(d, sum(widths)),
                  "conv_kernel": f32(m["mamba_d_conv"], conv), "conv_bias": f32(conv),
                  "dt_bias": f32(m["mamba_n_heads"]), "A_log": f32(m["mamba_n_heads"]),
                  "D_scale": f32(m["mamba_n_heads"]), "gate_norm_scale": f32(inner),
                  "out_proj_kernel": f32(inner, d)},
        "attn": {"q_kernel": f32(d, heads * hd), "k_kernel": f32(d, kvh * hd),
                 "v_kernel": f32(d, kvh * hd), "out_kernel": f32(heads * hd, d)},
        "ff": {"w1_kernel": f32(d, columns), "w3_kernel": f32(d, columns),
               "w2_kernel": f32(columns, d)}}
    tree = {"embed_tokens": f32(m["vocab_size"], d), "lm_head_kernel": f32(d, m["vocab_size"]),
            "final_norm_scale": f32(d)}
    for i in range(m["num_hidden_layers"]):
        tree[f"layer_{i}"] = layer()
    return tree


def batch_of(split: dict, rows):
    """The reference's view of one training batch: the rows' token sequences."""
    return jnp.asarray(split["tokens"][rows])
