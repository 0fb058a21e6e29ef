"""Plain LFM2-MoE decoder (``model_type`` ``lfm2_moe``), or one chip's share of it.

Written from the architecture's description, float32 ``jax.numpy``; imports nothing
of the program. ``m`` is the configuration file itself: the published keys at its
top level, with ``num_hidden_layers``, ``num_dense_layers``, ``num_experts`` and
``vocab_size`` as held here, ``published.num_experts`` the router's width, and
``share`` = ``{first_layer, first_expert}`` the first published layer kept and the
first expert held.

    block       h = x + mixer(rms(x));  y = h + ff(rms(h));  rms(x) = x/sqrt(mean x² + eps)·g
    conv        [B, C, X] = split3(W_in u);  z = B ⊙ X;  c_t = Σ_j w[j] ⊙ z_{t-L+1+j}
                (depthwise, causal, zeros before the start);  W_out (C ⊙ c)
    attention   q: H heads, k, v: KV heads of D; rms over D on q and on k, then RoPE
                (theta, dim i with i + D/2); causal softmax(q·k/√D)·v in groups; W_o
    dense ff    W_2 (silu(W_1 u) ⊙ W_3 u)
    sparse ff   s = sigmoid(W_r u); the k experts are the top-k of s + b; weights
                s_e / (Σ s_e + 1e-6) · routed_scaling_factor;
                Σ_{e held} w_e · W2_e (silu(W1_e u) ⊙ W3_e u): a loop over the held
                experts with masks, no sort, no capacity, no token dropped. What the
                experts held elsewhere would add is left out.
    head        the embedding, tied, over the held slice of the vocabulary, after a
                last rms; loss = mean next-token NLL over the S-1 targets a sequence

Departures, for memory alone: the loss runs one sequence at a time under
``jax.checkpoint`` (the batch's mean is the mean of its sequences' sums), blocks are
recomputed in the backward pass, and attention and the dense feed-forward walk a
sequence's rows in blocks of ``ROW_BLOCK`` (a sequence's float32 scores would be
8.6 GB at S 8192). The loops over blocks and over the held experts are ``lax.map``
and ``lax.scan``, so the program holds each body once. Compiled for a v5e, a step of
``reference/train.py`` then needs 6.8 GB of temporaries beside its 7.5 GB of state
(9.0 GB with blocks of 256 rows, 8.5 GB with 1024: the compiler's schedule, not the
arithmetic, decides). ``b``
(``expert_bias_b``) gets no gradient; ``reference/train.py`` still applies its weight
decay to it, 3e-6 of itself a step, which no comparison sees.

Leaf names are those of the program's tree (``embed_tokens``, ``final_norm_scale``,
``layer_<i>/{mixer,ff}_norm_scale``, ``layer_<i>/conv/{in_proj,conv,out_proj}_kernel``,
``layer_<i>/attn/{q,k,v,out}_kernel``, ``layer_<i>/attn/{q,k}_norm_scale``,
``layer_<i>/ff/w{1,3,2}_kernel``, ``layer_<i>/moe/{router_kernel,expert_bias_b,
experts_w{1,3,2}_kernel}``); expert matrices are column-blocked by held expert:
``experts_w1_kernel [d, n·f]``, ``experts_w2_kernel [f, n·d]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import precision as prec

MASK = -1e30
ROW_BLOCK = 512         # rows of a sequence a row-wise stage holds at once


def _by_rows(fn, x, *more):
    """``fn`` over blocks of ``ROW_BLOCK`` rows of ``x`` (and of ``more``), one block
    at a time and recomputed in the backward pass: for memory, where rows are
    independent. ``fn(block, *more_blocks, first_row)``."""
    s = x.shape[0]
    rows = ROW_BLOCK if s % ROW_BLOCK == 0 else s
    cut = lambda a: a.reshape((s // rows, rows) + a.shape[1:])
    starts = jnp.arange(0, s, rows)
    out = jax.lax.map(lambda args: jax.checkpoint(fn)(*args),
                      (cut(x), *map(cut, more), starts))
    return out.reshape((s,) + out.shape[2:])


def kinds(m: dict) -> list[str]:
    first = m.get("share", {}).get("first_layer", 0)
    return list(m["layer_types"][first:first + m["num_hidden_layers"]])


def held(m: dict) -> tuple[int, int, int]:
    """(first held expert, how many are held, the router's width)."""
    return (m.get("share", {}).get("first_expert", 0), m["num_experts"],
            m.get("published", {}).get("num_experts", m["num_experts"]))


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [S, H, D]: dim i rotates with dim i + D/2 by pos · theta^(-2i/D)."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def conv_mixer(p, u, m, mm, es):
    taps, s = m["conv_L_cache"], u.shape[0]
    b, c, x = jnp.split(mm(u, p["in_proj_kernel"]), 3, axis=-1)
    z = jnp.pad(b * x, ((taps - 1, 0), (0, 0)))
    conv = sum(p["conv_kernel"][j] * z[j:j + s] for j in range(taps))
    return mm(c * conv, p["out_proj_kernel"])


def attention(p, u, m, mm, es):
    s, d = u.shape
    heads, kvh = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // heads
    theta = float(m["rope_parameters"]["rope_theta"])
    q = mm(u, p["q_kernel"]).reshape(s, heads, hd)
    k = mm(u, p["k_kernel"]).reshape(s, kvh, hd)
    v = mm(u, p["v_kernel"]).reshape(s, kvh, hd)
    q = _rope(_rms(q, p["q_norm_scale"], m["norm_eps"]), theta)
    k = _rope(_rms(k, p["k_norm_scale"], m["norm_eps"]), theta)
    k, v = (jnp.repeat(x, heads // kvh, axis=1) for x in (k, v))

    def rows(q_blk, start):
        scores = es("qhd,khd->hqk", q_blk, k) / jnp.sqrt(jnp.float32(hd))
        seen = (start + jnp.arange(q_blk.shape[0]))[:, None] >= jnp.arange(s)[None]
        w = jax.nn.softmax(jnp.where(seen[None], scores, MASK), axis=-1)
        return es("hqk,khd->qhd", w, v)

    return mm(_by_rows(rows, q).reshape(s, d), p["out_kernel"])


def dense_ff(p, u, m, mm, es):
    rows = lambda u, _: mm(jax.nn.silu(mm(u, p["w1_kernel"])) * mm(u, p["w3_kernel"]),
                           p["w2_kernel"])
    return _by_rows(rows, u)


def route(p, u, m, mm):
    """``(weights [S, k], experts [S, k])`` over all the router's experts."""
    scores = jax.nn.sigmoid(mm(u, p["router_kernel"]))
    _, experts = jax.lax.top_k(scores + jax.lax.stop_gradient(p["expert_bias_b"]),
                               m["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    return weights * m.get("routed_scaling_factor", 1), experts


def sparse_ff(p, u, m, mm, es):
    first, count, _ = held(m)
    weights, experts = route(p, u, m, mm)
    # [rows, n·width] -> one [rows, width] matrix a held expert
    per_expert = lambda name: jnp.moveaxis(
        p[name].reshape(p[name].shape[0], count, -1), 1, 0)

    def add_expert(out, e_and_its_matrices):
        e, w1, w3, w2 = e_and_its_matrices
        w_e = jnp.sum(jnp.where(experts == first + e, weights, 0.0), axis=-1)
        return out + w_e[:, None] * mm(jax.nn.silu(mm(u, w1)) * mm(u, w3), w2), None

    out, _ = jax.lax.scan(
        jax.checkpoint(add_expert), jnp.zeros_like(u),
        (jnp.arange(count), per_expert("experts_w1_kernel"),
         per_expert("experts_w3_kernel"), per_expert("experts_w2_kernel")))
    return out


def _mix(p, x, m, kind, mm, es):
    mixer, group = (conv_mixer, "conv") if kind == "conv" else (attention, "attn")
    return x + mixer(p[group], _rms(x, p["mixer_norm_scale"], m["norm_eps"]), m, mm, es)


def _block(p, x, m, kind, sparse, mm, es):
    h = _mix(p, x, m, kind, mm, es)
    u = _rms(h, p["ff_norm_scale"], m["norm_eps"])
    return h + (sparse_ff(p["moe"], u, m, mm, es) if sparse
                else dense_ff(p["ff"], u, m, mm, es))


def hidden_states(params, ids, m: dict, *, precision: str = "highest",
                  layers: int | None = None):
    """One sequence ``ids [S]`` -> ``[S, d]`` after ``layers`` blocks (all, and the
    last norm, when None)."""
    mm, es = prec.matmul(precision), prec.einsum(precision)
    x = params["embed_tokens"][ids]
    for i, kind in enumerate(kinds(m)[:layers]):
        block = lambda p, x, kind=kind, i=i: _block(
            p, x, m, kind, i >= m["num_dense_layers"], mm, es)
        x = jax.checkpoint(block)(params[f"layer_{i}"], x)
    if layers is None:
        x = _rms(x, params["final_norm_scale"], m["norm_eps"])
    return x


def logits(params, ids, m: dict, *, precision: str = "highest"):
    """``ids [S]`` -> ``[S, vocab]`` float32 logits of the next token."""
    x = hidden_states(params, ids, m, precision=precision)
    return prec.einsum(precision)("sd,vd->sv", x, params["embed_tokens"])


def router_choice(params, ids, m: dict, layer: int, *, precision: str = "highest"):
    """The experts ``[S, k]`` that sparse layer ``layer`` (an index into the kept
    layers) selects for one sequence."""
    x = hidden_states(params, ids, m, precision=precision, layers=layer)
    p = params[f"layer_{layer}"]
    mm, es = prec.matmul(precision), prec.einsum(precision)
    h = _mix(p, x, m, kinds(m)[layer], mm, es)
    return route(p["moe"], _rms(h, p["ff_norm_scale"], m["norm_eps"]), m, mm)[1]


def loss(params, tokens, m: dict, *, precision: str = "highest"):
    """Mean next-token NLL over the B·(S-1) targets of ``tokens`` [B, S]."""

    def one(ids):
        lp = jax.nn.log_softmax(logits(params, ids, m, precision=precision)[:-1], axis=-1)
        return -jnp.sum(jnp.take_along_axis(lp, ids[1:, None], axis=-1))

    total = jnp.sum(jax.lax.map(jax.checkpoint(one), tokens))
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def param_shapes(m: dict) -> dict:
    """The parameter tree's paths and shapes, float32, from the widths alone."""
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    d, heads, kvh = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // heads
    _, count, router = held(m)
    f, wide = m["moe_intermediate_size"], m["intermediate_size"]
    tree = {"embed_tokens": f32(m["vocab_size"], d), "final_norm_scale": f32(d)}
    for i, kind in enumerate(kinds(m)):
        layer = {"mixer_norm_scale": f32(d), "ff_norm_scale": f32(d)}
        if kind == "conv":
            layer["conv"] = {"in_proj_kernel": f32(d, 3 * d),
                             "conv_kernel": f32(m["conv_L_cache"], d),
                             "out_proj_kernel": f32(d, d)}
        else:
            layer["attn"] = {"q_kernel": f32(d, heads * hd), "k_kernel": f32(d, kvh * hd),
                             "v_kernel": f32(d, kvh * hd), "out_kernel": f32(heads * hd, d),
                             "q_norm_scale": f32(hd), "k_norm_scale": f32(hd)}
        if i < m["num_dense_layers"]:
            layer["ff"] = {"w1_kernel": f32(d, wide), "w3_kernel": f32(d, wide),
                           "w2_kernel": f32(wide, d)}
        else:
            layer["moe"] = {"router_kernel": f32(d, router), "expert_bias_b": f32(router),
                            "experts_w1_kernel": f32(d, count * f),
                            "experts_w3_kernel": f32(d, count * f),
                            "experts_w2_kernel": f32(f, count * d)}
        tree[f"layer_{i}"] = layer
    return tree


def batch_of(split: dict, rows):
    """The reference's view of one training batch: the rows' token sequences."""
    return jnp.asarray(split["tokens"][rows])
