"""Plain Nemotron-H decoder (``model_type`` ``nemotron_h``), or one chip's share of it.

Written from the architecture's description, float32 ``jax.numpy``; imports nothing
of the program. ``m`` is the configuration file itself: the published keys at its
top level, with the keys that count layers, routed experts, Mamba-2 heads and
groups, attention heads and ids as held here, ``published.n_routed_experts`` the
router's width, and ``share`` = ``{first_layer, first_expert, shared_expert_columns}``.

    layer       x + sublayer(rms(x)), the sublayer by the layer's letter in
                hybrid_override_pattern;  rms(x) = x/sqrt(mean x² + eps)·g
    M  mamba-2  [z | xBC | dt] = W_in u;  xBC = silu(conv4(xBC) + b) (depthwise, causal,
                zeros before the start);  [X | B | C] = split(xBC);  Δ = softplus(dt +
                dt_bias), A = −exp(A_log);  per head and TOKEN, one after the other:
                S_t = exp(Δ_t A) S_{t−1} + Δ_t X_t ⊗ B_t (S_0 = 0), Y_t = S_t C_t + D X_t
                (heads of group g read B, C of group g);  W_out (w ⊙ rms_group(Y ⊙ silu(z)))
    *  attention  q: H heads, k, v: KV heads of head_dim; no positions, no q/k norm;
                causal softmax(q·k/√D)·v in groups; W_o
    E  experts  s = sigmoid(W_r u); the k experts are the top-k of s + b; weights
                scaling · s_e / (Σ s_e + 1e-20);  l = W_fc1 u;
                W_fc2 Σ_{e held} w_e W2_e relu(W1_e l)²  +  W_s2 relu(W_s1 u)²:
                a loop over the held experts with masks, no sort, no capacity, no token
                dropped. What the experts (and the heads and shared-expert columns) held
                elsewhere would add is left out.
    head        its own matrix over the held slice of the vocabulary, after a last
                rms; loss = mean next-token NLL over the S-1 targets a sequence

The recurrence is the definition, a ``lax.scan`` over time: no chunks, no decay
matrices. Departures, for memory alone: the loss runs one sequence at a time under
``jax.checkpoint``, layers are recomputed in the backward pass (and groups of
``LAYER_GROUP`` layers once more, so that a sequence keeps 3 + 4 layer inputs of 134 MB
and not 11), the scan over time is
checkpointed every ``TIME_BLOCK`` tokens (a sequence's 8192 states of 16 x 64 x 128
floats would be 4.3 GB a layer), and attention and the head walk a sequence's rows in
blocks of ``ROW_BLOCK``. ``b`` (``expert_bias_b``) gets no gradient: where the file
gives ``moe_router_bias_update_rate`` it moves after a step by that rate, up for an
expert that fewer tokens chose than the mean over the router's experts and down for
one that more did (``loss(..., with_load=True)`` hands out the counts, ``rebalanced``
moves the biases).

Leaf names are those of the program's tree (``embed_tokens``, ``lm_head_kernel``,
``final_norm_scale``, ``layer_<i>/norm_scale``, ``layer_<i>/mamba/{in_proj_kernel,
conv_kernel, conv_bias, dt_bias, A_log, D_scale, gate_norm_scale, out_proj_kernel}``,
``layer_<i>/attn/{q,k,v,out}_kernel``, ``layer_<i>/moe/{router_kernel, expert_bias_b,
fc1_latent_kernel, fc2_latent_kernel, shared_w{1,2}_kernel, experts_w{1,2}_kernel}``);
expert matrices are column-blocked by held expert: ``experts_w1_kernel [latent, n·f]``,
``experts_w2_kernel [f, n·latent]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import precision as prec

MASK = -1e30
ROW_BLOCK = 512         # rows of a sequence a row-wise stage holds at once
TIME_BLOCK = 128        # tokens of the recurrence between two kept states
LAYER_GROUP = 4         # layers between two kept inputs; inside a group each layer's is kept
LETTERS = {"M": "mamba", "*": "attention", "E": "moe"}


def _by_rows(fn, x, *more):
    """``fn`` over blocks of ``ROW_BLOCK`` rows of ``x`` (and of ``more``), one block
    at a time and recomputed in the backward pass. ``fn(block, *more_blocks, first_row)``."""
    s = x.shape[0]
    rows = ROW_BLOCK if s % ROW_BLOCK == 0 else s
    cut = lambda a: a.reshape((s // rows, rows) + a.shape[1:])
    starts = jnp.arange(0, s, rows)
    out = jax.lax.map(lambda args: jax.checkpoint(fn)(*args),
                      (cut(x), *map(cut, more), starts))
    return out.reshape((s,) + out.shape[2:])


def kinds(m: dict) -> list[str]:
    first = m.get("share", {}).get("first_layer", 0)
    letters = m["hybrid_override_pattern"][first:first + m["num_hidden_layers"]]
    return [LETTERS[c] for c in letters]


def held(m: dict) -> tuple[int, int, int]:
    """(first held expert, how many are held, the router's width)."""
    return (m.get("share", {}).get("first_expert", 0), m["n_routed_experts"],
            m.get("published", {}).get("n_routed_experts", m["n_routed_experts"]))


def shared_columns(m: dict) -> int:
    whole = m["moe_shared_expert_intermediate_size"] * m.get("n_shared_experts", 1)
    return m.get("share", {}).get("shared_expert_columns", whole)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


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
    heads, groups = m["mamba_num_heads"], m["n_groups"]
    hd, n, taps = m["mamba_head_dim"], m["ssm_state_size"], m["conv_kernel"]
    inner, bc = heads * hd, groups * n
    z, xbc, dt = jnp.split(mm(u, p["in_proj_kernel"]), [inner, 2 * inner + 2 * bc], axis=-1)
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
    normed = _rms(gated, 1.0, m["norm_eps"]).reshape(s, inner) * p["gate_norm_scale"]
    return mm(normed, p["out_proj_kernel"])


def attention(p, u, m, mm, es):
    s = u.shape[0]
    heads, kvh, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q = mm(u, p["q_kernel"]).reshape(s, heads, hd)
    k = mm(u, p["k_kernel"]).reshape(s, kvh, hd)
    v = mm(u, p["v_kernel"]).reshape(s, kvh, hd)
    k, v = (jnp.repeat(x, heads // kvh, axis=1) for x in (k, v))

    def rows(q_blk, start):
        scores = es("qhd,khd->hqk", q_blk, k) / jnp.sqrt(jnp.float32(hd))
        seen = (start + jnp.arange(q_blk.shape[0]))[:, None] >= jnp.arange(s)[None]
        w = jax.nn.softmax(jnp.where(seen[None], scores, MASK), axis=-1)
        return es("hqk,khd->qhd", w, v)

    return mm(_by_rows(rows, q).reshape(s, heads * hd), p["out_kernel"])


def route(p, u, m, mm):
    """``(weights [S, k], experts [S, k])`` over all the router's experts."""
    scores = jax.nn.sigmoid(mm(u, p["router_kernel"]))
    _, experts = jax.lax.top_k(scores + jax.lax.stop_gradient(p["expert_bias_b"]),
                               m["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return weights * m["routed_scaling_factor"], experts


def experts_ff(p, u, m, mm, es):
    first, count, _ = held(m)
    weights, experts = route(p, u, m, mm)
    latent = mm(u, p["fc1_latent_kernel"])
    # [rows, n·width] -> one [rows, width] matrix a held expert
    per_expert = lambda name: jnp.moveaxis(
        p[name].reshape(p[name].shape[0], count, -1), 1, 0)

    def add_expert(out, e_and_its_matrices):
        e, w1, w2 = e_and_its_matrices
        w_e = jnp.sum(jnp.where(experts == first + e, weights, 0.0), axis=-1)
        return out + w_e[:, None] * mm(_relu2(mm(latent, w1)), w2), None

    routed, _ = jax.lax.scan(
        jax.checkpoint(add_expert), jnp.zeros_like(latent),
        (jnp.arange(count), per_expert("experts_w1_kernel"),
         per_expert("experts_w2_kernel")))
    chosen = jnp.sum(experts[..., None] == jnp.arange(p["router_kernel"].shape[1]),
                     axis=(0, 1), dtype=jnp.int32)
    return mm(routed, p["fc2_latent_kernel"]) \
        + mm(_relu2(mm(u, p["shared_w1_kernel"])), p["shared_w2_kernel"]), chosen


SUBLAYERS = {"mamba": (mamba_mixer, "mamba"), "attention": (attention, "attn"),
             "moe": (experts_ff, "moe")}


def _layer(p, x, m, kind, mm, es):
    """``(x + sublayer(rms(x)), how often each of the router's experts was chosen)``,
    the second None but for an expert layer."""
    sublayer, group = SUBLAYERS[kind]
    out = sublayer(p[group], _rms(x, p["norm_scale"], m["norm_eps"]), m, mm, es)
    out, chosen = out if kind == "moe" else (out, None)
    return x + out, chosen


def _forward(params, ids, m: dict, precision: str, layers: int | None):
    """One sequence ``ids [S]`` -> ``([S, d] after ``layers`` layers (all, and the last
    norm, when None), [the expert layers' ``chosen``])``."""
    mm, es = prec.matmul(precision), prec.einsum(precision)
    x = params["embed_tokens"][ids]
    stack = list(enumerate(kinds(m)[:layers]))

    def group(theirs, x, members):
        load = []
        for (_, kind), p in zip(members, theirs):
            x, chosen = jax.checkpoint(
                lambda p, x, kind=kind: _layer(p, x, m, kind, mm, es))(p, x)
            load += [] if chosen is None else [chosen]
        return x, load

    load = []
    for start in range(0, len(stack), LAYER_GROUP):
        members = stack[start:start + LAYER_GROUP]
        x, chosen = jax.checkpoint(
            lambda theirs, x, members=members: group(theirs, x, members))(
            [params[f"layer_{i}"] for i, _ in members], x)
        load += chosen
    if layers is None:
        x = _rms(x, params["final_norm_scale"], m["norm_eps"])
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
    """The experts ``[S, k]`` that expert layer ``layer`` (an index into the kept
    layers) selects for one sequence."""
    x = hidden_states(params, ids, m, precision=precision, layers=layer)
    p = params[f"layer_{layer}"]
    return route(p["moe"], _rms(x, p["norm_scale"], m["norm_eps"]), m,
                 prec.matmul(precision))[1]


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

    totals, load = jax.lax.map(jax.checkpoint(one), tokens)
    value = jnp.sum(totals) / (tokens.shape[0] * (tokens.shape[1] - 1))
    return (value, jnp.sum(load, axis=0)) if with_load else value


def rebalanced(params, load, m: dict):
    """``params`` with each expert layer's ``expert_bias_b`` moved by the file's
    ``moe_router_bias_update_rate`` toward balance: ``b + rate · sign(mean(load) − load)``
    over the router's experts, ``load`` a step's counts from ``loss``."""
    rate = m["moe_router_bias_update_rate"]
    moved = dict(params)
    for row, i in zip(load, (i for i, kind in enumerate(kinds(m)) if kind == "moe")):
        row = row.astype(jnp.float32)
        moe = dict(params[f"layer_{i}"]["moe"])
        moe["expert_bias_b"] = moe["expert_bias_b"] + rate * jnp.sign(jnp.mean(row) - row)
        moved[f"layer_{i}"] = dict(params[f"layer_{i}"], moe=moe)
    return moved


def param_shapes(m: dict) -> dict:
    """The parameter tree's paths and shapes, float32, from the widths alone."""
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    d, heads, kvh, hd = (m["hidden_size"], m["num_attention_heads"],
                         m["num_key_value_heads"], m["head_dim"])
    _, count, router = held(m)
    f, latent, shared = m["moe_intermediate_size"], m["moe_latent_size"], shared_columns(m)
    inner = m["mamba_num_heads"] * m["mamba_head_dim"]
    conv = inner + 2 * m["n_groups"] * m["ssm_state_size"]
    groups = {
        "mamba": lambda: {
            "in_proj_kernel": f32(d, inner + conv + m["mamba_num_heads"]),
            "conv_kernel": f32(m["conv_kernel"], conv), "conv_bias": f32(conv),
            "dt_bias": f32(m["mamba_num_heads"]), "A_log": f32(m["mamba_num_heads"]),
            "D_scale": f32(m["mamba_num_heads"]), "gate_norm_scale": f32(inner),
            "out_proj_kernel": f32(inner, d)},
        "attention": lambda: {
            "q_kernel": f32(d, heads * hd), "k_kernel": f32(d, kvh * hd),
            "v_kernel": f32(d, kvh * hd), "out_kernel": f32(heads * hd, d)},
        "moe": lambda: {
            "router_kernel": f32(d, router), "expert_bias_b": f32(router),
            "fc1_latent_kernel": f32(d, latent), "fc2_latent_kernel": f32(latent, d),
            "shared_w1_kernel": f32(d, shared), "shared_w2_kernel": f32(shared, d),
            "experts_w1_kernel": f32(latent, count * f),
            "experts_w2_kernel": f32(f, count * latent)},
    }
    tree = {"embed_tokens": f32(m["vocab_size"], d), "lm_head_kernel": f32(d, m["vocab_size"]),
            "final_norm_scale": f32(d)}
    for i, kind in enumerate(kinds(m)):
        tree[f"layer_{i}"] = {"norm_scale": f32(d), SUBLAYERS[kind][1]: groups[kind]()}
    return tree


def batch_of(split: dict, rows):
    """The reference's view of one training batch: the rows' token sequences."""
    return jnp.asarray(split["tokens"][rows])
