"""Plain Qwen3-Next decoder (``model_type`` ``qwen3_next``), or one chip's share of it.

Written from the architecture's description and the published ``qwen3_next`` modelling
code its keys are read by, float32 ``jax.numpy``; imports nothing of the program. ``m`` is
the configuration file itself: the published keys at its top level, with the keys that
count layers, routed experts and ids as held here, ``published.num_experts`` the router's
width, and ``share`` = ``{first_layer (numbered from 0), first_expert}``.

    layer       h = x + mixer(rms1(x));  y = h + moe(rms1(h));
                rms1(x) = x/sqrt(mean x² + eps) · (1 + w): every norm of the stream, the
                last one and the heads' q/k norms; the delta layer's output norm is plain
    delta       layer i with (i + 1) % full_attention_interval != 0.  [q̃ | k̃ | ṽ | z] = W_qkvz u,
                [b | a] = W_ba u;  (q̃, k̃, ṽ) = silu(conv4(q̃ | k̃ | ṽ)) (depthwise, causal, zeros
                before the start, no bias);  β = sigmoid(b), g = −exp(A_log) · softplus(a +
                dt_bias), one number a token and value head;  per key head q = q̃/‖q̃‖·K^-½,
                k = k̃/‖k̃‖ (‖x‖ = sqrt(Σx² + 1e-6)); value head h reads key head h // rep;  per
                value head and TOKEN, one after the other (``torch_recurrent_gated_delta_rule``):
                S ← e^{g_t} S;  S ← S + k_t ⊗ β_t (v_t − Sᵀ k_t);  o_t = Sᵀ q_t  (S_0 = 0);
                W_o (w ⊙ rms_head(o) ⊙ silu(z)): normed a head, THEN gated
    attention   W_q u is a head's query and its gate;  q, k ← rms1 a head;  the first
                ``head_dim · partial_rotary_factor`` channels turn by position, the published
                way: x·cos + rotate_half(x)·sin over that slice, angles t · theta^(−2j/rot) on
                both halves, the other channels carried;  the key/value heads repeated to
                the query heads;  causal softmax(q·k/√D)·v;  W_o (out ⊙ sigmoid(gate))
    experts     p = softmax(W_r u) over all the router's experts; the k largest; weights
                p_e / Σ_selected p;  Σ_{e held} w_e W2_e (silu(W1_e u) ⊙ W3_e u)  +
                sigmoid(w_g · u) · W_s2 (silu(W_s1 u) ⊙ W_s3 u): a loop over the held experts
                with masks, no sort, no capacity, no token dropped. What the experts held
                elsewhere would add is left out.
    head        its own matrix over the held slice of the vocabulary, after a last
                rms1; loss = mean next-token NLL over the S-1 targets a sequence

The recurrence is the definition, a ``lax.scan`` over time: no chunks, no triangular solve,
no mask of decays. Departures, for memory alone: layers are recomputed in the backward
pass, the scan over time is checkpointed every ``TIME_BLOCK`` tokens, attention, the
experts and the head walk a sequence's rows in blocks (``ROW_BLOCK``; attention's scores in
``SCORE_BLOCK`` query rows against every key), and of a batch's sequences a layer's mixer
takes one after the other while its experts and the head take them side by side: one
sequence's mixer holds 3.5 GB of float32 temporaries at the cell's size, and with the whole
model one sequence after the other each sequence's gradient of every parameter would
stand beside the running sum (17.4 GB and 18.5 GB where the chip allows a program 16.9;
``bench_results/hw_pr43/compile_reference.py``). Storage, where the program's
tree orders columns otherwise than the published checkpoint (with seeded weights the
distributions are the same): ``qkvz_kernel``'s columns are all key heads' q̃, then their k̃,
then all value heads' ṽ, then z (published: grouped by key head); ``ba_kernel``'s are all b,
then all a; ``q_kernel``'s are every head's query, then every head's gate (published: a head's
query and gate side by side).

Leaf names are those of the program's tree (``embed_tokens``, ``lm_head_kernel``,
``final_norm_offset``, ``layer_<i>/{mixer_norm_offset, ff_norm_offset}``,
``layer_<i>/gdn/{qkvz,ba,conv,out}_kernel``, ``…/gdn/{A_log, dt_bias, o_norm_scale}``,
``layer_<i>/attn/{q,k,v,out}_kernel``, ``…/attn/{q,k}_norm_offset``, ``layer_<i>/moe/{router_kernel,
shared_w{1,2,3}_kernel, shared_gate_kernel, experts_w{1,2,3}_kernel}``); expert matrices are
column-blocked by held expert: ``experts_w1_kernel [d, n·f]``, ``experts_w2_kernel [f, n·d]``.
The lines this file shares with ``kimi_linear.py`` and ``deepseek_v3.py`` are copied, not
imported: one model's reference does not follow another's edits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import precision as prec

MASK = -1e30
ROW_BLOCK = 512         # rows of a sequence a row-wise stage holds at once
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


def _numbers(m: dict) -> range:
    """The kept layers' published numbers, from 0."""
    first = m.get("share", {}).get("first_layer", 0)
    return range(first, first + m["num_hidden_layers"])


def kinds(m: dict) -> list[str]:
    """The kept layers' mixers, ``gdn`` or ``attn``."""
    return ["attn" if (i + 1) % m["full_attention_interval"] == 0 else "gdn"
            for i in _numbers(m)]


def sparse(m: dict) -> list[bool]:
    """Whether each kept layer's feed-forward is the expert layer: every one."""
    return [True for _ in _numbers(m)]


def held(m: dict) -> tuple[int, int, int]:
    """(first held expert, how many are held, the router's width)."""
    return (m.get("share", {}).get("first_expert", 0), m["num_experts"],
            m.get("published", {}).get("num_experts", m["num_experts"]))


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _rms1(x, w, eps):
    """The family's norm: the weight is one plus the leaf."""
    return _rms(x, eps) * (1.0 + w)


def gated_delta_rule(q, k, v, g, beta, es):
    """``q``, ``k`` ``[S, H, K]``, ``v [S, H, V]``, ``g``, ``beta`` ``[S, H]`` -> ``o [S, H, V]``, token by
    token from a zero state ``[H, K, V]``."""
    s, heads, _ = q.shape

    def token(state, now):
        q_t, k_t, v_t, g_t, b_t = now
        state = jnp.exp(g_t)[:, None, None] * state
        delta = (v_t - es("hk,hkv->hv", k_t, state)) * b_t[:, None]
        state = state + k_t[:, :, None] * delta[:, None, :]
        return state, es("hk,hkv->hv", q_t, state)

    steps = TIME_BLOCK if s % TIME_BLOCK == 0 else s
    cut = lambda x: x.reshape((s // steps, steps) + x.shape[1:])
    block = jax.checkpoint(lambda state, nows: jax.lax.scan(token, state, nows))
    _, o = jax.lax.scan(block, jnp.zeros((heads, k.shape[-1], v.shape[-1]), jnp.float32),
                        tuple(map(cut, (q, k, v, g, beta))))
    return o.reshape(s, heads, -1)


def gdn_mixer(p, u, m, mm, es):
    s = u.shape[0]
    key_heads, value_heads = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv, taps = m["linear_key_head_dim"], m["linear_value_head_dim"], m["linear_conv_kernel_dim"]
    keys, values = key_heads * dk, value_heads * dv
    mixed, z = jnp.split(mm(u, p["qkvz_kernel"]), [2 * keys + values], axis=-1)
    padded = jnp.pad(mixed, ((taps - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(p["conv_kernel"][j] * padded[j:j + s] for j in range(taps)))
    q, k, v = jnp.split(mixed, [keys, 2 * keys], axis=-1)
    b, a = jnp.split(mm(u, p["ba_kernel"]), 2, axis=-1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
    rep = value_heads // key_heads          # repeat_interleave: value head h, key head h // rep
    q = jnp.repeat(unit(q.reshape(s, key_heads, dk)), rep, axis=1) * dk ** -0.5
    k = jnp.repeat(unit(k.reshape(s, key_heads, dk)), rep, axis=1)
    o = gated_delta_rule(q, k, v.reshape(s, value_heads, dv), g, beta, es)
    normed = p["o_norm_scale"] * _rms(o, m["rms_norm_eps"])
    gated = normed * jax.nn.silu(z.reshape(s, value_heads, dv))
    return mm(gated.reshape(s, values), p["out_kernel"])


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _partly_rotated(x, m):
    """``x [S, H, D]``: the first ``D · partial_rotary_factor`` channels turned by position."""
    s, rot = x.shape[0], int(m["head_dim"] * m["partial_rotary_factor"])
    inv_freq = 1.0 / m["rope_theta"] ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    turned, carried = x[..., :rot], x[..., rot:]
    turned = turned * jnp.cos(emb) + _rotate_half(turned) * jnp.sin(emb)
    return jnp.concatenate([turned, carried], axis=-1)


def attention_mixer(p, u, m, mm, es):
    s = u.shape[0]
    heads, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps = m["rms_norm_eps"]
    q, gate = jnp.split(mm(u, p["q_kernel"]), 2, axis=-1)       # all queries, then all gates
    q = _rms1(q.reshape(s, heads, hd), p["q_norm_offset"], eps)
    k = _rms1(mm(u, p["k_kernel"]).reshape(s, kv, hd), p["k_norm_offset"], eps)
    v = mm(u, p["v_kernel"]).reshape(s, kv, hd)
    q, k = _partly_rotated(q, m), _partly_rotated(k, m)
    k, v = (jnp.repeat(x, heads // kv, axis=1) for x in (k, v))

    def rows(q_blk, start):
        scores = es("qhd,khd->hqk", q_blk, k) / jnp.sqrt(jnp.float32(hd))
        seen = (start + jnp.arange(q_blk.shape[0]))[:, None] >= jnp.arange(s)[None]
        w = jax.nn.softmax(jnp.where(seen[None], scores, MASK), axis=-1)
        return es("hqk,khd->qhd", w, v)

    out = _by_rows(rows, q, rows=SCORE_BLOCK).reshape(s, heads * hd)
    return mm(out * jax.nn.sigmoid(gate), p["out_kernel"])


def _gated(u, w1, w3, w2, mm):
    return mm(jax.nn.silu(mm(u, w1)) * mm(u, w3), w2)


def route(p, u, m, mm):
    """``(weights [S, k], experts [S, k])`` over all the router's experts."""
    probs = jax.nn.softmax(mm(u, p["router_kernel"]), axis=-1)
    picked, experts = jax.lax.top_k(probs, m["num_experts_per_tok"])
    return picked / jnp.sum(picked, axis=-1, keepdims=True), experts


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
    return routed + jax.nn.sigmoid(mm(u, p["shared_gate_kernel"])) * shared, chosen


MIXERS = {"gdn": gdn_mixer, "attn": attention_mixer}


def _mixed(p, x, m, kind, mm, es):
    """``x + mixer(rms1(x))``: the first half of a layer."""
    return x + MIXERS[kind](p[kind], _rms1(x, p["mixer_norm_offset"], m["rms_norm_eps"]),
                            m, mm, es)


def _layer(p, x, m, kind, mm, es):
    """``x [B, S, d]`` -> ``(the layer's output, how often each of the router's experts was
    chosen by the batch's tokens)``: the mixer a sequence at a time, the experts side by
    side."""
    h = jax.lax.map(jax.checkpoint(lambda row: _mixed(p, row, m, kind, mm, es)), x)
    out, chosen = jax.vmap(lambda rows: experts_ff(
        p["moe"], _rms1(rows, p["ff_norm_offset"], m["rms_norm_eps"]), m, mm))(h)
    return h + out, jnp.sum(chosen, axis=0)


def _forward(params, tokens, m: dict, precision: str, layers: int | None):
    """``tokens [B, S]`` -> ``([B, S, d] after ``layers`` layers (all, and the last norm,
    when None), [the layers' ``chosen``])``."""
    mm, es = prec.matmul(precision), prec.einsum(precision)
    x = params["embed_tokens"][tokens]
    load = []
    for i, kind in list(enumerate(kinds(m)))[:layers]:
        x, chosen = jax.checkpoint(
            lambda p, x, kind=kind: _layer(p, x, m, kind, mm, es))(params[f"layer_{i}"], x)
        load.append(chosen)
    if layers is None:
        x = _rms1(x, params["final_norm_offset"], m["rms_norm_eps"])
    return x, load


def hidden_states(params, ids, m: dict, *, precision: str = "highest",
                  layers: int | None = None):
    """One sequence ``ids [S]`` -> ``[S, d]`` after ``layers`` layers (all, and the
    last norm, when None)."""
    return _forward(params, ids[None], m, precision, layers)[0][0]


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
    h = _mixed(p, x, m, kinds(m)[layer], mm, es)
    return route(p["moe"], _rms1(h, p["ff_norm_offset"], m["rms_norm_eps"]), m, mm)[1]


def loss(params, tokens, m: dict, *, precision: str = "highest", with_load: bool = False):
    """Mean next-token NLL over the B·(S-1) targets of ``tokens`` [B, S]; with
    ``with_load`` also ``[layers, router's experts] int32``, how many of the batch's tokens
    chose each expert."""
    mm = prec.matmul(precision)
    hidden, load = _forward(params, tokens, m, precision, None)

    def head(x, ids):
        def rows(x_blk, target, start):
            lp = jax.nn.log_softmax(mm(x_blk, params["lm_head_kernel"]), axis=-1)
            return -jnp.take_along_axis(lp, target[:, None], axis=-1)[:, 0]

        # row t's target is token t + 1; the last row has none
        return jnp.sum(_by_rows(rows, x, jnp.roll(ids, -1))[:-1])

    totals = jax.vmap(jax.checkpoint(head))(hidden, tokens)
    value = jnp.sum(totals) / (tokens.shape[0] * (tokens.shape[1] - 1))
    return (value, jnp.stack(load)) if with_load else value


def param_shapes(m: dict) -> dict:
    """The parameter tree's paths and shapes, float32, from the widths alone."""
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    d, heads, kv, hd = (m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"],
                        m["head_dim"])
    keys = m["linear_num_key_heads"] * m["linear_key_head_dim"]
    value_heads = m["linear_num_value_heads"]
    values = value_heads * m["linear_value_head_dim"]
    _, count, router = held(m)
    f, shared = m["moe_intermediate_size"], m["shared_expert_intermediate_size"]
    groups = {
        "gdn": lambda: {
            "qkvz_kernel": f32(d, 2 * keys + 2 * values),
            "conv_kernel": f32(m["linear_conv_kernel_dim"], 2 * keys + values),
            "ba_kernel": f32(d, 2 * value_heads), "A_log": f32(value_heads),
            "dt_bias": f32(value_heads), "o_norm_scale": f32(m["linear_value_head_dim"]),
            "out_kernel": f32(values, d)},
        "attn": lambda: {
            "q_kernel": f32(d, 2 * heads * hd), "k_kernel": f32(d, kv * hd),
            "v_kernel": f32(d, kv * hd), "q_norm_offset": f32(hd), "k_norm_offset": f32(hd),
            "out_kernel": f32(heads * hd, d)},
    }
    experts = lambda: {
        "router_kernel": f32(d, router),
        "shared_w1_kernel": f32(d, shared), "shared_w3_kernel": f32(d, shared),
        "shared_w2_kernel": f32(shared, d), "shared_gate_kernel": f32(d, 1),
        "experts_w1_kernel": f32(d, count * f), "experts_w3_kernel": f32(d, count * f),
        "experts_w2_kernel": f32(f, count * d)}
    tree = {"embed_tokens": f32(m["vocab_size"], d), "lm_head_kernel": f32(d, m["vocab_size"]),
            "final_norm_offset": f32(d)}
    for i, kind in enumerate(kinds(m)):
        tree[f"layer_{i}"] = {"mixer_norm_offset": f32(d), "ff_norm_offset": f32(d),
                              kind: groups[kind](), "moe": experts()}
    return tree


def batch_of(split: dict, rows):
    """The reference's view of one training batch: the rows' token sequences."""
    return jnp.asarray(split["tokens"][rows])
