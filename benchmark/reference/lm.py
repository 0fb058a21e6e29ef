"""Plain decoder LM: pre-LN blocks, grouped-query attention, rotary positions
(half-split, base 10000), tanh-GELU MLP, LayerNorm eps 1e-5, linear head.

Written from the architecture's description (the configuration file's
``model`` group), not from ``models/lm.py``. It reads a parameter tree by the
leaf names a checkpoint of that model carries: ``tok_embed``, ``pos_embed``
(absent under rope), ``block_<i>/{ln1,ln2}_{scale,bias}``,
``block_<i>/attn/{q,kv,out}_{kernel,bias}``, ``block_<i>/mlp_{up,down}_{kernel,
bias}``, ``ln_f_{scale,bias}``, ``head_{kernel,bias}``. ``kv_kernel`` packs K
then V, each ``kv_heads x head_dim`` wide.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import precision as prec

MASK = -1e30


def _layer_norm(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + 1e-5) * g + b


def _rope(x, positions):
    """x [B,S,H,D]: rotate the first half of D against the second."""
    d = x.shape[-1]
    inv = 10000.0 ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None, None] * inv      # [S,1,D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _block(p, x, m, mm, es):
    b, s, d = x.shape
    heads, kvh = m["num_heads"], m["kv_heads"]
    hd = d // heads
    h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"])
    a = p["attn"]
    q = (mm(h, a["q_kernel"]) + a["q_bias"]).reshape(b, s, heads, hd)
    kv = (mm(h, a["kv_kernel"]) + a["kv_bias"]).reshape(b, s, 2, kvh, hd)
    k, v = kv[:, :, 0], kv[:, :, 1]
    if m["rope"]:
        pos = jnp.arange(s)
        q, k = _rope(q, pos), _rope(k, pos)
    rep = heads // kvh
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = es("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None, None], scores, MASK)
    w = jax.nn.softmax(scores, axis=-1)
    out = es("bhqk,bkhd->bqhd", w, v).reshape(b, s, d)
    x = x + mm(out, a["out_kernel"]) + a["out_bias"]
    h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"])
    h = jax.nn.gelu(mm(h, p["mlp_up_kernel"]) + p["mlp_up_bias"], approximate=True)
    return x + mm(h, p["mlp_down_kernel"]) + p["mlp_down_bias"]


def log_probs(params, inputs, m: dict, *, precision: str = "highest",
              remat: bool = False):
    """``inputs`` [B,S] int32 (the shift-right stream, BOS first) ->
    [B,S,vocab] float32 log-probabilities of the next token."""
    mm, es = prec.matmul(precision), prec.einsum(precision)
    x = params["tok_embed"][inputs]
    if not m["rope"]:
        x = x + params["pos_embed"][None, : inputs.shape[1]]
    block = (lambda p, x: _block(p, x, m, mm, es))
    if remat:
        block = jax.checkpoint(block)
    for i in range(m["num_layers"]):
        x = block(params[f"block_{i}"], x)
    x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])
    logits = mm(x, params["head_kernel"]) + params["head_bias"]
    return jax.nn.log_softmax(logits, axis=-1)


def shift_right(targets, bos: int):
    first = jnp.full((targets.shape[0], 1), bos, targets.dtype)
    return jnp.concatenate([first, targets[:, :-1]], axis=1)


def loss(params, targets, m: dict, *, precision: str = "highest"):
    """Mean next-token NLL over all B x S positions of ``targets`` [B,S]."""
    lp = log_probs(params, shift_right(targets, m["vocab_size"] - 1), m,
                   precision=precision, remat=True)
    return -jnp.mean(jnp.take_along_axis(lp, targets[..., None], axis=-1))


def param_shapes(m: dict) -> dict:
    """The parameter tree's paths and shapes, float32, from the widths alone."""
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    d, hidden = m["embed_dim"], m["mlp_ratio"] * m["embed_dim"]
    kv = 2 * m["kv_heads"] * (d // m["num_heads"])
    block = lambda: {
        "ln1_scale": f32(d), "ln1_bias": f32(d), "ln2_scale": f32(d), "ln2_bias": f32(d),
        "attn": {"q_kernel": f32(d, d), "q_bias": f32(d),
                 "kv_kernel": f32(d, kv), "kv_bias": f32(kv),
                 "out_kernel": f32(d, d), "out_bias": f32(d)},
        "mlp_up_kernel": f32(d, hidden), "mlp_up_bias": f32(hidden),
        "mlp_down_kernel": f32(hidden, d), "mlp_down_bias": f32(d)}
    tree = {f"block_{i}": block() for i in range(m["num_layers"])}
    tree.update(tok_embed=f32(m["vocab_size"], d), ln_f_scale=f32(d), ln_f_bias=f32(d),
                head_kernel=f32(d, m["vocab_size"]), head_bias=f32(m["vocab_size"]))
    if not m["rope"]:
        tree["pos_embed"] = f32(m["seq_len"], d)
    return tree


def batch_of(split: dict, rows):
    """The reference's view of one training batch: the rows' token streams."""
    return jnp.asarray(split["tokens"][rows])
