"""The ``qwen3_next`` stack of ``models/hybrid_lm.py`` (three gated delta-rule layers with one
decay a token and head to one gated softmax attention, every layer with softmax-routed
experts beside a sigmoid-gated shared one) against the plain reference
(``benchmark/reference/qwen3_next.py``, which imports nothing of the program and walks the
recurrence token by token), and ``ops/kda.py``'s scan at a scalar decay against that
recurrence: small sizes, float32, seeded weights; Pallas in interpret mode."""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import precision as prec  # noqa: E402
from reference import qwen3_next as ref  # noqa: E402
import weights as bench_weights  # noqa: E402

from csed_514_project_distributed_training_using_pytorch_tpu import ops  # noqa: E402
from csed_514_project_distributed_training_using_pytorch_tpu.models import (  # noqa: E402
    hybrid_lm,
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops import kda, moe  # noqa: E402
from csed_514_project_distributed_training_using_pytorch_tpu.ops.rotary import (  # noqa: E402
    apply_rotary,
)

CONFIG_FILE = os.path.join(BENCH, "configs", "qwen3-next-80b-a3b-ep16.json")
SEQ, VOCAB, TILING = 40, 64, (8, 4, 2)      # 40 tokens: two groups of 16 and a padded tail
MM, ES = prec.matmul("highest"), prec.einsum("highest")


def tiny_config(**changes) -> dict:
    """The benchmark's configuration with its widths cut: hidden 64, 4 query heads on 2
    key/value heads of 16 (4 channels rotated), 4 delta value heads on 2 key heads of 8, 4
    of 16 experts of width 32 held, 3 of them a token beside a gated shared one; one period."""
    with open(CONFIG_FILE) as fh:
        config = json.load(fh)
    config.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
                  shared_expert_intermediate_size=32, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, linear_num_key_heads=2,
                  linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8,
                  num_experts=4, num_experts_per_tok=3, vocab_size=VOCAB, num_hidden_layers=4)
    config["published"] = dict(config["published"], num_experts=16, num_hidden_layers=4)
    config.update(changes)
    return config


def build(config, seed=20261002, **kw):
    model = hybrid_lm.from_config(config, vocab_size=config["vocab_size"], seq_len=SEQ,
                                  expert_block=8, kda_tiling=TILING, **kw)
    return model, bench_weights.make(ref.param_shapes(config), seed)


def tokens(batch=2, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, VOCAB, (batch, SEQ)),
                       jnp.int32)


@functools.lru_cache(maxsize=None)
def reference_loss_and_gradients():
    """The reference on the tiny configuration's seeded weights and ``tokens()``, once a run
    of this file (a quarter of a minute on the CPU)."""
    config = tiny_config()
    params = bench_weights.make(ref.param_shapes(config), 20261002)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: ref.loss(p, tokens(), config))(params)


def flat(tree) -> dict:
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


# (a) the scan at a scalar decay -----------------------------------------------------------


def recurrence(q, k, v, g, beta, *, key_heads, eps, normed=True, decay_after=False, **_):
    """``gdn_scan``'s contract token by token (``torch_recurrent_gated_delta_rule``): the
    flat operands, ``g`` and ``beta`` ``[B, S, H]``; value head ``h`` on key head ``h // rep``.
    ``decay_after``: the planted fault that decays the state after its correction."""
    b, s, heads = beta.shape
    dk, dv, rep = q.shape[2] // key_heads, v.shape[2] // heads, heads // key_heads
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(unit(q.reshape(b, s, key_heads, dk)) * dk ** -0.5, rep, axis=2)
    k = jnp.repeat(unit(k.reshape(b, s, key_heads, dk)), rep, axis=2)

    def token(state, now):
        q_t, k_t, v_t, g_t, b_t = now
        alpha = jnp.exp(g_t)[..., None, None]
        if not decay_after:
            state = alpha * state
        fix = (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state)) * b_t[..., None]
        state = state + k_t[..., None] * fix[..., None, :]
        if decay_after:
            state = alpha * state
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    by_time = lambda x: jnp.moveaxis(x, 1, 0)
    _, o = jax.lax.scan(token, jnp.zeros((b, heads, dk, dv)),
                        tuple(map(by_time, (q, k, v.reshape(b, s, heads, dv), g, beta))))
    o = by_time(o)
    if normed:
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    return o.reshape(b, s, heads * dv)


def operands(s, key_heads, heads, d, steep, seed=0, batch=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(key, (batch, s, key_heads * d)) for key in keys[:2])
    v, w = (jax.random.normal(key, (batch, s, heads * d)) for key in keys[2:4])
    g = -steep * jax.nn.softplus(jax.random.normal(keys[4], (batch, s, heads)))
    return (q, k, v, g, jax.nn.sigmoid(jax.random.normal(keys[5], (batch, s, heads)))), w


GDN_ROWS = kda.GDN_TILING.chunk * kda.GDN_TILING.group     # tokens of a grid step
# (tokens, key heads, value heads, width, (chunk, sub, group), how steep the decay is)
SCANS = {
    "chunks, groups and a padded tail": (40, 2, 4, 8, (8, 4, 2), 1.0),
    "decays near zero": (37, 2, 2, 8, (8, 4, 2), 1e-3),
    "past -50 a chunk": (48, 1, 2, 8, (8, 2, 2), 15.0),
    "one chunk a group, sub-block a chunk": (24, 1, 3, 16, (8, 8, 1), 0.3),
    # the scalar branch's committed tiling (ops.kda.GDN_TILING) at the published 128 x 128 head
    "committed tiling, published head: a tail padded inside the first group":
        (GDN_ROWS // 4 + 2, 1, 2, 128, kda.GDN_TILING, 1.0),
    "committed tiling, published head: a kept state enters a second, padded group":
        (GDN_ROWS + kda.GDN_TILING.chunk + 6, 1, 2, 128, kda.GDN_TILING, 0.3),
    "committed tiling, published head: whole rows, decays near zero":
        (GDN_ROWS, 1, 1, 128, kda.GDN_TILING, 1e-3),
}


@pytest.mark.parametrize("case", SCANS)
def test_the_scalar_scan_matches_the_recurrence_in_value_and_every_gradient(case):
    s, key_heads, heads, d, (chunk, sub, group), steep = SCANS[case]
    args, w = operands(s, key_heads, heads, d, steep)
    scan = lambda *a: jnp.sum(w * kda.gdn_scan(*a, key_heads=key_heads, eps=1e-6,
                                               chunk=chunk, sub=sub, group=group))
    plain = lambda *a: jnp.sum(w * recurrence(*a, key_heads=key_heads, eps=1e-6))
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(scan, argnums=(0, 1, 2, 3, 4))(*args)
        want, wants = jax.value_and_grad(plain, argnums=(0, 1, 2, 3, 4))(*args)
    if case == "past -50 a chunk":
        assert float(jnp.sum(args[3][:, :chunk], axis=1).max()) < -50
    assert abs(float(got) - float(want)) < 1e-4 * max(abs(float(want)), 1.0)
    for name, a, b in zip("q k v g beta".split(), grads, wants):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=3e-5 * float(jnp.abs(b).max()), err_msg=name)


def test_the_scalar_scan_is_the_channel_scan_fed_one_decay_on_every_channel():
    """Same recurrence, two algorithms: ``kda_scan`` with the key heads repeated and the
    scalar on all of a head's channels (exact diagonals and doublings) gives what
    ``gdn_scan`` gives with one product and a mask."""
    (q, k, v, g, beta), _ = operands(40, 2, 4, 8, 1.0, seed=3)
    by_head = lambda x: jnp.repeat(x.reshape(2, 40, 2, 8), 2, axis=2).reshape(2, 40, 32)
    with jax.default_matmul_precision("highest"):
        got = kda.gdn_scan(q, k, v, g, beta, key_heads=2, eps=1e-6, chunk=8, sub=4, group=2)
        want = kda.kda_scan(by_head(q), by_head(k), v, jnp.repeat(g, 8, axis=-1), beta,
                            eps=1e-6, chunk=8, sub=4, group=2)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_two_value_heads_read_one_key_head():
    """Value heads 0 and 1 see key head 0 and not key head 1: zeroing key head 1's queries
    and keys leaves their outputs and moves the other two's; the key heads' gradients are
    the sum over their two value heads."""
    (q, k, v, g, beta), w = operands(16, 2, 4, 8, 0.5, seed=5)
    scan = functools.partial(kda.gdn_scan, key_heads=2, eps=1e-6, chunk=8, sub=4, group=1)
    cut = lambda x: x.at[:, :, 8:].set(0.0)
    with jax.default_matmul_precision("highest"):
        whole, halved = scan(q, k, v, g, beta), scan(cut(q), cut(k), v, g, beta)
        dq = jax.grad(lambda q: jnp.sum(w * scan(q, k, v, g, beta)))(q)
        only = lambda h: jnp.zeros_like(w).at[:, :, 8 * h:8 * h + 8].set(w[:, :, 8 * h:8 * h + 8])
        parts = [jax.grad(lambda q: jnp.sum(only(h) * scan(q, k, v, g, beta)))(q)
                 for h in range(4)]
    np.testing.assert_array_equal(whole[:, :, :16], halved[:, :, :16])
    assert float(jnp.abs(whole[:, :, 16:] - halved[:, :, 16:]).max()) > 0.1
    for h in range(4):      # value head h's output moves key head h // 2's queries alone
        other = slice(8, 16) if h < 2 else slice(0, 8)
        assert float(jnp.abs(parts[h][:, :, other]).max()) == 0.0
    np.testing.assert_allclose(dq, sum(parts), atol=1e-5 * float(jnp.abs(dq).max()))


def test_the_scan_refuses_key_heads_that_do_not_divide_the_value_heads():
    (q, k, v, g, beta), _ = operands(16, 2, 3, 8, 1.0)
    with pytest.raises(ValueError, match="2 key heads do not divide 3 value heads"):
        kda.gdn_scan(q, k, v, g, beta, key_heads=2, eps=1e-6, chunk=8, sub=4, group=1)


def test_the_plan_says_the_decay_is_a_scalar():
    plan = kda.scan_plan(heads=32, key_heads=16, key_dim=128, value_dim=128, seq_len=8192)
    assert (plan["decay"], plan["key_heads"], plan["heads"]) == ("scalar", 16, 32)
    assert (plan["chunk"], plan["sub_block"], plan["group"]) == kda.GDN_TILING
    assert plan["states_per_sequence"] == 8192 // GDN_ROWS
    assert plan["chunks_per_sequence"] == 8192 // kda.GDN_TILING.chunk
    assert plan["state_bytes_per_sequence"] == 8192 // GDN_ROWS * 32 * 128 * 128 * 4
    channel = kda.scan_plan(heads=32, key_dim=128, value_dim=128, seq_len=8192)
    assert "decay" not in channel       # and a decay a channel has a tiling of its own
    assert (channel["chunk"], channel["sub_block"], channel["group"]) == kda.KDA_TILING


def test_a_tiling_given_in_part_keeps_the_rest_of_the_decay_kinds_own():
    plan = kda.scan_plan(heads=4, key_heads=2, key_dim=8, value_dim=8, seq_len=100, group=1)
    assert (plan["chunk"], plan["sub_block"], plan["group"]) == \
        (kda.GDN_TILING.chunk, kda.GDN_TILING.sub, 1)
    assert plan["states_per_sequence"] == -(-100 // kda.GDN_TILING.chunk)


@pytest.mark.parametrize("tiling", [None, (16, 4, 2)], ids=["the kernels' own", "a triple"])
def test_the_gdn_plan_reads_the_tiling_the_kernels_are_built_with(monkeypatch, tiling):
    """The published file names no tiling. What ``gdn_mixer`` hands ``ops.kda`` to build
    ``gdn_fwd`` / ``gdn_bwd`` with is what ``gdn_plan`` (the ``compile`` event's ``gdn``) reports:
    the scalar branch's own, or the ``kda_tiling`` triple the caller passed."""
    with open(CONFIG_FILE) as fh:
        config = json.load(fh)
    model = hybrid_lm.from_config(config, vocab_size=config["vocab_size"], seq_len=8192,
                                  dtype=jnp.bfloat16, **({"kda_tiling": tiling} if tiling else {}))
    built = []

    def make_op(chunk, sub, group, eps, scalar=False, rep=1):
        built.append(((chunk, sub, group), scalar, rep))
        return lambda q, k, v, g, beta: v

    monkeypatch.setattr(kda, "_make_op", make_op)
    p = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))["params"]["layer_0"]["gdn"]
    u = jax.ShapeDtypeStruct((2, 8192, model.hidden_size), jnp.bfloat16)
    jax.eval_shape(lambda p, u: hybrid_lm.gdn_mixer(p, u, model), p, u)
    plan = model.gdn_plan()
    assert built == [((plan["chunk"], plan["sub_block"], plan["group"]), True, 2)]
    assert built[0][0] == (tiling or kda.GDN_TILING)
    assert plan["states_per_sequence"] == 8192 // (plan["chunk"] * plan["group"])


# (b) the model against the reference -----------------------------------------------------


@pytest.mark.parametrize("layer, kind", [(0, "gdn"), (3, "attn")])
def test_a_mixer_matches_the_references(layer, kind):
    config = tiny_config()
    model, params = build(config)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 64))
    p = params[f"layer_{layer}"][kind]
    with jax.default_matmul_precision("highest"):
        got = (hybrid_lm.gdn_mixer(p, u, model) if kind == "gdn"
               else hybrid_lm.attention_mixer(p, u, jnp.arange(SEQ), model))
        want = jax.vmap(lambda row: ref.MIXERS[kind](p, row, config, MM, ES))(u)
    np.testing.assert_allclose(got, want, atol=3e-5 * float(jnp.abs(want).max()))


def test_logits_match_the_reference():
    config = tiny_config()
    model, params = build(config)
    assert model.layer_types == ("gdn", "gdn", "gdn", "full_attention")
    ids = tokens()
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, ids)
        want = jax.vmap(lambda row: jax.nn.log_softmax(ref.logits(params, row, config)))(ids)
    np.testing.assert_allclose(got, want, atol=3e-5)


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_leafs_gradient_match_the_reference(remat):
    config = tiny_config()
    model, params = build(config, remat=remat)
    ids = tokens()
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(lambda p: model.loss(p, ids)[0])(params)
    want, wants = reference_loss_and_gradients()
    assert abs(float(got) - float(want)) < 1e-5
    got, want = flat(grads), flat(wants)
    assert got.keys() == want.keys() and not any("expert_bias_b" in name for name in got)
    for name in want:
        scale = max(float(jnp.abs(want[name]).max()), 1e-3)
        np.testing.assert_allclose(got[name], want[name], atol=2e-4 * scale, err_msg=name)


def test_router_choices_and_weights_are_the_references():
    """Softmax over the router's 16, the 3 largest, renormalised: ``moe.route`` against the
    reference's ``route``, and the model's diagnostic against the reference's."""
    config = tiny_config()
    model, params = build(config)
    ids = tokens()
    u = jax.random.normal(jax.random.PRNGKey(2), (SEQ, 64))
    p = params["layer_1"]["moe"]
    with jax.default_matmul_precision("highest"):
        weights, experts = hybrid_lm.routed(p, u, model)
        want_weights, want_experts = ref.route(p, u, config, MM)
        got = np.sort(np.asarray(model.router_choices(params, ids, 1)), axis=-1)
        want = np.sort(np.asarray(jax.vmap(
            lambda row: ref.router_choice(params, row, config, 1))(ids)), axis=-1)
    assert experts.shape == (SEQ, 3) and int(experts.max()) > 3      # over all 16
    np.testing.assert_array_equal(experts, want_experts)
    np.testing.assert_allclose(weights, want_weights, atol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-6)
    assert got.shape == (2, SEQ, 3) and (got == want).all()
    # the diagnostic routes as the layer does: a sigmoid over the same logits chooses alike
    # (both are monotone) but a model told so would look for a bias leaf this family lacks
    assert "expert_bias_b" not in p and model.router_scoring == "softmax"


def _gate_before_the_norm(p, u, model):
    """``gdn_mixer`` with its output gated first and normed then (``gated_group_norm``'s
    order, which is not this family's)."""
    (key_heads, heads), hd = model.gdn_heads, model.kda_head_dim
    keys = key_heads * hd
    qkv, z = jnp.split(u @ p["qkvz_kernel"], [2 * keys + heads * hd], axis=-1)
    q, k, v = jnp.split(jax.nn.silu(hybrid_lm.causal_depthwise_conv(qkv, p["conv_kernel"])),
                        [keys, 2 * keys], axis=-1)
    b, a = jnp.split(u @ p["ba_kernel"], 2, axis=-1)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    o = recurrence(q, k, v, g, jax.nn.sigmoid(b), key_heads=key_heads, eps=0.0, normed=False)
    gated = (o * jax.nn.silu(z)).reshape(*o.shape[:2], heads, hd)
    return ops.rms_norm(gated, p["o_norm_scale"], eps=model.norm_eps).reshape(o.shape) \
        @ p["out_kernel"]


FAULTS = ["gate before the norm in the delta layer's output", "h % 2 for h // 2",
          "the decay after the correction", "beta dropped", "rotation over the whole head",
          "the interleaved pairing", "the output gate dropped", "w for 1 + w in a head norm",
          "the shared expert's gate dropped", "sigmoid for softmax in the router",
          "2 of a token's 3 experts"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_comparison(fault, monkeypatch):
    """Each fault moves the loss by far more than the 1e-5 the sound program is held to."""
    config = tiny_config()
    model, params = build(config)
    # the recurrence in the kernels' place: the sound kernels are held to it above, and the
    # interpreter compiles a stack of them in a quarter of a minute a fault
    scan = recurrence
    monkeypatch.setattr(kda, "gdn_scan", scan)
    if fault.startswith("gate before"):
        monkeypatch.setattr(hybrid_lm, "gdn_mixer", _gate_before_the_norm)
    elif fault == "h % 2 for h // 2":       # key heads 0, 1, 0, 1 where 0, 0, 1, 1 are due
        monkeypatch.setattr(kda, "gdn_scan", lambda q, k, *rest, key_heads, **kw: scan(
            jnp.tile(q, 2), jnp.tile(k, 2), *rest, key_heads=2 * key_heads, **kw))
    elif fault == "the decay after the correction":
        monkeypatch.setattr(kda, "gdn_scan", functools.partial(scan, decay_after=True))
    elif fault == "beta dropped":
        monkeypatch.setattr(kda, "gdn_scan", lambda q, k, v, g, beta, **kw: scan(
            q, k, v, g, jnp.ones_like(beta), **kw))
    elif fault == "rotation over the whole head":
        model = dataclasses.replace(model, rope_dim=None)
    elif fault == "the interleaved pairing":
        monkeypatch.setattr(hybrid_lm, "apply_rotary",
                            functools.partial(apply_rotary, interleaved=True))
    elif fault == "the output gate dropped":
        model = dataclasses.replace(model, attention_gate=False)
        attn = params["layer_3"]["attn"]
        params = dict(params, layer_3=dict(params["layer_3"], attn=dict(
            attn, q_kernel=attn["q_kernel"][:, :4 * 16])))
    elif fault == "w for 1 + w in a head norm":
        attn = params["layer_3"]["attn"]
        params = dict(params, layer_3=dict(params["layer_3"], attn=dict(
            attn, q_norm_offset=attn["q_norm_offset"] - 1.0)))
    elif fault == "the shared expert's gate dropped":
        model = dataclasses.replace(model, shared_expert_gate=False)
    elif fault == "sigmoid for softmax in the router":
        model = dataclasses.replace(model, router_scoring="sigmoid")
    else:
        model = dataclasses.replace(model, num_experts_per_tok=2)
    with jax.default_matmul_precision("highest"):
        got = float(model.loss(params, tokens())[0])
    want = float(reference_loss_and_gradients()[0])
    assert abs(got - want) > 1e-3, (got, want)


# (c) the share tied to the model ----------------------------------------------------------

CHIPS = 4       # expert-parallel 4: four experts of 16 a chip


@pytest.mark.parametrize("first_layer, kind", [(0, "gdn"), (3, "full_attention")])
def test_the_shares_add_up_to_the_uncut_layer(first_layer, kind):
    """Four chips divide a layer's 16 experts; each computes the mixer, the router and the
    gated shared expert whole and its own experts' part. What the four add to the
    residual, with what every chip computes alike (a share whose experts' second matrices
    are zero) counted once, is what the uncut reference's layer adds: for a delta layer
    and for an attention layer."""
    config = tiny_config(num_experts=16, num_hidden_layers=1)
    config["share"] = dict(config["share"], first_layer=first_layer)
    params = bench_weights.make(ref.param_shapes(config), 7)["layer_0"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, 64))
    take = lambda w, chip: jax.lax.slice_in_dim(
        w, chip * w.shape[1] // CHIPS, (chip + 1) * w.shape[1] // CHIPS, axis=1)
    with jax.default_matmul_precision("highest"):
        whole = ref._layer(params, x, config, ref.kinds(config)[0], MM, ES)[0] - x
        parts, rows = [], 0
        for chip in range(CHIPS):
            m = dict(config, num_experts=16 // CHIPS)
            m["share"] = dict(config["share"], first_expert=chip * 4)
            model = hybrid_lm.from_config(m, vocab_size=VOCAB, seq_len=SEQ, expert_block=8,
                                          kda_tiling=TILING)
            assert model.layer_types == (kind,) and model.held_experts == (chip * 4, 4)
            leaves = dict(params, moe={name: take(w, chip) if name.startswith("experts_")
                                       else w for name, w in params["moe"].items()})
            block = hybrid_lm.make_block(model, kind, True)
            y, counts = block(leaves, x, jnp.arange(SEQ))
            parts.append(y - x)
            rows += int(counts.sum())
        alike = dict(leaves, moe=dict(leaves["moe"], experts_w2_kernel=jnp.zeros_like(
            leaves["moe"]["experts_w2_kernel"])))
        once = block(alike, x, jnp.arange(SEQ))[0] - x
    np.testing.assert_allclose(sum(parts) - (CHIPS - 1) * once, whole,
                               atol=3e-5 * float(jnp.abs(whole).max()))
    assert rows == 3 * 2 * SEQ                  # every assignment computed on one chip
    assert float(jnp.abs(parts[0] - whole).max()) > 1e-2     # one share is not the layer


# (d) the configuration file ---------------------------------------------------------------


def test_the_configuration_is_one_period_of_one_chips_share():
    with open(CONFIG_FILE) as fh:
        config = json.load(fh)
    model = hybrid_lm.from_config(config, vocab_size=18992, seq_len=8192)
    assert model.layer_types == ("gdn", "gdn", "gdn", "full_attention")
    assert ref.kinds(config) == ["gdn", "gdn", "gdn", "attn"]
    assert ref.sparse(config) == [model.is_sparse(i) for i in range(4)] == [True] * 4
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))["params"]
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    delta = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128 + 4096 * 2048
    attention = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    experts = 2048 * 512 + 32 * 3 * 2048 * 512 + 3 * 2048 * 512 + 2048
    head = 2 * 18992 * 2048 + 2048
    assert (delta, attention, experts, head) == \
        (33_718_464, 27_263_488, 104_859_648, 77_793_280)
    by_hand = 3 * (delta + experts + 4096) + attention + experts + 4096 + head
    assert by_hand == 625_667_136
    assert count(shapes) == count(ref.param_shapes(config)) == config["parameters"] == by_hand
    assert count(shapes["layer_0"]["gdn"]) == delta
    assert count(shapes["layer_3"]["attn"]) == attention
    assert count(shapes["layer_0"]["moe"]) == experts and "expert_bias_b" not in shapes["layer_0"]["moe"]
    assert jax.tree.map(lambda x: x.shape, shapes) == \
        jax.tree.map(lambda x: x.shape, ref.param_shapes(config))
    assert (model.router_experts, model.held_experts, model.num_experts_per_tok,
            model.router_scoring) == (512, (0, 32), 10, "softmax")
    plan = model.expert_plan(2 * 8192)
    assert plan["row_bound"] == 10 * 2 * 8192 and plan["held"] == [0, 32]
    assert plan["scoring"] == "softmax" and "bias_update_rate" not in plan
    assert (model.rope_theta, model.rope_dim, model.qk_norm, model.attention_gate,
            model.tied_head, model.head_dim, model.value_head_dim, model.norm_eps,
            model.norm_unit_offset, model.shared_expert_size, model.gated_shared_expert,
            model.shared_expert_gate, model.gdn_heads, model.kda_head_dim,
            model.conv_kernel, model.num_dense_layers) == \
        (1e7, 64, True, True, False, 256, 256, 1e-6, True, 512, True, True, (16, 32), 128, 4, 0)
    assert model.rotary_plan() == {"rope_dim": 64, "rope_pairing": "half_split",
                                   "rope_theta": 1e7, "rotation": "permutation",
                                   "output_gate": "sigmoid"}
    gdn = model.gdn_plan()
    assert (gdn["heads"], gdn["key_heads"], gdn["key_dim"], gdn["value_dim"], gdn["chunk"],
            gdn["sub_block"], gdn["group"], gdn["decay"]) == \
        (32, 16, 128, 128, *kda.GDN_TILING, "scalar")
    assert model.kda_plan() is None
    assert sorted(config["reduced"]) == sorted(config["published"]) == \
        ["num_experts", "num_hidden_layers", "vocab_size"]
    for key, value in config["published"].items():
        assert config[key] != value
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["num_experts"] * config["share"]["expert_parallel"] == 512
    for key in ("deployment", "assumed", "share", "parameters", "reference"):
        assert config[key]


def test_every_number_of_the_catalogs_row_is_in_the_file_or_in_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(CONFIG_FILE) as fh:
        config = json.load(fh)
    with open(catalog) as fh:
        row = [json.loads(line) for line in fh if config["source"] in line][0]
    differ = sorted(k for k, v in row["config"].items() if config.get(k, "absent") != v)
    assert differ == sorted(config["reduced"])


@pytest.mark.parametrize("key, value, what", [
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("mlp_only_layers", [0], "mlp_only_layers"),
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("hidden_act", "gelu", "hidden_act"),
    ("attention_bias", True, "attention_bias"),
    ("partial_rotary_factor", 0.3, "partial_rotary_factor"),
    ("linear_num_value_heads", 3, "linear_num_key_heads"),
    ("linear_value_head_dim", 16, "linear_key_head_dim"),
    ("num_nextn_predict_layers", 1, "multi-token prediction"),
    ("mtp_num_hidden_layers", 1, "multi-token prediction")])
def test_what_the_file_states_and_the_module_does_not_compute_is_refused(key, value, what):
    with pytest.raises(ValueError, match=f"{what}.* is not written here"):
        hybrid_lm.from_config(tiny_config(**{key: value}), vocab_size=VOCAB, seq_len=SEQ)


def test_a_later_stage_holds_the_next_period():
    config = tiny_config(num_hidden_layers=5)
    config["share"] = dict(config["share"], first_layer=4)
    config["published"] = dict(config["published"], num_hidden_layers=12)
    model = hybrid_lm.from_config(config, vocab_size=VOCAB, seq_len=SEQ)
    assert model.layer_types == ("gdn", "gdn", "gdn", "full_attention", "gdn")
    assert ref.kinds(config) == ["gdn", "gdn", "gdn", "attn", "gdn"]
    config["share"]["first_layer"] = 9
    with pytest.raises(ValueError, match="shorter than first_layer"):
        hybrid_lm.from_config(config, vocab_size=VOCAB, seq_len=SEQ)


def test_the_other_families_keep_their_routers_and_norms():
    """What the family set is a field the others leave at today's value: a sigmoid router
    with its bias leaf, plain head norms over the whole head's rotation, no gate."""
    with open(os.path.join(BENCH, "configs", "lfm2-24b-a2b-ep8.json")) as fh:
        config = json.load(fh)
    model = hybrid_lm.from_config(config, vocab_size=config["vocab_size"], seq_len=8192)
    assert (model.router_scoring, model.rope_dim, model.attention_gate,
            model.shared_expert_gate, model.gdn_heads) == ("sigmoid", None, False, False, (0, 0))
    shapes = model.param_shapes()
    sparse = [i for i in range(len(model.layer_types)) if model.is_sparse(i)][0]
    assert "expert_bias_b" in shapes[f"layer_{sparse}"]["moe"]
    attn = [v["attn"] for v in shapes.values() if isinstance(v, dict) and "attn" in v][0]
    assert "q_norm_scale" in attn and attn["q_kernel"][1] == attn["out_kernel"][0]
    assert model.rotary_plan().keys() == {"rope_dim", "rope_pairing", "rope_theta", "rotation"}
    assert model.plans(jax.make_jaxpr(lambda: 0)(), 8)["gdn"] is None
    assert "scoring" not in model.expert_plan(8)
    assert moe.SCORINGS == ("sigmoid", "softmax")
    with pytest.raises(ValueError, match="router_scoring 'tanh'"):
        dataclasses.replace(model, router_scoring="tanh")


# (e) through train.lm -----------------------------------------------------------------------


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    from csed_514_project_distributed_training_using_pytorch_tpu.train import lm as train_lm
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import LMConfig
    work = tmp_path_factory.mktemp("qwen3_next_train")
    config_file = str(work / "tiny.json")
    with open(config_file, "w") as fh:
        json.dump(tiny_config(vocab_size=256), fh)
    tele = str(work / "t.jsonl")
    build_model = hybrid_lm.from_config
    with pytest.MonkeyPatch.context() as patch:     # the tiling is no key of the file
        patch.setattr(hybrid_lm, "from_config",
                      lambda *a, **kw: build_model(*a, **dict(kw, kda_tiling=TILING)))
        state, _ = train_lm.main(LMConfig(
            model_config=config_file, mesh="data=1", remat=True,
            corpus=os.path.join(REPO, "tests", "fixtures", "corpus_tiny"),
            epochs=2, batch_size=8, eval_batch=19, learning_rate=3e-3, seed=5,
            telemetry=tele, results_dir="", images_dir=str(work / "images"), generate=0))
    with open(tele) as fh:
        return state, [json.loads(line) for line in fh]


def test_main_trains_the_configuration_and_the_loss_falls(trained):
    _, events = trained
    epochs = [e for e in events if e["event"] == "epoch"]
    assert len(epochs) == 2 and epochs[1]["train_loss"] < epochs[0]["train_loss"]
    assert epochs[1]["val_loss"] < epochs[0]["val_loss"] < np.log(256) + 0.5
    for event in epochs:
        rows = np.asarray(event["expert_rows"])
        assert rows.shape == (event["steps"], 4)            # [steps, expert layers]
        assert 0 < rows.sum() <= 3 * 8 * 64 * rows.size     # under k·T


def test_the_compile_event_says_what_the_new_layers_ask(trained):
    state, events = trained
    event = [e for e in events if e["event"] == "compile"][0]
    attention = event["attention"]
    assert (attention["key_dim"], attention["value_dim"]) == (16, 16)
    assert (attention["rope_dim"], attention["rope_pairing"], attention["rope_theta"],
            attention["rotation"], attention["output_gate"]) == \
        (4, "half_split", 1e7, "permutation", "sigmoid")
    gdn = event["gdn"]
    assert (gdn["heads"], gdn["key_heads"], gdn["key_dim"], gdn["value_dim"], gdn["decay"],
            gdn["chunk"], gdn["sub_block"], gdn["group"]) == (4, 2, 8, 8, "scalar", *TILING)
    assert gdn["kept"] == ["kda_out", "kda_state"]
    assert gdn["vmem_limit_bytes"] == kda.VMEM_LIMIT    # what the kernels ask Mosaic for
    assert event["ssm"] is None and event["kda"] is None and event["eva"] is None
    assert event["experts"]["row_bound"] == 3 * 8 * 64 and event["experts"]["held"] == [0, 4]
    assert event["experts"]["scoring"] == "softmax"
    assert "bias_update_rate" not in event["experts"]
    assert event["recompute"]["kept_bytes"] > 0
    assert event["head_products"] == 3      # the [T, vocab] logits: once a pass
    assert {"gdn_mixer", "attention"} <= set(event["scopes"]["top_scopes"])
    with open(event["scopes"]["path"]) as fh:
        scopes = {scope for scope, _ in json.load(fh)["ops"].values() if scope}
    assert {"attention/rotary", "moe/shared", "moe/route"} <= scopes
    assert any(scope.startswith("gdn_mixer/gdn") for scope in scopes)
    assert not any("expert_bias_b" in name for name in flat(state.params))
