"""The ``kimi_linear`` stack of ``models/hybrid_lm.py``, ``ops/kda.py`` and the flash
kernels at a key width that is not the value width, against the plain reference
(``benchmark/reference/kimi_linear.py``, which imports nothing of the program) and
against hand-written loops: small sizes, float32, seeded weights; Pallas in interpret
mode."""

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

from reference import kimi_linear as ref  # noqa: E402
from reference import precision as prec  # noqa: E402
import weights as bench_weights  # noqa: E402

from csed_514_project_distributed_training_using_pytorch_tpu import ops  # noqa: E402
from csed_514_project_distributed_training_using_pytorch_tpu.models import (  # noqa: E402
    hybrid_lm,
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops import (  # noqa: E402
    kda, pallas_attention as pa,
)

CONFIG_FILE = os.path.join(BENCH, "configs", "kimi-linear-48b-a3b-ep32.json")
SEQ, VOCAB = 24, 64
TILING = (8, 4, 2)      # chunks of 8 tokens in sub-blocks of 4, a state kept every 16
MM, ES = prec.matmul("highest"), prec.einsum("highest")


def tiny_config(**changes) -> dict:
    """The benchmark's configuration with its widths and its depth cut: 4 of 16 experts
    held, 3 of them a token, 4 KDA heads of 8, 4 attention heads of 8 + 4 key and 8 value
    channels over a latent of 16; three layers, one of each kind the file's five have:
    KDA + dense, MLA + experts, KDA + experts."""
    with open(CONFIG_FILE) as fh:
        config = json.load(fh)
    config.update(hidden_size=32, intermediate_size=48, moe_intermediate_size=24,
                  num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=16,
                  qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                  num_experts=4, num_experts_per_token=3, vocab_size=VOCAB,
                  num_hidden_layers=3)
    config["linear_attn_config"] = dict(config["linear_attn_config"], num_heads=4, head_dim=8,
                                        kda_layers=[1, 3], full_attn_layers=[2])
    config["published"] = dict(config["published"], num_experts=16, num_hidden_layers=3)
    config.update(changes)
    return config


def build(config, seed=20260929, **kw):
    model = hybrid_lm.from_config(config, vocab_size=config["vocab_size"], seq_len=SEQ,
                                  expert_block=8, kda_tiling=TILING, **kw)
    return model, bench_weights.make(ref.param_shapes(config), seed)


def tokens(batch=2, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, VOCAB, (batch, SEQ)),
                       jnp.int32)


# (a) the scan ----------------------------------------------------------------------------


EPS = 1e-5      # under the output norm's root


def scan_inputs(b, s, h, k, v, decay, seed=0):
    """By head: ``q̃``, ``k̃`` of no particular length, as a projection's silu leaves them;
    ``g = −decay · softplus(N(0, 1))`` a channel; ``β`` a sigmoid."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (b, s, h, k)), jax.random.normal(ks[1], (b, s, h, k)),
            jax.random.normal(ks[2], (b, s, h, v)),
            -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, s, h, k))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h))))


def token_by_token(q, k, v, g, beta):
    """The definition: ``S_t = (I − β_t k_t k_tᵀ) Diag(exp(g_t)) S_{t−1} + β_t k_t v_tᵀ``,
    ``o_t = S_tᵀ q_t``, one token after the other from a zero state."""
    def token(state, now):
        q_t, k_t, v_t, g_t, b_t = now
        state = jnp.exp(g_t)[..., None] * state
        state = state - jnp.einsum("bhk,bhj,bhjv->bhkv", b_t[..., None] * k_t, k_t, state) \
            + jnp.einsum("bhk,bhv->bhkv", b_t[..., None] * k_t, v_t)
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    zero = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:])
    _, o = jax.lax.scan(token, zero, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def normed_recurrence(q, k, v, g, beta):
    """What ``kda_scan`` computes, in plain ``jnp`` by head: ``q̃`` to length ``K^-½`` and
    ``k̃`` to length one (``1e-6`` under the root), the recurrence, and each head's output
    over its root mean square."""
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    o = token_by_token(unit(q) * q.shape[-1] ** -0.5, unit(k), v, g, beta)
    return o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + EPS)


def flat_scan(q, k, v, g, beta, **tiles):
    """``kda_scan`` on the flat layout it takes, from and to the tests' layout by head."""
    flat = lambda x: x.reshape(x.shape[:2] + (-1,))
    return kda.kda_scan(flat(q), flat(k), flat(v), flat(g), beta, eps=EPS,
                        **tiles).reshape(v.shape)


OPERANDS = ("q", "k", "v", "g", "beta")


def scan_and_gradients(fn, args, w):
    return fn(*args), jax.grad(lambda *a: jnp.sum(w * fn(*a).astype(jnp.float32)),
                               argnums=(0, 1, 2, 3, 4))(*args)


def assert_the_scan_is_the_recurrence(args, tiles, out=2e-5, grad=3e-5):
    """Output and the gradient of every operand, each within its share of the largest
    entry of the float32 recurrence's on the same values."""
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    got, grads = scan_and_gradients(functools.partial(flat_scan, **tiles), args, w)
    with jax.default_matmul_precision("highest"):
        want, wants = scan_and_gradients(
            normed_recurrence, tuple(x.astype(jnp.float32) for x in args), w)
    np.testing.assert_allclose(got.astype(jnp.float32), want,
                               atol=out * float(jnp.abs(want).max()))
    for name, g, r in zip(OPERANDS, grads, wants):
        assert np.isfinite(np.asarray(g, np.float32)).all(), name
        np.testing.assert_allclose(g.astype(jnp.float32), r,
                                   atol=grad * float(jnp.abs(r).max()), err_msg=name)
    return grads


def test_the_references_recurrence_is_the_definition():
    q, k, v, g, beta = scan_inputs(1, 40, 2, 8, 8, 1.0)
    with jax.default_matmul_precision("highest"):
        got = ref.delta_rule(q[0], k[0], v[0], g[0], beta[0], ES)
    np.testing.assert_allclose(got, token_by_token(q, k, v, g, beta)[0], atol=1e-5)


KDA_CHUNK = kda.KDA_TILING.chunk
KDA_ROWS = KDA_CHUNK * kda.KDA_TILING.group     # tokens of a grid step
# (batch, S, heads, K, V, chunk, sub, group, decay): a chunk of 16 tokens at decay 8 sums
# log-decays of −8 · softplus(N(0, 1)) a token: G passes −100 inside it
SCAN_SIZES = {
    "decays near 0": (2, 64, 2, 16, 8, 16, 4, 2, 0.01),
    "a seeded model's decays": (2, 64, 2, 16, 8, 16, 4, 2, 1.0),
    "G passes -100 inside a chunk": (2, 64, 2, 16, 8, 16, 4, 2, 8.0),
    "a ragged tail is padded": (1, 21, 2, 8, 8, 8, 4, 2, 1.0),
    "shorter than a chunk": (1, 5, 1, 8, 16, 8, 4, 1, 1.0),
    "one sub-block a chunk": (1, 32, 2, 8, 8, 8, 8, 2, 1.0),
    "published tile: chunk 64, sub-block 16, 128 x 128": (1, 128, 1, 128, 128, 64, 16, 2, 1.0),
    "published tile: chunk 64, sub-block 8, 128 x 128": (1, 128, 1, 128, 128, 64, 8, 2, 1.0),
    "published tile: the committed chunk and sub-block (ops.kda.KDA_TILING), 128 x 128":
        (1, 2 * KDA_CHUNK, 1, 128, 128, KDA_CHUNK, kda.KDA_TILING.sub, 2, 1.0),
    # every pair that is not inside a sub-block of 4 is a product of two rescaled operands
    "published tile, steep: G passes -100 inside a chunk":
        (1, 2 * KDA_CHUNK, 1, 128, 128, KDA_CHUNK, kda.KDA_TILING.sub, 2, 8.0),
    # the whole committed tiling, its group too: a kept state enters a second group, whose
    # tail is padding (no multiple of the rows of a grid step)
    "committed tiling, 128 x 128: a second, padded group":
        (1, KDA_ROWS + KDA_CHUNK + 6, 1, 128, 128, *kda.KDA_TILING, 1.0),
    "committed tiling, 128 x 128, steep: a second, padded group":
        (1, KDA_ROWS + KDA_CHUNK + 6, 1, 128, 128, *kda.KDA_TILING, 8.0),
    # the tiling committed until PR 45, by value: a chunk of 64 stays held to the recurrence
    # whatever the constant becomes
    "chunk 64, sub-block 4, group 4, 128 x 128: a second, padded group":
        (1, 256 + 64 + 6, 1, 128, 128, 64, 4, 4, 1.0),
    "chunk 64, sub-block 4, group 4, 128 x 128, steep: a second, padded group":
        (1, 256 + 64 + 6, 1, 128, 128, 64, 4, 4, 8.0),
}


@pytest.mark.parametrize("size", SCAN_SIZES)
def test_the_scan_kernels_match_the_recurrence(size):
    """``kda_fwd`` and ``kda_bwd`` (the two unit norms, β and the output's statistic on the
    head's block; chunks, sub-blocks, the triangular inverse, a carried state, states kept
    a group) against the token-by-token recurrence with the norms, β and the statistic
    in plain ``jnp``: the output and the gradient of every operand, ``dβ`` among them, at
    decays that leave float32 if ``exp(−G)`` were ever formed and at decays near none."""
    *shape, chunk, sub, group, decay = SCAN_SIZES[size]
    args = scan_inputs(*shape, decay)
    if decay == 8.0:
        assert float(jnp.min(jnp.sum(args[3][:, :chunk], axis=1))) < -100
    with jax.default_matmul_precision("highest"):
        assert_the_scan_is_the_recurrence(args, dict(chunk=chunk, sub=sub, group=group))


@pytest.mark.parametrize("zeroed", ["q", "k", "q and k"])
def test_a_head_of_zeros_keeps_the_norms_finite(zeroed):
    """Some tokens of one head have ``q̃`` or ``k̃`` all zeros (silu of very negative
    channels): the ``1e-6`` under the root keeps the unit norm and its gradient finite
    inside the kernels, and both are the recurrence's."""
    q, k, v, g, beta = scan_inputs(2, 32, 2, 8, 8, 1.0, seed=3)
    gone = jnp.zeros((2, 32, 2, 1)).at[:, 3:9, 1].set(1.0).at[1, 20:, 0].set(1.0) == 1.0
    args = (jnp.where(gone, 0.0, q) if "q" in zeroed else q,
            jnp.where(gone, 0.0, k) if "k" in zeroed else k, v, g, beta)
    with jax.default_matmul_precision("highest"):
        assert_the_scan_is_the_recurrence(args, dict(chunk=8, sub=4, group=2))


@pytest.mark.parametrize("tail", [3, 11, 16])
def test_padding_tokens_write_nothing_and_hand_back_no_gradient(tail):
    """The tokens ``kda_scan`` pads a sequence with (``g = 0``, ``β = 0``, zero ``q̃``, ``k̃``,
    ``v``), given as a tail of the operands: their rows of the output are zero, the rows
    before them and their gradients are the unpadded sequence's, and the tail's own
    gradients are zero and not a NaN of ``0 · rsqrt(0)``."""
    args = scan_inputs(1, 21, 2, 8, 8, 1.0, seed=4)
    padded = tuple(jnp.pad(x, ((0, 0), (0, tail)) + ((0, 0),) * (x.ndim - 2)) for x in args)
    scan = functools.partial(flat_scan, chunk=8, sub=4, group=2)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    with jax.default_matmul_precision("highest"):
        want, wants = scan_and_gradients(scan, args, w)
        got, grads = scan_and_gradients(scan, padded, jnp.pad(w, ((0, 0), (0, tail), (0, 0),
                                                                  (0, 0))))
    assert float(jnp.abs(got[:, 21:]).max()) == 0.0
    np.testing.assert_allclose(got[:, :21], want, atol=1e-6)
    for name, g, r in zip(OPERANDS, grads, wants):
        assert float(jnp.abs(g[:, 21:]).max()) == 0.0, name
        np.testing.assert_allclose(g[:, :21], r, atol=1e-6 * float(jnp.abs(r).max()),
                                   err_msg=name)


BF16 = 0.03     # of the largest entry: what the bf16 path met with the norms and β outside


@pytest.mark.parametrize("size", SCAN_SIZES)
def test_bf16_operands_stay_near_the_float32_recurrence(size):
    """``q̃``, ``k̃``, ``v`` in bfloat16 as the model hands them (``g``, ``β`` float32) against
    the float32 recurrence on the same rounded values, output and gradients. With the
    norms and β applied outside the kernels and rounded to bfloat16 on the way in (PR 32)
    these inputs read up to 0.027 of the largest entry (output) and 0.030 (gradients);
    inside, where nothing is rounded before the products' own casts, 0.012 and 0.014."""
    *shape, chunk, sub, group, decay = SCAN_SIZES[size]
    q, k, v, g, beta = scan_inputs(*shape, decay)
    low = tuple(x.astype(jnp.bfloat16) for x in (q, k, v)) + (g, beta)
    grads = assert_the_scan_is_the_recurrence(low, dict(chunk=chunk, sub=sub, group=group),
                                              out=BF16, grad=BF16)
    assert grads[0].dtype == grads[2].dtype == jnp.bfloat16 and grads[4].dtype == jnp.float32


def test_the_scan_refuses_sub_blocks_that_do_not_halve_a_chunk():
    with pytest.raises(ValueError, match="sub-blocks"):
        flat_scan(*scan_inputs(1, 24, 1, 8, 8, 1.0), chunk=24, sub=8)


def test_the_scan_plan_counts_the_states_a_sequence_keeps():
    plan = kda.scan_plan(heads=32, key_dim=128, value_dim=128, seq_len=8192,
                         kept=hybrid_lm.KEPT)
    chunk, sub, group = kda.KDA_TILING
    assert plan == {"heads": 32, "key_dim": 128, "value_dim": 128, "chunk": chunk,
                    "sub_block": sub, "group": group, "chunks_per_sequence": 8192 // chunk,
                    "states_per_sequence": 8192 // KDA_ROWS,
                    "state_bytes_per_sequence": 8192 // KDA_ROWS * 32 * 128 * 128 * 4,
                    "kept": ["kda_out", "kda_state"],
                    "in_kernel": ["q_norm", "k_norm", "beta", "out_norm"],
                    "vmem_limit_bytes": kda.VMEM_LIMIT}


@pytest.mark.parametrize("key_heads", [None, 16], ids=["a decay a channel", "a scalar decay"])
def test_the_scan_plan_reports_the_fast_memory_the_kernels_ask_for(key_heads):
    """The ``compile`` event's ``kda`` / ``gdn`` field says what scoped fast memory the kernels
    were built with: what ``_params`` hands ``pallas_call``, one limit for both decay kinds."""
    plan = kda.scan_plan(heads=32, key_dim=128, value_dim=128, seq_len=8192, key_heads=key_heads)
    assert plan["vmem_limit_bytes"] == kda._params().vmem_limit_bytes == kda.VMEM_LIMIT
    assert 16 << 20 < plan["vmem_limit_bytes"] <= 48 << 20


@pytest.mark.parametrize("tiling", [None, (16, 4, 2)], ids=["the kernels' own", "a triple"])
def test_the_kda_plan_reads_the_tiling_the_kernels_are_built_with(monkeypatch, tiling):
    """The published file names no tiling. What ``kda_mixer`` hands ``ops.kda`` to build
    ``kda_fwd`` / ``kda_bwd`` with is what ``kda_plan`` (the ``compile`` event's ``kda``) reports:
    the per-channel branch's own, or the ``kda_tiling`` triple the caller passed."""
    with open(CONFIG_FILE) as fh:
        config = json.load(fh)
    model = hybrid_lm.from_config(config, vocab_size=config["vocab_size"], seq_len=8192,
                                  dtype=jnp.bfloat16, **({"kda_tiling": tiling} if tiling else {}))
    built = []

    def make_op(chunk, sub, group, eps, scalar=False, rep=1):
        built.append(((chunk, sub, group), scalar))
        return lambda q, k, v, g, beta: v

    monkeypatch.setattr(kda, "_make_op", make_op)
    number = config["linear_attn_config"]["kda_layers"][0] - 1      # the file counts from 1
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))["params"]
    p = shapes[f"layer_{number}"]["kda"]
    u = jax.ShapeDtypeStruct((2, 8192, model.hidden_size), jnp.bfloat16)
    jax.eval_shape(lambda p, u: hybrid_lm.kda_mixer(p, u, model), p, u)
    plan = model.kda_plan()
    assert built == [((plan["chunk"], plan["sub_block"], plan["group"]), False)]
    assert built[0][0] == (tiling or kda.KDA_TILING)
    assert plan["states_per_sequence"] == 8192 // (plan["chunk"] * plan["group"])
    assert model.gdn_plan() is None


# (b) latent attention through the flash kernels ---------------------------------------------


def plain_softmax(q, k, v, scale):
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    seen = jnp.arange(q.shape[1])[:, None] >= jnp.arange(k.shape[1])[None]
    return jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1), v)


@pytest.mark.parametrize("widths", [(192, 128), (24, 8), (8, 24)])
def test_the_flash_kernels_take_a_key_width_that_is_not_the_value_width(widths):
    """Causal attention at (key, value) widths 192 / 128, latent attention's, and two
    small pairs either way round: the output and the three gradients against plain
    softmax over materialised scores, scaled by the key width's root."""
    dk, dv = widths
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k = (jax.random.normal(key, (1, 256, 2, dk)) for key in ks[:2])
    v, w = (jax.random.normal(key, (1, 256, 2, dv)) for key in ks[2:])
    with jax.default_matmul_precision("highest"):
        got = pa.flash_attention(q, k, v, causal=True, block=128)
        want = plain_softmax(q, k, v, dk ** -0.5)
        np.testing.assert_allclose(got, want, atol=2e-5)
        grads = jax.grad(lambda *a: jnp.sum(w * pa.flash_attention(*a, causal=True, block=128)),
                         argnums=(0, 1, 2))(q, k, v)
        wants = jax.grad(lambda *a: jnp.sum(w * plain_softmax(*a, dk ** -0.5)),
                         argnums=(0, 1, 2))(q, k, v)
    for name, g, r in zip("qkv", grads, wants):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, atol=3e-5 * float(jnp.abs(r).max()), err_msg=name)


def test_the_mixer_runs_latent_attention_through_the_dispatcher(monkeypatch):
    """With the thresholds out of the way the model's MLA mixer takes the flash kernels
    (keys of 12 channels, values of 8) and agrees with the reference's mixer."""
    monkeypatch.setattr(pa, "FLASH_MIN_SCORE_BYTES", 1)
    monkeypatch.setattr(pa, "FLASH_MIN_HEAD_SCORE_BYTES", 1)
    config = tiny_config()
    model, params = build(config, attention_fn=ops.dispatch_attention)
    assert ops.dispatch_plan((2, SEQ, 4, model.head_dim), causal=True,
                             value_dim=model.value_head_dim)["impl"] == "flash"
    u = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 32))
    p = params["layer_1"]["mla"]
    with jax.default_matmul_precision("highest"):
        got = hybrid_lm.mla_mixer(p, u, jnp.arange(SEQ), model)
        want = jax.vmap(lambda row: ref.mla_mixer(p, row, config, MM, ES))(u)
    np.testing.assert_allclose(got, want, atol=3e-5 * float(jnp.abs(want).max()))


# (c) the model against the reference -------------------------------------------------------


def program_loss(model, params, ids):
    return model.loss(params, ids)[0]


def test_logits_match_the_reference():
    config = tiny_config()
    model, params = build(config)
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
        got, grads = jax.value_and_grad(lambda p: program_loss(model, p, ids))(params)
        want, wants = jax.value_and_grad(lambda p: ref.loss(p, ids, config))(params)
    assert abs(float(got) - float(want)) < 1e-5
    flat = lambda tree: {jax.tree_util.keystr(k): v
                         for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}
    got, want = flat(grads), flat(wants)
    assert got.keys() == want.keys()
    for name in want:
        scale = max(float(jnp.abs(want[name]).max()), 1e-3)
        np.testing.assert_allclose(got[name], want[name], atol=2e-4 * scale, err_msg=name)
    bias = [g for name, g in got.items() if "expert_bias_b" in name]
    assert bias and all(float(jnp.abs(g).max()) == 0.0 for g in bias)


def test_router_choices_are_the_references():
    config = tiny_config()
    model, params = build(config)
    ids = tokens()
    with jax.default_matmul_precision("highest"):
        got = np.sort(np.asarray(model.router_choices(params, ids, 2)), axis=-1)
        want = np.sort(np.asarray(jax.vmap(
            lambda row: ref.router_choice(params, row, config, 2))(ids)), axis=-1)
    assert got.shape == (2, SEQ, 3) and (got == want).all()


FAULTS = ["state not carried across chunks", "beta dropped from the correction",
          "decay applied after the correction", "shared expert dropped",
          "2 of a token's 3 experts", "k_pe rotated", "scale by the nope width alone"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_comparison(fault, monkeypatch):
    """Each fault moves the loss by far more than the 1e-5 the sound program is held
    to above."""
    config = tiny_config()
    model, params = build(config)
    whole = kda.kda_scan
    if fault == "state not carried across chunks":
        def chunk_by_chunk(q, k, v, g, beta, *, chunk, **kw):
            cut = lambda x: x.reshape((-1, chunk) + x.shape[2:])
            return whole(*map(cut, (q, k, v, g, beta)), chunk=chunk, **kw).reshape(v.shape)

        monkeypatch.setattr(hybrid_lm.kda, "kda_scan", chunk_by_chunk)
    elif fault == "beta dropped from the correction":
        # S_t = (I − k kᵀ) Diag(α) S + β k vᵀ: β = 1 with the values scaled in its place
        monkeypatch.setattr(hybrid_lm.kda, "kda_scan", lambda q, k, v, g, beta, **kw: whole(
            q, k, jnp.repeat(beta, v.shape[-1] // beta.shape[-1], axis=-1) * v, g,
            jnp.ones_like(beta), **kw))
    elif fault == "decay applied after the correction":
        # S_t = Diag(α)(I − β k kᵀ) S + β k vᵀ: the key the correction reads is k / α's
        # side of the state, which a decay one token late gives
        monkeypatch.setattr(hybrid_lm.kda, "kda_scan", lambda q, k, v, g, beta, **kw: whole(
            q, k, v, jnp.pad(g, ((0, 0), (1, 0), (0, 0)))[:, :-1], beta, **kw))
    elif fault == "shared expert dropped":
        model = dataclasses.replace(model, shared_expert_size=0)
    elif fault == "2 of a token's 3 experts":
        model = dataclasses.replace(model, num_experts_per_tok=2)
    elif fault == "k_pe rotated":
        from csed_514_project_distributed_training_using_pytorch_tpu.ops.rotary import (
            apply_rotary,
        )

        def rotated(q, k, v, *, causal):
            turn = lambda x: jnp.concatenate(
                [x[..., :8], apply_rotary(x[..., 8:], jnp.arange(x.shape[1]), base=10000.0)],
                axis=-1)
            return ops.full_attention(turn(q), turn(k), v, causal=causal)

        model = dataclasses.replace(model, attention_fn=rotated)
    else:
        model = dataclasses.replace(
            model, attention_fn=lambda q, k, v, *, causal: ops.full_attention(
                q * (12 / 8) ** 0.5, k, v, causal=causal))
    ids = tokens()
    with jax.default_matmul_precision("highest"):
        got = float(program_loss(model, params, ids))
        want = float(ref.loss(params, ids, config))
    assert abs(got - want) > 1e-3, (got, want)


# (d) the share tied to the model -------------------------------------------------------------

CHIPS = 4       # expert-parallel 4: four experts of 16 a chip


@pytest.mark.parametrize("first_layer", [3, 2], ids=["kda + experts", "mla + experts"])
def test_the_shares_add_up_to_the_uncut_layer(first_layer):
    """Four chips divide a layer's 16 experts; each computes the mixer, the router and
    the shared expert whole and its own experts' part. What the four add to the
    residual, with what every chip computes alike (a share whose experts' second
    matrices are zero) counted once, is what the uncut reference's layer adds."""
    config = tiny_config(num_experts=16, num_hidden_layers=1)
    config["share"] = dict(config["share"], first_layer=first_layer)
    params = bench_weights.make(ref.param_shapes(config), 7)["layer_0"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, 32))
    kind = ref.kinds(config)[0]
    take = lambda w, chip: jax.lax.slice_in_dim(
        w, chip * w.shape[1] // CHIPS, (chip + 1) * w.shape[1] // CHIPS, axis=1)
    with jax.default_matmul_precision("highest"):
        whole = jax.vmap(lambda row: ref._layer(params, row, config, kind, True,
                                                MM, ES)[0])(x) - x
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
            y, (counts, load) = block(leaves, x, jnp.arange(SEQ))
            parts.append(y - x)
            rows += int(counts.sum())
            assert load.shape == (16,) and int(load.sum()) == 3 * 2 * SEQ
        alike = dict(leaves, moe=dict(leaves["moe"], experts_w2_kernel=jnp.zeros_like(
            leaves["moe"]["experts_w2_kernel"])))
        once = block(alike, x, jnp.arange(SEQ))[0] - x
    np.testing.assert_allclose(sum(parts) - (CHIPS - 1) * once, whole,
                               atol=3e-5 * float(jnp.abs(whole).max()))
    assert rows == 3 * 2 * SEQ                  # every assignment computed on one chip
    assert float(jnp.abs(parts[0] - whole).max()) > 1e-2     # one share is not the layer


# (e) the configuration file ------------------------------------------------------------------


def test_the_configuration_is_one_period_of_one_chips_share():
    with open(CONFIG_FILE) as fh:
        config = json.load(fh)
    model = hybrid_lm.from_config(config, vocab_size=20480, seq_len=8192)
    assert model.layer_types == ("kda", "kda", "kda", "mla", "kda")
    assert ref.kinds(config) == list(model.layer_types)
    assert ref.sparse(config) == [model.is_sparse(i) for i in range(5)] == \
        [False, True, True, True, True]
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))["params"]
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == count(ref.param_shapes(config)) == config["parameters"] \
        == 602_434_432
    assert count(shapes["layer_0"]["kda"]) == 39_514_272
    assert count(shapes["layer_0"]["ff"]) == 63_700_992
    assert count(shapes["layer_3"]["mla"]) == 29_114_880
    assert count(shapes["layer_1"]["moe"]) == 64_291_072
    assert jax.tree.map(lambda x: x.shape, shapes) == \
        jax.tree.map(lambda x: x.shape, ref.param_shapes(config))
    assert (model.router_experts, model.held_experts, model.num_experts_per_tok) == \
        (256, (0, 8), 8)
    assert model.expert_plan(2 * 8192)["row_bound"] == 8 * 2 * 8192
    assert model.expert_plan(2 * 8192)["bias_update_rate"] == \
        config["moe_router_bias_update_rate"]
    assert model.kda_plan()["states_per_sequence"] == 8192 // KDA_ROWS
    assert (model.rope_theta, model.qk_norm, model.tied_head, model.head_dim,
            model.value_head_dim, model.routed_scaling_factor) == \
        (None, False, False, 192, 128, 2.446)
    assert sorted(config["reduced"]) == sorted(config["published"]) == \
        ["num_experts", "num_hidden_layers", "vocab_size"]
    for key, value in config["published"].items():
        assert config[key] != value


@pytest.mark.parametrize("key, value, what", [
    ("q_lora_rank", 1536, "q_lora_rank"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("mla_use_nope", False, "mla_use_nope"),
    ("num_expert_group", 8, "grouped expert selection"),
    ("topk_group", 4, "grouped expert selection"),
    ("num_nextn_predict_layers", 1, "multi-token prediction"),
    ("moe_layer_freq", 2, "moe_layer_freq"),
    ("hidden_act", "gelu", "hidden_act"),
    ("moe_router_activation_func", "softmax", "moe_router_activation_func"),
    ("moe_renormalize", False, "moe_renormalize")])
def test_what_the_file_states_and_the_module_does_not_compute_is_refused(key, value, what):
    with pytest.raises(ValueError, match=f"{what}.* is not written here"):
        hybrid_lm.from_config(tiny_config(**{key: value}), vocab_size=VOCAB, seq_len=SEQ)


def test_a_share_cannot_start_before_the_files_first_layer():
    config = tiny_config()
    config["share"] = dict(config["share"], first_layer=0)
    with pytest.raises(ValueError, match="first_layer"):
        hybrid_lm.from_config(config, vocab_size=VOCAB, seq_len=SEQ)


# (f) through train.lm -------------------------------------------------------------------------


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    from csed_514_project_distributed_training_using_pytorch_tpu.train import lm as train_lm
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import LMConfig
    work = tmp_path_factory.mktemp("kimi_train")
    config_file = str(work / "tiny.json")
    with open(config_file, "w") as fh:
        json.dump(tiny_config(vocab_size=256), fh)
    tele = str(work / "t.jsonl")
    build = hybrid_lm.from_config
    with pytest.MonkeyPatch.context() as patch:     # the tiling is no key of the file
        patch.setattr(hybrid_lm, "from_config",
                      lambda *a, **kw: build(*a, **dict(kw, kda_tiling=TILING)))
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
        assert rows.shape == (event["steps"], 2)            # [steps, expert layers]
        assert 0 < rows.sum() <= 3 * 8 * 64 * rows.size     # under min(k, held)·T


def test_the_compile_event_says_what_the_new_layers_ask(trained):
    state, events = trained
    event = [e for e in events if e["event"] == "compile"][0]
    assert event["kda"] == {"heads": 4, "key_dim": 8, "value_dim": 8, "chunk": 8,
                            "sub_block": 4, "group": 2, "chunks_per_sequence": 8,
                            "states_per_sequence": 4,
                            "state_bytes_per_sequence": 4 * 4 * 8 * 8 * 4,
                            "kept": ["kda_out", "kda_state"],
                            # the per-head scalars the kernels compute on a head's block
                            "in_kernel": ["q_norm", "k_norm", "beta", "out_norm"],
                            # the scoped fast memory the kernels ask Mosaic for
                            "vmem_limit_bytes": kda.VMEM_LIMIT}
    assert event["ssm"] is None
    assert (event["attention"]["key_dim"], event["attention"]["value_dim"]) == (12, 8)
    assert event["experts"]["row_bound"] == 3 * 8 * 64 and event["experts"]["held"] == [0, 4]
    assert event["recompute"]["kept_bytes"] > 0 and "mla_latent" in event["recompute"]["kept"]
    assert event["head_products"] == 3      # the [T, vocab] logits: once a pass
    # a stream in the model's dtype: three blocks' two norms and the last, the compiler's
    assert event["norm"] == {"impl": "xla", "calls": 7}
    rate = event["experts"]["bias_update_rate"]
    # the selection's bias: out of AdamW, moved by the balancing rule alone, a rate a step
    steps = sum(e["steps"] for e in events if e["event"] == "epoch")
    moved = np.asarray(state.params["layer_1"]["moe"]["expert_bias_b"]) / rate
    np.testing.assert_allclose(moved, np.round(moved), atol=1e-3)
    assert 0 < np.abs(moved).max() <= steps
