"""``models/hybrid_lm.py`` and ``ops/moe.py`` against the plain reference
(``benchmark/reference/lfm2_moe.py``, which imports nothing of the program) and
against hand-written loops: small sizes, float32, seeded weights."""

import dataclasses
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

from reference import lfm2_moe as ref  # noqa: E402
import head_rule  # noqa: E402
import weights as bench_weights  # noqa: E402

from csed_514_project_distributed_training_using_pytorch_tpu import ops  # noqa: E402
from csed_514_project_distributed_training_using_pytorch_tpu.models import (  # noqa: E402
    hybrid_lm,
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops import moe, optim  # noqa: E402
from csed_514_project_distributed_training_using_pytorch_tpu.ops.rotary import (  # noqa: E402
    apply_rotary,
)

CONFIG_FILE = os.path.join(BENCH, "configs", "lfm2-24b-a2b-ep8.json")
SEQ, VOCAB = 32, 64


def tiny_config(**changes) -> dict:
    """The benchmark's configuration with its widths cut: 4 of 16 experts held."""
    with open(CONFIG_FILE) as fh:
        config = json.load(fh)
    config.update(hidden_size=32, intermediate_size=48, moe_intermediate_size=24,
                  num_attention_heads=4, num_key_value_heads=2, vocab_size=VOCAB,
                  num_experts=4)
    config["published"] = dict(config["published"], num_experts=16)
    config.update(changes)
    return config


def build(config, **kw):
    model = hybrid_lm.from_config(config, vocab_size=VOCAB, seq_len=SEQ,
                                  expert_block=8, **kw)
    params = bench_weights.make(ref.param_shapes(config), 20260928)
    return model, params


def tokens(batch=3, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, VOCAB, (batch, SEQ)),
                       jnp.int32)


def one_layer(kind, sparse):
    return tiny_config(layer_types=[kind], num_hidden_layers=1,
                       num_dense_layers=0 if sparse else 1, share={"first_layer": 0})


STACKS = {
    "conv+dense": one_layer("conv", False),
    "attention+dense": one_layer("full_attention", False),
    "conv+sparse": one_layer("conv", True),
    "attention+sparse": one_layer("full_attention", True),
    "published layers 1-5": tiny_config(),
}


# (a) every block kind and the whole model against the reference ------------------------


@pytest.mark.parametrize("stack", STACKS)
def test_logits_match_the_reference(stack):
    config = STACKS[stack]
    model, params = build(config)
    got = model.apply({"params": params}, tokens())
    want = jax.nn.log_softmax(jax.vmap(lambda t: ref.logits(params, t, config))(tokens()))
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("stack", STACKS)
def test_loss_and_gradients_match_the_reference(stack, remat):
    config = STACKS[stack]
    model, params = build(config, remat=remat)
    (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(params, tokens())
    want, want_grads = jax.value_and_grad(lambda p: ref.loss(p, tokens(), config))(params)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.abs(w).max()) + 1e-9
        assert float(jnp.abs(g - w).max()) <= 2e-4 * scale, jax.tree_util.keystr(path)


@pytest.mark.parametrize("stack", STACKS)
def test_remat_changes_no_float(stack):
    """What ``remat`` keeps is what it would have recomputed: the loss and every
    gradient leaf are the same floats with and without it."""
    results = []
    for remat in (False, True):
        model, params = build(STACKS[stack], remat=remat)
        results.append(jax.value_and_grad(model.loss, has_aux=True)(params, tokens()))
    ((loss, _), grads), ((want, _), want_grads) = results
    np.testing.assert_array_equal(loss, want)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_array_equal(g, w, err_msg=jax.tree_util.keystr(path))


# (a') the head's loss and its rule: a tied table [vocab, d] ------------------------------


@pytest.mark.parametrize("dtype", head_rule.DTYPES)
def test_the_heads_rule_gives_the_plain_formulas_value_and_gradients(dtype):
    model, params = build(tiny_config(), dtype=head_rule.DTYPES[dtype])
    head_rule.check_value_and_gradients(model, params, tokens(), dtype)


@pytest.mark.parametrize("scale", [1 / (3 * (SEQ - 1)), 3.0], ids=["the mean", "times 3"])
@pytest.mark.parametrize("dtype", head_rule.DTYPES)
def test_a_cotangent_scales_the_heads_two_gradients(dtype, scale):
    model, params = build(tiny_config(), dtype=head_rule.DTYPES[dtype])
    head_rule.check_a_cotangent_scales_both_gradients(model, params, tokens(), dtype, scale)


@pytest.mark.parametrize("dtype", head_rule.DTYPES)
def test_a_sequences_last_row_gets_no_gradient_from_the_head(dtype):
    model, params = build(tiny_config(), dtype=head_rule.DTYPES[dtype])
    head_rule.check_the_last_row_gets_no_gradient(model, params, tokens())


@pytest.mark.parametrize("case", head_rule.PRODUCT_CASES)
def test_the_logits_are_multiplied_once_a_pass(case, monkeypatch):
    head_rule.check_head_products(lambda **kw: build(tiny_config(), **kw), tokens(),
                                  case, monkeypatch)


def _forward_runs(model, params):
    """``(flash_fwd calls, top_k's, sorts)`` in the loss's value and gradient."""
    jaxpr = jax.make_jaxpr(jax.value_and_grad(model.loss, has_aux=True))(params, tokens())
    names = [eqn.params["name"] if eqn.primitive.name == "pallas_call"
             else eqn.primitive.name for eqn in hybrid_lm._equations(jaxpr.jaxpr)]
    return names.count("flash_fwd"), names.count("top_k"), names.count("sort")


def test_remat_runs_the_flash_forward_the_router_and_the_sort_once(monkeypatch):
    """Published layers 1-5 (one attention layer, four sparse; the flash kernels in
    interpret mode): the backward pass of
    a block takes the flash kernel's output and statistics, the router's choice and
    the sort's products from its forward pass. Under a ``jax.checkpoint`` with no
    policy, as before PR 29, each ran again."""
    model, params = build(tiny_config(), remat=True, attention_fn=ops.flash_attention)
    assert _forward_runs(model, params) == (1, 4, 4)
    plain, _ = build(tiny_config(), attention_fn=ops.flash_attention)
    assert _forward_runs(plain, params) == (1, 4, 4)
    block = hybrid_lm.make_block
    monkeypatch.setattr(hybrid_lm, "make_block", lambda *a: jax.checkpoint(block(*a)))
    assert _forward_runs(plain, params) == (2, 8, 8)


ITEMSIZE = {"f32": 4, "bf16": 2, "i32": 4, "u32": 4, "bool": 1}


def _residual_bytes(capsys, model, params) -> int:
    """The bytes ``jax.ad_checkpoint.print_saved_residuals`` lists for the loss,
    the parameters left out (an argument is not held for the backward pass's sake)."""
    import re
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(lambda p: model.loss(p, tokens())[0], params)
    lines = [line for line in capsys.readouterr().out.strip().splitlines()
             if " from the argument " not in line]
    shapes = [re.match(r"(\w+)\[([\d,]*)\] ", line).groups() for line in lines]
    return sum(ITEMSIZE[dtype] * int(np.prod([int(n) for n in dims.split(",") if n]))
               for dtype, dims in shapes)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
def test_kept_bytes_are_the_named_residuals(capsys, dtype):
    """``recompute_plan``'s ``kept_bytes`` in the loss's gradient against jax's own
    list of what the backward pass is handed: with the policy it holds, beside
    everything a ``jax.checkpoint`` with no policy holds (the blocks' inputs) and the
    head's two gradients, the named values and nothing else."""
    model, params = build(tiny_config(), remat=True, attention_fn=ops.flash_attention, dtype=dtype)
    gradient = jax.make_jaxpr(jax.grad(lambda p: model.loss(p, tokens())[0]))(params)
    plan = model.recompute_plan(gradient)
    assert plan["kept"] == list(hybrid_lm.KEPT) and plan["kept_bytes"] > 0
    with_names = _residual_bytes(capsys, model, params)
    bare = dataclasses.replace(model, kept=())      # a family may keep other names
    assert with_names - _residual_bytes(capsys, bare, params) == plan["kept_bytes"]
    assert build(tiny_config())[0].recompute_plan(gradient) is None


def test_the_configuration_is_published_layers_1_to_5():
    model, params = build(tiny_config())
    assert model.layer_types == ("conv", "full_attention", "conv", "conv", "conv")
    assert model.num_dense_layers == 1 and model.sparse_layers == 4
    assert model.held_experts == (0, 4) and model.router_experts == 16
    assert "ff" in params["layer_0"] and "moe" in params["layer_1"]
    with open(CONFIG_FILE) as fh:
        full = hybrid_lm.from_config(json.load(fh), vocab_size=8192, seq_len=8192)
    count = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        full.param_shapes(), is_leaf=lambda x: isinstance(x, tuple)))
    assert count == 469_284_992 + 4 * 64        # ISSUE 26's count, and the four b
    assert full.expert_plan(4 * 8192) == {"held": [0, 8], "row_bound": 131072,
                                          "rows_buffer": 133120, "block": 256,
                                          "rows_moved": "arrived"}


def test_the_cells_attention_is_dispatched_to_the_flash_kernels():
    """``[4, 8192, 32, 64]`` causal: 34 GB of float32 scores, so never ``dense``."""
    plan = ops.dispatch_plan((4, 8192, 32, 64), causal=True)
    assert (plan["impl"], plan["seq_padded"], plan["block"]) == ("flash", 8192, 1024)
    assert plan["score_bytes"] == 4 * 4 * 32 * 8192 * 8192
    one_row = ops.dispatch_plan((1, 8192, 32, 64), causal=True)    # the checked steps
    assert one_row["impl"] == "flash"


# (b) the share ties to the model ---------------------------------------------------------


def _layer_inputs(router=16, d=32, f=24, t=40, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(u=jax.random.normal(ks[0], (t, d)),
                router_kernel=jax.random.normal(ks[1], (d, router)) * 0.4,
                expert_bias_b=jax.random.normal(ks[2], (router,)) * 0.1,
                experts_w1_kernel=jax.random.normal(ks[3], (d, router * f)) * 0.2,
                experts_w3_kernel=jax.random.normal(ks[4], (d, router * f)) * 0.2,
                experts_w2_kernel=jax.random.normal(ks[5], (f, router * d)) * 0.2)


def _program_share(x, first, count, f=24, d=32, k=4):
    """One chip's part: its own ``held`` and its own columns of the expert leaves."""
    w, experts = moe.route(x["u"], x["router_kernel"], x["expert_bias_b"], top_k=k)
    cut = lambda name, width: x[name][:, first * width:(first + count) * width]
    return moe.held_experts_ffn(
        x["u"], w, experts, cut("experts_w1_kernel", f), cut("experts_w3_kernel", f),
        cut("experts_w2_kernel", d), held=(first, count), block=8)


def _reference_layer(x, first, count, f=24, d=32):
    m = {"num_experts": count, "published": {"num_experts": 16},
         "share": {"first_expert": first}, "moe_intermediate_size": f,
         "num_experts_per_tok": 4}
    p = dict(x)
    for name, width in (("experts_w1_kernel", f), ("experts_w3_kernel", f),
                        ("experts_w2_kernel", d)):
        p[name] = x[name][:, first * width:(first + count) * width]
    from reference import precision as prec
    return ref.sparse_ff(p, x["u"], m, prec.matmul("highest"), prec.einsum("highest"))


@pytest.mark.parametrize("shares", [8, 4, 1])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """``shares`` chips hold 16/shares experts each; their results, each from its own
    ``held``, add up to the whole layer as the uncut reference computes it (there is
    no shared expert to count once), and their rows to all k·T assignments."""
    x = _layer_inputs()
    per = 16 // shares
    parts = [_program_share(x, first, per) for first in range(0, 16, per)]
    whole = _reference_layer(x, 0, 16)
    np.testing.assert_allclose(sum(out for out, _ in parts), whole, atol=2e-5)
    assert sum(int(counts.sum()) for _, counts in parts) == 4 * x["u"].shape[0]
    for first, (out, _) in zip(range(0, 16, per), parts):
        np.testing.assert_allclose(out, _reference_layer(x, first, per), atol=2e-5)


# (c) dropless under imbalance ------------------------------------------------------------


@pytest.mark.parametrize("case", ["one held expert gets every token",
                                  "no held expert gets any", "as routed"])
def test_dropless_at_any_imbalance(case):
    x = _layer_inputs()
    bias = np.zeros(16, np.float32)
    if case.startswith("one"):
        bias[5] = 10.0          # expert 5 (held by 4..7) is in every token's top-4
    elif case.startswith("no"):
        bias[4:8] = -10.0
    x["expert_bias_b"] = jnp.asarray(bias)
    out, counts = _program_share(x, 4, 4)
    np.testing.assert_allclose(out, _reference_layer(x, 4, 4), atol=2e-5)
    t = x["u"].shape[0]
    if case.startswith("one"):
        assert int(counts[1]) == t and int(counts.sum()) >= t
    elif case.startswith("no"):
        assert int(counts.sum()) == 0 and float(jnp.abs(out).max()) == 0.0
    else:
        assert 0 < int(counts.sum()) < 4 * t
    # and its gradients: every arrived row's, none beside
    target = jax.random.normal(jax.random.PRNGKey(9), out.shape)
    names = ("u", "router_kernel", "experts_w1_kernel", "experts_w3_kernel",
             "experts_w2_kernel")
    got = jax.grad(lambda *a: jnp.sum(_program_share(dict(x, **dict(zip(names, a))), 4, 4)[0]
                                      * target), argnums=range(5))(*[x[n] for n in names])
    want = jax.grad(lambda *a: jnp.sum(_reference_layer(dict(x, **dict(zip(names, a))), 4, 4)
                                       * target), argnums=range(5))(*[x[n] for n in names])
    for name, g, w in zip(names, got, want):
        if name.startswith("experts_"):     # the share's columns of the full leaf
            width = g.shape[1] // 16
            g, w = (a[:, 4 * width:8 * width] for a in (g, w))
        np.testing.assert_allclose(g, w, atol=3e-5, err_msg=name)


# (c') the crossings between token order and expert order --------------------------------


def _loop_layer(x, weights, experts, w1, w3, w2, held, f=24, d=32):
    """The held experts' part by a loop over experts, float32 throughout."""
    first, count = held
    x = x.astype(jnp.float32)
    out = jnp.zeros_like(x)
    for e in range(count):
        gate, up = x @ w1[:, e * f:(e + 1) * f], x @ w3[:, e * f:(e + 1) * f]
        y = (jax.nn.silu(gate) * up) @ w2[:, e * d:(e + 1) * d]
        share = jnp.sum(jnp.where(experts == first + e, weights, 0.0), axis=1)
        out = out + share[:, None] * y
    return out


def _crossing_case(case, t=40):
    """``(experts [t, 4], held)``: hand-made routing over 16 experts, tiles of 8 rows.
    Every token starts on experts 0..3, none of them held by ``(4, 4)``."""
    experts = np.tile(np.arange(4, dtype=np.int32), (t, 1))
    held = (4, 4)
    if case == "everything arrives":
        experts = (np.arange(t)[:, None] + 4 * np.arange(4)[None]) % 16
        held = (0, 16)
    elif case == "one held expert takes every row":
        experts[:, 0] = 5
    elif case == "counts on a tile edge":
        experts[:16, 0], experts[16:24, 0] = 4, 6          # 16 and 8 rows
    elif case == "counts one past a tile edge":
        experts[:17, 0], experts[17:26, 0] = 4, 6          # 17 and 9 rows
    elif case == "two and three held experts of one token":
        experts[:, 0], experts[:, 1] = 4, 6
        experts[::2, 2] = 7
    else:
        assert case == "nothing arrives"
    return jnp.asarray(experts, jnp.int32), held


CROSSING_CASES = ("nothing arrives", "everything arrives", "one held expert takes every row",
                  "counts on a tile edge", "counts one past a tile edge",
                  "two and three held experts of one token")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("case", CROSSING_CASES)
def test_the_crossings_move_every_arrived_row_and_no_other(case, dtype):
    """Forward and every gradient (x, routing weights, w1, w3, w2) against the loop, at
    the edges of what ``moe_gather`` and ``moe_combine`` walk: the arrived row tiles."""
    experts, held = _crossing_case(case)
    t, f, d, n = experts.shape[0], 24, 32, held[1]
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    x = jax.random.normal(ks[0], (t, d)).astype(dtype)
    weights = jax.nn.softmax(jax.random.normal(ks[1], (t, 4)), axis=-1)
    w1, w3 = (jax.random.normal(ks[i], (d, n * f)) * 0.2 for i in (2, 3))
    w2 = jax.random.normal(ks[4], (f, n * d)) * 0.2
    target = jax.random.normal(ks[5], (t, d))

    def program(x, weights, w1, w3, w2):
        out, counts = moe.held_experts_ffn(x, weights, experts, w1, w3, w2,
                                           held=held, block=8)
        return jnp.sum(out.astype(jnp.float32) * target), (out, counts)

    def loop(x, weights, w1, w3, w2):
        out = _loop_layer(x, weights, experts, w1, w3, w2, held)
        return jnp.sum(out * target), out

    args = (x, weights, w1, w3, w2)
    (_, (out, counts)), got = jax.value_and_grad(program, argnums=range(5), has_aux=True)(*args)
    (_, want_out), want = jax.value_and_grad(loop, argnums=range(5), has_aux=True)(*args)
    in_held = (np.asarray(experts) >= held[0]) & (np.asarray(experts) < held[0] + n)
    assert out.dtype == dtype and int(counts.sum()) == int(in_held.sum())
    np.testing.assert_array_equal(
        counts, [(np.asarray(experts) == held[0] + e).sum() for e in range(n)])
    untouched = ~in_held.any(axis=1)
    assert float(jnp.abs(out[untouched].astype(jnp.float32)).max(initial=0.0)) == 0.0
    assert float(jnp.abs(got[0][untouched].astype(jnp.float32)).max(initial=0.0)) == 0.0
    assert float(jnp.abs(jnp.where(in_held, 0.0, got[1])).max()) == 0.0
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    pairs = [("out", out, want_out)] + list(zip(("x", "weights", "w1", "w3", "w2"), got, want))
    for name, g, w in pairs:
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all(), name
        assert np.abs(g - w).max() <= tol * (np.abs(w).max() + 1e-6), name


def test_the_combine_sums_a_tokens_rows_in_float32_and_rounds_once():
    """Token 3 holds two weighted rows, ``(1 + 2^-8) · 1`` and ``2^-8 · 1``: their
    float32 sum ``1 + 2^-7`` is a bf16 number, while a bf16 running sum would round
    the first to 1 and stay there. Token 5 holds one row; the others none, whatever
    the rows they point at hold."""
    tm, d, tokens, k = 8, 32, 16, 4
    pos = np.zeros((tokens, k), np.int32)
    is_held = np.zeros((tokens, k), bool)
    pos[3, 0], pos[3, 2], pos[5, 1] = 0, 8, 1     # rows 0, 1 of tile 0; row 8 of tile 1
    is_held[3, 0] = is_held[3, 2] = is_held[5, 1] = True
    token_of_row = np.full(3 * tm, -1, np.int32)
    token_of_row[[0, 1, 8]] = 3, 5, 3
    assignment_of_row = np.zeros(3 * tm, np.int32)
    assignment_of_row[[0, 1, 8]] = 3 * k + 0, 5 * k + 1, 3 * k + 2
    sort = {"pos": jnp.asarray(pos), "is_held": jnp.asarray(is_held),
            "token_of_row": jnp.asarray(token_of_row), "num_tiles": jnp.int32(2),
            "assignment_of_row": jnp.asarray(assignment_of_row),
            # two experts, two tiles of 8 tokens: their rows of tokens 0-7, of 8-15
            "rows_of_tokens": jnp.asarray([[0, 2, 2], [8, 9, 9]], jnp.int32)}
    rows = jnp.full((3 * tm, d), jnp.nan, jnp.bfloat16).at[jnp.asarray([0, 1, 8])].set(1.0)
    weights = np.full((tokens, k), 0.25, np.float32)
    weights[3, 0], weights[3, 2], weights[5, 1] = 1 + 2.0 ** -8, 2.0 ** -8, 0.5
    out = moe._from_rows(moe._pack(rows, tm), sort, jnp.asarray(weights), d, jnp.bfloat16, tm)
    want = np.zeros((tokens, d), np.float32)
    want[3], want[5] = 1 + 2.0 ** -7, 0.5
    assert out.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out, np.float32), want)
    # and the way there: the rows of tokens 3, 5 and 3 again, whatever the rest holds
    x = jax.random.normal(jax.random.PRNGKey(0), (tokens, d)).astype(jnp.bfloat16)
    there, = moe._to_rows((x,), sort, tm)
    np.testing.assert_array_equal(np.asarray(there[jnp.asarray([0, 1, 8])], np.float32),
                                  np.asarray(x[jnp.asarray([3, 5, 3])], np.float32))


# (d) b moves the selection and not the weights -------------------------------------------


def test_the_bias_moves_the_selection_and_not_the_weights():
    x = _layer_inputs()
    scores = np.asarray(jax.nn.sigmoid(x["u"] @ x["router_kernel"]))
    bias = np.zeros(16, np.float32)
    bias[11] = 2.0              # larger than any gap between two sigmoids
    w0, e0 = moe.route(x["u"], x["router_kernel"], jnp.zeros(16), top_k=4)
    w1, e1 = moe.route(x["u"], x["router_kernel"], jnp.asarray(bias), top_k=4)
    assert not (np.sort(e0, -1) == np.sort(e1, -1)).all()
    assert (np.asarray(e1) == 11).any(axis=1).all()
    for w, e in ((w0, e0), (w1, e1)):
        picked = np.take_along_axis(scores, np.asarray(e), axis=1)
        np.testing.assert_allclose(w, picked / (picked.sum(1, keepdims=True) + 1e-6),
                                   rtol=1e-5)
    grad = jax.grad(lambda b: jnp.sum(moe.route(x["u"], x["router_kernel"], b,
                                                top_k=4)[0] ** 2))(jnp.asarray(bias))
    assert float(jnp.abs(grad).max()) == 0.0
    # weights.py zeroes leaves named `...bias`; this leaf is not one of them
    made = bench_weights.make(ref.param_shapes(tiny_config()), 7)
    assert float(jnp.abs(made["layer_1"]["moe"]["expert_bias_b"]).max()) > 0.0


def test_freeze_keeps_a_leaf_out_of_adamw_and_its_decay():
    params = {"moe": {"expert_bias_b": jnp.ones(4), "router_kernel": jnp.ones((2, 4))}}
    opt = optim.freeze(optim.adamw(0.1, weight_decay=0.5), hybrid_lm.is_frozen)
    grads = jax.tree_util.tree_map(jnp.zeros_like, params)
    new, _ = opt.update(params, opt.init(params), grads)
    assert (new["moe"]["expert_bias_b"] == 1.0).all()
    assert (new["moe"]["router_kernel"] < 1.0).all()      # decayed


# (e) RoPE and the convolution against hand-written loops ---------------------------------


def test_half_split_rope_at_theta_1e6_against_a_loop():
    s, h, d, theta = 6, 2, 8, 1e6
    x = np.random.default_rng(1).normal(size=(1, s, h, d)).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(s):
        for head in range(h):
            for i in range(d // 2):
                angle = t * theta ** (-2.0 * i / d)
                a, b = x[0, t, head, i], x[0, t, head, i + d // 2]
                want[0, t, head, i] = a * np.cos(angle) - b * np.sin(angle)
                want[0, t, head, i + d // 2] = b * np.cos(angle) + a * np.sin(angle)
    got = apply_rotary(jnp.asarray(x), jnp.arange(s), base=theta)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_causal_depthwise_convolution_against_a_loop():
    b, s, c, taps = 2, 7, 5, 3
    rng = np.random.default_rng(2)
    z = rng.normal(size=(b, s, c)).astype(np.float32)
    kernel = rng.normal(size=(taps, c)).astype(np.float32)
    want = np.zeros_like(z)
    for t in range(s):
        for j in range(taps):
            src = t - (taps - 1) + j
            if src >= 0:                    # zeros before the sequence's start
                want[:, t] += kernel[j] * z[:, src]
    got = hybrid_lm.causal_depthwise_conv(jnp.asarray(z), jnp.asarray(kernel))
    np.testing.assert_allclose(got, want, atol=1e-6)
    # causal: the first t outputs do not see what comes after them
    z2 = z.copy()
    z2[:, 4:] += 1.0
    again = hybrid_lm.causal_depthwise_conv(jnp.asarray(z2), jnp.asarray(kernel))
    np.testing.assert_allclose(again[:, :4], got[:, :4], atol=1e-6)


def test_rms_norm_and_swiglu():
    x = jnp.asarray(np.random.default_rng(3).normal(size=(4, 16)), jnp.float32)
    g = jnp.linspace(0.5, 1.5, 16)
    want = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-5) * g
    np.testing.assert_allclose(ops.rms_norm(x, g, eps=1e-5), want, rtol=1e-5)
    np.testing.assert_allclose(ops.swiglu(x, x + 1), x / (1 + np.exp(-x)) * (x + 1),
                               rtol=1e-5)


# (f) through train.lm.main ---------------------------------------------------------------


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    from csed_514_project_distributed_training_using_pytorch_tpu.train import lm as train_lm
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import LMConfig
    work = tmp_path_factory.mktemp("hybrid_train")
    config_file = str(work / "tiny.json")
    with open(config_file, "w") as fh:
        json.dump(tiny_config(vocab_size=256), fh)
    runs = []
    for name, remat in (("a", False), ("b", True)):
        tele = str(work / f"{name}.jsonl")
        state, history = train_lm.main(LMConfig(
            model_config=config_file, mesh="data=1", remat=remat,
            corpus=os.path.join(REPO, "tests", "fixtures", "corpus_tiny"),
            epochs=2, batch_size=8, eval_batch=19, learning_rate=3e-3, seed=5,
            telemetry=tele, results_dir="", images_dir=str(work / "images"), generate=0))
        with open(tele) as fh:
            runs.append((state, history, [json.loads(line) for line in fh]))
    return runs


def test_main_trains_the_configuration_and_the_loss_falls(trained):
    _, history, events = trained[0]
    epochs = [e for e in events if e["event"] == "epoch"]
    assert len(epochs) == 2 and epochs[1]["train_loss"] < epochs[0]["train_loss"]
    assert epochs[1]["val_loss"] < epochs[0]["val_loss"] < np.log(256) + 0.5


def test_the_events_carry_the_expert_layers_fields(trained):
    _, _, events = trained[0]
    compile_event = [e for e in events if e["event"] == "compile"][0]
    assert compile_event["experts"] == {"held": [0, 4], "row_bound": 8 * 64 * 4,
                                        "rows_buffer": (8 + 4) * 256, "block": 256,
                                        "rows_moved": "arrived"}
    assert compile_event["attention"]["impl"] == "dense"
    assert compile_event["attention"]["rotation"] == "permutation"
    for event in (e for e in events if e["event"] == "epoch"):
        rows = np.asarray(event["expert_rows"])
        assert rows.shape == (event["steps"], 4)          # [steps, sparse layers]
        lo, mean, hi = (np.asarray(event[f"expert_rows_{k}"]) for k in ("min", "mean", "max"))
        assert (lo <= mean).all() and (mean <= hi).all()
        np.testing.assert_allclose(mean * 4, rows)         # 4 experts held
        moved = np.asarray(event["expert_rows_moved"])     # whole tiles, one at least
        assert moved.shape == rows.shape and (moved % 256 == 0).all()
        assert (moved >= np.maximum(rows, 4 * 256)).all() and (moved < rows + 4 * 256).all()
        assert 0 < rows.sum() < 4 * 8 * 64 * rows.size     # under the static bound


def test_the_compile_event_says_what_recomputation_keeps(trained):
    """``recompute``: null without ``--remat``; with it the kept names and the bytes
    a step of 8 x 64 tokens holds under them (float32, dense attention: the flash
    names tag nothing here). ``head_products`` beside it, either way: the epoch
    program multiplies the ``[T, vocab]`` logits three times a step."""
    (_, _, plain), (_, _, remat) = trained
    event = lambda events: [e for e in events if e["event"] == "compile"][0]
    assert event(plain)["recompute"] is None
    assert event(plain)["head_products"] == event(remat)["head_products"] == 3
    t, d, k, held = 8 * 64, 32, 4, 4
    rows = (t * k // 256 + held) * 256
    sort = 2 * rows * 4 + t * k * (4 + 1) + 4 + held * (t // 256 + 1) * 4 + (rows // 256) * 4
    route = t * 16 * 4 + 2 * t * k * 4
    assert event(remat)["recompute"] == {
        "kept": list(hybrid_lm.KEPT),
        "kept_bytes": 5 * t * d * 4 + 2 * t * d * 4 + 4 * 3 * t * d * 4 + t * 48 * 4
        + 4 * (route + sort)}


def test_two_runs_from_one_seed_agree_and_the_bias_stays(trained):
    """The second run recomputes (``remat``): the same floats all the same."""
    (a, _, ea), (b, _, eb) = trained
    for x, y in zip(jax.tree_util.tree_leaves(a.params), jax.tree_util.tree_leaves(b.params)):
        np.testing.assert_array_equal(x, y)
    pick = lambda events: [(e["train_loss"], e["expert_rows"]) for e in events
                           if e["event"] == "epoch"]
    assert pick(ea) == pick(eb)
    assert float(np.abs(a.params["layer_1"]["moe"]["expert_bias_b"]).max()) == 0.0
    assert float(np.abs(a.params["layer_1"]["moe"]["router_kernel"]).max()) > 0.0


def test_model_config_without_a_corpus_is_refused():
    from csed_514_project_distributed_training_using_pytorch_tpu.train import lm as train_lm
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import LMConfig
    with pytest.raises(ValueError, match="--corpus"):
        train_lm.main(LMConfig(model_config=CONFIG_FILE))
