"""``models/hybrid_lm.py`` and ``ops/moe.py`` against the plain reference
(``benchmark/reference/lfm2_moe.py``, which imports nothing of the program) and
against hand-written loops: small sizes, float32, seeded weights."""

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
                                          "rows_buffer": 133120, "block": 256}


def test_the_cells_attention_is_dispatched_to_the_flash_kernels():
    """``[4, 8192, 32, 64]`` causal: 34 GB of float32 scores, so never ``dense``."""
    plan = ops.dispatch_plan((4, 8192, 32, 64), causal=True)
    assert (plan["impl"], plan["seq_padded"], plan["layout"]) == ("flash", 8192, "packed")
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
    for name in ("a", "b"):
        tele = str(work / f"{name}.jsonl")
        state, history = train_lm.main(LMConfig(
            model_config=config_file, mesh="data=1",
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
                                        "rows_buffer": (8 + 4) * 256, "block": 256}
    assert compile_event["attention"]["impl"] == "dense"
    for event in (e for e in events if e["event"] == "epoch"):
        rows = np.asarray(event["expert_rows"])
        assert rows.shape == (event["steps"], 4)          # [steps, sparse layers]
        lo, mean, hi = (np.asarray(event[f"expert_rows_{k}"]) for k in ("min", "mean", "max"))
        assert (lo <= mean).all() and (mean <= hi).all()
        np.testing.assert_allclose(mean * 4, rows)         # 4 experts held
        assert 0 < rows.sum() < 4 * 8 * 64 * rows.size     # under the static bound


def test_two_runs_from_one_seed_agree_and_the_bias_stays(trained):
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
