"""The ``evabyte`` stack of ``models/hybrid_lm.py`` and ``ops/eva.py`` against the plain
reference (``benchmark/reference/evabyte.py``, which imports nothing of the program) and
against ``ops/eva.py``'s own ``jax.numpy`` path: small sizes, float32, seeded weights;
Pallas in interpret mode."""

import dataclasses
import json
import math
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

from reference import evabyte as ref  # noqa: E402
from reference import precision as prec  # noqa: E402
import weights as bench_weights  # noqa: E402

from csed_514_project_distributed_training_using_pytorch_tpu import ops  # noqa: E402
from csed_514_project_distributed_training_using_pytorch_tpu.models import (  # noqa: E402
    hybrid_lm,
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops import eva  # noqa: E402

CONFIG_FILE = os.path.join(BENCH, "configs", "evabyte-6.5b-tp2.json")
SEQ, VOCAB = 128, 40
MM, ES = prec.matmul("highest"), prec.einsum("highest")


def tiny_config(**changes) -> dict:
    """The benchmark's configuration with its widths and its depth cut: hidden 64, 4 heads
    of 16 (all held), a window of 32 in chunks of 4, 3 prediction heads, 2 layers, 96
    feed-forward columns (all held), 40 ids."""
    with open(CONFIG_FILE) as fh:
        config = json.load(fh)
    config.update(hidden_size=64, intermediate_size=96, num_attention_heads=4,
                  num_key_value_heads=4, window_size=32, chunk_size=4, num_pred_heads=3,
                  num_hidden_layers=2, vocab_size=VOCAB)
    config["published"] = dict(num_hidden_layers=2, num_attention_heads=4,
                               num_key_value_heads=4)
    config["share"] = dict(config["share"], heads=4, mlp_columns=96)
    config.update(changes)
    return config


def build(config, seed=20260930, seq_len=SEQ, **kw):
    model = hybrid_lm.from_config(config, vocab_size=config["vocab_size"], seq_len=seq_len,
                                  **kw)
    return model, bench_weights.make(ref.param_shapes(config), seed)


def tokens(batch=2, seed=0, seq_len=SEQ):
    return jnp.asarray(np.random.default_rng(seed).integers(0, VOCAB, (batch, seq_len)),
                       jnp.int32)


def worst(a, b) -> float:
    """The largest leaf-wise gap of two trees, relative to the second's largest entry."""
    gaps = jax.tree_util.tree_map(
        lambda x, y: float(jnp.max(jnp.abs(x - y)) / (jnp.max(jnp.abs(y)) + 1e-12)), a, b)
    return max(jax.tree_util.tree_leaves(gaps))


# (a) the kernels against the module's own jax.numpy path --------------------------------


def operands(n, s, d, chunk, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v, cotangent = (jax.random.normal(key, (n, s, d)) for key in keys[:4])
    phi, mu = (jax.random.normal(key, (n, d)) for key in keys[4:])
    return q, k, v, phi, mu, cotangent


@pytest.mark.parametrize("s, window, chunk, d", [
    (128, 128, 16, 32),     # one window: no summary is seen, the remote kernels never run
    (512, 128, 16, 32),     # four: every block of summaries is cut at a column
    (768, 256, 2, 16),      # three windows of 128 summaries: whole blocks and dead ones
])
def test_kernels_in_interpret_mode_are_the_dense_path_forward_and_backward(s, window, chunk, d):
    q, k, v, phi, mu, cotangent = operands(2, s, d, chunk)

    def value_and_grads(core):
        def loss(q, k, v, phi, mu):
            ks, vs = eva.chunk_summaries(k, v, phi, mu, chunk=chunk)
            return jnp.sum(core(q, k, v, ks, vs, window=window, chunk=chunk) * cotangent)
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, phi, mu)

    want, want_grads = value_and_grads(eva.dense_attention)
    got, got_grads = value_and_grads(eva.kernel_attention)
    assert float(got) == pytest.approx(float(want), rel=1e-5, abs=1e-4)
    for name, a, b in zip("q k v phi mu".split(), got_grads, want_grads):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)
    if s == window:     # nothing reaches the summaries
        assert not np.any(got_grads[3]) and not np.any(got_grads[4])


def test_the_dense_path_is_the_equations_token_by_token():
    """Query t against a hand-written loop: the keys of its window up to t, the summaries
    of the chunks before its window, one softmax."""
    s, window, chunk, d = 24, 8, 2, 4
    q, k, v, phi, mu, _ = operands(1, s, d, chunk, seed=3)
    ks, vs = eva.chunk_summaries(k, v, phi, mu, chunk=chunk)
    for j in range(s // chunk):         # the summaries themselves
        rows = slice(chunk * j, chunk * (j + 1))
        a = jax.nn.softmax(k[0, rows] @ phi[0])
        np.testing.assert_allclose(ks[0, j], a @ k[0, rows] + mu[0], atol=1e-5)
        np.testing.assert_allclose(vs[0, j], a @ v[0, rows], atol=1e-5)
    out = eva.dense_attention(q, k, v, ks, vs, window=window, chunk=chunk)
    for t in range(s):
        w = t // window
        keys = jnp.concatenate([k[0, w * window:t + 1], ks[0, :w * (window // chunk)]])
        values = jnp.concatenate([v[0, w * window:t + 1], vs[0, :w * (window // chunk)]])
        weights = jax.nn.softmax(keys @ q[0, t] * d ** -0.5)
        np.testing.assert_allclose(out[0, t], weights @ values, atol=1e-5, err_msg=str(t))


def test_eva_attention_packs_heads_and_refuses_ragged_sizes():
    b, s, h, d = 2, 64, 3, 8
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    q, k, v = (jax.random.normal(key, (b, s, h, d)) for key in keys[:3])
    phi, mu = (jax.random.normal(key, (h, d)) for key in keys[3:])
    out = eva.eva_attention(q, k, v, phi, mu, window=16, chunk=4)
    for i in range(b):
        for head in range(h):
            one = lambda x: x[i, :, head][None]
            ks, vs = eva.chunk_summaries(one(k), one(v), phi[head][None], mu[head][None],
                                         chunk=4)
            want = eva.dense_attention(one(q), one(k), one(v), ks, vs, window=16, chunk=4)
            np.testing.assert_allclose(out[i, :, head], want[0], atol=1e-5)
    with pytest.raises(ValueError, match="whole windows"):
        eva.eva_attention(q, k, v, phi, mu, window=48, chunk=4)
    with pytest.raises(ValueError, match="whole chunks"):
        eva.eva_attention(q, k, v, phi, mu, window=16, chunk=3)


def test_the_plan_says_which_path_and_which_blocks():
    plan = eva.attention_plan(heads=16, head_dim=128, seq_len=32768, window=2048, chunk=16,
                              kept=("eva_out", "eva_lse", "ff_gate"))
    assert plan == {"impl": "kernels", "heads": 16, "head_dim": 128, "window": 2048,
                    "chunk": 16, "windows_per_sequence": 16, "summaries_per_window": 128,
                    "summaries_per_sequence": 2048, "query_block": 1024,
                    "summary_block": 512, "kept": ["eva_out", "eva_lse"]}
    small = eva.attention_plan(heads=4, head_dim=16, seq_len=128, window=32, chunk=4)
    assert (small["impl"], small["query_block"], small["kept"]) == ("dense", None, [])


# (b) the model against the plain reference -----------------------------------------------


@pytest.mark.parametrize("remat", [False, True])
def test_logits_loss_and_every_gradient_are_the_references(remat):
    config = tiny_config()
    model, params = build(config, remat=remat)
    assert jax.tree_util.tree_map(lambda x: tuple(x.shape), params) == model.param_shapes()
    ids = tokens()
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, ids)
        want = jax.vmap(lambda row: jax.nn.log_softmax(ref.logits(params, row, config)))(ids)
        (loss, counts), grads = jax.value_and_grad(model.loss, has_aux=True)(params, ids)
        want_loss, want_grads = jax.value_and_grad(
            lambda p: ref.loss(p, ids, config))(params)
    assert got.shape == (2, SEQ, 3, VOCAB) and counts is None
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert worst(grads, want_grads) < 1e-5
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree_util.tree_leaves(grads))


def test_the_loss_leaves_out_the_places_past_the_end():
    """Head i of place t predicts token t + 1 + i: 127 + 126 + 125 targets a sequence, and
    the tokens rolled in from the sequence's start count nowhere."""
    config = tiny_config(num_hidden_layers=1)
    model, params = build(config)
    ids = tokens(batch=1)
    assert model.targets_per_seq() == 127 + 126 + 125 == ref.targets_per_sequence(config, SEQ)
    log_probs = model.apply({"params": params}, ids)[0]
    picked = [log_probs[t, i, ids[0, t + 1 + i]]
              for i in range(3) for t in range(SEQ - 1 - i)]
    total, _ = model.nll(params, ids)
    assert float(total) == pytest.approx(-float(sum(picked)), rel=1e-5)
    assert float(model.loss(params, ids)[0]) == pytest.approx(float(total) / 378, rel=1e-6)
    moved = ids.at[0, :3].set((ids[0, :3] + 1) % VOCAB)      # other first tokens as targets
    shifted = model.apply({"params": params}, moved)[0]
    assert not np.allclose(shifted, log_probs)      # the inputs changed, and so the logits


def test_one_head_is_the_plain_next_token_loss():
    config = tiny_config(num_pred_heads=1)
    model, params = build(config)
    ids = tokens()
    with jax.default_matmul_precision("highest"):
        got, want = model.loss(params, ids)[0], ref.loss(params, ids, config)
    assert model.targets_per_seq() == SEQ - 1
    assert float(got) == pytest.approx(float(want), rel=1e-6)


def test_the_residual_stream_is_float32_under_bfloat16_matmuls():
    config = tiny_config()
    model, params = build(config, dtype=jnp.bfloat16)
    ids = tokens()
    x, _, counts = model._blocks(params, ids)
    assert x.dtype == jnp.float32 and counts == []
    hidden, _ = model.hidden_states(params, ids)
    assert hidden.dtype == jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        want = ref.loss(params, ids, config)
    assert float(model.loss(params, ids)[0]) == pytest.approx(float(want), rel=2e-2)
    plain = dataclasses.replace(model, fp32_residual=False)
    assert plain._blocks(params, ids)[0].dtype == jnp.bfloat16


def test_a_float32_stream_under_bfloat16_holds_every_norms_output(monkeypatch):
    """Two layers, bfloat16 under the float32 stream: five norms a forward pass, each
    output behind an optimization barrier (and, in the gradient, each cotangent), as is
    each bfloat16 q and k on its way into the rotation; the head's own barriers aside, a
    float32 model has none. Loss and every gradient are those
    of the same model with the barrier taken out: it moves when a value is written, not
    what is written."""
    config = tiny_config()
    model, params = build(config, dtype=jnp.bfloat16, remat=True)
    ids = tokens()
    count = lambda fn, *a: str(jax.make_jaxpr(fn)(*a)).count("optimization_barrier")
    blocks = lambda m: lambda p: jnp.sum(m._blocks(p, ids)[0])
    assert model.norm_plan() == {"impl": "barrier", "calls": 5}
    # two norms a layer, and the two operands of its rotation (``ops/rotary.py``)
    assert count(blocks(model), params) == 4 + 4
    assert count(blocks(build(config)[0]), params) == 0         # float32 matmuls: none
    (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(params, ids)
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    assert count(blocks(model), params) == 0
    (plain, _), plain_grads = jax.value_and_grad(model.loss, has_aux=True)(params, ids)
    assert float(loss) == pytest.approx(float(plain), rel=1e-6)
    assert worst(grads, plain_grads) < 1e-5
    assert all(g.dtype == jnp.float32 for g in jax.tree_util.tree_leaves(grads))


def test_a_norm_leaf_is_what_is_added_to_one():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 8))
    g = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (8,))
    np.testing.assert_allclose(ops.rms_norm(x, g, offset=1.0), ops.rms_norm(x, 1.0 + g),
                               rtol=1e-6)
    np.testing.assert_array_equal(ops.rms_norm(x, g, offset=0.0), ops.rms_norm(x, g))
    model, _ = build(tiny_config())
    leaves = model.init(jax.random.PRNGKey(0))["params"]
    assert set(leaves["layer_0"]) == {"mixer_norm_offset", "ff_norm_offset", "eva", "ff"}
    assert not np.any(leaves["final_norm_offset"]) and not np.any(
        leaves["layer_1"]["ff_norm_offset"])
    assert np.any(leaves["layer_0"]["eva"]["adaptive_phi"])


def test_a_stack_with_no_expert_layer_has_no_plan_no_counts_and_no_choices():
    config = tiny_config()
    model, params = build(config)
    assert model.sparse_layers == 0 and not any(model.is_sparse(i) for i in range(2))
    assert model.expert_plan(256) is None and model.ssm_plan() is None
    assert model.kda_plan() is None and model.eva_plan()["impl"] == "dense"
    assert model.hidden_states(params, tokens())[1] is None
    with pytest.raises(ValueError, match="holds no expert layer"):
        model.router_choices(params, tokens(), 0)
    assert model.kept == hybrid_lm.EVA_KEPT and model.layer_types == ("eva", "eva")
    assert hybrid_lm.MIXER_SCOPES["eva"] == "eva_mixer"


# (c) the share -----------------------------------------------------------------------------


def test_two_shares_out_projections_and_down_projections_add_up_to_the_layers():
    """The guide's section 4: heads 0-1 and 2-3, columns 0-47 and 48-95, each share's
    partial result of the same input; their sum is the uncut layer's."""
    whole = tiny_config(num_hidden_layers=1)
    half = tiny_config(num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2)
    half["share"] = dict(half["share"], heads=2, mlp_columns=48)
    model, params = build(whole)
    part, _ = build(half)
    assert part.head_dim == model.head_dim == 16 and part.intermediate_size == 48
    layer = params["layer_0"]
    u = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64))
    positions = jnp.arange(SEQ)

    def share(chip):
        heads, cols = slice(32 * chip, 32 * (chip + 1)), slice(48 * chip, 48 * (chip + 1))
        mixer = {**{f"{n}_kernel": layer["eva"][f"{n}_kernel"][:, heads] for n in "qkv"},
                 "out_kernel": layer["eva"]["out_kernel"][heads],
                 "adaptive_phi": layer["eva"]["adaptive_phi"][2 * chip:2 * chip + 2],
                 "adaptive_mu_k": layer["eva"]["adaptive_mu_k"][2 * chip:2 * chip + 2]}
        ff = {"w1_kernel": layer["ff"]["w1_kernel"][:, cols],
              "w3_kernel": layer["ff"]["w3_kernel"][:, cols],
              "w2_kernel": layer["ff"]["w2_kernel"][cols]}
        return (hybrid_lm.eva_mixer(mixer, u, positions, part), hybrid_lm.dense_ff(ff, u))

    with jax.default_matmul_precision("highest"):
        (mixed_0, ff_0), (mixed_1, ff_1) = share(0), share(1)
        mixed = hybrid_lm.eva_mixer(layer["eva"], u, positions, model)
        ff = hybrid_lm.dense_ff(layer["ff"], u)
        reference = ref.eva_mixer(layer["eva"], u[0], whole, MM, ES)
    np.testing.assert_allclose(mixed_0 + mixed_1, mixed, atol=1e-5)
    np.testing.assert_allclose(ff_0 + ff_1, ff, atol=1e-5)
    np.testing.assert_allclose(mixed[0], reference, atol=1e-5)
    assert float(jnp.max(jnp.abs(mixed_1))) > 1e-3 and float(jnp.max(jnp.abs(ff_1))) > 1e-3


def test_the_benchmarks_file_builds_its_share_and_counts_its_parameters():
    with open(CONFIG_FILE) as fh:
        config = json.load(fh)
    model = hybrid_lm.from_config(config, vocab_size=320, seq_len=32768)
    count = sum(math.prod(shape) for shape in jax.tree_util.tree_leaves(
        model.param_shapes(), is_leaf=lambda x: isinstance(x, tuple)))
    assert count == config["parameters"]
    assert count == config["num_hidden_layers"] * 101_199_872 + 320 * 4096 + 4096 * 2560 + 4096
    assert (model.num_attention_heads, model.head_dim, model.intermediate_size) == \
        (16, 128, 5504)
    assert (model.eva_window, model.eva_chunk, model.num_pred_heads, model.rope_theta) == \
        (2048, 16, 8, 1e5)
    assert model.norm_unit_offset and model.fp32_residual and not model.tied_head
    assert model.layer_types == ("eva",) * config["num_hidden_layers"]
    assert model.eva_plan()["impl"] == "kernels"


# (d) what is stated and not computed is refused by name -----------------------------------


@pytest.mark.parametrize("changes, message", [
    (dict(num_chunks=8), "num_chunks not null"),
    (dict(rope_scaling={"type": "linear", "factor": 2.0}), "rope_scaling not null"),
    (dict(window_size=30), "window_size that is not a multiple of chunk_size"),
    (dict(hidden_act="gelu"), "hidden_act other than silu"),
    (dict(attention_bias=True), "attention_bias true"),
    (dict(attention_class="mha"), "attention_class other than eva"),
    (dict(num_key_value_heads=2), "num_key_value_heads other than num_attention_heads"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings true"),
])
def test_what_the_file_states_and_the_module_does_not_compute_is_refused(changes, message):
    with pytest.raises(ValueError, match=message):
        build(tiny_config(**changes))


def test_a_sequence_that_is_not_whole_windows_and_a_share_of_other_heads_are_refused():
    with pytest.raises(ValueError, match="whole windows"):
        build(tiny_config(), seq_len=SEQ + 16)
    config = tiny_config()
    config["share"] = dict(config["share"], heads=2)
    with pytest.raises(ValueError, match="share.heads other than num_attention_heads"):
        build(config)
    with pytest.raises(ValueError, match="model_type 'evabite'"):
        build(tiny_config(model_type="evabite"))


# (e) through train.lm ----------------------------------------------------------------------


@pytest.mark.parametrize("bf16, norm", [(False, "xla"), (True, "barrier")],
                         ids=["float32", "bfloat16-under-float32"])
def test_train_lm_trains_the_file_and_its_compile_event_carries_the_eva_plan(tmp_path, bf16,
                                                                             norm):
    from csed_514_project_distributed_training_using_pytorch_tpu.train import lm
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import LMConfig
    config = tiny_config(vocab_size=256)        # the fixture corpus's ids
    config["window_size"], config["chunk_size"] = 16, 4     # its sequences are 32 long
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps({k: v for k, v in config.items() if k != "train"}))
    telemetry = tmp_path / "t.jsonl"
    _, history = lm.main(LMConfig(
        model_config=str(path), corpus=os.path.join(REPO, "tests", "fixtures", "corpus_tiny"),
        mesh="data=1", epochs=2, batch_size=8, eval_batch=19, telemetry=str(telemetry),
        results_dir="", images_dir=str(tmp_path / "images"), generate=0, remat=True,
        optimizer="adamw", learning_rate=3e-3,
        clip_grad_norm=1.0, bf16=bf16))
    events = [json.loads(line) for line in telemetry.read_text().splitlines()]
    compiled = [e for e in events if e["event"] == "compile"][0]
    # five norms a forward pass; held behind barriers where the float32 stream is the wider
    assert compiled["norm"] == {"impl": norm, "calls": 5}
    assert compiled["eva"]["window"] == 16 and compiled["eva"]["kept"] == ["eva_out", "eva_lse"]
    assert compiled["experts"] is None and compiled["ssm"] is None and compiled["kda"] is None
    # no mixer goes through the dispatcher: no ``impl``, the mixers' rotation alone
    assert compiled["attention"] == {"rope_dim": 16, "rope_pairing": "half_split",
                                     "rope_theta": config["rope_theta"],
                                     "rotation": "permutation"}
    assert compiled["head_products"] == 3
    assert compiled["recompute"]["kept"] == ["eva_out", "eva_lse"]
    epochs = [e for e in events if e["event"] == "epoch"]
    assert len(epochs) == 2 and all(e["expert_rows"] is None for e in epochs)
    assert epochs[1]["train_loss"] < epochs[0]["train_loss"]
    scopes = json.loads((tmp_path / "t.jsonl.scopes.json").read_text())
    named = {scope for scope, _ in scopes["ops"].values() if scope}
    for scope in ("eva_mixer", "eva_mixer/eva/summaries", "eva_mixer/eva/attention",
                  "dense_ff", "head_loss"):
        assert any(name == scope or name.startswith(scope + "/") for name in named), scope
