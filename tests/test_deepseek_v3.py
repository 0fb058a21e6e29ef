"""The ``deepseek_v3`` stack of ``models/hybrid_lm.py`` (latent attention with a rotated
shared key in every layer, a leading dense layer, fine-grained experts beside shared
ones) against the plain reference (``benchmark/reference/deepseek_v3.py``, which imports
nothing of the program and rotates the published way: pairs moved apart, then
half-split): small sizes, float32, seeded weights; Pallas in interpret mode."""

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

from reference import deepseek_v3 as ref  # noqa: E402
from reference import precision as prec  # noqa: E402
import weights as bench_weights  # noqa: E402

from csed_514_project_distributed_training_using_pytorch_tpu import ops  # noqa: E402
from csed_514_project_distributed_training_using_pytorch_tpu.models import (  # noqa: E402
    hybrid_lm,
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops import (  # noqa: E402
    pallas_attention as pa,
)

CONFIG_FILE = os.path.join(BENCH, "configs", "kanana-2-30b-a3b-ep8.json")
KIMI_FILE = os.path.join(BENCH, "configs", "kimi-linear-48b-a3b-ep32.json")
SEQ, VOCAB = 64, 64
MM, ES = prec.matmul("highest"), prec.einsum("highest")


def tiny_config(**changes) -> dict:
    """The benchmark's configuration with its widths and its depth cut: hidden 64, 4 heads
    of 16 + 8 key and 16 value channels over a latent of 32, 4 of 16 experts of width 32
    held, 3 of them a token beside 2 shared; one dense layer and two expert layers."""
    with open(CONFIG_FILE) as fh:
        config = json.load(fh)
    config.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
                  num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24, head_dim=8,
                  v_head_dim=16, n_routed_experts=4, num_experts_per_tok=3,
                  vocab_size=VOCAB, num_hidden_layers=3)
    config["published"] = dict(config["published"], n_routed_experts=16, num_hidden_layers=3)
    config.update(changes)
    return config


def build(config, seed=20261001, **kw):
    model = hybrid_lm.from_config(config, vocab_size=config["vocab_size"], seq_len=SEQ,
                                  expert_block=8, **kw)
    return model, bench_weights.make(ref.param_shapes(config), seed)


def tokens(batch=2, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, VOCAB, (batch, SEQ)),
                       jnp.int32)


def program_loss(model, params, ids):
    return model.loss(params, ids)[0]


def flat(tree) -> dict:
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


# (a) the mixer ---------------------------------------------------------------------------


@pytest.mark.parametrize("interleave", [True, False], ids=["interleaved", "half-split"])
def test_the_mixer_rotates_the_shared_channels_as_the_reference_does(interleave):
    """Either pairing of the file: the program turns the pairs where they lie, the reference
    moves them apart first; what the mixer hands on is the same."""
    config = tiny_config(rope_interleave=interleave)
    model, params = build(config)
    assert model.rope_interleave is interleave and model.rope_theta == 1e6
    u = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 64))
    p = params["layer_1"]["mla"]
    with jax.default_matmul_precision("highest"):
        got = hybrid_lm.mla_mixer(p, u, jnp.arange(SEQ), model)
        want = jax.vmap(lambda row: ref.mla_mixer(p, row, config, MM, ES))(u)
    np.testing.assert_allclose(got, want, atol=3e-5 * float(jnp.abs(want).max()))


def test_the_mixer_runs_through_the_flash_kernels(monkeypatch):
    """With the thresholds out of the way the rotated queries and keys (24 channels,
    values of 16) go through the dispatcher's flash kernels and agree with the reference."""
    monkeypatch.setattr(pa, "FLASH_MIN_SCORE_BYTES", 1)
    monkeypatch.setattr(pa, "FLASH_MIN_HEAD_SCORE_BYTES", 1)
    config = tiny_config()
    model, params = build(config, attention_fn=ops.dispatch_attention)
    assert ops.dispatch_plan((2, SEQ, 4, model.head_dim), causal=True,
                             value_dim=model.value_head_dim)["impl"] == "flash"
    u = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 64))
    p = params["layer_0"]["mla"]
    with jax.default_matmul_precision("highest"):
        got = hybrid_lm.mla_mixer(p, u, jnp.arange(SEQ), model)
        want = jax.vmap(lambda row: ref.mla_mixer(p, row, config, MM, ES))(u)
    np.testing.assert_allclose(got, want, atol=3e-5 * float(jnp.abs(want).max()))


def test_a_kimi_linear_file_still_builds_a_model_with_no_rotation():
    """One ``mla_mixer`` for both families: without ``rope_theta`` it reads no positions."""
    with open(KIMI_FILE) as fh:
        config = json.load(fh)
    model = hybrid_lm.from_config(config, vocab_size=config["vocab_size"], seq_len=8192)
    assert model.rope_theta is None and "mla" in model.layer_types
    assert model.kept == hybrid_lm.KEPT
    assert model.rotary_plan() == {"rope_dim": None, "rope_pairing": None,
                                   "rope_theta": None, "rotation": None}
    rotating, params = build(tiny_config())
    small, p = dataclasses.replace(rotating, rope_theta=None), params["layer_0"]["mla"]
    u = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 64))
    text = str(jax.make_jaxpr(lambda u: hybrid_lm.mla_mixer(p, u, jnp.arange(SEQ), small))(u))
    assert "cos" not in text and "sin" not in text
    text = str(jax.make_jaxpr(lambda u: hybrid_lm.mla_mixer(p, u, jnp.arange(SEQ), rotating))(u))
    assert "cos" in text and "sin" in text


# (b) the model against the reference -------------------------------------------------------


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
    assert model.kept == hybrid_lm.MLA_KEPT
    ids = tokens()
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(lambda p: program_loss(model, p, ids))(params)
        want, wants = jax.value_and_grad(lambda p: ref.loss(p, ids, config))(params)
    assert abs(float(got) - float(want)) < 1e-5
    got, want = flat(grads), flat(wants)
    assert got.keys() == want.keys()
    for name in want:
        scale = max(float(jnp.abs(want[name]).max()), 1e-3)
        np.testing.assert_allclose(got[name], want[name], atol=2e-4 * scale, err_msg=name)
    bias = [g for name, g in got.items() if "expert_bias_b" in name]
    assert len(bias) == 2 and all(float(jnp.abs(g).max()) == 0.0 for g in bias)


def test_the_bias_moves_after_a_step_as_the_references_does():
    """``rebalance`` on the load the program's loss hands out against the reference's
    ``rebalanced`` on its own: every expert layer's bias a whole rate up or down."""
    config = tiny_config()
    model, params = build(config)
    ids = tokens()
    with jax.default_matmul_precision("highest"):
        _, arrived = model.loss(params, ids)
        got, counts = model.rebalance(params, arrived)
        _, load = ref.loss(params, ids, config, with_load=True)
        want = ref.rebalanced(params, load, config)
    assert counts.shape == (2, 4) and np.asarray(arrived[1]).tolist() == np.asarray(load).tolist()
    for i in (1, 2):
        old = params[f"layer_{i}"]["moe"]["expert_bias_b"]
        new = got[f"layer_{i}"]["moe"]["expert_bias_b"]
        np.testing.assert_array_equal(new, want[f"layer_{i}"]["moe"]["expert_bias_b"])
        steps = np.asarray((new - old) / config["moe_router_bias_update_rate"])
        assert np.abs(steps).max() == pytest.approx(1.0, abs=1e-3) and steps.shape == (16,)
    same = {k: v for k, v in flat(got).items() if "expert_bias_b" not in k}
    assert all(np.array_equal(v, flat(params)[k]) for k, v in same.items())


def test_router_choices_are_the_references():
    config = tiny_config()
    model, params = build(config)
    ids = tokens()
    with jax.default_matmul_precision("highest"):
        got = np.sort(np.asarray(model.router_choices(params, ids, 1)), axis=-1)
        want = np.sort(np.asarray(jax.vmap(
            lambda row: ref.router_choice(params, row, config, 1))(ids)), axis=-1)
    assert got.shape == (2, SEQ, 3) and (got == want).all()


FAULTS = ["rotation left out", "half-split pairing on an interleaved file",
          "the key rotated and the query not", "rotation over the whole head",
          "shared experts dropped", "one shared expert of two", "2 of a token's 3 experts",
          "scale by the nope width alone"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_comparison(fault):
    """Each fault moves the loss by far more than the 1e-5 the sound program is held
    to above."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops.rotary import (
        apply_rotary,
    )
    config = tiny_config()
    model, params = build(config)
    if fault == "rotation left out":
        model = dataclasses.replace(model, rope_theta=None)
    elif fault == "half-split pairing on an interleaved file":
        model = dataclasses.replace(model, rope_interleave=False)
    elif fault == "the key rotated and the query not":
        back = lambda x: jnp.concatenate([x[..., :16], apply_rotary(
            x[..., 16:], -jnp.arange(x.shape[1]), base=1e6, interleaved=True)], axis=-1)
        model = dataclasses.replace(
            model, attention_fn=lambda q, k, v, *, causal: ops.full_attention(
                back(q), k, v, causal=causal))
    elif fault == "rotation over the whole head":
        turn = lambda x: apply_rotary(x, jnp.arange(x.shape[1]), base=1e6, interleaved=True)
        model = dataclasses.replace(
            model, rope_theta=None,
            attention_fn=lambda q, k, v, *, causal: ops.full_attention(
                turn(q), turn(k), v, causal=causal))
    elif fault == "shared experts dropped":
        model = dataclasses.replace(model, shared_expert_size=0)
    elif fault == "one shared expert of two":
        model = dataclasses.replace(model, shared_expert_size=32)
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: x[:, :32] if path[-1].key in ("shared_w1_kernel", "shared_w3_kernel")
            else x[:32] if path[-1].key == "shared_w2_kernel" else x, params)
    elif fault == "2 of a token's 3 experts":
        model = dataclasses.replace(model, num_experts_per_tok=2)
    else:
        model = dataclasses.replace(
            model, attention_fn=lambda q, k, v, *, causal: ops.full_attention(
                q * (24 / 16) ** 0.5, k, v, causal=causal))
    ids = tokens()
    full = build(config)[1]
    with jax.default_matmul_precision("highest"):
        got = float(program_loss(model, params, ids))
        want = float(ref.loss(full, ids, config))
    assert abs(got - want) > 1e-3, (got, want)


# (c) the share tied to the model -------------------------------------------------------------

CHIPS = 4       # expert-parallel 4: four experts of 16 a chip


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips divide a layer's 16 experts; each computes the mixer, the router and
    the shared experts whole and its own experts' part. What the four add to the
    residual, with what every chip computes alike (a share whose experts' second
    matrices are zero) counted once, is what the uncut reference's layer adds."""
    config = tiny_config(n_routed_experts=16, num_hidden_layers=1)
    config["share"] = dict(config["share"], first_layer=1)
    params = bench_weights.make(ref.param_shapes(config), 7)["layer_0"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, 64))
    take = lambda w, chip: jax.lax.slice_in_dim(
        w, chip * w.shape[1] // CHIPS, (chip + 1) * w.shape[1] // CHIPS, axis=1)
    with jax.default_matmul_precision("highest"):
        whole = jax.vmap(lambda row: ref._layer(params, row, config, True, MM, ES)[0])(x) - x
        parts, rows = [], 0
        for chip in range(CHIPS):
            m = dict(config, n_routed_experts=16 // CHIPS)
            m["share"] = dict(config["share"], first_expert=chip * 4)
            model = hybrid_lm.from_config(m, vocab_size=VOCAB, seq_len=SEQ, expert_block=8)
            assert model.layer_types == ("mla",) and model.held_experts == (chip * 4, 4)
            assert model.is_sparse(0) and model.shared_expert_size == 2 * 32
            leaves = dict(params, moe={name: take(w, chip) if name.startswith("experts_")
                                       else w for name, w in params["moe"].items()})
            block = hybrid_lm.make_block(model, "mla", True)
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


# (d) the configuration file ------------------------------------------------------------------


def test_the_configuration_is_one_chips_share_of_the_first_stage():
    with open(CONFIG_FILE) as fh:
        config = json.load(fh)
    depth = config["num_hidden_layers"]
    model = hybrid_lm.from_config(config, vocab_size=16032, seq_len=8192)
    assert model.layer_types == ("mla",) * depth
    assert ref.sparse(config) == [model.is_sparse(i) for i in range(depth)] == \
        [False] + [True] * (depth - 1)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))["params"]
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    mixer = 2048 * 6144 + 2048 * 576 + 512 + 512 * 8192 + 4096 * 2048
    dense, head = 3 * 2048 * 6144, 2 * 16032 * 2048 + 2048
    sparse = 2048 * 128 + 128 + 3 * 2048 * 1536 + 16 * 3 * 2048 * 768
    assert (mixer, dense, sparse, head) == (26_345_984, 37_748_736, 85_196_928, 65_669_120)
    by_hand = head + depth * (mixer + 2 * 2048) + dense + (depth - 1) * sparse
    assert by_hand == {6: 687_502_976, 5: 575_955_968}[depth]
    assert count(shapes) == count(ref.param_shapes(config)) == config["parameters"] == by_hand
    assert count(shapes["layer_0"]["mla"]) == mixer
    assert count(shapes["layer_0"]["ff"]) == dense
    assert count(shapes["layer_1"]["moe"]) == sparse
    assert jax.tree.map(lambda x: x.shape, shapes) == \
        jax.tree.map(lambda x: x.shape, ref.param_shapes(config))
    assert (model.router_experts, model.held_experts, model.num_experts_per_tok) == \
        (128, (0, 16), 6)
    plan = model.expert_plan(2 * 8192)
    assert plan["row_bound"] == 6 * 2 * 8192 and plan["held"] == [0, 16]
    assert plan["bias_update_rate"] == config["moe_router_bias_update_rate"]
    assert (model.rope_theta, model.rope_interleave, model.qk_norm, model.tied_head,
            model.head_dim, model.value_head_dim, model.routed_scaling_factor,
            model.norm_eps, model.shared_expert_size, model.gated_shared_expert) == \
        (1e6, True, False, False, 192, 128, 2.448, 1e-6, 1536, True)
    assert model.rotary_plan() == {"rope_dim": 64, "rope_pairing": "interleaved",
                                   "rope_theta": 1e6, "rotation": "permutation"}
    assert sorted(config["reduced"]) == sorted(config["published"]) == \
        ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    for key, value in config["published"].items():
        assert config[key] != value
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["vocab_size"] % 128           # an eighth of the vocabulary, not a tile's multiple


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
    ("q_lora_rank", 1536, "q_lora_rank"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("n_group", 8, "grouped expert selection"),
    ("topk_group", 4, "grouped expert selection"),
    ("scoring_func", "softmax", "scoring_func"),
    ("topk_method", "greedy", "topk_method"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("moe_layer_freq", 2, "moe_layer_freq"),
    ("hidden_act", "gelu", "hidden_act"),
    ("attention_bias", True, "attention_bias"),
    ("num_key_value_heads", 2, "num_key_value_heads"),
    ("num_nextn_predict_layers", 1, "multi-token prediction")])
def test_what_the_file_states_and_the_module_does_not_compute_is_refused(key, value, what):
    with pytest.raises(ValueError, match=f"{what}.* is not written here"):
        hybrid_lm.from_config(tiny_config(**{key: value}), vocab_size=VOCAB, seq_len=SEQ)


def test_a_later_stage_holds_no_dense_layer():
    config = tiny_config()
    config["share"] = dict(config["share"], first_layer=1)
    config["published"] = dict(config["published"], num_hidden_layers=4)
    model = hybrid_lm.from_config(config, vocab_size=VOCAB, seq_len=SEQ)
    assert model.num_dense_layers == 0 and model.sparse_layers == 3
    assert ref.sparse(config) == [True, True, True]


# (e) through train.lm -------------------------------------------------------------------------


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    from csed_514_project_distributed_training_using_pytorch_tpu.train import lm as train_lm
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import LMConfig
    work = tmp_path_factory.mktemp("deepseek_v3_train")
    config_file = str(work / "tiny.json")
    with open(config_file, "w") as fh:
        json.dump(tiny_config(vocab_size=256), fh)
    tele = str(work / "t.jsonl")
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
        assert 0 < rows.sum() <= 3 * 8 * 64 * rows.size     # under k·T


def test_the_compile_event_says_what_the_new_layers_ask(trained):
    state, events = trained
    event = [e for e in events if e["event"] == "compile"][0]
    attention = event["attention"]
    assert (attention["key_dim"], attention["value_dim"]) == (24, 16)
    assert (attention["rope_dim"], attention["rope_pairing"], attention["rope_theta"],
            attention["rotation"]) == (8, "interleaved", 1e6, "permutation")
    assert event["ssm"] is None and event["kda"] is None and event["eva"] is None
    assert event["experts"]["row_bound"] == 3 * 8 * 64 and event["experts"]["held"] == [0, 4]
    assert event["recompute"]["kept"] == list(hybrid_lm.MLA_KEPT)
    assert event["recompute"]["kept_bytes"] > 0
    assert event["head_products"] == 3      # the [T, vocab] logits: once a pass
    assert "mla_attention" in event["scopes"]["top_scopes"]
    with open(event["scopes"]["path"]) as fh:
        scopes = {scope for scope, _ in json.load(fh)["ops"].values() if scope}
    assert "mla_attention/rotary" in scopes
    rate = event["experts"]["bias_update_rate"]
    steps = sum(e["steps"] for e in events if e["event"] == "epoch")
    moved = [np.asarray(state.params[f"layer_{i}"]["moe"]["expert_bias_b"]) for i in (1, 2)]
    seeded = hybrid_lm.from_config(tiny_config(vocab_size=256), vocab_size=256, seq_len=64)
    for bias in moved:      # zero at the start: whole multiples of the rate, at most one a step
        assert np.abs(bias).max() <= steps * rate * (1 + 1e-3) and np.abs(bias).max() > 0
        np.testing.assert_allclose(bias / rate, np.round(bias / rate), atol=1e-2)
    assert seeded.router_bias_update_rate == rate
