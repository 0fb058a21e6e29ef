"""The ``falcon_h1`` stack of ``models/hybrid_lm.py`` (every block a Mamba-2 mixer and a
rotated GQA attention that read one normed input side by side, a dense feed-forward, and the
family's forward multipliers on the activations: fourteen numbers under eleven keys) against
the plain reference (``benchmark/reference/falcon_h1.py``, which imports nothing of the
program and walks the recurrence token by token): small sizes, float32, seeded weights;
Pallas in interpret mode."""

import dataclasses
import functools
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

from reference import falcon_h1 as ref  # noqa: E402
from reference import precision as prec  # noqa: E402
import weights as bench_weights  # noqa: E402

from csed_514_project_distributed_training_using_pytorch_tpu import ops  # noqa: E402
from csed_514_project_distributed_training_using_pytorch_tpu.models import (  # noqa: E402
    hybrid_lm,
)

CONFIG_FILE = os.path.join(BENCH, "configs", "falcon-h1-34b-tp4.json")
NEMOTRON_FILE = os.path.join(BENCH, "configs", "nemotron3-super-120b-tp8-ep64.json")
SEQ, VOCAB = 40, 64         # 40 tokens: two chunks of 16 and a padded tail
MM, ES = prec.matmul("highest"), prec.einsum("highest")
# the uncut tiny layer, and what one of ``chips`` tensor-parallel chips holds of it
WHOLE = dict(num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=4, mamba_n_groups=2,
             mamba_d_ssm=32, columns=96)


def tiny_config(**changes) -> dict:
    """The benchmark's configuration with its widths cut: hidden 64, 4 query heads on 2
    key/value heads of 16, 4 Mamba-2 heads of 8 in 2 groups with a state of 16 and chunks of
    16, 96 feed-forward columns, 2 layers; every multiplier as published but
    ``attention_in_multiplier``, whose published 1 would make its case empty."""
    with open(CONFIG_FILE) as fh:
        config = json.load(fh)
    config.update(hidden_size=64, intermediate_size=96, head_dim=16, mamba_d_head=8,
                  mamba_d_state=16, mamba_chunk_size=16, vocab_size=VOCAB,
                  num_hidden_layers=2, attention_in_multiplier=0.7,
                  **{k: v for k, v in WHOLE.items() if k != "columns"})
    config["published"] = dict(config["published"], num_hidden_layers=2,
                               mamba_n_heads=WHOLE["mamba_n_heads"])
    config["share"] = dict(config["share"], mlp_columns=WHOLE["columns"],
                           mamba_channels=WHOLE["mamba_d_ssm"])
    config.update(changes)
    return config


def build(config, seed=20261004, **kw):
    model = hybrid_lm.from_config(config, vocab_size=config["vocab_size"], seq_len=SEQ, **kw)
    return model, bench_weights.make(ref.param_shapes(config), seed)


def tokens(batch=2, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, VOCAB, (batch, SEQ)),
                       jnp.int32)


@functools.lru_cache(maxsize=None)
def reference_loss_and_gradients():
    """The reference on the tiny configuration's seeded weights and ``tokens()``, once a run
    of this file."""
    config = tiny_config()
    params = bench_weights.make(ref.param_shapes(config), 20261004)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: ref.loss(p, tokens(), config))(params)


def flat(tree) -> dict:
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def worst_gradient_gap(grads) -> float:
    """The largest distance of a leaf's gradient from the reference's, as a share of the
    reference's own norm of that leaf."""
    got, want = flat(grads), flat(reference_loss_and_gradients()[1])
    assert got.keys() == want.keys()
    return max(float(jnp.linalg.norm(got[k] - want[k]) / jnp.linalg.norm(want[k]))
               for k in want)


# Float32 on both sides at ``highest``: the program's chunked scan and blocked softmax against
# the recurrence and materialised scores differ by reduction order alone, 2e-6 of a leaf's
# norm as read; 1e-4 leaves that fifty times of room, and the multiplier that matters least
# at this size (the dt segment's) moves some leaf by a thousand times the tolerance when it
# is dropped (0.125; the others 1.0 to 207).
TOLERANCE = 1e-4


# (a) the model against the reference -------------------------------------------------------


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_leafs_gradient_match_the_reference(remat):
    model, params = build(tiny_config(), remat=remat)
    assert model.layer_types == ("parallel", "parallel") and model.kept == hybrid_lm.H1_KEPT
    ids = tokens()
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(lambda p: model.loss(p, ids)[0])(params)
    want, _ = reference_loss_and_gradients()
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    assert worst_gradient_gap(grads) < TOLERANCE


def test_logits_match_the_reference():
    config = tiny_config()
    model, params = build(config)
    ids = tokens()
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, ids)
        want = jax.vmap(lambda row: jax.nn.log_softmax(ref.logits(params, row, config)))(ids)
    np.testing.assert_allclose(got, want, atol=3e-5)


MULTIPLIERS = [*(dict(field=name) for name in (
    "embedding", "lm_head", "attention_in", "attention_out", "key", "ssm_in", "ssm_out")),
    *(dict(field="ssm", index=i) for i in range(5)),
    *(dict(field="mlp", index=i) for i in range(2))]
SEGMENTS = ("z", "x", "B", "C", "dt")


def _case_name(case):
    if "index" not in case:
        return f"{case['field']}_multiplier"
    part = SEGMENTS[case["index"]] if case["field"] == "ssm" else ("gate", "down")[case["index"]]
    return f"{case['field']}_multipliers[{case['index']}] ({part})"


@pytest.mark.parametrize("case", MULTIPLIERS, ids=_case_name)
def test_a_multiplier_left_at_one_leaves_the_reference(case):
    """None of the fourteen is dead code: the program with this one multiplier at 1 (and the
    thirteen others as the file has them) differs from the reference, which has all of them,
    by over a thousand times the tolerance in some leaf's gradient."""
    model, params = build(tiny_config())
    value = getattr(model.multipliers, case["field"])
    if "index" in case:
        value = tuple(1.0 if i == case["index"] else v for i, v in enumerate(value))
    else:
        value = 1.0
    assert value != getattr(model.multipliers, case["field"])
    dropped = dataclasses.replace(model, multipliers=dataclasses.replace(
        model.multipliers, **{case["field"]: value}))
    ids = tokens()
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(lambda p: dropped.loss(p, ids)[0])(params)
    assert worst_gradient_gap(grads) > 1000 * TOLERANCE


def test_the_multipliers_are_the_files_by_key_and_the_plan_lists_them():
    with open(CONFIG_FILE) as fh:
        config = json.load(fh)
    model = hybrid_lm.from_config(config, vocab_size=config["vocab_size"], seq_len=8192)
    m = model.multipliers
    assert (m.embedding, m.lm_head, m.attention_in, m.attention_out, m.key, m.ssm_in,
            m.ssm_out) == tuple(config[f"{k}_multiplier"] for k in (
                "embedding", "lm_head", "attention_in", "attention_out", "key", "ssm_in",
                "ssm_out"))
    assert m.ssm == tuple(config["ssm_multipliers"]) and m.mlp == tuple(config["mlp_multipliers"])
    plans = model.plans(jax.make_jaxpr(lambda: 0)(), 8192)
    assert plans["multipliers"] == dataclasses.asdict(m) and len(plans["multipliers"]) == 9
    assert plans["ssm"]["heads"] == 8 and plans["ssm"]["groups"] == 1
    assert (plans["ssm"]["head_dim"], plans["ssm"]["state"], plans["ssm"]["chunk"]) == \
        (128, 256, 128)
    assert model.rotary_plan() == {"rope_dim": 128, "rope_pairing": "half_split",
                                   "rope_theta": 1e11, "rotation": "permutation"}
    with open(NEMOTRON_FILE) as fh:
        other = json.load(fh)
    other = hybrid_lm.from_config(other, vocab_size=other["vocab_size"], seq_len=8192)
    assert other.multipliers is None
    assert other.plans(jax.make_jaxpr(lambda: 0)(), 8)["multipliers"] is None


def test_the_files_parameters_are_the_programs_tree():
    with open(CONFIG_FILE) as fh:
        config = json.load(fh)
    model = hybrid_lm.from_config(config, vocab_size=config["vocab_size"], seq_len=8192)
    shapes = jax.tree_util.tree_leaves(model.param_shapes(),
                                       is_leaf=lambda x: isinstance(x, tuple))
    assert sum(math.prod(shape) for shape in shapes) == config["parameters"] == 769_637_472
    assert jax.tree_util.tree_map(lambda x: x.shape, ref.param_shapes(config)) == \
        jax.tree_util.tree_map(tuple, model.param_shapes(),
                               is_leaf=lambda x: isinstance(x, tuple))
    assert model.intermediate_size == 5376 and (model.mamba_heads, model.mamba_groups) == (8, 1)
    assert not model.sparse_layers and model.layer_types == ("parallel",) * 4


@pytest.mark.parametrize("columns", [16, 48, 64], ids=["four blocks", "a ragged last block",
                                                       "one block: the plain gather"])
def test_the_embeddings_rows_come_whole_or_in_column_blocks_alike(monkeypatch, columns):
    """A hidden width past ``EMBED_COLUMNS`` (5120 at the cell's size, 4096 the cap) is
    gathered in column blocks side by side: the same rows, and the same gradient of the
    table to the last bit (each entry is the same sum of the same rows in the same order)."""
    monkeypatch.setattr(ops.nn, "EMBED_COLUMNS", columns)
    table = jax.random.normal(jax.random.PRNGKey(3), (VOCAB, 64))
    ids, w = tokens(), jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, 64))
    np.testing.assert_array_equal(ops.embedding_rows(table, ids), table[ids])
    grad = lambda rows: jax.grad(lambda t: jnp.sum(rows(t) * w))(table)
    np.testing.assert_array_equal(grad(lambda t: ops.embedding_rows(t, ids)),
                                  grad(lambda t: t[ids]))
    blocks = str(jax.make_jaxpr(lambda t: ops.embedding_rows(t, ids))(table)).count("gather")
    assert blocks == -(-64 // columns)


def test_the_cells_table_is_gathered_in_two_blocks_and_the_other_cells_in_one():
    table = lambda d: jax.ShapeDtypeStruct((32640, d), jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    gathers = lambda d: str(jax.make_jaxpr(ops.embedding_rows)(table(d), ids)).count("gather")
    assert gathers(5120) == 2 and gathers(4096) == gathers(2304) == gathers(2048) == 1


# (b) what no multiplier costs a family that has none ---------------------------------------


def _mamba_mixer_before(p, u, model):
    """``hybrid_lm.mamba_mixer`` as it stood before the multipliers (PR 45's text)."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import ssm
    checkpoint_name = jax.ad_checkpoint.checkpoint_name
    b, s, _ = u.shape
    heads, groups = model.mamba_heads, model.mamba_groups
    hd, n = model.mamba_head_dim, model.ssm_state_size
    inner, bc = heads * hd, groups * n
    z, xbc, dt = jnp.split(
        checkpoint_name(hybrid_lm._dense(u, p["in_proj_kernel"]), "mamba_in_proj"),
        [inner, 2 * inner + 2 * bc], axis=-1)
    xbc = jax.nn.silu(hybrid_lm.causal_depthwise_conv(xbc, p["conv_kernel"])
                      + p["conv_bias"].astype(xbc.dtype))
    x, b_in, c_out = jnp.split(xbc, [inner, inner + bc], axis=-1)
    x = x.reshape(b, s, heads, hd)
    step = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    decay = -jnp.exp(p["A_log"].astype(jnp.float32))
    y = ssm.ssd_scan(x, step, step * decay, b_in.reshape(b, s, groups, n),
                     c_out.reshape(b, s, groups, n), chunk=model.chunk_size)
    y = y.astype(jnp.float32) + p["D_scale"].astype(jnp.float32)[:, None] * x
    normed = hybrid_lm.gated_group_norm(y.reshape(b, s, inner), z, p["gate_norm_scale"],
                                        groups, model.norm_eps)
    return hybrid_lm._dense(normed.astype(u.dtype), p["out_proj_kernel"])


def test_a_nemotron_file_lowers_to_the_text_it_lowered_to_before(monkeypatch):
    """``mamba_mixer`` with no multiplier given traces what it traced before them: the loss
    of a ``nemotron_h`` file (tiny widths, its own test's cut) and its gradient lower to the
    same text through the function as it stands and through its former text."""
    import test_nemotron_h as nemotron
    config = nemotron.tiny_config()
    model = hybrid_lm.from_config(config, vocab_size=config["vocab_size"], seq_len=32,
                                  expert_block=8, dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))["params"]
    ids = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    lowered = lambda: jax.jit(jax.grad(lambda p, x: model.loss(p, x)[0])).lower(
        params, ids).as_text()
    now = lowered()
    monkeypatch.setattr(hybrid_lm, "mamba_mixer", _mamba_mixer_before)
    assert "mamba" in model.layer_types and now == lowered()


# (c) the share tied to the model -------------------------------------------------------------


def share_of(p, config, chip: int, chips: int):
    """Chip ``chip`` of ``chips``'s configuration and its slices of a whole layer's leaves:
    its Mamba-2 heads with the B and C of the group they read, its query heads with their
    key/value head, its feed-forward columns; the two stream norms whole. With more chips
    than groups several chips read one group, and each holds that group's B and C."""
    heads, groups, kv = (config[k] for k in ("mamba_n_heads", "mamba_n_groups",
                                             "num_key_value_heads"))
    hd, n, ad = config["mamba_d_head"], config["mamba_d_state"], config["head_dim"]
    mine, q_mine = heads // chips, config["num_attention_heads"] // chips
    columns = config["share"]["mlp_columns"] // chips
    held_groups, held_kv = max(groups // chips, 1), max(kv // chips, 1)
    first_group, first_kv = chip * groups // chips, chip * kv // chips
    m = dict(config, mamba_n_heads=mine, mamba_n_groups=held_groups,
             num_attention_heads=q_mine, num_key_value_heads=held_kv)
    m["share"] = dict(config["share"], mlp_columns=columns, mamba_channels=mine * hd)
    take = lambda v, start, width, axis: jax.lax.slice_in_dim(v, start, start + width, axis=axis)
    inner, bc = heads * hd, groups * n
    channels = lambda v, axis: take(v, chip * mine * hd, mine * hd, axis)
    state = lambda v, axis: take(v, first_group * n, held_groups * n, axis)
    a = p["mamba"]
    z, x, b, c, dt = jnp.split(a["in_proj_kernel"], np.cumsum([inner, inner, bc, bc]), axis=1)
    cx, cb, cc = jnp.split(a["conv_kernel"], np.cumsum([inner, bc]), axis=1)
    bx, bb, bcc = jnp.split(a["conv_bias"], np.cumsum([inner, bc]))
    of_heads = lambda v, axis=0: take(v, chip * mine, mine, axis)
    mamba = dict(
        in_proj_kernel=jnp.concatenate([channels(z, 1), channels(x, 1), state(b, 1),
                                        state(c, 1), of_heads(dt, 1)], axis=1),
        conv_kernel=jnp.concatenate([channels(cx, 1), state(cb, 1), state(cc, 1)], axis=1),
        conv_bias=jnp.concatenate([channels(bx, 0), state(bb, 0), state(bcc, 0)]),
        dt_bias=of_heads(a["dt_bias"]), A_log=of_heads(a["A_log"]),
        D_scale=of_heads(a["D_scale"]), gate_norm_scale=channels(a["gate_norm_scale"], 0),
        out_proj_kernel=channels(a["out_proj_kernel"], 0))
    t = p["attn"]
    attn = dict(q_kernel=take(t["q_kernel"], chip * q_mine * ad, q_mine * ad, 1),
                k_kernel=take(t["k_kernel"], first_kv * ad, held_kv * ad, 1),
                v_kernel=take(t["v_kernel"], first_kv * ad, held_kv * ad, 1),
                out_kernel=take(t["out_kernel"], chip * q_mine * ad, q_mine * ad, 0))
    f = p["ff"]
    ff = dict(w1_kernel=take(f["w1_kernel"], chip * columns, columns, 1),
              w3_kernel=take(f["w3_kernel"], chip * columns, columns, 1),
              w2_kernel=take(f["w2_kernel"], chip * columns, columns, 0))
    return m, dict(p, mamba=mamba, attn=attn, ff=ff)


def _program_parts(leaves, m, u):
    """What a share adds to the stream through the program: its two mixers' sum from the
    normed input ``u``, and its feed-forward's from ``u`` too (any input serves)."""
    model = hybrid_lm.from_config(m, vocab_size=VOCAB, seq_len=SEQ)
    mixed = hybrid_lm.parallel_mixers(leaves, u, jnp.arange(SEQ), model)
    return mixed, hybrid_lm.dense_ff(leaves["ff"], u, model.multipliers.mlp)


def test_shares_of_whole_groups_add_up_to_the_uncut_layer():
    """Tensor parallelism as wide as the groups are many (2 over 2): each chip holds a whole
    group of the gated norm, so what the chips' out-, o- and down-projections add, from their
    own configurations and leaves through the program, sums to what the uncut reference's
    mixers and feed-forward add."""
    config = tiny_config(num_hidden_layers=1)
    p = bench_weights.make(ref.param_shapes(config), 7)["layer_0"]
    u = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, 64))
    with jax.default_matmul_precision("highest"):
        mixed = jax.vmap(lambda row: ref.mixers(p, row, config, MM, ES))(u)
        fed = jax.vmap(lambda row: ref.dense_ff(p["ff"], row, config, MM))(u)
        parts = [_program_parts(*reversed(share_of(p, config, chip, 2)), u)
                 for chip in range(2)]
    for whole, shares in ((mixed, [a for a, _ in parts]), (fed, [b for _, b in parts])):
        np.testing.assert_allclose(sum(shares), whole, atol=3e-5 * float(jnp.abs(whole).max()))
        assert float(jnp.abs(shares[0] - whole).max()) > 0.1 * float(jnp.abs(whole).max())


@pytest.mark.parametrize("chip", range(4))
def test_half_a_group_a_share_equals_the_reference_given_the_same_share(chip):
    """The committed cut, 4 chips over 2 groups: a chip holds half a group's heads. The sum
    over the chips is NOT asked here, and would not hold: the gated norm divides by the root
    mean square of a group's channels, the published group spans two chips, and this share
    takes the statistic over its own half (the configuration's one stated departure; a
    deployment exchanges the halves' sums of squares). What is held to the reference is each
    share alone, given the same leaves and the same configuration; the attention's and the
    feed-forward's parts have no such statistic and still add up."""
    config = tiny_config(num_hidden_layers=1)
    p = bench_weights.make(ref.param_shapes(config), 7)["layer_0"]
    u = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, 64))
    m, leaves = share_of(p, config, chip, 4)
    assert (m["mamba_n_heads"], m["mamba_n_groups"], m["num_key_value_heads"]) == (1, 1, 1)
    with jax.default_matmul_precision("highest"):
        got_mixed, got_fed = _program_parts(leaves, m, u)
        want_mixed = jax.vmap(lambda row: ref.mixers(leaves, row, m, MM, ES))(u)
        want_fed = jax.vmap(lambda row: ref.dense_ff(leaves["ff"], row, m, MM))(u)
    for got, want in ((got_mixed, want_mixed), (got_fed, want_fed)):
        np.testing.assert_allclose(got, want, atol=3e-5 * float(jnp.abs(want).max()))


def test_the_statistic_over_half_a_group_is_why_four_shares_do_not_add_up():
    config = tiny_config(num_hidden_layers=1)
    p = bench_weights.make(ref.param_shapes(config), 7)["layer_0"]
    u = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, 64))
    attention_alone = dict(config, ssm_out_multiplier=0.0)
    with jax.default_matmul_precision("highest"):
        summed = {name: sum(jax.vmap(lambda row: ref.mixers(leaves, row, m, MM, ES))(u)
                            for m, leaves in (share_of(p, c, chip, 4) for chip in range(4)))
                  for name, c in (("both", config), ("attention", attention_alone))}
        whole = {name: jax.vmap(lambda row: ref.mixers(p, row, c, MM, ES))(u)
                 for name, c in (("both", config), ("attention", attention_alone))}
    gap = lambda name: float(jnp.abs(summed[name] - whole[name]).max()) \
        / float(jnp.abs(whole[name]).max())
    assert gap("attention") < 3e-5 and gap("both") > 1e-2


# (d) the parser -----------------------------------------------------------------------------


@pytest.mark.parametrize("key, value, what", [
    ("attn_layer_indices", [0, 2], "attn_layer_indices not null"),
    ("mamba_use_mlp", False, "mamba_use_mlp false"),
    ("mamba_norm_before_gate", True, "mamba_norm_before_gate true"),
    ("mamba_rms_norm", False, "mamba_rms_norm false"),
    ("attention_bias", True, "a bias on a projection"),
    ("mamba_proj_bias", True, "a bias on a projection"),
    ("mlp_bias", True, "a bias on a projection"),
    ("projectors_bias", True, "a bias on a projection"),
    ("mamba_conv_bias", False, "a convolution without bias"),
    ("rope_scaling", {"type": "linear", "factor": 2}, "rope_scaling not null"),
    ("tie_word_embeddings", True, "tie_word_embeddings true"),
    ("hidden_act", "gelu", "hidden_act other than silu"),
    ("mamba_d_ssm", 48, "mamba_d_ssm other than"),
    ("share", {"mlp_columns": 96, "mamba_channels": 16}, "share.mamba_channels other than"),
    ("ssm_multipliers", [1.0, 1.0, 1.0], "ssm_multipliers that are not five"),
])
def test_what_the_file_states_and_the_module_does_not_compute_is_refused(key, value, what):
    with pytest.raises(ValueError, match=what + ".* is not written here"):
        hybrid_lm.from_config(tiny_config(**{key: value}), vocab_size=VOCAB, seq_len=SEQ)


def test_the_other_families_have_no_parallel_block_and_no_multiplier():
    for name in ("lfm2-24b-a2b-ep8", "kimi-linear-48b-a3b-ep32", "evabyte-6.5b-tp2",
                 "kanana-2-30b-a3b-ep8", "qwen3-next-80b-a3b-ep16"):
        with open(os.path.join(BENCH, "configs", name + ".json")) as fh:
            config = json.load(fh)
        model = hybrid_lm.from_config(config, vocab_size=config["vocab_size"],
                                      seq_len=int(config.get("max_seq_length", 8192)))
        assert model.multipliers is None and "parallel" not in model.layer_types, name


# (e) through train.lm -----------------------------------------------------------------------


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    from csed_514_project_distributed_training_using_pytorch_tpu.train import lm as train_lm
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import LMConfig
    work = tmp_path_factory.mktemp("falcon_h1_train")
    config_file = str(work / "tiny.json")
    with open(config_file, "w") as fh:
        json.dump(tiny_config(vocab_size=256), fh)
    tele = str(work / "t.jsonl")
    state, _ = train_lm.main(LMConfig(
        model_config=config_file, mesh="data=1", remat=True,
        corpus=os.path.join(REPO, "tests", "fixtures", "corpus_tiny"),
        epochs=2, batch_size=8, eval_batch=19, learning_rate=3e-2, seed=5,
        telemetry=tele, results_dir="", images_dir=str(work / "images"), generate=0))
    with open(tele) as fh:
        return state, [json.loads(line) for line in fh]


def test_main_trains_the_configuration_and_the_loss_falls(trained):
    _, events = trained
    epochs = [e for e in events if e["event"] == "epoch"]
    assert len(epochs) == 2 and epochs[1]["train_loss"] < epochs[0]["train_loss"]
    assert epochs[1]["val_loss"] < epochs[0]["val_loss"] < np.log(256) + 0.5
    assert all(e["expert_rows"] is None for e in epochs)        # no expert layer


def test_the_compile_event_says_what_the_new_block_asks(trained):
    _, events = trained
    event = [e for e in events if e["event"] == "compile"][0]
    attention = event["attention"]
    assert (attention["key_dim"], attention["value_dim"]) == (16, 16)
    assert (attention["rope_dim"], attention["rope_pairing"], attention["rope_theta"],
            attention["rotation"]) == (16, "half_split", 1e11, "permutation")
    ssm = event["ssm"]
    assert (ssm["heads"], ssm["groups"], ssm["head_dim"], ssm["state"], ssm["chunk"]) == \
        (4, 2, 8, 16, 16)
    assert ssm["chunks_per_sequence"] == 4 and ssm["kept"] == ["ssd_out", "ssd_state"]
    multipliers = event["multipliers"]
    assert len(multipliers) == 9 and multipliers["attention_in"] == 0.7
    assert sum(len(v) if isinstance(v, list) else 1 for v in multipliers.values()) == 14
    assert multipliers["ssm"] == tiny_config()["ssm_multipliers"]
    assert event["recompute"]["kept"] == list(hybrid_lm.H1_KEPT)
    assert event["recompute"]["kept_bytes"] > 0
    assert event["experts"] is None and event["kda"] is None and event["gdn"] is None
    assert event["head_products"] == 3      # the [T, vocab] logits: once a pass
    assert {"parallel_mixer", "dense_ff", "head_loss", "optimizer"} <= \
        set(event["scopes"]["top_scopes"])
    with open(event["scopes"]["path"]) as fh:
        scopes = {scope for scope, _ in json.load(fh)["ops"].values() if scope}
    assert any(scope.startswith("parallel_mixer/ssm/ssd") for scope in scopes)
    assert any(scope.startswith("parallel_mixer/attention/rotary") for scope in scopes)
    assert not any(scope.startswith(("mamba_mixer", "attention")) for scope in scopes)
