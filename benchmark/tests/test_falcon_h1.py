"""The ``train_corpus_h1`` driver and the ``falcon-h1-34b-tp4`` configuration at a tiny
width on the CPU (float32), through everything of a run except the look for a chip; the
counts file against a hand count; the file's ``parameters`` against the reference's tree;
the cell's manifest entries, by membership and not by position."""

import dataclasses
import json
import math
import os
import shutil
import time

import pytest
from test_drivers import _checks

import counts_falcon_h1 as counts

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "falcon_h1_train_8k"
CONFIG = "falcon-h1-34b-tp4"
TRAFFIC = "train_8k_b1"
OWN_METRICS = {"parallel_mixer_outside_kernels_ms"}
SHARED_METRICS = {"ssd_scan_roofline_share", "gated_attention_roofline_share",
                  "kimi_linear_train_mfu", "kimi_linear_step_roofline_share",
                  "scope_named_share", "recompute_share", "head_loss_ms", "dense_ff_ms"}
REDUCED = {"num_hidden_layers": 72, "vocab_size": 261120, "num_attention_heads": 20,
           "num_key_value_heads": 4, "mamba_n_heads": 32, "mamba_n_groups": 2}


def _read(*path):
    with open(os.path.join(*path)) as fh:
        return json.load(fh)


def _edit(path, fn):
    obj = _read(path)
    fn(obj)
    with open(path, "w") as fh:
        json.dump(obj, fh)


def _tiny(c):
    """Hidden 64, 4 query heads on 2 key/value heads of 16, 4 Mamba-2 heads of 8 in 2 groups
    with a state of 16 and chunks of 16, 96 feed-forward columns, 2 layers, 40 ids; every
    multiplier kept, and ``attention_in_multiplier`` moved off the published 1."""
    c.update(hidden_size=64, intermediate_size=96, head_dim=16, num_attention_heads=4,
             num_key_value_heads=2, mamba_n_heads=4, mamba_n_groups=2, mamba_d_head=8,
             mamba_d_ssm=32, mamba_d_state=16, mamba_chunk_size=16, num_hidden_layers=2,
             vocab_size=40, attention_in_multiplier=0.7)
    c["published"] = dict(c["published"], num_hidden_layers=2, mamba_n_heads=4)
    c["share"] = dict(c["share"], mlp_columns=96, mamba_channels=32)


@pytest.fixture(scope="module")
def config():
    return _read(BENCH, "configs", CONFIG + ".json")


# the counts ----------------------------------------------------------------------------


def test_the_flops_are_a_hand_count_at_the_small_size(config):
    """Q 16, P 8, N 16, 4 heads in 2 groups: a chunk is 2 · 2 · 16² · 16 of scores and
    4 · (2 · 16² · 8 + 4 · 16 · 16 · 8) a head; attention 4 heads · 2 · 32 a visible pair."""
    small = dict(config)
    _tiny(small)
    chunk = 2 * 2 * 16 * 16 * 16 + 4 * (2 * 16 * 16 * 8 + 4 * 16 * 16 * 8)
    assert counts.scan_forward_flops_per_token(small) == chunk / 16
    assert counts.scan_train_flops_per_example(small, 128) == 3 * 128 * 2 * chunk / 16
    assert counts.attention_train_flops_per_example(small, 128) == \
        3 * 128 * 2 * 4 * 2 * 32 * 129 / 2
    parts = counts.forward_flops_per_token(small, 64.5)
    assert parts == {"mamba_projections": 2 * 2 * 64 * (2 * 32 + 2 * 32 + 4 + 32),
                     "mamba_scan": 2 * chunk / 16,
                     "attention_projections": 2 * 2 * 64 * 16 * (2 * 4 + 2 * 2),
                     "attention": 2 * 4 * 2 * 32 * 64.5, "dense_ff": 2 * 2 * 3 * 64 * 96,
                     "head": 2 * 64 * 40,
                     "total": sum(v for k, v in parts.items() if k != "total")}
    assert counts.train_flops_per_example(small, 128) == \
        3 * (128 * (parts["total"] - parts["head"]) + 127 * parts["head"])


def test_the_cells_step_is_the_issues_arithmetic(config):
    """ISSUE 47: the share's forward FLOPs a token are feed-forward 53 %, head 27 %, the two
    mixers 20 %, the scan 1.4 M a layer; an example is 30.8 TFLOP before recomputation."""
    parts = counts.forward_flops_per_token(config, (8192 + 1) / 2)
    share = {k: v / parts["total"] for k, v in parts.items()}
    mixers = sum(share[k] for k in ("mamba_projections", "mamba_scan",
                                    "attention_projections", "attention"))
    assert 0.52 < share["dense_ff"] < 0.54 and 0.26 < share["head"] < 0.28
    assert 0.19 < mixers < 0.22
    assert round(counts.scan_forward_flops_per_token(config) / 1e6, 1) == 1.4
    assert 30.5 < counts.train_flops_per_example(config, 8192) / 1e12 < 31.0


def test_reduced_names_counts_and_no_width(config):
    assert set(config["reduced"]) == set(config["published"]) == set(REDUCED)
    assert config["published"] == REDUCED
    assert all(config[k] != config["published"][k] for k in config["reduced"])
    catalog = os.path.join("/opt/skills/guides/model-configs", "architectures.jsonl")
    if os.path.exists(catalog):             # every number of the row, but the reduced keys
        with open(catalog) as fh:
            row = [r for r in map(json.loads, fh) if r["name"] == "Falcon-H1-34B-Instruct"][0]
        assert config["source"] == row["source_url"]
        assert {k: v for k, v in row["config"].items() if k not in REDUCED} == \
            {k: config[k] for k in row["config"] if k not in REDUCED}
    widths = dict(hidden_size=5120, head_dim=128, mamba_d_head=128, mamba_d_state=256,
                  mamba_d_conv=4, mamba_chunk_size=128, intermediate_size=21504,
                  rms_norm_eps=1e-5, rope_theta=100000000000,
                  lm_head_multiplier=0.0078125, key_multiplier=0.011048543456039804)
    assert {k: config[k] for k in widths} == widths
    assert len(config["ssm_multipliers"]) == 5 and len(config["mlp_multipliers"]) == 2
    share = config["share"]
    assert (share["chips_per_layer"], share["tensor_parallel"], share["vocab_parallel"],
            share["mlp_columns"], share["first_layer"]) == (4, 4, 8, 21504 // 4, 0)
    # a width of the whole model keeps its published value; the share says what is held
    assert config["mamba_d_ssm"] == 4096 == REDUCED["mamba_n_heads"] * config["mamba_d_head"]
    assert share["mamba_channels"] == config["mamba_n_heads"] * config["mamba_d_head"] == 1024
    assert "4 chips" in config["deployment"] and "all-reduce" in config["deployment"]
    assert any("half a group" in a for a in config["assumed"])


def test_the_files_parameters_are_the_references_tree(config):
    import jax
    from reference import falcon_h1 as ref
    leaves = jax.tree_util.tree_leaves(ref.param_shapes(config))
    mamba = 5120 * 2568 + 1536 * 4 + 1536 + 3 * 8 + 1024 + 1024 * 5120
    layer = mamba + (2 * 5120 * 640 + 2 * 5120 * 128) + 3 * 5120 * 5376 + 2 * 5120
    assert (mamba, layer) == (18_399_768, 108_849_688)
    assert sum(math.prod(x.shape) for x in leaves) == config["parameters"] == \
        4 * layer + 2 * 32640 * 5120 + 5120 == 769_637_472


# the manifest ----------------------------------------------------------------------------


def test_the_cells_entries_name_files_that_are_there(config):
    manifest = _read(REPO, "BENCHMARK.json")
    cell = [w for w in manifest["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1)
    entry = [c for c in manifest["configs"] if c["name"] == CONFIG][0]
    assert _read(REPO, entry["file"]) == config
    assert entry["reduced"] == config["reduced"] and entry["source"] == config["source"]
    mix = _read(BENCH, "traffic", TRAFFIC + ".json")
    assert (mix["batch"], mix["seq_len"], mix["steps_per_epoch"], mix["test_examples"]) == \
        (1, 8192, 8, 1)
    workload = _read(BENCH, "workloads", CELL + ".json")
    assert workload["driver"] == "train_corpus_h1" and workload["loss_steps"] == 3
    assert os.path.exists(os.path.join(BENCH, "drivers", workload["driver"] + ".py"))
    assert os.path.exists(os.path.join(BENCH, "reference", config["reference"] + ".py"))
    flops = config["train"]["flops"]
    assert all(callable(getattr(counts, flops[key]))
               for key in ("per_example", "scan_per_example", "attention_per_example"))
    rate = [e for e in manifest["end_to_end"] if e["name"] == "train_examples_per_s"][0]
    assert CELL in rate["workloads"]
    listed = {m["name"]: m for m in manifest["per_layer"] if CELL in m.get("workloads", [])}
    assert OWN_METRICS | SHARED_METRICS <= set(listed)
    for name in OWN_METRICS:
        metric, spec = listed[name], _read(BENCH, "layer_metrics", name + ".json")
        assert (spec["layer"], spec["unit"]) == (metric["layer"], metric["unit"])
        assert os.path.exists(os.path.join(BENCH, "reducers", spec["reducer"] + ".py"))
        assert metric["workloads"] == [CELL] and metric["moves"] == "train_examples_per_s"
    kernels = set(_read(BENCH, "layer_metrics", "ssd_scan_roofline_share.json")["params"]["ops"]) \
        | set(_read(BENCH, "layer_metrics", "gated_attention_roofline_share.json")
              ["params"]["ops"])
    assert kernels == set(_read(BENCH, "layer_metrics", "parallel_mixer_outside_kernels_ms.json")
                          ["params"]["exclude_ops"])


# the driver, tiny, on the CPU ------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("falcon_h1_root"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)

    def config(c):
        _tiny(c)
        c["train"]["args"].update(bf16=False, learning_rate=3e-4)
        c["train"]["optimizer"].update(learning_rate=3e-4)      # a handful of tiny steps
    _edit(os.path.join(bench, "configs", CONFIG + ".json"), config)

    def traffic(t):
        t.update(batch=2, steps_per_epoch=4, test_examples=2, seq_len=48)
        t["trainer_args"].update(batch_size=2, eval_batch=2)
    _edit(os.path.join(bench, "traffic", TRAFFIC + ".json"), traffic)
    return root


@pytest.fixture()
def run(tiny_root):
    import harness

    def run_cell(*, seed=4700000047, seconds=1.0, trace=False, **kw):
        lines = []
        result = harness.run_cell(tiny_root, CELL, seed=seed, seconds=seconds, trace=trace,
                                  t_process=time.perf_counter(), require_chip=False,
                                  out=lines.append, **kw)
        return result, lines

    return run_cell


def test_sound_run_is_correct(run, capsys):
    result, lines = run()
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["train_examples_per_s"]["value"] > 0
    got = _checks(lines)
    assert got["window_compiles"] == 0.0
    assert max(got[k] for k in ("loss_gap", "moment_norm_gap", "delta_norm_gap")) < 1e-3
    assert "routing:" not in capsys.readouterr().out      # no router, no line


def test_traced_run_reports_the_counters_and_leaves_the_device_shares_out(run):
    """The CPU has no device plane: the readers of the device trace find nothing and
    leave their metric out; the host-clock utilisation and the cache's counter are there."""
    result, lines = run(seconds=2.0, trace=True)
    assert result["correct"] is True, lines
    metrics = result["metrics"]
    assert {"kimi_linear_train_mfu", "compile_cache_misses"} <= set(metrics)
    assert not any("roofline" in name or name.endswith("_ms") for name in metrics)


def test_control_is_not_correct(run, tiny_root):
    result, lines = run(seed=4700000051, control=True)
    got = _checks(lines)
    limits = _read(tiny_root, "benchmark", "workloads", CELL + ".json")["limits"]
    assert result["correct"] is False
    assert any(got[k] > limits[k] for k in ("loss_gap", "moment_norm_gap", "delta_norm_gap"))


FAULTS = {"the five ssm_multipliers dropped": dict(ssm=(1.0,) * 5),
          "key_multiplier at 1": dict(key=1.0)}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_dropped_multiplier_is_not_correct(run, tiny_root, monkeypatch, fault):
    """The two faults the cell's limits were read against on the chip, planted in the
    program at the tiny width: the first gradient or the parameters' change leaves the
    reference's (near a seeded start the loss is ln(vocab) whatever the mixers see)."""
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    parse = hybrid_lm._FAMILIES["falcon_h1"]

    def faulty(config):
        pattern, fields = parse(config)
        return pattern, dict(fields, multipliers=dataclasses.replace(
            fields["multipliers"], **FAULTS[fault]))

    monkeypatch.setitem(hybrid_lm._FAMILIES, "falcon_h1", faulty)
    result, lines = run()
    got = _checks(lines)
    limits = _read(tiny_root, "benchmark", "workloads", CELL + ".json")["limits"]
    assert result["correct"] is False, lines
    assert any(got[k] > limits[k] for k in ("loss_gap", "moment_norm_gap", "delta_norm_gap"))


def test_a_program_without_the_family_is_refused_before_anything_is_written(
        tiny_root, monkeypatch):
    """The parent's tree: ``from_config`` names no ``falcon_h1``, and the run ends with the
    harness's refusal, not a trace of a model it cannot build."""
    import harness
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    monkeypatch.delitem(hybrid_lm._FAMILIES, "falcon_h1")
    with pytest.raises(harness.Refused, match="cannot build this configuration"):
        harness.run_cell(tiny_root, CELL, seed=1, seconds=1.0, trace=False,
                         t_process=time.perf_counter(), require_chip=False)
