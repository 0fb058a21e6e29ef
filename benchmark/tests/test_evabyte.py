"""The ``train_corpus_eva`` driver and the ``evabyte-6.5b-tp2`` configuration at a tiny
width on the CPU (float32), through everything of a run except the look for a chip; the
counts file against a hand count; the file's ``parameters`` against the reference's tree;
the cell's manifest entries, by membership and not by position."""

import json
import math
import os
import shutil
import time

import pytest
from test_drivers import _checks

import counts_evabyte as counts

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "evabyte_train_32k"
CONFIG = "evabyte-6.5b-tp2"
TRAFFIC = "train_32k_b1"
OWN_METRICS = {"evabyte_train_mfu", "evabyte_step_roofline_share",
               "eva_attention_roofline_share", "eva_mixer_outside_kernels_ms", "dense_ff_ms"}
SHARED_METRICS = {"scope_named_share", "recompute_share", "head_loss_ms"}


def _read(*path):
    with open(os.path.join(*path)) as fh:
        return json.load(fh)


def _edit(path, fn):
    obj = _read(path)
    fn(obj)
    with open(path, "w") as fh:
        json.dump(obj, fh)


def _tiny(c):
    """Hidden 64, 4 heads of 16 (all held), window 32 of chunks of 4, 3 prediction heads,
    2 layers, 96 feed-forward columns, 40 ids."""
    c.update(hidden_size=64, intermediate_size=96, num_attention_heads=4,
             num_key_value_heads=4, window_size=32, chunk_size=4, num_pred_heads=3,
             num_hidden_layers=2, vocab_size=40)
    c["published"] = dict(num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4)
    c["share"] = dict(c["share"], heads=4, mlp_columns=96)


@pytest.fixture(scope="module")
def config():
    return _read(BENCH, "configs", CONFIG + ".json")


# the counts ----------------------------------------------------------------------------


def test_the_pairs_and_the_flops_are_a_hand_count_at_the_small_size(config):
    """S 128, W 32, c 4, M 8: four windows of 32 · 33 / 2 = 528 exact pairs, and
    32 · 8 · (0 + 1 + 2 + 3) = 1536 summary pairs, a head."""
    small = dict(config)
    _tiny(small)
    assert counts.attention_pairs_per_example(small, 128) == (4 * 528, 1536)
    d, heads = 16, 4
    forward = heads * (4 * d * (4 * 528 + 1536) + 6 * d * 128)
    assert counts.eva_attention_forward_flops_per_example(small, 128) == forward
    assert counts.eva_attention_train_flops_per_example(small, 128) == 3 * 2 * forward
    parts = counts.forward_flops_per_example(small, 128)
    assert parts == {"eva_projections": 2 * 128 * 2 * 4 * 64 * 64, "eva_attention": 2 * forward,
                     "dense_ff": 2 * 128 * 2 * 3 * 64 * 96, "head": 128 * 2 * 64 * 3 * 40,
                     "total": sum(v for k, v in parts.items() if k != "total")}
    assert counts.train_flops_per_example(small, 128) == 3 * parts["total"]


def test_the_cells_step_is_the_issues_arithmetic(config):
    """ISSUE 37: a query of the last window sees up to 2048 keys and 1920 summaries; the
    attention is about 7 % of the counted FLOPs, its projections 30 %, the feed-forward
    61 % (two thirds of what is not attention), the head under 2 %; a step of six layers is
    about 130 TFLOP before recomputation."""
    exact, summary = counts.attention_pairs_per_example(config, 32768)
    assert exact == 16 * 2048 * 2049 // 2 and summary == 2048 * 128 * 120
    parts = counts.forward_flops_per_example(config, 32768)
    share = {k: v / parts["total"] for k, v in parts.items()}
    assert 0.06 < share["eva_attention"] < 0.08 and 0.60 < share["dense_ff"] < 0.62
    assert share["head"] < 0.02 and 0.29 < share["eva_projections"] < 0.31
    assert round(counts.train_flops_per_example(config, 32768) / 1e12) == \
        round(3 * parts["total"] / 1e12)
    assert 120 < counts.train_flops_per_example(config, 32768) / 1e12 < 135


def test_reduced_names_counts_and_no_width(config):
    assert set(config["reduced"]) == set(config["published"]) == {
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads"}
    assert all(config[k] != config["published"][k] for k in config["reduced"])
    published_widths = dict(hidden_size=4096, intermediate_size=11008, window_size=2048,
                            chunk_size=16, num_pred_heads=8, vocab_size=320,
                            max_seq_length=32768, rope_theta=100000, rms_norm_eps=1e-5)
    assert {k: config[k] for k in published_widths} == published_widths
    share = config["share"]
    assert (share["chips_per_layer"], share["chip"], share["heads"], share["mlp_columns"],
            share["first_layer"]) == (2, 0, config["num_attention_heads"], 5504, 0)
    assert "2 chips" in config["deployment"] and "all-reduce" in config["deployment"]
    assert len(config["assumed"]) >= 8 and all(isinstance(a, str) for a in config["assumed"])


def test_the_files_parameters_are_the_references_tree(config):
    import jax
    from reference import evabyte as ref
    leaves = jax.tree_util.tree_leaves(ref.param_shapes(config))
    layer = 4 * 4096 * 2048 + 2 * 16 * 128 + 3 * 4096 * 5504 + 2 * 4096
    assert layer == 101_199_872
    assert sum(math.prod(x.shape) for x in leaves) == config["parameters"] == \
        config["num_hidden_layers"] * layer + 320 * 4096 + 4096 * 8 * 320 + 4096


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
        (1, config["max_seq_length"], 4, 1)
    workload = _read(BENCH, "workloads", CELL + ".json")
    assert workload["driver"] == "train_corpus_eva" and workload["loss_steps"] == 3
    assert os.path.exists(os.path.join(BENCH, "drivers", workload["driver"] + ".py"))
    assert os.path.exists(os.path.join(BENCH, "reference", config["reference"] + ".py"))
    flops = config["train"]["flops"]
    assert all(callable(getattr(counts, flops[key]))
               for key in ("per_example", "attention_per_example"))
    rate = [e for e in manifest["end_to_end"] if e["name"] == "train_examples_per_s"][0]
    assert CELL in rate["workloads"]
    listed = {m["name"]: m for m in manifest["per_layer"] if CELL in m.get("workloads", [])}
    assert OWN_METRICS | SHARED_METRICS <= set(listed)
    for name in OWN_METRICS:
        metric, spec = listed[name], _read(BENCH, "layer_metrics", name + ".json")
        assert (spec["layer"], spec["unit"]) == (metric["layer"], metric["unit"])
        assert os.path.exists(os.path.join(BENCH, "reducers", spec["reducer"] + ".py"))
        assert metric["workloads"] == [CELL] and metric["moves"] == "train_examples_per_s"
    kernels = set(_read(BENCH, "layer_metrics", "eva_attention_roofline_share.json")
                  ["params"]["ops"])
    assert kernels == set(_read(BENCH, "layer_metrics", "eva_mixer_outside_kernels_ms.json")
                          ["params"]["exclude_ops"]) >= {"eva_fwd", "eva_dq", "eva_dkv"}


# the driver, tiny, on the CPU ------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("evabyte_root"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)

    def config(c):
        _tiny(c)
        c["train"]["args"].update(bf16=False, learning_rate=3e-4)
        c["train"]["optimizer"].update(learning_rate=3e-4)      # a handful of tiny steps
    _edit(os.path.join(bench, "configs", CONFIG + ".json"), config)

    def traffic(t):
        t.update(batch=2, steps_per_epoch=4, test_examples=2, seq_len=128)
        t["trainer_args"].update(batch_size=2, eval_batch=2)
    _edit(os.path.join(bench, "traffic", TRAFFIC + ".json"), traffic)
    return root


@pytest.fixture()
def run(tiny_root):
    import harness

    def run_cell(*, seed=3700000037, seconds=1.0, trace=False, **kw):
        lines = []
        result = harness.run_cell(tiny_root, CELL, seed=seed, seconds=seconds, trace=trace,
                                  t_process=time.perf_counter(), require_chip=False,
                                  out=lines.append, **kw)
        return result, lines

    return run_cell


def test_the_model_view_has_no_expert_key_and_the_driver_asks_for_none(config):
    import harness
    driver = harness.load_module(os.path.join(BENCH, "drivers", "train_corpus_eva.py"),
                                 "bench_driver_train_corpus_eva_for_test")
    view = driver.corpus._model_view(config)
    assert "train" not in view and not {"num_dense_layers", "num_experts_per_tok",
                                        "num_experts", "moe_intermediate_size"} & set(view)
    assert view["model_type"] == "evabyte" and view["share"]["mlp_columns"] == 5504


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
    assert {"evabyte_train_mfu", "compile_cache_misses"} <= set(metrics)
    assert not any("roofline" in name or name.endswith("_ms") for name in metrics)


def test_control_is_not_correct(run, tiny_root):
    result, lines = run(seed=3700000041, control=True)
    got = _checks(lines)
    limits = _read(tiny_root, "benchmark", "workloads", CELL + ".json")["limits"]
    assert result["correct"] is False
    assert any(got[k] > limits[k] for k in ("loss_gap", "moment_norm_gap", "delta_norm_gap"))


def test_summaries_seen_from_their_own_window_are_not_correct(run, tiny_root, monkeypatch):
    """A query that also sees the summaries of its own window's chunks counts keys twice:
    the loss or the first gradient leaves the reference's."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import eva
    plain = eva.dense_attention

    def one_window_early(q, k, v, ks, vs, *, window, chunk):
        import jax.numpy as jnp
        shift = window // chunk     # summary j + M stands where j should: seen a window early
        early = lambda x: jnp.pad(x[:, shift:], ((0, 0), (0, shift), (0, 0)))
        return plain(q, k, v, early(ks), early(vs), window=window, chunk=chunk)

    monkeypatch.setattr(eva, "dense_attention", one_window_early)
    result, lines = run()
    got = _checks(lines)
    limits = _read(tiny_root, "benchmark", "workloads", CELL + ".json")["limits"]
    assert result["correct"] is False, lines
    assert any(got[k] > limits[k] for k in ("loss_gap", "moment_norm_gap"))
