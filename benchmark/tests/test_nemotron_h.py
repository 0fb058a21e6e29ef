"""The ``train_corpus_ssm`` driver and the ``nemotron3-super-120b-tp8-ep64``
configuration at a tiny width on the CPU (float32), through everything of a run
except the look for a chip; the counts file against the configuration's own
arithmetic; the cell's manifest entries."""

import json
import os
import shutil
import time

import pytest
from test_drivers import _checks

import counts_nemotron_h as counts

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "nemotron_h_train_8k"
CONFIG = "nemotron3-super-120b-tp8-ep64"
METRICS = ("nemotron_train_mfu", "nemotron_step_roofline_share", "ssd_scan_roofline_share",
           "latent_expert_matmul_roofline_share", "latent_expert_rows_share",
           "latent_expert_load_imbalance")


def _read(*path):
    with open(os.path.join(*path)) as fh:
        return json.load(fh)


def _edit(path, fn):
    obj = _read(path)
    fn(obj)
    with open(path, "w") as fh:
        json.dump(obj, fh)


@pytest.fixture(scope="module")
def config():
    return _read(BENCH, "configs", CONFIG + ".json")


# the counts ----------------------------------------------------------------------------


def test_forward_flops_by_part_are_the_issues_arithmetic(config):
    """MFLOP a token, forward, as ISSUE 30 derived them from the shapes."""
    parts = counts.forward_flops_per_token(config, (8192 + 1) / 2.0)
    mega = {k: round(v / 1e6) for k, v in parts.items()}
    assert mega == {"mamba_projections": 137, "mamba_scan": 4, "attention_mixers": 19,
                    "routers": 21, "latent_projections": 84, "shared_expert": 55,
                    "experts": 19, "head": 134, "total": 473}
    assert counts.train_flops_per_example(config, 8192) == pytest.approx(
        3 * (8192 * (parts["total"] - parts["head"]) + 8191 * parts["head"]))


def test_the_scan_is_counted_as_its_chunks(config):
    q, n, p, heads = 128, 128, 64, 16
    per_chunk = 2 * q * q * n + heads * (2 * q * q * p + 4 * q * n * p)
    assert counts.scan_forward_flops_per_token(config) == per_chunk / q
    assert counts.scan_train_flops_per_example(config, 8192) == 3 * 5 * 64 * per_chunk
    assert counts.expert_train_flops_per_row(config) == 3 * 2 * 2 * 1024 * 2688


def test_reduced_names_counts_and_no_width(config):
    """``reduced`` is layers, experts, ids, heads and groups held, and the module that
    is left out; each with its published value beside it. The widths (hidden, head,
    state, latent, expert and shared-expert sizes, experts a token) stand as published."""
    assert set(config["reduced"]) == set(config["published"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size", "mamba_num_heads", "n_groups",
        "num_attention_heads", "num_key_value_heads", "num_nextn_predict_layers"}
    assert all(config[k] != config["published"][k] for k in config["reduced"])
    published_widths = dict(
        hidden_size=4096, head_dim=128, mamba_head_dim=64, ssm_state_size=128, conv_kernel=4,
        chunk_size=128, expand=2, moe_latent_size=1024, moe_intermediate_size=2688,
        intermediate_size=2688, moe_shared_expert_intermediate_size=5376,
        num_experts_per_tok=22, routed_scaling_factor=5, n_shared_experts=1)
    assert {k: config[k] for k in published_widths} == published_widths


# the manifest ----------------------------------------------------------------------------


def test_the_cells_entries_name_files_that_are_there():
    manifest = _read(REPO, "BENCHMARK.json")
    cell = [w for w in manifest["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "train_8k_b2", 1)
    entry = [c for c in manifest["configs"] if c["name"] == CONFIG][0]
    config = _read(REPO, entry["file"])
    assert entry["reduced"] == config["reduced"] and entry["source"] == config["source"]
    traffic = _read(BENCH, "traffic", "train_8k_b2.json")
    assert (traffic["batch"], traffic["seq_len"], traffic["steps_per_epoch"],
            traffic["zipf_exponent"], traffic["test_examples"]) == (2, 8192, 8, 1.1, 2)
    assert config["train"]["args"]["learning_rate"] == 1e-6
    workload = _read(BENCH, "workloads", CELL + ".json")
    assert workload["driver"] == "train_corpus_ssm" and workload["loss_steps"] == 3
    assert os.path.exists(os.path.join(BENCH, "reference", config["reference"] + ".py"))
    listed = {m["name"]: m for m in manifest["per_layer"] if CELL in m.get("workloads", [])}
    assert set(listed) == set(METRICS)
    for name, metric in listed.items():
        spec = _read(BENCH, "layer_metrics", name + ".json")
        assert (spec["layer"], spec["unit"]) == (metric["layer"], metric["unit"])
        assert os.path.exists(os.path.join(BENCH, "reducers", spec["reducer"] + ".py"))
        assert metric["workloads"] == [CELL] and metric["moves"] == "train_examples_per_s"


# the driver, tiny, on the CPU ------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("nemotron_root"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)

    def config(c):      # the widths cut, the share's kinds kept: M E * E
        c.update(hidden_size=32, head_dim=8, num_attention_heads=4, num_key_value_heads=2,
                 mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
                 chunk_size=16, moe_intermediate_size=24, moe_latent_size=16,
                 n_routed_experts=4, num_experts_per_tok=6, vocab_size=64,
                 num_hidden_layers=4, hybrid_override_pattern="ME*E")
        c["published"]["n_routed_experts"] = 16
        c["share"].update(first_layer=0, shared_expert_columns=20)
        c["train"]["args"].update(bf16=False, learning_rate=3e-4)
        c["train"]["optimizer"].update(learning_rate=3e-4)      # a handful of tiny steps
    _edit(os.path.join(bench, "configs", CONFIG + ".json"), config)

    def traffic(t):
        t.update(batch=2, steps_per_epoch=4, test_examples=2, seq_len=48)
        t["trainer_args"].update(batch_size=2, eval_batch=2)
    _edit(os.path.join(bench, "traffic", "train_8k_b2.json"), traffic)
    return root


@pytest.fixture()
def run(tiny_root):
    import harness

    def run_cell(*, seed=3000000031, seconds=1.0, trace=False, **kw):
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
    assert "routing: 0.000 %" in capsys.readouterr().out      # float32 on both sides


def test_traced_run_reports_the_counters_and_leaves_the_device_shares_out(run):
    """The CPU has no device plane: the readers of the device trace find nothing and
    leave their metric out; the counters and the host-clock utilisation are there."""
    result, lines = run(seconds=2.0, trace=True)
    assert result["correct"] is True, lines
    metrics = result["metrics"]
    assert {"latent_expert_load_imbalance", "latent_expert_rows_share", "nemotron_train_mfu",
            "compile_cache_misses"} <= set(metrics)
    assert not any("roofline" in name for name in metrics)
    assert metrics["latent_expert_load_imbalance"]["value"] >= 1.0
    # 4 of 16 experts held, 6 a token: 1.5 of the bound's 4 rows a token are expected
    assert 0.2 < metrics["latent_expert_rows_share"]["value"] < 0.6


def test_control_is_not_correct(run, tiny_root):
    result, lines = run(seed=3000000041, control=True)
    got = _checks(lines)
    limits = _read(tiny_root, "benchmark", "workloads", CELL + ".json")["limits"]
    assert result["correct"] is False
    assert any(got[k] > limits[k] for k in ("loss_gap", "moment_norm_gap", "delta_norm_gap"))


def test_a_state_that_is_not_carried_is_not_correct(run, tiny_root, monkeypatch):
    """The scan restarts from a zero state at every chunk: the loss or the first
    gradient leaves the reference's."""
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    whole = hybrid_lm.ssm.ssd_scan

    def chunk_by_chunk(x, dt, a, b, c, *, chunk):
        cut = lambda v: v.reshape((-1, chunk) + v.shape[2:])
        return whole(*map(cut, (x, dt, a, b, c)), chunk=chunk).reshape(x.shape)

    monkeypatch.setattr(hybrid_lm.ssm, "ssd_scan", chunk_by_chunk)
    result, lines = run()
    got = _checks(lines)
    limits = _read(tiny_root, "benchmark", "workloads", CELL + ".json")["limits"]
    assert result["correct"] is False, lines
    assert any(got[k] > limits[k] for k in ("loss_gap", "moment_norm_gap"))


def test_a_program_that_drops_the_bias_rule_is_not_correct(run, tiny_root, monkeypatch):
    """The selection biases stay where they were seeded while the reference's move a
    rate a step: the bias leaves' change is the worst leaf of ``delta_norm_gap``."""
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    monkeypatch.setattr(hybrid_lm.HybridLM, "rebalance",
                        lambda self, params, arrived: (params, arrived[0]))
    result, lines = run()
    limits = _read(tiny_root, "benchmark", "workloads", CELL + ".json")["limits"]
    assert result["correct"] is False, lines
    assert _checks(lines)["delta_norm_gap"] > limits["delta_norm_gap"]
