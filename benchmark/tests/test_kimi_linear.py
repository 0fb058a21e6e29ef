"""The ``train_corpus_kda`` driver and the ``kimi-linear-48b-a3b-ep32`` configuration at
a tiny width on the CPU (float32), through everything of a run except the look for a
chip; the counts file against the configuration's own arithmetic; the file's
``parameters`` against the reference's tree; the cell's manifest entries."""

import json
import math
import os
import shutil
import time

import pytest
from test_drivers import _checks

import counts_kimi_linear as counts

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "kimi_linear_train_8k"
CONFIG = "kimi-linear-48b-a3b-ep32"
METRICS = ("kimi_linear_train_mfu", "kimi_linear_step_roofline_share",
           "kda_scan_roofline_share", "mla_attention_roofline_share",
           "kimi_expert_matmul_roofline_share", "kimi_expert_rows_share",
           "kimi_expert_load_imbalance")


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
    """MFLOP a token, forward, from the shapes; ISSUE 32's 2.3 GFLOP a token trained
    and 38 TFLOP a step of 2 x 8192."""
    parts = counts.forward_flops_per_token(config, (8192 + 1) / 2.0)
    mega = {k: round(v / 1e6) for k, v in parts.items()}
    assert mega == {"kda_projections": 316, "kda_scan": 21, "mla_projections": 58,
                    "mla_attention": 84, "dense_ff": 127, "routers": 5, "shared_expert": 57,
                    "experts": 14, "head": 94, "total": 776}
    per_example = counts.train_flops_per_example(config, 8192)
    assert per_example == pytest.approx(
        3 * (8192 * (parts["total"] - parts["head"]) + 8191 * parts["head"]))
    assert round(per_example / 8192 / 1e9, 1) == 2.3 and round(2 * per_example / 1e12) == 38


def test_the_scan_and_the_attention_are_counted_as_their_kernels_do_them(config):
    c, k, v, heads = 64, 128, 128, 32
    per_chunk = c * c * (5 * k + 3 * v) + 6 * c * k * v
    assert counts.kda_scan_forward_flops_per_token(config) == heads * per_chunk / c
    assert counts.kda_scan_train_flops_per_example(config, 8192) == \
        3 * 4 * (8192 // c) * heads * per_chunk
    pairs = 8192 * 8193 // 2
    assert counts.mla_attention_train_flops_per_example(config, 8192) == \
        3 * 1 * heads * 2 * (192 + 128) * pairs
    assert counts.expert_train_flops_per_row(config) == 3 * 3 * 2 * 2304 * 1024


def test_reduced_names_counts_and_no_width(config):
    """``reduced`` is layers, experts and ids held, each with its published value beside
    it; every width stands as published; the file says what it assumed and which
    deployment it is a share of."""
    assert set(config["reduced"]) == set(config["published"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert all(config[k] != config["published"][k] for k in config["reduced"])
    published_widths = dict(
        hidden_size=2304, intermediate_size=9216, moe_intermediate_size=1024, head_dim=72,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_attention_heads=32, num_key_value_heads=32, num_experts_per_token=8,
        num_shared_experts=1, routed_scaling_factor=2.446, first_k_dense_replace=1)
    assert {k: config[k] for k in published_widths} == published_widths
    linear = config["linear_attn_config"]
    assert (linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"]) == \
        (32, 128, 4)
    assert len(linear["kda_layers"]) + len(linear["full_attn_layers"]) == 27
    assert config["share"]["chips_per_layer"] == 32 and "32 chips" in config["deployment"]
    assert len(config["assumed"]) >= 8 and all(isinstance(a, str) for a in config["assumed"])


def test_the_files_parameters_are_the_references_tree(config):
    import jax
    from reference import kimi_linear as ref
    leaves = jax.tree_util.tree_leaves(ref.param_shapes(config))
    assert sum(math.prod(x.shape) for x in leaves) == config["parameters"] == 602_434_432


# the manifest ----------------------------------------------------------------------------


def test_the_cells_entries_name_files_that_are_there():
    manifest = _read(REPO, "BENCHMARK.json")
    cell = [w for w in manifest["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "train_8k_b2", 1)
    entry = [c for c in manifest["configs"] if c["name"] == CONFIG][0]
    config = _read(REPO, entry["file"])
    assert entry["reduced"] == config["reduced"] and entry["source"] == config["source"]
    assert config["train"]["args"]["learning_rate"] == 1e-6
    workload = _read(BENCH, "workloads", CELL + ".json")
    assert workload["driver"] == "train_corpus_kda" and workload["loss_steps"] == 3
    assert os.path.exists(os.path.join(BENCH, "drivers", workload["driver"] + ".py"))
    assert os.path.exists(os.path.join(BENCH, "reference", config["reference"] + ".py"))
    flops = config["train"]["flops"]
    for key in ("per_example", "expert_per_row", "scan_per_example", "attention_per_example"):
        assert callable(getattr(counts, flops[key]))
    rate = [e for e in manifest["end_to_end"] if e["name"] == "train_examples_per_s"][0]
    assert CELL in rate["workloads"]
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
    root = str(tmp_path_factory.mktemp("kimi_root"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)

    def config(c):      # the widths cut, the share's layers kept: KDA x 3, MLA, KDA
        c.update(hidden_size=32, intermediate_size=48, moe_intermediate_size=24,
                 num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=16,
                 qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                 num_experts=4, num_experts_per_token=3, vocab_size=64)
        c["linear_attn_config"].update(num_heads=4, head_dim=8)
        c["published"]["num_experts"] = 16
        c["train"]["args"].update(bf16=False, learning_rate=3e-4)
        c["train"]["optimizer"].update(learning_rate=3e-4)      # a handful of tiny steps
    _edit(os.path.join(bench, "configs", CONFIG + ".json"), config)

    def traffic(t):
        t.update(batch=2, steps_per_epoch=4, test_examples=2, seq_len=48)
        t["trainer_args"].update(batch_size=2, eval_batch=2)
    _edit(os.path.join(bench, "traffic", "train_8k_b2.json"), traffic)
    return root


@pytest.fixture()
def run(tiny_root, monkeypatch):
    import harness
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm

    # chunks of 8 tokens in sub-blocks of 4, a state kept every 16: six chunks a sequence
    build = hybrid_lm.from_config
    monkeypatch.setattr(hybrid_lm, "from_config",
                        lambda *a, **kw: build(*a, **dict(kw, kda_tiling=(8, 4, 2))))

    def run_cell(*, seed=3200000032, seconds=1.0, trace=False, **kw):
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
    assert {"kimi_expert_load_imbalance", "kimi_expert_rows_share", "kimi_linear_train_mfu",
            "compile_cache_misses"} <= set(metrics)
    assert not any("roofline" in name for name in metrics)
    assert metrics["kimi_expert_load_imbalance"]["value"] >= 1.0
    # 4 of 16 experts held, 3 a token: 0.75 of the bound's 3 rows a token are expected
    assert 0.1 < metrics["kimi_expert_rows_share"]["value"] < 0.5


def test_control_is_not_correct(run, tiny_root):
    result, lines = run(seed=3200000041, control=True)
    got = _checks(lines)
    limits = _read(tiny_root, "benchmark", "workloads", CELL + ".json")["limits"]
    assert result["correct"] is False
    assert any(got[k] > limits[k] for k in ("loss_gap", "moment_norm_gap", "delta_norm_gap"))


def test_a_state_that_is_not_carried_is_not_correct(run, tiny_root, monkeypatch):
    """The scan restarts from a zero state at every chunk: the loss or the first
    gradient leaves the reference's."""
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    whole = hybrid_lm.kda.kda_scan

    def chunk_by_chunk(q, k, v, g, beta, *, chunk, **kw):
        cut = lambda x: x.reshape((-1, chunk) + x.shape[2:])
        return whole(*map(cut, (q, k, v, g, beta)), chunk=chunk, **kw).reshape(v.shape)

    monkeypatch.setattr(hybrid_lm.kda, "kda_scan", chunk_by_chunk)
    result, lines = run()
    got = _checks(lines)
    limits = _read(tiny_root, "benchmark", "workloads", CELL + ".json")["limits"]
    assert result["correct"] is False, lines
    assert any(got[k] > limits[k] for k in ("loss_gap", "moment_norm_gap"))
