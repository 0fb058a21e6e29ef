"""The ``train_corpus_gdn`` driver and the ``qwen3-next-80b-a3b-ep16`` configuration at a tiny
width on the CPU (float32), through everything of a run except the look for a chip; the
counts file against a hand count; the file's ``parameters`` against the reference's tree;
the cell's manifest entries (lists compared by membership, not by position: a later cell
appended to a shared metric's list does not fail them)."""

import dataclasses
import json
import math
import os
import shutil
import time

import pytest
from test_drivers import _checks

import counts_qwen3_next as counts

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "qwen3_next_train_8k"
CONFIG = "qwen3-next-80b-a3b-ep16"
OWN = ("gdn_scan_roofline_share", "gated_attention_roofline_share",
       "gdn_mixer_outside_kernels_ms")
# accepted metrics the cell is appended to: the reducer and its parameters are the same, and
# the counts come from the configuration's own ``train.flops.module``
SHARED = ("kimi_linear_train_mfu", "kimi_linear_step_roofline_share",
          "kimi_expert_matmul_roofline_share", "kimi_expert_rows_share",
          "kimi_expert_load_imbalance", "scope_named_share", "recompute_share",
          "moe_routing_ms", "head_loss_ms")


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


def _tiny(c):
    """The widths cut: hidden 64, 4 query heads on 2 key/value heads of 16 (4 channels
    rotated), 4 delta value heads on 2 key heads of 8, 4 of 16 experts of 32 held, 3 a token
    beside a gated shared one; one period."""
    c.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
             shared_expert_intermediate_size=32, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
             linear_key_head_dim=8, linear_value_head_dim=8, num_experts=4,
             num_experts_per_tok=3, vocab_size=64)
    c["published"].update(num_experts=16)


# the counts ----------------------------------------------------------------------------


def test_forward_flops_by_part_are_the_issues_arithmetic(config):
    """MFLOP a token, forward, from the shapes: ISSUE 43's 466 (465 here: the scan's scores
    are counted once a key head), 1.40 GFLOP a token trained and 22.9 TFLOP a step of
    2 x 8192; the mixers 73 %, the head 17 %, the held experts 3.4 %."""
    parts = counts.forward_flops_per_token(config, 8192 / 2.0)
    mega = {k: round(v / 1e6, 1) for k, v in parts.items()}
    assert mega == {"gdn_projections": 202.1, "gdn_scan": 14.2, "attention_projections": 54.5,
                    "attention": 67.1, "routers": 8.4, "shared_expert": 25.2, "experts": 15.7,
                    "head": 77.8, "total": 465.0}
    mixers = sum(parts[k] for k in ("gdn_projections", "gdn_scan", "attention_projections",
                                    "attention"))
    assert round(100 * mixers / parts["total"]) == 73
    assert round(100 * parts["head"] / parts["total"]) == 17
    assert round(100 * parts["experts"] / parts["total"], 1) == 3.4
    per_example = counts.train_flops_per_example(config, 8192)
    parts = counts.forward_flops_per_token(config, (8192 + 1) / 2.0)
    assert per_example == pytest.approx(
        3 * (8192 * (parts["total"] - parts["head"]) + 8191 * parts["head"]))
    assert round(per_example / 8192 / 1e9, 2) == 1.39
    assert round(2 * per_example / 1e12, 1) == 22.9


def test_the_counts_at_the_small_size_are_a_hand_count():
    c = json.loads(json.dumps(_read(BENCH, "configs", CONFIG + ".json")))
    _tiny(c)
    parts = counts.forward_flops_per_token(c, 10.0)
    scan = (2 * 4.0 * 64 * 64 * 8 + 4 * (64 * 64 * (8 + 3.0 * 8) + 6.0 * 64 * 8 * 8)) / 64
    assert parts == {
        "gdn_projections": 3 * 2.0 * (64 * (16 + 16 + 32 + 32) + 64 * 8 + 32 * 64),
        "gdn_scan": 3 * scan,
        "attention_projections": 1 * 2.0 * (64 * 2 * 4 * 16 + 2 * 64 * 2 * 16 + 4 * 16 * 64),
        "attention": 1 * 4 * 2.0 * (16 + 16) * 10.0,
        "routers": 4 * 2.0 * 64 * 16,
        "shared_expert": 4 * (3 * 2.0 * 64 * 32 + 2.0 * 64),
        "experts": 4 * (3 * 4 / 16) * 3 * 2.0 * 64 * 32,
        "head": 2.0 * 64 * 64,
        "total": sum(v for k, v in parts.items() if k != "total")}
    later = dict(c, num_hidden_layers=5, share=dict(c["share"], first_layer=4))
    assert counts._layers(later) == {"gdn": 4, "attention": 1, "experts": 5}


def test_the_scan_and_the_attention_are_counted_as_their_algorithms_do_them(config):
    pairs = 8192 * 8193 // 2
    assert counts.attention_train_flops_per_example(config, 8192) == \
        3 * 1 * 16 * 2 * (256 + 256) * pairs
    a_chunk = 16 * 4 * 64 * 64 * 128 + 32 * (64 * 64 * (128 + 3 * 128) + 6 * 64 * 128 * 128)
    assert counts.gdn_scan_train_flops_per_example(config, 8192) == \
        3 * 3 * (8192 // 64) * a_chunk
    assert counts.expert_train_flops_per_row(config) == 3 * 6 * 2048 * 512


def test_reduced_names_counts_and_no_width(config):
    """``reduced`` is layers, experts and ids held, each with its published value beside
    it; every width stands as published; the file says what it assumed and which
    deployment it is a share of."""
    assert set(config["reduced"]) == set(config["published"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert all(config[k] != config["published"][k] for k in config["reduced"])
    assert config["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                   "vocab_size": 151936}
    published_widths = dict(
        hidden_size=2048, intermediate_size=5120, moe_intermediate_size=512,
        shared_expert_intermediate_size=512, head_dim=256, num_attention_heads=16,
        num_key_value_heads=2, linear_num_key_heads=16, linear_num_value_heads=32,
        linear_key_head_dim=128, linear_value_head_dim=128, linear_conv_kernel_dim=4,
        num_experts_per_tok=10, full_attention_interval=4, partial_rotary_factor=0.25,
        rope_theta=10000000, rms_norm_eps=1e-06, norm_topk_prob=True, rope_scaling=None)
    assert {k: config[k] for k in published_widths} == published_widths
    assert config["share"]["chips_per_layer"] == 16 and "16 chips" in config["deployment"]
    assert (config["share"]["first_layer"], config["share"]["first_expert"]) == (0, 0)
    assert len(config["assumed"]) >= 10 and all(isinstance(a, str) for a in config["assumed"])


def test_the_files_parameters_are_the_references_tree(config):
    import jax
    from reference import qwen3_next as ref
    leaves = jax.tree_util.tree_leaves(ref.param_shapes(config))
    assert sum(math.prod(x.shape) for x in leaves) == config["parameters"] == 625_667_136


def test_the_reference_ties_itself_to_no_other_models_reference():
    with open(os.path.join(BENCH, "reference", "qwen3_next.py")) as fh:
        imports = [line for line in fh if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations\n", "import jax\n",
                       "import jax.numpy as jnp\n", "from . import precision as prec\n"]


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
    assert workload["driver"] == "train_corpus_gdn" and workload["loss_steps"] == 3
    assert "warmup_epochs" not in workload
    assert os.path.exists(os.path.join(BENCH, "drivers", workload["driver"] + ".py"))
    assert os.path.exists(os.path.join(BENCH, "reference", config["reference"] + ".py"))
    flops = config["train"]["flops"]
    assert flops["module"] == "counts_qwen3_next"
    for key in ("per_example", "expert_per_row", "scan_per_example", "attention_per_example"):
        assert callable(getattr(counts, flops[key]))
    rate = [e for e in manifest["end_to_end"] if e["name"] == "train_examples_per_s"][0]
    assert CELL in rate["workloads"]
    listed = {m["name"]: m for m in manifest["per_layer"] if CELL in m.get("workloads", [])}
    assert set(listed) == set(OWN) | set(SHARED)
    for name, metric in listed.items():
        spec = _read(BENCH, "layer_metrics", name + ".json")
        assert (spec["layer"], spec["unit"]) == (metric["layer"], metric["unit"])
        assert os.path.exists(os.path.join(BENCH, "reducers", spec["reducer"] + ".py"))
        assert metric["moves"] == "train_examples_per_s"
        # by membership: a later cell appended to an own metric's list does not fail this
        assert CELL in metric["workloads"]
        assert (metric["workloads"][0] == CELL) == (name in OWN)
    shares = [n for n in listed if "mfu" in n or "roofline" in n]
    assert sorted(shares) == ["gated_attention_roofline_share", "gdn_scan_roofline_share",
                              "kimi_expert_matmul_roofline_share",
                              "kimi_linear_step_roofline_share", "kimi_linear_train_mfu"]
    scan = _read(BENCH, "layer_metrics", "gdn_scan_roofline_share.json")["params"]
    assert scan == {"ops": ["gdn_fwd", "gdn_bwd"], "work": "gdn_scan_train_flops",
                    "peak": "flops_per_s"}


# the driver, tiny, on the CPU ------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("qwen3_next_root"))
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
    _edit(os.path.join(bench, "traffic", "train_8k_b2.json"), traffic)
    return root


@pytest.fixture()
def run(tiny_root, monkeypatch):
    import harness
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm

    # chunks of 8 tokens, inverses in sub-blocks of 4, a state kept every 16: six chunks
    build = hybrid_lm.from_config
    monkeypatch.setattr(hybrid_lm, "from_config",
                        lambda *a, **kw: build(*a, **dict(kw, kda_tiling=(8, 4, 2))))

    def run_cell(*, seed=4300000043, seconds=1.0, trace=False, **kw):
        lines = []
        result = harness.run_cell(tiny_root, CELL, seed=seed, seconds=seconds, trace=trace,
                                  t_process=time.perf_counter(), require_chip=False,
                                  out=lines.append, **kw)
        return result, lines

    return run_cell


def test_the_drivers_model_view_names_the_first_expert_layer(tiny_root):
    from reference import qwen3_next as ref
    view = {k: v for k, v in _read(tiny_root, "benchmark", "configs", CONFIG + ".json").items()
            if k not in ("train", "model")}
    assert ref.sparse(view) == [True] * 4 and ref.sparse(view).index(True) == 0
    assert view["num_experts_per_tok"] == 3 and "num_dense_layers" not in view


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
    assert not any("roofline" in name or name.endswith("_ms") for name in metrics)
    assert metrics["kimi_expert_load_imbalance"]["value"] >= 1.0
    # 4 of 16 experts held, 3 a token: 0.75 of the bound's 3 rows a token are expected
    assert 0.1 < metrics["kimi_expert_rows_share"]["value"] < 0.5


def test_control_is_not_correct(run, tiny_root):
    result, lines = run(seed=4300000045, control=True)
    got = _checks(lines)
    limits = _read(tiny_root, "benchmark", "workloads", CELL + ".json")["limits"]
    assert result["correct"] is False
    assert any(got[k] > limits[k] for k in ("loss_gap", "moment_norm_gap", "delta_norm_gap"))


@pytest.mark.parametrize("fault", ["rotation over the whole head", "beta dropped"])
def test_a_program_that_does_not_compute_the_files_mixers_is_not_correct(
        run, tiny_root, fault, monkeypatch):
    """Two of the errors this configuration exists to catch, through the whole run: all 16
    of a head's channels turned where 4 are due (the planted fault of the chip runs), and a
    delta rule whose correction is not scaled by beta."""
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    if fault.startswith("rotation"):
        build = hybrid_lm.from_config    # the fixture's, with the tiling
        monkeypatch.setattr(hybrid_lm, "from_config", lambda *a, **kw: dataclasses.replace(
            build(*a, **kw), rope_dim=None))
    else:
        import jax.numpy as jnp
        scan = hybrid_lm.kda.gdn_scan
        monkeypatch.setattr(hybrid_lm.kda, "gdn_scan", lambda q, k, v, g, beta, **kw: scan(
            q, k, v, g, jnp.ones_like(beta), **kw))
    result, lines = run()
    got = _checks(lines)
    limits = _read(tiny_root, "benchmark", "workloads", CELL + ".json")["limits"]
    assert result["correct"] is False, lines
    assert any(got[k] > limits[k] for k in ("loss_gap", "moment_norm_gap", "delta_norm_gap"))


def test_a_program_from_before_the_family_is_refused_at_once(run, monkeypatch):
    import harness
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    monkeypatch.setattr(hybrid_lm, "_FAMILIES", {k: v for k, v in hybrid_lm._FAMILIES.items()
                                                 if k != "qwen3_next"})
    t0 = time.perf_counter()
    with pytest.raises(harness.Refused, match="qwen3_next"):
        run()
    assert time.perf_counter() - t0 < 5.0
