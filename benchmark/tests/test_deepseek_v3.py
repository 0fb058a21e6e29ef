"""The ``train_corpus_mla`` driver and the ``kanana-2-30b-a3b-ep8`` configuration at a tiny
width on the CPU (float32), through everything of a run except the look for a chip; the
counts file against a hand count; the file's ``parameters`` against the reference's tree;
the cell's manifest entries (lists compared by membership, not by position: a later cell
appended to a shared metric's list does not fail them)."""

import json
import math
import os
import shutil
import time

import pytest
from test_drivers import _checks

import counts_deepseek_v3 as counts

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "kanana2_train_8k"
CONFIG = "kanana-2-30b-a3b-ep8"
OWN = ("mla_mixer_outside_kernels_ms", "mla_rotary_ms")
# accepted metrics the cell is appended to: the reducer and its parameters are the same, and
# the counts come from the configuration's own ``train.flops.module``
SHARED = ("kimi_linear_train_mfu", "kimi_linear_step_roofline_share",
          "kimi_expert_matmul_roofline_share", "kimi_expert_rows_share",
          "kimi_expert_load_imbalance", "mla_attention_roofline_share", "scope_named_share",
          "recompute_share", "moe_routing_ms", "head_loss_ms", "dense_ff_ms")


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
    """The widths cut: hidden 64, 4 heads of 16 + 8 / 16 over a latent of 32, 4 of 16
    experts of 32 held, 3 a token beside 2 shared, one dense layer and two expert layers."""
    c.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
             num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24, head_dim=8,
             v_head_dim=16, n_routed_experts=4, num_experts_per_tok=3, vocab_size=64,
             num_hidden_layers=3)
    c["published"].update(n_routed_experts=16)


# the counts ----------------------------------------------------------------------------


def test_forward_flops_by_part_are_the_issues_arithmetic(config):
    """MFLOP a token, forward, from the shapes: ISSUE 39's 1,093 at six layers (flash 46 %,
    projections 29 %), 3.28 GFLOP a token trained and 53.7 TFLOP a step of 2 x 8192."""
    six = dict(config, num_hidden_layers=6)
    parts = counts.forward_flops_per_token(six, 8192 / 2.0)
    mega = {k: round(v / 1e6, 1) for k, v in parts.items()}
    assert mega == {"mla_projections": 316.1, "mla_attention": 503.3, "dense_ff": 75.5,
                    "routers": 2.6, "shared_experts": 94.4, "experts": 35.4, "head": 65.7,
                    "total": 1093.0}
    assert round(100 * parts["mla_attention"] / parts["total"]) == 46
    assert round(100 * parts["mla_projections"] / parts["total"]) == 29
    per_example = counts.train_flops_per_example(six, 8192)
    parts = counts.forward_flops_per_token(six, (8192 + 1) / 2.0)
    assert per_example == pytest.approx(
        3 * (8192 * (parts["total"] - parts["head"]) + 8191 * parts["head"]))
    assert round(per_example / 8192 / 1e9, 2) == 3.28
    assert round(2 * per_example / 1e12, 1) == 53.7
    five = counts.train_flops_per_example(dict(config, num_hidden_layers=5), 8192)
    assert round(2 * five / 1e12, 1) == 45.7


def test_the_counts_at_the_small_size_are_a_hand_count():
    c = json.loads(json.dumps(_read(BENCH, "configs", CONFIG + ".json")))
    _tiny(c)
    parts = counts.forward_flops_per_token(c, 10.0)
    assert parts == {
        "mla_projections": 3 * 2.0 * (64 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 4 * 16 * 64),
        "mla_attention": 3 * 4 * 2.0 * (24 + 16) * 10.0,
        "dense_ff": 1 * 3 * 2.0 * 64 * 96,
        "routers": 2 * 2.0 * 64 * 16,
        "shared_experts": 2 * 3 * 2.0 * 64 * 64,
        "experts": 2 * (3 * 4 / 16) * 3 * 2.0 * 64 * 32,
        "head": 2.0 * 64 * 64,
        "total": sum(v for k, v in parts.items() if k != "total")}
    later = dict(c, share=dict(c["share"], first_layer=1))
    assert counts._layers(later) == {"mla": 3, "dense": 0, "experts": 3}


def test_the_attention_and_the_experts_are_counted_as_their_kernels_do_them(config):
    pairs = 8192 * 8193 // 2
    assert counts.mla_attention_train_flops_per_example(config, 8192) == \
        3 * config["num_hidden_layers"] * 32 * 2 * (192 + 128) * pairs
    assert counts.expert_train_flops_per_row(config) == 3 * 6 * 2048 * 768


def test_reduced_names_counts_and_no_width(config):
    """``reduced`` is layers, experts and ids held, each with its published value beside
    it; every width stands as published; the file says what it assumed and which
    deployment it is a share of."""
    assert set(config["reduced"]) == set(config["published"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert all(config[k] != config["published"][k] for k in config["reduced"])
    assert config["published"] == {"num_hidden_layers": 48, "n_routed_experts": 128,
                                   "vocab_size": 128256}
    published_widths = dict(
        hidden_size=2048, intermediate_size=6144, moe_intermediate_size=768, head_dim=64,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, qk_head_dim=192,
        v_head_dim=128, num_attention_heads=32, num_key_value_heads=32, num_experts_per_tok=6,
        n_shared_experts=2, routed_scaling_factor=2.448, first_k_dense_replace=1,
        rope_theta=1000000, rope_interleave=True, q_lora_rank=None, rope_scaling=None)
    assert {k: config[k] for k in published_widths} == published_widths
    assert config["share"]["chips_per_layer"] == 8 and "8 chips" in config["deployment"]
    assert (config["share"]["first_layer"], config["share"]["first_expert"]) == (0, 0)
    assert len(config["assumed"]) >= 7 and all(isinstance(a, str) for a in config["assumed"])


def test_the_files_parameters_are_the_references_tree(config):
    import jax
    from reference import deepseek_v3 as ref
    leaves = jax.tree_util.tree_leaves(ref.param_shapes(config))
    assert sum(math.prod(x.shape) for x in leaves) == config["parameters"] == \
        {6: 687_502_976, 5: 575_955_968}[config["num_hidden_layers"]]


def test_the_reference_ties_itself_to_no_other_models_reference():
    with open(os.path.join(BENCH, "reference", "deepseek_v3.py")) as fh:
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
    assert workload["driver"] == "train_corpus_mla" and workload["loss_steps"] == 3
    assert os.path.exists(os.path.join(BENCH, "drivers", workload["driver"] + ".py"))
    assert os.path.exists(os.path.join(BENCH, "reference", config["reference"] + ".py"))
    flops = config["train"]["flops"]
    assert flops["module"] == "counts_deepseek_v3"
    for key in ("per_example", "expert_per_row", "attention_per_example"):
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
        assert (metric["workloads"] == [CELL]) == (name in OWN)
    shares = [n for n in listed if "mfu" in n or "roofline" in n]
    assert sorted(shares) == ["kimi_expert_matmul_roofline_share",
                              "kimi_linear_step_roofline_share", "kimi_linear_train_mfu",
                              "mla_attention_roofline_share"]


# the driver, tiny, on the CPU ------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("kanana_root"))
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
def run(tiny_root):
    import harness

    def run_cell(*, seed=3900000039, seconds=1.0, trace=False, **kw):
        lines = []
        result = harness.run_cell(tiny_root, CELL, seed=seed, seconds=seconds, trace=trace,
                                  t_process=time.perf_counter(), require_chip=False,
                                  out=lines.append, **kw)
        return result, lines

    return run_cell


def test_the_drivers_model_view_names_the_first_expert_layer(tiny_root):
    import harness
    from reference import deepseek_v3 as ref
    view = {k: v for k, v in _read(tiny_root, "benchmark", "configs", CONFIG + ".json").items()
            if k not in ("train", "model")}
    assert ref.sparse(view) == [False, True, True] and ref.sparse(view).index(True) == 1
    assert view["num_experts_per_tok"] == 3 and "num_dense_layers" not in view
    driver = harness.load_module(os.path.join(BENCH, "drivers", "train_corpus_mla.py"),
                                 "bench_driver_train_corpus_mla_test")
    assert driver.corpus._model_view(_read(BENCH, "configs", CONFIG + ".json")).keys() >= \
        {"first_k_dense_replace", "n_routed_experts", "share", "published"}


@pytest.mark.parametrize("cell, calls", [({"warmup_epochs": 4}, 3), ({"warmup_epochs": 1}, 0)],
                         ids=["four-epochs", "the-train-drivers-one"])
def test_the_warm_up_runs_the_timed_program_as_often_as_the_cell_says(monkeypatch, cell, calls):
    """The first call is the checked one, as ``FrugalSeam`` makes it; the others follow it on
    the same arguments, and the last one's state and losses go back to the trainer."""
    import types

    import harness
    driver = harness.load_module(os.path.join(BENCH, "drivers", "train_corpus_mla.py"),
                                 "bench_driver_train_corpus_mla_warm")
    seen = []
    monkeypatch.setattr(driver.corpus.FrugalSeam, "_first_call",
                        lambda self, state, rest: (seen.append("checked") or state + 1, "out"))
    timed = lambda state, *rest: (seen.append(rest) or state + 1, f"out after {state}")
    seam = driver.WarmSeam(timed, None, types.SimpleNamespace(cell=cell), (8, 2))
    rest = ("tokens", "zeros", "plan", "rng")
    assert seam._first_call(0, rest) == (1 + calls, f"out after {calls}" if calls else "out")
    assert seen == ["checked"] + [rest] * calls
    assert _read(BENCH, "workloads", CELL + ".json")["warmup_epochs"] == 20


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
    result, lines = run(seed=3900000041, control=True)
    got = _checks(lines)
    limits = _read(tiny_root, "benchmark", "workloads", CELL + ".json")["limits"]
    assert result["correct"] is False
    assert any(got[k] > limits[k] for k in ("loss_gap", "moment_norm_gap", "delta_norm_gap"))


@pytest.mark.parametrize("fault", ["rotation left out", "half-split pairing"])
def test_a_program_that_does_not_rotate_as_the_file_says_is_not_correct(run, tiny_root, fault,
                                                                         monkeypatch):
    """The error this configuration exists to catch: the shared key and the queries' last
    channels carried as they are, or turned in the other pairing."""
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    build = hybrid_lm.from_config
    wrong = {"rope_theta": None} if fault == "rotation left out" else {"rope_interleave": False}

    def faulty(*a, **kw):
        import dataclasses
        return dataclasses.replace(build(*a, **kw), **wrong)

    monkeypatch.setattr(hybrid_lm, "from_config", faulty)
    result, lines = run()
    got = _checks(lines)
    limits = _read(tiny_root, "benchmark", "workloads", CELL + ".json")["limits"]
    assert result["correct"] is False, lines
    assert any(got[k] > limits[k] for k in ("loss_gap", "moment_norm_gap"))


def test_a_program_from_before_the_family_is_refused_at_once(run, monkeypatch):
    import harness
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    monkeypatch.setattr(hybrid_lm, "_FAMILIES", {k: v for k, v in hybrid_lm._FAMILIES.items()
                                                 if k != "deepseek_v3"})
    t0 = time.perf_counter()
    with pytest.raises(harness.Refused, match="deepseek_v3"):
        run()
    assert time.perf_counter() - t0 < 5.0
