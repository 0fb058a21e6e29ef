"""The ``train_corpus`` driver and the ``lfm2-24b-a2b-ep8`` configuration at a tiny
width on the CPU (float32), through everything of a run except the look for a chip:
the sound run is correct and reports the expert layer's counters; the control and
planted faults come out not correct, by the cell's own limits."""

import json
import os
import shutil
import sys
import time

import pytest
from test_drivers import _broken_trainer, _checks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "lfm2_moe_train_8k"
CONFIG = "lfm2-24b-a2b-ep8"


def _edit(path, fn):
    with open(path) as fh:
        obj = json.load(fh)
    fn(obj)
    with open(path, "w") as fh:
        json.dump(obj, fh)


@pytest.fixture(scope="module")
def tiny_lfm2_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("lfm2_root"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)

    def config(c):      # the widths cut, the share and the pattern kept
        c.update(hidden_size=32, intermediate_size=48, moe_intermediate_size=24,
                 num_attention_heads=4, num_key_value_heads=2, vocab_size=64,
                 num_experts=4)
        c["published"]["num_experts"] = 16
        c["train"]["args"].update(bf16=False, learning_rate=3e-4)
        c["train"]["optimizer"].update(learning_rate=3e-4)      # a handful of tiny steps
    _edit(os.path.join(bench, "configs", CONFIG + ".json"), config)

    def traffic(t):
        t.update(batch=2, steps_per_epoch=4, test_examples=2, seq_len=48)
        t["trainer_args"].update(batch_size=2, eval_batch=2)
    _edit(os.path.join(bench, "traffic", "train_8k_b4.json"), traffic)
    return root


@pytest.fixture()
def run(tiny_lfm2_root):
    import harness

    def run_cell(*, seed=3000000011, seconds=1.0, root=None, trace=False, **kw):
        lines = []
        result = harness.run_cell(root or tiny_lfm2_root, CELL, seed=seed,
                                  seconds=seconds, trace=trace,
                                  t_process=time.perf_counter(), require_chip=False,
                                  out=lines.append, **kw)
        return result, lines

    return run_cell


def _limits(root):
    with open(os.path.join(root, "benchmark", "workloads", CELL + ".json")) as fh:
        return json.load(fh)["limits"]


def test_sound_run_is_correct_and_two_seeds_differ(run, capsys):
    result, lines = run()
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["train_examples_per_s"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    assert json.loads(lines[-1]) == result
    got = _checks(lines)
    assert got["window_compiles"] == 0.0
    assert max(got[k] for k in ("loss_gap", "moment_norm_gap", "delta_norm_gap")) < 1e-3
    assert "routing: 0.000 %" in capsys.readouterr().out      # float32 on both sides
    other, _ = run(seed=3000000012)
    assert other["correct"] is True


def test_traced_run_reports_the_expert_layer_counters(run):
    """The CPU has no device plane: the readers of the device trace find nothing
    and leave their metric out; the counters and the host-clock utilisation are
    there."""
    result, lines = run(seconds=2.0, trace=True)
    assert result["correct"] is True, lines
    metrics = result["metrics"]
    assert {"expert_load_imbalance", "expert_rows_share", "moe_train_mfu",
            "compile_cache_misses"} <= set(metrics)
    assert not any("roofline" in name for name in metrics)
    assert metrics["expert_load_imbalance"]["value"] >= 1.0
    # 4 of 16 experts held, 4 a token: a quarter of the bound is expected here
    assert 0.1 < metrics["expert_rows_share"]["value"] < 0.45


def test_control_is_not_correct(run, tiny_lfm2_root):
    result, lines = run(seed=3000000021, control=True)
    got, limits = _checks(lines), _limits(tiny_lfm2_root)
    assert result["correct"] is False
    assert any(got[k] > limits[k] for k in ("loss_gap", "moment_norm_gap", "delta_norm_gap"))


def test_half_the_batch_left_out_is_not_correct(run, tiny_lfm2_root, monkeypatch):
    def wrap(compiled):
        def half(state, tokens, zeros, plan, *rest):
            import jax
            b = plan.shape[1]
            cut = plan.at[:, b // 2:].set(plan[:, :b // 2])
            return compiled(state, tokens, zeros, jax.device_put(cut, plan.sharding), *rest)
        return half

    _broken_trainer(monkeypatch, wrap)
    result, lines = run()
    got, limits = _checks(lines), _limits(tiny_lfm2_root)
    assert result["correct"] is False, lines
    # near its seeded start this model's loss is ln(vocab) whatever the rows, so the
    # reference's losses stay close; the one-row program's, on the plan's rows, do not
    assert got["one_row_loss_gap"] > limits["one_row_loss_gap"]


def test_clip_dropped_is_not_correct(run, tiny_lfm2_root, tmp_path):
    root = str(tmp_path / "root")
    shutil.copytree(tiny_lfm2_root, root)
    _edit(os.path.join(root, "benchmark", "configs", CONFIG + ".json"),
          lambda c: c["train"]["args"].update(clip_grad_norm=0.0))
    result, lines = run(root=root)
    assert result["correct"] is False, lines
    assert _checks(lines)["moment_norm_gap"] > _limits(tiny_lfm2_root)["moment_norm_gap"]


def test_three_of_a_tokens_held_experts_is_not_correct(run, tiny_lfm2_root, monkeypatch):
    """The program computes only the first three of each token's selected experts
    (the fourth's assignment goes nowhere): the loss or the parameters' change
    leaves the reference's."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import moe
    original = moe.held_experts_ffn

    def three(x, weights, experts, *args, **kw):
        return original(x, weights, experts.at[:, -1].set(-1), *args, **kw)

    monkeypatch.setattr(moe, "held_experts_ffn", three)
    result, lines = run()
    got, limits = _checks(lines), _limits(tiny_lfm2_root)
    assert result["correct"] is False, lines
    assert got["loss_gap"] > limits["loss_gap"] or got["delta_norm_gap"] > limits["delta_norm_gap"]


def test_the_program_without_the_configuration_refuses(run, monkeypatch):
    """What the parent commit is: a trainer with no ``model_config``. One line."""
    import dataclasses

    import harness
    from csed_514_project_distributed_training_using_pytorch_tpu.utils import config as C
    fields = [(f.name, f.type, f) for f in dataclasses.fields(C.LMConfig)
              if f.name != "model_config"]
    monkeypatch.setattr(C, "LMConfig", dataclasses.make_dataclass("LMConfig", fields))
    with pytest.raises(harness.Refused, match="model_config"):
        run()
