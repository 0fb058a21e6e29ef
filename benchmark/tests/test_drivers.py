"""Each driver at a tiny size on the CPU, through everything of a run except
the look for a chip; the control and a broken timed path come out not correct;
new files are discovered with no edit to an existing one."""

import json
import os
import shutil

import pytest


def _checks(lines):
    out = {}
    for line in lines:
        if line.startswith("check "):
            name, rest = line[len("check "):].split(": ", 1)
            out[name] = float(rest.split(" ")[0])
    return out


@pytest.mark.parametrize("cell,metric", [
    ("lm_train_b16", "train_examples_per_s"),
])
def test_driver_runs_and_is_correct(run_cell, cell, metric):
    result, lines = run_cell(cell)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"][metric]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert json.loads(lines[-1]) == result           # the last line is the result
    assert _checks(lines)["window_compiles"] == 0.0


@pytest.mark.parametrize("cell,metrics", [
    ("lm_train_b16", {"epoch_gap_ms", "execute_examples_per_s", "compile_cache_misses"}),
])
def test_traced_run_reports_what_needs_no_device(run_cell, cell, metrics):
    """The ``--trace 1`` path end to end; the CPU has no device plane, so the
    readers of the device trace find nothing and are left out of the line."""
    result, lines = run_cell(cell, seconds=4.0, trace=True)
    assert result["correct"] is True, lines
    assert metrics <= set(result["metrics"])
    assert not any("roofline" in name for name in result["metrics"])
    assert "busy_s" not in result["device"] and "breakdown" not in result


@pytest.mark.parametrize("cell,number", [
    ("lm_train_b16", "moment_norm_gap"),
])
def test_control_is_not_correct(run_cell, tiny_root, cell, number):
    """The precision below the stated one, in the program's place: fp8 matmul
    operands for the bf16 trainer. Limits are the cell's own."""
    limit = _limits(tiny_root, cell)[number]
    _, sound = run_cell(cell, seed=3000000021)
    result, lines = run_cell(cell, seed=3000000021, control=True)
    assert _checks(sound)[number] <= limit
    assert _checks(lines)[number] > limit, lines
    assert result["correct"] is False


def _limits(root, cell):
    with open(os.path.join(root, "benchmark", "workloads", cell + ".json")) as fh:
        return json.load(fh)["limits"]


def _broken_trainer(monkeypatch, wrap):
    """The timed path broken underneath the driver's seam: ``wrap(compiled)``
    stands where the trainer's compiled epoch program stands."""
    from csed_514_project_distributed_training_using_pytorch_tpu.utils import telemetry as T
    original = T.aot_compile

    def broken(jit_fn, *args):
        compiled, aot = original(jit_fn, *args)
        return wrap(compiled), aot

    monkeypatch.setattr(T, "aot_compile", broken)


def test_step_that_returns_its_state_unchanged_is_not_correct(run_cell, monkeypatch):
    def wrap(compiled):
        def identity_step(state, *rest):
            import jax
            keep = jax.tree_util.tree_map(lambda x: x + 0, state)
            _, out = compiled(state, *rest)
            return keep, out
        return identity_step

    _broken_trainer(monkeypatch, wrap)
    result, lines = run_cell("lm_train_b16")
    assert result["correct"] is False, lines
    assert _checks(lines)["state_unmoved"] == float("inf")


def test_half_the_batch_left_out_is_not_correct(run_cell, tiny_root, monkeypatch):
    """The timed epoch program trains on half of each batch's rows (the other
    half repeats them): its losses leave the reference's and the one-row
    program's, which both follow the trainer's own plan."""
    def wrap(compiled):
        def half(state, tokens, zeros, plan, *rest):
            import jax
            b = plan.shape[1]
            cut = plan.at[:, b // 2:].set(plan[:, :b // 2])
            return compiled(state, tokens, zeros, jax.device_put(cut, plan.sharding), *rest)
        return half

    _broken_trainer(monkeypatch, wrap)
    result, lines = run_cell("lm_train_b16")
    got, limits = _checks(lines), _limits(tiny_root, "lm_train_b16")
    assert result["correct"] is False, lines
    assert got["loss_gap"] > limits["loss_gap"]
    assert got["one_row_loss_gap"] > limits["one_row_loss_gap"]


@pytest.mark.parametrize("fault,number", [
    ({"learning_rate": 0.0003 * 1.05}, "delta_norm_gap"),   # a rate 5 % off
    ({"clip_grad_norm": 0.0}, "moment_norm_gap"),           # the clip dropped
])
def test_a_mild_training_fault_is_not_correct(run_cell, tiny_root, tmp_path, fault, number):
    """The trainer is given another recipe than the configuration states (and
    the reference follows). Limits are the cell's own."""
    root = str(tmp_path / "root")
    shutil.copytree(tiny_root, root)
    path = os.path.join(root, "benchmark", "configs", "pixel-lm-d1024.json")
    with open(path) as fh:
        config = json.load(fh)
    config["train"]["args"].update(fault)
    with open(path, "w") as fh:
        json.dump(config, fh)
    result, lines = run_cell("lm_train_b16", root=root)
    assert result["correct"] is False, lines
    assert _checks(lines)[number] > _limits(tiny_root, "lm_train_b16")[number]


def test_new_files_are_discovered_without_editing_any(tiny_root, tmp_path, run_cell):
    """A configuration, a cell (with its traffic) and a per-layer metric that
    reuse a driver and a reducer are new files plus manifest entries only."""
    root = str(tmp_path / "root")
    shutil.copytree(tiny_root, root)
    bench = os.path.join(root, "benchmark")
    before = {}
    for folder, _, files in os.walk(bench):
        for f in files:
            p = os.path.join(folder, f)
            before[p] = open(p, "rb").read()

    def clone(kind, old, new, edit):
        with open(os.path.join(bench, kind, old + ".json")) as fh:
            obj = json.load(fh)
        edit(obj)
        with open(os.path.join(bench, kind, new + ".json"), "w") as fh:
            json.dump(obj, fh)

    def narrower(c):
        c["name"] = "pixel-lm-narrow"
        for group in (c["model"], c["train"]["args"]):
            group["embed_dim"] = 16
    clone("configs", "pixel-lm-d1024", "pixel-lm-narrow", narrower)
    clone("traffic", "train_b16", "train_b8", lambda t: (
        t.update(batch=8), t["trainer_args"].update(batch_size=8)))
    clone("workloads", "lm_train_b16", "narrow_train_b8", lambda w: None)
    clone("layer_metrics", "epoch_gap_ms", "epoch_data_ms", lambda m: m.update(
        params={"plus": ["data_s"], "scale": 1000.0}))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    manifest["configs"].append({"name": "pixel-lm-narrow", "source": "test",
                                "file": "benchmark/configs/pixel-lm-narrow.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "narrow_train_b8", "config": "pixel-lm-narrow",
                                  "traffic": "train_b8", "chips": 1, "why": "test"})
    manifest["end_to_end"][0]["workloads"].append("narrow_train_b8")
    manifest["per_layer"].append({"name": "epoch_data_ms", "unit": "ms", "better": "lower",
                                  "source": "program_span", "layer": "trainer loop",
                                  "moves": "train_examples_per_s",
                                  "workloads": ["narrow_train_b8"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(manifest, fh)
    result, lines = run_cell("narrow_train_b8", root=root)
    assert result["correct"] is True, lines
    # the new per-layer metric is read by the existing reducer, in a traced-style pass
    import harness
    obs = harness.Observations(epochs=[{"data_s": 0.002, "wall_s": 1.0}])
    got = harness.layer_metrics(manifest, bench, "narrow_train_b8", obs)
    assert got["epoch_data_ms"] == {"value": 2.0, "unit": "ms"}
    assert "epoch_gap_ms" not in got            # listed for another cell only
    for p, content in before.items():
        assert open(p, "rb").read() == content, f"{p} was edited"
