"""Structured run telemetry (utils/telemetry.py, train/step.py health carry,
tools/telemetry_report.py): tier-1 CPU coverage.

- every emitted event must be strict JSONL (``json.loads`` per line, typed by
  ``"event"``), atomically written, process-0 gated;
- the health-stats-enabled scanned epoch must produce BITWISE-identical params to
  the unmetered epoch, and the flag-off path must add zero ops to the step body;
- a tiny end-to-end single-trainer run must produce the acceptance schema
  (manifest + epoch events with compile_s/execute_s/examples_per_s/flops_per_step,
  health events with grad_norm);
- the report CLI must render one-run and A-vs-B summaries without error.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from csed_514_project_distributed_training_using_pytorch_tpu.data.mnist import (
    Dataset, _normalize, _synthesize_split,
)
from csed_514_project_distributed_training_using_pytorch_tpu.models.cnn import Net
from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
    create_train_state, make_epoch_fn, make_train_step,
)
from csed_514_project_distributed_training_using_pytorch_tpu.utils import (
    metrics as M,
    telemetry as T,
)

_REPO = os.path.join(os.path.dirname(__file__), os.pardir)


# ---------------------------------------------------------------- writer/schema


def test_writer_emits_valid_typed_jsonl_atomically(tmp_path):
    path = str(tmp_path / "run.jsonl")
    w = T.TelemetryWriter(path)
    w.emit({"event": "manifest", "devices": 1})
    w.emit({"event": "epoch", "epoch": 1, "loss": float("nan"),
            "nested": {"inf": float("inf"), "xs": [1.0, float("-inf")]}})
    rows = [json.loads(line) for line in open(path)]
    assert [r["event"] for r in rows] == ["manifest", "epoch"]
    assert all("t_s" in r for r in rows)
    # Strict-JSONL rule: non-finite floats become null, recursively.
    assert rows[1]["loss"] is None
    assert rows[1]["nested"]["inf"] is None
    assert rows[1]["nested"]["xs"] == [1.0, None]
    # Atomic write: no .tmp residue next to the artifact.
    assert not os.path.exists(path + ".tmp")


def test_writer_requires_event_type_and_gates_to_process0(tmp_path, monkeypatch):
    path = str(tmp_path / "run.jsonl")
    with pytest.raises(ValueError, match="event"):
        T.TelemetryWriter(path).emit({"epoch": 1})
    # Empty path disables everything.
    T.TelemetryWriter("").emit({"event": "epoch"})
    # Non-zero processes write nothing (one file per fleet).
    monkeypatch.setattr(M, "is_logging_process", lambda: False)
    w = T.TelemetryWriter(path)
    assert not w.enabled
    w.emit({"event": "manifest"})
    assert not os.path.exists(path)


def test_manifest_event_schema():
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import (
        SingleProcessConfig,
    )

    ev = T.manifest_event(SingleProcessConfig(bf16=True), run_type="single")
    assert ev["event"] == "manifest" and ev["run_type"] == "single"
    for key in ("schema_version", "platform", "device_kind", "device_count",
                "process_count", "jax_version", "jaxlib_version",
                "python_version", "config", "precision"):
        assert key in ev, key
    assert ev["precision"]["bf16"] is True
    assert ev["config"]["n_epochs"] == 3
    json.dumps(ev, allow_nan=False)          # fully serializable as strict JSON

    from csed_514_project_distributed_training_using_pytorch_tpu.parallel.mesh import (
        make_mesh,
    )

    ev = T.manifest_event(mesh=make_mesh(8))
    assert ev["mesh"]["shape"] == {"data": 8}
    assert ev["mesh"]["axis_names"] == ["data"]


def test_estimate_mfu():
    est = T.estimate_mfu(1e9, 0.001)
    # cost_analysis FLOPs are the per-device module's share — the rate is per chip.
    assert est["achieved_flops_per_s_per_device"] == pytest.approx(1e12)
    # CPU platform: peak unknown — mfu must be None, never a guess.
    assert est["peak_flops_per_s_per_device"] is None and est["mfu"] is None
    assert T.estimate_mfu(None, 0.1)["achieved_flops_per_s_per_device"] is None
    ev = T.mfu_event(1e9, 0.001)
    assert ev["event"] == "mfu"


def test_aot_compile_times_and_prices_a_jit_program():
    fn = jax.jit(lambda x: (x @ x).sum())
    compiled, aot = T.aot_compile(fn, jnp.ones((64, 64), jnp.float32))
    assert compiled is not None
    assert aot["compile_s"] > 0 and aot["lower_s"] > 0
    assert aot["flops"] and aot["flops"] > 2 * 64 * 64 * 64 * 0.9
    assert float(compiled(jnp.ones((64, 64), jnp.float32))) == pytest.approx(64.0**3)
    # Objects without .lower (the cached-sharding compile wrappers) degrade to None.
    assert T.aot_compile(lambda x: x, jnp.ones(())) == (None, None)


def test_aot_compile_says_why_it_fell_back(capsys):
    """A program the compiler refuses (on the chip: RESOURCE_EXHAUSTED) degrades to
    ``(None, None)`` as before, and the first line of the reason is logged."""
    def refuses(x):
        raise ValueError("RESOURCE_EXHAUSTED: Used 15.86G of 15.75G hbm\nsecond line")

    assert T.aot_compile(jax.jit(refuses), jnp.ones(())) == (None, None)
    out = capsys.readouterr().out
    assert "aot_compile: falling back to jit (ValueError: RESOURCE_EXHAUSTED: Used " \
           "15.86G of 15.75G hbm)" in out
    assert "second line" not in out


# ------------------------------------------------------- health-stats equivalence


def _tiny_batches(n=64, steps=4, batch=16):
    rng = np.random.default_rng(0)
    images = rng.normal(size=(n, 28, 28, 1)).astype(np.float32)
    labels = rng.integers(0, 10, size=n).astype(np.int32)
    idx = rng.permutation(n)[:steps * batch].reshape(steps, batch).astype(np.int32)
    return jnp.asarray(images), jnp.asarray(labels), jnp.asarray(idx)


def test_health_epoch_bitwise_equals_unmetered_epoch():
    """Acceptance: the metered scan must not perturb training AT ALL — the grad-norm
    computation only reads the grads, so params (and losses) are bitwise identical."""
    images, labels, idx = _tiny_batches()
    kw = dict(learning_rate=0.05, momentum=0.5)
    rng = jax.random.PRNGKey(3)

    plain = jax.jit(make_epoch_fn(Net(), **kw))
    metered = jax.jit(make_epoch_fn(Net(), **kw, health=True))
    s0 = create_train_state(Net(), jax.random.PRNGKey(7))
    s1 = create_train_state(Net(), jax.random.PRNGKey(7))

    s0, losses0 = plain(s0, images, labels, idx, rng)
    s1, (losses1, health) = metered(s1, images, labels, idx, rng)

    assert np.array_equal(np.asarray(losses0), np.asarray(losses1))
    for a, b in zip(jax.tree_util.tree_leaves(s0.params),
                    jax.tree_util.tree_leaves(s1.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))  # bitwise

    # The accumulators agree with the returned losses array...
    losses = np.asarray(losses0)
    assert float(health.loss_min) == pytest.approx(losses.min(), rel=1e-6)
    assert float(health.loss_max) == pytest.approx(losses.max(), rel=1e-6)
    assert float(health.loss_sum) == pytest.approx(losses.sum(), rel=1e-6)
    # ...and the grad norms are real positive measurements.
    assert float(health.grad_norm_max) >= float(health.grad_norm_sum) / len(losses) > 0


def test_flag_off_path_adds_no_ops_to_the_step():
    """The default (with_metrics=False) step must trace to EXACTLY the program the
    pre-telemetry step traced to, and the metered step to a strictly larger one."""
    state = create_train_state(Net(), jax.random.PRNGKey(0))
    args = (state, jnp.zeros((8, 28, 28, 1), jnp.float32),
            jnp.zeros((8,), jnp.int32), jax.random.PRNGKey(1))
    kw = dict(learning_rate=0.05, momentum=0.5)

    default = jax.make_jaxpr(make_train_step(Net(), **kw))(*args)
    off = jax.make_jaxpr(make_train_step(Net(), **kw, with_metrics=False))(*args)
    on = jax.make_jaxpr(make_train_step(Net(), **kw, with_metrics=True))(*args)
    assert str(off) == str(default)
    assert len(on.jaxpr.eqns) > len(off.jaxpr.eqns)

    # Same guarantee one level up, for the scanned epoch program.
    images, labels, idx = _tiny_batches()
    eargs = (state, images, labels, idx, jax.random.PRNGKey(1))
    e_default = jax.make_jaxpr(make_epoch_fn(Net(), **kw))(*eargs)
    e_off = jax.make_jaxpr(make_epoch_fn(Net(), **kw, health=False))(*eargs)
    assert str(e_off) == str(e_default)


def test_health_composes_with_grad_accum_and_clipping():
    """with_metrics reports the PRE-clip norm and must not disturb the accumulated
    update: metered and unmetered grad-accum+clip steps stay bitwise identical."""
    images, labels, idx = _tiny_batches()
    kw = dict(learning_rate=0.05, momentum=0.5, grad_accum=2, clip_grad_norm=0.1)
    rng = jax.random.PRNGKey(3)
    s0 = create_train_state(Net(), jax.random.PRNGKey(7))
    s1 = create_train_state(Net(), jax.random.PRNGKey(7))
    s0, _ = jax.jit(make_epoch_fn(Net(), **kw))(s0, images, labels, idx, rng)
    s1, (_, health) = jax.jit(make_epoch_fn(Net(), **kw, health=True))(
        s1, images, labels, idx, rng)
    for a, b in zip(jax.tree_util.tree_leaves(s0.params),
                    jax.tree_util.tree_leaves(s1.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # Clipped to 0.1, yet the reported (pre-clip) norm exceeds it.
    assert float(health.grad_norm_max) > 0.1


# ------------------------------------------------------------ end-to-end trainer


@pytest.fixture(scope="module")
def micro_datasets():
    xs, ys = _synthesize_split(192, seed=400)
    train = Dataset(_normalize(xs), ys.astype(np.int32), "synthetic")
    xs, ys = _synthesize_split(64, seed=401)
    test = Dataset(_normalize(xs), ys.astype(np.int32), "synthetic")
    return train, test


def test_single_trainer_telemetry_acceptance_schema(tmp_path, micro_datasets):
    """The acceptance-criteria run, miniaturized: --telemetry produces valid JSONL
    with a manifest and per-epoch events carrying compile_s / execute_s /
    examples_per_s / flops_per_step, plus health events with grad_norm."""
    from csed_514_project_distributed_training_using_pytorch_tpu.train import single
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import (
        SingleProcessConfig,
    )

    path = str(tmp_path / "run.jsonl")
    cfg = SingleProcessConfig(
        n_epochs=2, batch_size_train=64, batch_size_test=64, log_interval=2,
        telemetry=path, health_stats=True,
        results_dir=str(tmp_path / "results"), images_dir=str(tmp_path / "images"))
    single.main(cfg, datasets=micro_datasets)

    rows = [json.loads(line) for line in open(path)]   # every line is valid JSON
    events = [r["event"] for r in rows]
    assert events[0] == "manifest"
    assert events.count("epoch") == 2 and events.count("health") == 2
    assert "compile" in events and "mfu" in events

    man = rows[0]
    assert man["config"]["n_epochs"] == 2 and man["device_count"] >= 1

    for ep in (r for r in rows if r["event"] == "epoch"):
        assert ep["compile_s"] > 0
        assert ep["execute_s"] > 0
        assert ep["examples_per_s"] > 0
        assert ep["flops_per_step"] > 0
        assert ep["steps"] == 3            # 192 examples / batch 64
    for h in (r for r in rows if r["event"] == "health"):
        assert h["grad_norm"] > 0 and h["param_norm"] > 0
        assert h["loss_min"] <= h["loss_mean"] <= h["loss_max"]


def test_health_stats_rejected_on_host_pipeline_path(micro_datasets, tmp_path):
    from csed_514_project_distributed_training_using_pytorch_tpu.train import single
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import (
        SingleProcessConfig,
    )

    cfg = SingleProcessConfig(health_stats=True, use_host_pipeline=True,
                              telemetry=str(tmp_path / "t.jsonl"),
                              results_dir=str(tmp_path), images_dir=str(tmp_path))
    with pytest.raises(ValueError, match="health-stats"):
        single.main(cfg, datasets=micro_datasets)
    # ...and --health-stats without --telemetry has nowhere to put its events.
    cfg = SingleProcessConfig(health_stats=True,
                              results_dir=str(tmp_path), images_dir=str(tmp_path))
    with pytest.raises(ValueError, match="telemetry"):
        single.main(cfg, datasets=micro_datasets)


# ------------------------------------------------------------------- report CLI


def _write_fake_run(path, *, execute_s, examples_per_s, grad_norms=(0.7, 0.5)):
    rows = [
        {"event": "manifest", "run_type": "single", "device_kind": "cpu",
         "device_count": 1, "process_count": 1, "jax_version": "0", "mesh": None},
        {"event": "compile", "fn": "epoch", "lower_s": 0.1, "compile_s": 0.9,
         "flops_per_call": 1e9, "steps_per_call": 10, "flops_per_step": 1e8},
    ]
    for i, g in enumerate(grad_norms):
        rows.append({"event": "epoch", "epoch": i, "examples": 1000, "steps": 10,
                     "wall_s": execute_s + 0.1, "execute_s": execute_s,
                     "eval_s": 0.05, "data_s": 0.01, "compile_s": 1.0,
                     "examples_per_s": examples_per_s, "flops_per_step": 1e8,
                     "train_loss": 2.0 - i * 0.5, "val_loss": 2.1 - i * 0.5,
                     "mfu": None})
        rows.append({"event": "health", "epoch": i, "steps": 10, "grad_norm": g,
                     "grad_norm_max": g * 1.2, "loss_min": 1.0, "loss_max": 2.5,
                     "loss_mean": 1.7, "param_norm": 5.0})
    rows.append({"event": "mfu", "flops_per_step": 1e8, "step_s": execute_s / 10,
                 "achieved_flops_per_s": 1e9, "device_kind": "cpu", "devices": 1,
                 "peak_flops_per_s_per_device": None, "mfu": None})
    rows.append({"event": "bench", "metric": "epoch wall-clock", "value": 0.2,
                 "unit": "s", "examples_per_s": 300000.0})
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


def _run_report(*files):
    env = dict(os.environ, PYTHONPATH=_REPO, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "telemetry_report.py"),
         *files],
        capture_output=True, text=True, env=env, timeout=180, cwd=_REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


def test_report_cli_single_run(tmp_path):
    a = str(tmp_path / "a.jsonl")
    _write_fake_run(a, execute_s=1.0, examples_per_s=1000.0)
    out = _run_report(a)
    assert "single run on cpu x1" in out
    assert "compile_s 1" in out
    assert "examples/s 1000" in out
    assert "grad_norm 0.7000 -> 0.5000" in out
    assert "bench: epoch wall-clock" in out


def test_report_cli_a_vs_b_comparison(tmp_path):
    a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    _write_fake_run(a, execute_s=1.0, examples_per_s=1000.0)
    _write_fake_run(b, execute_s=0.5, examples_per_s=2000.0)
    out = _run_report(a, b)
    assert "B/A" in out
    assert "0.500x" in out       # execute_s halved
    assert "2.000x" in out       # examples/s doubled


def test_report_cli_reads_loss_curve_metrics_jsonl(tmp_path):
    """The loss-curve companion artifact goes through the same reader (the
    load_metrics_jsonl satellite): final losses surface in the summary."""
    h = M.MetricsHistory()
    h.record_train(64, 2.3)
    h.record_train(128, 1.5)
    h.record_test(128, 1.8)
    path = str(tmp_path / "metrics.jsonl")
    M.save_metrics_jsonl(h, path)
    out = _run_report(path)
    assert "metrics.jsonl (3 events)" in out


# -----------------------------------------------------------------------------------------
# Shared-reader tolerances + the serving stream mode (serving PR satellites)
# -----------------------------------------------------------------------------------------


def test_load_metrics_jsonl_passes_unknown_event_types_through(tmp_path):
    """Serve logs and training logs share one reader: event types the reader has
    never heard of load as plain dicts, untouched and in order."""
    path = str(tmp_path / "mixed.jsonl")
    rows = [{"event": "epoch", "epoch": 0, "wall_s": 1.0},
            {"event": "some_future_event", "payload": {"x": [1, 2]}},
            {"event": "serve", "request_id": 0, "finish": "ok"}]
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    assert M.load_metrics_jsonl(path) == rows


def test_load_metrics_jsonl_skips_torn_final_line_only(tmp_path):
    """Stream-mode writers (the serving path) append per event, so a kill can
    tear the trailing line: everything before it still loads. A malformed line
    anywhere EARLIER means corruption and still raises."""
    torn = str(tmp_path / "torn.jsonl")
    with open(torn, "w") as f:
        f.write('{"event": "serve", "request_id": 0}\n')
        f.write('{"event": "serve", "request_')          # killed mid-write
    assert M.load_metrics_jsonl(torn) == [{"event": "serve", "request_id": 0}]

    corrupt = str(tmp_path / "corrupt.jsonl")
    with open(corrupt, "w") as f:
        f.write('not json at all\n')
        f.write('{"event": "serve", "request_id": 0}\n')
    with pytest.raises(json.JSONDecodeError):
        M.load_metrics_jsonl(corrupt)


def test_stream_writer_appends_per_emit_and_round_trips(tmp_path):
    """TelemetryWriter(stream=True): one flushed line per emit (no rewrite), the
    same sanitize rule (NaN -> null), process-0 gating, close() releases."""
    path = str(tmp_path / "serve.jsonl")
    with T.TelemetryWriter(path, stream=True) as w:
        w.emit({"event": "serve", "request_id": 0, "ttft_s": 0.5})
        first_size = os.path.getsize(path)
        w.emit({"event": "serve", "request_id": 1, "ttft_s": float("nan")})
        assert os.path.getsize(path) > first_size        # appended, not rewritten
    rows = M.load_metrics_jsonl(path)
    assert [r["request_id"] for r in rows] == [0, 1]
    assert rows[1]["ttft_s"] is None


def test_stream_writer_gates_to_process_zero(tmp_path, monkeypatch):
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    path = str(tmp_path / "gated.jsonl")
    w = T.TelemetryWriter(path, stream=True)
    w.emit({"event": "serve"})
    w.close()
    assert not os.path.exists(path)


def test_serve_event_and_summary_schema():
    ev = T.serve_event(request_id=3, prompt_len=4, new_tokens=8, finish="ok",
                       queue_wait_s=0.1, ttft_s=0.2, tpot_s=0.01, e2e_s=0.5)
    assert ev["event"] == "serve" and ev["finish"] == "ok"
    assert ev["tokens_per_s"] == pytest.approx(8 / 0.4)  # e2e minus queue wait
    summ = T.serve_summary_event(
        requests=4, ok=3, timeout=1, new_tokens=30, wall_s=2.0, steps=40,
        slot_occupancy=0.75, ttft_s=[0.1, 0.2, 0.3, None],
        tpot_s=[0.01] * 4, e2e_s=[0.5] * 4, queue_wait_s=[0.0] * 4)
    assert summ["tokens_per_s"] == pytest.approx(15.0)
    assert summ["ttft_s"] == {"p50": 0.2, "p95": 0.3, "p99": 0.3}
    assert T.percentiles([]) is None
