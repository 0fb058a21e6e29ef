"""The span primitive (``utils/profiling.py``) and the LM trainer's loop of spans:
nesting and ``drain()``, the ``epoch`` event's span fields, and the same spans read
back from a profiler trace."""

import glob
import json
import os
import threading
import time
import types

import numpy as np
import pytest

from csed_514_project_distributed_training_using_pytorch_tpu.data.mnist import (
    Dataset, _normalize, _synthesize_split,
)
from csed_514_project_distributed_training_using_pytorch_tpu.obs import goodput
from csed_514_project_distributed_training_using_pytorch_tpu.train import lm as lm_train
from csed_514_project_distributed_training_using_pytorch_tpu.utils import (
    profiling as P,
)
from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import (
    LMConfig, parse_config,
)

SPAN_FIELDS = [f"{name}_s" for name in lm_train.EPOCH_SPANS]


@pytest.fixture(autouse=True)
def fresh_table():
    P.drain()


# ----------------------------------------------------------------- the primitive


def test_every_span_is_timed_under_its_name():
    with P.step("loop", 0):
        with P.span("loop/a"):
            with P.span("a/deeper"):
                time.sleep(0.002)
        with P.span("loop/b"):
            pass
        with P.span("loop/a"):              # a second close adds to the same key
            time.sleep(0.002)
    seconds, period_s = P.drain()
    assert set(seconds) == {"loop", "loop/a", "a/deeper", "loop/b"}
    assert seconds["loop/a"] >= 0.004 > seconds["loop/b"] >= 0.0
    assert seconds["loop/a"] >= seconds["a/deeper"] >= 0.002
    # the step's children do not nest in each other: disjoint pieces of the step
    assert seconds["loop/a"] + seconds["loop/b"] <= seconds["loop"] <= period_s


def test_drain_resets_the_table_and_the_period():
    with P.step("loop", 0):
        with P.span("loop/a"):
            pass
    assert set(P.drain()[0]) == {"loop", "loop/a"}
    time.sleep(0.002)
    seconds, period_s = P.drain()
    assert seconds == {}
    assert 0.002 <= period_s < 1.0


def test_drain_reports_the_step_before_the_open_one_too():
    with P.step("loop", 0):
        with P.span("loop/tail"):
            pass
    with P.step("loop", 1):
        with P.span("loop/head"):
            pass
        with P.span("loop/emit"):
            seconds, _ = P.drain()
    assert {"loop/tail", "loop/head"} <= set(seconds)
    # the rest of the span the drain was called from lands in the next period
    assert set(P.drain()[0]) == {"loop", "loop/emit"}


def test_drain_splits_the_spans_open_at_that_instant(monkeypatch):
    """Their time so far counts in this period and the rest in the next, so the
    seconds of a period are pieces of it. The test drives the module's clock: a
    bound on a ``sleep`` is a bound on how loaded the machine is."""
    now = [100.0]
    monkeypatch.setattr(P, "time", types.SimpleNamespace(perf_counter=lambda: now[0]))
    P.drain()                               # the table's last drain, on this clock
    with P.step("loop", 0):
        with P.span("loop/emit"):
            now[0] += 3.0
            first, period_1 = P.drain()
            now[0] += 1.0
        second, period_2 = P.drain()
    assert (first, period_1) == ({"loop": 3.0, "loop/emit": 3.0}, 3.0)
    assert (second, period_2) == ({"loop": 1.0, "loop/emit": 1.0}, 1.0)   # the rest only


def test_an_exception_closes_its_spans():
    with pytest.raises(KeyError):
        with P.step("loop", 0):
            with P.span("loop/a"):
                with P.span("a/deeper"):
                    raise KeyError("x")
    assert set(P.drain()[0]) == {"loop", "loop/a", "a/deeper"}
    assert P.drain()[0] == {}               # nothing was left open to split


def test_the_table_is_per_thread():
    """A span on a worker thread (the write-behind checkpointer) never lands in
    the loop's table, even while the loop's step is open; the worker has a table
    of its own."""
    seen = {}

    def worker():
        with P.span("loop/on_worker"):
            pass
        seen["worker"] = set(P.drain()[0])

    with P.step("loop", 0):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        with P.span("loop/a"):
            pass
    assert set(P.drain()[0]) == {"loop", "loop/a"}
    assert seen["worker"] == {"loop/on_worker"}


# ------------------------------------------------------- the LM trainer's loop


def _split(n, seed):
    xs, ys = _synthesize_split(n, seed=seed)
    return Dataset(_normalize(xs), ys.astype(np.int32), "synthetic")


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """Three epochs of ``train.lm.main`` at a tiny width, under ``--profile``;
    every ``drain()`` the loop makes is kept beside what it emitted."""
    tmp = tmp_path_factory.mktemp("spans")
    cfg = LMConfig(epochs=3, batch_size=16, eval_batch=16, embed_dim=16, num_layers=1,
                   num_heads=2, generate=0, results_dir=str(tmp / "results"),
                   images_dir=str(tmp / "images"), telemetry=str(tmp / "t.jsonl"),
                   profile=True, profile_dir=str(tmp / "profile"))
    drains = []
    original = P.drain

    def recording_drain():
        out = original()
        drains.append(out)
        return out

    P.drain = recording_drain
    try:
        lm_train.main(cfg, datasets=(_split(64, 50), _split(16, 51)))
    finally:
        P.drain = original
    with open(cfg.telemetry) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    return {"config": cfg, "drains": drains[1:],    # the first is the loop's reset
            "epochs": [r for r in rows if r["event"] == "epoch"]}


def test_every_epoch_event_holds_every_span_field(traced_run):
    epochs = traced_run["epochs"]
    assert [e["epoch"] for e in epochs] == [0, 1, 2]
    for e in epochs:
        for field in SPAN_FIELDS + ["period_s", "wall_s"]:
            assert isinstance(e[field], float) and e[field] >= 0.0, (field, e)
        assert sum(e[f] for f in SPAN_FIELDS) <= e["period_s"]
        for field in ("data_s", "execute_s", "eval_s", "log_s"):
            assert e[field] > 0.0
    # the event holds what was drained at its emit: the first has no tail before it,
    # the later ones the previous iteration's emit, guard, checkpoint and tick
    assert epochs[0]["guard_s"] == 0.0
    assert epochs[0]["checkpoint_s"] == 0.0
    for e in epochs[1:]:
        assert e["emit_s"] > 0.0 and e["guard_s"] > 0.0 and e["tick_s"] > 0.0
        assert e["checkpoint_s"] > 0.0      # results_dir is set: a checkpoint an epoch


def test_every_period_holds_the_same_spans(traced_run):
    drains = traced_run["drains"]
    assert len(drains) == 3
    head = {"epoch", "epoch/tick", "epoch/data", "epoch/execute", "execute/dispatch",
            "execute/wait", "execute/loss_fetch", "epoch/eval", "epoch/log"}
    # the first period has this iteration's head alone, up to the drain inside emit
    assert set(drains[0][0]) == head | {"epoch/emit"}
    assert set(drains[1][0]) == set(drains[2][0]) == head | {
        "epoch/emit", "epoch/guard", "epoch/checkpoint"}
    for (seconds, period_s), event in zip(drains, traced_run["epochs"]):
        assert event["period_s"] == pytest.approx(period_s)
        for name in lm_train.EPOCH_SPANS:
            assert event[f"{name}_s"] == pytest.approx(seconds.get(f"epoch/{name}", 0.0))
        # dispatch, wait and the loss fetch are execute's, not the event's
        assert (seconds["execute/dispatch"] + seconds["execute/wait"]
                + seconds["execute/loss_fetch"]) <= seconds["epoch/execute"]


def test_goodput_reads_the_fields_it_always_read(traced_run):
    """``execute_s``, ``eval_s`` and ``data_s`` keep their meaning: compute is the
    epochs' execute + eval, data wait their data_s, and the three stay inside wall_s."""
    epochs = traced_run["epochs"]
    report = goodput.decompose([traced_run["config"].telemetry])
    seg = report["segments"]
    assert seg["compute_s"] == pytest.approx(
        sum(e["execute_s"] + e["eval_s"] for e in epochs))
    assert seg["data_wait_s"] == pytest.approx(sum(e["data_s"] for e in epochs))
    for e in epochs:
        assert e["data_s"] + e["execute_s"] + e["eval_s"] <= e["wall_s"]
        assert e["examples_per_s"] == pytest.approx(e["examples"] / e["execute_s"])


def test_the_trace_holds_each_step_and_its_spans(traced_run):
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(traced_run["config"].profile_dir, "plugins",
                                   "profile", "*", "*.xplane.pb"))
    assert len(paths) == 1
    events = [(str(e.name), int(e.start_ns), int(e.start_ns + e.duration_ns),
               dict(e.stats) if str(e.name) == "epoch" else None)
              for plane in ProfileData.from_file(paths[0]).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if str(e.name).startswith(("epoch", "execute/"))]
    steps = sorted((e for e in events if e[0] == "epoch"), key=lambda e: e[1])
    assert [e[3]["step_num"] for e in steps] == [0, 1, 2]
    children = [e for e in events if e[0].startswith("epoch/")]
    per_step = {"epoch/tick": 2, "epoch/guard": 2,
                **{f"epoch/{n}": 1 for n in lm_train.EPOCH_SPANS
                   if n not in ("tick", "guard")}}
    for _, lo, hi, _ in steps:
        inside = [e[0] for e in children if lo <= e[1] and e[2] <= hi]
        assert {n: inside.count(n) for n in set(inside)} == per_step
    assert len(children) == 3 * sum(per_step.values())      # none outside a step
    for name in ("execute/dispatch", "execute/wait", "execute/loss_fetch"):
        assert sum(e[0] == name for e in events) == 3


def test_lm_trainer_takes_the_profile_flags():
    cfg = parse_config(LMConfig, ["--profile", "--profile-dir", "/tmp/x"])
    assert cfg.profile is True and cfg.profile_dir == "/tmp/x"
    assert LMConfig().profile is False
