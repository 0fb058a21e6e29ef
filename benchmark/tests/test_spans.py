"""The readers of the program's own spans: ``idle_named_share`` on a hand-worked
trace, and the three ``epoch_*_ms`` metrics on the traced CPU rehearsal."""

import json
import os

import pytest
from jax.profiler import ProfileData

import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
NEW = ["epoch_eval_ms", "epoch_host_ms", "epoch_unnamed_ms", "idle_named_share"]

# Device ops in [0,10), [30,32), [50,52) and [70,72) us: three gaps of 20, 18 and
# 18 us. The first lies half inside `epoch/emit` ([20,31) us, which ends under an
# op); its other half is before the first `epoch/*` span and is set aside. The
# second is outside every `epoch/*` span. The third lies 10 us inside `epoch/eval`
# ([60,71) us). `main` covers all and `execute/wait` is a deeper span, so neither
# counts.
HAND = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 30000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 50000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 70000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.12 = f32[8] fusion(...)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 80000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 11000000 }
    events { metadata_id: 3 offset_ps: 33000000 duration_ps: 16000000 }
    events { metadata_id: 4 offset_ps: 60000000 duration_ps: 11000000 } }
  event_metadata { key: 1 value { id: 1 name: "main" } }
  event_metadata { key: 2 value { id: 2 name: "epoch/emit" } }
  event_metadata { key: 3 value { id: 3 name: "execute/wait" } }
  event_metadata { key: 4 value { id: 4 name: "epoch/eval" } } }
'''


def _profile(text):
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def _span_idle():
    return harness.load_module(os.path.join(BENCH, "reducers", "span_idle.py"),
                               "bench_reducer_span_idle")


def test_idle_named_share_by_hand(monkeypatch, capsys):
    span_idle = _span_idle()
    idle, named, by_name, aside = span_idle.idle_by_span(_profile(HAND), "epoch/")
    assert (idle, named, aside) == (46_000, 20_000, 10_000)
    assert by_name == {"epoch/emit": 10_000, "epoch/eval": 10_000}
    monkeypatch.setattr(span_idle.xplane, "find_trace", lambda d: d)
    monkeypatch.setattr(span_idle.xplane, "load", lambda path: _profile(HAND))
    share = span_idle.read(harness.Observations(trace_dir="x"), prefix="epoch/")
    assert share == pytest.approx(100.0 * 20 / 46)
    line = capsys.readouterr().out
    assert line.startswith("idle by span: epoch/emit 0.010 ms, epoch/eval 0.010 ms, "
                           "unnamed 0.026 ms (of 0.046 ms idle")
    assert "0.010 ms outside them set aside" in line


@pytest.mark.parametrize("text", [
    'planes { id: 1 name: "/host:CPU" }',                       # no device plane
    HAND.replace('"epoch/emit"', '"emit"')                      # a program with no span
        .replace('"epoch/eval"', '"eval"'),
    HAND.replace("offset_ps: 30000000", "offset_ps: 10000000")  # no idle time at all
        .replace("offset_ps: 50000000", "offset_ps: 12000000")
        .replace("offset_ps: 70000000", "offset_ps: 14000000"),
    HAND.replace('"epoch/eval"', '"eval"')          # idle only before and after the spans
        .replace("offset_ps: 20000000 duration_ps: 11000000",
                 "offset_ps: 1000000 duration_ps: 8000000"),
])
def test_idle_named_share_reads_nothing(monkeypatch, text):
    span_idle = _span_idle()
    monkeypatch.setattr(span_idle.xplane, "find_trace", lambda d: d)
    monkeypatch.setattr(span_idle.xplane, "load", lambda path: _profile(text))
    assert span_idle.read(harness.Observations(trace_dir="x"), prefix="epoch/") is None


def test_no_trace_reads_nothing(tmp_path):
    obs = harness.Observations(trace_dir=str(tmp_path))
    assert _span_idle().read(obs, prefix="epoch/") is None


def test_traced_rehearsal_reports_the_span_metrics(run_cell):
    """The CPU has no device plane: the three metrics of the `epoch` event's span
    fields are on the line, `idle_named_share` is left out."""
    result, lines = run_cell("lm_train_b16", seconds=4.0, trace=True)
    assert result["correct"] is True, lines
    got = result["metrics"]
    assert {"epoch_eval_ms", "epoch_host_ms", "epoch_unnamed_ms"} <= set(got)
    assert "idle_named_share" not in got
    assert got["epoch_eval_ms"]["value"] > 0 and got["epoch_host_ms"]["value"] > 0
    assert got["epoch_unnamed_ms"]["value"] >= 0
    assert all(got[name]["unit"] == "ms" for name in NEW[:3])


def test_a_program_without_the_span_fields_is_left_out():
    """The parent's `epoch` events have no `log_s` ... `period_s`: the readers
    find nothing and do not raise; `eval_s` it always had."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    old = {"event": "epoch", "wall_s": 1.0, "execute_s": 0.9, "eval_s": 0.05,
           "data_s": 0.001, "loop_s": 1.0}
    got = harness.layer_metrics(manifest, BENCH, "lm_train_b16",
                                harness.Observations(epochs=[old]))
    assert got["epoch_eval_ms"]["value"] == pytest.approx(50.0)
    assert "epoch_host_ms" not in got and "epoch_unnamed_ms" not in got
    assert "idle_named_share" not in got
    new = dict(old, log_s=0.002, emit_s=0.003, guard_s=0.0, checkpoint_s=0.0,
               tick_s=0.001, period_s=0.96)
    got = harness.layer_metrics(manifest, BENCH, "lm_train_b16",
                                harness.Observations(epochs=[new]))
    assert got["epoch_host_ms"]["value"] == pytest.approx(7.0)
    assert got["epoch_unnamed_ms"]["value"] == pytest.approx(3.0)


def test_the_manifest_lists_the_four_for_the_training_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        per_layer = {m["name"]: m for m in json.load(fh)["per_layer"]}
    assert list(per_layer)[-4:] == NEW
    for name in NEW:
        assert per_layer[name]["layer"] == "trainer loop"
        assert per_layer[name]["moves"] == "train_examples_per_s"
        assert per_layer[name]["workloads"] == ["lm_train_b16"]
