"""The trace reduction: a hand-worked trace, and one recorded on the chip.

``trace_lm_train_v5e.xplane.pb`` is the head of a `lm_train_b16` trace taken
on a TPU v5 lite in PR 23 (two 32-step epochs), cut by ``trim_trace.py`` to the
first epoch program's first ops, the long gap after it and the next ops.
"""

import os

import pytest
from jax.profiler import ProfileData

import xplane

HERE = os.path.dirname(os.path.abspath(__file__))

HAND = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 30000000 duration_ps: 2000000 } }
  lines { id: 2 name: "Async XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 12000000 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "%while.7 = (s32[]) while(...)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.12 = f32[8] fusion(...)" } }
  event_metadata { key: 3 value { id: 3 name: "%all-reduce.1 = f32[8] all-reduce(...)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000000 }
    events { metadata_id: 2 offset_ps: 11000000 duration_ps: 18000000 } }
  event_metadata { key: 1 value { id: 1 name: "main" } }
  event_metadata { key: 2 value { id: 2 name: "device_get" } } }
'''


def _hand():
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(HAND))


def test_hand_worked_trace():
    r = xplane.reduce(_hand())
    assert r["devices"] == 1
    # ops run in [0,10) and [30,32) microseconds: 12 us busy over a 32 us span
    assert r["busy_s"] == pytest.approx(12e-6)
    assert r["span_s"] == pytest.approx(32e-6)
    # self time: while 10 - 3 - 4 = 3 us; the fusions 3 + 2; the all-reduce 4;
    # instance numbers are dropped, the async line is not counted
    assert r["ops"] == pytest.approx({"while": 3e-6, "fusion": 5e-6, "all-reduce": 4e-6})
    assert r["device_ops"][0][0] == "fusion"
    # the one gap, 10 -> 30 us, goes to the innermost host event covering it
    assert r["idle_gaps"] == [["python:device_get", pytest.approx(20e-6)]]


def test_op_name_is_xlas_own():
    assert xplane.op_name("%multiply_reduce_fusion.92 = (f32[16,8,784]{2,1,0}) fusion(") \
        == "multiply_reduce_fusion"
    assert xplane.op_name("%all-reduce.3 = f32[21840] all-reduce(f32[21840] %x)") == "all-reduce"
    assert xplane.op_name("%copy = s32[1] copy(s32[1] %p)") == "copy"


def test_no_device_plane_reads_nothing():
    empty = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace('planes { id: 1 name: "/host:CPU" }'))
    assert xplane.reduce(empty)["devices"] == 0


def test_trace_recorded_on_the_chip():
    profile = xplane.load(os.path.join(HERE, "trace_lm_train_v5e.xplane.pb"))
    ops = xplane.device_op_events(profile)
    assert list(ops) == ["/device:TPU:0"] and len(ops["/device:TPU:0"]) == 441
    r = xplane.reduce(profile)
    assert r["devices"] == 1
    # one 32-step epoch program of 2.973 s, then the boundary, then the next ops
    assert r["busy_s"] == pytest.approx(2.973268, rel=1e-5)
    assert r["span_s"] == pytest.approx(2.976411, rel=1e-5)
    assert 0.0 < 1.0 - r["busy_s"] / r["span_s"] < 0.002
    # the epoch's `while` spans its body; the kept body ops are taken out of it
    whole = max(e[1] - e[0] for e in ops["/device:TPU:0"]) / 1e9
    assert r["ops"]["while"] < whole
    assert r["ops"]["while"] + sum(v for k, v in r["ops"].items() if k != "while") \
        == pytest.approx(r["busy_s"], rel=1e-6)
    # the boundary gap is the host waiting on the device
    assert r["idle_gaps"][0][0] == "python:$api.py:3097 block_until_ready"
