"""Profiler trace (.xplane.pb) -> numbers: device busy/idle, per-op self time,
idle gaps and what the host was doing in them.

Read with ``jax.profiler.ProfileData`` alone. A TPU device plane
(``/device:TPU:<n>``) carries a line of XLA ops whose events nest (a ``while``
spans its body's ops), so per-op time is *self* time: an event's duration
minus what its children cover. Busy time is the union of that line's events.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
HOST_PREFIX = "/host:"


def find_trace(profile_dir: str) -> str:
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` output directory."""
    paths = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def op_name(text: str) -> str:
    """XLA's own name of an op, without the HLO text after it and without the
    instance number: ``%multiply_reduce_fusion.92 = (f32[...`` ->
    ``multiply_reduce_fusion``. Instances of one fusion kind add up."""
    name = text.split(" = ", 1)[0].strip().lstrip("%")
    head, _, tail = name.rpartition(".")
    return (head if head and tail.isdigit() else name)[:80]


def _events(line) -> list[tuple[int, int, str]]:
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), op_name(str(e.name)))
            for e in line.events]


def device_op_events(profile) -> dict[str, list[tuple[int, int, str]]]:
    """``{plane name: [(start_ns, end_ns, op name), ...]}`` for every device
    plane that ran at least one op."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name == OP_LINE:
                ev = _events(line)
                if ev:
                    out[plane.name] = sorted(ev, key=lambda e: (e[0], -e[1]))
    return out


def union_ns(events) -> tuple[int, list[tuple[int, int]]]:
    """Length of the union of ``(start, end, ...)`` intervals, and the merged
    intervals themselves."""
    merged: list[list[int]] = []
    for ev in sorted(events, key=lambda e: e[0]):
        s, e = ev[0], ev[1]
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def self_times(events) -> dict[str, int]:
    """Self time per op name (ns): duration minus the part nested events cover.
    ``events`` sorted by (start, -end)."""
    total: dict[str, int] = {}
    stack: list[list] = []      # [end, name, self_ns, covered_until]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, self_ns, _ = stack.pop()
            total[name] = total.get(name, 0) + self_ns

    for s, e, name in events:
        close(s)
        if stack:
            parent = stack[-1]
            lo = max(s, parent[3])
            hi = min(e, parent[0])
            if hi > lo:
                parent[2] -= hi - lo
                parent[3] = hi
        stack.append([e, name, e - s, s])
    close(float("inf"))
    return total


def host_events(profile):
    """Every host event once, as arrays: start, end (ns) and a label index."""
    import numpy as np
    starts, ends, labels, names = [], [], [], {}
    for plane in profile.planes:
        if not plane.name.startswith(HOST_PREFIX):
            continue
        for line in plane.lines:
            thread = line.name.split("/")[0]
            for e in line.events:
                s = int(e.start_ns)
                starts.append(s)
                ends.append(s + int(e.duration_ns))
                labels.append(names.setdefault(f"{thread}:{str(e.name)[:80]}", len(names)))
    return (np.asarray(starts, np.int64), np.asarray(ends, np.int64),
            np.asarray(labels, np.int64), list(names))


def host_activity(host, lo: int, hi: int) -> str:
    """What the host was doing in ``[lo, hi]``, as ``<thread>:<event>``: the
    shortest host event that covers at least half of the gap (the innermost
    frame, not the ``main`` that spans everything); failing that, the event
    that overlaps it longest; ``"(no host event)"`` if none does."""
    import numpy as np
    starts, ends, labels, names = host
    if not len(starts):
        return "(no host event)"
    overlap = np.minimum(ends, hi) - np.maximum(starts, lo)
    if overlap.max() <= 0:
        return "(no host event)"
    covering = np.flatnonzero(2 * overlap >= hi - lo)
    if len(covering):
        pick = covering[np.argmin((ends - starts)[covering])]
    else:
        pick = int(np.argmax(overlap))
    return names[labels[pick]]


def reduce(profile, *, top: int = 10) -> dict:
    """The numbers a traced run reports.

    ``busy_s``: union of device-op intervals, averaged over device planes.
    ``span_s``: first op start to last op end, the longest over planes.
    ``ops``: self seconds per op name, averaged over planes, every op.
    ``device_ops`` / ``idle_gaps``: the ``top`` entries of each, as
    ``[name, seconds]`` lists for the result line's ``breakdown``.
    """
    planes = device_op_events(profile)
    if not planes:
        return {"devices": 0, "busy_s": 0.0, "span_s": 0.0, "ops": {},
                "device_ops": [], "idle_gaps": []}
    n = len(planes)
    busy = 0
    span = 0
    ops: dict[str, float] = {}
    first_gaps: list[tuple[int, int]] = []
    for i, events in enumerate(planes.values()):
        b, merged = union_ns(events)
        busy += b
        span = max(span, merged[-1][1] - merged[0][0])
        for name, ns in self_times(events).items():
            ops[name] = ops.get(name, 0.0) + ns / 1e9 / n
        if i == 0:
            first_gaps = [(a[1], b2[0]) for a, b2 in zip(merged, merged[1:])]
    gaps: dict[str, float] = {}
    host = host_events(profile)
    for lo, hi in sorted(first_gaps, key=lambda g: g[0] - g[1])[:100]:
        what = host_activity(host, lo, hi)
        gaps[what] = gaps.get(what, 0.0) + (hi - lo) / 1e9
    ranked = lambda d: [[k, v] for k, v in
                        sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"devices": n, "busy_s": busy / 1e9 / n, "span_s": span / 1e9,
            "ops": ops, "device_ops": ranked(ops), "idle_gaps": ranked(gaps)}
