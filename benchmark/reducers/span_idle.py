"""Share of the device's idle time that the program's own spans name.

Idle is every gap between the merged op intervals of the first device plane,
from the start of the first host span whose name starts with ``prefix`` to the
end of the last one (``utils.profiling.span`` events, on the profiler's clock
beside the device's ops). A span that opened before the profiler did is not on
the trace, so before the first one idle cannot be told named from unnamed;
from there on the program's spans are all there, and what none of them covers
is code that escaped them. Each gap is intersected with the spans: a gap that
a span covers by half counts by half. The metric reads nothing (``None``)
where there is no trace, no device plane (the CPU rehearsals), no idle time,
or a program that has no such span.

Prints one line, largest first: ``idle by span: epoch/emit 4.8 ms, ...,
unnamed 0.3 ms``. The harness has reduced the same file already; this reads
it a second time, and the line says what that took.
"""

import time

import xplane


def named_spans(profile, prefix: str) -> list[tuple[int, int, str]]:
    """``(start_ns, end_ns, name)`` of every host event named ``<prefix>...``."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith(xplane.HOST_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                name = str(e.name)
                if name.startswith(prefix):
                    start = int(e.start_ns)
                    out.append((start, start + int(e.duration_ns), name))
    return out


def idle_by_span(profile, prefix: str):
    """``(idle_ns, named_ns, {span name: ns}, aside_ns)`` of the first device plane,
    or ``None`` without a device plane or without a span of that prefix.
    ``aside_ns`` is the idle time before the first such span starts and after the
    last one ends, which is not in ``idle_ns``: there the trace cannot say whether
    a span was open (one that opened before the profiler did is not on it)."""
    planes = xplane.device_op_events(profile)
    spans = named_spans(profile, prefix)
    if not planes or not spans:
        return None
    _, busy = xplane.union_ns(next(iter(planes.values())))
    first, last = min(s[0] for s in spans), max(s[1] for s in spans)
    by_name: dict[str, int] = {}
    pieces = []
    idle = aside = 0
    for (_, gap_lo), (gap_hi, _) in zip(busy, busy[1:]):
        lo, hi = max(gap_lo, first), min(gap_hi, last)
        counted = max(hi - lo, 0)
        idle += counted
        aside += gap_hi - gap_lo - counted
        for start, end, name in spans:
            a, b = max(lo, start), min(hi, end)
            if b > a:
                by_name[name] = by_name.get(name, 0) + b - a
                pieces.append((a, b))
    return idle, xplane.union_ns(pieces)[0], by_name, aside


def read(obs, *, prefix: str):
    t0 = time.perf_counter()
    try:
        profile = xplane.load(xplane.find_trace(obs.trace_dir))
    except FileNotFoundError:
        return None
    load_s = time.perf_counter() - t0
    found = idle_by_span(profile, prefix)
    if found is None or not found[0]:
        return None
    idle, named, by_name, aside = found
    parts = [f"{name} {ns / 1e6:.3f} ms"
             for name, ns in sorted(by_name.items(), key=lambda kv: -kv[1])]
    parts.append(f"unnamed {(idle - named) / 1e6:.3f} ms")
    print(f"idle by span: {', '.join(parts)} (of {idle / 1e6:.3f} ms idle between "
          f"the first `{prefix}` span's start and the last one's end, "
          f"{aside / 1e6:.3f} ms outside them set aside; a host wait returns 2-3 ms "
          f"after its device op ends, so neighbouring spans are split to about "
          f"+-2 ms, the total is exact; the trace read a second time in {load_s:.2f} s)")
    return 100.0 * named / idle
