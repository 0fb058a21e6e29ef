"""Cut a recorded .xplane.pb down to a fixture a repository can carry.

    python benchmark/tests/trim_trace.py <trace dir or .xplane.pb> <out.xplane.pb>

Keeps the first device plane's first XLA ops up to the first idle gap longer
than a millisecond and a few ops after it, and the host events that overlap
that stretch on the ``python`` and main threads. Times are kept as recorded.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(src, dst, ops=400, after=40):
    from jax.profiler import ProfileData

    import xplane
    path = src if src.endswith(".pb") else xplane.find_trace(src)
    profile = ProfileData.from_file(path)
    device = [p for p in profile.planes if p.name.startswith(xplane.DEVICE_PREFIX)][0]
    line = [l for l in device.lines if l.name == xplane.OP_LINE][0]
    events = [(int(e.start_ns), int(e.duration_ns), str(e.name)) for e in line.events]
    events.sort()
    keep, gap_at = [], None
    for i, ev in enumerate(events):
        if gap_at is None and keep and ev[0] - (keep[-1][0] + keep[-1][1]) > 1_000_000 \
                and len(keep) >= ops:
            gap_at = len(keep)
        if gap_at is not None and len(keep) >= gap_at + after:
            break
        if gap_at is None and len(keep) >= ops:
            # skip ahead to the op before the next long gap
            nxt = next((j for j in range(i, len(events) - 1)
                        if events[j + 1][0] - (events[j][0] + events[j][1]) > 1_000_000),
                       None)
            if nxt is None:
                break
            keep.append(events[nxt])
            gap_at = len(keep)
            keep.extend(events[nxt + 1:nxt + 1 + after])
            break
        keep.append(ev)
    lo, hi = keep[0][0], keep[-1][0] + keep[-1][1]
    host_lines = []
    for plane in profile.planes:
        if not plane.name.startswith(xplane.HOST_PREFIX):
            continue
        for l in plane.lines:
            if not (l.name == "python" or l.name.startswith("main/")):
                continue
            evs = [(int(e.start_ns), int(e.duration_ns), str(e.name)) for e in l.events
                   if int(e.start_ns) < hi and int(e.start_ns + e.duration_ns) > lo
                   and int(e.duration_ns) > 200_000]
            host_lines.append((l.name, evs[:300]))
    names, out = {}, []

    def meta(name):
        # XLA's name and the start of its HLO text; no quotes or backslashes
        name = name[:120].replace('"', "'").replace("\\", "/")
        return names.setdefault(name, len(names) + 1)

    def line_text(lid, name, evs):
        body = "".join(
            f"    events {{ metadata_id: {meta(n)} offset_ps: {s * 1000} "
            f"duration_ps: {d * 1000} }}\n" for s, d, n in evs)
        return f'  lines {{ id: {lid} name: "{name}" timestamp_ns: 0\n{body}  }}\n'

    dev = line_text(1, xplane.OP_LINE, keep)
    host = "".join(line_text(i + 1, n, e) for i, (n, e) in enumerate(host_lines))
    metas = "".join(
        f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
        for n, i in names.items())
    text = (f'planes {{ id: 1 name: "{device.name}"\n{dev}{metas}}}\n'
            f'planes {{ id: 2 name: "/host:CPU"\n{host}{metas}}}\n')
    with open(dst, "wb") as fh:
        fh.write(ProfileData.text_proto_to_serialized_xspace(text))
    print(f"{len(keep)} device ops, {sum(len(e) for _, e in host_lines)} host events, "
          f"{os.path.getsize(dst)} bytes")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
