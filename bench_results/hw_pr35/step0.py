"""ISSUE 35, step 0: what a v5e's trace events carry. One traced run of a cell through
benchmark/run.py, in this process, with ``telemetry.aot_compile`` wrapped so that the epoch
program's compiled text is kept (and the time ``as_text()`` takes is read); then every plane,
line and stat of the trace is printed, and trace and text come back under chiprun_out/hw_pr35/.
usage: python bench_results/hw_pr35/step0.py [cell] [seed]      (chip only)"""
import collections, gzip, json, os, shutil, sys, time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
OUT = os.path.join(ROOT, "chiprun_out", "hw_pr35")
os.makedirs(OUT, exist_ok=True)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
cell = sys.argv[1] if len(sys.argv) > 1 else "lfm2_moe_train_8k"
seed = sys.argv[2] if len(sys.argv) > 2 else "3500000001"

from csed_514_project_distributed_training_using_pytorch_tpu.utils import telemetry as T

original = T.aot_compile


def keeping_text(jit_fn, *args):
    compiled, aot = original(jit_fn, *args)
    if compiled is not None:
        t0 = time.perf_counter()
        text = compiled.as_text()
        t1 = time.perf_counter()
        with gzip.open(os.path.join(OUT, f"{cell}.epoch.txt.gz"), "wt") as fh:
            fh.write(text)
        print(f"step0: as_text() {t1 - t0:.2f} s, {len(text)} bytes, "
              f"{text.count(chr(10))} lines, written in {time.perf_counter() - t1:.2f} s",
              flush=True)
    return compiled, aot


T.aot_compile = keeping_text
import run as bench_run

rc = bench_run.main(["--workload", cell, "--seed", seed, "--seconds", "40", "--trace", "1"])
print("step0: run.py returned", rc, flush=True)

import xplane

path = xplane.find_trace(os.path.join(ROOT, ".bench_work", cell, "trace"))
print("step0: trace", path, os.path.getsize(path), "bytes")
profile = xplane.load(path)
report = []
say = lambda *a: report.append(" ".join(str(x) for x in a))
stats_of = lambda e: [(str(k), str(v)[:200]) for k, v in e.stats]
for plane in profile.planes:
    lines = list(plane.lines)
    say("PLANE", plane.name, "stats", [(str(k), str(v)[:80]) for k, v in plane.stats][:30])
    for line in lines:
        events = list(line.events)
        if not events:
            say("  LINE", line.name, 0)
            continue
        lo = min(e.start_ns for e in events)
        hi = max(e.start_ns + e.duration_ns for e in events)
        say("  LINE", repr(line.name), len(events), "events", f"{(hi - lo) / 1e9:.3f} s")
        if not plane.name.startswith("/device:"):
            for e in events[:2]:
                say("     ", str(e.name)[:100], e.start_ns, e.duration_ns, stats_of(e))
            continue
        for e in events[:3] + events[len(events) // 2:len(events) // 2 + 3]:
            say("     ", str(e.name)[:160], e.start_ns, e.duration_ns, stats_of(e))
        keys = collections.defaultdict(collections.Counter)
        for e in events:
            for k, v in e.stats:
                keys[str(k)][str(v)[:120]] += 1
        for k, values in keys.items():
            few = values.most_common(12)
            say("     STAT", k, len(values), "distinct;", few)
        if line.name in ("XLA Modules", "Steps", "Framework Ops", "XLA TraceMe") \
                or len(events) <= 80:
            for e in events[:80]:
                say("     EV", str(e.name)[:120], e.start_ns, e.duration_ns, stats_of(e))
        if line.name == xplane.OP_LINE:
            same = [e for e in events if str(e.name).split(" = ")[0].strip().lstrip("%")
                    in ("fusion.12", "fusion.3", "copy.1")]
            seen = set()
            for e in same:
                key = tuple(sorted(stats_of(e)))
                if key not in seen and len(seen) < 12:
                    seen.add(key)
                    say("     SAME-NAME", str(e.name)[:300], e.start_ns, e.duration_ns,
                        stats_of(e))
            whiles = [e for e in events if str(e.name).lstrip("%").startswith("while")]
            for e in whiles[:6]:
                say("     WHILE", str(e.name)[:200], e.start_ns, e.duration_ns, stats_of(e))
with open(os.path.join(OUT, f"{cell}.step0.txt"), "w") as fh:
    fh.write("\n".join(report) + "\n")
print("\n".join(report)[-20000:])
gz = os.path.join(OUT, f"{cell}.xplane.pb.gz")
with open(path, "rb") as src, gzip.open(gz, "wb", compresslevel=6) as dst:
    shutil.copyfileobj(src, dst)
if os.path.getsize(gz) > 45 * 2 ** 20:
    os.remove(gz)
    print("step0: the trace is too large to bring back")
tele = os.path.join(ROOT, ".bench_work", cell, "telemetry.jsonl")
if os.path.exists(tele):
    shutil.copy(tele, os.path.join(OUT, f"{cell}.telemetry.jsonl"))
print(json.dumps({"rc": rc, "sizes": {f: os.path.getsize(os.path.join(OUT, f))
                                      for f in os.listdir(OUT)}}))
