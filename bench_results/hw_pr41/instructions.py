"""Device self time of a traced run by program and instruction (``fusion.2013``, not the op name
the result line adds instances up under), ms a traced step, largest first: what
benchmark/reducers/scope_time.py joins to the scope table, before the join. Read beside the text
of compile_epoch.py's compile, whose instructions carry the same numbers.
usage: python3 instructions.py <tree root whose .bench_work/<cell>/trace holds the trace> <cell> <out.json> [steps]"""
import json, os, sys
root, cell, out = sys.argv[1:4]
steps = int(sys.argv[4]) if len(sys.argv) > 4 else 8
sys.path[:0] = [os.path.join(root, "benchmark"), os.path.join(root, "benchmark", "reducers")]
import xplane, scope_time
times, runs = scope_time.program_times(xplane.load(xplane.find_trace(os.path.join(root, ".bench_work", cell, "trace"))))
rows = sorted(([program, name, round(ns / 1e6 / steps, 4)] for (program, name), ns in times.items()),
              key=lambda r: -r[2])
with open(out, "w") as fh:
    json.dump({"runs": runs, "steps": steps, "ms_per_step": rows}, fh)
print(f"  instructions: {len(rows)} of {runs} -> {out}")
