"""What a call of pairs.sh left under chiprun_out/pr48/<call>/, a run a line: the result line's
end-to-end and per-layer numbers, and for a traced run the scope table's rows under one scope by
sub-scope and XLA op, ms a traced step, beside the first traced run of the cell (the parent).
usage: python3 bench_results/hw_pr48/summarize.py <call dir> [scope prefix, default eva_mixer]"""
import collections, glob, json, os, sys
call, prefix = sys.argv[1], (sys.argv[2] if len(sys.argv) > 2 else "eva_mixer")
for path in sorted(glob.glob(os.path.join(call, "*.jsonl"))):
    if ".compile." in path:
        continue
    for line in open(path):
        r = json.loads(line)
        m = {k: round(v["value"], 4) for k, v in r.get("metrics", {}).items()}
        print(os.path.basename(path)[:-6], r["tree"], "seed", r["seed"], "trace", r["trace"], "rc", r["rc"],
              "took", r["took_s"], "correct", r.get("correct"), "memory_peak_bytes",
              r.get("device", {}).get("memory_peak_bytes"), "busy_s", r.get("device", {}).get("busy_s"), m)
base = None
for path in sorted(glob.glob(os.path.join(call, "*.scope_time.json")), key=lambda p: int(os.path.basename(p).split(".")[1])):
    d = json.load(open(path))
    steps = d["steps"]
    rows = collections.Counter()
    for scope, which, op, ns in d["rows"]:
        if scope and (scope == prefix or scope.startswith(prefix + "/")):
            rows[(scope, op)] += ns / 1e6 / steps
    total = sum(rows.values())
    print(f"\n{os.path.basename(path)}: epoch program {d['epoch_program_ns'] / 1e6 / steps:.1f} ms a step, "
          f"{prefix} {total:.1f}, unnamed {d['unnamed_ns'] / 1e6 / steps:.1f} "
          f"(copy {d['unnamed_by_op'].get('copy', 0) / 1e6 / steps:.1f})")
    if base is None:
        base = rows
    for key in sorted(set(rows) | set(base), key=lambda k: -max(rows[k], base[k])):
        if max(rows[key], base[key]) >= 1.0:
            print(f"  {rows[key]:8.1f} [{base[key]:8.1f}]  {key[0]}  {key[1]}")
