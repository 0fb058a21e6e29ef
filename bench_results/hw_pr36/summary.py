"""One line a run of the calls' <cell>.jsonl files (pairs.sh writes them): tree, seed, traced or not,
the rate, set-up, memory and, of a traced run, the per-layer metrics and a busy step.
usage: python bench_results/hw_pr36/summary.py chiprun_out/pr36/*/<cell>.jsonl ..."""
import json, sys
for path in sys.argv[1:]:
    print(path)
    for line in open(path):
        r = json.loads(line)
        m = {k: v["value"] for k, v in r.get("metrics", {}).items()}
        dev = r.get("device", {})
        row = {"order": r["order"], "tree": r["tree"], "seed": r["seed"], "trace": r["trace"], "correct": r.get("correct"),
               "rate": m.pop("train_examples_per_s", None), "setup_s": round(m.pop("setup_s"), 1) if "setup_s" in m else None,
               "memory_peak_bytes": dev.get("memory_peak_bytes"), "took_s": r["took_s"]}
        if r["trace"]:
            steps = 16
            row.update({k: round(v, 4) for k, v in m.items()},
                       busy_ms_a_step=round(dev["busy_s"] / steps * 1e3, 2),
                       idle_pct=round(100 * (1 - dev["busy_s"] / dev["window_s"]), 3))
        print("  ", json.dumps(row))
