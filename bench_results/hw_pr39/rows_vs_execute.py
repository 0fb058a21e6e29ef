"""An epoch's `execute_s` beside the rows that arrived at the held experts, by seed, from the
epoch events a call kept (chiprun_out/hw_pr39/<run>.epochs.jsonl).
usage: python rows_vs_execute.py <run>.epochs.jsonl ..."""
import json, sys
import numpy as np
for path in sys.argv[1:]:
    events = [json.loads(line) for line in open(path)][1:]      # the first is the warm-up epoch
    execute = [e["execute_s"] for e in events]
    rows = [float(np.asarray(e["expert_rows"]).sum()) for e in events]
    print(json.dumps({"run": path.rsplit("/", 1)[-1].replace(".epochs.jsonl", ""), "epochs": len(events),
                      "execute_s": [round(x, 3) for x in execute], "rows_k": [round(r / 1e3) for r in rows],
                      "mean_execute_s": round(float(np.mean(execute)), 4), "mean_rows_k": round(float(np.mean(rows)) / 1e3, 1)}))
