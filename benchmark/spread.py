"""python benchmark/spread.py <cell>.jsonl: the spread of each end-to-end
metric in each set of runs (interquartile distance over the median, as the
contract defines it), the wider of the two, five times that, and the second
set's median against the first's."""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import stats    # noqa: E402


def main(path: str) -> int:
    with open(path) as fh:
        rows = [json.loads(line) for line in fh if line.startswith("{")]
    sets = sorted({r["set"] for r in rows})
    bad = [r for r in rows if not r["correct"] or r["rc"] != 0]
    print(f"{path}: {len(rows)} runs, {len(bad)} not correct or failed; "
          f"peak memory {max(r['device']['memory_peak_bytes'] for r in rows)} bytes")
    for metric in rows[0]["metrics"]:
        per = {s: [r["metrics"][metric]["value"] for r in rows if r["set"] == s]
               for s in sets}
        # each side's first run compiles; set-up's own spread is not judged
        spreads = {s: stats.iqr_share(v) for s, v in per.items() if len(v) >= 2}
        medians = {s: stats.median(v) for s, v in per.items()}
        widest = max(spreads.values()) if spreads else float("nan")
        drift = (medians[sets[-1]] / medians[sets[0]] - 1.0) if len(sets) > 1 else 0.0
        print(f"  {metric}: medians {medians}, spreads "
              f"{ {s: round(v, 5) for s, v in spreads.items()} }, widest {widest:.5f}, "
              f"x5 = {5 * widest:.4f}, second set vs first {drift:+.4f}")
        print(f"    values: { {s: [round(x, 4) for x in v] for s, v in per.items()} }")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
