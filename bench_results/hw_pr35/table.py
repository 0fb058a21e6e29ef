"""PERF.md §5's table from the traced runs' scope_time.json files (benchmark/reducers/scope_time.py
writes one a traced run): ms a traced step by scope and pass, scopes rolled up to their first two
segments (a kernel's name under moe/experts counts in moe/experts), one column a cell.
usage: python table.py <label>=<scope_time.json> ...      [--detail <scope> to list a scope by XLA op and pass]"""
import json, sys
args = [a for a in sys.argv[1:] if "=" in a]
detail = sys.argv[sys.argv.index("--detail") + 1] if "--detail" in sys.argv else None
docs = {a.split("=", 1)[0]: json.load(open(a.split("=", 1)[1])) for a in args}
def roll(scope):     # two segments, and the three flash kernels as one row
    two = "/".join(scope.split("/")[:2])
    return two.split("/flash_")[0] + "/flash_*" if "/flash_" in two else two
cols, keys = {}, set()
for label, d in docs.items():
    ms = lambda ns: ns / 1e6 / d["steps"]
    col = {}
    for scope, which, op, ns in d["rows"]:
        key = roll(scope) if scope else "unnamed"
        col.setdefault(key, {}).setdefault(which or "none", 0.0)
        col[key][which or "none"] += ms(ns)
    col["not in the table"] = {"none": ms(d["not_in_table_ns"])}
    col["(mixed fusions, inside the rows above)"] = {"none": ms(d["mixed_fusions_ns"])}
    col["**the epoch program**"] = {"none": ms(d["epoch_program_ns"])}
    col["other programs: " + ", ".join(sorted(d["other_programs_ns"]))] = {"none": ms(sum(d["other_programs_ns"].values()))}
    cols[label] = col
    keys |= set(col)
    if detail:
        rows = sorted(((ms(ns), which, op) for scope, which, op, ns in d["rows"]
                       if (scope or "unnamed") == detail or (scope or "").startswith(detail + "/")), reverse=True)
        print(label, detail, [(round(v, 2), w, o) for v, w, o in rows[:14]])
special = [k for k in keys if k.startswith(("unnamed", "not in", "(mixed", "**the", "other programs"))]
order = sorted(keys - set(special), key=lambda k: -max(sum(c.get(k, {}).values()) for c in cols.values()))
order += sorted(special, key=lambda k: ("unnamed", "not in", "(mixed", "**the", "other").index(next(p for p in ("unnamed", "not in", "(mixed", "**the", "other") if k.startswith(p))))
cell = lambda p, k: "—" if not p else (f"{sum(p.values()):.1f}" if set(p) <= {"none"} or k in special else
                                    f"{sum(p.values()):.1f} ({p.get('forward', 0):.1f} / {p.get('recompute', 0):.1f} / {p.get('backward', 0):.1f})")
print("| Scope: ms a traced step, total (forward / recompute / backward) | " + " | ".join(f"`{l}`" for l in cols) + " |")
print("| --- |" + " --- |" * len(cols))
for k in order:
    name = k if k.startswith(("**", "(", "other", "not in", "unnamed")) else f"`{k}`"
    print(f"| {name} | " + " | ".join(cell(c.get(k), k) for c in cols.values()) + " |")
