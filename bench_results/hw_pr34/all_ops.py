"""Every device op of a traced run's window, by XLA's name: self seconds, the benchmark's own
reduction (benchmark/xplane.py::reduce, whose result line keeps the first ten).
usage: python3 all_ops.py <tree root whose .bench_work/<cell>/trace holds the trace> <cell> <out.json>"""
import importlib.util, json, os, sys
root, cell, out = sys.argv[1:4]
spec = importlib.util.spec_from_file_location("bench_xplane", os.path.join(root, "benchmark", "xplane.py"))
xplane = importlib.util.module_from_spec(spec); spec.loader.exec_module(xplane)
got = xplane.reduce(xplane.load(xplane.find_trace(os.path.join(root, ".bench_work", cell, "trace"))))
with open(out, "w") as fh:
    json.dump({"busy_s": got["busy_s"], "span_s": got["span_s"],
               "ops": sorted(got["ops"].items(), key=lambda kv: -kv[1])}, fh)
print(f"  all ops: {len(got['ops'])} names, busy {got['busy_s']:.4f} s -> {out}")
