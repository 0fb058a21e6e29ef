"""Device time of the epoch program by the model's own scopes.

A trace names a device op by its instruction (``%fusion.12 = ...``); the program says
which ``jax.named_scope`` and which pass (forward, recompute, backward) made each
instruction of its epoch program (``utils.profiling.scope_table`` of the executable
that runs, written by ``train/lm.py`` to ``<telemetry path>.scopes.json`` beside the
run's telemetry). This joins the two. Of the first device plane's ``XLA Ops`` line,
every event is assigned to the program whose run covers it (the ``XLA Modules`` line of
the same plane: one event a run of a program, named ``<module>(<fingerprint>)``), self
times are taken per (program, instruction) with ``xplane.self_times`` (a ``while`` spans
its body), and the epoch program's are looked up in the table:

    scope, pass   the instruction's ``op_name`` holds a scope
    unnamed       it holds none, or the instruction has no ``op_name`` (copies the
                  compiler placed, mostly): listed by XLA's op name in the file
    not in the table   the instruction is not in the table at all: the table describes
                  another executable than the one that ran
    mixed fusions (inside the first two) fusions whose fused computation holds more
                  than one scope; they count whole under their root's
    other programs   ``jit_evaluate`` and the small host-side programs, by module name:
                  ``fusion.12`` exists in several programs, and only the epoch
                  program's is in the table

The four add up to the sum of ``xplane.self_times`` over the line, to the nanosecond.
Reduced once a run (the harness loads a reducer's module anew for every metric): the
result is kept on ``obs.trace["scope_time"]``, printed as one line, largest first, in
ms a traced step, and written whole to ``<work>/scope_time.json``. A metric reads
nothing (``None``) where there is no trace, no device plane, no table beside the
telemetry (a program older than the table) or no run of the table's program on the
trace.

Parameters of a metric: ``scopes`` (scope prefixes: ``moe/route`` selects itself and
what is under it; absent: every instruction), ``named`` (true: only instructions with a
scope), ``passes``, ``exclude_ops`` (XLA op names, instances added up, as
``xplane.op_name`` gives them: a kernel's ``name``), ``as``: ``share`` (% of the epoch
program's device self time) or ``ms_per_step``.

As a script, for an operator's ``train.lm --profile --telemetry t.jsonl`` run:

    python benchmark/reducers/scope_time.py <profile dir> <t.jsonl.scopes.json>
"""

import bisect
import json
import os
import sys
import time

if __name__ == "__main__":      # the benchmark's modules are one directory up
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import xplane

MODULE_LINE = "XLA Modules"
PASS_LETTERS = (("forward", "f"), ("recompute", "r"), ("backward", "b"))


def instruction(text: str) -> str:
    """``%fusion.12 = f32[8] fusion(...)`` -> ``fusion.12``: the number kept."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def module_name(text: str) -> str:
    """``jit_epoch(5186353903213891921)`` -> ``jit_epoch``."""
    return text.split("(", 1)[0].strip()


def program_times(profile) -> tuple[dict, dict] | None:
    """``({(program, instruction): self ns}, {program: runs})`` of the first device plane
    that ran an op, or ``None`` without one. An op belongs to the run of a program that
    covers its start; ``""`` where none does (a plane with no ``XLA Modules`` line)."""
    for plane in profile.planes:
        if not plane.name.startswith(xplane.DEVICE_PREFIX):
            continue
        lines = {line.name: line for line in plane.lines}
        if xplane.OP_LINE not in lines:
            continue
        runs = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                       module_name(str(e.name)))
                      for e in getattr(lines.get(MODULE_LINE), "events", ()))
        starts = [r[0] for r in runs]

        def program(at: int) -> str:
            i = bisect.bisect_right(starts, at) - 1
            return runs[i][2] if i >= 0 and at < runs[i][1] else ""

        events = sorted(((int(e.start_ns), int(e.start_ns + e.duration_ns),
                          (program(int(e.start_ns)), instruction(str(e.name))))
                         for e in lines[xplane.OP_LINE].events),
                        key=lambda e: (e[0], -e[1]))
        if not events:
            continue
        calls: dict[str, int] = {}
        for _, _, name in runs:
            calls[name] = calls.get(name, 0) + 1
        return xplane.self_times(events), calls
    return None


def join(times: dict, table: dict) -> dict:
    """Self times by (program, instruction) against one program's table: every
    nanosecond lands in exactly one of ``rows`` (the table's program: ``[scope, pass,
    XLA op name, ns]``, scope ``None`` for unnamed), ``not_in_table`` or
    ``other_programs``."""
    module, ops, mixed = table["module"], table["ops"], set(table.get("mixed", ()))
    rows: dict[tuple, int] = {}
    missing: dict[str, int] = {}
    others: dict[str, int] = {}
    mixed_ns = 0
    for (program, name), ns in times.items():
        if program != module:
            others[program] = others.get(program, 0) + ns
        elif name not in ops:
            missing[xplane.op_name(name)] = missing.get(xplane.op_name(name), 0) + ns
        else:
            scope, which = ops[name]
            key = (scope, which, xplane.op_name(name))
            rows[key] = rows.get(key, 0) + ns
            if name in mixed:
                mixed_ns += ns
    return {"module": module,
            "rows": [[*key, ns] for key, ns in sorted(rows.items(), key=lambda kv: -kv[1])],
            "not_in_table": missing, "other_programs": others, "mixed_ns": mixed_ns}


def selected_ns(joined: dict, *, scopes=None, named=False, passes=None, exclude_ops=()) -> int:
    total = 0
    for scope, which, op, ns in joined["rows"]:
        if scopes is not None and not any(
                scope == s or (scope or "").startswith(s + "/") for s in scopes):
            continue
        if (named and scope is None) or (passes is not None and which not in passes) \
                or op in exclude_ops:
            continue
        total += ns
    return total


def epoch_ns(joined: dict) -> int:
    """The table's program's device self time: its rows and what the table lacks."""
    return sum(r[3] for r in joined["rows"]) + sum(joined["not_in_table"].values())


def by_scope(joined: dict) -> tuple[dict, dict]:
    """``({scope: {pass: ns}}, {XLA op name: ns} of the unnamed)``."""
    scopes: dict[str, dict] = {}
    unnamed: dict[str, int] = {}
    for scope, which, op, ns in joined["rows"]:
        if scope is None:
            unnamed[op] = unnamed.get(op, 0) + ns
        else:
            passes = scopes.setdefault(scope, {})
            passes[which] = passes.get(which, 0) + ns
    return scopes, unnamed


def line(joined: dict, steps: int) -> str:
    ms = lambda ns: f"{ns / 1e6 / steps:.3f}"
    scopes, unnamed = by_scope(joined)
    parts = [f"{scope} {ms(sum(p.values()))} ("
             + " ".join(f"{letter} {ms(p.get(which, 0))}" for which, letter in PASS_LETTERS)
             + ")" for scope, p in sorted(scopes.items(),
                                          key=lambda kv: (-sum(kv[1].values()), kv[0]))]
    parts += [f"unnamed {ms(sum(unnamed.values()))}",
              f"not in the table {ms(sum(joined['not_in_table'].values()))}",
              f"mixed fusions {ms(joined['mixed_ns'])}"]
    others = ", ".join(f"{name or '(no program)'} {ms(ns)}" for name, ns in
                       sorted(joined["other_programs"].items(), key=lambda kv: -kv[1]))
    return (f"device time by scope ({joined['module']}, ms a step over {steps} steps): "
            + ", ".join(parts) + f"; other programs: {others or 'none'}")


def document(joined: dict, steps: int, calls: dict) -> dict:
    """The whole table, as ``scope_time.json`` holds it (ns over the trace)."""
    scopes, unnamed = by_scope(joined)
    return {"module": joined["module"], "steps": steps, "program_runs": calls,
            "epoch_program_ns": epoch_ns(joined), "scopes": scopes,
            "unnamed_ns": sum(unnamed.values()), "unnamed_by_op": unnamed,
            "not_in_table_ns": sum(joined["not_in_table"].values()),
            "not_in_table_by_op": joined["not_in_table"],
            "mixed_fusions_ns": joined["mixed_ns"],
            "other_programs_ns": joined["other_programs"],
            "rows": joined["rows"]}


def find_table(work: str) -> dict | None:
    """The newest ``*.scopes.json`` beside the run's telemetry, or ``None``."""
    try:
        paths = [os.path.join(work, f) for f in os.listdir(work)
                 if f.endswith(".scopes.json")]
    except FileNotFoundError:
        return None
    if not paths:
        return None
    with open(max(paths, key=os.path.getmtime)) as fh:
        return json.load(fh)


def _reduced(obs) -> dict | None:
    """The join of this run, made once and kept on ``obs.trace``."""
    if not obs.trace or not obs.trace.get("devices"):
        return None
    if "scope_time" in obs.trace:
        return obs.trace["scope_time"]
    obs.trace["scope_time"] = None
    work = os.path.dirname(obs.trace_dir)
    table = find_table(work)
    steps = int(obs.trace_units.get("steps", 0))
    if table is None or not steps:
        return None
    t0 = time.perf_counter()
    try:
        profile = xplane.load(xplane.find_trace(obs.trace_dir))
    except FileNotFoundError:
        return None
    found = program_times(profile)
    if found is None:
        return None
    times, calls = found
    joined = join(times, table)
    if not joined["rows"] and not joined["not_in_table"]:
        print(f"device time by scope: no run of {table['module']} on the trace "
              f"(programs: {sorted(calls)})")
        return None
    print(line(joined, steps) + f" (the trace read again and joined in "
          f"{time.perf_counter() - t0:.2f} s)")
    with open(os.path.join(work, "scope_time.json"), "w") as fh:
        json.dump(document(joined, steps, calls), fh)
    obs.trace["scope_time"] = joined
    return joined


def read(obs, **params):
    joined = _reduced(obs)
    if joined is None:
        return None
    how = params.pop("as")
    ns = selected_ns(joined, **params)
    if how == "share":
        return 100.0 * ns / epoch_ns(joined)
    if how == "ms_per_step":
        return ns / 1e6 / int(obs.trace_units["steps"])
    raise ValueError(f"as: {how!r} is not share or ms_per_step")


def main(argv) -> int:
    if len(argv) != 2:
        print(__doc__.split("As a script")[1].strip(), file=sys.stderr)
        return 2
    with open(argv[1]) as fh:
        table = json.load(fh)
    found = program_times(xplane.load(xplane.find_trace(argv[0])))
    if found is None:
        print("no device plane with an op on the trace", file=sys.stderr)
        return 1
    times, calls = found
    joined = join(times, table)
    runs = calls.get(table["module"], 0)
    steps = runs * int(table.get("steps_per_call") or 1)
    if not steps:
        print(f"no run of {table['module']} on the trace (programs: {sorted(calls)})",
              file=sys.stderr)
        return 1
    print(line(joined, steps))
    print(f"{runs} runs of {table['module']}, {epoch_ns(joined) / 1e6 / steps:.3f} ms a "
          f"step of device self time; named "
          f"{100.0 * selected_ns(joined, named=True) / epoch_ns(joined):.2f} %, recompute "
          f"{100.0 * selected_ns(joined, passes=['recompute']) / epoch_ns(joined):.2f} %")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
