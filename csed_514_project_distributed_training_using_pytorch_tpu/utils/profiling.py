"""Optional tracing/profiling: the profiler switch, and the one span primitive.

The reference's only instrument is coarse wall-clock (``t0 = time.time()``, reference
``src/train.py:10,99``; SURVEY.md §5 "tracing/profiling") — kept, in ``utils.metrics.Stopwatch``,
because it *is* the baseline metric. This module adds what the reference lacks:

- ``maybe_profile``: an opt-in ``jax.profiler`` device trace (TPU timeline incl. ICI
  collectives, viewable in TensorBoard/Perfetto) behind a flag, costing nothing when
  disabled.
- ``span`` / ``step`` / ``drain``: a loop names its own time. A span is a host event
  on the profiler's own clock (``jax.profiler.TraceAnnotation``), beside the device's
  ops whenever anyone is taking a trace (``--profile``, a benchmark), and a
  ``perf_counter`` duration added under its name to a per-thread table in memory that
  the loop drains into its own telemetry event once per iteration (``train/lm.py``
  reads the ``epoch/*`` names → the ``epoch`` event's ``*_s`` fields). Nothing is
  written per span; with no trace running a span costs two clock reads and one
  inactive ``TraceMe``.

- ``scope_of`` / ``scope_table``: which ``jax.named_scope`` and which pass (forward,
  recompute, backward) made each instruction of a compiled program, read from the
  ``op_name`` the compiler kept in the program's own text. A trace names device ops by
  instruction (``fusion.12``); joined to this table, device time is by the model's scopes
  (``benchmark/reducers/scope_time.py`` does the join, and prints it for any
  ``--profile --telemetry`` run).

The structured (always-parseable, per-run) counterpart is ``utils/telemetry.py`` — the
trace is for timeline forensics, telemetry for the numbers.
"""

from __future__ import annotations

import contextlib
import os
import re
import threading
import time

import jax

from csed_514_project_distributed_training_using_pytorch_tpu.utils import metrics


@contextlib.contextmanager
def maybe_profile(enabled: bool, log_dir: str):
    """Capture a jax.profiler trace of the enclosed block when ``enabled``.

    Process-0 gated INTERNALLY (one trace per fleet, not one per host — every rank
    tracing would multiply IO and clobber nothing useful), creates ``log_dir`` if
    missing, and logs the trace path so a run's stdout says where its timeline went.
    """
    if not enabled or not metrics.is_logging_process():
        yield
        return
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        metrics.log(f"Saved profiler trace to {log_dir}")


class _ThreadSpans(threading.local):
    """One thread's open spans and its table of closed ones. Per thread, so a span
    on a worker (the write-behind checkpointer) is annotated on that thread's own
    line of the trace and never lands in the loop's table: it does not block the loop."""

    def __init__(self):
        self.stack: list[_Span] = []            # open spans, outermost first
        self.seconds: dict[str, float] = {}
        self.t_drain = time.perf_counter()


_spans = _ThreadSpans()


class _Span:
    __slots__ = ("name", "_annotation", "_t0")

    def __init__(self, name: str, annotation):
        self.name, self._annotation = name, annotation

    def __enter__(self):
        _spans.stack.append(self)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        local = _spans
        local.stack.pop()
        local.seconds[self.name] = local.seconds.get(self.name, 0.0) + t1 - self._t0
        return False


def span(name: str) -> _Span:
    """``with span("epoch/eval"): ...`` — a named host region. ``name`` is what the
    trace shows and what ``drain()`` keys by."""
    return _Span(name, jax.profiler.TraceAnnotation(name))


def step(name: str, n: int) -> _Span:
    """One iteration of a loop: a span whose trace event carries ``step_num``
    (``StepTraceAnnotation``), so a timeline can bound the spans opened inside it."""
    return _Span(name, jax.profiler.StepTraceAnnotation(name, step_num=n))


def drain() -> tuple[dict[str, float], float]:
    """``({name: seconds}, period_s)`` of the calling thread's spans since its
    previous drain, and reset. A loop that drains once per iteration, from inside one
    of its step's children, gets one of each child: the tail of the previous step and
    the head of the open one. The spans open at this instant (the step, the child it
    is called from) are split here: their time so far counts in this period, the rest
    in the next, so spans that do not nest in each other are disjoint pieces of the
    period and never sum past it."""
    local = _spans
    now = time.perf_counter()
    for open_span in local.stack:
        local.seconds[open_span.name] = (local.seconds.get(open_span.name, 0.0)
                                         + now - open_span._t0)
        open_span._t0 = now
    out = local.seconds, now - local.t_drain
    local.seconds, local.t_drain = {}, now
    return out


# -- from a compiled program's text to the scope and the pass of each instruction --------

# Segments of an ``op_name`` that say how the program is built, not who wrote the op.
_STRUCTURE = frozenset({"while", "body", "cond", "closed_call", "checkpoint",
                        "rematted_computation", "remat", "remat2", "pjit", "core_call",
                        "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
                        "custom_lin", "named_call", "shard_map"})
_TRANSFORMS = frozenset({"transpose", "jvp", "vmap"})   # jvp(scope): keep what it wraps
_CALL = re.compile(r"([\w.\-]*)\(([^()]*)\)")          # jit(f): a function, no scope
_SCOPE = re.compile(r"[A-Za-z_][\w.\-]*\Z")
_INSTANCE = re.compile(r"_\d+\Z")                                   # flax: TransformerBlock_3
_BRANCH = re.compile(r"branch_\d+_fun\Z")                            # a ``lax.cond``'s arms
PASSES = ("forward", "recompute", "backward")


def scope_of(op_name: str | None) -> tuple[str | None, str | None]:
    """``(scope, pass)`` of one instruction, from the ``op_name`` of its metadata.

    An ``op_name`` is the name stack at the op's trace, then the primitive:
    ``jit(epoch)/while/body/closed_call/transpose(jvp(jvp()))/checkpoint/
    rematted_computation/moe/route/dot_general``. ``pass`` is ``recompute`` if
    ``rematted_computation`` is a segment (``jax.checkpoint`` running a block's forward
    again), else ``backward`` if a ``transpose(`` wraps anything, else ``forward``.
    ``scope`` is the path of what ``jax.named_scope`` (a flax module, a Pallas kernel's
    ``name``) pushed: the structural segments and the primitive dropped, ``jvp(...)`` /
    ``transpose(...)`` unwrapped around a scope opened inside them, ``jit(f)`` dropped
    whole, a flax instance's number dropped (``TransformerBlock_3`` adds to
    ``TransformerBlock``); ``None`` when nothing is left. No scope's name is known
    here: one added later is found as it is. ``(None, None)`` without an ``op_name``."""
    if not op_name:
        return None, None
    which = ("recompute" if "rematted_computation" in op_name
             else "backward" if "transpose(" in op_name else "forward")
    path, before = op_name, None
    while path != before:       # innermost parentheses first
        before, path = path, _CALL.sub(
            lambda m: m.group(2) if m.group(1) in _TRANSFORMS else "", path)
    kept = [_INSTANCE.sub("", seg) for seg in path.split("/")[:-1]
            if seg not in _STRUCTURE and _SCOPE.match(seg) and not _BRANCH.match(seg)]
    return "/".join(kept) or None, which


_COMPUTATION = re.compile(r"(?:ENTRY )?%(\S+) \(.*\) -> .* \{\s*\Z")
_INSTRUCTION = re.compile(r"\s+(?:ROOT )?%(\S+) = ")
_OPCODE = re.compile(r"(?:^|[\]})] )([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\b(calls|to_apply)=%([^\s,)}]+)")
# written in the text, never run as an op of their own
_FREE = frozenset({"parameter", "constant", "tuple", "get-tuple-element", "bitcast"})


def scope_table(text: str, *, detail: bool = False) -> dict:
    """A compiled program's text (``compiled.as_text()``) as ``{"module": its name,
    "ops": {instruction: [scope, pass]}, "mixed": [instructions]}``.

    ``ops`` has one entry an instruction that can run on the device, from every
    computation but the ones inside an instruction (a fusion's fused computation, a
    reduction's or a sort's scalar function): the ``while`` bodies hold the step. A
    profiler's trace names a device op by exactly that instruction name. ``mixed``:
    the fusions whose fused computation (and the fusions nested in it) holds
    instructions of more than one scope; such a fusion still counts whole under its
    own ``op_name``, which is its root's. A fusion the compiler left without an
    ``op_name`` (a scatter it built around a gradient's ``add_any``) takes the scope
    and pass its contents agree on, and stays unnamed where they do not.
    ``detail=True`` adds ``"detail": {instruction: {"shape", "op_name"}}``."""
    module = ""
    computations: dict[str, list] = {}     # name -> [(instruction, opcode, op_name, shape)]
    inside: set[str] = set()               # computations that are part of one instruction
    fusions: dict[str, str] = {}           # fusion instruction -> its fused computation
    current = None
    for line in text.splitlines():
        if current is None:
            if line.startswith("HloModule "):
                module = line[len("HloModule "):].split(",", 1)[0].strip()
            else:
                m = _COMPUTATION.match(line)
                if m:
                    current = computations.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.group(1), line[m.end():]
        op = _OPCODE.search(rest)       # the first `<shape> opcode(`: a tuple's shape has none
        opcode = op.group(1) if op else ""
        shape = rest[:op.start(1)].strip() if op else ""
        named = _OP_NAME.search(line)
        current.append((name, opcode, named.group(1) if named else None, shape))
        for how, callee in _CALLS.findall(line):
            if how == "calls" and opcode == "fusion":
                fusions[name] = callee
                inside.add(callee)
            elif how == "to_apply" and opcode != "call":
                inside.add(callee)
    def held(callee: str) -> set:
        """``(scope, pass)`` of every instruction with an ``op_name`` in a fused
        computation and in the fusions nested in it."""
        found = set()
        for name, _, op_name, _ in computations.get(callee, ()):
            if op_name:
                found.add(scope_of(op_name))
            if name in fusions:
                found |= held(fusions[name])
        return found

    ops, details, mixed = {}, {}, []
    for computation, instructions in computations.items():
        if computation in inside:
            continue
        for name, opcode, op_name, shape in instructions:
            if opcode in _FREE:
                continue
            ops[name] = list(scope_of(op_name))
            if name in fusions:
                contents = held(fusions[name])
                if len({scope for scope, _ in contents}) > 1:
                    mixed.append(name)
                if not op_name and len(contents) == 1:
                    (agreed,) = contents
                    ops[name] = list(agreed)
            if detail:
                details[name] = {"shape": shape[:60], "op_name": op_name}
    table = {"module": module, "ops": ops, "mixed": mixed}
    if detail:
        table["detail"] = details
    return table
