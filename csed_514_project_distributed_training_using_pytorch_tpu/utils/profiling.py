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

The structured (always-parseable, per-run) counterpart is ``utils/telemetry.py`` — the
trace is for timeline forensics, telemetry for the numbers.
"""

from __future__ import annotations

import contextlib
import os
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
