"""Structured run telemetry: machine-readable record of WHAT ran and WHERE the time went.

The reference's entire observability surface is ``t0 = time.time()`` plus print lines
(SURVEY.md §5), faithfully reproduced in ``utils/metrics.py`` — which means nothing
downstream can answer "what mesh was that run on", "how much of epoch 1 was XLA
compile", or "was training healthy" without parsing stdout. This module is the
structured layer every perf PR proves its numbers through:

- **events** — one JSON object per line (strict JSONL: non-finite floats become
  ``null``), each typed by an ``"event"`` key. The types and their producers:

  =============  =====================================================================
  ``manifest``   once per run: config snapshot, mesh axes/shape, device kind+count,
                 process count, jax/jaxlib/python versions, precision flags
  ``compile``    AOT compile timing of the epoch program (``jit(...).lower().compile()``)
                 plus its ``cost_analysis()`` FLOPs
  ``epoch``      per epoch: wall/execute/eval/data-feed seconds, examples/s,
                 compile_s, flops_per_step, train/val loss; from a loop of
                 ``utils.profiling`` spans (``train/lm.py``) every other phase
                 too (log/emit/guard/checkpoint/tick seconds and ``period_s``)
  ``health``     per epoch when ``--health-stats`` is on: grad-norm mean/max, loss
                 min/max/mean, param norm — accumulated INSIDE the compiled scan
                 (see ``train/step.py``), zero extra host syncs on the hot path
  ``mfu``        steady-state throughput: measured step seconds vs compiled FLOPs vs
                 the chip's published peak (``utils/benchmarks.py``)
  ``bench``      one line per ``bench*.py`` measurement (same schema, comparable to
                 training runs in ``tools/telemetry_report.py``)
  ``serve``      one line per served request (``serving/server.py``): TTFT/TPOT,
                 queue wait, e2e latency, tokens/s, finish reason
  ``serve_summary``  once per serving run at drain: request counts, aggregate
                 tokens/s, slot occupancy, p50/p95/p99 latency percentiles, and
                 the admission queue's snapshot (depth/oldest-age/rejected)
  ``route``      written by the fleet router (``serving/router.py``, via the
                 jax-free ``utils.jsonl.JsonlWriter`` — same schema, same
                 reader): one line per routed request — replica, affinity hit,
                 redispatch count, finish, latencies
  ``replica``    router lifecycle record: a replica start/fail/restart/dead
                 transition with reason (crash/hung), exit code, backoff
  ``router_summary``  once per router run at drain: fleet-wide counts,
                 redispatch/duplicate totals, affinity hit rate, per-replica
                 dispatch table, aggregated replica prefix-cache stats
  ``checkpoint`` one line per checkpoint save/restore (``utils/checkpoint.py``
                 savers + ``restore_for_resume``): op, path, full/sharded kind,
                 bytes, wall seconds, step, and — for the write-behind saver —
                 how many queued states the write coalesced away
  ``preempt``    once, when a ``--handle-preemption`` trainer honors SIGTERM at an
                 epoch boundary: the stop epoch/step and the durable checkpoint
                 (the run then exits 75 — resilience/preemption.py)
  ``restart``    written by the fleet supervisor (``resilience/supervisor.py``,
                 via its own jax-free writer — same schema, same reader): attempt,
                 crash/hung/timeout reason, exit code, the checkpoint the next
                 attempt resumes from, backoff seconds
  ``plan``       once per ``--plan`` run (``plan/``): the chosen mesh/microbatch
                 split, its source (auto/tune/file), predicted step seconds +
                 per-chip bytes, and how many candidates were ranked
  ``autotune``   one line per empirically trialed candidate (``--plan tune``,
                 ``plan/autotune.py``): mesh, analytical rank, predicted vs
                 measured step seconds, AOT compile seconds, compiled FLOPs
  =============  =====================================================================

- **writer** — ``TelemetryWriter`` is process-0 gated (a fleet writes ONE file) and
  atomic: every emit rewrites the file via tmp+rename (the checkpoint writer's
  ``_atomic_write``), so a reader never observes a torn line and a killed run keeps
  every event emitted before the kill. Event volume is O(epochs), not O(steps) —
  rewriting is cheap by construction, because anything per-step would be a host sync
  the compiled-epoch design exists to delete. The serving path is the exception:
  its volume is O(requests), so ``TelemetryWriter(path, stream=True)`` appends one
  flushed line per emit instead of rewriting — a kill can tear at most the final
  line, which ``metrics.load_metrics_jsonl`` tolerates (torn-tail rule).

Read side: ``utils.metrics.load_metrics_jsonl`` (shared with the loss-curve JSONL);
renderer: ``tools/telemetry_report.py``.
"""

from __future__ import annotations

import dataclasses
import json
import math
import platform
import threading
import time

import jax
import numpy as np

from csed_514_project_distributed_training_using_pytorch_tpu.utils import metrics as M
from csed_514_project_distributed_training_using_pytorch_tpu.utils import profiling

SCHEMA_VERSION = 1


def _finite(x):
    """Strict-JSONL rule (same as ``metrics.save_metrics_jsonl``): non-finite → None."""
    if x is None:
        return None
    x = float(x)
    return x if math.isfinite(x) else None


def _sanitize(obj):
    """Deep-copy ``obj`` with every non-finite float mapped to None — a diverged run
    (NaN loss, inf grad norm) must still serialize as valid JSON."""
    if isinstance(obj, float):
        return _finite(obj)
    if isinstance(obj, dict):
        return {k: _sanitize(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_sanitize(v) for v in obj]
    return obj


class TelemetryWriter:
    """Append-only event stream as atomically-(re)written JSONL; process-0 gated.

    ``path`` empty/None disables everything — every ``emit`` is then a no-op, so
    trainers call unconditionally and the off path costs a truthiness check.

    ``stream=True`` switches to append-per-emit (one flushed line each event, file
    truncated at the first emit): the serving path's mode, where event volume is
    O(requests) and the atomic full rewrite would go quadratic. A kill can tear at
    most the trailing line; the shared reader skips exactly that.

    History preservation (``preserve=True``, non-stream mode): a NEW writer
    on an EXISTING path loads the prior events first (through the guarded
    reader — a crashed writer's torn final line is dropped) and every rewrite
    carries them. This is the ``JsonlWriter`` append doctrine applied to the
    rewrite mode, for RESUMED runs only: a supervised restart re-runs the
    same trainer command — same ``--telemetry`` path — and the crashed
    attempt's events must survive into the resumed run's file, or run-level
    accounting (``obs/goodput.py``: replayed-epoch badput needs the FIRST
    attempt's epoch history) is impossible. Attempts stay distinguishable:
    each one opens with its own ``manifest`` event. The trainers pass
    ``preserve=bool(config.resume_from)`` — a FRESH run on a stale path
    still truncates (two unrelated runs must not blend into one fake
    multi-attempt history).
    """

    def __init__(self, path: str | None, *, stream: bool = False,
                 preserve: bool = False):
        self.path = path or ""
        self.stream = bool(stream)
        self.preserve = bool(preserve)
        self._fh = None
        self._truncated = False       # stream mode: first open truncates, later
                                      # reopens (emit after close) append
        self._events: list[dict] = []
        self._loaded_history = False  # non-stream: prior-run events loaded once,
                                      # lazily (only the logging process reads)
        self._t0 = time.time()
        # emit() must be thread-safe: the write-behind checkpointer reports its
        # completed writes from its worker thread while the trainer keeps emitting
        # epoch events from the main one.
        self._emit_lock = threading.Lock()

    @property
    def enabled(self) -> bool:
        return bool(self.path) and M.is_logging_process()

    def emit(self, event: dict) -> None:
        """Record one typed event; rewrite the JSONL atomically (default) or
        append+flush the one line (``stream=True``)."""
        if not self.enabled:
            return
        if "event" not in event:
            raise ValueError(f"telemetry event missing its 'event' type key: {event}")
        import json
        import os

        from csed_514_project_distributed_training_using_pytorch_tpu.utils.checkpoint import (
            _atomic_write,
        )

        row = dict(event)
        row.setdefault("t_s", round(time.time() - self._t0, 6))
        row = _sanitize(row)
        with self._emit_lock:
            if self.stream:
                # No in-memory event log here: stream mode exists for O(requests)
                # volume, and the disk line IS the record. Reopening after close()
                # appends — a writer shared across serving runs must never truncate
                # lines it already flushed.
                if self._fh is None:
                    os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
                    self._fh = open(self.path, "a" if self._truncated else "w")
                    self._truncated = True
                self._fh.write(json.dumps(row, allow_nan=False) + "\n")
                self._fh.flush()
                return
            if not self._loaded_history:
                self._loaded_history = True
                if self.preserve and os.path.exists(self.path):
                    from csed_514_project_distributed_training_using_pytorch_tpu.utils.jsonl import (
                        read_jsonl,
                    )
                    self._events = read_jsonl(self.path) + self._events
            self._events.append(row)
            payload = "".join(json.dumps(e, allow_nan=False) + "\n"
                              for e in self._events)
            _atomic_write(self.path, payload.encode())

    def close(self) -> None:
        """Release the stream-mode file handle (no-op otherwise)."""
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def manifest_event(config=None, *, mesh=None, run_type: str = "") -> dict:
    """The once-per-run provenance record: config, topology, software versions.

    ``config`` is any of the frozen config dataclasses (snapshotted field-by-field);
    ``mesh`` the jax Mesh when the trainer has one (axis names + sizes).
    """
    try:
        import jaxlib
        jaxlib_version = jaxlib.__version__
    except Exception:  # pragma: no cover - jaxlib always ships with jax
        jaxlib_version = None
    devs = jax.devices()
    ev = {
        "event": "manifest",
        "schema_version": SCHEMA_VERSION,
        "run_type": run_type or (type(config).__name__ if config is not None else ""),
        "unix_time": time.time(),
        "platform": devs[0].platform,
        "device_kind": getattr(devs[0], "device_kind", devs[0].platform),
        "device_count": len(devs),
        "local_device_count": jax.local_device_count(),
        "process_count": jax.process_count(),
        "process_index": jax.process_index(),
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib_version,
        "python_version": platform.python_version(),
    }
    if mesh is not None:
        ev["mesh"] = {"axis_names": list(mesh.axis_names),
                      "shape": {str(k): int(v) for k, v in mesh.shape.items()}}
    if config is not None and dataclasses.is_dataclass(config):
        cfg = dataclasses.asdict(config)
        ev["config"] = cfg
        ev["precision"] = {"bf16": bool(cfg.get("bf16", False)),
                           "jax_enable_x64": bool(jax.config.jax_enable_x64)}
    return ev


def _compiled_cost_value(compiled, key: str) -> float | None:
    """One positive value out of an AOT program's ``cost_analysis()`` dict —
    None when the backend doesn't report it."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return None
    try:
        value = cost.get(key)
    except AttributeError:
        return None
    return float(value) if value and value > 0 else None


def compiled_flops(compiled) -> float | None:
    """Total FLOPs of ONE invocation of an AOT-compiled program, from XLA's
    ``cost_analysis()`` — None when the backend doesn't report them."""
    return _compiled_cost_value(compiled, "flops")


def compiled_bytes_accessed(compiled) -> float | None:
    """Total HBM bytes one invocation actually touches, from XLA's
    ``cost_analysis()`` ``bytes accessed`` — the BYTE-TRUE traffic of the
    compiled program (int8 operands priced at one byte, fusions not
    double-counted), as opposed to a dtype-naive estimate from tensor shapes.
    None when the backend doesn't report it."""
    return _compiled_cost_value(compiled, "bytes accessed")


def aot_compile(jit_fn, *args) -> tuple[object | None, dict | None]:
    """Time ``jit_fn.lower(*args).compile()`` — the compile/execute split.

    Returns ``(compiled, {"lower_s", "compile_s", "flops", "bytes_accessed",
    "jaxpr", "scopes", "scopes_s"})`` (``jaxpr``: the traced program's, for a caller
    that reads what the trace holds, as ``HybridLM.recompute_plan`` does; ``scopes``:
    ``profiling.scope_table`` of the executable's own text, which ``jax.named_scope``
    and which pass made each instruction, and ``scopes_s`` the seconds printing and
    parsing it took. From ``compiled`` and not from the lowering: the persistent
    cache's key leaves op metadata out, so an executable another tree wrote can be
    the one that runs, with that tree's names and numbering, and a trace names
    device ops by the numbering of what ran); the caller should
    invoke ``compiled`` directly (the AOT program does not populate ``jit_fn``'s
    cache, so calling the jit object afterwards would compile twice). ``args`` may
    mix concrete arrays and ``jax.ShapeDtypeStruct``s. ``(None, None)`` when the
    callee has no ``.lower`` (the cached-sharding compile wrappers) or lowering
    fails — callers then fall back to the ordinary jit path with compile time
    folded into the first epoch; the first line of the swallowed exception is
    logged, so a program too large for the chip says ``RESOURCE_EXHAUSTED``.
    """
    if not hasattr(jit_fn, "lower"):
        return None, None
    try:
        t0 = time.perf_counter()
        traced = jit_fn.trace(*args)
        lowered = traced.lower()
        lower_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
    except Exception as e:
        reason = (str(e).strip().splitlines() or [""])[0]
        M.log(f"aot_compile: falling back to jit ({type(e).__name__}: {reason})")
        return None, None
    t0 = time.perf_counter()
    scopes = profiling.scope_table(compiled.as_text())
    scopes_s = time.perf_counter() - t0
    return compiled, {"lower_s": lower_s, "compile_s": compile_s,
                      "flops": compiled_flops(compiled),
                      "bytes_accessed": compiled_bytes_accessed(compiled),
                      "jaxpr": traced.jaxpr, "scopes": scopes, "scopes_s": scopes_s}


def write_scope_table(telemetry_path: str, table: dict, *,
                      steps_per_call: int | None = None) -> dict:
    """``aot["scopes"]`` as ``<telemetry path>.scopes.json``, one line an instruction
    (a file of its own: a ``compile`` event is one line), with the program's
    ``steps_per_call`` so that a reader can speak of a step. Returns the ``compile``
    event's ``scopes`` field: the file's ``path``, the program's ``module`` name, its
    count of ``instructions``, the share of them whose ``op_name`` holds a scope
    (``named_share``), how many fusions hold more than one (``mixed``) and the first
    segment of every scope found (``top_scopes``: an executable that the compile cache
    handed over from an older tree carries that tree's names, and this list says so).
    What reads the file: ``benchmark/reducers/scope_time.py``, beside a trace of the
    same run."""
    path = telemetry_path + ".scopes.json"
    ops = table["ops"]
    if M.is_logging_process():
        with open(path, "w") as fh:
            fh.write('{"module": %s,\n "steps_per_call": %s,\n "mixed": %s,\n "ops": {\n'
                     % (json.dumps(table["module"]), json.dumps(steps_per_call),
                        json.dumps(table["mixed"])))
            fh.write(",\n".join(f"{json.dumps(name)}: {json.dumps(where)}"
                                for name, where in ops.items()))
            fh.write("\n}}\n")
    return {"path": path, "module": table["module"], "instructions": len(ops),
            "named_share": _finite(sum(1 for scope, _ in ops.values() if scope)
                                   / len(ops) if ops else None),
            "mixed": len(table["mixed"]),
            "top_scopes": sorted({scope.split("/")[0] for scope, _ in ops.values() if scope})}


def compile_event(fn_name: str, aot: dict, *, steps_per_call: int | None = None,
                  attention: dict | None = None, scopes: dict | None = None,
                  plans: dict | None = None) -> dict:
    """The ``compile`` event for one AOT-timed program. ``attention``: which core the
    program's attention calls get and why (``ops.dispatch_plan``'s dict: ``impl``,
    ``score_bytes``, ``seq_padded``, ``block``, ``backward``), for the trainers that
    route through the dispatcher, with the model's own fields merged in
    (``HybridLM.rotary_plan``). ``plans``: what the model says of the program, written
    into the event whole, a field a key (``HybridLM.plans`` names the keys and what each
    holds; a model that says nothing adds none). ``scopes``: where the table of each
    instruction's scope and pass was written and what it holds (``write_scope_table``);
    ``scopes_s``, what building it added to the run's set-up, is the ``aot`` dict's."""
    flops = aot.get("flops")
    return {
        "event": "compile",
        "fn": fn_name,
        "lower_s": _finite(aot.get("lower_s")),
        "compile_s": _finite(aot.get("compile_s")),
        "flops_per_call": _finite(flops),
        "steps_per_call": steps_per_call,
        "flops_per_step": _finite(flops / steps_per_call
                                  if flops and steps_per_call else None),
        "bytes_accessed_per_call": _finite(aot.get("bytes_accessed")),
        "bytes_accessed_per_step": _finite(
            aot["bytes_accessed"] / steps_per_call
            if aot.get("bytes_accessed") and steps_per_call else None),
        "attention": attention,
        **(plans or {}),
        "scopes": scopes,
        "scopes_s": _finite(aot.get("scopes_s")),
    }


def epoch_event(epoch: int, *, examples: int, steps: int | None = None,
                wall_s: float | None = None, execute_s: float | None = None,
                eval_s: float | None = None, data_s: float | None = None,
                compile_s: float | None = None, flops_per_step: float | None = None,
                train_loss: float | None = None, val_loss: float | None = None,
                mfu: float | None = None, log_s: float | None = None,
                emit_s: float | None = None, guard_s: float | None = None,
                checkpoint_s: float | None = None, tick_s: float | None = None,
                period_s: float | None = None, expert_counts=None,
                expert_block: int | None = None) -> dict:
    """Per-epoch phase-timing record. ``execute_s`` is device execution of the epoch
    program (closed by a host fetch, SURVEY.md §7c); ``wall_s`` the whole epoch
    including host work; ``data_s`` index-plan/feed construction; ``compile_s`` the
    AOT epoch-program compile (constant per run, repeated per event so each line is
    self-contained).

    ``log_s`` … ``tick_s`` are the other phases of a loop that names all of its time
    with ``utils.profiling`` spans (``train/lm.py``; README "Telemetry" has the table),
    and ``period_s`` the time since the previous event's drain that the ``*_s`` span
    fields are pieces of. The event is emitted before its own iteration ends, so it
    holds what was drained at its emit: ``emit_s``, ``guard_s``, ``checkpoint_s`` and
    the boundary half of ``tick_s`` are the PREVIOUS iteration's tail. ``null`` from a
    trainer whose loop has no such span.

    ``expert_counts`` ``[steps, sparse layers, held experts]``: the rows that arrived
    at each held expert, out of the epoch program with the losses. The event carries,
    per step and sparse layer, their sum (``expert_rows``) and the smallest, mean and
    largest count over the held experts, and ``expert_rows_moved``: the rows of the
    row tiles that arrived (each held expert's count rounded up to whole tiles of
    ``expert_block`` rows, one at least), which is what a crossing between token order
    and expert order touches; ``null`` for a model with no expert layer."""
    ex = _finite(execute_s)
    experts = {f"expert_rows{suffix}": None
               for suffix in ("", "_min", "_mean", "_max", "_moved")}
    if expert_counts is not None:
        counts = np.asarray(expert_counts)
        tiles = np.maximum(1, -(-counts // expert_block))
        experts = {"expert_rows": counts.sum(-1).tolist(),
                   "expert_rows_moved": (tiles * expert_block).sum(-1).tolist(),
                   "expert_rows_min": counts.min(-1).tolist(),
                   "expert_rows_mean": counts.mean(-1).tolist(),
                   "expert_rows_max": counts.max(-1).tolist()}
    return {
        "event": "epoch",
        "epoch": int(epoch),
        "examples": int(examples),
        "steps": int(steps) if steps is not None else None,
        "wall_s": _finite(wall_s),
        "execute_s": ex,
        "eval_s": _finite(eval_s),
        "data_s": _finite(data_s),
        "log_s": _finite(log_s),
        "emit_s": _finite(emit_s),
        "guard_s": _finite(guard_s),
        "checkpoint_s": _finite(checkpoint_s),
        "tick_s": _finite(tick_s),
        "period_s": _finite(period_s),
        "compile_s": _finite(compile_s),
        "examples_per_s": _finite(examples / ex if ex else None),
        "steps_per_s": _finite(steps / ex if ex and steps else None),
        "flops_per_step": _finite(flops_per_step),
        "train_loss": _finite(train_loss),
        "val_loss": _finite(val_loss),
        "mfu": _finite(mfu),
        **experts,
    }


def data_event(epoch: int, *, batches: int, sequences: int,
               wait_s: float | None = None, throttle_s: float = 0.0,
               cursor: dict | None = None,
               stream_digest: int | None = None) -> dict:
    """Per-epoch streaming-loader ledger (``data/stream.py``): how many
    batches the epoch consumed, the seconds the consumer spent blocked on the
    loader (the goodput ``data_wait`` input, charged inside the epoch event's
    ``data_s``), the resume cursor the matching checkpoint manifest carries,
    and the epoch's stream CRC — the bitwise pin deterministic-resume tests
    compare across a kill/resume boundary."""
    return {
        "event": "data",
        "epoch": int(epoch),
        "batches": int(batches),
        "sequences": int(sequences),
        "wait_s": _finite(wait_s),
        "throttle_s": _finite(throttle_s),
        "cursor": dict(cursor) if cursor else None,
        "stream_digest": int(stream_digest) if stream_digest is not None else None,
    }


def health_event(epoch: int, health, steps: int, *,
                 param_norm: float | None = None) -> dict:
    """The ``health`` event from a ``train.step.HealthStats`` carry (host-fetched
    once per epoch). ``grad_norm`` is the per-step mean — the headline trajectory;
    min/max bound the epoch."""
    steps = max(int(steps), 1)
    return {
        "event": "health",
        "epoch": int(epoch),
        "steps": steps,
        "grad_norm": _finite(float(health.grad_norm_sum) / steps),
        "grad_norm_max": _finite(float(health.grad_norm_max)),
        "loss_min": _finite(float(health.loss_min)),
        "loss_max": _finite(float(health.loss_max)),
        "loss_mean": _finite(float(health.loss_sum) / steps),
        "param_norm": _finite(param_norm),
    }


def anomaly_event(epoch: int, guard, steps: int, *,
                  fingerprint: float | None = None, skip: str = "") -> dict:
    """The per-epoch ``anomaly`` event from a ``train.step.GuardState`` carry
    (host-fetched once per epoch with the losses — no extra syncs). Counters
    are CUMULATIVE for the attempt (a rollback resumes the healthy
    checkpoint's counters, so a resumed attempt restarts from its baseline);
    ``fingerprint`` is the cross-replica param fingerprint
    (``param_fingerprint``), ``skip`` the active ``--skip-steps`` windows."""
    import math as _math

    mean = float(guard.ema_mean)
    std = _math.sqrt(max(float(guard.ema_sq) - mean * mean, 0.0))
    return {
        "event": "anomaly",
        "epoch": int(epoch),
        "steps": int(steps),
        "anomalies": int(guard.anomalies),
        "nonfinite": int(guard.nonfinite),
        "spikes": int(guard.spikes),
        "skipped": int(guard.skipped),
        "clean_steps": int(guard.count),
        "first_anomaly_step": int(guard.first_anomaly_step),
        "last_anomaly_step": int(guard.last_anomaly_step),
        "grad_norm_ema": _finite(mean),
        "grad_norm_std": _finite(std),
        "fingerprint": _finite(fingerprint),
        "skip": skip,
    }


def _local_blocks(leaf):
    """This process's deduped addressable blocks of ``leaf`` as host arrays
    (sorted by global offset for a deterministic fold), or None when the
    local blocks do not cover the full logical array — the multi-host-sharded
    case, where per-process fingerprints would differ by construction."""
    import numpy as np

    if not hasattr(leaf, "addressable_shards"):
        return [np.asarray(leaf)]
    blocks: dict[tuple, object] = {}
    covered = 0
    for sh in leaf.addressable_shards:
        key = tuple(0 if s.start is None else int(s.start) for s in sh.index)
        if key in blocks:
            continue                     # a replica of an already-seen block
        data = np.asarray(sh.data)
        blocks[key] = data
        covered += data.size
    if covered != leaf.size:
        return None
    return [blocks[k] for k in sorted(blocks)]


def param_fingerprint(tree) -> float | None:
    """Cross-replica state fingerprint: the f32 per-leaf absolute-sum folded
    over this process's LOCAL view of the tree — cheap, deterministic, and
    identical across replicas iff their replicated state actually is.
    Deliberately NOT a jitted global reduction: on a multi-host fleet that
    would all-reduce, handing every process the identical (corruption
    included) scalar — the detector would be structurally blind. Host-local
    math means each process vouches only for the bytes it holds. Computed
    once per epoch at the sanctioned boundary fetch and compared by the
    supervisor's fingerprint-verify mode through the heartbeat files
    (``resilience/heartbeat.py::fingerprint_mismatch``) — post-update
    divergence (SDC, desync) is detected before the diverged state can be
    RESUMED as truth (the supervisor rolls back strictly past the mismatch
    step). Returns None when this process's addressable shards do not cover
    the full state (multi-host FSDP/TP: per-process fingerprints would differ
    by construction, and a beat without a fingerprint is simply not
    compared)."""
    import numpy as np

    total = np.float32(0.0)
    for leaf in jax.tree_util.tree_leaves(tree):
        blocks = _local_blocks(leaf)
        if blocks is None:
            return None
        for data in blocks:
            total += np.abs(data.astype(np.float32)).sum(dtype=np.float32)
    return float(total)


def checkpoint_event(*, op: str, path: str, kind: str = "full",
                     nbytes: int | None = None, wall_s: float | None = None,
                     step: int | None = None, coalesced: int | None = None,
                     background: bool = False) -> dict:
    """One checkpoint save/restore (``utils/checkpoint.py``). ``op`` is ``"save"``
    or ``"restore"``; ``kind`` ``"full"`` (one msgpack file) or ``"sharded"``
    (per-process directory). ``coalesced`` counts the queued states a write-behind
    save absorbed before this write hit disk (async saver only)."""
    return {
        "event": "checkpoint",
        "op": op,
        "path": path,
        "kind": kind,
        "bytes": int(nbytes) if nbytes is not None else None,
        "wall_s": _finite(wall_s),
        "step": int(step) if step is not None else None,
        "background": bool(background),
        "coalesced": int(coalesced) if coalesced is not None else None,
    }


def preempt_event(*, epoch: int, step: int, checkpoint: str = "") -> dict:
    """A cooperative preemption stop (resilience/preemption.py): where the run
    halted and which checkpoint that progress is durable in."""
    return {
        "event": "preempt",
        "epoch": int(epoch),
        "step": int(step),
        "checkpoint": checkpoint,
    }


def plan_event(plan, *, candidates: int | None = None) -> dict:
    """The once-per-run ``plan`` record (``plan.apply_plan``): which layout the
    planner picked, from which source, at what predicted/measured cost.
    ``plan`` is a ``plan.artifact.Plan``; the full candidate table lives in the
    saved plan JSON — this line carries the decision, not the search."""
    predicted = plan.predicted or {}
    return {
        "event": "plan",
        "run_type": plan.run_type,
        "source": plan.source,
        "mesh": plan.mesh,
        "axes": dict(plan.axes),
        "fsdp": bool(plan.fsdp),
        "grad_accum": int(plan.grad_accum),
        "pipeline_microbatches": int(plan.pipeline_microbatches),
        "device_count": int(plan.device_count),
        "global_batch": int(plan.global_batch),
        "predicted_step_s": _finite(predicted.get("step_s")),
        "predicted_bytes_per_chip": _finite(predicted.get("total_bytes_per_chip")),
        "measured_step_s": _finite(plan.measured_step_s),
        "candidates": (int(candidates) if candidates is not None
                       else len(plan.candidates)),
    }


def autotune_event(*, mesh: str, fsdp: bool, grad_accum: int, microbatches: int,
                   rank: int, predicted_step_s: float | None,
                   measured_step_s: float | None = None,
                   compile_s: float | None = None,
                   flops_per_step: float | None = None) -> dict:
    """One empirically trialed candidate (``plan/autotune.py``): the analytical
    prediction next to the measured fact, so the cost model is auditable from
    the telemetry alone. ``measured_step_s`` None = the trial harness could not
    build this layout (analytical estimate retained in the ranking)."""
    return {
        "event": "autotune",
        "mesh": mesh,
        "fsdp": bool(fsdp),
        "grad_accum": int(grad_accum),
        "microbatches": int(microbatches),
        "rank": int(rank),
        "predicted_step_s": _finite(predicted_step_s),
        "measured_step_s": _finite(measured_step_s),
        "compile_s": _finite(compile_s),
        "flops_per_step": _finite(flops_per_step),
    }


def _l2_norm_program(tree):
    from csed_514_project_distributed_training_using_pytorch_tpu.ops.optim import (
        global_l2_norm as _norm,
    )

    return _norm(tree)


_l2_norm_jit = jax.jit(_l2_norm_program)


def global_l2_norm(tree) -> float:
    """Global L2 norm of a pytree (param-norm for the health event; called once per
    epoch, off the hot path; the formula is ``ops.optim.global_l2_norm`` — one
    owner with the clip and the grad-norm accumulator). Runs as one jitted program
    so sharded leaves (TP/FSDP states) reduce via compiler-inserted collectives —
    eager ops on non-fully-addressable arrays would fail on a multi-host fleet.

    On a multi-host fleet this IS an SPMD computation: every process must enter it.
    The trainers therefore compute it whenever ``--health-stats`` is on — outside
    the process-0 emission gate — and only process 0 emits the event."""
    return float(jax.device_get(_l2_norm_jit(tree)))


def estimate_mfu(flops_per_step: float | None, step_s: float | None,
                 bytes_per_step: float | None = None) -> dict:
    """Model-FLOP-utilization against the chip's published bf16 peak.

    ``flops_per_step`` comes from ``compiled.cost_analysis()``, which prices the
    post-SPMD-partitioning PER-DEVICE module — each device's share of the step —
    so ``mfu`` divides the per-device achieved rate by ONE chip's peak. That is
    the same quantity ``bench.py`` reports (global analytic FLOPs over
    ``peak * devices``): the two conventions agree when work divides evenly, so
    A-vs-B comparisons across telemetry and bench files compare like with like.
    Uses ``utils.benchmarks.peak_flops`` (the committed spec-sheet table); ``mfu``
    is None off-TPU or on an unknown device kind — never a guess.

    ``bytes_per_step`` (``compiled_bytes_accessed`` / steps — XLA's own count
    of the bytes the compiled step ACTUALLY touches, so an int8 operand is
    priced at one byte) adds the bandwidth side: achieved bytes/s and the HBM
    roofline fraction ``hbm_frac``. Quantization moves this number, which is
    why it must be measured, not derived from a parameter count at an assumed
    dtype."""
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.benchmarks import (
        peak_flops,
        peak_hbm_bytes,
    )

    devs = jax.devices()
    device_kind = getattr(devs[0], "device_kind", devs[0].platform)
    achieved = (flops_per_step / step_s if flops_per_step and step_s else None)
    on_tpu = devs[0].platform == "tpu"
    peak = peak_flops(device_kind) if on_tpu else None
    bw = (bytes_per_step / step_s if bytes_per_step and step_s else None)
    peak_bw = peak_hbm_bytes(device_kind) if on_tpu else None
    return {
        "flops_per_step": _finite(flops_per_step),
        "step_s": _finite(step_s),
        "achieved_flops_per_s_per_device": _finite(achieved),
        "device_kind": device_kind,
        "devices": len(devs),
        "peak_flops_per_s_per_device": _finite(peak),
        "mfu": _finite(achieved / peak if achieved and peak else None),
        "bytes_accessed_per_step": _finite(bytes_per_step),
        "achieved_bytes_per_s_per_device": _finite(bw),
        "peak_hbm_bytes_per_s": _finite(peak_bw),
        "hbm_frac": _finite(bw / peak_bw if bw and peak_bw else None),
    }


def mfu_event(flops_per_step: float | None, step_s: float | None,
              bytes_per_step: float | None = None) -> dict:
    """The steady-state ``mfu`` event (emit once, with the best measured step time)."""
    return {"event": "mfu", **estimate_mfu(flops_per_step, step_s,
                                           bytes_per_step)}


# Nearest-rank percentiles — the one estimator all serving summaries and the
# report CLI share. Owned by the jax-free utils.jsonl (the router needs it
# without importing jax); re-exported here, its historical home.
from csed_514_project_distributed_training_using_pytorch_tpu.obs.hist import (  # noqa: E402
    LogHistogram,
)
from csed_514_project_distributed_training_using_pytorch_tpu.utils.jsonl import (  # noqa: E402
    percentiles,
)


def series_percentiles(series, qs=(50, 95, 99)) -> dict | None:
    """p50/p95/p99 of a latency series that is EITHER a raw sequence (the
    nearest-rank oracle, ``utils.jsonl.percentiles``) or an ``obs.hist``
    ``LogHistogram`` sketch (bounded memory, quantiles within its configured
    relative error). The serving summaries call this so the schema stays
    identical while the backing store became O(buckets)."""
    if isinstance(series, LogHistogram):
        return series.percentiles(qs)
    return percentiles(series, qs)


def serve_event(*, request_id: int, prompt_len: int, new_tokens: int, finish: str,
                queue_wait_s: float | None = None, ttft_s: float | None = None,
                tpot_s: float | None = None, e2e_s: float | None = None,
                tenant: str = "default", preemptions: int = 0) -> dict:
    """One served request (``serving/server.py``): the per-request latency record.
    ``tokens_per_s`` is request-local decode throughput — generated tokens over the
    time since admission (e2e minus queue wait). ``tenant`` is the request's
    service class (``"default"`` = the implicit single-tenant class);
    ``preemptions`` how many times it was parked mid-decode by priority
    pressure (DESIGN.md §22) — a parked-then-resumed request finishes
    ``"ok"``, token-identical, but its e2e carries the squeeze it absorbed."""
    decode_s = (e2e_s - queue_wait_s
                if e2e_s is not None and queue_wait_s is not None else None)
    return {
        "event": "serve",
        "request_id": int(request_id),
        "prompt_len": int(prompt_len),
        "new_tokens": int(new_tokens),
        "finish": finish,
        "queue_wait_s": _finite(queue_wait_s),
        "ttft_s": _finite(ttft_s),
        "tpot_s": _finite(tpot_s),
        "e2e_s": _finite(e2e_s),
        "tokens_per_s": _finite(new_tokens / decode_s
                                if new_tokens and decode_s else None),
        "tenant": tenant,
        "preemptions": int(preemptions),
    }


def shed_event(*, tenant: str, reason: str, request_id: int | None = None,
               priority: int | None = None, source: str = "server") -> dict:
    """One overload-shedding decision (``serving/scheduler.py`` via the
    server/router front doors): ``reason`` is ``"quota"`` (token-bucket
    refusal), ``"refused"`` (arrival shed because the queue was full of
    strictly higher-priority work), or ``"displaced"`` (a queued request
    evicted so a higher class could be admitted). These are the deliberate
    degradations — the whole point of SLO tiers is that they land on the
    best-effort class, which this event makes auditable per tenant."""
    return {
        "event": "shed",
        "source": source,
        "tenant": tenant,
        "reason": reason,
        "request_id": int(request_id) if request_id is not None else None,
        "priority": int(priority) if priority is not None else None,
    }


def tenant_summary_event(*, tenant: str, source: str = "server",
                         requests: int = 0, ok: int = 0, timeout: int = 0,
                         shed: int = 0, new_tokens: int = 0,
                         preemptions: int = 0,
                         ttft_s: dict | None = None,
                         e2e_s: dict | None = None,
                         slo: dict | None = None) -> dict:
    """One tenant's drain-time ledger (``serving/server.py`` /
    ``serving/router.py``): counts, latency percentiles, preemptions
    absorbed, and attainment against the tenant's own SLO — the per-class
    A/B surface (the committed tenant-burst artifact compares the paid
    tenant's row across loaded/unloaded runs)."""
    return {
        "event": "tenant_summary",
        "source": source,
        "tenant": tenant,
        "requests": int(requests),
        "ok": int(ok),
        "timeout": int(timeout),
        "shed": int(shed),
        "new_tokens": int(new_tokens),
        "preemptions": int(preemptions),
        "ttft_s": ttft_s,
        "e2e_s": e2e_s,
        "slo": slo,
    }


def prefill_event(*, request_id: int, prompt_len: int, chunks: int, tokens: int,
                  cache_hit_len: int, wall_s: float | None,
                  latency_s: float | None = None) -> dict:
    """One completed prompt prefill (``serving/engine.py`` chunked path):
    ``chunks`` program invocations covered ``tokens`` prompt positions
    (``cache_hit_len`` more came free from the prefix cache; a full hit is
    ``chunks == 0``). ``wall_s`` is the host wall spent in THIS prompt's chunk
    programs — so ``tokens_per_s`` is true prefill throughput, not deflated by
    queueing; ``latency_s`` is admission to decode-ready (includes waiting
    behind other prompts under the chunk budget)."""
    return {
        "event": "prefill",
        "request_id": int(request_id),
        "prompt_len": int(prompt_len),
        "chunks": int(chunks),
        "tokens": int(tokens),
        "cache_hit_len": int(cache_hit_len),
        "wall_s": _finite(wall_s),
        "latency_s": _finite(latency_s),
        "tokens_per_s": _finite(tokens / wall_s if tokens and wall_s else None),
    }



def spec_event(*, step: int, active: int, proposed: int, accepted: int,
               emitted: int, draft_wall_s: float | None = None,
               verify_wall_s: float | None = None) -> dict:
    """One speculative verify step (``serving/engine.py`` spec mode):
    ``active`` slots offered ``proposed`` draft tokens, ``accepted`` of them
    survived verification and ``emitted`` tokens landed (accepted drafts plus
    one correction/bonus per slot). ``emitted_per_slot`` is the step's
    amortization factor — tokens emitted per slot per full-cache read; its
    FLOOR is 1.0 even at zero acceptance (the correction token always lands),
    so monitor acceptance from ``accepted``/``proposed``, not from it."""
    return {
        "event": "spec",
        "step": int(step),
        "active": int(active),
        "proposed": int(proposed),
        "accepted": int(accepted),
        "emitted": int(emitted),
        "emitted_per_slot": _finite(emitted / active if active else None),
        "draft_wall_s": _finite(draft_wall_s),
        "verify_wall_s": _finite(verify_wall_s),
    }


def serve_summary_event(*, requests: int, ok: int, timeout: int, new_tokens: int,
                        wall_s: float | None, steps: int | None = None,
                        shed: int = 0,
                        decode_invocations: int | None = None,
                        generated_tokens: int | None = None,
                        spec: dict | None = None,
                        slot_occupancy: float | None = None,
                        prefill_tokens: int | None = None,
                        prefill_chunks: int | None = None,
                        prefill_wall_s: float | None = None,
                        prefix_cache: dict | None = None,
                        queue: dict | None = None,
                        byte_accounting: dict | None = None,
                        kv_pages: dict | None = None,
                        slo: dict | None = None,
                        preemptions: int | None = None,
                        resumes: int | None = None,
                        tenants: dict | None = None,
                        ttft_s=(), tpot_s=(), e2e_s=(), queue_wait_s=()) -> dict:
    """The once-per-run serving aggregate, emitted at drain: counts, aggregate
    tokens/s over the server's whole wall clock, slot occupancy, and p50/p95/p99
    of each latency series (the per-request ``serve`` lines remain the raw data —
    the summary is what survives a truncated log and what A-vs-B compares).
    ``queue`` is the admission queue's ``RequestQueue.snapshot()`` (depth /
    oldest-age / rejected count) — the backpressure ledger. ``byte_accounting``
    (emitted as ``"bytes"``) is the engine's byte-TRUE decode working set
    (``ContinuousBatchingEngine.byte_accounting()`` — decode bytes/token, KV
    bytes/slot, slots-at-budget, kv_dtype), the quantization A/B ledger.
    ``slo`` is the run-level SLO attainment dict (``obs.slo
    .AttainmentTracker.summary()``) when the server carries a spec.
    ``kv_pages`` is the paged engine's ``page_stats()`` ledger (pool
    occupancy / sharing / refusals / COW copies) — None on a contiguous
    engine, so the field's presence is itself the layout A/B marker. The four
    latency series accept raw sequences or ``obs.hist.LogHistogram`` sketches
    (the server keeps sketches — O(buckets), not O(requests))."""
    return {
        "event": "serve_summary",
        "requests": int(requests),
        "ok": int(ok),
        "timeout": int(timeout),
        "new_tokens": int(new_tokens),
        "wall_s": _finite(wall_s),
        "tokens_per_s": _finite(new_tokens / wall_s
                                if new_tokens and wall_s else None),
        "steps": int(steps) if steps is not None else None,
        # Multi-token decode steps (speculative decoding) break the historical
        # steps == tokens 1:1: report PROGRAM INVOCATIONS and GENERATED TOKENS
        # as separate counters so tokens/s and MFU math stay honest at K>1.
        "decode_invocations": (int(decode_invocations)
                               if decode_invocations is not None else None),
        "generated_tokens": (int(generated_tokens)
                             if generated_tokens is not None else None),
        "tokens_per_invocation": _finite(
            generated_tokens / decode_invocations
            if generated_tokens and decode_invocations else None),
        "spec": spec,
        "slot_occupancy": _finite(slot_occupancy),
        "prefill_tokens": int(prefill_tokens) if prefill_tokens is not None
        else None,
        "prefill_chunks": int(prefill_chunks) if prefill_chunks is not None
        else None,
        "prefill_wall_s": _finite(prefill_wall_s),
        "prefill_tokens_per_s": _finite(
            prefill_tokens / prefill_wall_s
            if prefill_tokens and prefill_wall_s else None),
        "prefix_cache": prefix_cache,
        "queue": queue,
        "bytes": byte_accounting,
        "kv_pages": kv_pages,
        "slo": slo,
        # The tenancy ledger (DESIGN.md §22): deliberate degradations (shed)
        # and mid-decode evictions (preemptions/resumes) are first-class
        # outcomes, never folded into timeouts — plus the per-tenant rows.
        "shed": int(shed),
        "preemptions": int(preemptions) if preemptions is not None else None,
        "resumes": int(resumes) if resumes is not None else None,
        "tenants": tenants,
        "ttft_s": series_percentiles(ttft_s),
        "tpot_s": series_percentiles(tpot_s),
        "e2e_s": series_percentiles(e2e_s),
        "queue_wait_s": series_percentiles(queue_wait_s),
    }


def kv_pages_event(*, source: str = "server", stats: dict) -> dict:
    """One paged-KV pool ledger line (``serving/server.py`` at drain, paged
    engines only): the engine's ``page_stats()`` dict — pool shape
    (num_pages/page_size/groups), occupancy (free/in_use/shared/peak_in_use),
    the alloc/free/refusal counters, live-token fragmentation, and COW copies.
    A standalone kind (not just the ``serve_summary`` field) so ``fleet_top``
    and the report's A-vs-B table can scan for it without parsing summaries."""
    return {"event": "kv_pages", "source": source, **stats}


def promote_event(*, action: str, candidate: str, step: int | None = None,
                  reason: str = "", incumbent: str = "",
                  nll: float | None = None, incumbent_nll: float | None = None,
                  perf_s: float | None = None,
                  incumbent_perf_s: float | None = None) -> dict:
    """One promotion-gate lifecycle transition (``deploy/promoter.py``):
    ``action`` is ``candidate_seen`` / ``gate_pass`` / ``gate_fail`` /
    ``canary_start`` / ``promoted`` / ``rolled_back``. ``candidate`` and
    ``incumbent`` are checkpoint paths; the NLL and perf pairs record the
    gate's actual measurements so a rejected candidate's margin is auditable
    from the stream alone."""
    return {
        "event": "promote",
        "action": action,
        "candidate": candidate,
        "step": int(step) if step is not None else None,
        "reason": reason,
        "incumbent": incumbent,
        "nll": _finite(nll),
        "incumbent_nll": _finite(incumbent_nll),
        "perf_s": _finite(perf_s),
        "incumbent_perf_s": _finite(incumbent_perf_s),
    }


def canary_event(*, candidate: str, replica: int, verdict: str,
                 window_s: float | None = None,
                 canary_attainment: float | None = None,
                 fleet_attainment: float | None = None,
                 canary_nll: float | None = None,
                 fleet_nll: float | None = None,
                 canary_requests: int | None = None,
                 fleet_requests: int | None = None,
                 reason: str = "") -> dict:
    """One canary-window verdict (``deploy/promoter.py``): the candidate on
    ONE replica vs the rest of the fleet over the same attainment window —
    windowed SLO attainment (fractions) and sampled-token NLL under the
    shared last-good scorer. ``verdict`` is ``pass`` / ``fail`` /
    ``inconclusive`` (too few requests to judge)."""
    return {
        "event": "canary",
        "candidate": candidate,
        "replica": int(replica),
        "verdict": verdict,
        "window_s": _finite(window_s),
        "canary_attainment": _finite(canary_attainment),
        "fleet_attainment": _finite(fleet_attainment),
        "canary_nll": _finite(canary_nll),
        "fleet_nll": _finite(fleet_nll),
        "canary_requests": (int(canary_requests)
                            if canary_requests is not None else None),
        "fleet_requests": (int(fleet_requests)
                           if fleet_requests is not None else None),
        "reason": reason,
    }
