"""House-rule configuration: WHICH modules each checker binds to.

Checker *logic* lives in ``tools/graftlint/checkers/``; this module is the one
place the repo-specific scope decisions live, so adding a module to a rule is a
one-line diff reviewed next to the other scope choices. All paths are
package-relative (``serving/router.py``) or repo-relative for scripts
(``tools/serve_loadgen.py``); ``resolve()`` maps them onto graph modules.
"""

from __future__ import annotations

# -- backend-purity -----------------------------------------------------------------
# Modules DECLARED jax-free: importing one must not reach jax/jaxlib through any
# top-level import, transitively (lazy function-body imports are the sanctioned
# on-demand escape). The fleet-side doctrine (utils/jsonl.py docstring): a
# process that supervises accelerator-owning children must never claim a device
# itself — and the cheapest way to guarantee "never initializes a backend" is
# "never even imports it".
BACKEND_FREE = (
    "serving/router.py",
    "serving/autoscaler.py",
    "serving/scheduler.py",
    "serving/prefix_cache.py",
    "serving/tiers.py",
    "serving/wire.py",
    "resilience/supervisor.py",
    "resilience/heartbeat.py",
    "resilience/preemption.py",
    "resilience/faults.py",
    "resilience/netfaults.py",
    "resilience/poison.py",
    "utils/jsonl.py",
    "utils/trace.py",
    "utils/telemetry_events.py",
    "obs/hist.py",
    "obs/slo.py",
    "obs/goodput.py",
    "tools/serve_loadgen.py",
    "tools/trace_report.py",
    "tools/fleet_top.py",
    # The chip smoke's parent runs its phases as serial children that each need
    # the chip; a parent that reached jax at import could come to hold it.
    "chip_smoke.py",
    "train/launch.py",
    "utils/compile_cache.py",
)

# Import targets that count as "the backend" for backend-purity.
BACKEND_MODULES = ("jax", "jaxlib", "flax")

# -- telemetry-schema ---------------------------------------------------------------
# The one registry every emitted {"event": "..."} literal must appear in.
# graftlint reads it by AST (EVENT_KINDS dict literal), never by import.
EVENT_REGISTRY = "utils/telemetry_events.py"
EVENT_REGISTRY_NAME = "EVENT_KINDS"

# -- process0-gate ------------------------------------------------------------------
# SPMD trainer paths: every process runs this code, so any file write must go
# through an internally process-0-gated helper (TelemetryWriter,
# metrics.save_metrics_jsonl, utils.plotting, the checkpoint savers) or sit
# under an explicit `if is_logging_process():` / `if jax.process_index() == 0:`
# gate — otherwise N processes race on one path.
GATED_WRITE_MODULES = (
    "train/single.py",
    "train/distributed.py",
    "train/composed.py",
    "train/lm.py",
    "train/smoke.py",
)

# -- host-sync-hazard ---------------------------------------------------------------
# Hot regions: per module, either a tuple of function/method names whose bodies
# form the per-token / per-step host loop, or "scan-bodies" meaning every local
# function passed to lax.scan (the compiled epoch's step body). Inside a hot
# region, forcing a device value to host (.item(), float()/int(), np.asarray,
# jax.device_get) is a per-iteration sync — the exact tax the one-program
# design exists to delete (reference src/train_dist.py:85).
HOT_REGIONS: dict[str, tuple[str, ...] | str] = {
    "serving/engine.py": ("step", "_spec_tick", "_run_prefill",
                          "_finish_prefill"),
    "train/step.py": "scan-bodies",
}

# Callee names whose RESULT is a device value (taint sources) are structural:
# any call through a `*_jit`-suffixed binding or subscript of a `*_jits`
# mapping, plus immediately-invoked jax.jit — see checkers/host_sync.py.

# -- retrace-hazard -----------------------------------------------------------------
# The per-call-jit rules (immediately-invoked / loop-built wrappers) bind to
# LIBRARY code only — the package, where a wrapper built per call really does
# mean one XLA compile per request/epoch. One-shot harnesses (__graft_entry__
# dryrun legs, bench sweeps that deliberately compile one program per swept
# config) invoke each jit exactly once by construction, so the rule would only
# generate pragma noise there. The unhashable-static-literal rule stays global:
# that one is a runtime error wherever it appears.
RETRACE_LIBRARY_ONLY = True

# -- resolve-guard ------------------------------------------------------------------
# Helper functions allowed to call set_result/set_exception without an inline
# try/except InvalidStateError (none today: the repo idiom is the inline guard;
# a future `resolve_future()` helper registers itself here).
RESOLVE_HELPERS: tuple[str, ...] = ()

# -- scope helpers ------------------------------------------------------------------


def package_relpath(graph, rule_path: str) -> str:
    """Rule path -> repo-relative path (`tools/...` and root-level scripts pass
    through unchanged)."""
    if rule_path.startswith("tools/") or "/" not in rule_path:
        return rule_path
    return f"{graph.package}/{rule_path}"


def matches(graph, module, rule_paths) -> bool:
    return any(module.path == package_relpath(graph, p) for p in rule_paths)
