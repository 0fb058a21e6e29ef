"""Run one cell once: find its files by name, build it through a driver,
measure, check, and reduce to the contract's result line.

Nothing here names a cell, a configuration, a traffic mix or a metric. The
manifest (``BENCHMARK.json``) gives names; each name is a file:

    configs/<config>.json        sizes, dtype, trainer arguments
    traffic/<traffic>.json       parameters of the mix
    workloads/<cell>.json        driver kind, limits of ``correct``
    drivers/<kind>.py            ``run(ctx) -> Observations``
    layer_metrics/<metric>.json  reducer name + its parameters
    reducers/<name>.py           ``read(obs, **params) -> float | None``
    reference/<name>.py          the configuration's plain reference
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time


class Refused(Exception):
    """The run cannot be made as asked: one line of reason, no result line."""


@dataclasses.dataclass
class Context:
    """What a driver is handed."""
    root: str                   # the checkout (holds BENCHMARK.json)
    bench: str                  # the benchmark's directory
    cell: dict                  # workloads/<cell>.json + the manifest's entry
    config: dict                # configs/<config>.json
    mix: dict                   # traffic/<traffic>.json
    seed: int
    seconds: float
    trace: bool
    work: str                   # scratch directory inside the checkout
    t_process: float            # perf_counter() at process start
    cache_events: dict          # live counters of compile-cache hits/misses
    control: bool = False       # control.py only: the lower precision in the program's place


@dataclasses.dataclass
class Observations:
    """What a driver hands back; reducers read it."""
    window_s: float = 0.0
    t_first: float = 0.0                    # perf_counter() of first measured instant
    attempted: int = 0
    failed: int = 0
    end_to_end: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    epochs: list = dataclasses.field(default_factory=list)      # telemetry events
    shapes: dict = dataclasses.field(default_factory=dict)      # from counts.py
    checks: list = dataclasses.field(default_factory=list)      # (name, value, limit)
    trace_dir: str = ""
    trace_window_s: float = 0.0
    trace_units: dict = dataclasses.field(default_factory=dict)  # steps/ticks traced
    memory_peak_bytes: int = 0
    trace: dict | None = None               # xplane.reduce() output
    peaks: dict = dataclasses.field(default_factory=dict)
    chips: int = 1


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise Refused(f"no file {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reference(bench: str, name: str):
    """``reference/<name>.py`` as a module of the ``reference`` package."""
    import importlib
    find(bench, "reference", name, ".py")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    return importlib.import_module(f"reference.{name}")


def find(bench: str, kind: str, name: str, ext: str) -> str:
    """``<bench>/<kind>/<name><ext>``, found by listing the directory."""
    folder = os.path.join(bench, kind)
    for entry in sorted(os.listdir(folder)):
        if entry == name + ext:
            return os.path.join(folder, entry)
    raise Refused(f"{kind}/{name}{ext} not found under {bench}")


def read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(root: str, workload: str) -> tuple[dict, dict, dict, dict, str]:
    manifest = read_json(os.path.join(root, "BENCHMARK.json"))
    entries = [w for w in manifest["workloads"] if w["name"] == workload]
    if not entries:
        raise Refused(f"workload {workload!r} is not in BENCHMARK.json")
    entry = entries[0]
    bench = os.path.join(root, manifest["paths"][0])
    cfg_entry = [c for c in manifest["configs"] if c["name"] == entry["config"]][0]
    config = read_json(os.path.join(root, cfg_entry["file"]))
    mix = read_json(find(bench, "traffic", entry["traffic"], ".json"))
    cell = dict(read_json(find(bench, "workloads", workload, ".json")), **entry)
    return manifest, cell, config, mix, bench


def check_devices(bench: str, chips: int) -> dict:
    """All devices TPU, as many as the cell asks, of a kind the peaks table
    knows. Anything else is refused: there is no CPU fallback."""
    import jax
    devices = jax.devices()
    platforms = sorted({d.platform for d in devices})
    if platforms != ["tpu"]:
        raise Refused(f"devices are {platforms}, not tpu: no accelerator, no run")
    if len(devices) != chips:
        raise Refused(f"the cell needs {chips} chip(s), jax sees {len(devices)}")
    return peaks_row(bench, devices[0].device_kind)


def peaks_row(bench: str, kind: str) -> dict:
    table = read_json(os.path.join(bench, "peaks.json"))
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


class CacheEvents:
    """jax's compile-cache and compile events, counted as chip_smoke.py does."""

    def __init__(self):
        self.counts = {"cache_hits": 0, "cache_misses": 0, "compiles": 0}
        from jax import monitoring
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.counts["cache_misses"] += 1

    def _on_duration(self, name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.counts["compiles"] += 1


def _fullest_chip(read) -> int:
    import jax
    return max(int(read(d.memory_stats() or {})) for d in jax.local_devices())


def memory_now_bytes() -> int:
    """The fullest chip's memory as the runtime reads it now: the arrays alive
    (``bytes_in_use``) and what it has reserved for its loaded programs'
    temporaries (``bytes_reserved``), which the allocator's count leaves out."""
    return _fullest_chip(lambda s: s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0))


def memory_peak_bytes(window_readings=()) -> int:
    """The peak on the fullest chip: the allocator's own peak of arrays, or the
    largest of the driver's ``memory_now_bytes()`` readings inside the window,
    when the timed programs hold their reservation."""
    return max([_fullest_chip(lambda s: s.get("peak_bytes_in_use", 0)), *window_readings])


def layer_metrics(manifest: dict, bench: str, workload: str, obs: Observations) -> dict:
    """Every per-layer metric the manifest lists for this cell, read by its
    own reducer. A reader that finds nothing returns None and is left out."""
    out = {}
    for metric in manifest["per_layer"]:
        if "workloads" in metric and workload not in metric["workloads"]:
            continue
        spec = read_json(find(bench, "layer_metrics", metric["name"], ".json"))
        reducer = load_module(find(bench, "reducers", spec["reducer"], ".py"),
                              f"bench_reducer_{spec['reducer']}")
        value = reducer.read(obs, **spec.get("params", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def run_cell(root: str, workload: str, *, seed: int, seconds: float, trace: bool,
             t_process: float, require_chip: bool = True, control: bool = False,
             out=print) -> dict:
    """One run of one cell. Returns the result line's object (also printed,
    last, through ``out``)."""
    manifest, cell, config, mix, bench = load_cell(root, workload)
    try:
        import csed_514_project_distributed_training_using_pytorch_tpu  # noqa: F401
    except ImportError as e:
        raise Refused(f"the program is not in this directory: {e}")
    import jax
    if require_chip:
        peaks = check_devices(bench, int(cell["chips"]))
    else:                       # tests: the CPU stands in, no peak is claimed
        peaks = {"flops_per_s": float("nan"), "hbm_bytes_per_s": float("nan")}
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.compile_cache import (
        enable_compile_cache,
    )
    if require_chip:
        enable_compile_cache()
    events = CacheEvents()
    work = os.path.join(root, ".bench_work", workload)
    os.makedirs(work, exist_ok=True)
    ctx = Context(root=root, bench=bench, cell=cell, config=config, mix=mix,
                  seed=int(seed), seconds=float(seconds), trace=bool(trace),
                  work=work, t_process=t_process,
                  cache_events=events.counts, control=control)
    driver = load_module(find(bench, "drivers", cell["driver"], ".py"),
                         f"bench_driver_{cell['driver']}")
    obs = driver.run(ctx)
    obs.peaks, obs.chips = peaks, int(cell["chips"])
    obs.end_to_end["setup_s"] = obs.t_first - t_process
    correct = True
    for name, value, limit in obs.checks:
        ok = value == value and value <= limit      # NaN fails
        correct = correct and ok
        out(f"check {name}: {value!r} (limit {limit!r}) {'ok' if ok else 'FAILED'}")
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": obs.memory_peak_bytes}
    result = {"correct": bool(correct), "attempted": int(obs.attempted),
              "failed": int(obs.failed)}
    if trace:
        xplane = load_module(os.path.join(bench, "xplane.py"), "bench_xplane")
        try:
            obs.trace = xplane.reduce(xplane.load(xplane.find_trace(obs.trace_dir)))
        except FileNotFoundError as e:
            out(f"trace: {e}")
        result["metrics"] = layer_metrics(manifest, bench, workload, obs)
        if obs.trace and obs.trace["devices"]:
            device["busy_s"] = obs.trace["busy_s"]
            device["window_s"] = obs.trace_window_s
            result["breakdown"] = {"device_ops": obs.trace["device_ops"],
                                   "idle_gaps": obs.trace["idle_gaps"]}
    else:
        units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
        missing = [n for n in cell["end_to_end"] if n not in obs.end_to_end]
        if missing:
            raise Refused(f"driver reported no {missing}")
        result["metrics"] = {n: {"value": float(obs.end_to_end[n]), "unit": units[n]}
                             for n in cell["end_to_end"]}
    result["device"] = device
    out(json.dumps(result))
    return result
