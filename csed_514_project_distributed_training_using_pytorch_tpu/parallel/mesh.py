"""Device mesh + cluster bootstrap.

Replaces the reference's rendezvous layer: ``os.environ['MASTER_ADDR']='10.128.0.2'`` /
``MASTER_PORT`` + ``dist.init_process_group("gloo", rank, world_size)`` (reference
``src/train_dist.py:144-146``, ``src/run1.py:21-23``), where the master IP is an
edit-the-source constant and the rank is encoded in *which launcher file you run*
(``src/run1.py:31`` vs ``src/run2.py:31``). Here:

- on a TPU pod slice, ``initialize_cluster()`` calls ``jax.distributed.initialize()`` with no
  arguments — coordinator address, process id, and world size all come from slice metadata, so
  every host runs the *same* command (this deletes the run1/run2 hand-editing pattern, the
  north-star ask in BASELINE.json);
- explicit coordinator/rank arguments remain available for non-TPU fleets (the gloo-style
  TCP-rendezvous analog);
- ``make_mesh()`` builds the ``jax.sharding.Mesh`` the SPMD step is compiled over. Default is
  the reference-parity one-axis ``('data',)`` mesh; multi-axis shapes (e.g. ``(data, model)``)
  are supported so wider parallelism can be layered on without redesign.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh


@dataclass(frozen=True)
class ProcessInfo:
    """This host's coordinates in the cluster (≙ the reference's rank/world_size pair,
    ``src/train_dist.py:131,141``, but discovered rather than hand-assigned)."""

    process_index: int
    process_count: int
    local_device_count: int
    global_device_count: int

    @property
    def is_coordinator(self) -> bool:
        """True on the process that owns rank-gated side effects (checkpoint writes, plots);
        ≙ the reference's ``if rank == 0`` (``src/train_dist.py:163``)."""
        return self.process_index == 0


def initialize_cluster(coordinator_address: str | None = None,
                       num_processes: int | None = None,
                       process_id: int | None = None,
                       initialization_timeout: int | None = None) -> ProcessInfo:
    """Join (or create) the distributed runtime and report this process's coordinates.

    No-op on a single-process run — safe to call unconditionally from every entry point.

    ``initialization_timeout`` (seconds; or env ``JAX_INITIALIZATION_TIMEOUT``) bounds the
    rendezvous wait — the clean-abort behavior SURVEY.md §5 "failure detection" asks for,
    where the reference's gloo rendezvous blocks forever on a missing peer
    (``src/train_dist.py:146``). On expiry the coordination client terminates the process
    with a DEADLINE_EXCEEDED fatal (not a catchable exception); exceptions jax does raise
    are re-raised with the cluster coordinates attached.
    """
    # Explicit arguments win; otherwise the rendezvous coordinates come from the environment
    # (as set by train.launch or a fleet runner). This is the analog of the reference's
    # MASTER_ADDR/MASTER_PORT env pair (src/train_dist.py:144-145) — except the process id is
    # handed in by the launcher, never hand-edited into the source.
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS") or None
    if num_processes is None and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("JAX_PROCESS_ID"):
        process_id = int(os.environ["JAX_PROCESS_ID"])

    if initialization_timeout is None and os.environ.get("JAX_INITIALIZATION_TIMEOUT"):
        initialization_timeout = int(os.environ["JAX_INITIALIZATION_TIMEOUT"])

    # TPU pod slice metadata lists one hostname per host; a single entry means this is not
    # a multi-host fleet and no coordinator service is needed.
    slice_hosts = [h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",") if h]
    multi_host = coordinator_address is not None or len(slice_hosts) > 1
    # Check the distributed-runtime state directly: touching jax.process_count() here would
    # initialize the local XLA backend first, after which jax.distributed.initialize raises.
    if multi_host and not jax.distributed.is_initialized():
        kwargs = {}
        if initialization_timeout is not None:
            kwargs["initialization_timeout"] = initialization_timeout
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                **kwargs,
            )
        except Exception as e:
            raise RuntimeError(
                f"cluster rendezvous failed: coordinator={coordinator_address!r}, "
                f"process_id={process_id}, num_processes={num_processes}, "
                f"timeout={initialization_timeout or 'default'}s — check that every "
                f"peer is up and reachable (≙ a hung init_process_group in the "
                f"reference, src/train_dist.py:146)") from e
    return process_info()


def process_info() -> ProcessInfo:
    return ProcessInfo(
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        local_device_count=jax.local_device_count(),
        global_device_count=jax.device_count(),
    )


_KNOWN_AXES = ("data", "seq", "model", "expert", "stage")


def parse_mesh_spec(spec: str) -> tuple[tuple[str, ...], tuple[int, ...]]:
    """``"data=2,seq=2,model=2"`` → (axis names, axis sizes). Order is the user's;
    unknown axis names and non-positive sizes are rejected. Shared by every trainer
    that accepts a ``--mesh`` string."""
    names, sizes = [], []
    for part in [p for p in spec.split(",") if p]:
        if "=" not in part:
            raise ValueError(f"mesh axis {part!r} must be name=size")
        name, _, size_s = part.partition("=")
        name = name.strip()
        if name not in _KNOWN_AXES:
            raise ValueError(f"unknown mesh axis {name!r} — choose from {_KNOWN_AXES}")
        if name in names:
            raise ValueError(f"duplicate mesh axis {name!r}")
        try:
            size = int(size_s)
        except ValueError:
            raise ValueError(f"mesh axis size {size_s!r} is not an integer") from None
        if size < 1:
            raise ValueError(f"mesh axis {name} size must be >= 1, got {size}")
        names.append(name)
        sizes.append(size)
    if not names:
        raise ValueError("empty --mesh spec")
    return tuple(names), tuple(sizes)


def make_mesh(num_devices: int | None = None,
              axis_names: tuple[str, ...] = ("data",),
              axis_shape: tuple[int, ...] | None = None) -> Mesh:
    """Build a device mesh.

    ``num_devices=None`` uses every addressable device (all chips on all hosts). With the
    default one-axis ``('data',)`` layout this is the analog of the reference's flat world of N
    single-process machines (``world_size``, ``src/train_dist.py:131``) — except chips within a
    host ride ICI and the axis order follows the physical topology, since
    ``jax.devices()`` enumerates in topology order.
    """
    devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(f"requested {num_devices} devices, have {len(devices)}")
        devices = devices[:num_devices]
    if axis_shape is None:
        axis_shape = (len(devices),) + (1,) * (len(axis_names) - 1)
    if int(np.prod(axis_shape)) != len(devices):
        raise ValueError(f"axis_shape {axis_shape} != {len(devices)} devices")
    return Mesh(np.asarray(devices).reshape(axis_shape), axis_names)


def _slice_granules(devices, num_slices: int | None) -> dict:
    """DCN granule membership for ``make_hybrid_mesh``: a dict of granule id →
    topology-ordered device list.

    Natural granules first: real slice boundaries (multi-slice TPU), else host
    boundaries (multi-process). A SINGLE natural granule carries no topology
    information (e.g. single-slice backends report slice_index=0 on every device),
    so it falls through to the virtual ``num_slices`` partitioning rather than
    shadowing it. When ``num_slices`` names FEWER granules than the platform's H
    natural HOST granules and divides H (hosts-per-slice > 1 without the
    multi-slice ``slice_index`` attribute), contiguous host granules merge — in
    topology order, so intra-super-granule links stay as local as the enumeration
    allows. Real ``slice_index`` granules never merge (their boundaries ARE the
    DCN; grouping them would put per-layer collectives on it), and any other
    mismatch errors: the real topology wins."""
    n = len(devices)
    if {getattr(d, "slice_index", None) for d in devices} != {None}:
        natural, mergeable = (lambda d: d.slice_index), False
    elif len({d.process_index for d in devices}) > 1:
        # Host granules are a PROXY for slice membership — hosts-per-slice > 1 is
        # a legitimate layout, so these (unlike real slice_index granules, whose
        # boundaries ARE the DCN) may merge under a smaller num_slices below.
        natural, mergeable = (lambda d: d.process_index), True
    else:
        natural, mergeable = (lambda d: 0), False
    granules: dict = {}
    for d in devices:
        granules.setdefault(natural(d), []).append(d)
    if len(granules) == 1:
        if num_slices is None:
            raise ValueError(
                "single-slice single-process platform: pass num_slices to "
                "partition devices into virtual slices (or use make_mesh — "
                "there is no DCN here)")
        per = n // num_slices
        return {s: list(devices[s * per:(s + 1) * per])
                for s in range(num_slices)}
    slice_ids = sorted(granules)
    if num_slices is not None and len(slice_ids) != num_slices:
        if (mergeable and num_slices < len(slice_ids)
                and len(slice_ids) % num_slices == 0):
            per_super = len(slice_ids) // num_slices
            return {s: [d for g in slice_ids[s * per_super:(s + 1) * per_super]
                        for d in granules[g]]
                    for s in range(num_slices)}
        raise ValueError(
            f"num_slices {num_slices} != the platform's {len(slice_ids)} "
            f"natural granules (slices/hosts)"
            + (" and does not divide them" if mergeable else "")
            + " — the real topology wins; drop or match the override")
    return granules


# Nominal per-device budget when neither the runtime nor the spec table knows the
# chip (CPU test platforms, unknown kinds) — deterministic rather than a guess
# per machine; override with PLAN_HBM_BYTES.
DEFAULT_DEVICE_MEMORY = 16 << 30


def device_memory_budget(device=None) -> tuple[int, str]:
    """Usable accelerator-memory bytes for one device, with provenance.

    Returns ``(bytes, source)`` where source is ``"env"`` (the ``PLAN_HBM_BYTES``
    override), ``"runtime"`` (the PJRT ``memory_stats()['bytes_limit']`` this
    process actually got), ``"spec"`` (the committed per-kind capacity table —
    ``utils.benchmarks.HBM_CAPACITY_BY_KIND``, next to its bandwidth/FLOPs
    siblings), or ``"nominal"`` (unknown device — the deterministic default).
    The planner's memory pruning (``plan/search.py``) treats only the first two
    as hard facts; the table is what a pod the process can't see yet is judged
    by."""
    # Lazy: utils.benchmarks pulls the trainer stack, which imports this module.
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.benchmarks import (
        HBM_CAPACITY_BY_KIND, lookup_by_kind,
    )

    if os.environ.get("PLAN_HBM_BYTES"):
        return int(os.environ["PLAN_HBM_BYTES"]), "env"
    if device is None:
        device = jax.devices()[0]
    try:
        stats = device.memory_stats()
    except Exception:
        stats = None
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"]), "runtime"
    kind = str(getattr(device, "device_kind", device.platform))
    cap = lookup_by_kind(HBM_CAPACITY_BY_KIND, kind)
    if cap is not None:
        return int(cap), "spec"
    return int(DEFAULT_DEVICE_MEMORY), "nominal"


def num_granules(devices=None) -> int:
    """How many DCN granules (slices, else hosts) the device set spans — the
    count whose boundaries collectives must cross the data-center network to
    pass. 1 means everything rides ICI (single slice, single host)."""
    if devices is None:
        devices = jax.devices()
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    if slice_ids != {None}:
        return len(slice_ids)
    return max(len({d.process_index for d in devices}), 1)


def topology_summary(devices=None) -> dict:
    """One-call snapshot of the physical topology the planner costs layouts
    against: device count/kind/platform, per-chip memory budget (+ provenance),
    and the DCN granule count. Pure introspection — no backend mutation, safe
    before or after ``initialize_cluster``."""
    if devices is None:
        devices = jax.devices()
    budget, source = device_memory_budget(devices[0])
    return {
        "platform": devices[0].platform,
        "device_kind": str(getattr(devices[0], "device_kind",
                                   devices[0].platform)),
        "device_count": len(devices),
        "process_count": jax.process_count(),
        "hbm_bytes": budget,
        "hbm_source": source,
        "num_granules": num_granules(devices),
    }


def make_hybrid_mesh(axis_names: tuple[str, ...], axis_shape: tuple[int, ...],
                     *, dcn_axis: str = "data", num_slices: int | None = None,
                     devices=None) -> Mesh:
    """Device mesh for multi-slice (ICI × DCN) topologies: ``dcn_axis``'s LEADING
    factor strides across slices — the only axis whose collectives cross the
    data-center network — while its within-slice remainder and every other axis stay
    inside a slice and ride ICI.

    This is the scaling-book recipe for multi-pod training: put (the outer factor
    of) data parallelism on DCN, where one gradient all-reduce per step amortizes
    the slow links, and keep model/seq/expert sharding — whose collectives fire per
    layer — on ICI. The device arrangement is what
    ``jax.experimental.mesh_utils.create_hybrid_device_mesh`` produces for the same
    split (slice-major along ``dcn_axis``); first-party here so the slice
    granule can also be VIRTUAL (``num_slices`` on a single-slice or CPU platform),
    which is how the multi-slice layout is exercised without multi-slice hardware —
    the same trick the virtual 8-device CPU mesh plays for multi-chip.

    Slice membership comes from ``device.slice_index`` (multi-slice TPU), else
    process index (one granule per host), else an explicit ``num_slices``
    partitioning the topology-ordered device list into equal contiguous granules.
    """
    if dcn_axis not in axis_names:
        raise ValueError(f"dcn_axis {dcn_axis!r} not in axis_names {axis_names}")
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if int(np.prod(axis_shape)) != n:
        raise ValueError(f"axis_shape {axis_shape} != {n} devices")
    if num_slices is not None and (num_slices < 1 or n % num_slices):
        raise ValueError(f"num_slices {num_slices} must be >= 1 and divide the "
                         f"{n} devices")

    granules = _slice_granules(devices, num_slices)
    slice_ids = sorted(granules)
    sizes = {len(v) for v in granules.values()}
    if len(sizes) != 1:
        raise ValueError(f"uneven slices: {sorted(sizes)} devices per granule")

    pos = axis_names.index(dcn_axis)
    n_slices = len(slice_ids)
    if axis_shape[pos] % n_slices:
        raise ValueError(
            f"{dcn_axis} axis size {axis_shape[pos]} must divide by the "
            f"{n_slices} slices (its leading factor is the DCN dimension)")
    inner = axis_shape[pos] // n_slices
    per_slice_shape = axis_shape[:pos] + (inner,) + axis_shape[pos + 1:]
    if int(np.prod(per_slice_shape)) != sizes.pop():
        raise ValueError(f"per-slice shape {per_slice_shape} != slice device count")
    stacked = np.stack([np.asarray(granules[s]).reshape(per_slice_shape)
                        for s in slice_ids], axis=pos)
    return Mesh(stacked.reshape(axis_shape), axis_names)
