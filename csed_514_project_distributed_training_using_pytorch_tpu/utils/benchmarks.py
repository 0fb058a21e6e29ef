"""Shared benchmark protocol: honest wall-clock for one training epoch on a mesh.

This is the measurement behind both headline artifacts of the reference — the single number
"time to train 1 epoch" and the time-vs-worker-count scaling curve (reference README.md:20,
``images/Time to train (1 epoch) vs. Number of machines.png``; the reference instruments it
as ``time.time() - t0`` around its epoch loop, ``src/train.py:10,99``).

Protocol details (SURVEY.md §7 hard part (c)):

- the whole epoch is ONE jit-compiled scanned program over the mesh (no per-step Python);
- one untimed warmup epoch pays for compilation and data fault-in;
- each timed epoch is closed by a device→host fetch of a scalar that is data-dependent on
  the epoch's final loss AND on the final step's parameter update (a leaf of the returned
  state), so the last backward/all-reduce/SGD cannot still be in flight at t1. A transfer
  of a value data-dependent on the whole epoch is complete only when the epoch is, on any
  backend.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from csed_514_project_distributed_training_using_pytorch_tpu.data.mnist import Dataset
from csed_514_project_distributed_training_using_pytorch_tpu.models.cnn import Net
from csed_514_project_distributed_training_using_pytorch_tpu.parallel import (
    data_parallel as dp,
)
from csed_514_project_distributed_training_using_pytorch_tpu.parallel.sampler import (
    ShardedSampler,
)
from csed_514_project_distributed_training_using_pytorch_tpu.train.distributed import (
    epoch_index_plan,
)
from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
    create_train_state, make_epoch_fn,
)


# The reference-parity training configuration both bench entry points measure under
# (reference src/train.py:12-16; global batch stays fixed as devices grow, :133).
GLOBAL_BATCH = 64
LEARNING_RATE = 0.01
MOMENTUM = 0.5

# Per-example model FLOPs, forward pass, computed statically from the flagship
# architecture (models/cnn.py; SURVEY.md §3.4): conv as 2·H_out·W_out·C_out·(K·K·C_in)
# MACs, dense as 2·in·out.
FWD_FLOPS_PER_EXAMPLE = (
    2 * 24 * 24 * 10 * (5 * 5 * 1)      # conv1: 288,000
    + 2 * 8 * 8 * 20 * (5 * 5 * 10)     # conv2: 640,000
    + 2 * 320 * 50                      # fc1:    32,000
    + 2 * 50 * 10                       # fc2:     1,000
)
TRAIN_FLOPS_PER_EXAMPLE = 3 * FWD_FLOPS_PER_EXAMPLE   # fwd + ~2× for backward

# bf16 peak per chip by device_kind substring (public spec sheets). The model computes in
# f32, so an MFU against bf16 peak is a conservative lower bound. Ordered: first match
# wins, so more specific kinds come before their prefixes.
PEAK_FLOPS_BY_KIND = [
    ("v6", 918e12), ("v5p", 459e12), ("v5", 197e12), ("v4", 275e12),
    ("v3", 123e12), ("v2", 45e12),
]

# Published per-chip HBM bandwidth — the roofline batched KV-cache decode is judged
# against (decode is bandwidth-bound: every step re-reads the cache + weights).
PEAK_HBM_BYTES_BY_KIND = [
    ("v6", 1640e9), ("v5p", 2765e9), ("v5", 819e9), ("v4", 1228e9),
    ("v3", 900e9), ("v2", 700e9),
]

# Published per-chip HBM CAPACITY (spec sheets) — what a chip the process can't
# introspect yet is judged by (``parallel.mesh.device_memory_budget``'s fallback
# when the runtime reports no limit).
HBM_CAPACITY_BY_KIND = [
    ("v6", 32 << 30), ("v5p", 95 << 30), ("v5", 16 << 30), ("v4", 32 << 30),
    ("v3", 16 << 30), ("v2", 8 << 30),
]


def lookup_by_kind(table, device_kind: str, default=None):
    """First-match substring lookup over a device-kind spec table — the ONE
    matcher behind every per-kind table here (peak FLOPs, HBM bandwidth/
    capacity) and the planner's interconnect table (``plan.costs``). Tables are
    ordered most-specific-first ('v5p' before 'v5'); adding a chip generation
    means adding rows, never another matcher."""
    kind = device_kind.lower()
    return next((val for key, val in table if key in kind), default)


def peak_hbm_bytes(device_kind: str) -> float | None:
    """Peak HBM bytes/s for a TPU ``device_kind`` string, or None if unknown."""
    return lookup_by_kind(PEAK_HBM_BYTES_BY_KIND, device_kind)


def chained_diff_time(chain, *, n1=2, grow=8, max_n=4096, min_delta=0.25,
                      reps=3, warmup=1):
    """Per-iteration time of a chained computation via the two-point difference
    ``(t(N2) − t(N1)) / (N2 − N1)``: the fixed per-dispatch cost (launch + the
    closing host fetch) cancels exactly in the difference, so it cannot swamp a
    short op the way a one-dispatch-per-rep measurement lets it.
    ``chain(n)`` returns a zero-arg callable that runs the n-long
    chained program AND blocks on a data-dependent fetch. N2 grows geometrically
    (``grow``× per probe, capped at ``max_n``) until the chained work adds
    ``min_delta`` seconds over N1, so per-dispatch jitter (~ms) cannot dominate the
    difference. Returns ``(per_iter_seconds, (n1, t1), (n2, t2), converged)`` —
    ``converged`` is False when ``max_n`` was exhausted before the chain ever added
    ``min_delta`` seconds, i.e. the two-point difference is still jitter-dominated
    and callers should mark the row as such in their artifacts (r4 advisor
    finding). One owner for the protocol — a fix lands in every bench at once
    (bench_attention, bench_lm)."""
    def timed(run):
        for _ in range(warmup):
            run()
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            run()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    t1 = timed(chain(n1))
    n2, t2 = n1, t1
    while n2 < max_n:
        n2 = min(n2 * grow, max_n)
        t2 = timed(chain(n2))
        if t2 - t1 >= min_delta:
            break
    return (max((t2 - t1) / (n2 - n1), 1e-9), (n1, t1), (n2, t2),
            t2 - t1 >= min_delta)


def timed_state_run(run, state):
    """Time ONE compiled ``state -> (state, losses)`` program with the sync
    protocol the microbenches share: the clock stops only after a device→host fetch
    of a scalar data-dependent on the last loss AND a parameter leaf, so neither the
    last forward nor the last update can still be in flight.
    Returns ``(state, seconds, last_loss)``. One owner for the probe — a sync-protocol
    fix lands in every bench at once."""
    import jax.numpy as jnp

    t0 = time.perf_counter()
    state, losses = run(state)
    probe = losses[-1] + jax.tree_util.tree_leaves(state.params)[0].astype(
        jnp.float32).ravel()[0]
    jax.device_get(probe)
    return state, time.perf_counter() - t0, float(jax.device_get(losses[-1]))


def peak_flops(device_kind: str) -> float | None:
    """bf16 peak FLOP/s for a TPU ``device_kind`` string, or None if unknown."""
    return lookup_by_kind(PEAK_FLOPS_BY_KIND, device_kind)


@dataclass(frozen=True)
class EpochBenchResult:
    """One mesh-size measurement of the reference's headline metric."""

    devices: int
    epoch_seconds: list[float]      # every timed epoch, in order
    median_seconds: float
    steps_per_epoch: int
    final_train_loss: float
    final_state: object             # TrainState after warmup + timed epochs (for eval)


def time_epochs(mesh: Mesh, train_ds: Dataset, *, global_batch: int = 64,
                learning_rate: float = 0.01, momentum: float = 0.5,
                seed: int = 1, sampler_seed: int = 42,
                timed_epochs: int = 3, unroll: int = 1,
                pregather: bool = False) -> EpochBenchResult:
    """Measure full-epoch wall-clock on ``mesh`` under the protocol above.

    Hyperparameter defaults are the reference's single-trainer values
    (``src/train.py:12-16``); the global batch stays fixed as devices grow — the reference's
    weak per-worker scaling regime (``src/train_dist.py:133``).
    """
    world = mesh.shape["data"]
    if global_batch % world:
        raise ValueError(f"global batch {global_batch} not divisible by device count "
                         f"{world} — the reported protocol would be wrong")

    model = Net()
    state = jax.device_put(create_train_state(model, jax.random.PRNGKey(seed)),
                           dp.replicated(mesh))
    rng = jax.random.PRNGKey(seed + 1)

    train_x = dp.put_global(mesh, train_ds.images, P())
    train_y = dp.put_global(mesh, train_ds.labels, P())
    epoch_fn = dp.compile_epoch(
        make_epoch_fn(model, learning_rate=learning_rate, momentum=momentum,
                      unroll=unroll, pregather=pregather), mesh)
    samplers = [ShardedSampler(len(train_ds), num_replicas=world, rank=r,
                               seed=sampler_seed) for r in range(world)]

    def one_epoch(state, epoch):
        plan = epoch_index_plan(samplers, epoch, global_batch // world)
        plan_d = dp.put_global(mesh, plan, P(None, "data"))
        state, losses = epoch_fn(state, train_x, train_y, plan_d, rng)
        # The honest sync point: fetch a scalar data-dependent on BOTH the final step's
        # forward (losses[-1]) and its backward/all-reduce/SGD update (a parameter leaf of
        # the returned state) — losses[-1] alone would let the last update stay in flight
        # at t1 (advisor finding r1).
        probe = losses[-1] + jax.tree_util.tree_leaves(state.params)[0].ravel()[0]
        jax.device_get(probe)
        final_loss = float(jax.device_get(losses[-1]))
        return state, final_loss, plan.shape[0]

    state, final_loss, steps = one_epoch(state, 0)       # warmup: compile + fault-in

    times = []
    for epoch in range(1, timed_epochs + 1):
        t0 = time.perf_counter()
        state, final_loss, steps = one_epoch(state, epoch)
        times.append(time.perf_counter() - t0)

    return EpochBenchResult(
        devices=world,
        epoch_seconds=times,
        median_seconds=float(np.median(times)),
        steps_per_epoch=steps,
        final_train_loss=final_loss,
        final_state=state,
    )
