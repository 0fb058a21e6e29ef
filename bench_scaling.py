"""Scaling benchmark: time-to-train-one-epoch vs device count — the reference's headline
chart (README.md:20, ``images/Time to train (1 epoch) vs. Number of machines.png``:
≈17.5 / 11.3 / 7.6 / 5.0 at 1 / 2 / 4 / 8 gloo machines — 3.5× at 8 workers, 44% efficiency;
BASELINE.md). Same weak-scaling regime: fixed global batch 64, per-device batch 64/N
(reference ``src/train_dist.py:133``).

Runs one measurement per power-of-two device count up to everything addressable (a single
chip yields just N=1), prints one JSON line per count plus a summary line with speedups and
parallel efficiency, and writes the reference-format chart to
``images/time_vs_devices.png``. Measurement protocol (warmup + median of timed epochs closed
by a host fetch of the final loss scalar): ``utils/benchmarks.py``.

Run on real hardware: ``python bench_scaling.py``. Multi-chip logic can be exercised without
a pod on the virtual CPU mesh (``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8``), but virtual devices share one host's
cores, so those times do NOT measure scaling — the JSON carries ``platform`` so nobody
mistakes one for the other.
"""

import argparse
import json

import jax

from csed_514_project_distributed_training_using_pytorch_tpu.data import load_mnist, mnist
from csed_514_project_distributed_training_using_pytorch_tpu.parallel.mesh import make_mesh
from csed_514_project_distributed_training_using_pytorch_tpu.utils import plotting
from csed_514_project_distributed_training_using_pytorch_tpu.utils.benchmarks import (
    GLOBAL_BATCH, LEARNING_RATE, MOMENTUM, time_epochs,
)


def device_counts(available: int) -> list[int]:
    counts = []
    n = 1
    while n <= available and GLOBAL_BATCH % n == 0:
        counts.append(n)
        n *= 2
    return counts


def _plan_prediction(n: int, steps_per_epoch: int | None = None) -> dict:
    """The planner's view of one device count (``plan/``): rank the legal
    layouts for the reference CNN protocol at ``n`` chips and return the pick's
    predicted step/epoch seconds — the analytical curve the measured one is
    judged against (``--plan``)."""
    import dataclasses

    from csed_514_project_distributed_training_using_pytorch_tpu import plan as plan_mod

    topo = dataclasses.replace(plan_mod.Topology.detect(), num_devices=n)
    scenario = plan_mod.scenarios.for_cnn(GLOBAL_BATCH, topo)
    best = plan_mod.search(scenario)[0]
    out = {"planned_mesh": best.candidate.mesh_spec(),
           "predicted_step_s": round(best.costs.step_s, 8)}
    if steps_per_epoch:
        out["predicted_epoch_seconds"] = round(
            best.costs.step_s * steps_per_epoch, 4)
    return out


def run(max_train_examples: int = 0, timed_epochs: int = 3,
        unroll: int = 1, pregather: bool = False,
        with_plan: bool = False) -> list[dict]:
    available = len(jax.devices())
    platform = jax.devices()[0].platform
    train_ds, _ = load_mnist("files")
    train_ds = mnist.truncate(train_ds, max_train_examples)

    rows = []
    for n in device_counts(available):
        result = time_epochs(make_mesh(n), train_ds, global_batch=GLOBAL_BATCH,
                             learning_rate=LEARNING_RATE, momentum=MOMENTUM,
                             timed_epochs=timed_epochs, unroll=unroll,
                             pregather=pregather)
        rows.append({
            "devices": n,
            "epoch_seconds": round(result.median_seconds, 4),
            "platform": platform,
            "steps_per_epoch": result.steps_per_epoch,
            "scan_unroll": unroll,
            "pregather": pregather,
            "data_source": train_ds.source,
        })
        if with_plan:
            # Planner validation: the analytical pick + its predicted epoch
            # time ride in the same JSON row as the measurement, so the
            # predicted-vs-measured delta (and whether the planner's layout
            # ordering matches the measured curve's) is one jq away.
            rows[-1].update(_plan_prediction(n, result.steps_per_epoch))
            rows[-1]["predicted_vs_measured"] = round(
                rows[-1]["predicted_epoch_seconds"] / rows[-1]["epoch_seconds"],
                3)
        print(json.dumps(rows[-1]), flush=True)

    base = rows[0]["epoch_seconds"]
    for row in rows:
        row["speedup"] = round(base / row["epoch_seconds"], 2)
        row["efficiency"] = round(row["speedup"] / row["devices"], 2)
    summary = {
        "metric": "1-epoch wall-clock scaling (fixed global batch 64)",
        "reference_speedups": {"1": 1.0, "2": 1.55, "4": 2.30, "8": 3.5},
        "measured": [{k: r[k] for k in ("devices", "epoch_seconds", "speedup",
                                        "efficiency")} for r in rows],
    }
    if with_plan:
        summary["planner"] = [
            {k: r[k] for k in ("devices", "planned_mesh",
                               "predicted_epoch_seconds",
                               "predicted_vs_measured")} for r in rows]
    print(json.dumps(summary), flush=True)

    plotting.save_scaling_curve([r["devices"] for r in rows],
                                [r["epoch_seconds"] for r in rows],
                                "images/time_vs_devices.png")
    return rows


def run_batch_sweep(batches: list[int], max_train_examples: int = 0,
                    timed_epochs: int = 3) -> list[dict]:
    """Global-batch sweep at fixed (maximum) device count — BASELINE.json configs[3]
    ("8-chip pmap MNIST ... global-batch sweep 256/1024/4096"). The reference's regime is
    throughput-oriented weak scaling of work per step: per-device batch = global/N grows
    with the global batch while the device count stays fixed, so examples/s rising with
    batch size is the MXU-utilization story the sweep exists to show. Learning rate stays
    at the reference value — this sweep measures throughput, not convergence tuning.

    Writes one JSON line per batch size, a summary line, and
    ``images/time_vs_global_batch.png``.
    """
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.benchmarks import (
        TRAIN_FLOPS_PER_EXAMPLE,
    )

    n = len(jax.devices())
    platform = jax.devices()[0].platform
    train_ds, _ = load_mnist("files")
    train_ds = mnist.truncate(train_ds, max_train_examples)
    mesh = make_mesh(n)

    rows = []
    for gb in batches:
        if gb % n or gb > len(train_ds):
            print(json.dumps({"global_batch": gb,
                              "skipped": f"not divisible by {n} devices or larger "
                                         f"than the {len(train_ds)}-example split"}),
                  flush=True)
            continue
        result = time_epochs(mesh, train_ds, global_batch=gb,
                             learning_rate=LEARNING_RATE, momentum=MOMENTUM,
                             timed_epochs=timed_epochs)
        examples = result.steps_per_epoch * gb
        rows.append({
            "global_batch": gb,
            "devices": n,
            "per_device_batch": gb // n,
            "epoch_seconds": round(result.median_seconds, 4),
            "examples_per_s": round(examples / result.median_seconds, 1),
            "achieved_model_flops_per_s": round(
                examples / result.median_seconds * TRAIN_FLOPS_PER_EXAMPLE),
            "steps_per_epoch": result.steps_per_epoch,
            "platform": platform,
            "data_source": train_ds.source,
        })
        print(json.dumps(rows[-1]), flush=True)

    print(json.dumps({
        "metric": "global-batch sweep, fixed device count (BASELINE.json configs[3])",
        "devices": n, "platform": platform,
        "measured": [{k: r[k] for k in ("global_batch", "epoch_seconds",
                                        "examples_per_s")} for r in rows],
    }), flush=True)
    if rows:
        plotting.save_batch_sweep_curve(
            [r["global_batch"] for r in rows], [r["examples_per_s"] for r in rows],
            "images/time_vs_global_batch.png")
    return rows


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    parser.add_argument("--max-train-examples", type=int, default=0,
                        help="0 = full 60k (the published protocol); >0 truncates for "
                             "quick functional runs")
    parser.add_argument("--timed-epochs", type=int, default=3)
    parser.add_argument("--unroll", type=int, default=1,
                        help="scan-body unroll factor for the device sweep "
                             "(semantics-preserving; amortizes per-step control "
                             "overhead on tiny models)")
    parser.add_argument("--pregather", action="store_true",
                        help="gather each epoch's batches once before the scan "
                             "(semantics-preserving; the shipped bench.py default)")
    parser.add_argument("--sweep-global-batch", nargs="*", type=int, default=None,
                        metavar="B",
                        help="run the global-batch sweep instead of the device sweep "
                             "(default sizes 256 1024 4096 when given no values)")
    parser.add_argument("--plan", action="store_true",
                        help="also run the parallelism planner (plan/) per device "
                             "count and emit its pick + predicted epoch seconds "
                             "next to each measurement — the predicted-vs-"
                             "measured validation of the cost model")
    args = parser.parse_args()
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    if args.sweep_global_batch is not None:
        run_batch_sweep(args.sweep_global_batch or [256, 1024, 4096],
                        args.max_train_examples, args.timed_epochs)
    else:
        run(args.max_train_examples, args.timed_epochs, args.unroll,
            args.pregather, with_plan=args.plan)
