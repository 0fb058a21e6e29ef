"""Benchmark: MNIST 1-epoch wall-clock on TPU — the reference's headline metric.

The reference's published result is time-to-train-one-epoch vs machine count: ≈17.5 on one
e2-standard-8 CPU machine and ≈7.6 on four machines with DDP/gloo, unit unlabeled on the chart
(BASELINE.md). ``vs_baseline`` reported here is the speedup over the reference's best
(4-machine, 7.6) figure under the *most conservative* reading of its unlabeled y-axis —
seconds. Anything >1 beats the whole reference cluster with this framework.

One process, one measurement, one JSON line on stdout. The published metric is a device
time, so it is measured on the chip or not at all: when jax's platform is not ``tpu`` the
script exits non-zero with a one-line reason and prints no measurement. The only non-TPU
run is the functional one behind ``BENCH_MAX_TRAIN_EXAMPLES=N`` (a truncated split, for
tests), and that run is labelled FUNCTIONAL TEST in its metric name — never the published
name, never a ``vs_baseline``.

Throughput/MFU: alongside epoch seconds the JSON carries steps/s, examples/s, achieved
model FLOP/s, and an MFU estimate against the chip's bf16 peak (the model runs f32, so the
estimate is conservative). Model FLOPs/step are computed statically from the flagship
architecture (SURVEY.md §3.4).

Measurement protocol (warmup + median of 7 timed epochs — in the r3 captures the first
timed epoch ran ~40-50% slow, and 3-sample medians straddling it made those captures
diverge; min and all samples ride beside the median — each epoch closed by a host fetch of
a scalar data-dependent on its final *parameter update*): ``utils/benchmarks.py``;
``BENCH_TIMED_EPOCHS`` overrides the count.
"""

import json
import os
import sys

BASELINE_BEST = 7.6          # reference 4-machine DDP/gloo epoch time (BASELINE.md)


class NotOnChip(RuntimeError):
    """The published protocol was asked for on a platform that is not ``tpu``."""


def measure() -> dict:
    """The measurement. Raises :class:`NotOnChip` before any timing when the platform
    is not ``tpu`` and the run is not the truncated functional one."""
    import jax

    dev = jax.devices()[0]
    # Functional-test knob only — the published protocol is the full 60k split (0).
    truncated_to = int(os.environ.get("BENCH_MAX_TRAIN_EXAMPLES", "0"))
    if dev.platform != "tpu" and truncated_to <= 0:
        raise NotOnChip(
            f"platform is {dev.platform!r}, not 'tpu': the MNIST epoch time is a device "
            f"metric and is only measured on the chip (BENCH_MAX_TRAIN_EXAMPLES=N runs "
            f"a labelled functional test instead)")

    from csed_514_project_distributed_training_using_pytorch_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()

    from csed_514_project_distributed_training_using_pytorch_tpu.data import load_mnist
    from csed_514_project_distributed_training_using_pytorch_tpu.models.cnn import Net
    from csed_514_project_distributed_training_using_pytorch_tpu.parallel import (
        data_parallel as dp,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.parallel.mesh import (
        make_mesh,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
        make_eval_fn,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.benchmarks import (
        GLOBAL_BATCH, LEARNING_RATE, MOMENTUM, TRAIN_FLOPS_PER_EXAMPLE, peak_flops,
        time_epochs,
    )

    from csed_514_project_distributed_training_using_pytorch_tpu.data import mnist

    mesh = make_mesh()
    train_ds, test_ds = load_mnist("files")
    full_split = truncated_to <= 0 or truncated_to >= len(train_ds)
    if dev.platform != "tpu" and full_split:
        raise NotOnChip(
            f"platform is {dev.platform!r}, not 'tpu', and BENCH_MAX_TRAIN_EXAMPLES="
            f"{truncated_to} does not truncate the {len(train_ds)}-example split")
    train_ds = mnist.truncate(train_ds, truncated_to)
    # Scan-body unroll factor (semantics-preserving, equivalence-tested); >1 amortizes
    # per-iteration control overhead, which can rival compute on a model this small.
    # Default 8: the round-2 hardware sweep (bench_results/bench_r2_tpu_knob_sweep/)
    # measured unroll=8 + pregather as the best stable configuration on a v5e chip
    # (0.171-0.176 s/epoch vs 0.194 at unroll=1 without pregather).
    unroll = int(os.environ.get("BENCH_UNROLL", "8"))
    # Gather the epoch's batches once before the scan instead of per step (semantics-
    # preserving, equivalence-tested); trades one epoch-sized HBM copy for gather latency.
    pregather = (os.environ.get("BENCH_PREGATHER", "on").strip().lower()
                 in ("1", "true", "yes", "on"))

    # 7 timed epochs (r4): in the r3 captures the first timed epoch ran ~40-50%
    # slow (residual warm-up the single warmup epoch didn't absorb), and the r3
    # driver/builder captures diverged (0.1973 vs 0.2516 s) purely on 3-sample
    # medians straddling it; a 7-sample median sits firmly in the steady state, and
    # min/median are both reported so the spread is visible in the artifact.
    timed = max(1, int(os.environ.get("BENCH_TIMED_EPOCHS", "7")))
    result = time_epochs(mesh, train_ds, global_batch=GLOBAL_BATCH,
                         learning_rate=LEARNING_RATE, momentum=MOMENTUM,
                         seed=1, timed_epochs=timed, unroll=unroll,
                         pregather=pregather)

    eval_fn = dp.compile_eval(make_eval_fn(Net(), batch_size=1000), mesh)
    test_x = dp.put_global(mesh, test_ds.images, jax.sharding.PartitionSpec())
    test_y = dp.put_global(mesh, test_ds.labels, jax.sharding.PartitionSpec())
    sum_nll, correct = jax.device_get(
        eval_fn(result.final_state.params, test_x, test_y))

    examples_per_epoch = result.steps_per_epoch * GLOBAL_BATCH
    examples_per_s = examples_per_epoch / result.median_seconds
    achieved_flops = examples_per_s * TRAIN_FLOPS_PER_EXAMPLE
    peak = peak_flops(dev.device_kind) if dev.platform == "tpu" else None

    return {
        # Telemetry event typing: the bench artifact is one "bench" event in the
        # utils/telemetry.py schema, so tools/telemetry_report.py compares bench
        # runs against training runs through the same reader.
        "event": "bench",
        # A truncated functional run is labeled as such and never compared against the
        # reference's FULL-epoch time — a 16-step "epoch" beating 7.6 s means nothing.
        "metric": ("MNIST 1-epoch wall-clock (60k examples, global batch 64)"
                   if full_split else
                   f"MNIST truncated-epoch wall-clock ({len(train_ds)} examples, "
                   f"global batch 64) — FUNCTIONAL TEST, not the published protocol"),
        "value": round(result.median_seconds, 4),
        "unit": "s",
        "vs_baseline": (round(BASELINE_BEST / result.median_seconds, 2)
                        if full_split else None),
        "devices": result.devices,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "steps_per_epoch": result.steps_per_epoch,
        "train_examples": len(train_ds),
        "scan_unroll": unroll,
        "pregather": pregather,
        "steps_per_s": round(result.steps_per_epoch / result.median_seconds, 1),
        "examples_per_s": round(examples_per_s, 1),
        "model_train_flops_per_example": TRAIN_FLOPS_PER_EXAMPLE,
        "achieved_model_flops_per_s": round(achieved_flops),
        "mfu_vs_bf16_peak": (round(achieved_flops / (peak * result.devices), 8)
                             if peak else None),
        "epoch_seconds_all": [round(t, 4) for t in result.epoch_seconds],
        "min_epoch_seconds": round(min(result.epoch_seconds), 4),
        "final_train_loss": round(result.final_train_loss, 4),
        "epochs_trained": 1 + timed,        # warmup + timed, all real training
        "test_nll_after_run": round(float(sum_nll) / len(test_ds), 4),
        "test_accuracy_after_run": round(float(correct) / len(test_ds), 4),
        "data_source": train_ds.source,
    }


def _emit(payload: dict, telemetry_path: str | None) -> None:
    """Print the one bench JSON line and (``--telemetry PATH``) append it as a
    telemetry event — the same ``"event": "bench"`` schema the trainers' telemetry
    files use, so ``tools/telemetry_report.py`` compares bench and training runs.
    A diverged run's NaN serializes as null (strict JSONL), never a bare NaN token."""
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.telemetry import (
        _sanitize,
    )

    payload.setdefault("event", "bench")
    line = json.dumps(_sanitize(payload), allow_nan=False)
    print(line)
    if telemetry_path:
        os.makedirs(os.path.dirname(telemetry_path) or ".", exist_ok=True)
        with open(telemetry_path, "a") as f:
            f.write(line + "\n")


def _telemetry_path() -> str | None:
    """The optional ``--telemetry PATH`` argv pair."""
    argv = sys.argv
    if "--telemetry" in argv:
        i = argv.index("--telemetry")
        if i + 1 < len(argv):
            return argv[i + 1]
    return None


def main() -> int:
    try:
        payload = measure()
    except NotOnChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    _emit(payload, _telemetry_path())
    return 0


if __name__ == "__main__":
    sys.exit(main())
