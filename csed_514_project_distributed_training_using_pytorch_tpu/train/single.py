"""Single-process trainer — the reference ``src/train.py`` workflow, TPU-native.

Reproduces, in order (call stack in SURVEY.md §3.1): wall-clock start, seeding, loader
construction, the 6-digit sample-grid figure, baseline eval *before* training, then
``n_epochs`` of (train with a progress line + metric record + checkpoint every
``log_interval`` batches, then eval), and the final train/test loss-curve figure
(reference ``src/train.py:10-117``).

TPU-first differences:

- the hot loop runs as jit-compiled ``lax.scan`` segments of ``log_interval`` steps over the
  device-resident dataset — one host sync per *log tick* (which the reference already pays to
  print) instead of per batch, and zero per-step Python dispatch;
- the loop is a ``main(config)`` function, not an import-time script (the reference executes
  on import, SURVEY.md §3.1), and reads everything from ``SingleProcessConfig`` instead of
  module globals (quirk §2d.3);
- checkpoints keep the reference's overwrite-in-place every-log-tick policy
  (``src/train.py:84-85``, quirk §2d.4) but are atomic and restorable (``--resume``).
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from csed_514_project_distributed_training_using_pytorch_tpu.data import (
    BatchLoader, download_mnist, load_mnist, mnist,
)
from csed_514_project_distributed_training_using_pytorch_tpu.models import (
    build_model,
    validate_model_config,
)
from csed_514_project_distributed_training_using_pytorch_tpu import resilience
from csed_514_project_distributed_training_using_pytorch_tpu.ops import optim
from csed_514_project_distributed_training_using_pytorch_tpu.train.guard import (
    GuardRuntime,
)
from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
    TrainState, create_train_state, init_health, make_epoch_fn, make_eval_fn,
    make_train_step, merge_health, update_health,
)
from csed_514_project_distributed_training_using_pytorch_tpu.utils import checkpoint
from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import (
    SingleProcessConfig, parse_config,
)
from csed_514_project_distributed_training_using_pytorch_tpu.utils import metrics as M
from csed_514_project_distributed_training_using_pytorch_tpu.utils import plotting
from csed_514_project_distributed_training_using_pytorch_tpu.utils import profiling
from csed_514_project_distributed_training_using_pytorch_tpu.utils import (
    telemetry as T,
)


def main(config: SingleProcessConfig = SingleProcessConfig(), *,
         resume_from: str | None = None,
         datasets=None) -> tuple[TrainState, M.MetricsHistory]:
    """Run the full single-process workflow; returns final state + metric history.

    ``datasets`` optionally injects a pre-built ``(train, test)`` Dataset pair (tests,
    notebooks); by default MNIST is loaded from ``config.data_dir``.
    """
    watch = M.Stopwatch()                       # ≙ t0, reference src/train.py:10
    validate_model_config(config.model, remat=config.remat,
                          remat_policy=config.remat_policy, causal=config.causal,
                          attention_window=config.attention_window,
                          kv_heads=config.kv_heads, rope=config.rope)  # fail fast, pre-side-effects
    if config.grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {config.grad_accum}")
    if config.grad_accum > 1 and config.batch_size_train % config.grad_accum:
        raise ValueError(f"batch_size_train {config.batch_size_train} not divisible "
                         f"by grad_accum {config.grad_accum}")
    if config.health_stats and config.use_host_pipeline:
        raise ValueError("--health-stats rides the compiled scan carry "
                         "(train/step.py::HealthStats) — it is not available on the "
                         "per-batch --use-host-pipeline path")
    if config.health_stats and not config.telemetry:
        raise ValueError("--health-stats emits telemetry 'health' events and has no "
                         "other output — pass --telemetry PATH too")
    tele = T.TelemetryWriter(config.telemetry,
                             preserve=bool(config.resume_from))
    tele.emit(T.manifest_event(config, run_type="single"))
    # Resilience wiring (flag-gated, host-side only; with both flags off no step
    # fetch or syscall is added — same zero-cost discipline as --health-stats).
    rt = resilience.RunHooks(heartbeat_dir=config.heartbeat_dir,
                             handle_preemption=config.handle_preemption)
    # Numerical immune system (--guard): in-step verdict + identity update;
    # host side is epoch-boundary bookkeeping only.
    grt = GuardRuntime(config, tele=tele,
                       store_dir=os.path.join(config.results_dir, "checkpoints"))
    if config.download_data and datasets is None:
        download_mnist(config.data_dir)   # ≙ torchvision download=True, src/train.py:26-31
    train_ds, test_ds = datasets if datasets is not None else load_mnist(config.data_dir)
    train_ds = mnist.truncate(train_ds, config.max_train_examples)
    test_ds = mnist.truncate(test_ds, config.max_test_examples)

    M.log(f"Loaded MNIST ({train_ds.source}): {len(train_ds)} train / {len(test_ds)} test")
    root = jax.random.PRNGKey(config.seed)      # ≙ torch.manual_seed, src/train.py:19-21
    init_rng, dropout_rng = jax.random.split(root)
    train_loader = BatchLoader(train_ds, config.batch_size_train, shuffle=True,
                               seed=config.seed)

    # Sample grid before training (≙ reference src/train.py:43-57).
    plotting.save_sample_grid(test_ds.images, test_ds.labels,
                              os.path.join(config.images_dir, "train_images.png"))

    model = build_model(config.model, bf16=config.bf16, remat=config.remat,
                        remat_policy=config.remat_policy,
                        causal=config.causal,
                        attention_window=config.attention_window,
                        kv_heads=config.kv_heads, rope=config.rope)
    optimizer = optim.make_optimizer(config.optimizer,
                                     learning_rate=config.learning_rate,
                                     momentum=config.momentum,
                                     weight_decay=config.weight_decay)
    if config.optimizer != "sgd" and config.use_pallas_kernels:
        raise ValueError("--use-pallas-kernels fuses the SGD-momentum update — it "
                         "requires --optimizer sgd")
    state = create_train_state(model, init_rng, optimizer=optimizer,
                               ema=config.ema_decay > 0, guard=config.guard)
    resume_from = resume_from or config.resume_from or None
    if resume_from:                             # the restore path the reference lacks
        t_restore = time.perf_counter()
        state = checkpoint.restore_train_state(resume_from, state)
        if tele.enabled:
            tele.emit(T.checkpoint_event(
                op="restore", path=resume_from, kind="full",
                nbytes=os.path.getsize(resume_from),
                wall_s=time.perf_counter() - t_restore, step=int(state.step)))
        M.log(f"Resumed from {resume_from} at step {int(state.step)}")
        # Manifest cursor cross-check (DESIGN.md §26): a versioned checkpoint
        # carries the data position that produced it; a disagreeing config
        # resumes a DIFFERENT stream and should say so up front.
        note = checkpoint.check_cursor_resume(resume_from, seed=config.seed,
                                              step=int(state.step))
        if note:
            M.log(f"WARNING: {note}")
    grt.baseline(state)     # this attempt's anomaly-counter zero point
    # Schedule horizon = THIS invocation's planned end: the restored step plus
    # n_epochs of updates (single-trainer resume means "train n_epochs MORE", unlike
    # the distributed/composed trainers' skip-completed-epochs semantics). Anchoring
    # past the restored step keeps a resumed cosine run decaying over its own span
    # instead of evaluating beyond the original horizon at multiplier 0 (a silently
    # frozen run). drop_last=False: the ragged tail batch is still one update.
    total_steps = (int(state.step)
                   + config.n_epochs * (-(-len(train_ds) // config.batch_size_train)))
    lr_schedule = optim.make_lr_schedule(config.lr_schedule,
                                         warmup_steps=config.warmup_steps,
                                         total_steps=total_steps)
    if lr_schedule is not None and config.use_pallas_kernels:
        raise ValueError("--use-pallas-kernels bakes the learning rate into the "
                         "fused update kernel — use the default constant schedule "
                         "without warmup")

    # Device-resident datasets: the one and only host->device transfer.
    train_x, train_y = jnp.asarray(train_ds.images), jnp.asarray(train_ds.labels)
    test_x, test_y = jnp.asarray(test_ds.images), jnp.asarray(test_ds.labels)

    health = config.health_stats
    segment_fn = jax.jit(
        make_epoch_fn(model, learning_rate=config.learning_rate,
                      momentum=config.momentum,
                      use_pallas=config.use_pallas_kernels,
                      unroll=config.scan_unroll, pregather=config.pregather,
                      grad_accum=config.grad_accum, optimizer=optimizer,
                      lr_schedule=lr_schedule,
                      clip_grad_norm=config.clip_grad_norm,
                      ema_decay=config.ema_decay,
                      label_smoothing=config.label_smoothing,
                      health=health, guard=grt.spec),
        donate_argnums=(0,))
    step_fn = jax.jit(
        make_train_step(model, learning_rate=config.learning_rate,
                        momentum=config.momentum,
                        use_pallas=config.use_pallas_kernels,
                        grad_accum=config.grad_accum, optimizer=optimizer,
                        lr_schedule=lr_schedule,
                        clip_grad_norm=config.clip_grad_norm,
                        ema_decay=config.ema_decay,
                        label_smoothing=config.label_smoothing,
                        with_metrics=health, guard=grt.spec),
        donate_argnums=(0,))
    # The final partial batch (drop_last=False) is ragged and need not divide by
    # grad_accum; accumulation is a memory knob, so the tail just steps unaccumulated.
    if config.grad_accum == 1:
        tail_step_fn = step_fn
    else:
        tail_step_fn = jax.jit(
            make_train_step(model, learning_rate=config.learning_rate,
                            momentum=config.momentum,
                            use_pallas=config.use_pallas_kernels,
                            optimizer=optimizer, lr_schedule=lr_schedule,
                            clip_grad_norm=config.clip_grad_norm,
                            ema_decay=config.ema_decay,
                            label_smoothing=config.label_smoothing,
                            with_metrics=health, guard=grt.spec),
            donate_argnums=(0,))
    eval_fn = jax.jit(make_eval_fn(model, batch_size=config.batch_size_test))

    # Compile/execute split (telemetry): AOT-compile the epoch-segment program via
    # jit(...).lower().compile() so first-epoch wall time decomposes into compile_s
    # (here) + execute_s (the loop's honest-synced device time), and so XLA's
    # cost_analysis() prices the step for the MFU estimate. The compiled program is
    # then what the loop invokes — the jit cache never pays a second compile.
    segment_call = segment_fn
    compile_s = flops_per_step = None
    if config.telemetry and not config.use_host_pipeline:
        idx_struct = jax.ShapeDtypeStruct(
            (config.log_interval, config.batch_size_train), jnp.int32)
        compiled, aot = T.aot_compile(segment_fn, state, train_x, train_y,
                                      idx_struct, dropout_rng)
        if compiled is not None:
            segment_call = compiled
            compile_s = aot["lower_s"] + aot["compile_s"]
            if aot["flops"]:
                flops_per_step = aot["flops"] / config.log_interval
            tele.emit(T.compile_event("epoch_segment", aot,
                                      steps_per_call=config.log_interval))

    history = M.MetricsHistory()
    n_train, n_test = len(train_ds), len(test_ds)
    ckpt_path = os.path.join(config.results_dir, "model.ckpt")
    ckpt_store = os.path.join(config.results_dir, "checkpoints")
    saver = checkpoint.make_saver(config.async_checkpoint, tele=tele)

    def evaluate(state: TrainState, examples_seen: int) -> None:
        # EMA-enabled runs evaluate the averaged weights (the reason to keep an EMA).
        eval_params = state.ema if state.ema is not None else state.params
        sum_nll, correct = jax.device_get(eval_fn(eval_params, test_x, test_y))
        avg = float(sum_nll) / n_test           # ≙ sum-then-divide, src/train.py:94-97
        history.record_test(examples_seen, avg)
        M.log(M.test_summary_line(avg, int(correct), n_test, watch.elapsed()))

    def train_epoch(state: TrainState, epoch: int):
        times = {"execute": 0.0, "data": 0.0, "loss_sum": 0.0, "loss_steps": 0}
        t_data = time.perf_counter()
        train_loader.set_epoch(epoch)
        indices = train_loader.sampler.epoch_indices(epoch)
        idx_full = train_loader.epoch_index_matrix(epoch, allow_empty=True)
        times["data"] = time.perf_counter() - t_data
        full_steps = idx_full.shape[0]
        epoch_health = init_health() if health else None

        # log_interval-sized jit'd scan segments, then the ragged tail.
        li = config.log_interval
        for seg_start in range(0, full_steps, li):
            seg = idx_full[seg_start:seg_start + li]
            t_exec = time.perf_counter()
            if len(seg) == li:
                state, out = segment_call(state, train_x, train_y,
                                          jnp.asarray(seg), dropout_rng)
                if health:
                    losses, seg_health = out
                    epoch_health = merge_health(epoch_health, seg_health)
                else:
                    losses = out
                seg_losses = np.asarray(jax.device_get(losses))
            else:  # tail of < log_interval full batches — stepwise (same compiled step)
                step_losses = []
                for row in seg:
                    state, out = step_fn(state, train_x[jnp.asarray(row)],
                                         train_y[jnp.asarray(row)], dropout_rng)
                    if health:
                        loss, gnorm = out
                        epoch_health = update_health(epoch_health, loss, gnorm)
                    else:
                        loss = out
                    step_losses.append(loss)    # device scalars — ONE fetch below
                seg_losses = np.asarray(jax.device_get(step_losses))
            last_loss = float(seg_losses[-1])   # the tick's host sync, as before
            # Epoch-mean accumulation (telemetry): same per-epoch train_loss
            # definition as the distributed/LM/composed epoch events.
            times["loss_sum"] += float(seg_losses.sum())
            times["loss_steps"] += seg_losses.size
            times["execute"] += time.perf_counter() - t_exec  # closed by the fetch above
            batches_done = min(seg_start + li, full_steps)
            examples_seen = (epoch - 1) * n_train + batches_done * config.batch_size_train
            M.log(M.train_progress_line(epoch, batches_done * config.batch_size_train,
                                        n_train, last_loss))
            history.record_train(examples_seen, last_loss)
            # every-log-tick overwrite checkpoint (≙ reference src/train.py:84-85)
            saver.save_train_state(ckpt_path, state)

        # final partial batch (drop_last=False, ≙ torch DataLoader default)
        tail = indices[full_steps * config.batch_size_train:]
        if len(tail):
            t_exec = time.perf_counter()
            state, out = tail_step_fn(state, train_x[jnp.asarray(tail)],
                                      train_y[jnp.asarray(tail)], dropout_rng)
            if health:
                epoch_health = update_health(epoch_health, *out)
                tail_loss = out[0]
            else:
                tail_loss = out
            times["loss_sum"] += float(tail_loss)
            times["loss_steps"] += 1
            times["execute"] += time.perf_counter() - t_exec
        return state, epoch_health, times

    def train_epoch_host_pipeline(state: TrainState, epoch: int):
        """The reference-shaped loop: host batches through the native C++ threaded
        prefetcher (the DataLoader worker-pool analog), one device dispatch per batch.
        Identical step sequence (same index plan, same per-step RNG fold) to the scan fast
        path — only the feeding mechanism differs. (--health-stats is rejected up
        front on this path — the accumulators ride the scan carry.)"""
        t_epoch = time.perf_counter()
        train_loader.set_epoch(epoch)
        train_loader.pop_wait_s()       # this epoch's stall ledger starts at zero
        full_steps = train_loader.epoch_index_matrix(epoch, allow_empty=True).shape[0]
        step_losses = []      # device scalars — fetched ONCE at epoch end
        # Live per-batch bar (≙ the reference's tqdm, src/train_dist.py:76) — only
        # here, where a per-step dispatch already exists; tty/process-0 gated.
        with M.ProgressBar(full_steps, desc=f"Epoch {epoch} ") as bar:
            for b, (bx, by) in enumerate(train_loader.prefetch_iter(epoch),
                                         start=1):
                state, loss = step_fn(state, jnp.asarray(bx), jnp.asarray(by),
                                      dropout_rng)
                step_losses.append(loss)
                if b % config.log_interval == 0 or b == full_steps:
                    # The log line and the in-place bar share the terminal: finish
                    # the bar's line first (float(loss) syncs here anyway — the bar
                    # itself never forces a per-batch device sync).
                    bar.close()
                    examples_seen = ((epoch - 1) * n_train
                                     + b * config.batch_size_train)
                    M.log(M.train_progress_line(epoch,
                                                b * config.batch_size_train,
                                                n_train, float(loss)))
                    history.record_train(examples_seen, float(loss))
                    saver.save_train_state(ckpt_path, state)
                bar.update(1)
        tail = train_loader.sampler.epoch_indices(epoch)[
            full_steps * config.batch_size_train:]
        if len(tail):
            state, tail_loss = tail_step_fn(state, jnp.asarray(train_ds.images[tail]),
                                            jnp.asarray(train_ds.labels[tail]),
                                            dropout_rng)
            step_losses.append(tail_loss)
        losses = np.asarray(jax.device_get(step_losses)) if step_losses else np.zeros(0)
        # Per-batch host dispatch: device execution overlaps the feed, so the
        # compile/execute split doesn't decompose here — but the loader now
        # meters the seconds the CONSUMER actually blocked on it, so report
        # loop-minus-stall as execute and the stall as data (the goodput
        # data_wait input; before this the split read data=0 even on a
        # data-starved run, DESIGN.md §26).
        wait_s = train_loader.pop_wait_s()
        loop_s = time.perf_counter() - t_epoch
        return state, None, {"execute": max(0.0, loop_s - wait_s),
                             "data": wait_s,
                             "loss_sum": float(losses.sum()),
                             "loss_steps": int(losses.size)}

    if config.use_host_pipeline:
        train_epoch = train_epoch_host_pipeline

    try:
        with profiling.maybe_profile(config.profile, config.profile_dir):
            with profiling.span("eval"):
                evaluate(state, 0)              # baseline eval, ≙ src/train.py:106
            best_step_s = None
            for epoch in range(1, config.n_epochs + 1):
                # heartbeat (with the previous boundary's param fingerprint)
                # + armed faults; no-op off
                rt.epoch_tick(state, epoch, fingerprint=grt.fingerprint)
                step_before = int(state.step)
                t_epoch = time.perf_counter()
                with profiling.step("train_epoch", epoch):
                    state, epoch_health, times = train_epoch(state, epoch)
                jax.block_until_ready(state.params)  # honest wall-clock (SURVEY.md §7c)
                wall_s = time.perf_counter() - t_epoch
                t_eval = time.perf_counter()
                with profiling.span("eval"):
                    evaluate(state, epoch * n_train)
                if epoch_health is not None:
                    # SPMD-entered by every process (the norm program would
                    # deadlock a fleet if only process 0 ran it); emission below
                    # stays process-0 gated.
                    health_host = jax.device_get(epoch_health)
                    param_norm = T.global_l2_norm(state.params)
                if tele.enabled:
                    eval_s = time.perf_counter() - t_eval
                    steps = int(state.step) - step_before
                    step_s = times["execute"] / steps if steps else None
                    if step_s and (best_step_s is None or step_s < best_step_s):
                        best_step_s = step_s
                    tele.emit(T.epoch_event(
                        epoch, examples=n_train, steps=steps, wall_s=wall_s,
                        execute_s=times["execute"], eval_s=eval_s,
                        data_s=times["data"], compile_s=compile_s,
                        flops_per_step=flops_per_step,
                        train_loss=times["loss_sum"] / times["loss_steps"]
                        if times["loss_steps"] else None,
                        val_loss=history.test_losses[-1],
                        mfu=T.estimate_mfu(flops_per_step, step_s)["mfu"]))
                    if epoch_health is not None:
                        tele.emit(T.health_event(epoch, health_host, steps,
                                                 param_norm=param_norm))
                # Guard boundary: anomaly verdict fetch + event + fingerprint,
                # then the manifest health stamp for the versioned save.
                stamp = grt.epoch_end(state, epoch,
                                      steps=int(state.step) - step_before)
                if config.keep_checkpoints:
                    # Versioned store (manifest + checksums + keep-last-N GC) for
                    # the supervisor's newest-HEALTHY resume scan.
                    checkpoint.save_versioned(
                        ckpt_store, state, keep=config.keep_checkpoints,
                        tele=tele, health=stamp,
                        # The manifest's data cursor: the (seed, epoch)-pure
                        # permutation's resume anchor (DESIGN.md §26).
                        cursor={"version": 1, "kind": "epoch",
                                "seed": config.seed, "epoch": epoch + 1,
                                "batch": 0, "step": int(state.step)})
                # Anomaly policy AFTER the stamped checkpoint is durable
                # (raises Poisoned; __main__ exits 65).
                grt.check_poisoned(state)
                # Cooperative preemption at the epoch boundary. The per-tick
                # overwrite checkpoint lags the tail batch, so save explicitly
                # before raising (raises Preempted; __main__ exits 75).
                rt.check_preempt(
                    epoch=epoch, state=state, checkpoint=ckpt_path, tele=tele,
                    save=lambda: saver.save_train_state(ckpt_path, state))
            if tele.enabled and best_step_s is not None:
                tele.emit(T.mfu_event(flops_per_step, best_step_s))

        plotting.save_loss_curves(
            history, os.path.join(config.images_dir, "train_test_curve.png"))
        M.save_metrics_jsonl(history, os.path.join(config.results_dir, "metrics.jsonl"))
        saver.save_train_state(ckpt_path, state)
    finally:
        # Drain the write-behind queue even when the loop raises or is signalled —
        # the queued checkpoint is exactly the killed-run artifact the per-tick
        # policy exists for, and flush() re-raises deferred background IO errors.
        # The preemption latch is uninstalled so in-process callers get their
        # signal semantics back.
        rt.uninstall()
        saver.flush()
    return state, history


if __name__ == "__main__":
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    try:
        main(parse_config(SingleProcessConfig))
    except resilience.Preempted as e:
        M.log(f"preempted at step {e.step} (checkpoint {e.checkpoint or 'n/a'}); "
              f"exiting {resilience.EXIT_PREEMPTED} — resume with --resume-from")
        raise SystemExit(resilience.EXIT_PREEMPTED)
    except resilience.Poisoned as e:
        M.log(f"poisoned at step {e.step} (anomaly window "
              f"{e.window[0]}:{e.window[1]}); exiting "
              f"{resilience.EXIT_POISONED} — the supervisor rolls back to the "
              f"newest healthy checkpoint and skips the window")
        raise SystemExit(resilience.EXIT_POISONED)
