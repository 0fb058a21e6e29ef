"""Distributed data-parallel trainer — the reference ``src/train_dist.py`` workflow, SPMD.

Reproduces the workflow of SURVEY.md §3.2: rendezvous, per-replica data sharding with
per-epoch reshuffle (``DistributedSampler(seed=42)`` + ``set_epoch``, reference
``src/train_dist.py:33-37,72``), ``epochs`` rounds of (train over the sharded global batch,
evaluate, print an epoch summary with train/val loss, accuracy, elapsed), then a
process-0-only final params save and the distributed loss-curve figure
(``src/train_dist.py:70-116,161-164``).

What is *not* here, by design (the TPU-native re-expression):

- no ``DDP(model)`` wrapper and no backend string — parallelism is the mesh + sharding
  annotations on ONE jit-compiled epoch program; XLA inserts the gradient all-reduce
  (``src/train_dist.py:63,146`` have no equivalent lines);
- no per-machine launcher files with a hand-assigned rank (``src/run1.py:31`` vs
  ``src/run2.py:31``) — every host runs this same module; coordinates come from
  ``jax.distributed`` metadata;
- no per-step ``loss.item()`` host sync or tqdm tick (``src/train_dist.py:85-87``) — losses
  come back per epoch as one array (the cadence of printed *epoch* summaries is identical);
- the per-worker batch is ``global_batch_size // world`` exactly as the reference computes it
  (``src/train_dist.py:133``: fixed global batch, weak per-worker scaling).

Sharding layout: per-replica example order comes from the same ``ShardedSampler`` contract,
laid out as a ``[steps, global_batch]`` index plan whose column-block ``r`` is replica ``r``'s
shard, so sharding the plan's second axis over the mesh reproduces DistributedSampler's
division of labor exactly. The final sub-global-batch remainder of each epoch is dropped
(static shapes; ≤ world-1 examples/epoch, re-covered by the next epoch's reshuffle).
"""

from __future__ import annotations

import os
import time

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from csed_514_project_distributed_training_using_pytorch_tpu.data import (
    download_mnist, load_mnist, mnist,
)
from csed_514_project_distributed_training_using_pytorch_tpu.data.loader import (
    iter_plan_batches,
)
from csed_514_project_distributed_training_using_pytorch_tpu.models import (
    build_model,
    validate_model_config,
)
from csed_514_project_distributed_training_using_pytorch_tpu.parallel import (
    data_parallel as dp,
)
from csed_514_project_distributed_training_using_pytorch_tpu.parallel.mesh import (
    initialize_cluster, make_mesh,
)
from csed_514_project_distributed_training_using_pytorch_tpu.parallel.sampler import (
    ShardedSampler,
)
from csed_514_project_distributed_training_using_pytorch_tpu import resilience
from csed_514_project_distributed_training_using_pytorch_tpu.train.guard import (
    GuardRuntime,
)
from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
    TrainState, create_train_state, make_epoch_fn, make_eval_fn,
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops import optim
from csed_514_project_distributed_training_using_pytorch_tpu.utils import checkpoint
from csed_514_project_distributed_training_using_pytorch_tpu.utils import metrics as M
from csed_514_project_distributed_training_using_pytorch_tpu.utils import plotting
from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import (
    DistributedConfig, parse_config,
)
from csed_514_project_distributed_training_using_pytorch_tpu.utils.determinism import (
    assert_replicas_synced,
)
from csed_514_project_distributed_training_using_pytorch_tpu.utils.profiling import (
    maybe_profile,
)
from csed_514_project_distributed_training_using_pytorch_tpu.utils import (
    telemetry as T,
)


def epoch_index_plan(samplers: list[ShardedSampler], epoch: int,
                     per_replica_batch: int) -> np.ndarray:
    """Build the ``[steps, world * per_replica_batch]`` index plan for one epoch.

    Column-block ``r`` holds replica ``r``'s examples in its sampler order, so a
    ``P(None, 'data')`` sharding gives each device exactly its DistributedSampler shard.
    """
    per = [s.epoch_indices(epoch) for s in samplers]
    steps = len(per[0]) // per_replica_batch
    blocks = [p[:steps * per_replica_batch].reshape(steps, per_replica_batch) for p in per]
    return np.concatenate(blocks, axis=1)


def _host_local_columns(mesh, per_replica_batch: int) -> tuple[int, int]:
    """This process's contiguous column block of the ``[steps, global_batch]`` plan: the
    rows owned by its addressable devices under the ``P('data')`` batch sharding. The
    device order of the mesh groups devices by process (jax.devices() ordering), which the
    host-local feed contract requires (``dp.global_batch_from_host_local``); asserted, not
    assumed."""
    mesh_devs = list(mesh.devices.flat)
    local_ids = {d.id for d in jax.local_devices()}
    positions = [i for i, d in enumerate(mesh_devs) if d.id in local_ids]
    if positions != list(range(positions[0], positions[0] + len(positions))):
        raise RuntimeError(
            f"addressable devices are not contiguous in the mesh ({positions}) — the "
            f"host-local feed path requires process-contiguous device order")
    return positions[0] * per_replica_batch, (positions[-1] + 1) * per_replica_batch


def main(config: DistributedConfig = DistributedConfig(), *,
         num_devices: int | None = None,
         datasets=None) -> tuple[TrainState, M.MetricsHistory]:
    """Run distributed training over all (or ``num_devices``) addressable devices; every host
    in a multi-host fleet runs this same function."""
    watch = M.Stopwatch()                         # ≙ t0, reference src/train_dist.py:119
    validate_model_config(config.model, remat=config.remat,
                          remat_policy=config.remat_policy, causal=config.causal,
                          attention_window=config.attention_window,
                          kv_heads=config.kv_heads, rope=config.rope)  # fail fast, pre-rendezvous
    if config.grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {config.grad_accum}")
    if config.health_stats and config.host_local_feed:
        raise ValueError("--health-stats rides the compiled scan carry "
                         "(train/step.py::HealthStats) — it is not available on the "
                         "per-batch --host-local-feed path")
    if config.health_stats and not config.telemetry:
        raise ValueError("--health-stats emits telemetry 'health' events and has no "
                         "other output — pass --telemetry PATH too")
    info = initialize_cluster()                   # ≙ init_process_group, :146
    mesh = make_mesh(num_devices)
    tele = T.TelemetryWriter(config.telemetry,
                             preserve=bool(config.resume_from))
    tele.emit(T.manifest_event(config, mesh=mesh, run_type="distributed"))
    # Resilience wiring (flag-gated, host-side only — the compiled epoch program is
    # untouched, and with both flags off no step fetch or syscall is added).
    rt = resilience.RunHooks(heartbeat_dir=config.heartbeat_dir,
                             handle_preemption=config.handle_preemption,
                             process_index=info.process_index)
    # Numerical immune system (--guard): in-step anomaly verdict + guarded
    # identity update; host side is epoch-boundary bookkeeping only.
    grt = GuardRuntime(config, tele=tele,
                       store_dir=os.path.join(config.results_dir, "checkpoints"))
    world = mesh.shape["data"]                    # ≙ world_size, :131 — but discovered
    if config.global_batch_size % world:
        raise ValueError(f"global batch {config.global_batch_size} not divisible by "
                         f"world size {world}")
    per_replica_batch = config.global_batch_size // world   # ≙ :133
    if config.grad_accum > 1 and per_replica_batch % config.grad_accum:
        raise ValueError(
            f"per-replica batch {per_replica_batch} not divisible by grad_accum "
            f"{config.grad_accum} — each microbatch must still shard evenly")

    root = jax.random.PRNGKey(config.seed)        # ≙ torch.manual_seed, :135-137
    init_rng, dropout_rng = jax.random.split(root)

    if config.download_data and datasets is None:
        download_mnist(config.data_dir)   # ≙ download=True, src/train_dist.py:22-30;
        #                                   atomic per-file install → fleet-safe
    train_ds, test_ds = datasets if datasets is not None else load_mnist(config.data_dir)
    train_ds = mnist.truncate(train_ds, config.max_train_examples)
    test_ds = mnist.truncate(test_ds, config.max_test_examples)
    n_train, n_test = len(train_ds), len(test_ds)
    M.log(f"Distributed training: {world} devices on {info.process_count} process(es), "
          f"global batch {config.global_batch_size} "
          f"(per-replica {per_replica_batch}), data source: {train_ds.source}")

    samplers = [ShardedSampler(n_train, num_replicas=world, rank=r,
                               seed=config.sampler_seed) for r in range(world)]

    model = build_model(config.model, bf16=config.bf16, remat=config.remat,
                        remat_policy=config.remat_policy,
                        causal=config.causal,
                        attention_window=config.attention_window,
                        kv_heads=config.kv_heads, rope=config.rope)
    optimizer = optim.make_optimizer(config.optimizer,
                                     learning_rate=config.learning_rate,
                                     momentum=config.momentum,
                                     weight_decay=config.weight_decay)
    state = create_train_state(model, init_rng, optimizer=optimizer,
                               ema=config.ema_decay > 0, guard=config.guard)
    steps_per_epoch = samplers[0].num_samples // per_replica_batch
    lr_schedule = optim.make_lr_schedule(config.lr_schedule,
                                         warmup_steps=config.warmup_steps,
                                         total_steps=config.epochs * steps_per_epoch)
    start_epoch = 0
    if config.resume_from:                        # the resume path the reference lacks
        state, start_epoch, warning = checkpoint.restore_for_resume(
            config.resume_from, state,
            process_index=info.process_index, process_count=info.process_count,
            steps_per_epoch=steps_per_epoch, tele=tele)
        if warning:
            M.log(f"WARNING: {warning}")
        M.log(f"Resumed from {config.resume_from} at step {int(state.step)} "
              f"(starting epoch {start_epoch})")
        # Manifest cursor cross-check (DESIGN.md §26): the checkpoint's stamped
        # data position must agree with the derived start epoch.
        note = checkpoint.check_cursor_resume(config.resume_from,
                                              seed=config.seed,
                                              step=int(state.step),
                                              start_epoch=start_epoch)
        if note:
            M.log(f"WARNING: {note}")
    grt.baseline(state)     # this attempt's anomaly-counter zero point
    if config.fsdp:
        # ZeRO/FSDP mode (r5): params + SGD/AdamW state shard over the data axis;
        # XLA inserts the per-use all-gathers and gradient reduce-scatters from
        # the annotations (parallel/fsdp.py). Same trajectory as plain DP.
        from csed_514_project_distributed_training_using_pytorch_tpu.parallel import (
            fsdp,
        )
        state = fsdp.shard_train_state(mesh, state)
    else:
        state = jax.device_put(state, dp.replicated(mesh))
    # Host fetches replicate ON DEVICE first — device_get on an FSDP-sharded array
    # would fail on a multi-host fleet where no process addresses every shard.
    gather = dp.gather_replicated(mesh)
    ckpt_path = os.path.join(config.results_dir, "model_dist.ckpt")

    if not config.host_local_feed:
        train_x = dp.put_global(mesh, train_ds.images, P())
        train_y = dp.put_global(mesh, train_ds.labels, P())
    eval_spec = P("data") if config.shard_eval else P()
    test_x = dp.put_global(mesh, test_ds.images, eval_spec)
    test_y = dp.put_global(mesh, test_ds.labels, eval_spec)

    health = config.health_stats
    epoch_body = make_epoch_fn(model, learning_rate=config.learning_rate,
                               momentum=config.momentum,
                               unroll=config.scan_unroll,
                               pregather=config.pregather,
                               grad_accum=config.grad_accum, optimizer=optimizer,
                               lr_schedule=lr_schedule,
                               clip_grad_norm=config.clip_grad_norm,
                               ema_decay=config.ema_decay,
                               label_smoothing=config.label_smoothing,
                               health=health, guard=grt.spec)
    if config.fsdp:
        epoch_fn = fsdp.compile_epoch_fsdp(epoch_body, mesh)
    else:
        epoch_fn = dp.compile_epoch(epoch_body, mesh)
    # Compile/execute split (telemetry): AOT-compile the whole-epoch program and
    # price its FLOPs; the compiled program replaces the jit path so nothing
    # compiles twice. The FSDP wrapper resolves shardings from the first call's
    # state and has no .lower — aot_compile then returns None and compile time
    # folds into the first epoch's wall clock (compile_s stays null).
    # Gated on the CONFIG flag, not tele.enabled: every process must take the same
    # compile path (AOT-compiled vs jit) on a multi-host fleet; only emission is
    # process-0 gated.
    compile_s = flops_per_step = None
    if config.telemetry and not config.host_local_feed:
        plan_struct = jax.ShapeDtypeStruct(
            (steps_per_epoch, config.global_batch_size), np.int32)
        compiled, aot = T.aot_compile(epoch_fn, state, train_x, train_y,
                                      plan_struct, dropout_rng)
        if compiled is not None:
            epoch_fn = compiled
            compile_s = aot["lower_s"] + aot["compile_s"]
            if aot["flops"]:
                flops_per_step = aot["flops"] / steps_per_epoch
            tele.emit(T.compile_event("epoch", aot,
                                      steps_per_call=steps_per_epoch))
    eval_fn = dp.compile_eval(
        make_eval_fn(model, batch_size=config.batch_size_test), mesh,
        shard=config.shard_eval)

    if config.host_local_feed:
        from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
            make_train_step,
        )
        step_body = make_train_step(model, learning_rate=config.learning_rate,
                                    momentum=config.momentum,
                                    grad_accum=config.grad_accum,
                                    optimizer=optimizer, lr_schedule=lr_schedule,
                                    clip_grad_norm=config.clip_grad_norm,
                                    ema_decay=config.ema_decay,
                                    label_smoothing=config.label_smoothing,
                                    guard=grt.spec)
        step_fn = (fsdp.compile_step_fsdp(step_body, mesh) if config.fsdp
                   else dp.compile_step(step_body, mesh))
        col_lo, col_hi = _host_local_columns(mesh, per_replica_batch)
        M.log(f"Host-local feed: this process feeds global-batch columns "
              f"[{col_lo}:{col_hi}]")

    def run_epoch_device_resident(state, plan):
        """Fast path: whole epoch as one compiled scan over the device-resident split."""
        plan_d = dp.put_global(mesh, plan, P(None, "data"))
        return epoch_fn(state, train_x, train_y, plan_d, dropout_rng)

    def run_epoch_host_local(state, plan):
        """Multi-host input pipeline (SURVEY.md §7 hard part (d)): per step, this process
        gathers ONLY its addressable devices' rows of the global batch on host and
        assembles the globally-sharded arrays from per-process shards — the dataset never
        needs to be resident on (or even known to) other hosts. Identical plan and step
        math to the fast path; only the feeding mechanism differs. Host batches come
        through the native threaded prefetcher when built (the reference's distributed
        loader is exactly where its ``num_workers=4`` pool lives,
        ``src/train_dist.py:43-45``): workers gather step s+1's shard while step s runs
        on device."""
        losses = []
        # Live per-batch bar (≙ the reference's tqdm, src/train_dist.py:76) — only
        # on this host-fed path, where a per-step dispatch already exists; the bar
        # never forces a device sync (no per-step loss fetch), and it renders only
        # on a process-0 tty.
        with M.ProgressBar(plan.shape[0], desc="train ") as bar:
            for bx, by in iter_plan_batches(train_ds, plan[:, col_lo:col_hi]):
                gi, gl = dp.global_batch_from_host_local(mesh, bx, by)
                state, loss = step_fn(state, gi, gl, dropout_rng)
                losses.append(loss)
                bar.update(1)
        return state, jax.numpy.stack(losses)

    history = M.MetricsHistory()
    saver = checkpoint.make_saver(config.async_checkpoint, tele=tele)
    ckpt_store = os.path.join(config.results_dir, "checkpoints")

    try:
        with maybe_profile(config.profile, config.profile_dir):
            best_step_s = None
            for epoch in range(start_epoch, config.epochs):   # ≙ the epoch loop, :70
                # heartbeat (with the previous boundary's param fingerprint)
                # + armed faults; no-op off
                rt.epoch_tick(state, epoch, fingerprint=grt.fingerprint)
                t_epoch = time.perf_counter()
                plan = epoch_index_plan(samplers, epoch, per_replica_batch)  # ≙ set_epoch, :72
                data_s = time.perf_counter() - t_epoch
                t_exec = time.perf_counter()
                if config.host_local_feed:
                    state, losses = run_epoch_host_local(state, plan)
                else:
                    state, out = run_epoch_device_resident(state, plan)
                    losses, epoch_health = out if health else (out, None)

                losses = np.asarray(jax.device_get(losses))  # the honest sync point
                execute_s = time.perf_counter() - t_exec
                train_loss = float(losses.mean())     # per-epoch mean of per-step global means
                examples = (epoch + 1) * plan.size
                for i, l in enumerate(losses[::config.log_interval]):
                    history.record_train(epoch * plan.size +
                                         i * config.log_interval * plan.shape[1],
                                         float(l))

                t_eval = time.perf_counter()
                eval_params = state.ema if state.ema is not None else state.params
                if config.fsdp:
                    # compile_eval pins replicated param shardings; jit rejects a
                    # mismatched committed layout, so gather the shards on device.
                    eval_params = gather(eval_params)
                sum_nll, correct = jax.device_get(
                    eval_fn(eval_params, test_x, test_y))   # ≙ eval loop, :92-109
                eval_s = time.perf_counter() - t_eval
                val_loss = float(sum_nll) / n_test
                accuracy = float(correct) / n_test
                history.record_test(examples, val_loss)
                M.log(M.dist_epoch_summary_line(epoch, train_loss, val_loss, accuracy,
                                                watch.elapsed()))  # ≙ :113-114
                if health:
                    # SPMD-entered by every process (the norm program would
                    # deadlock a fleet if only process 0 ran it); emission below
                    # stays process-0 gated.
                    health_host = jax.device_get(epoch_health)
                    param_norm = T.global_l2_norm(state.params)
                if tele.enabled:
                    steps = int(losses.shape[0])
                    step_s = execute_s / steps if steps else None
                    if step_s and (best_step_s is None or step_s < best_step_s):
                        best_step_s = step_s
                    tele.emit(T.epoch_event(
                        epoch, examples=plan.size, steps=steps,
                        wall_s=time.perf_counter() - t_epoch,
                        execute_s=execute_s, eval_s=eval_s, data_s=data_s,
                        compile_s=compile_s, flops_per_step=flops_per_step,
                        train_loss=train_loss, val_loss=val_loss,
                        mfu=T.estimate_mfu(flops_per_step, step_s)["mfu"]))
                    if health:
                        tele.emit(T.health_event(epoch, health_host, steps,
                                                 param_norm=param_norm))
                # Guard boundary: fetch the anomaly verdict, emit the anomaly
                # event, compute the cross-replica fingerprint (host-local by
                # design — a global reduction would hand every process the
                # same scalar), and build the manifest health stamp.
                stamp = grt.epoch_end(state, epoch, steps=int(losses.shape[0]))
                # Per-epoch full-state checkpoint (process-0 gated, atomic) so a killed run
                # can resume with --resume-from; the reference only ever saves final params.
                # Device-resident gathered state: the saver is process-0 gated and
                # device_gets internally — non-0 processes must not pay a host fetch.
                ck_state = gather(state)
                saver.save_train_state(ckpt_path, ck_state)
                if config.keep_checkpoints:
                    # Versioned store (manifest + checksums + keep-last-N GC): what
                    # the fleet supervisor's newest-HEALTHY resume scan reads.
                    checkpoint.save_versioned(
                        ckpt_store, ck_state, keep=config.keep_checkpoints,
                        tele=tele, health=stamp,
                        # The manifest's data cursor: the (seed, epoch)-pure
                        # permutation's resume anchor (DESIGN.md §26).
                        cursor={"version": 1, "kind": "epoch",
                                "seed": config.seed, "epoch": epoch + 1,
                                "batch": 0, "step": int(ck_state.step)})
                # Anomaly policy AFTER the (stamped) checkpoint is durable: the
                # supervisor rolls back to the newest CLEAN stamp and restarts
                # with --skip-steps (raises Poisoned; __main__ exits 65).
                grt.check_poisoned(state)
                # Cooperative preemption: honor a pending SIGTERM now, with this
                # epoch's checkpoint durable (raises Preempted; __main__ exits 75).
                rt.check_preempt(epoch=epoch, state=state, checkpoint=ckpt_path,
                                 tele=tele)
            if tele.enabled and best_step_s is not None:
                tele.emit(T.mfu_event(flops_per_step, best_step_s))

        if not config.fsdp:
            # The desync "race detector" (SURVEY.md §5). Under FSDP the replica-sync
            # invariant it guards does not apply: sharded leaves hold DIFFERENT
            # slices by design, and gathered copies are replicated-by-construction
            # (the check would be vacuous, not reassuring).
            assert_replicas_synced(state.params)

        plotting.save_loss_curves(
            history, os.path.join(config.images_dir, "train_test_curve_dist.png"))  # ≙ :161
        M.save_metrics_jsonl(history, os.path.join(config.results_dir, "metrics.jsonl"))
        # The export must be the weights the reported metrics came from: the EMA tree
        # when --ema-decay is set (eval consumes it above), the raw params otherwise.
        export_state = gather(state)    # on device; save_params is process-0 gated
        checkpoint.save_params(
            os.path.join(config.results_dir, "model_dist.msgpack"),
            export_state.ema if export_state.ema is not None
            else export_state.params)   # ≙ :163-164
    finally:
        # Drain the write-behind queue even on an exception/signal/preemption
        # mid-run — the queued per-epoch checkpoint is the resume artifact a killed
        # run needs, and flush() re-raises deferred background IO errors. The
        # preemption latch is uninstalled so in-process callers get their signal
        # semantics back.
        rt.uninstall()
        saver.flush()
    return state, history


if __name__ == "__main__":
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    try:
        main(parse_config(DistributedConfig))
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
