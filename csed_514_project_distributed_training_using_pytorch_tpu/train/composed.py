"""Composed-parallelism trainer: one CLI over an arbitrary named device mesh.

Beyond-parity user surface (the reference's only distributed mode is DP —
``src/train_dist.py``; the DP-parity trainer is ``train/distributed.py``): train the
transformer family with any combination of

- ``data``  — batch sharding + compiler-inserted gradient all-reduce (DP),
- ``seq``   — sequence/context parallelism over a sequence-sharded axis: ring attention
  (``parallel/ring_attention.py``, the default) or the head-scatter all-to-all schedule
  (``--seq-impl ulysses``, ``parallel/ulysses.py``),
- ``model`` — Megatron column/row weight sharding (TP, ``parallel/tensor_parallel.py``),
- ``expert`` — Switch MoE blocks with expert-sharded weights (EP,
  ``parallel/expert_parallel.py``; the axis size sets the expert count, and the
  load-balance aux loss flows into the objective via ``make_train_step``),

declared as one ``--mesh`` string, e.g. ``--mesh data=2,seq=2,model=2`` on 8 devices.
Axes of size 1 are legal (``--mesh data=8`` is plain DP). Everything else is the
standard machinery: same TrainState, same checkpoint format (interchangeable with the
unsharded trainers — pinned in tests), same metric lines.

- ``stage`` — GPipe pipeline parallelism over the transformer's block stack (PP,
  ``parallel/pipeline.py``): the run trains in the stage-stacked parameter layout
  (each device holds only its stages' layers) and the checkpoint bridge
  (``stack_transformer_blocks``/``unstack_transformer_blocks``) converts to/from the
  standard per-name layout at the boundary, so PP checkpoints interchange with every
  other mesh. Composes with ``data`` (``--mesh data=2,stage=2``) and with ``model``
  (``--mesh data=2,stage=2,model=2`` — the pipeline keeps stage/data manual and the
  model axis AUTO, so Megatron TP annotations still apply inside each stage) and
  with ``--flash-attention`` (the dispatcher's pallas kernel traces inside the
  pipeline body); ``seq``/``expert`` with ``stage`` would need nested shard_maps
  and are rejected up front.

This is deliberately a thin composition of the parallel/ primitives: the entire
"strategy" is the mesh declaration plus sharding rules; XLA inserts every collective.
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from csed_514_project_distributed_training_using_pytorch_tpu.data import (
    download_mnist, load_mnist, mnist,
)
from csed_514_project_distributed_training_using_pytorch_tpu.models import (
    validate_remat_policy,
    TransformerClassifier,
)
from csed_514_project_distributed_training_using_pytorch_tpu.parallel.mesh import (
    parse_mesh_spec,
)
from csed_514_project_distributed_training_using_pytorch_tpu.parallel import (
    initialize_cluster,
    make_mesh,
    make_ring_attention_fn,
    make_ulysses_attention_fn,
)
from csed_514_project_distributed_training_using_pytorch_tpu.parallel import (
    data_parallel as dp,
)
from csed_514_project_distributed_training_using_pytorch_tpu.parallel import (
    pipeline,
)
from csed_514_project_distributed_training_using_pytorch_tpu import resilience
from csed_514_project_distributed_training_using_pytorch_tpu.ops import optim
from csed_514_project_distributed_training_using_pytorch_tpu.train.guard import (
    GuardRuntime,
)
from csed_514_project_distributed_training_using_pytorch_tpu.parallel import (
    tensor_parallel as tp,
)
from jax.sharding import PartitionSpec as P
from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
    TrainState,
    create_train_state,
    make_epoch_fn,
    make_eval_fn,
)
from csed_514_project_distributed_training_using_pytorch_tpu.utils import checkpoint
from csed_514_project_distributed_training_using_pytorch_tpu.utils import metrics as M
from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import (
    ComposedConfig, parse_config,
)
from csed_514_project_distributed_training_using_pytorch_tpu.utils.profiling import (
    maybe_profile,
)
from csed_514_project_distributed_training_using_pytorch_tpu.utils import (
    telemetry as T,
)

def main(config: ComposedConfig = ComposedConfig(), *,
         datasets=None) -> tuple[TrainState, M.MetricsHistory]:
    """Run composed-mesh training; returns final (host-resident) state + history."""
    watch = M.Stopwatch()
    run_plan, plan_events = None, []
    if config.plan:
        # Resolve BEFORE the mesh spec is read: the plan rewrites mesh/fsdp/
        # grad_accum/pipeline_microbatches on the (frozen) config. Deterministic
        # across processes for auto/file; tune degrades to auto on a fleet.
        # Autotune trial events buffer until the telemetry writer exists below.
        from csed_514_project_distributed_training_using_pytorch_tpu import (
            plan as plan_mod,
        )
        initialize_cluster()     # idempotent; planning needs the global topology
        config, run_plan = plan_mod.apply_plan(config, "composed",
                                               emit=plan_events.append)
    axis_names, axis_sizes = parse_mesh_spec(config.mesh)
    if config.kv_heads and (
            config.kv_heads < 0
            or TransformerClassifier.num_heads % config.kv_heads):
        raise ValueError(f"--kv-heads {config.kv_heads} must be a positive divisor "
                         f"of the transformer's {TransformerClassifier.num_heads} "
                         f"heads")
    # r4: sliding windows compose with EVERY attention schedule — einsum ring,
    # ring-of-flash (static hop offsets, truncated ring), einsum zig-zag
    # (global-position chunk masks), flash zig-zag (traced SMEM-scalar offsets),
    # and ulysses (full sequence local). Only the width itself needs validating.
    if config.attention_window:
        from csed_514_project_distributed_training_using_pytorch_tpu.ops.attention import (
            validate_window,
        )
        validate_window(config.attention_window)
    n_mesh_devices = int(np.prod(axis_sizes))
    info = initialize_cluster()   # no-op single-process; multi-host rendezvous otherwise

    if config.download_data and datasets is None:
        download_mnist(config.data_dir)
    train_ds, test_ds = datasets if datasets is not None else load_mnist(config.data_dir)
    train_ds = mnist.truncate(train_ds, config.max_train_examples)
    test_ds = mnist.truncate(test_ds, config.max_test_examples)

    if config.dcn_data:
        # Multi-slice layout: the data axis's leading factor (one per slice/granule)
        # is the ONLY mesh dimension whose collectives cross DCN; everything else
        # rides ICI. Virtual granules let this compile/run on single-slice or CPU
        # platforms (the dryrun exercises it at 8 virtual devices).
        from csed_514_project_distributed_training_using_pytorch_tpu.parallel import (
            make_hybrid_mesh,
        )
        if "data" not in axis_names:
            raise ValueError("--dcn-data needs a data axis in --mesh (it is the "
                             "axis whose leading factor spans slices)")
        mesh = make_hybrid_mesh(axis_names, axis_sizes, dcn_axis="data",
                                num_slices=config.dcn_data,
                                devices=jax.devices()[:n_mesh_devices])
    else:
        mesh = make_mesh(n_mesh_devices, axis_names=axis_names,
                         axis_shape=axis_sizes)
    if config.health_stats and not config.telemetry:
        raise ValueError("--health-stats emits telemetry 'health' events and has no "
                         "other output — pass --telemetry PATH too")
    tele = T.TelemetryWriter(config.telemetry,
                             preserve=bool(config.resume_from))
    tele.emit(T.manifest_event(config, mesh=mesh, run_type="composed"))
    if run_plan is not None:
        tele.emit(T.plan_event(run_plan))
        for ev in plan_events:
            tele.emit(ev)
    # Resilience wiring (flag-gated, host-side only — zero-cost when off).
    rt = resilience.RunHooks(heartbeat_dir=config.heartbeat_dir,
                             handle_preemption=config.handle_preemption,
                             process_index=info.process_index)
    # Numerical immune system (--guard): in-step verdict + identity update;
    # host side is epoch-boundary bookkeeping only.
    grt = GuardRuntime(config, tele=tele,
                       store_dir=os.path.join(config.results_dir, "checkpoints")
                       if config.results_dir else "")
    data_size = mesh.shape.get("data", 1)
    seq_size = mesh.shape.get("seq", 1)
    model_size = mesh.shape.get("model", 1)
    expert_size = mesh.shape.get("expert", 1)
    stage_size = mesh.shape.get("stage", 1)
    if config.batch_size % max(data_size, 1):
        raise ValueError(f"batch {config.batch_size} not divisible by data axis "
                         f"{data_size}")
    if config.grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {config.grad_accum}")
    validate_remat_policy(config.remat, config.remat_policy)
    if config.batch_size % config.grad_accum:
        raise ValueError(f"batch {config.batch_size} not divisible by grad_accum "
                         f"{config.grad_accum}")
    if (config.grad_accum > 1
            and (config.batch_size // config.grad_accum) % max(data_size, 1)):
        # Same fail-fast as train/distributed.py: an indivisible microbatch would make
        # GSPMD silently reshard inside the hot program, defeating DP scaling.
        raise ValueError(
            f"microbatch {config.batch_size // config.grad_accum} "
            f"(batch/grad_accum) not divisible by data axis {data_size} — each "
            f"microbatch must still shard evenly")
    if stage_size > 1:
        # r5: ``model`` composes with ``stage`` — the pipeline's shard_map keeps
        # only stage/data manual and leaves the model axis AUTO, so the Megatron
        # annotations still drive compiler-inserted TP collectives inside each
        # stage (parallel/pipeline.py). seq/expert stay rejected: their schedules
        # are shard_maps of their own and genuinely would need nesting.
        if seq_size > 1 or expert_size > 1:
            raise ValueError(
                "a stage axis composes with data and model only — seq/expert "
                "inside a pipeline stage would need nested shard_maps")
        if config.dropout_rate:
            raise ValueError("stage pipelining requires dropout_rate == 0 "
                             "(microbatch ticks do not thread dropout keys)")
        if config.remat:
            raise ValueError("--remat has no effect under a stage axis (the pipeline "
                             "engine applies blocks itself) — drop it")
        if config.zigzag_attention:
            raise ValueError(
                "--zigzag-attention needs a seq axis, which does not compose with "
                "a stage axis")
        if config.fsdp:
            raise ValueError(
                "--fsdp does not compose with a stage axis: the pipeline's "
                "shard_map keeps the data axis MANUAL, which conflicts with "
                "ZeRO's data-axis weight sharding")
        if config.flash_attention and model_size > 1:
            raise ValueError(
                "--flash-attention under stage x model is unsupported: the flash "
                "pallas_call cannot be partitioned by the AUTO model axis inside "
                "the pipeline body (drop model or flash)")
        if config.sharded_checkpoint:
            raise ValueError(
                "--sharded-checkpoint saves the device state's own layout, and the "
                "stage axis trains in the stacked layout — its shard keys would not "
                "interchange; use the default full-state checkpoint with stages")
        # The engine sees batch_size // grad_accum per call (the accumulation path
        # feeds microbatches), so the pipeline divisibility guards must use that.
        step_batch = config.batch_size // config.grad_accum
        if step_batch % config.pipeline_microbatches:
            raise ValueError(
                f"per-call batch {step_batch} (batch/grad_accum) not divisible by "
                f"{config.pipeline_microbatches} pipeline microbatches")
        if (step_batch // config.pipeline_microbatches) % data_size:
            raise ValueError(
                f"pipeline microbatch {step_batch // config.pipeline_microbatches} "
                f"not divisible by data axis {data_size}")
        if config.batch_size_test % config.pipeline_microbatches:
            raise ValueError(
                f"test batch {config.batch_size_test} not divisible by "
                f"{config.pipeline_microbatches} pipeline microbatches")

    attention_fn = None
    if config.seq_impl not in ("ring", "ulysses"):
        raise ValueError(
            f"--seq-impl must be 'ring' or 'ulysses', got {config.seq_impl!r}")
    if config.seq_impl == "ulysses" and config.zigzag_attention:
        raise ValueError("--zigzag-attention is a ring schedule — it does not "
                         "compose with --seq-impl ulysses")
    if config.seq_impl == "ulysses" and seq_size > 1:
        # Head-scatter all-to-all SP (parallel/ulysses.py); the wrapper enforces
        # seq_len/head divisibility with actionable messages. --flash-attention
        # selects the flash kernel as the full-sequence local op. Without a seq axis
        # the impl choice is moot and the flash/dense chain below applies unchanged.
        attention_fn = make_ulysses_attention_fn(
            mesh, use_flash=config.flash_attention,
            window=config.attention_window)
    elif config.zigzag_attention:
        if not config.causal:
            raise ValueError("--zigzag-attention is causal-only — add --causal")
        if "seq" not in mesh.shape:
            raise ValueError("--zigzag-attention needs a seq axis in --mesh")
        if config.flash_attention:
            # Both flags: the full long-context causal composition — zig-zag load
            # balance across chips, flash kernels within each live chunk pair.
            from csed_514_project_distributed_training_using_pytorch_tpu.ops import (
                pallas_attention as pa,
            )
            chunk = 2 * max(seq_size, 1) * pa.BLOCK
            if config.seq_len % chunk:
                raise ValueError(
                    f"--zigzag-attention --flash-attention needs seq_len divisible "
                    f"by 2·seq_axis·BLOCK = {chunk}, got {config.seq_len} "
                    f"(e.g. --seq-len {chunk})")
            attention_fn = make_ring_attention_fn(
                mesh, use_flash=True, use_zigzag=True,
                window=config.attention_window)
        else:
            if config.seq_len % (2 * max(seq_size, 1)):
                raise ValueError(
                    f"--zigzag-attention needs seq_len divisible by 2·seq_axis = "
                    f"{2 * max(seq_size, 1)}, got {config.seq_len}")
            attention_fn = make_ring_attention_fn(
                mesh, use_zigzag=True, window=config.attention_window)
    elif config.flash_attention:
        from csed_514_project_distributed_training_using_pytorch_tpu.ops import (
            pallas_attention as pa,
        )
        if config.seq_len % (max(seq_size, 1) * pa.BLOCK):
            raise ValueError(
                f"--flash-attention needs seq_len divisible by "
                f"seq_axis·BLOCK = {max(seq_size, 1)}·{pa.BLOCK}, got "
                f"{config.seq_len} (e.g. --seq-len {max(seq_size, 1) * pa.BLOCK})")
        # Ring-of-flash under a seq axis (flash kernels on every hop, trainable custom
        # VJP); the measured-crossover dispatcher otherwise (dense while the float32
        # scores stay on-chip, flash once they would go through HBM:
        # ops.dispatch_plan — the flag can never regress throughput; windowed/banded
        # when requested).
        if seq_size > 1:
            attention_fn = make_ring_attention_fn(
                mesh, use_flash=True, window=config.attention_window)
        elif config.attention_window:
            import functools
            attention_fn = functools.partial(
                pa.dispatch_attention, window=config.attention_window)
        else:
            attention_fn = pa.dispatch_attention
    elif seq_size > 1:
        # Plain einsum ring; --attention-window binds the sliding band into the
        # hop schedule (windowed context parallelism — out-of-band hops skip).
        attention_fn = make_ring_attention_fn(mesh,
                                              window=config.attention_window)
    elif config.attention_window:
        from csed_514_project_distributed_training_using_pytorch_tpu.ops.attention import (
            windowed_attention_fn,
        )
        attention_fn = windowed_attention_fn(config.attention_window)
    model_kwargs = {"dropout_rate": config.dropout_rate,
                    "seq_len": config.seq_len,
                    "dtype": jnp.bfloat16 if config.bf16 else jnp.float32,
                    "remat": config.remat,
                    "remat_policy": config.remat_policy,
                    "causal": config.causal}
    if config.kv_heads:
        model_kwargs["num_kv_heads"] = config.kv_heads
    if config.rope:
        model_kwargs["rope"] = True
    if attention_fn is not None:
        model_kwargs["attention_fn"] = attention_fn
    if not 1 <= config.moe_top_k <= max(expert_size, 1):
        raise ValueError(f"--moe-top-k must be in [1, expert axis size], got "
                         f"{config.moe_top_k} with expert={expert_size}")
    if expert_size > 1:
        model_kwargs["num_experts"] = expert_size
        model_kwargs["expert_mesh"] = mesh
        model_kwargs["expert_top_k"] = config.moe_top_k
    model = TransformerClassifier(**model_kwargs)
    if seq_size > 1 and model.seq_len % seq_size:
        raise ValueError(f"model seq_len {model.seq_len} not divisible by seq axis "
                         f"{seq_size}")

    M.log(f"Composed training: mesh "
          f"{dict(zip(axis_names, axis_sizes))} over {n_mesh_devices} devices "
          f"on {info.process_count} process(es), "
          f"batch {config.batch_size}, data source: {train_ds.source}")

    rep = dp.replicated(mesh)
    n_train, n_test = len(train_ds), len(test_ds)
    steps_per_epoch = n_train // config.batch_size
    if steps_per_epoch == 0:
        raise ValueError(f"batch {config.batch_size} larger than the train split "
                         f"({n_train} examples) — nothing to step")
    optimizer = optim.make_optimizer(config.optimizer,
                                     learning_rate=config.learning_rate,
                                     momentum=config.momentum,
                                     weight_decay=config.weight_decay)
    base_state = create_train_state(model, jax.random.PRNGKey(config.seed),
                                    optimizer=optimizer,
                                    ema=config.ema_decay > 0,
                                    guard=config.guard)
    lr_schedule = optim.make_lr_schedule(config.lr_schedule,
                                         warmup_steps=config.warmup_steps,
                                         total_steps=config.epochs * steps_per_epoch)
    start_epoch = 0
    if config.resume_from:
        # Checkpoints are always in the standard per-name layout, so a composed run
        # resumes from ANY mesh's checkpoint — including across stage layouts (the
        # bridge below re-stacks).
        base_state, start_epoch, warning = checkpoint.restore_for_resume(
            config.resume_from, base_state,
            process_index=info.process_index, process_count=info.process_count,
            steps_per_epoch=steps_per_epoch, tele=tele)
        if warning:
            M.log(f"WARNING: {warning}")
        M.log(f"Resumed from {config.resume_from} at step {int(base_state.step)} "
              f"(starting epoch {start_epoch})")
        # Manifest cursor cross-check (DESIGN.md §26): the checkpoint's stamped
        # data position must agree with the derived start epoch.
        note = checkpoint.check_cursor_resume(config.resume_from,
                                              seed=config.seed,
                                              step=int(base_state.step),
                                              start_epoch=start_epoch)
        if note:
            M.log(f"WARNING: {note}")
    grt.baseline(base_state)    # this attempt's anomaly-counter zero point
    # Whole epochs run as ONE compiled scan under the composed shardings (same program
    # structure as train/distributed.py): per-step Python dispatch — an index-plan
    # upload, an on-device gather, a reshard, a step call — dominates at this model
    # size (SURVEY.md §7e), and previously made this trainer an order of magnitude
    # slower than the DP trainer it shares a flag surface with (r2 verdict, weak #3).
    if stage_size > 1:
        # PP path: train in the stage-stacked layout (each device holds only its
        # stages' layers); same init values via the checkpoint bridge, restored to the
        # standard per-name layout at the end.
        engine = pipeline.PipelinedClassifier(
            model, mesh, num_microbatches=config.pipeline_microbatches,
            batch_axis="data" if data_size > 1 else None,
            schedule=config.pipeline_schedule)
        def to_stacked(tree):
            stacked, rest = pipeline.stack_transformer_blocks(tree, model.num_layers)
            return {"blocks": stacked, "rest": rest}

        # The optimizer state bridges per params-congruent subtree (AdamW stacks each
        # moment tree like the params; SGD velocity IS one such tree).
        stacked_state = TrainState(to_stacked(base_state.params),
                                   optim.map_param_trees(base_state.velocity,
                                                         to_stacked),
                                   base_state.step,
                                   to_stacked(base_state.ema)
                                   if base_state.ema is not None else None,
                                   base_state.guard)   # scalars pass through
        state_sh = pipeline.stacked_state_shardings(mesh, stacked_state)
        state = jax.device_put(stacked_state, state_sh)
        idx_sh = (jax.sharding.NamedSharding(mesh, P(None, "data"))
                  if data_size > 1 else rep)
        epoch_fn = jax.jit(
            make_epoch_fn(engine, learning_rate=config.learning_rate,
                          momentum=config.momentum,
                          grad_accum=config.grad_accum, optimizer=optimizer,
                          lr_schedule=lr_schedule,
                          clip_grad_norm=config.clip_grad_norm,
                          ema_decay=config.ema_decay,
                          label_smoothing=config.label_smoothing,
                          health=config.health_stats, guard=grt.spec),
            in_shardings=(state_sh, rep, rep, idx_sh, rep),
            out_shardings=(state_sh, rep), donate_argnums=(0,))
        param_shardings = state_sh.params
        # Eval batches stay replicated (the reference's every-rank-evaluates
        # semantics), so the eval engine pipelines without data-sharded microbatches.
        eval_model = pipeline.PipelinedClassifier(
            model, mesh, num_microbatches=config.pipeline_microbatches,
            batch_axis=None, schedule=config.pipeline_schedule)
    else:
        epoch_body = make_epoch_fn(model, learning_rate=config.learning_rate,
                                   momentum=config.momentum,
                                   grad_accum=config.grad_accum,
                                   optimizer=optimizer,
                                   lr_schedule=lr_schedule,
                                   clip_grad_norm=config.clip_grad_norm,
                                   ema_decay=config.ema_decay,
                                   label_smoothing=config.label_smoothing,
                                   health=config.health_stats, guard=grt.spec)
        if config.fsdp:
            # ZeRO x TP hybrid (r5): params + optimizer state shard over BOTH the
            # data axis (largest free dim) and the Megatron model axis — memory
            # divides by data_size x model_size (parallel/fsdp.py).
            from csed_514_project_distributed_training_using_pytorch_tpu.parallel import (
                fsdp,
            )
            state_sh = fsdp.hybrid_state_shardings(mesh, base_state)
            state = jax.device_put(base_state, state_sh)
            epoch_fn = fsdp.compile_epoch_hybrid(
                epoch_body, mesh, data_axis="data" if data_size > 1 else None)
            param_shardings = state_sh.params
        else:
            state = tp.shard_train_state(mesh, base_state)
            epoch_fn = tp.compile_epoch_tp(
                epoch_body, mesh, data_axis="data" if data_size > 1 else None)
            param_shardings = tp.state_shardings(mesh, state).params
        eval_model = model
    # Eval consumes the sharded params in place (no host gather — multi-host safe);
    # sums/counts come back replicated, which every process can read.
    eval_fn = jax.jit(make_eval_fn(eval_model, batch_size=config.batch_size_test),
                      in_shardings=(param_shardings, rep, rep),
                      out_shardings=(rep, rep))

    # Every process holds the identical dataset (pure function of the seed / the same
    # files) and derives the identical permutation — the same contract parallel/sampler
    # documents. The split uploads ONCE, replicated; per-step batches are on-device
    # gathers (only the 64-int index plan crosses the host boundary each step).
    train_x = dp.put_global(mesh, train_ds.images, P())
    train_y = dp.put_global(mesh, train_ds.labels, P())
    test_x = dp.put_global(mesh, test_ds.images, P())
    test_y = dp.put_global(mesh, test_ds.labels, P())
    history = M.MetricsHistory()
    saver = checkpoint.make_saver(config.async_checkpoint, tele=tele)
    plan_spec = P(None, "data") if data_size > 1 else P()
    # One dropout key for the whole run, hoisted out of the loop (each step folds it
    # with state.step inside the compiled program — same per-step keys as before).
    dropout_rng = jax.random.PRNGKey(config.seed + 1)
    # Replicate shards on device (all-gather), then fetch — device_get on a sharded
    # array would fail on a multi-host fleet where no process addresses every shard.
    gather = dp.gather_replicated(mesh)

    def to_host_standard(state) -> TrainState:
        """Gathered host copy in the standard per-name checkpoint layout (the
        interchange contract with every other mesh — stage layouts bridge back)."""
        host_state = jax.device_get(gather(state))
        if stage_size > 1:
            unstack = lambda t: pipeline.unstack_transformer_blocks(t["blocks"],
                                                                    t["rest"])
            host_state = TrainState(
                unstack(host_state.params),
                optim.map_param_trees(host_state.velocity, unstack),
                host_state.step,
                unstack(host_state.ema)
                if host_state.ema is not None else None,
                host_state.guard)      # scalars pass through the bridge
        return host_state

    ckpt_path = (os.path.join(config.results_dir, "model_composed.ckpt")
                 if config.results_dir else "")
    if ckpt_path:
        os.makedirs(config.results_dir, exist_ok=True)

    # Compile/execute split (telemetry): AOT-compile + FLOP-price the epoch program
    # (stage/jit path; the TP/FSDP cached-sharding wrappers have no .lower —
    # compile_s stays null and folds into the first epoch's wall clock).
    # Gated on the CONFIG flag, not tele.enabled: every process must take the same
    # compile path (AOT-compiled vs jit) on a multi-host fleet.
    compile_s = flops_per_step = None
    if config.telemetry:
        plan_struct = jax.ShapeDtypeStruct(
            (steps_per_epoch, config.batch_size), np.int32)
        compiled, aot = T.aot_compile(epoch_fn, state, train_x, train_y,
                                      plan_struct, dropout_rng)
        if compiled is not None:
            epoch_fn = compiled
            compile_s = aot["lower_s"] + aot["compile_s"]
            if aot["flops"]:
                flops_per_step = aot["flops"] / steps_per_epoch
            tele.emit(T.compile_event("epoch", aot,
                                      steps_per_call=steps_per_epoch))

    try:
        host_state = _run_epochs(
            config, state, mesh, epoch_fn, eval_fn, train_x, train_y, test_x,
            test_y, dropout_rng, plan_spec, n_train, n_test, steps_per_epoch,
            start_epoch, history, watch, saver, ckpt_path, to_host_standard,
            tele, compile_s, flops_per_step, rt, grt)
    finally:
        # Drain the write-behind queue even on an exception/signal/preemption
        # mid-run — the queued per-epoch checkpoint is the resume artifact a killed
        # run needs, and flush() re-raises deferred background IO errors. The
        # preemption latch is uninstalled so in-process callers get their signal
        # semantics back.
        rt.uninstall()
        saver.flush()
    if ckpt_path:
        M.log(f"Saved {ckpt_path}")
    if config.results_dir:
        M.save_metrics_jsonl(history,
                             os.path.join(config.results_dir, "metrics.jsonl"))
    return host_state, history


def _run_epochs(config, state, mesh, epoch_fn, eval_fn, train_x, train_y, test_x,
                test_y, dropout_rng, plan_spec, n_train, n_test, steps_per_epoch,
                start_epoch, history, watch, saver, ckpt_path, to_host_standard,
                tele, compile_s, flops_per_step, rt, grt=None):
    """The composed trainer's epoch loop, split out so the caller can guarantee the
    async-checkpoint flush in a ``finally`` regardless of where the loop fails."""
    host_state = None
    best_step_s = None
    ckpt_store = (os.path.join(config.results_dir, "checkpoints")
                  if config.results_dir else "")
    with maybe_profile(config.profile, config.profile_dir):
        for epoch in range(start_epoch, config.epochs):
            # heartbeat (with the previous boundary's param fingerprint)
            # + armed faults; no-op off
            rt.epoch_tick(state, epoch,
                          fingerprint=grt.fingerprint if grt else None)
            t_epoch = time.perf_counter()
            # (seed, epoch)-keyed permutation — a pure function, so a resumed run
            # replays exactly the epochs it missed (same contract as
            # parallel/sampler.py's global_permutation).
            perm = np.random.default_rng(
                np.random.SeedSequence([config.seed, epoch])).permutation(n_train)
            plan = dp.put_global(
                mesh,
                perm[:steps_per_epoch * config.batch_size].astype(np.int32)
                .reshape(steps_per_epoch, config.batch_size), plan_spec)
            data_s = time.perf_counter() - t_epoch
            t_exec = time.perf_counter()
            state, out = epoch_fn(state, train_x, train_y, plan, dropout_rng)
            losses, epoch_health = (out if config.health_stats else (out, None))
            jax.block_until_ready(state.params)
            epoch_loss = float(np.asarray(jax.device_get(losses)).mean())
            execute_s = time.perf_counter() - t_exec
            t_eval = time.perf_counter()
            eval_params = state.ema if state.ema is not None else state.params
            sum_nll, correct = jax.device_get(eval_fn(eval_params, test_x, test_y))
            eval_s = time.perf_counter() - t_eval
            examples_trained = (epoch + 1) * steps_per_epoch * config.batch_size
            history.record_train(examples_trained, epoch_loss)
            history.record_test(examples_trained, float(sum_nll) / n_test)
            M.log(f"Epoch {epoch}: train_loss: {epoch_loss:.4f}, "
                  f"val_loss: {float(sum_nll) / n_test:.4f}, "
                  f"accuracy: {int(correct) / n_test:.4f}, "
                  f"time_elapsed: {watch.elapsed():.2f}s")
            if epoch_health is not None:
                # SPMD-entered by every process (the norm program would deadlock
                # a fleet if only process 0 ran it); emission below stays
                # process-0 gated.
                health_host = jax.device_get(epoch_health)
                param_norm = T.global_l2_norm(state.params)
            if tele.enabled:
                step_s = execute_s / steps_per_epoch if steps_per_epoch else None
                if step_s and (best_step_s is None or step_s < best_step_s):
                    best_step_s = step_s
                tele.emit(T.epoch_event(
                    epoch, examples=steps_per_epoch * config.batch_size,
                    steps=steps_per_epoch, wall_s=time.perf_counter() - t_epoch,
                    execute_s=execute_s, eval_s=eval_s, data_s=data_s,
                    compile_s=compile_s, flops_per_step=flops_per_step,
                    train_loss=epoch_loss, val_loss=float(sum_nll) / n_test,
                    mfu=T.estimate_mfu(flops_per_step, step_s)["mfu"]))
                if epoch_health is not None:
                    tele.emit(T.health_event(epoch, health_host, steps_per_epoch,
                                             param_norm=param_norm))
            # Guard boundary: anomaly verdict fetch + event + cross-replica
            # fingerprint, then the manifest health stamp for the save.
            stamp = (grt.epoch_end(state, epoch, steps_per_epoch)
                     if grt else None)
            # Per-epoch full-state checkpoint (standard layout, process-0 gated,
            # atomic) so a killed run resumes with --resume-from on ANY mesh. The
            # final epoch's host copy doubles as the return value — no second
            # gather/save after the loop.
            if ckpt_path:
                if config.sharded_checkpoint:
                    # Distributed writer: every process saves only the shards it
                    # addresses, straight from device — no all-gather, no host copy
                    # of the full state on any single process.
                    checkpoint.save_train_state_sharded(ckpt_path + ".sharded",
                                                        state)
                host_state = to_host_standard(state)
                saver.save_train_state(ckpt_path, host_state)
                if ckpt_store and config.keep_checkpoints:
                    # Versioned store (manifest + checksums + keep-last-N GC) for
                    # the supervisor's newest-HEALTHY resume scan.
                    checkpoint.save_versioned(
                        ckpt_store, host_state, keep=config.keep_checkpoints,
                        tele=tele, health=stamp,
                        # The manifest's data cursor: the (seed, epoch)-pure
                        # permutation's resume anchor (DESIGN.md §26).
                        cursor={"version": 1, "kind": "epoch",
                                "seed": config.seed, "epoch": epoch + 1,
                                "batch": 0, "step": int(host_state.step)})
            # Anomaly policy AFTER the stamped checkpoint is durable (raises
            # Poisoned; __main__ exits 65).
            if grt:
                grt.check_poisoned(state)
            # Cooperative preemption at the epoch boundary, with this epoch's
            # checkpoint durable (raises Preempted; __main__ exits 75).
            rt.check_preempt(epoch=epoch, state=state, checkpoint=ckpt_path,
                             tele=tele)

    if tele.enabled and best_step_s is not None:
        tele.emit(T.mfu_event(flops_per_step, best_step_s))
    if host_state is None:      # no results_dir, or the resume skipped every epoch
        host_state = to_host_standard(state)
        if ckpt_path:           # zero-epoch resume must still leave a checkpoint
            saver.save_train_state(ckpt_path, host_state)
    return host_state


if __name__ == "__main__":
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    try:
        main(parse_config(ComposedConfig))
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
