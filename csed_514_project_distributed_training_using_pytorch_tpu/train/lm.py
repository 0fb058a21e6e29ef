"""Autoregressive pixel-LM trainer: next-token training + on-device generation.

Beyond-parity surface (the reference trains one classifier and has no language model,
reference ``src/model.py:4-22``): teacher-forced next-token training of
``models/lm.py::TransformerLM`` over quantized MNIST pixel streams, data-parallel over
every addressable device, with the same machinery as the other trainers — scanned-epoch
compiled programs (``train/step.py``), the optimizer/schedule/clipping stack
(``ops/optim.py``), per-epoch checkpoints with ``--resume-from``, and the metric-line +
loss-curve conventions. After training it samples digits with the KV-cache decoder
(``models/lm.py::generate``) and saves them as an image grid — the generation path is a
first-class user surface, not a demo.

The LM reuses ``make_train_step`` wholesale via its ``loss_fn`` override: the epoch
program gathers ``[B, S]`` token batches from the device-resident token array by index
plan exactly like the classifier trainers gather images (zero per-step host traffic).
"""

from __future__ import annotations

import functools
import os
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from csed_514_project_distributed_training_using_pytorch_tpu.data import (
    download_mnist, load_mnist, mnist,
)
from csed_514_project_distributed_training_using_pytorch_tpu.data import (
    stream as stream_mod,
)
from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
from csed_514_project_distributed_training_using_pytorch_tpu.models import lm as lm_mod
from csed_514_project_distributed_training_using_pytorch_tpu.models import (
    validate_remat_policy,
)
from csed_514_project_distributed_training_using_pytorch_tpu import ops, resilience
from csed_514_project_distributed_training_using_pytorch_tpu.ops import optim
from csed_514_project_distributed_training_using_pytorch_tpu.train.guard import (
    GuardRuntime,
)
from csed_514_project_distributed_training_using_pytorch_tpu.parallel import (
    data_parallel as dp,
)
from csed_514_project_distributed_training_using_pytorch_tpu.parallel.mesh import (
    initialize_cluster, make_mesh,
)
from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
    TrainState, create_train_state, make_epoch_from_step, make_train_step,
)
from csed_514_project_distributed_training_using_pytorch_tpu.utils import checkpoint
from csed_514_project_distributed_training_using_pytorch_tpu.utils import metrics as M
from csed_514_project_distributed_training_using_pytorch_tpu.utils import plotting
from csed_514_project_distributed_training_using_pytorch_tpu.utils import profiling
from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import (
    LMConfig, parse_config,
)
from csed_514_project_distributed_training_using_pytorch_tpu.utils import (
    telemetry as T,
)


# The direct children of the epoch loop's `step("epoch", n)`, as `epoch/<name>` on a
# trace and `<name>_s` in the `epoch` telemetry event.
EPOCH_SPANS = ("data", "execute", "eval", "log", "emit", "guard", "checkpoint", "tick")


def _attention_plan(config: LMConfig, seq_len: int, world: int, shape: tuple, *,
                    dispatched: bool) -> dict:
    """The ``compile`` event's ``attention`` field: what the step's attention call
    gets, by the dispatcher's own predicate on the per-device microbatch; where the
    model keeps the dense core (``dispatched`` false) the same keys say so. ``shape``
    is the model's ``Trainee.attention_shape``: heads, their width, their values'."""
    heads, head_dim, value_dim = shape
    plan = ops.dispatch_plan(
        (config.batch_size // world // config.grad_accum, seq_len, heads, head_dim),
        causal=True, window=config.attention_window, value_dim=value_dim)
    if not dispatched:
        plan.update(impl="dense", seq_padded=None, block=None, backward=None)
    return plan


def make_eval_nll_fn(batch_nll, *, batch_size: int):
    """``evaluate(params, tokens) -> sum_nll`` — summed next-token NLL over the split by
    the model's ``Trainee.eval_nll`` a batch (divide by ``N·targets_per_seq`` for the mean;
    ``exp`` of that is perplexity), one scanned program like the classifier's eval."""

    def evaluate(params, tokens):
        n = tokens.shape[0]
        if n % batch_size:
            raise ValueError(f"eval split size {n} not divisible by eval batch "
                             f"{batch_size}")
        xs = tokens.reshape((n // batch_size, batch_size) + tokens.shape[1:])

        def body(carry, batch):
            return carry + batch_nll(params, batch), None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), xs)
        return total

    return evaluate


def main(config: LMConfig = LMConfig(), *,
         datasets=None) -> tuple[TrainState, M.MetricsHistory]:
    """Run LM training over all addressable devices; returns final state + history."""
    watch = M.Stopwatch()
    if config.grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {config.grad_accum}")
    if config.health_stats and not config.telemetry:
        raise ValueError("--health-stats emits telemetry 'health' events and has no "
                         "other output — pass --telemetry PATH too")
    validate_remat_policy(config.remat, config.remat_policy)
    if config.attention_window:
        # Fail fast, pre-data/rendezvous (one owner for the message).
        from csed_514_project_distributed_training_using_pytorch_tpu.ops.attention import (
            validate_window,
        )
        validate_window(config.attention_window)
    if config.kv_heads and (config.kv_heads < 0
                            or config.num_heads % config.kv_heads):
        raise ValueError(f"--kv-heads {config.kv_heads} must be a positive divisor "
                         f"of --num-heads {config.num_heads}")
    if config.model_config and not config.corpus:
        raise ValueError("--model-config trains on a token corpus: pass --corpus DIR "
                         "(tools/build_corpus.py) whose vocabulary is the file's")
    info = initialize_cluster()
    run_plan, plan_events = None, []
    if config.plan:
        # Resolve BEFORE the mesh spec is read: the plan rewrites mesh/
        # grad_accum on the (frozen) config (data x model search — plan/).
        # Autotune trial events buffer until the telemetry writer exists below.
        from csed_514_project_distributed_training_using_pytorch_tpu import (
            plan as plan_mod,
        )
        config, run_plan = plan_mod.apply_plan(config, "lm",
                                               emit=plan_events.append)
    if config.mesh:
        # Optional named mesh: data (DP) x seq (context parallelism — ring or
        # zig-zag causal attention over the sequence-sharded pixel stream) x
        # model (Megatron TP over the blocks' column/row kernels — r5; the ring
        # spec already shards the head dim over `model`, so seq x model composes).
        from csed_514_project_distributed_training_using_pytorch_tpu.parallel.mesh import (
            parse_mesh_spec,
        )
        axis_names, axis_sizes = parse_mesh_spec(config.mesh)
        if (any(n not in ("data", "seq", "model") for n in axis_names)
                or "data" not in axis_names):
            raise ValueError("the LM trainer's --mesh needs a data axis and supports "
                             f"data, seq, and model axes only, got {config.mesh!r} "
                             f"(use data=1,seq=N for pure context parallelism)")
        mesh = make_mesh(int(np.prod(axis_sizes)), axis_names=axis_names,
                         axis_shape=axis_sizes)
    else:
        mesh = make_mesh()
    world = mesh.shape.get("data", 1)
    seq_size = mesh.shape.get("seq", 1)
    model_size = mesh.shape.get("model", 1)
    if config.zigzag_attention and seq_size < 2:
        raise ValueError("--zigzag-attention needs a seq axis in --mesh")
    # r4: --attention-window composes with the zig-zag schedule too (global-
    # position chunk-pair band masks in zigzag_ring_attention) — no guard needed.
    if config.batch_size % world:
        raise ValueError(f"batch {config.batch_size} not divisible by data axis "
                         f"{world}")

    loader = None
    eval_batch = config.eval_batch
    if config.corpus:
        # Streaming token-shard corpus (data/stream.py, DESIGN.md §26): the
        # epoch feed comes off disk through the deterministic cursor loader;
        # vocab/seq_len are the corpus's, not MNIST's. The scanned epoch
        # program is unchanged — each epoch's batches materialize into the
        # device-resident token array and the plan is the identity (the
        # loader already emitted them in stream order).
        loader = stream_mod.StreamLoader(config.corpus, config.batch_size,
                                         seed=config.seed,
                                         throttle_s=config.data_throttle_s)
        seq_len = loader.seq_len
        vocab = loader.vocab
        test_tokens = stream_mod.eval_tokens(config.corpus)
        if test_tokens is None or not len(test_tokens):
            raise ValueError(f"--corpus {config.corpus} has no eval split — "
                             f"rebuild with tools/build_corpus.py --eval-frac")
        n_train = loader.batches_per_epoch * config.batch_size
        eval_batch = min(config.eval_batch, len(test_tokens))
        n_test = len(test_tokens) - len(test_tokens) % eval_batch
        test_tokens = test_tokens[:n_test]
        train_tokens = None
        data_source = f"corpus:{config.corpus}"
    else:
        if config.download_data and datasets is None:
            download_mnist(config.data_dir)
        train_ds, test_ds = (datasets if datasets is not None
                             else load_mnist(config.data_dir))
        train_ds = mnist.truncate(train_ds, config.max_train_examples)
        test_ds = mnist.truncate(test_ds, config.max_test_examples)

        # Tokenize ONCE on host; the token arrays are the device-resident dataset.
        train_tokens = np.asarray(lm_mod.tokenize_images_to_ids(
            jnp.asarray(train_ds.images), num_levels=config.num_levels))
        test_tokens = np.asarray(lm_mod.tokenize_images_to_ids(
            jnp.asarray(test_ds.images), num_levels=config.num_levels))
        n_train, n_test = len(train_tokens), len(test_tokens)
        seq_len = train_tokens.shape[1]
        vocab = config.num_levels
        data_source = train_ds.source

    lm_kwargs = {}
    if seq_size > 1:
        # Context parallelism for the decoder: the ring (or zig-zag) causal core
        # plugs in without touching parameters, so seq-mesh checkpoints interchange
        # with DP runs (trajectory equality pinned in tests).
        from csed_514_project_distributed_training_using_pytorch_tpu.parallel import (
            make_ring_attention_fn,
        )
        need = 2 * seq_size if config.zigzag_attention else seq_size
        if seq_len % need:
            raise ValueError(f"seq_len {seq_len} must divide by "
                             f"{'2*seq axis' if config.zigzag_attention else 'the seq axis'}"
                             f" = {need}")
        # --attention-window binds the sliding band into the ring schedule itself
        # (windowed context parallelism, r3: out-of-band hops skip their einsums);
        # the model's own attention_window field must then stay 0 — the decode
        # clone below re-adds it for the KV-cache mask.
        lm_kwargs["attention_fn"] = make_ring_attention_fn(
            mesh, use_zigzag=config.zigzag_attention,
            window=config.attention_window)
    elif mesh.size == 1:
        # One device: the core is picked per call from its shapes (dense while the
        # float32 scores stay on-chip, the flash kernels once they would go through
        # HBM: ops.dispatch_plan). On a mesh of several devices the dense core stays:
        # jit's partitioner cannot split a Pallas call over the data axis, and no
        # shard_map wraps it here yet.
        lm_kwargs["attention_fn"] = ops.dispatch_attention
    # Fail fast on sampling knobs: generate() re-checks these, but its first call is
    # AFTER the full training loop — a bad flag must not cost the whole run.
    if not 0 <= config.top_k <= vocab + 1:
        raise ValueError(f"top_k {config.top_k} outside [0, {vocab + 1}]")
    if not 0.0 < config.top_p <= 1.0:
        raise ValueError(f"top_p {config.top_p} outside (0, 1]")
    if config.model_config:
        # A published architecture from its file (or one chip's share of it): the
        # same loop, optimizer, telemetry and spans; only the model and its loss
        # differ.
        if mesh.size > 1 or config.attention_window:
            # jit's partitioner cannot split the expert layer's (or the flash)
            # Pallas calls over a data axis, and no shard_map wraps them here yet.
            raise ValueError("--model-config trains on one device for now (--mesh "
                             "data=1), and takes no --attention-window")
        if config.label_smoothing or config.dropout_rate:
            raise ValueError("--model-config takes no --label-smoothing and no "
                             "--dropout-rate: its stack and its loss have neither")
        model = hybrid_lm.from_config_file(
            config.model_config, vocab_size=vocab, seq_len=seq_len,
            dtype=jnp.bfloat16 if config.bf16 else jnp.float32, remat=config.remat,
            **lm_kwargs)
    else:
        model = lm_mod.TransformerLM(
            vocab_size=vocab + 1, seq_len=seq_len,
            embed_dim=config.embed_dim, num_layers=config.num_layers,
            num_heads=config.num_heads, dropout_rate=config.dropout_rate,
            num_kv_heads=config.kv_heads or None,
            attention_window=(0 if seq_size > 1 else config.attention_window),
            rope=config.rope,
            dtype=jnp.bfloat16 if config.bf16 else jnp.float32, remat=config.remat,
            remat_policy=config.remat_policy,
            **lm_kwargs)
    # all that is asked of the model from here on
    view = model.trainee(deterministic=config.dropout_rate == 0.0,
                         label_smoothing=config.label_smoothing)
    # Decoding is single-chip (host params): restore the default core, and the
    # window as a model field so the KV-cache decode mask applies the same band the
    # (possibly ring-windowed) training attention did — decode parity holds across
    # the mesh choice because attention has no window-dependent parameters.
    decode_model = (model.clone(attention_fn=ops.full_attention,
                                attention_window=config.attention_window)
                    if seq_size > 1 else model)
    M.log(f"LM training: mesh {dict(mesh.shape)} on {info.process_count} process(es), "
          f"batch {config.batch_size}, vocab {vocab}"
          f"{'+BOS' if model.vocab_size > vocab else ''}, "
          f"seq {seq_len}, data source: {data_source}")
    # Telemetry + resilience wiring live ABOVE the resume so the restore is recorded;
    # resilience hooks are flag-gated, host-side only (zero-cost when off).
    tele = T.TelemetryWriter(config.telemetry,
                             preserve=bool(config.resume_from))
    tele.emit(T.manifest_event(config, mesh=mesh, run_type="lm"))
    if run_plan is not None:
        tele.emit(T.plan_event(run_plan))
        for ev in plan_events:
            tele.emit(ev)
    rt = resilience.RunHooks(heartbeat_dir=config.heartbeat_dir,
                             handle_preemption=config.handle_preemption,
                             process_index=info.process_index)
    # Numerical immune system (--guard): in-step verdict + identity update;
    # host side is epoch-boundary bookkeeping only.
    grt = GuardRuntime(config, tele=tele,
                       store_dir=os.path.join(config.results_dir, "checkpoints")
                       if config.results_dir else "")

    optimizer = optim.make_optimizer(config.optimizer,
                                     learning_rate=config.learning_rate,
                                     momentum=config.momentum,
                                     weight_decay=config.weight_decay)
    if view.is_frozen is not None:
        optimizer = optim.freeze(optimizer, view.is_frozen)
    state = create_train_state(model, jax.random.PRNGKey(config.seed),
                               sample_input_shape=(1, seq_len),
                               optimizer=optimizer, ema=config.ema_decay > 0,
                               guard=config.guard)
    steps_per_epoch = n_train // config.batch_size
    if steps_per_epoch == 0:
        raise ValueError(f"batch {config.batch_size} larger than the train split "
                         f"({n_train} examples) — nothing to step")
    lr_schedule = optim.make_lr_schedule(config.lr_schedule,
                                         warmup_steps=config.warmup_steps,
                                         total_steps=config.epochs * steps_per_epoch)
    start_epoch = 0
    if config.resume_from:
        state, start_epoch, warning = checkpoint.restore_for_resume(
            config.resume_from, state,
            process_index=info.process_index, process_count=info.process_count,
            steps_per_epoch=steps_per_epoch, tele=tele)
        if warning:
            M.log(f"WARNING: {warning}")
        M.log(f"Resumed from {config.resume_from} at step {int(state.step)} "
              f"(starting epoch {start_epoch})")
        # Manifest cursor (DESIGN.md §26): the checkpoint and the stream
        # position that produced it are one artifact. Stream cursors VERIFY
        # against this corpus (drift raises — silently resuming a reshuffled
        # or edited corpus would feed different bytes than the step count
        # paid for) and override the step-derived start epoch; epoch cursors
        # cross-check it.
        man_cursor = checkpoint.cursor_for(config.resume_from)
        if loader is not None and man_cursor is not None:
            cur_epoch, cur_batch = loader.verify_cursor(man_cursor)
            if cur_batch:
                M.log(f"WARNING: stream cursor resumes mid-epoch (batch "
                      f"{cur_batch}) but the epoch program replays whole "
                      f"epochs — starting at epoch {cur_epoch}")
            start_epoch = cur_epoch
        else:
            note = checkpoint.check_cursor_resume(
                config.resume_from, seed=config.seed, step=int(state.step),
                start_epoch=start_epoch)
            if note:
                M.log(f"WARNING: {note}")
    grt.baseline(state)     # this attempt's anomaly-counter zero point
    if model_size > 1:
        # Megatron TP (r5): column/row kernels shard over `model` (the LM blocks
        # reuse TransformerBlock's leaf names, so the classifier's partition rules
        # apply as-is); embeddings/head/LNs replicate. One block owns BOTH the
        # placement and the matching epoch compiler so they cannot diverge.
        from csed_514_project_distributed_training_using_pytorch_tpu.parallel import (
            tensor_parallel as tp,
        )
        state = tp.shard_train_state(mesh, state)
        compile_lm_epoch = functools.partial(tp.compile_epoch_tp, mesh=mesh,
                                             data_axis="data")
    else:
        state = jax.device_put(state, dp.replicated(mesh))
        compile_lm_epoch = functools.partial(dp.compile_epoch, mesh=mesh)
    # Host fetches must replicate ON DEVICE first (all-gather) — device_get on a
    # TP-sharded array would fail on a multi-host fleet where no process
    # addresses every shard.
    gather = dp.gather_replicated(mesh)

    health = config.health_stats
    step_fn = make_train_step(model, learning_rate=config.learning_rate,
                              momentum=config.momentum, grad_accum=config.grad_accum,
                              optimizer=optimizer, lr_schedule=lr_schedule,
                              clip_grad_norm=config.clip_grad_norm,
                              ema_decay=config.ema_decay, loss_fn=view.loss,
                              with_metrics=health, guard=grt.spec,
                              loss_has_aux=view.has_aux, after_update=view.after_update)
    epoch_fn = compile_lm_epoch(make_epoch_from_step(step_fn, health=health,
                                                     aux=view.has_aux))
    eval_fn = jax.jit(make_eval_nll_fn(view.eval_nll, batch_size=eval_batch))

    # Corpus mode: the device token array is REFILLED per epoch from the
    # streaming loader (same shape every epoch — the compiled program is
    # oblivious); seed it with zeros so AOT compile below sees real arrays.
    tokens_d = dp.put_global(
        mesh, (np.zeros((n_train, seq_len), np.int32) if loader is not None
               else train_tokens), P())
    # ys is unused by the LM loss; a zero vector keeps the epoch program's
    # (images, labels, plan) signature without a second token gather per step.
    zeros_d = dp.put_global(mesh, np.zeros(n_train, np.int32), P())
    test_d = dp.put_global(mesh, test_tokens, P())
    dropout_rng = jax.random.PRNGKey(config.seed + 1)
    # Compile/execute split (telemetry): AOT-compile + FLOP-price the epoch program
    # (DP path; the TP cached-sharding wrapper has no .lower — compile_s stays null
    # and folds into the first epoch).
    # Gated on the CONFIG flag, not tele.enabled: every process must take the same
    # compile path (AOT-compiled vs jit) on a multi-host fleet.
    compile_s = flops_per_step = bytes_per_step = None
    if config.telemetry:
        plan_struct = jax.ShapeDtypeStruct(
            (steps_per_epoch, config.batch_size), np.int32)
        compiled, aot = T.aot_compile(epoch_fn, state, tokens_d, zeros_d,
                                      plan_struct, dropout_rng)
        if compiled is not None:
            epoch_fn = compiled
            compile_s = aot["lower_s"] + aot["compile_s"]
            if aot["flops"]:
                flops_per_step = aot["flops"] / steps_per_epoch
            if aot.get("bytes_accessed"):
                bytes_per_step = aot["bytes_accessed"] / steps_per_epoch
            # no plan for a stack none of whose mixers goes through ``attention_fn``:
            # the model's own fields alone (how its mixers turn q and k)
            plan = {} if view.attention_shape is None else _attention_plan(
                config, seq_len, world, view.attention_shape, dispatched=mesh.size == 1)
            attention = None if seq_size > 1 else {**plan, **view.attention_fields} or None
            step_tokens = config.batch_size // world // config.grad_accum * seq_len
            tele.emit(T.compile_event("epoch", aot,
                                      steps_per_call=steps_per_epoch,
                                      attention=attention,
                                      scopes=T.write_scope_table(
                                          config.telemetry, aot["scopes"],
                                          steps_per_call=steps_per_epoch),
                                      plans=view.plans(aot["jaxpr"], step_tokens)))
    history = M.MetricsHistory()
    saver = checkpoint.make_saver(config.async_checkpoint, tele=tele)

    ckpt_path = (os.path.join(config.results_dir, "model_lm.ckpt")
                 if config.results_dir else "")
    if ckpt_path:
        os.makedirs(config.results_dir, exist_ok=True)

    try:
        with profiling.maybe_profile(config.profile, config.profile_dir):
            state = _run_epochs(config, state, mesh, epoch_fn, eval_fn, tokens_d,
                                zeros_d, test_d, dropout_rng, n_train, n_test, view,
                                steps_per_epoch, start_epoch, history, watch, saver,
                                ckpt_path, gather, tele, compile_s, flops_per_step, rt,
                                bytes_per_step, grt, loader)
    finally:
        # Drain the write-behind queue even on an exception/signal/preemption
        # mid-run — the queued per-epoch checkpoint is the resume artifact a killed
        # run needs, and flush() re-raises deferred background IO errors. The
        # preemption latch is uninstalled so in-process callers get their signal
        # semantics back.
        rt.uninstall()
        saver.flush()

    host_state = jax.device_get(gather(state))
    if ckpt_path:
        M.log(f"Saved {ckpt_path}")
    if config.generate > 0 and loader is None:
        # Corpus-trained models skip the digit grids: ids_to_images only means
        # something for the pixel-stream tokenizer.
        def sample_grid(filename: str, seed_offset: int, batch: int, **gen_kw):
            gen_params = (host_state.ema if host_state.ema is not None
                          else host_state.params)
            # Cold path: runs once per figure AFTER training, and each call's
            # closure (batch/gen_kw) differs — a cached wrapper would never
            # be reused, so the per-call jit is sanctioned here.
            ids = jax.jit(lambda key: lm_mod.generate(  # graftlint: disable=retrace-hazard
                decode_model, gen_params, key, batch=batch,
                temperature=config.temperature, top_k=config.top_k,
                top_p=config.top_p, **gen_kw))(
                    jax.random.PRNGKey(config.seed + seed_offset))
            path = os.path.join(config.images_dir, filename)
            if plotting.save_generated_grid(
                    np.asarray(lm_mod.ids_to_images(ids,
                                                    num_levels=config.num_levels)),
                    path, n=batch) is not None:
                M.log(f"Saved {path}")

        sample_grid("lm_samples.png", 2, config.generate)
        # Digit completion: teacher-force the top half of real test images, sample
        # the bottom half — the prompt-conditioned generation surface.
        n_c = min(config.generate, n_test)
        sample_grid("lm_completions.png", 3, n_c,
                    prompt=jnp.asarray(test_tokens[:n_c]),
                    prompt_len=seq_len // 2)
    plotting.save_loss_curves(history,
                              os.path.join(config.images_dir, "lm_loss_curve.png"))
    if config.results_dir:
        M.save_metrics_jsonl(history,
                             os.path.join(config.results_dir, "metrics.jsonl"))
    return host_state, history


def _run_epochs(config, state, mesh, epoch_fn, eval_fn, tokens_d, zeros_d, test_d,
                dropout_rng, n_train, n_test, view, steps_per_epoch, start_epoch,
                history, watch, saver, ckpt_path, gather, tele, compile_s,
                flops_per_step, rt, bytes_per_step=None, grt=None, loader=None):
    """The LM trainer's epoch loop, split out so the caller can guarantee the
    async-checkpoint flush in a ``finally`` regardless of where the loop fails."""
    best_step_s = None
    ckpt_store = (os.path.join(config.results_dir, "checkpoints")
                  if config.results_dir else "")
    # Every statement of the loop body sits inside exactly one named span (README
    # "Telemetry" has the table): a host event on the profiler's clock whenever a
    # trace is being taken, and one `*_s` field of the `epoch` event. An earlier run
    # in this process must not reach into this run's first period: start a fresh table.
    profiling.drain()
    if tele.enabled:
        # estimate_mfu's first use imports utils.benchmarks and the distributed
        # trainer with it (6-15 ms): paid here, not inside the first `epoch/emit`.
        T.estimate_mfu(None, None)
    for epoch in range(start_epoch, config.epochs):
        with profiling.step("epoch", epoch):
            with profiling.span("epoch/tick"):
                # heartbeat (with the previous boundary's param fingerprint) + armed
                # faults; no-op off
                rt.epoch_tick(state, epoch,
                              fingerprint=grt.fingerprint if grt else None)
            with profiling.span("epoch/data"):
                t_epoch = time.perf_counter()       # wall_s alone
                stream_wait_s = stream_digest = None
                if loader is not None:
                    # Streaming corpus feed (data/stream.py): the loader's
                    # (seed, epoch)-pure shard shuffle IS the permutation, already
                    # in batch order — refill the device token array and run the
                    # identity plan. Loader stall (shard IO, sha256,
                    # --data-throttle-s) lands in this epoch's data_s and therefore
                    # in goodput's data_wait.
                    epoch_np = loader.epoch_tokens(epoch)
                    stream_wait_s = loader.pop_wait_s()
                    stream_digest = zlib.crc32(epoch_np.tobytes())
                    tokens_d = dp.put_global(mesh, epoch_np, P())
                    plan = dp.put_global(
                        mesh,
                        np.arange(steps_per_epoch * config.batch_size, dtype=np.int32)
                        .reshape(steps_per_epoch, config.batch_size), P(None, "data"))
                else:
                    # (seed, epoch)-keyed permutation — the parallel/sampler
                    # contract, so resumed runs replay exactly the epochs they missed.
                    perm = np.random.default_rng(np.random.SeedSequence(
                        [config.seed, epoch])).permutation(n_train)
                    plan = dp.put_global(
                        mesh,
                        perm[:steps_per_epoch * config.batch_size].astype(np.int32)
                        .reshape(steps_per_epoch, config.batch_size), P(None, "data"))
            with profiling.span("epoch/execute"):
                with profiling.span("execute/dispatch"):
                    state, out = epoch_fn(state, tokens_d, zeros_d, plan, dropout_rng)
                # (losses[, health][, the expert layers' arrived rows, None for a
                # stack with no expert layer]): see train.step.make_epoch_from_step
                out = out if isinstance(out, tuple) else (out,)
                losses = out[0]
                epoch_health = out[1] if config.health_stats else None
                expert_counts = out[-1] if view.has_aux else None
                with profiling.span("execute/wait"):
                    jax.block_until_ready(state.params)
                with profiling.span("execute/loss_fetch"):
                    train_loss = float(np.asarray(jax.device_get(losses)).mean())
                    if expert_counts is not None:
                        expert_counts = np.asarray(jax.device_get(expert_counts))
            with profiling.span("epoch/eval"):
                eval_params = state.ema if state.ema is not None else state.params
                sum_nll = float(jax.device_get(eval_fn(eval_params, test_d)))
            with profiling.span("epoch/log"):
                val_nll = sum_nll / (n_test * view.targets_per_seq)
                examples = (epoch + 1) * steps_per_epoch * config.batch_size
                history.record_train(examples, train_loss)
                history.record_test(examples, val_nll)
                M.log(f"Epoch {epoch}: train_loss: {train_loss:.4f}, "
                      f"val_nll/token: {val_nll:.4f}, "
                      f"val_ppl: {float(np.exp(val_nll)):.3f}, "
                      f"time_elapsed: {watch.elapsed():.2f}s")
                if epoch_health is not None:
                    # SPMD-entered by every process (the norm program would deadlock
                    # a fleet if only process 0 ran it); emission below stays
                    # process-0 gated.
                    health_host = jax.device_get(epoch_health)
                    param_norm = T.global_l2_norm(state.params)
            with profiling.span("epoch/emit"):
                if tele.enabled:
                    # The event is emitted before its own iteration ends, so it
                    # carries what is drained here: this iteration's head (tick,
                    # data, execute, eval, log) and the previous one's tail (emit,
                    # guard, checkpoint, tick). A span that did not run reads 0.0.
                    spans, period_s = profiling.drain()
                    span_s = {f"{name}_s": spans.get(f"epoch/{name}", 0.0)
                              for name in EPOCH_SPANS}
                    step_s = (span_s["execute_s"] / steps_per_epoch
                              if steps_per_epoch else None)
                    if step_s and (best_step_s is None or step_s < best_step_s):
                        best_step_s = step_s
                    tele.emit(T.epoch_event(
                        epoch, examples=steps_per_epoch * config.batch_size,
                        steps=steps_per_epoch, wall_s=time.perf_counter() - t_epoch,
                        period_s=period_s, **span_s,
                        compile_s=compile_s, flops_per_step=flops_per_step,
                        train_loss=train_loss, val_loss=val_nll,
                        mfu=T.estimate_mfu(flops_per_step, step_s)["mfu"],
                        expert_counts=expert_counts,
                        expert_block=view.expert_block))
                    if epoch_health is not None:
                        tele.emit(T.health_event(epoch, health_host, steps_per_epoch,
                                                 param_norm=param_norm))
                    if loader is not None:
                        # The stream ledger next to the epoch event: stall wall,
                        # next-epoch cursor (the one the checkpoint below stamps),
                        # and the epoch's token CRC — the bitwise pin the
                        # deterministic-resume tests compare across a kill.
                        tele.emit(T.data_event(
                            epoch, batches=steps_per_epoch,
                            sequences=steps_per_epoch * config.batch_size,
                            wait_s=stream_wait_s, throttle_s=config.data_throttle_s,
                            cursor=loader.cursor(epoch + 1, 0),
                            stream_digest=stream_digest))
            with profiling.span("epoch/guard"):
                # Guard boundary: anomaly verdict fetch + event + cross-replica
                # fingerprint, then the manifest health stamp for the versioned save.
                stamp = grt.epoch_end(state, epoch, steps_per_epoch) if grt else None
            with profiling.span("epoch/checkpoint"):
                if ckpt_path:
                    # Device-resident gathered state: the saver is process-0 gated
                    # and device_gets internally — non-0 processes must not pay a
                    # host fetch.
                    ck_state = gather(state)
                    saver.save_train_state(ckpt_path, ck_state)
                    if ckpt_store and config.keep_checkpoints:
                        # Versioned store (manifest + checksums + keep-last-N GC)
                        # for the supervisor's newest-HEALTHY resume scan. The
                        # cursor stamps the NEXT epoch's stream position into the
                        # manifest (DESIGN.md §26).
                        cursor = (loader.cursor(epoch + 1, 0) if loader is not None
                                  else {"version": 1, "kind": "epoch",
                                        "seed": config.seed, "epoch": epoch + 1,
                                        "batch": 0, "step": int(ck_state.step)})
                        checkpoint.save_versioned(ckpt_store, ck_state,
                                                  keep=config.keep_checkpoints,
                                                  tele=tele, health=stamp,
                                                  cursor=cursor)
            with profiling.span("epoch/guard"):
                # Anomaly policy AFTER the stamped checkpoint is durable (raises
                # Poisoned; __main__ exits 65).
                if grt:
                    grt.check_poisoned(state)
            with profiling.span("epoch/tick"):
                # Cooperative preemption at the epoch boundary, with this epoch's
                # checkpoint durable (raises Preempted; __main__ exits 75).
                rt.check_preempt(epoch=epoch, state=state, checkpoint=ckpt_path,
                                 tele=tele)
    if tele.enabled and best_step_s is not None:
        # bytes_per_step is XLA's own bytes-accessed count for the compiled
        # step (byte-true under quantized dtypes): the mfu event carries the
        # bandwidth roofline side alongside the FLOP side.
        tele.emit(T.mfu_event(flops_per_step, best_step_s, bytes_per_step))
    return state


if __name__ == "__main__":
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    try:
        main(parse_config(LMConfig))
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
