"""The compiled train/eval step — forward, backward, and update as ONE XLA program.

This is the TPU-native replacement for the reference's per-batch sequence
``zero_grad → forward → nll → backward → optimizer.step`` (reference ``src/train.py:72-76``,
``src/train_dist.py:80-84``), which there spans the Python interpreter, the C++ autograd
engine, and (distributed) DDP's bucketed allreduce hooks. Here the whole thing — including the
gradient all-reduce when compiled over a multi-device mesh (see
``parallel/data_parallel.py``) — is a single jit-compiled, fused XLA program:

- ``make_train_step``: one optimizer step; the autograd-engine analog is ``jax.value_and_grad``.
- ``make_epoch_fn``: a ``lax.scan`` over a whole epoch (or a log-interval segment) of steps,
  gathering batches from the *device-resident* dataset by index — zero host↔device transfer
  and zero Python dispatch on the hot path, unlike the reference's per-step ``.item()`` sync
  (``src/train_dist.py:85``, SURVEY.md §7 hard part (c)).
- ``make_eval_fn``: full-split evaluation (sum-NLL + correct count) as one scanned program —
  the reference's ``test()`` loop (``src/train.py:87-104``, ``src/train_dist.py:92-109``)
  with its deprecated ``size_average=False`` sum-then-divide semantics.

Dropout randomness: a per-epoch PRNG key folded with the global step index gives every step a
fresh, reproducible key (SURVEY.md §7 hard part (b)); under SPMD the mask array itself is
batch-sharded, so replicas draw distinct masks from the same key.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from csed_514_project_distributed_training_using_pytorch_tpu import ops
from csed_514_project_distributed_training_using_pytorch_tpu.ops.optim import (
    Optimizer,
    clip_by_global_norm,
    global_l2_norm,
    sgd,
    sgd_init,
)


class HealthStats(NamedTuple):
    """Training-health accumulators that ride the epoch scan's CARRY.

    The compiled-``lax.scan`` epoch (DESIGN.md §1) makes per-step host logging
    impossible by construction — so the health signal is accumulated *inside* the
    compiled program (five f32 scalars threaded through the carry) and fetched
    ONCE at epoch end with the losses array: zero extra host syncs on the hot
    path. Gradient norms are measured PRE-clip — the explosion detector must see
    what clipping would otherwise hide. ``utils.telemetry.health_event`` turns one
    of these into the ``health`` JSONL event."""

    loss_min: jax.Array
    loss_max: jax.Array
    loss_sum: jax.Array
    grad_norm_sum: jax.Array
    grad_norm_max: jax.Array


def init_health() -> HealthStats:
    """Identity element for ``update_health`` (min over inf, max over -inf, sums over 0)."""
    inf = jnp.asarray(jnp.inf, jnp.float32)
    zero = jnp.zeros((), jnp.float32)
    return HealthStats(inf, -inf, zero, zero, zero)


def update_health(h: HealthStats, loss, grad_norm) -> HealthStats:
    """Fold one step's (loss, pre-clip global grad norm) into the accumulators."""
    loss = loss.astype(jnp.float32)
    grad_norm = grad_norm.astype(jnp.float32)
    return HealthStats(jnp.minimum(h.loss_min, loss),
                       jnp.maximum(h.loss_max, loss),
                       h.loss_sum + loss,
                       h.grad_norm_sum + grad_norm,
                       jnp.maximum(h.grad_norm_max, grad_norm))


def merge_health(a: HealthStats, b: HealthStats) -> HealthStats:
    """Combine accumulators from two scan segments of the same epoch (the
    single-process trainer runs an epoch as log-interval-sized segments)."""
    return HealthStats(jnp.minimum(a.loss_min, b.loss_min),
                       jnp.maximum(a.loss_max, b.loss_max),
                       a.loss_sum + b.loss_sum,
                       a.grad_norm_sum + b.grad_norm_sum,
                       jnp.maximum(a.grad_norm_max, b.grad_norm_max))


class GuardSpec(NamedTuple):
    """Static knobs of the numerical guard (``--guard``): the anomaly verdict
    computed INSIDE the compiled step and the replay windows to skip.

    ``zscore``/``rel_floor`` parameterize the spike detector: a step whose
    pre-clip global grad norm exceeds ``ema_mean + zscore * max(ema_std,
    rel_floor * ema_mean)`` is a spike (the floor keeps a near-zero-variance
    warm stream from tripping on ordinary jitter). ``warmup_steps`` clean
    steps must be observed before the z-test arms — non-finite detection is
    always armed. ``ema_decay`` is the detector's window. ``skip`` is the
    static tuple of half-open ``(lo, hi)`` step windows a supervised restart
    replays as identity updates (``--skip-steps``; baked at trace time — each
    restart is a fresh process and compiles anyway)."""

    zscore: float = 8.0
    warmup_steps: int = 4
    ema_decay: float = 0.9
    rel_floor: float = 0.5
    skip: tuple = ()


class GuardState(NamedTuple):
    """The guard's scan-carry accumulators — nine scalars riding the
    ``TrainState`` pytree (an optional field, like ``ema``: absent = zero
    cost, and guard-off checkpoints stay byte-identical). Checkpointing the
    detector state is deliberate: a rollback resumes with the EMA it had at
    the healthy point, so the z-test re-arms exactly where the oracle's
    would — the bitwise-replay contract extends to the guard itself."""

    ema_mean: jax.Array            # EMA of clean pre-clip grad norms
    ema_sq: jax.Array              # EMA of their squares (variance source)
    count: jax.Array               # clean steps folded into the EMA (i32)
    anomalies: jax.Array           # detected anomalies (nonfinite + spikes)
    nonfinite: jax.Array           # non-finite loss/grad verdicts
    spikes: jax.Array              # z-score verdicts
    skipped: jax.Array             # identity updates applied (anomaly + window)
    first_anomaly_step: jax.Array  # -1 until the first anomaly
    last_anomaly_step: jax.Array   # -1 until the first anomaly


def init_guard() -> GuardState:
    # One fresh array per field: the state is donated into the compiled step,
    # and aliased leaves would be the same buffer donated twice.
    f0 = lambda: jnp.zeros((), jnp.float32)
    i0 = lambda: jnp.zeros((), jnp.int32)
    none = lambda: jnp.asarray(-1, jnp.int32)
    return GuardState(f0(), f0(), i0(), i0(), i0(), i0(), i0(), none(), none())


def _grad_poison_fn():
    """Trace-time fold of any armed grad-poison faults (``resilience/faults.py``
    ``nan``/``spike``/``bitflip``) into the step: returns ``None`` (zero added
    ops — the flag-off bitwise pin) unless ``RESILIENCE_FAULTS`` arms a poison
    matching this process. Poison fires at EXACT step equality, so a resumed
    attempt replaying the step reproduces it — determinism is what makes the
    skip set a complete cure."""
    from csed_514_project_distributed_training_using_pytorch_tpu.resilience import (
        faults,
    )

    specs = faults.grad_poisons()
    if not specs:
        return None

    def poison(grads, step):
        for f in specs:
            hit = step == f.step
            if f.kind == "nan":
                grads = jax.tree_util.tree_map(
                    lambda g: jnp.where(hit, jnp.full_like(g, jnp.nan), g),
                    grads)
            elif f.kind == "spike":
                scale = jnp.asarray(f.scale, jnp.float32)
                grads = jax.tree_util.tree_map(
                    lambda g: jnp.where(hit, (g.astype(jnp.float32)
                                              * scale).astype(g.dtype), g),
                    grads)
            else:                          # bitflip: one element of one leaf
                def flip(path, g, f=f):
                    if f.leaf not in jax.tree_util.keystr(path):
                        return g
                    flat = g.reshape(-1)
                    planted = jnp.where(hit, jnp.asarray(f.scale, g.dtype),
                                        flat[0])
                    return flat.at[0].set(planted).reshape(g.shape)

                grads = jax.tree_util.tree_map_with_path(flip, grads)
        return grads

    return poison


class TrainState(NamedTuple):
    """Model + optimizer state as one pytree (params, optimizer state, global step).

    ``velocity`` is the optimizer state: the SGD velocity tree historically (and for
    ``--optimizer sgd`` today), or the AdamW moment state — see the state-shape
    contract in ``ops/optim.py``. The field name stays for checkpoint compatibility.

    ``ema`` is the optional params-shaped exponential-moving-average tree
    (``--ema-decay``); ``None`` (the default, and the reference-parity surface) keeps
    the pytree free of it. It shards exactly like ``params`` under every layout, and
    ``utils.checkpoint.restore_train_state`` reconciles checkpoints written on either
    side of the flag.

    ``guard`` is the optional :class:`GuardState` (``--guard``): nine scalar
    anomaly-detector accumulators that ride the same optional-field contract —
    ``None`` keeps the pytree (and the checkpoint bytes) identical to before
    the guard existed; the restore paths reconcile across the flag exactly
    like ``ema``."""

    params: dict
    velocity: dict
    step: jax.Array  # int32 scalar
    ema: dict | None = None
    guard: GuardState | None = None


def create_train_state(model, rng: jax.Array,
                       sample_input_shape=(1, 28, 28, 1), *,
                       optimizer: Optimizer | None = None,
                       ema: bool = False, guard: bool = False) -> TrainState:
    """Initialize params (PyTorch-default distributions, see ``ops/initializers.py``) and
    zero optimizer state (SGD velocity by default). Under SPMD every process derives
    identical state from the same seed — the replica-consistency analog of DDP's initial
    parameter broadcast (reference ``src/train_dist.py:63``).

    ``ema=True`` seeds the EMA tree as a copy of the initial params (torch
    ``swa_utils.AveragedModel``'s construction-time copy). ``guard=True``
    attaches a fresh :class:`GuardState` (the ``--guard`` anomaly detector)."""
    variables = model.init({"params": rng}, jnp.zeros(sample_input_shape))
    params = variables["params"]
    opt_init = optimizer.init if optimizer is not None else sgd_init
    return TrainState(params=params, velocity=opt_init(params),
                      step=jnp.zeros((), jnp.int32),
                      ema=jax.tree_util.tree_map(jnp.array, params) if ema else None,
                      guard=init_guard() if guard else None)


def make_train_step(model, *, learning_rate: float, momentum: float,
                    use_pallas: bool = False, grad_accum: int = 1,
                    aux_loss_weight: float = 0.01,
                    optimizer: Optimizer | None = None,
                    lr_schedule: Callable | None = None,
                    clip_grad_norm: float = 0.0,
                    ema_decay: float = 0.0,
                    label_smoothing: float = 0.0,
                    loss_fn: Callable | None = None,
                    with_metrics: bool = False,
                    guard: GuardSpec | None = None,
                    loss_has_aux: bool = False,
                    after_update: Callable | None = None) -> Callable:
    """Build ``step(state, images, labels, rng) -> (state, loss)``.

    The loss is the canonical ``nll(log_probs)`` formulation (see
    ``ops.cross_entropy_loss`` for why this also covers the reference's distributed
    CrossEntropyLoss objective). Wrap in ``jax.jit`` (or compile over a mesh via
    ``parallel.data_parallel.compile_step``) before use.

    ``use_pallas=True`` swaps in the fused Pallas loss and optimizer kernels
    (``ops/pallas_kernels.py``) — numerically equivalent to float32 round-off; intended for
    the single-device step path (a Pallas call is an opaque unit to the GSPMD partitioner,
    so the multi-mesh ``compile_epoch`` path keeps the XLA-fused default).

    ``grad_accum=N`` splits the batch into N equal microbatches, accumulates their
    gradients in a ``lax.scan``, and applies ONE optimizer update on the mean — peak
    activation memory shrinks N× while the update equals the full-batch step exactly
    (equal-size microbatch means average to the batch mean; pinned in
    ``tests/test_train_step.py``). Dropout draws a distinct mask per microbatch.

    Models that ``sow`` auxiliary losses into the ``"aux_loss"`` collection (the MoE
    transformer's load-balance term, ``models/transformer.py``) have their sum added to
    the objective scaled by ``aux_loss_weight``; for every other model the collection is
    empty and the term is exactly zero.

    ``optimizer`` (an ``ops.optim.Optimizer``) swaps the update rule — e.g.
    ``optim.adamw(...)``; ``None`` keeps the reference-parity SGD built from
    ``learning_rate``/``momentum``. The state passed in must come from the matching
    ``create_train_state(..., optimizer=...)``.

    ``lr_schedule`` (from ``optim.make_lr_schedule``) maps ``state.step`` to a
    learning-rate multiplier inside the compiled step — warmup/cosine cost zero host
    round-trips. Not supported with ``use_pallas`` (the fused kernel bakes the rate).

    ``clip_grad_norm > 0`` clips the (microbatch-averaged) gradients to that global
    norm before the update, with torch ``clip_grad_norm_`` semantics
    (``optim.clip_by_global_norm``); 0 disables. Under SPMD the clip sees the
    all-reduced global gradient, so every replica scales identically.

    ``ema_decay > 0`` maintains ``state.ema`` — an exponential moving average of the
    params updated INSIDE the compiled step after each optimizer update, with torch
    ``swa_utils.AveragedModel(avg_fn=get_ema_multi_avg_fn(decay))`` semantics (pinned
    against real torch in ``tests/test_optim.py``): the first update copies the fresh
    params, later updates apply ``ema ← decay·ema + (1−decay)·params``. The state must
    come from ``create_train_state(..., ema=True)``.

    ``loss_fn(params, xs, ys, rng) -> scalar`` overrides the classification objective
    entirely (e.g. the LM's next-token loss, ``train/lm.py``) while keeping every
    other mechanism — grad-accum, clipping, schedules, optimizers — unchanged. Not
    supported with ``use_pallas`` (the fused kernels implement the standard loss).

    ``loss_has_aux=True``: ``loss_fn`` returns ``(loss, aux)``, ``aux`` any pytree of
    counters the forward pass hands out (the expert layers' arrived rows,
    ``models/hybrid_lm.py``); the step then returns ``(state, (out, aux))`` with ``out``
    what it would have returned without, and the scanned epoch stacks ``aux`` over
    its steps beside the losses. Microbatches' ``aux`` add up.

    ``after_update(params, aux) -> (params, aux)`` (with ``loss_has_aux``) runs after
    the optimizer's update, on the parameters it wrote and what the forward pass
    handed out, for a rule that moves a leaf by counts and not by a gradient (the
    expert layers' selection bias, ``HybridLM.rebalance``); the ``aux`` it returns is
    the step's. The moving average and a guarded step's skip do not see it.

    ``with_metrics=True`` changes the return to ``(state, (loss, grad_norm))``,
    where ``grad_norm`` is the PRE-clip global L2 norm of the (microbatch-averaged)
    gradients — the ``--health-stats`` signal accumulated by the scanned epoch
    (``HealthStats``). The flag-off path is byte-for-byte the unmetered step: no
    new ops enter the compiled program (pinned in ``tests/test_telemetry.py``),
    and the update math is identical either way (the norm only READS the grads),
    so metered and unmetered training produce bitwise-identical params.

    ``guard`` (a :class:`GuardSpec`) arms the numerical immune system: the step
    computes a fixed-shape anomaly verdict (non-finite loss/grads, grad-norm
    z-score against the EMA threaded through ``state.guard``) and a poisoned
    step deterministically selects the IDENTITY update — params/opt-state/EMA
    unchanged, skip counters bumped, ``step`` still advanced so the data order
    and per-step RNG folds of a run with skips stay aligned with one without.
    Steps inside ``guard.skip`` windows take the identity update without
    counting as anomalies (the supervised-replay contract). The state must
    come from ``create_train_state(..., guard=True)``. ``guard=None`` adds
    zero ops (bitwise flag-off pin), and a guard whose verdict never fires
    selects the freshly-computed update exactly (``jnp.where`` on a false
    predicate is bitwise the false branch) — anomaly-free guard-on training is
    bitwise identical to guard-off.
    """
    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
    if optimizer is None:
        optimizer = sgd(learning_rate, momentum)
    if use_pallas and optimizer.name != "sgd":
        raise ValueError("use_pallas fuses the SGD-momentum update kernel — "
                         f"optimizer {optimizer.name!r} is not supported there")
    if use_pallas and lr_schedule is not None:
        raise ValueError("use_pallas bakes the learning rate into the fused kernel — "
                         "lr_schedule is not supported there")
    if use_pallas and label_smoothing:
        raise ValueError("use_pallas fuses the plain NLL loss kernel — "
                         "label_smoothing is not supported there")
    if use_pallas and loss_fn is not None:
        raise ValueError("use_pallas fuses the standard NLL loss kernel — a custom "
                         "loss_fn is not supported there")
    if use_pallas:
        from csed_514_project_distributed_training_using_pytorch_tpu.ops import (
            pallas_kernels as pk,
        )

    def default_loss_fn(params, images, labels, rng):
        log_probs, variables = model.apply(
            {"params": params}, images, deterministic=False,
            rngs={"dropout": rng}, mutable=["aux_loss"])
        aux_leaves = jax.tree_util.tree_leaves(variables.get("aux_loss", {}))
        aux = (aux_loss_weight * sum(aux_leaves)) if aux_leaves else 0.0
        if use_pallas:
            # log_softmax is idempotent: fused nll-from-logits on log-probs is identical.
            return pk.nll_from_logits(log_probs, labels) + aux
        return ops.nll_loss(log_probs, labels,
                            label_smoothing=label_smoothing) + aux

    if loss_fn is None:
        loss_fn = default_loss_fn

    poison = _grad_poison_fn()

    def apply_update(state, grads, loss):
        if poison is not None:
            # Armed grad-poison injection (deterministic, exact-step) — applied
            # to the (accumulation-averaged) grads BEFORE the norm is measured,
            # so the detector sees exactly what the update would apply.
            grads = poison(grads, state.step)
        # The health-stats grad norm is PRE-clip (clipping must not hide an
        # explosion) — which is exactly the norm the clip computes and returns, so
        # the metered clipped step measures it once.
        gnorm = None
        if clip_grad_norm > 0.0:
            grads, gnorm = clip_by_global_norm(grads, clip_grad_norm)
        elif with_metrics or guard is not None:
            gnorm = global_l2_norm(grads)
        if use_pallas:
            # Hyperparams come from the Optimizer (not this function's kwargs) so an
            # explicitly passed optim.sgd(...) can never silently diverge from what
            # the kernel applies.
            params, velocity = pk.sgd_momentum_step(
                state.params, state.velocity, grads,
                learning_rate=optimizer.hyperparams["learning_rate"],
                momentum=optimizer.hyperparams["momentum"])
        else:
            scale = lr_schedule(state.step) if lr_schedule is not None else 1.0
            params, velocity = optimizer.update(state.params, state.velocity, grads,
                                                lr_scale=scale)
        ema = state.ema
        if ema_decay > 0.0:
            if ema is None:
                raise ValueError("ema_decay needs create_train_state(..., ema=True)")
            # torch AveragedModel.update_parameters: the first call (n_averaged == 0)
            # copies the params; later calls apply the EMA rule. state.step is the
            # pre-increment counter, so it doubles as n_averaged.
            first = state.step == 0
            ema = jax.tree_util.tree_map(
                lambda e, p: jnp.where(first, p,
                                       ema_decay * e + (1.0 - ema_decay) * p),
                ema, params)
        new_guard = state.guard
        if guard is not None:
            if state.guard is None:
                raise ValueError("a guarded step needs "
                                 "create_train_state(..., guard=True)")
            g = state.guard
            loss32 = loss.astype(jnp.float32)
            gnorm32 = gnorm.astype(jnp.float32)
            finite = jnp.isfinite(loss32) & jnp.isfinite(gnorm32)
            # Spike test: deviation from the clean-step EMA, with a relative
            # floor under the std so a flat warm stream's jitter cannot trip.
            std = jnp.sqrt(jnp.maximum(g.ema_sq - g.ema_mean * g.ema_mean, 0.0))
            threshold = g.ema_mean + guard.zscore * jnp.maximum(
                std, guard.rel_floor * g.ema_mean)
            warm = g.count >= guard.warmup_steps
            spike = warm & finite & (gnorm32 > threshold)
            in_window = jnp.zeros((), bool)
            for lo, hi in guard.skip:
                in_window = in_window | ((state.step >= lo) & (state.step < hi))
            # Replay-window steps are deliberate skips, never anomalies — a
            # resumed attempt re-detecting the poison it is skipping would
            # immediately re-trip the --anomaly-exit policy.
            nonfinite = ~finite & ~in_window
            spike = spike & ~in_window
            anomaly = nonfinite | spike
            skip = anomaly | in_window
            # A poisoned/window step selects the IDENTITY update. jnp.where
            # selects exactly (no arithmetic on the unselected branch), so a
            # NaN update can never leak and a clean step is bitwise the
            # unguarded update.
            keep = lambda new, old: jax.tree_util.tree_map(
                lambda n, o: jnp.where(skip, o, n), new, old)
            params = keep(params, state.params)
            velocity = keep(velocity, state.velocity)
            if ema_decay > 0.0:
                ema = keep(ema, state.ema)
            clean = ~skip
            gsafe = jnp.where(finite, gnorm32, 0.0)
            d = jnp.asarray(guard.ema_decay, jnp.float32)
            seeded = g.count > 0   # first clean sample seeds the EMA directly
            new_mean = jnp.where(
                clean, jnp.where(seeded, d * g.ema_mean + (1.0 - d) * gsafe,
                                 gsafe), g.ema_mean)
            new_sq = jnp.where(
                clean, jnp.where(seeded, d * g.ema_sq
                                 + (1.0 - d) * gsafe * gsafe,
                                 gsafe * gsafe), g.ema_sq)
            one = jnp.ones((), jnp.int32)
            zero = jnp.zeros((), jnp.int32)
            new_guard = GuardState(
                ema_mean=new_mean, ema_sq=new_sq,
                count=g.count + jnp.where(clean, one, zero),
                anomalies=g.anomalies + jnp.where(anomaly, one, zero),
                nonfinite=g.nonfinite + jnp.where(nonfinite, one, zero),
                spikes=g.spikes + jnp.where(spike, one, zero),
                skipped=g.skipped + jnp.where(skip, one, zero),
                first_anomaly_step=jnp.where(
                    anomaly & (g.first_anomaly_step < 0),
                    state.step.astype(jnp.int32), g.first_anomaly_step),
                last_anomaly_step=jnp.where(anomaly,
                                            state.step.astype(jnp.int32),
                                            g.last_anomaly_step))
        new_state = TrainState(params, velocity, state.step + 1, ema, new_guard)
        if with_metrics:
            return new_state, (loss, gnorm)
        return new_state, loss

    value_and_grad = jax.value_and_grad(loss_fn, has_aux=loss_has_aux)

    def with_aux(new_state: TrainState, out, aux):
        if after_update is not None:
            params, aux = after_update(new_state.params, aux)
            new_state = new_state._replace(params=params)
        return new_state, (out, aux)

    def step(state: TrainState, images, labels, rng) -> tuple[TrainState, jax.Array]:
        step_rng = jax.random.fold_in(rng, state.step)
        loss, grads = value_and_grad(state.params, images, labels, step_rng)
        # the clip, the update and ``after_update`` under one name on a trace
        # (``utils.profiling.scope_of``): device time outside every model scope
        with jax.named_scope("optimizer"):
            if not loss_has_aux:
                return apply_update(state, grads, loss)
            return with_aux(*apply_update(state, grads, loss[0]), loss[1])

    if grad_accum == 1:
        return step

    def accum_step(state: TrainState, images, labels, rng) -> tuple[TrainState, jax.Array]:
        b = images.shape[0]
        if b % grad_accum:
            raise ValueError(f"batch {b} not divisible by grad_accum {grad_accum}")
        micro = b // grad_accum
        xs = images.reshape((grad_accum, micro) + images.shape[1:])
        ys = labels.reshape(grad_accum, micro)
        step_rng = jax.random.fold_in(rng, state.step)

        def body(carry, chunk):
            grads_sum, loss_sum = carry
            x, y, i = chunk
            loss, grads = value_and_grad(
                state.params, x, y, jax.random.fold_in(step_rng, i))
            loss, aux = loss if loss_has_aux else (loss, None)
            return (jax.tree_util.tree_map(jnp.add, grads_sum, grads),
                    loss_sum + loss), aux

        zeros = jax.tree_util.tree_map(jnp.zeros_like, state.params)
        (grads_sum, loss_sum), aux = lax.scan(
            body, (zeros, jnp.zeros((), jnp.float32)),
            (xs, ys, jnp.arange(grad_accum)))
        with jax.named_scope("optimizer"):
            grads = jax.tree_util.tree_map(lambda g: g / grad_accum, grads_sum)
            new_state, out = apply_update(state, grads, loss_sum / grad_accum)
            if not loss_has_aux:
                return new_state, out
            return with_aux(new_state, out,
                            jax.tree_util.tree_map(lambda a: a.sum(0), aux))

    return accum_step


def make_epoch_fn(model, *, learning_rate: float, momentum: float,
                  use_pallas: bool = False, unroll: int = 1,
                  pregather: bool = False, grad_accum: int = 1,
                  optimizer: Optimizer | None = None,
                  lr_schedule: Callable | None = None,
                  clip_grad_norm: float = 0.0,
                  ema_decay: float = 0.0,
                  label_smoothing: float = 0.0,
                  health: bool = False,
                  guard: GuardSpec | None = None) -> Callable:
    """Build ``epoch(state, images, labels, idx_matrix, rng) -> (state, losses)``.

    ``images``/``labels`` are the full (device-resident) training split; ``idx_matrix`` is a
    ``[num_steps, batch]`` int32 index plan (from ``BatchLoader.epoch_index_matrix`` — the
    sampler output). The scan runs ``num_steps`` optimizer steps with no host round-trip;
    per-step losses come back as one ``[num_steps]`` array for logging, replacing the
    reference's per-step ``loss.item()`` host syncs (``src/train_dist.py:85``).

    ``unroll`` replicates the step body that many times per scan iteration (semantics
    unchanged — SGD stays strictly sequential); on a tiny model, per-iteration control
    overhead can rival the step's compute, and unrolling amortizes it at the cost of
    compile time.

    ``pregather`` (semantics unchanged) gathers the whole epoch's batches ONCE before the
    scan — one big take instead of one small gather per step — and scans over the
    pre-batched arrays; trades HBM (one epoch-sized copy of the split) for per-step
    gather latency.

    ``health=True`` builds the step with ``with_metrics`` and threads
    ``HealthStats`` accumulators through the scan carry; the epoch then returns
    ``(state, (losses, health))`` — same program otherwise, bitwise-identical
    params (pinned in ``tests/test_telemetry.py``).

    ``guard`` (a :class:`GuardSpec`) arms the in-scan anomaly verdict +
    guarded identity update (see ``make_train_step``); the detector state
    rides ``state.guard`` through the carry — no signature change, no extra
    host syncs (the verdict is fetched with the epoch's one sanctioned
    ``state`` read).
    """
    train_step = make_train_step(model, learning_rate=learning_rate, momentum=momentum,
                                 use_pallas=use_pallas, grad_accum=grad_accum,
                                 optimizer=optimizer, lr_schedule=lr_schedule,
                                 clip_grad_norm=clip_grad_norm, ema_decay=ema_decay,
                                 label_smoothing=label_smoothing,
                                 with_metrics=health, guard=guard)
    return make_epoch_from_step(train_step, unroll=unroll, pregather=pregather,
                                health=health)


def make_epoch_from_step(train_step: Callable, *, unroll: int = 1,
                         pregather: bool = False, health: bool = False,
                         aux: bool = False) -> Callable:
    """Wrap any ``step(state, images, labels, rng)`` into the scanned epoch program
    (same contract as ``make_epoch_fn`` — used for alternative step implementations,
    e.g. the LM trainer's next-token step, ``train/lm.py``).

    ``health=True`` expects a step built with ``with_metrics=True`` (returning
    ``(state, (loss, grad_norm))``), carries ``HealthStats`` through the scan, and
    returns ``(state, (losses, health))``.

    ``aux=True`` expects a step built with ``loss_has_aux=True``; the epoch's second
    result is then a tuple that ends in the steps' stacked ``aux``:
    ``(losses, aux)``, or ``(losses, health, aux)`` with ``health``."""

    def epoch(state: TrainState, images, labels, idx_matrix, rng):
        def apply(carry, x, y):
            st, h = carry if health else (carry, None)
            st, out = train_step(st, x, y, rng)
            out, extra = out if aux else (out, None)
            if health:
                loss, gnorm = out
                st, out = (st, update_health(h, loss, gnorm)), loss
            return st, ((out, extra) if aux else out)

        init = (state, init_health()) if health else state

        if pregather:
            def body(carry, batch):
                x, y = batch
                return apply(carry, x, y)

            xs = (jnp.take(images, idx_matrix.reshape(-1), axis=0)
                  .reshape(idx_matrix.shape + images.shape[1:]))
            ys = jnp.take(labels, idx_matrix.reshape(-1),
                          axis=0).reshape(idx_matrix.shape)
            out, losses = lax.scan(body, init, (xs, ys), unroll=unroll)
        else:
            def body(carry, idx):
                return apply(carry, jnp.take(images, idx, axis=0),
                             jnp.take(labels, idx, axis=0))

            out, losses = lax.scan(body, init, idx_matrix, unroll=unroll)

        losses, extra = losses if aux else (losses, None)
        if health:
            out, h = out
            losses = (losses, h)
        if aux:
            losses = (*losses, extra) if health else (losses, extra)
        return out, losses

    return epoch


def make_eval_fn(model, *, batch_size: int = 1000) -> Callable:
    """Build ``evaluate(params, images, labels) -> (sum_nll, num_correct)``.

    Reproduces the reference ``test()`` semantics: deterministic forward, NLL summed over the
    split then divided by its size by the caller (``src/train.py:94-97``), plus argmax
    accuracy (``src/train.py:95-96``). The split size must divide by ``batch_size`` (MNIST
    test: 10,000 / 1,000, reference ``src/train.py:14``).
    """

    def evaluate(params, images, labels):
        n = images.shape[0]
        if n % batch_size:
            raise ValueError(f"eval split size {n} not divisible by eval batch "
                             f"{batch_size} — the tail would be silently dropped while "
                             f"callers divide by the full split size")
        num_batches = n // batch_size
        xs = images[:num_batches * batch_size].reshape(
            (num_batches, batch_size) + images.shape[1:])
        ys = labels[:num_batches * batch_size].reshape(num_batches, batch_size)

        def body(carry, batch):
            x, y = batch
            log_probs = model.apply({"params": params}, x)
            sum_nll, correct = carry
            sum_nll += ops.nll_loss(log_probs, y, reduction="sum")
            correct += jnp.sum(jnp.argmax(log_probs, axis=-1) == y)
            return (sum_nll, correct), None

        (sum_nll, correct), _ = lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)), (xs, ys))
        return sum_nll, correct

    return evaluate
