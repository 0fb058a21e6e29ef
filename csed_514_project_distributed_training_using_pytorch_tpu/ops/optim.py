"""Optimizers as pure pytree transforms: SGD-momentum (the parity surface) + AdamW.

SGD reproduces ``torch.optim.SGD(lr, momentum)`` semantics exactly (reference
``src/train.py:60-61`` lr=0.01 mom=0.5; ``src/train_dist.py:66`` lr=0.02 mom=0.5), i.e. the
torch update with no dampening/nesterov/weight-decay:

    v <- momentum * v + g
    p <- p - lr * v

(Torch initializes the buffer to the first gradient; starting from v=0 gives the identical
sequence since ``momentum*0 + g == g``.) Implemented first-party rather than via optax to keep
the update rule explicit and dependency-free.

AdamW (beyond-parity — the reference's only optimizer is SGD) reproduces
``torch.optim.AdamW`` semantics (decoupled weight decay, bias correction) and is pinned
against real torch in ``tests/test_optim.py``:

    t <- t + 1
    m <- b1*m + (1-b1)*g          v <- b2*v + (1-b2)*g²
    p <- p - lr*(m/(1-b1^t) / (sqrt(v/(1-b2^t)) + eps) + weight_decay*p)

State-shape contract (what keeps every sharding/checkpoint path working unchanged):
``TrainState.velocity`` holds the optimizer state. For SGD it is a params-congruent
velocity tree (the historical layout — old checkpoints restore as-is). For AdamW it is
``{"m": <params tree>, "v": <params tree>, "count": int32 scalar}`` — each moment subtree
is params-congruent, so the path/shape-driven partition-spec rules (``tensor_parallel``,
``fsdp``) derive the SAME shardings for the moments as for their parameters (ZeRO-style)
without pairing against the params tree; only code that restructures the state wholesale
(the pipeline stack/unstack bridge) needs ``map_param_trees`` below.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


class Optimizer(NamedTuple):
    """``(init, update, name, hyperparams)``: ``init(params) -> opt_state``;
    ``update(params, opt_state, grads) -> (new_params, new_opt_state)``.
    ``hyperparams`` records the constructor knobs — consumers that re-implement the
    update (the fused Pallas SGD kernel path) read them from here so they can never
    diverge from what the ``update`` closure applies."""

    init: Callable
    update: Callable
    name: str
    hyperparams: dict


def sgd_init(params):
    """Zero velocity buffers, one per parameter leaf (the torch momentum_buffer analog)."""
    return jax.tree_util.tree_map(jnp.zeros_like, params)


def sgd_update(params, velocity, grads, *, learning_rate: float, momentum: float):
    """One SGD-momentum step; returns (new_params, new_velocity)."""
    new_velocity = jax.tree_util.tree_map(
        lambda v, g: momentum * v + g, velocity, grads)
    new_params = jax.tree_util.tree_map(
        lambda p, v: p - learning_rate * v, params, new_velocity)
    return new_params, new_velocity


def sgd(learning_rate: float, momentum: float) -> Optimizer:
    """The reference's optimizer as an ``Optimizer`` pair (state = velocity tree).

    ``update(..., lr_scale=s)`` applies a step-dependent multiplier to the learning
    rate only (torch ``lr_scheduler`` semantics: the velocity accumulates RAW
    gradients; the rate applies at the parameter write)."""

    def update(params, velocity, grads, *, lr_scale=1.0):
        return sgd_update(params, velocity, grads,
                          learning_rate=learning_rate * lr_scale,
                          momentum=momentum)

    return Optimizer(init=sgd_init, update=update, name="sgd",
                     hyperparams={"learning_rate": learning_rate,
                                  "momentum": momentum})


def adamw_init(params):
    """Zero first/second moments + step count (torch ``state['step']`` analog)."""
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"m": zeros(), "v": zeros(), "count": jnp.zeros((), jnp.int32)}


def adamw(learning_rate: float, *, b1: float = 0.9, b2: float = 0.999,
          eps: float = 1e-8, weight_decay: float = 0.0) -> Optimizer:
    """AdamW with torch semantics (decoupled decay; bias-corrected moments)."""

    def update(params, opt_state, grads, *, lr_scale=1.0):
        count = opt_state["count"] + 1
        c = count.astype(jnp.float32)
        m = jax.tree_util.tree_map(lambda m_, g: b1 * m_ + (1.0 - b1) * g,
                                   opt_state["m"], grads)
        v = jax.tree_util.tree_map(lambda v_, g: b2 * v_ + (1.0 - b2) * g * g,
                                   opt_state["v"], grads)
        bc1 = 1.0 - jnp.power(b1, c)
        bc2 = 1.0 - jnp.power(b2, c)

        def leaf(p, m_, v_):
            step_dir = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
            # lr_scale multiplies the whole scheduled rate — including the decoupled
            # decay term, matching torch AdamW under an lr_scheduler (decay is
            # p -= lr_t * weight_decay * p there too).
            return p - learning_rate * lr_scale * (step_dir + weight_decay * p)

        new_params = jax.tree_util.tree_map(leaf, params, m, v)
        return new_params, {"m": m, "v": v, "count": count}

    return Optimizer(init=adamw_init, update=update, name="adamw",
                     hyperparams={"learning_rate": learning_rate, "b1": b1,
                                  "b2": b2, "eps": eps,
                                  "weight_decay": weight_decay})


def freeze(optimizer: Optimizer, is_frozen: Callable) -> Optimizer:
    """``optimizer`` with the leaves whose path ``is_frozen(path)`` names held as they
    are: out of the update and of its weight decay (a constant that sits in the
    parameter tree so that checkpoints and seeded weights carry it, such as an expert
    layer's selection bias). Their moments stay whatever the gradient makes of them."""

    def update(params, opt_state, grads, **kwargs):
        new_params, new_state = optimizer.update(params, opt_state, grads, **kwargs)
        return jax.tree_util.tree_map_with_path(
            lambda path, old, new: old if is_frozen(path) else new,
            params, new_params), new_state

    return optimizer._replace(update=update)


def make_optimizer(name: str, *, learning_rate: float, momentum: float,
                   weight_decay: float = 0.0) -> Optimizer:
    """CLI-name → ``Optimizer`` (the trainers' ``--optimizer`` surface)."""
    if name == "sgd":
        if weight_decay:
            raise ValueError("--weight-decay is an AdamW knob — the reference-parity "
                             "SGD has none (reference src/train.py:60-61)")
        return sgd(learning_rate, momentum)
    if name == "adamw":
        return adamw(learning_rate, weight_decay=weight_decay)
    raise ValueError(f"unknown optimizer {name!r} — choose 'sgd' or 'adamw'")


def global_l2_norm(tree) -> jax.Array:
    """Global L2 norm of a pytree in f32 (torch ``clip_grad_norm_``'s norm). The ONE
    owner of the formula — the clip below, the health-stats grad norm
    (``train/step.py``), and the telemetry param norm (``utils/telemetry.py``) all
    reduce through it, so they can never drift apart."""
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in leaves))


def clip_by_global_norm(grads, max_norm: float, *, eps: float = 1e-6):
    """Global-norm gradient clipping with ``torch.nn.utils.clip_grad_norm_``'s exact
    semantics (including its ``eps`` in the denominator): returns
    ``(clipped_grads, global_norm)``. Grads are scaled by
    ``min(1, max_norm / (norm + eps))`` — a no-op whenever the norm is within bounds.
    Pinned against real torch in ``tests/test_optim.py``."""
    gnorm = global_l2_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (gnorm + eps))
    return jax.tree_util.tree_map(lambda g: g * scale, grads), gnorm


def make_lr_schedule(name: str, *, warmup_steps: int = 0,
                     total_steps: int = 0) -> Callable | None:
    """Step → learning-rate multiplier in (0, 1], traced inside the compiled step.

    - ``"constant"``: 1.0, with an optional linear warmup ramp over the first
      ``warmup_steps`` updates (scale ``(step+1)/warmup_steps``, so step 0 trains at
      ``1/warmup_steps`` rather than 0 — torch LambdaLR convention for a ramp that
      never multiplies by zero).
    - ``"cosine"``: the warmup ramp, then cosine decay from 1 → 0 across the
      remaining ``total_steps - warmup_steps`` updates (the standard half-period
      schedule); requires ``total_steps > warmup_steps``.

    Returns ``None`` for a warmup-free constant schedule so callers can skip the
    multiply entirely (the hot-loop fast path stays untouched).
    """
    if warmup_steps < 0:
        raise ValueError(f"warmup_steps must be >= 0, got {warmup_steps}")

    def ramp(step):
        s = step.astype(jnp.float32)
        return jnp.minimum(1.0, (s + 1.0) / warmup_steps)

    if name == "constant":
        return ramp if warmup_steps > 0 else None
    if name == "cosine":
        if total_steps <= warmup_steps:
            raise ValueError(
                f"cosine schedule needs total_steps > warmup_steps, got "
                f"{total_steps} <= {warmup_steps}")

        def sched(step):
            s = step.astype(jnp.float32)
            t = jnp.clip((s - warmup_steps) / (total_steps - warmup_steps), 0.0, 1.0)
            cos = 0.5 * (1.0 + jnp.cos(jnp.pi * t))
            return (ramp(step) if warmup_steps > 0 else 1.0) * cos

        return sched
    raise ValueError(f"unknown lr schedule {name!r} — choose 'constant' or 'cosine'")


def is_adam_state(opt_state) -> bool:
    """True for the AdamW moment-state layout (see the module docstring contract)."""
    return isinstance(opt_state, dict) and set(opt_state) == {"m", "v", "count"}


def map_param_trees(opt_state, fn: Callable, scalar_fn: Callable | None = None):
    """Apply ``fn`` to every params-congruent subtree of an optimizer state.

    SGD state IS one params-congruent tree → ``fn(state)``. AdamW state maps ``fn``
    over the two moment trees and ``scalar_fn`` (default: identity) over the count —
    the single seam that lets structure-rewriting code (the pipeline stack/unstack
    bridge, the stacked-layout shardings) stay optimizer-agnostic.
    """
    if is_adam_state(opt_state):
        keep = scalar_fn if scalar_fn is not None else (lambda x: x)
        return {"m": fn(opt_state["m"]), "v": fn(opt_state["v"]),
                "count": keep(opt_state["count"])}
    return fn(opt_state)
