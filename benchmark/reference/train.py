"""Plain optimizer steps: follow a loss through its first steps.

AdamW with torch semantics (decoupled decay, bias-corrected moments, eps
outside the root) behind global-norm clipping with torch's eps of 1e-6.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(tree)))


def make_step(loss_fn, opt: dict):
    """``step(params, state, batch, count) -> (params, state, loss)``; ``state``
    is AdamW's ``{"m", "v"}``."""
    if opt["name"] != "adamw":
        raise ValueError(f"unknown optimizer {opt['name']!r}")
    lr = opt["learning_rate"]
    clip = opt.get("clip_grad_norm", 0.0)

    def step(params, state, batch, count):
        value, grads = jax.value_and_grad(loss_fn)(params, batch)
        if clip:
            scale = jnp.minimum(1.0, clip / (_global_norm(grads) + 1e-6))
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        b1, b2, eps = opt.get("b1", 0.9), opt.get("b2", 0.999), opt.get("eps", 1e-8)
        wd = opt.get("weight_decay", 0.0)
        c = count.astype(jnp.float32) + 1.0
        m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, state["m"], grads)
        v = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, state["v"], grads)
        bc1, bc2 = 1 - b1 ** c, 1 - b2 ** c
        params = jax.tree_util.tree_map(
            lambda p, a, b: p - lr * ((a / bc1) / (jnp.sqrt(b / bc2) + eps) + wd * p),
            params, m, v)
        return params, {"m": m, "v": v}, value

    return jax.jit(step, donate_argnums=(0, 1))


def leaf_norms(tree) -> dict[str, float]:
    """``{"a/b/c": l2 norm}`` for every leaf."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = jax.device_get([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                            for _, x in flat])
    keys = ["/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in p)
            for p, _ in flat]
    return {k: float(n) for k, n in zip(keys, norms)}


def follow(loss_fn, params0, batches, opt: dict) -> dict:
    """Run ``len(batches)`` steps from ``params0``. Returns each step's loss,
    the per-leaf norm of the optimizer's first moment after the first step
    (the first gradient as the optimizer got it, times ``1 - b1`` under
    AdamW), and of the parameters' change after the last."""
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params0)
    state = {"m": zeros(), "v": zeros()}
    step = make_step(loss_fn, opt)
    params = jax.tree_util.tree_map(jnp.copy, params0)
    losses, moment_norms = [], None
    for i, batch in enumerate(batches):
        params, state, value = step(params, state, batch, jnp.int32(i))
        losses.append(value)
        if i == 0:
            moment_norms = leaf_norms(state["m"])
    delta = jax.tree_util.tree_map(lambda a, b: a - b, params, params0)
    return {"losses": [float(x) for x in jax.device_get(losses)],
            "moment_norms": moment_norms,
            "delta_norms": leaf_norms(delta)}
