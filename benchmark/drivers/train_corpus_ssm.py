"""Driver ``train_corpus_ssm``: the ``train_corpus`` driver for a model whose layers
are one sublayer each, some of them state-space scans.

Everything of a run is the ``train_corpus`` driver's (and through it the ``train``
driver's), loaded from its file and not copied. This file adds what that driver
cannot hand a reducer for such a cell:

- **the first expert layer.** ``train_corpus`` reads ``num_dense_layers`` as the index
  of the first sparse layer (its ``routing:`` line); a stack built from
  ``hybrid_override_pattern`` has no such key, so the model's view gains it: the
  index of the first expert layer among the kept ones, by the reference's own
  ``kinds``. The program ignores the key.
- **the scan's work.** ``ssd_scan_train_flops``: the scan kernels' counted FLOPs
  (``train.flops.scan_per_example`` of the configuration's counts file) of the
  examples the measured (or traced) epochs trained, for ``ssd_scan_roofline_share``.
- **the reference's memory.** ``reference/train.py``'s ``follow`` keeps the seeded
  weights beside the state to subtract them at the end: 2.0 GB that the reference's
  6.1 GB of state and 8.4 GB of temporaries (two gradients of 2.0 GB among them)
  leave no room for on a 15.75 GB chip. ``reference_follow`` here drives the same
  ``make_step`` and ``leaf_norms`` and regenerates the seeded values inside the
  subtraction, as ``train_corpus``'s first call does for the trainer.
- **the expert rows' bound.** A token can send a held expert at most one row, so the
  static bound on arrived rows is ``min(k, held) · T`` a layer, not ``k · T``: the
  counter ``expert_row_bound`` is scaled to it.
- **the selection bias's rule.** Where the file gives ``moe_router_bias_update_rate``
  the reference's step is ``balanced_step``: ``reference/train.py``'s ``make_step``
  written out for a loss that also hands out the routers' load (that one differentiates
  a loss of one result), then the reference's own ``rebalanced``. The rule is what takes
  the seed out of the cell's rate: with a fixed bias the rows that arrive at the held
  experts, and the step's time with them, followed the seeded router (PERF.md, PR 30).
"""

from __future__ import annotations

import os

import harness
import weights

corpus = harness.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                          "train_corpus.py"),
                             "bench_driver_train_corpus_for_ssm")


def balanced_step(ref, ref_train, model: dict, precision: str, opt: dict):
    """``reference/train.py``'s ``make_step`` (AdamW with torch semantics behind
    global-norm clipping, the same constants), for ``ref.loss(..., with_load=True)``:
    after the optimizer the selection biases move by the load of the step's forward
    pass."""
    import jax
    import jax.numpy as jnp
    if opt["name"] != "adamw":
        raise ValueError(f"unknown optimizer {opt['name']!r}")
    lr, clip = opt["learning_rate"], opt.get("clip_grad_norm", 0.0)
    b1, b2, eps = opt.get("b1", 0.9), opt.get("b2", 0.999), opt.get("eps", 1e-8)
    wd = opt.get("weight_decay", 0.0)
    tmap = jax.tree_util.tree_map

    def step(params, state, batch, count):
        (value, load), grads = jax.value_and_grad(
            lambda p: ref.loss(p, batch, model, precision=precision, with_load=True),
            has_aux=True)(params)
        if clip:
            scale = jnp.minimum(1.0, clip / (ref_train._global_norm(grads) + 1e-6))
            grads = tmap(lambda g: g * scale, grads)
        c = count.astype(jnp.float32) + 1.0
        m = tmap(lambda a, g: b1 * a + (1 - b1) * g, state["m"], grads)
        v = tmap(lambda a, g: b2 * a + (1 - b2) * g * g, state["v"], grads)
        bc1, bc2 = 1 - b1 ** c, 1 - b2 ** c
        params = tmap(lambda p, a, b: p - lr * ((a / bc1) / (jnp.sqrt(b / bc2) + eps) + wd * p),
                      params, m, v)
        return ref.rebalanced(params, load, model), {"m": m, "v": v}, value

    return jax.jit(step, donate_argnums=(0, 1))


def reference_follow(ctx, plan, train: dict, precision: str) -> dict:
    """What ``train.reference_follow`` returns, holding one copy of the weights."""
    import jax
    import jax.numpy as jnp
    ref = harness.load_reference(ctx.bench, ctx.config["reference"])
    ref_train = harness.load_reference(ctx.bench, "train")
    model, opt = ctx.config["model"], ctx.config["train"]["optimizer"]
    template = ref.param_shapes(model)
    if model.get("moe_router_bias_update_rate"):
        step = balanced_step(ref, ref_train, model, precision, opt)
    else:
        step = ref_train.make_step(
            lambda p, b: ref.loss(p, b, model, precision=precision), opt)
    params = weights.make(template, ctx.seed)
    state = {"m": jax.tree_util.tree_map(jnp.zeros_like, params),
             "v": jax.tree_util.tree_map(jnp.zeros_like, params)}
    losses, moment_norms = [], None
    for i, rows in enumerate(plan[:int(ctx.cell.get("loss_steps", 3))]):
        params, state, value = step(params, state, ref.batch_of(train, rows), jnp.int32(i))
        losses.append(float(value))
        if i == 0:
            moment_norms = ref_train.leaf_norms(state["m"])
    del state
    moved_by = jax.jit(lambda params: jax.tree_util.tree_map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), params,
        weights.make(template, ctx.seed)))
    return {"losses": losses, "moment_norms": moment_norms,
            "delta_norms": ref_train.leaf_norms(moved_by(params))}


def run(ctx) -> harness.Observations:
    ref = harness.load_reference(ctx.bench, ctx.config["reference"])
    plain_view, plain_follow = corpus._model_view, corpus.base.reference_follow

    def model_view(config: dict) -> dict:
        view = plain_view(config)
        return dict(view, num_dense_layers=ref.kinds(view).index("moe"))

    corpus._model_view, corpus.base.reference_follow = model_view, reference_follow
    try:
        obs = corpus.run(ctx)
    finally:
        corpus._model_view, corpus.base.reference_follow = plain_view, plain_follow
    if ctx.control:
        return obs
    view, spec = plain_view(ctx.config), ctx.config["train"]["flops"]
    counts = harness.load_module(os.path.join(ctx.bench, spec["module"] + ".py"),
                                 "bench_" + spec["module"])
    scan = getattr(counts, spec["scan_per_example"])(view, int(ctx.mix["seq_len"]))
    obs.counters["ssd_scan_train_flops"] = scan * obs.counters["examples"]
    if "expert_row_bound" in obs.counters:
        k = view["num_experts_per_tok"]
        obs.counters["expert_row_bound"] *= min(k, view["n_routed_experts"]) / k
    return obs
