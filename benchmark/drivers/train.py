"""Driver ``train``: a trainer's ``main`` in-process, whole epochs as the window.

The trainer is named by the configuration's ``train.module`` and built by its
own ``main(config, datasets=...)``: mesh, state, epoch program, telemetry, eval
are the trainer's. The driver needs three things ``main`` does not hand out,
and gets all three at one seam, the compiled epoch program that
``telemetry.aot_compile`` returns to the trainer:

- the weights come from ``--seed`` through the benchmark (``weights.py``), so
  the first call's state carries them instead of the trainer's own init;
- the first call (the warm-up epoch) gives the losses of the first steps, which
  the plain reference follows; the state after one step and after three comes
  from the trainer's same jitted epoch function lowered for a plan of one row
  (a 32-step scan shows no state inside it), whose losses have to agree with
  the timed program's;
- the window ends at an epoch boundary: once ``--seconds`` have passed, the
  next call raises instead of running, and ``main`` unwinds through its own
  ``finally``.

Every later call passes straight through. The rate is the examples of the
whole epochs after the first over the host's clock from the first measured
call to the call that closes the window: everything the trainer's loop does in
between (the epoch program, eval, telemetry, a checkpoint, the next plan) is
in it, and it does not depend on where the stop lands. The time inside the
epoch program alone (telemetry's ``execute_s``) is kept as a per-layer number.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import time

import numpy as np

import compare
import counts
import data
import harness
import weights


class _WindowClosed(Exception):
    pass


def _losses(out):
    """Per-step losses of an epoch call (health stats, when on, ride beside)."""
    import jax
    return np.asarray(jax.device_get(out[0] if isinstance(out, tuple) else out))


class EpochSeam:
    """Stands where the trainer's compiled epoch program stands."""

    def __init__(self, compiled, one_row, ctx, plan_shape):
        self.compiled, self.one_row = compiled, one_row
        self.ctx, self.plan_shape = ctx, plan_shape
        self.calls = 0
        self.first = {}             # what the first call produced, as host numbers
        self.t_first = None
        self.t_run = 0.0            # when the last measured call was handed on
        self.loop_s = []            # per measured epoch: that call -> the next call
        self.trace_on = False
        self.trace_t0 = self.trace_window_s = 0.0
        self.trace_calls = 0
        self.compiles_at_first = None
        self.window_bytes = 0       # the chip's memory as the first measured call starts

    def __call__(self, state, *rest):
        import jax
        ctx = self.ctx
        if self.calls == 0:
            return self._first_call(state, rest)
        now = time.perf_counter()
        if self.calls > 1:
            self.loop_s.append(now - self.t_run)
        if self.calls == 1:
            self.t_first = now
            self.window_bytes = harness.memory_now_bytes()
            self.compiles_at_first = ctx.cache_events["compiles"]
            if ctx.trace:
                jax.profiler.start_trace(os.path.join(ctx.work, "trace"))
                self.trace_on, self.trace_t0 = True, time.perf_counter()
        if self.trace_on and self.calls == 1 + int(ctx.mix.get("trace_epochs", 2)):
            self.trace_window_s = time.perf_counter() - self.trace_t0
            jax.profiler.stop_trace()       # takes seconds: not part of the window
            self.trace_on = False
            self.trace_calls = self.calls - 1
        if not self.trace_on and now - self.t_first >= ctx.seconds:
            raise _WindowClosed()
        self.calls += 1
        self.t_run = time.perf_counter()    # after the profiler's start and stop
        return self.compiled(state, *rest)

    def _first_call(self, state, rest):
        """The checked steps, then the warm-up epoch. Both start from the
        benchmark's seeded weights. The trainer's own jitted epoch function,
        lowered for a plan of one row, is driven three times: the state after
        the first gives the first gradient as the optimizer got it, the state
        after the third the parameters' change. Then the timed program itself
        runs its first call from the same weights; its first losses are the
        ones compared, and have to agree with the one-row program's."""
        import jax
        import jax.numpy as jnp
        plan = rest[2]
        if tuple(plan.shape) != tuple(self.plan_shape):
            raise harness.Refused(f"the epoch program's fourth argument is "
                                  f"{plan.shape}, not the plan {self.plan_shape}")
        ref_train = harness.load_reference(self.ctx.bench, "train")
        shardings = jax.tree_util.tree_map(lambda x: x.sharding, state.params)
        fresh = lambda: jax.device_put(weights.make(state.params, self.ctx.seed),
                                       shardings)
        host_plan = np.asarray(jax.device_get(plan))
        self.first["plan"] = host_plan
        probe = jax.tree_util.tree_map(jnp.copy, state)._replace(params=fresh())
        steps, losses = int(self.ctx.cell.get("loss_steps", 3)), []
        for k in range(steps):
            row = jax.device_put(host_plan[k:k + 1], plan.sharding)
            probe, out = self.one_row(probe, rest[0], rest[1], row, *rest[3:])
            losses.append(float(_losses(out)[0]))
            if k == 0:
                # the optimizer's first moment (AdamW keeps {"m", "v", "count"})
                self.first["moment_norms"] = ref_train.leaf_norms(probe.velocity["m"])
        diff = jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))
        moved = lambda params: ref_train.leaf_norms(diff(params, fresh()))
        self.first["delta_norms"] = moved(probe.params)
        self.first["one_row_losses"] = losses
        del probe
        new_state, out = self.compiled(state._replace(params=fresh()), *rest)
        self.first["losses"] = [float(x) for x in _losses(out)]
        # how far the timed program's own first call moved the parameters
        self.first["moved"] = sum(v * v for v in moved(new_state.params).values()) ** 0.5
        self.calls = 1
        return new_state, out


def _install_seam(ctx, plan_shape):
    """Wrap ``telemetry.aot_compile`` so the trainer receives the seam."""
    from csed_514_project_distributed_training_using_pytorch_tpu.utils import (
        telemetry as T,
    )
    original = T.aot_compile
    holder = {}

    def aot_compile(jit_fn, *args):
        compiled, aot = original(jit_fn, *args)
        if compiled is None:
            raise harness.Refused("the trainer's epoch program did not compile "
                                  "ahead of time; the driver has no seam")
        import jax
        row = jax.ShapeDtypeStruct((1, plan_shape[1]), args[3].dtype)
        one_row = jit_fn.lower(*args[:3], row, *args[4:]).compile()
        holder["seam"] = EpochSeam(compiled, one_row, ctx, plan_shape)
        return holder["seam"], aot

    T.aot_compile = aot_compile
    return holder, lambda: setattr(T, "aot_compile", original)


def _splits(ctx, n_train: int, n_test: int, num_levels: int | None) -> tuple[dict, dict]:
    out = []
    for n, salt in ((n_train, 1), (n_test, 2)):
        u8, labels = data.images_u8(n, (ctx.seed * 2 + salt) % (2 ** 63))
        split = {"images": data.normalize(u8), "labels": labels}
        if num_levels:
            split["tokens"] = data.pixel_tokens(split["images"], num_levels)
        out.append(split)
    return out[0], out[1]


def run(ctx) -> harness.Observations:
    import jax
    from csed_514_project_distributed_training_using_pytorch_tpu.data import mnist
    from csed_514_project_distributed_training_using_pytorch_tpu.utils import (
        config as config_mod,
    )

    spec, mix = ctx.config["train"], ctx.mix
    batch, steps = int(mix["batch"]), int(mix["steps_per_epoch"])
    n_train = int(mix.get("train_examples", batch * steps))
    train, test = _splits(ctx, n_train, int(mix["test_examples"]),
                          ctx.config["model"].get("num_levels"))
    to_ds = lambda s: mnist.Dataset(s["images"], s["labels"], "benchmark-seeded")
    tele_path = os.path.join(ctx.work, "telemetry.jsonl")
    args = dict(spec["args"], **mix.get("trainer_args", {}))
    args.setdefault("results_dir", os.path.join(ctx.work, "results"))
    args.update(seed=ctx.seed % (2 ** 31), telemetry=tele_path, epochs=10 ** 6,
                images_dir=os.path.join(ctx.work, "images"))
    config = getattr(config_mod, spec["config_class"])(**args)
    trainer = importlib.import_module(spec["module"])
    if ctx.control:
        return _control(ctx, train, steps, batch)
    holder, uninstall = _install_seam(ctx, (steps, batch))
    try:
        trainer.main(config, datasets=(to_ds(train), to_ds(test)))
        raise harness.Refused("the trainer returned before the window closed")
    except _WindowClosed:
        pass
    finally:
        uninstall()
    seam = holder["seam"]
    obs = harness.Observations()
    obs.memory_peak_bytes = harness.memory_peak_bytes([seam.window_bytes])
    print(f"memory: {seam.window_bytes} bytes as the first measured call starts (arrays "
          f"alive + the runtime's reservation for the programs' temporaries); the "
          f"allocator's peak of arrays alone {harness.memory_peak_bytes()}")
    obs.t_first = seam.t_first
    with open(tele_path) as fh:
        events = [json.loads(line) for line in fh if line.strip()]
    measured = [e for e in events if e.get("event") == "epoch"][1:]
    if len(measured) != len(seam.loop_s):
        raise harness.Refused(f"{len(measured)} epoch events after the first for "
                              f"{len(seam.loop_s)} measured calls")
    if ctx.trace:               # the traced epochs are the traced run's window
        measured = measured[:seam.trace_calls]
    for event, loop_s in zip(measured, seam.loop_s):
        event["loop_s"] = loop_s
    obs.epochs = measured
    obs.window_s = sum(e["loop_s"] for e in measured)
    obs.attempted = len(measured)
    obs.failed = 0
    examples = sum(e["examples"] for e in measured)
    busy = sum(e["execute_s"] + e["data_s"] for e in measured)
    obs.end_to_end["train_examples_per_s"] = examples / obs.window_s
    obs.counters.update(examples=examples, execute_data_s=busy)
    obs.counters.update(ctx.cache_events)
    obs.counters["compile_cache_misses"] = ctx.cache_events["cache_misses"]
    obs.counters["window_compiles"] = \
        ctx.cache_events["compiles"] - seam.compiles_at_first
    obs.counters["steps"] = sum(e["steps"] for e in measured)
    obs.trace_dir = os.path.join(ctx.work, "trace")
    obs.trace_window_s = seam.trace_window_s
    obs.trace_units = {"steps": seam.trace_calls * steps,
                       "examples": seam.trace_calls * steps * batch}
    obs.shapes = {"batch": batch, "steps_per_epoch": steps}
    if "flops_per_example" in spec:     # configurations that state their FLOP count
        flops = getattr(counts, spec["flops_per_example"])(ctx.config["model"])
        obs.shapes.update(flops_per_example=flops, flops_per_step=flops * batch)
    print(f"train: {len(measured)} measured epochs of {steps} steps x {batch}, "
          f"{examples} examples in {obs.window_s:.3f} s of wall "
          f"({busy:.3f} s of execute+data); first-epoch loss "
          f"{seam.first['losses'][0]:.4f} -> {seam.first['losses'][-1]:.4f}, "
          f"last epoch {measured[-1]['train_loss']:.4f}")
    obs.checks = _check(ctx, seam.first, train, measured[-1]["train_loss"])
    obs.checks.append(("window_compiles", float(obs.counters["window_compiles"]), 0.0))
    return obs


def reference_follow(ctx, plan: np.ndarray, train: dict, precision: str) -> dict:
    """The plain reference through the first call's batches, from the same
    seeded weights, at ``precision``."""
    import jax
    import jax.numpy as jnp
    ref = harness.load_reference(ctx.bench, ctx.config["reference"])
    ref_train = harness.load_reference(ctx.bench, "train")
    model = ctx.config["model"]
    template = ref.param_shapes(model)
    params0 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                     weights.make(template, ctx.seed))
    loss_fn = lambda p, b: ref.loss(p, b, model, precision=precision)
    steps = int(ctx.cell.get("loss_steps", 3))
    batches = [ref.batch_of(train, rows) for rows in plan[:steps]]
    return ref_train.follow(loss_fn, params0, batches, ctx.config["train"]["optimizer"])


def _control(ctx, train, steps, batch) -> harness.Observations:
    """No program: the reference at the precision below the stated one stands
    in its place, on the trainer's own kind of feed (a seeded permutation)."""
    import jax
    plan = np.random.default_rng(ctx.seed).permutation(steps * batch) \
        .reshape(steps, batch).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        low = reference_follow(ctx, plan, train, ctx.config["train"]["control_precision"])
    obs = harness.Observations()
    obs.t_first = time.perf_counter()
    obs.end_to_end["train_examples_per_s"] = float("nan")
    obs.checks = _check(ctx, dict(low, plan=plan), train, low["losses"][-1])
    return obs


def _check(ctx, first: dict, train, last_loss: float) -> list:
    """``first``: what the program's first steps gave (losses, norms, plan)."""
    limits = ctx.cell["limits"]
    import jax
    with jax.default_matmul_precision("highest"):
        ref = reference_follow(ctx, first["plan"], train, "highest")
    head = len(ref["losses"])
    values = {
        "loss_gap": compare.worst_relative(first["losses"][:head], ref["losses"]),
        "one_row_loss_gap": compare.worst_relative(
            first.get("one_row_losses", first["losses"][:head]), first["losses"][:head]),
        "moment_norm_gap": compare.worst_leaf_gap(first["moment_norms"],
                                                  ref["moment_norms"]),
        "delta_norm_gap": compare.worst_leaf_gap(first["delta_norms"],
                                                 ref["delta_norms"]),
        "loss_not_falling": last_loss / first["losses"][0],
    }
    if "moved" in first:
        # a timed program that hands its state back unchanged moves nothing:
        # three steps' change over the whole first call's (inf when that is 0)
        three = sum(v * v for v in first["delta_norms"].values()) ** 0.5
        values["state_unmoved"] = three / first["moved"] if first["moved"] else float("inf")
    print(f"reference: losses {['%.5f' % x for x in ref['losses']]}; program "
          f"{['%.5f' % x for x in first['losses'][:head]]}")
    return [(name, float(values[name]), float(limits[name])) for name in limits
            if name in values]
