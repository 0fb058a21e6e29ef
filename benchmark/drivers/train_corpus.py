"""Driver ``train_corpus``: the ``train`` driver for a model that comes from its
configuration file and trains on a token corpus (``train.lm`` with ``model_config``
and ``corpus``), at a size whose train state fills half the chip.

Everything of a run is the ``train`` driver's, loaded from its file and not copied:
the seam at ``telemetry.aot_compile``, the window, the observations, ``_check`` and
the control. This file adds what that driver cannot do for such a cell:

- **the feed.** It draws the token sequences from ``--seed`` (ids from a Zipf law
  over the vocabulary slice, in a seeded rank order), writes them into the run's
  work directory as a corpus of the layout ``data/stream.py`` reads, and hands the
  trainer ``corpus=``. What the reference follows is what the trainer fed: the seam
  reads the device token array of the first call, holds it to being rows of that
  corpus, and the plan's rows index it.
- **memory.** Two train states do not fit beside the programs, so the first call
  (``FrugalSeam._first_call``) drives the checked one-row steps on the trainer's
  own state with the seeded weights in place, reads the norms with the seeded
  weights regenerated inside the reduction, and zeroes that state in place for the
  timed program. The reference (7.5 GB of state too) runs after the trainer has
  unwound and its programs are unloaded, so the two never stand on the chip
  together.
- **counts.** FLOPs come from ``counts_lfm2_moe.py`` (named by the configuration's
  ``train.flops``); the expert layers' arrived rows come out of the measured
  ``epoch`` events into the counters the ``expert layer`` metrics read.
- **routing.** It prints the share of the first sparse layer's assignments that the
  program (at its stated precision) and the reference (float32) choose differently
  on the first batch: near-tied fourth experts, which the limits do not hide.
"""

from __future__ import annotations

import dataclasses
import gc
import hashlib
import io
import json
import os

import numpy as np

import harness
import weights

base = harness.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                        "train.py"), "bench_driver_train_for_corpus")

_RUN: dict = {}        # this run's feed and seam, for the hooks below


def zipf_sequences(n: int, seq_len: int, vocab: int, exponent: float, seed: int):
    """``[n, seq_len]`` ids: rank r drawn with probability ∝ r^-exponent, ranks
    mapped to ids by a seeded permutation."""
    rng = np.random.default_rng(seed)
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -exponent
    ranks = rng.choice(vocab, size=(n, seq_len), p=p / p.sum())
    return rng.permutation(vocab)[ranks].astype(np.uint16)


def write_corpus(folder: str, train: np.ndarray, test: np.ndarray, vocab: int) -> None:
    """A corpus directory as ``data/stream.py`` documents it: one train shard, the
    eval split, ``corpus.json`` with their sha256."""
    os.makedirs(folder, exist_ok=True)

    def save(name, arr):
        buf = io.BytesIO()
        np.save(buf, arr, allow_pickle=False)
        with open(os.path.join(folder, name), "wb") as fh:
            fh.write(buf.getvalue())
        return {"file": name, "sequences": int(len(arr)),
                "sha256": hashlib.sha256(buf.getvalue()).hexdigest()}

    meta = {"version": 1, "tokenizer": "benchmark-zipf", "vocab": int(vocab),
            "seq_len": int(train.shape[1]), "shards": [save("shard_0000.npy", train)],
            "eval": save("eval.npy", test)}
    with open(os.path.join(folder, "corpus.json"), "w") as fh:
        json.dump(meta, fh)


class FrugalSeam(base.EpochSeam):
    """The ``train`` driver's seam with a first call that holds one train state."""

    def _first_call(self, state, rest):
        import jax
        import jax.numpy as jnp
        ctx, plan = self.ctx, rest[2]
        if tuple(plan.shape) != tuple(self.plan_shape):
            raise harness.Refused(f"the epoch program's fourth argument is "
                                  f"{plan.shape}, not the plan {self.plan_shape}")
        _RUN["seam"] = self
        ref_train = harness.load_reference(ctx.bench, "train")
        fed = np.asarray(jax.device_get(rest[0]))
        known = {row.tobytes() for row in _RUN["corpus_rows"]}
        if fed.shape != _RUN["corpus_rows"].shape or any(
                row.astype(np.uint16).tobytes() not in known for row in fed):
            raise harness.Refused("the trainer fed rows that are not the corpus's")
        _RUN["train"]["tokens"] = fed
        template = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state.params)
        shardings = jax.tree_util.tree_map(lambda x: x.sharding, state.params)
        fresh = lambda: jax.device_put(weights.make(template, ctx.seed), shardings)
        # distance of each leaf from its seeded value, the seeded value regenerated
        # inside the reduction instead of standing beside the state
        moved_by = jax.jit(lambda params: jax.tree_util.tree_map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32)))),
            params, weights.make(template, ctx.seed)))
        moved = lambda params: ref_train.leaf_norms(moved_by(params))
        for leaf in jax.tree_util.tree_leaves(state.params):
            leaf.delete()               # the trainer's own init: never used
        host_plan = np.asarray(jax.device_get(plan))
        self.first["plan"] = host_plan
        probe = state._replace(params=fresh())
        del state
        losses = []
        for k in range(int(ctx.cell.get("loss_steps", 3))):
            row = jax.device_put(host_plan[k:k + 1], plan.sharding)
            probe, out = self.one_row(probe, rest[0], rest[1], row, *rest[3:])
            losses.append(float(base._losses(out)[0]))
            if k == 0:
                self.first["moment_norms"] = ref_train.leaf_norms(probe.velocity["m"])
        self.first["delta_norms"] = moved(probe.params)
        self.first["one_row_losses"] = losses
        for leaf in jax.tree_util.tree_leaves(probe.params):
            leaf.delete()
        zeroed = jax.jit(lambda s: jax.tree_util.tree_map(jnp.zeros_like, s),
                         donate_argnums=0)(probe._replace(params=None))
        del probe
        new_state, out = self.compiled(zeroed._replace(params=fresh()), *rest)
        self.first["losses"] = [float(x) for x in base._losses(out)]
        self.first["moved"] = sum(v * v for v in moved(new_state.params).values()) ** 0.5
        self.calls = 1
        return new_state, out


def _check(ctx, first: dict, train, last_loss: float) -> list:
    """The ``train`` driver's ``_check``, once the trainer's programs are unloaded;
    then the routing's disagreement."""
    import jax
    seam = _RUN.get("seam")
    if seam is not None:
        seam.compiled = seam.one_row = None
        gc.collect()
        jax.clear_caches()
        print(f"memory: {harness.memory_now_bytes()} bytes as the reference starts")
    checks = _RUN["base_check"](ctx, first, train, last_loss)
    if seam is not None:
        _routing_disagreement(ctx, train["tokens"][first["plan"][0]])
    return checks


def _model_view(config: dict) -> dict:
    """What the reference and the counts read as ``model``: the file itself."""
    return {k: v for k, v in config.items() if k not in ("train", "model")}


def _routing_disagreement(ctx, batch: np.ndarray) -> None:
    import jax
    import jax.numpy as jnp
    from csed_514_project_distributed_training_using_pytorch_tpu import ops
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    model_view = ctx.config["model"]
    ref = harness.load_reference(ctx.bench, ctx.config["reference"])
    layer = int(model_view["num_dense_layers"])        # the first sparse layer
    dtype = jnp.bfloat16 if ctx.config["train"]["args"].get("bf16") else jnp.float32
    model = hybrid_lm.from_config(model_view, vocab_size=model_view["vocab_size"],
                                  seq_len=batch.shape[1], dtype=dtype,
                                  attention_fn=ops.dispatch_attention)
    params = weights.make(ref.param_shapes(model_view), ctx.seed)
    ids = jnp.asarray(batch, jnp.int32)
    got = np.asarray(jax.jit(lambda p, x: model.router_choices(p, x, layer))(params, ids))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, x: jax.lax.map(
            lambda row: ref.router_choice(p, row, model_view, layer), x))(params, ids))
    # an assignment differs when the reference's selection for its token lacks it
    differ = (got[..., :, None] != want[..., None, :]).all(axis=-1).mean()
    print(f"routing: {100 * differ:.3f} % of the first sparse layer's assignments "
          f"(first batch, {got.shape[0] * got.shape[1]} tokens x {got.shape[-1]}) "
          f"differ between the program ({jnp.dtype(dtype).name}) and the reference "
          f"(float32)")


def run(ctx) -> harness.Observations:
    from csed_514_project_distributed_training_using_pytorch_tpu.utils import (
        config as config_mod,
    )
    spec, mix = ctx.config["train"], ctx.mix
    fields = {f.name for f in dataclasses.fields(getattr(config_mod, spec["config_class"]))}
    if not {"model_config", "corpus"} <= fields:
        raise harness.Refused("the program's trainer takes no model_config and corpus: "
                              "it cannot run this configuration")
    model_view = _model_view(ctx.config)
    batch, steps = int(mix["batch"]), int(mix["steps_per_epoch"])
    n_train, n_test = int(mix.get("train_examples", batch * steps)), int(mix["test_examples"])
    rows = zipf_sequences(n_train + n_test, int(mix["seq_len"]), int(model_view["vocab_size"]),
                          float(mix["zipf_exponent"]), ctx.seed)
    corpus = os.path.join(ctx.work, "corpus")
    config_file = os.path.join(ctx.work, "model_config.json")
    if not ctx.control:
        write_corpus(corpus, rows[:n_train], rows[n_train:], int(model_view["vocab_size"]))
        with open(config_file, "w") as fh:
            json.dump(model_view, fh)
    # `datasets=` is ignored under `corpus=`; the train driver still wraps two splits
    train = {"tokens": rows[:n_train].astype(np.int32), "images": None, "labels": None}
    _RUN.clear()
    _RUN.update(train=train, corpus_rows=rows[:n_train], base_check=base._check)
    mix = dict(mix, trainer_args=dict(mix.get("trainer_args", {}), corpus=corpus,
                                      model_config=config_file))
    ctx = dataclasses.replace(ctx, mix=mix, config=dict(ctx.config, model=model_view))
    hooks = {"_splits": lambda *_: (train, {"images": None, "labels": None}),
             "EpochSeam": FrugalSeam, "_check": _check}
    kept = {name: getattr(base, name) for name in hooks}
    try:
        for name, hook in hooks.items():
            setattr(base, name, hook)
        obs = base.run(ctx)
    finally:
        for name, original in kept.items():
            setattr(base, name, original)
    if ctx.control:
        return obs
    counts = harness.load_module(os.path.join(ctx.bench, spec["flops"]["module"] + ".py"),
                                 "bench_" + spec["flops"]["module"])
    flops = getattr(counts, spec["flops"]["per_example"])(model_view, int(mix["seq_len"]))
    obs.shapes.update(flops_per_example=flops, flops_per_step=flops * batch)
    layers = [np.asarray(e["expert_rows"], np.float64) for e in obs.epochs
              if e.get("expert_rows") is not None]
    if layers:                  # [steps, sparse layers] an epoch
        arrived = float(sum(a.sum() for a in layers))
        slots = sum(a.size for a in layers)
        peak = np.concatenate([np.asarray(e["expert_rows_max"], np.float64).ravel()
                               for e in obs.epochs])
        mean = np.concatenate([np.asarray(e["expert_rows_mean"], np.float64).ravel()
                               for e in obs.epochs])
        per_row = getattr(counts, spec["flops"]["expert_per_row"])(model_view)
        obs.counters.update(
            expert_rows=arrived,
            expert_row_bound=float(slots * model_view["num_experts_per_tok"]
                                   * batch * int(mix["seq_len"])),
            expert_load_imbalance=float(np.mean(peak / np.maximum(mean, 1e-9))),
            expert_train_flops=arrived * per_row)
    return obs
