"""Driver ``train_corpus_h1``: the ``train_corpus`` driver for a ``falcon_h1`` file, whose
every layer is a Mamba-2 mixer beside a rotated GQA attention on one normed input and then
a dense feed-forward, and which has no expert layer at all.

Everything of a run is the ``train_corpus`` driver's (and through it the ``train``
driver's), loaded from its file through ``train_corpus_ssm`` and not copied, as
``train_corpus_eva`` does. This file adds what those cannot do for such a cell:

- **a model view without expert keys.** No router, so no ``routing:`` line and no expert
  counter; the view is the file itself.
- **a program that cannot build the file.** One whose ``HybridLM.from_config`` refuses the
  view (a tree from before the family) is refused here, before anything is written or
  compiled.
- **the reference's memory.** The ``reference_follow`` that holds one copy of the seeded
  weights is ``train_corpus_ssm``'s; with no ``moe_router_bias_update_rate`` in the file it
  drives ``reference/train.py``'s own ``make_step``. That step compiles for the described
  chip at 9.24 GB of arguments + 4.96 GB of temporaries (``bench_results/hw_pr47/
  compile_reference.py``): the compiler folds the clip's scale into each leaf's update and
  no scaled copy of the 3.08 GB gradient stands, so the step needs no rewrite here.
- **the mixers' work.** ``ssd_scan_train_flops`` and ``attention_train_flops``: the scan
  kernels' and the flash kernels' counted FLOPs (``train.flops.scan_per_example`` and
  ``attention_per_example`` of the configuration's counts file) of the examples the measured
  (or traced) epochs trained, for ``ssd_scan_roofline_share`` and
  ``gated_attention_roofline_share``.
"""

from __future__ import annotations

import os

import harness

ssm = harness.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                       "train_corpus_ssm.py"),
                          "bench_driver_train_corpus_ssm_for_h1")
corpus = ssm.corpus


def run(ctx) -> harness.Observations:
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    view = corpus._model_view(ctx.config)
    try:
        hybrid_lm.from_config(view, seq_len=int(ctx.mix["seq_len"]),
                              vocab_size=int(ctx.config["vocab_size"]))
    except ValueError as e:
        raise harness.Refused(f"the program's HybridLM cannot build this configuration: {e}")
    plain_routing, plain_follow = corpus._routing_disagreement, corpus.base.reference_follow
    corpus._routing_disagreement = lambda *_: None
    corpus.base.reference_follow = ssm.reference_follow
    try:
        obs = corpus.run(ctx)
    finally:
        corpus._routing_disagreement = plain_routing
        corpus.base.reference_follow = plain_follow
    if ctx.control:
        return obs
    spec = ctx.config["train"]["flops"]
    counts = harness.load_module(os.path.join(ctx.bench, spec["module"] + ".py"),
                                 "bench_" + spec["module"])
    for counter, function in (("ssd_scan_train_flops", spec["scan_per_example"]),
                              ("attention_train_flops", spec["attention_per_example"])):
        per_example = getattr(counts, function)(view, int(ctx.mix["seq_len"]))
        obs.counters[counter] = per_example * obs.counters["examples"]
    return obs
