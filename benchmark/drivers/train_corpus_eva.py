"""Driver ``train_corpus_eva``: the ``train_corpus`` driver for an ``evabyte`` file, whose
layers are an EVA mixer and a dense feed-forward and which has no expert layer at all.

Everything of a run is the ``train_corpus`` driver's (and through it the ``train``
driver's); the ``reference_follow`` that holds one copy of the weights is the
``train_corpus_ssm`` driver's (with no ``moe_router_bias_update_rate`` in the file it
drives ``reference/train.py``'s own ``make_step``). Both are loaded from their files and
not copied. This file adds what those cannot do for such a cell:

- **a model view without expert keys.** ``train_corpus`` prints the share of the first
  expert layer's assignments that the program and the reference choose differently
  (``_routing_disagreement``, which reads ``num_dense_layers``) and bounds the arrived
  rows by ``num_experts_per_tok``; a stack with no router has neither, so the routing
  line is left out and the view is the file itself. The ``epoch`` events carry no
  ``expert_rows``, so no expert counter is set.
- **the new mixer's work.** ``eva_attention_train_flops``: the attention kernels' and the
  summaries' counted FLOPs (``train.flops.attention_per_example`` of the configuration's
  counts file) of the examples the measured (or traced) epochs trained, for
  ``eva_attention_roofline_share``.
"""

from __future__ import annotations

import os

import harness

ssm = harness.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                       "train_corpus_ssm.py"),
                          "bench_driver_train_corpus_ssm_for_eva")
corpus = ssm.corpus


def run(ctx) -> harness.Observations:
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    if "eva" not in hybrid_lm.LAYER_KINDS:
        raise harness.Refused("the program's HybridLM has no eva layer: it cannot run "
                              "this configuration")
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
    view, spec = corpus._model_view(ctx.config), ctx.config["train"]["flops"]
    counts = harness.load_module(os.path.join(ctx.bench, spec["module"] + ".py"),
                                 "bench_" + spec["module"])
    per_example = getattr(counts, spec["attention_per_example"])(view, int(ctx.mix["seq_len"]))
    obs.counters["eva_attention_train_flops"] = per_example * obs.counters["examples"]
    return obs
