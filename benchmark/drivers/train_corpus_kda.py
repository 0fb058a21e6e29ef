"""Driver ``train_corpus_kda``: the ``train_corpus`` driver for a ``kimi_linear`` file,
whose layers are a delta-rule (KDA) or a latent-attention (MLA) mixer and a
feed-forward.

Everything of a run is the ``train_corpus`` driver's (and through it the ``train``
driver's); the reference's step with the selection bias's rule and the
``reference_follow`` that holds one copy of the weights are the ``train_corpus_ssm``
driver's. Both are loaded from their files and not copied. This file adds what those
cannot hand a reducer for such a cell:

- **the model's view.** ``train_corpus`` reads ``num_dense_layers`` as the index of the
  first expert layer among the kept ones and ``num_experts_per_tok`` for the rows'
  bound; a ``kimi_linear`` file says ``first_k_dense_replace`` (a count of published
  layers, from the model's first) and ``num_experts_per_token``, so the view gains the
  two keys, the first by the reference's own ``sparse``. The program reads neither.
- **the new mixers' work.** ``kda_scan_train_flops`` and ``mla_attention_train_flops``:
  the scan kernels' and the flash kernels' counted FLOPs (``train.flops.scan_per_example``
  and ``attention_per_example`` of the configuration's counts file) of the examples the
  measured (or traced) epochs trained, for ``kda_scan_roofline_share`` and
  ``mla_attention_roofline_share``.

The rows' bound needs no scaling here: a token's 8 assignments are no more than the 8
held experts, so ``min(k, held) · T`` is ``k · T``.
"""

from __future__ import annotations

import os

import harness

ssm = harness.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                       "train_corpus_ssm.py"),
                          "bench_driver_train_corpus_ssm_for_kda")
corpus = ssm.corpus


def run(ctx) -> harness.Observations:
    ref = harness.load_reference(ctx.bench, ctx.config["reference"])
    plain_view, plain_follow = corpus._model_view, corpus.base.reference_follow

    def model_view(config: dict) -> dict:
        view = plain_view(config)
        return dict(view, num_dense_layers=ref.sparse(view).index(True),
                    num_experts_per_tok=view["num_experts_per_token"])

    corpus._model_view, corpus.base.reference_follow = model_view, ssm.reference_follow
    try:
        obs = corpus.run(ctx)
    finally:
        corpus._model_view, corpus.base.reference_follow = plain_view, plain_follow
    if ctx.control:
        return obs
    view, spec = plain_view(ctx.config), ctx.config["train"]["flops"]
    counts = harness.load_module(os.path.join(ctx.bench, spec["module"] + ".py"),
                                 "bench_" + spec["module"])
    for counter, function in (("kda_scan_train_flops", spec["scan_per_example"]),
                              ("mla_attention_train_flops", spec["attention_per_example"])):
        per_example = getattr(counts, function)(view, int(ctx.mix["seq_len"]))
        obs.counters[counter] = per_example * obs.counters["examples"]
    return obs
