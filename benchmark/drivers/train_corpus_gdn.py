"""Driver ``train_corpus_gdn``: the ``train_corpus`` driver for a ``qwen3_next`` file, whose
layers are a gated delta-rule mixer (one decay a token and head) or a gated softmax
attention, each before an expert feed-forward.

Everything of a run is the ``train_corpus`` driver's (and through it the ``train``
driver's); the ``reference_follow`` that holds one copy of the weights is the
``train_corpus_ssm`` driver's (the family has no selection bias, so the reference's step is
``reference/train.py``'s own). Both are loaded from their files and not copied, as
``train_corpus_kda`` does. This file adds what those cannot hand a reducer for such a cell:

- **the model's view.** ``train_corpus`` reads ``num_dense_layers`` as the index of the first
  expert layer among the kept ones; a ``qwen3_next`` file has no such key (every layer has
  experts), so the view gains it, by the reference's own ``sparse``. ``num_experts_per_tok``
  is the file's own key. The program reads neither of the view's additions.
- **a program that cannot build the file.** One whose ``HybridLM.from_config`` refuses the
  view (a tree from before the family) is refused here, before anything is written or
  compiled.
- **the mixers' work.** ``gdn_scan_train_flops`` and ``attention_train_flops``: the scan
  kernels' and the flash kernels' counted FLOPs (``train.flops.scan_per_example`` and
  ``attention_per_example`` of the configuration's counts file) of the examples the measured
  (or traced) epochs trained, for ``gdn_scan_roofline_share`` and
  ``gated_attention_roofline_share``.

The rows' bound needs no scaling here: a token's 10 assignments are fewer than the 32
held experts, so ``min(k, held) · T`` is ``k · T``.
"""

from __future__ import annotations

import os

import harness

ssm = harness.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                       "train_corpus_ssm.py"),
                          "bench_driver_train_corpus_ssm_for_gdn")
corpus = ssm.corpus


def run(ctx) -> harness.Observations:
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    ref = harness.load_reference(ctx.bench, ctx.config["reference"])
    plain_view, plain_follow = corpus._model_view, corpus.base.reference_follow

    def model_view(config: dict) -> dict:
        view = plain_view(config)
        return dict(view, num_dense_layers=ref.sparse(view).index(True))

    try:
        hybrid_lm.from_config(model_view(ctx.config), seq_len=int(ctx.mix["seq_len"]),
                              vocab_size=int(ctx.config["vocab_size"]))
    except ValueError as e:
        raise harness.Refused(f"the program's HybridLM cannot build this configuration: {e}")
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
    for counter, function in (("gdn_scan_train_flops", spec["scan_per_example"]),
                              ("attention_train_flops", spec["attention_per_example"])):
        per_example = getattr(counts, function)(view, int(ctx.mix["seq_len"]))
        obs.counters[counter] = per_example * obs.counters["examples"]
    return obs
