"""Driver ``train_corpus_mla``: the ``train_corpus`` driver for a ``deepseek_v3`` file,
whose layers are a latent-attention (MLA) mixer with a rotated shared key and a dense or
an expert feed-forward.

Everything of a run is the ``train_corpus`` driver's (and through it the ``train``
driver's); the reference's step with the selection bias's rule and the
``reference_follow`` that holds one copy of the weights are the ``train_corpus_ssm``
driver's. Both are loaded from their files and not copied, as ``train_corpus_kda`` does.
This file adds what those cannot hand a reducer for such a cell:

- **the model's view.** ``train_corpus`` reads ``num_dense_layers`` as the index of the
  first expert layer among the kept ones; a ``deepseek_v3`` file says
  ``first_k_dense_replace`` (a count of published layers, from the model's layer 0), so the
  view gains the key, by the reference's own ``sparse``. ``num_experts_per_tok`` is the
  file's own key. The program reads neither of the view's additions.
- **a program that cannot build the file.** One whose ``HybridLM.from_config`` refuses the
  view (a tree from before the family) is refused here, before anything is written or
  compiled.
- **the mixers' work.** ``mla_attention_train_flops``: the flash kernels' counted FLOPs
  (``train.flops.attention_per_example`` of the configuration's counts file) of the
  examples the measured (or traced) epochs trained, for ``mla_attention_roofline_share``.

- **a warm-up as long as the selection bias needs.** From a seeded start most tokens of a
  step choose the same few experts, and the rows that reach the 16 held ones, which an
  epoch's time follows, depend on the seed until the bias has moved about 0.15; at the
  published 0.001 a step that is many epochs. The cell's ``warmup_epochs`` (1 would be the
  ``train`` driver's one) says how many calls of the timed program pass before the window
  opens: the first is the checked one, as it was, and the others follow it inside the
  seam's first call, on the same rows, so the trainer and its telemetry see one warm-up
  epoch. All of it is set-up.

The rows' bound needs no scaling here: a token's 6 assignments are fewer than the 16
held experts, so ``min(k, held) · T`` is ``k · T``.
"""

from __future__ import annotations

import os

import harness

ssm = harness.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                       "train_corpus_ssm.py"),
                          "bench_driver_train_corpus_ssm_for_mla")
corpus = ssm.corpus


class WarmSeam(corpus.FrugalSeam):
    """``FrugalSeam`` whose first call goes on for the cell's ``warmup_epochs``."""

    def _first_call(self, state, rest):
        state, out = super()._first_call(state, rest)
        for _ in range(int(self.ctx.cell["warmup_epochs"]) - 1):
            state, out = self.compiled(state, *rest)
        return state, out


def run(ctx) -> harness.Observations:
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    ref = harness.load_reference(ctx.bench, ctx.config["reference"])
    plain = corpus._model_view, corpus.base.reference_follow, corpus.FrugalSeam
    plain_view = plain[0]

    def model_view(config: dict) -> dict:
        view = plain_view(config)
        return dict(view, num_dense_layers=ref.sparse(view).index(True))

    try:
        hybrid_lm.from_config(model_view(ctx.config), seq_len=int(ctx.mix["seq_len"]),
                              vocab_size=int(ctx.config["vocab_size"]))
    except ValueError as e:
        raise harness.Refused(f"the program's HybridLM cannot build this configuration: {e}")
    corpus._model_view, corpus.base.reference_follow, corpus.FrugalSeam = (
        model_view, ssm.reference_follow, WarmSeam)
    try:
        obs = corpus.run(ctx)
    finally:
        corpus._model_view, corpus.base.reference_follow, corpus.FrugalSeam = plain
    if ctx.control:
        return obs
    view, spec = plain_view(ctx.config), ctx.config["train"]["flops"]
    counts = harness.load_module(os.path.join(ctx.bench, spec["module"] + ".py"),
                                 "bench_" + spec["module"])
    per_example = getattr(counts, spec["attention_per_example"])(view, int(ctx.mix["seq_len"]))
    obs.counters["mla_attention_train_flops"] = per_example * obs.counters["examples"]
    return obs
