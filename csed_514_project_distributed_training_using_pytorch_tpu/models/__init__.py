"""Model zoo.

The reference defines exactly one model — the MNIST CNN ``Net`` (reference
``src/model.py:4-22``); ``models.cnn.Net`` is its TPU-native re-expression.
``models.transformer`` is the beyond-parity attention family that exercises the
framework's sequence-parallel machinery (``parallel/ring_attention.py``); both share the
same call contract, so every trainer accepts either.
"""

import dataclasses
from typing import Callable

from csed_514_project_distributed_training_using_pytorch_tpu.models.cnn import Net
from csed_514_project_distributed_training_using_pytorch_tpu.models.transformer import (
    TransformerClassifier,
    validate_remat_policy,
)


import jax.numpy as jnp

VALID_MODELS = ("cnn", "transformer")


@dataclasses.dataclass(frozen=True)
class Trainee:
    """All that ``train/lm.py`` asks of a language model. The model builds it
    (``TransformerLM.trainee``, ``HybridLM.trainee``); the trainer names no model class."""

    loss: Callable      # (params, xs, ys, rng) -> the step's loss; (loss, aux) with ``has_aux``
    eval_nll: Callable  # (params, batch) -> the batch's summed next-token NLL
    targets_per_seq: int                    # a split's mean NLL is its sum over N times this
    has_aux: bool = False                   # ``aux`` ends the epoch program's output
    after_update: Callable | None = None    # ``train.step.make_train_step``'s, given ``aux``
    is_frozen: Callable | None = None       # ``ops.optim.freeze``'s predicate; None: no wrapper
    # (heads, head_dim, value_dim or None) of an ``attention_fn`` call, where a mixer makes
    # one, and the model's own fields of the ``compile`` event's ``attention``
    attention_shape: tuple | None = None
    attention_fields: dict = dataclasses.field(default_factory=dict)
    plans: Callable = lambda jaxpr, step_tokens: {}     # -> that event's model fields
    expert_block: int | None = None         # the experts' row tile, ``epoch_event``'s


def validate_model_config(name: str, *, remat: bool = False,
                          causal: bool = False,
                          attention_window: int = 0,
                          kv_heads: int = 0, rope: bool = False,
                          remat_policy: str = "") -> None:
    """Fail fast on a bad ``--model`` value or model/knob combination — callers run this
    before any data download, dataset load, or cluster rendezvous so typos cost
    milliseconds, not side effects (on a fleet: not a full rendezvous per host)."""
    if name not in VALID_MODELS:
        raise ValueError(
            f"unknown model {name!r} — choose one of {', '.join(VALID_MODELS)}")
    if remat and name == "cnn":
        raise ValueError("--remat applies to the transformer family only "
                         "(the CNN's activations are a few hundred KB)")
    validate_remat_policy(remat, remat_policy)
    if causal and name == "cnn":
        raise ValueError("--causal applies to the transformer family only "
                         "(the CNN has no attention to mask)")
    if attention_window and name == "cnn":
        raise ValueError("--attention-window applies to the transformer family only "
                         "(the CNN has no attention to window)")
    if attention_window < 0:
        raise ValueError(f"--attention-window must be >= 0, got {attention_window}")
    if kv_heads and name == "cnn":
        raise ValueError("--kv-heads applies to the transformer family only "
                         "(the CNN has no attention heads)")
    if rope and name == "cnn":
        raise ValueError("--rope applies to the transformer family only "
                         "(the CNN has no attention positions)")
    if kv_heads < 0:
        raise ValueError(f"--kv-heads must be >= 0, got {kv_heads}")
    if kv_heads and TransformerClassifier.num_heads % kv_heads:
        # The classifier's head count is fixed; reject non-divisors pre-side-effects.
        raise ValueError(f"--kv-heads {kv_heads} must divide the transformer's "
                         f"{TransformerClassifier.num_heads} heads")


def build_model(name: str, *, bf16: bool = False, remat: bool = False,
                causal: bool = False, attention_window: int = 0,
                kv_heads: int = 0, rope: bool = False,
                remat_policy: str = ""):
    """Model factory behind the trainers' ``--model`` flag. Both families share the
    ``(x, *, deterministic)`` call contract on ``[B, 28, 28, 1]`` input, so every
    trainer/eval/checkpoint path works with either.

    ``bf16`` runs activations in bfloat16 (the MXU's native dtype) with float32 master
    weights and float32 softmax/loss statistics. ``remat`` (transformer only) recomputes
    each block's activations on backward — the ``jax.checkpoint`` memory/FLOPs trade.
    ``causal`` (transformer only) masks attention decoder-style. ``attention_window``
    (transformer only; 0 = full attention) restricts attention to a sliding window of
    that width (``ops.full_attention``'s ``window`` semantics) — the local-attention
    long-context knob.
    """
    validate_model_config(name, remat=remat, causal=causal,
                          attention_window=attention_window, kv_heads=kv_heads,
                          remat_policy=remat_policy)
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    if name == "cnn":
        return Net(dtype=dtype)
    kwargs = {}
    if rope:
        kwargs["rope"] = True
    if kv_heads:
        kwargs["num_kv_heads"] = kv_heads
    if attention_window:
        from csed_514_project_distributed_training_using_pytorch_tpu.ops.attention import (
            windowed_attention_fn,
        )
        kwargs["attention_fn"] = windowed_attention_fn(attention_window)
    return TransformerClassifier(dtype=dtype, remat=remat, causal=causal,
                                 remat_policy=remat_policy, **kwargs)


__all__ = ["Net", "Trainee", "TransformerClassifier", "build_model", "validate_model_config", "validate_remat_policy",
           "VALID_MODELS"]
