"""Functional neural-network ops (the framework's op layer).

These are the TPU-native equivalents of the ATen CPU kernels the reference leans on for every
forward/backward (reference ``src/model.py:16-22``; SURVEY.md §2b): each op is a pure function
on arrays, traced once under ``jax.jit`` and compiled by XLA into fused TPU kernels (conv/matmul
on the MXU, elementwise fused into neighbors).
"""

from csed_514_project_distributed_training_using_pytorch_tpu.ops.nn import (
    conv2d,
    max_pool2d,
    dense,
    relu,
    log_softmax,
    nll_loss,
    cross_entropy_loss,
    dropout,
    dropout2d,
    layer_norm,
    rms_norm,
    embedding_rows,
    swiglu,
    gelu,
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops.attention import (
    full_attention,
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops.pallas_attention import (
    dispatch_attention,
    dispatch_plan,
    flash_attention,
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops.initializers import (
    torch_kaiming_uniform,
    torch_fan_in_uniform,
)

__all__ = [
    "conv2d",
    "max_pool2d",
    "dense",
    "relu",
    "log_softmax",
    "nll_loss",
    "cross_entropy_loss",
    "dropout",
    "dropout2d",
    "layer_norm",
    "rms_norm",
    "embedding_rows",
    "swiglu",
    "gelu",
    "full_attention",
    "flash_attention",
    "dispatch_attention",
    "dispatch_plan",
    "torch_kaiming_uniform",
    "torch_fan_in_uniform",
]
