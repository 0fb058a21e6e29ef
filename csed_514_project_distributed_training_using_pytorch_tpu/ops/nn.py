"""Core functional NN ops, NHWC/TPU-first.

TPU-native analog of the ATen kernels invoked by the reference model's forward
(reference ``src/model.py:15-22``): conv2d, max-pool, dense, dropout (elementwise and
channelwise), log_softmax, and the two loss formulations the reference uses
(``F.nll_loss`` at ``src/train.py:74,94`` and ``nn.CrossEntropyLoss`` at
``src/train_dist.py:67``).

Layout note: everything here is NHWC (``[batch, height, width, channels]``) with HWIO conv
kernels — the layout XLA:TPU tiles best onto the MXU — rather than the reference's NCHW.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def conv2d(x: jax.Array, w: jax.Array, b: jax.Array | None = None, *,
           stride: int = 1, padding: str = "VALID") -> jax.Array:
    """2-D convolution, NHWC x HWIO -> NHWC.

    Equivalent of ``nn.Conv2d`` with default stride/no padding as used at reference
    ``src/model.py:9-10`` (kernel 5, valid padding). Runs on the MXU.
    """
    out = lax.conv_general_dilated(
        x, w,
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    if b is not None:
        out = out + b
    return out


def max_pool2d(x: jax.Array, window: int = 2, stride: int | None = None) -> jax.Array:
    """Max pooling over spatial dims of an NHWC tensor.

    Equivalent of ``F.max_pool2d(x, 2)`` at reference ``src/model.py:16-17``.
    """
    if stride is None:
        stride = window
    return lax.reduce_window(
        x,
        -jnp.inf,
        lax.max,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, stride, stride, 1),
        padding="VALID",
    )


def dense(x: jax.Array, w: jax.Array, b: jax.Array | None = None) -> jax.Array:
    """Affine layer ``x @ w + b`` with ``w: [in, out]``.

    Equivalent of ``nn.Linear`` at reference ``src/model.py:12-13``. Batched matmul on the MXU;
    accumulation is requested in float32 regardless of input dtype so bfloat16 activations
    keep full-precision sums.
    """
    out = jnp.matmul(x, w, preferred_element_type=jnp.float32)
    out = out.astype(x.dtype)
    if b is not None:
        out = out + b
    return out


def relu(x: jax.Array) -> jax.Array:
    """Rectified linear unit (``F.relu``, reference ``src/model.py:16-19``)."""
    return jnp.maximum(x, 0)


def log_softmax(x: jax.Array, axis: int = -1) -> jax.Array:
    """Numerically-stable log-softmax (``F.log_softmax(x)``, reference ``src/model.py:22``)."""
    shifted = x - lax.stop_gradient(jnp.max(x, axis=axis, keepdims=True))
    return shifted - jnp.log(jnp.sum(jnp.exp(shifted), axis=axis, keepdims=True))


def nll_loss(log_probs: jax.Array, labels: jax.Array, *, reduction: str = "mean",
             label_smoothing: float = 0.0) -> jax.Array:
    """Negative log-likelihood of integer labels under ``log_probs``.

    Equivalent of ``F.nll_loss`` (reference ``src/train.py:74``) and of its deprecated
    ``size_average=False`` sum-reduction form (reference ``src/train.py:94``) via
    ``reduction="sum"``.

    ``label_smoothing=s`` trains against the smoothed target distribution
    ``(1−s)·onehot + s/C`` — torch ``CrossEntropyLoss(label_smoothing=s)`` semantics
    (pinned against real torch in ``tests/test_ops.py``); per-example loss becomes
    ``(1−s)·nll + s·mean_c(−log_probs)``.
    """
    picked = jnp.take_along_axis(log_probs, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    if label_smoothing:
        smooth = jnp.mean(log_probs, axis=-1)
        picked = (1.0 - label_smoothing) * picked + label_smoothing * smooth
    if reduction == "mean":
        return -jnp.mean(picked)
    if reduction == "sum":
        return -jnp.sum(picked)
    if reduction == "none":
        return -picked
    raise ValueError(f"unknown reduction {reduction!r}")


def cross_entropy_loss(logits: jax.Array, labels: jax.Array, *, reduction: str = "mean") -> jax.Array:
    """Softmax cross-entropy from unnormalized (or, as in the reference's distributed path,
    already-log-softmaxed) inputs.

    Equivalent of ``nn.CrossEntropyLoss`` (reference ``src/train_dist.py:67``). Note the
    reference feeds it the output of a model that already ends in log_softmax
    (``src/model.py:22``) — an effective double log-softmax (SURVEY.md §2d.1). Since
    log_softmax is idempotent, that composition is *mathematically identical* to the
    single-process ``log_softmax + nll`` objective (verified in tests/test_ops.py), so this
    framework uses the one canonical ``nll_loss(model(x))`` formulation everywhere; this
    function is provided for API parity and for users porting loss code.
    """
    return nll_loss(log_softmax(logits), labels, reduction=reduction)


def layer_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array,
               *, eps: float = 1e-5) -> jax.Array:
    """Layer normalization over the last axis with learned scale/shift.

    Not used by the reference's CNN (it has no normalization layers) — this is part of the
    beyond-parity attention model family (``models/transformer.py``). Statistics are computed
    in float32 so bfloat16 activations normalize accurately, then cast back.
    """
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    normed = (xf - mean) * lax.rsqrt(var + eps)
    return (normed * gamma.astype(jnp.float32)
            + beta.astype(jnp.float32)).astype(x.dtype)


def rms_norm(x: jax.Array, gamma: jax.Array, *, eps: float = 1e-5,
             offset: float = 0.0) -> jax.Array:
    """Root-mean-square normalization over the last axis with a learned scale and no
    shift (``x / sqrt(mean(x²) + eps) · (offset + gamma)``): the norm of the catalog
    decoders (``models/hybrid_lm.py``), also applied per attention head to q and k.
    ``offset`` 1 is the unit offset of a norm whose leaf starts at zero. Statistics in
    float32, like ``layer_norm``."""
    xf = x.astype(jnp.float32)
    normed = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    weight = gamma.astype(jnp.float32)
    if offset:
        weight = weight + offset
    return (normed * weight).astype(x.dtype)


# The widest rows one gather of an embedding takes: a choice for the v5e, from eight timings
# of the backward scatter-add alone (8192 or 16384 rows into the table's bfloat16 copy:
# ``bench_results/hw_pr47/embed_on_chip.py`` / ``embed_on_chip.jsonl``, PERF.md section 6,
# PR 47). Rows of 5120 channels scatter back in 59.3 ms, of 4096 in 3.8 and of 2304 in 3.5;
# 5120 as 4096 + 1024 side by side in 4.7, as five of 1024 in 5.2, the same sums to the last
# bit. NOT a rule of "wide rows are slow": 5120 as two of 2560 takes 28.0 ms, so a row of
# 2560 is still some four times slower than one of 4096, and the cause is not known
# (PERF.md section 7). Only 4096 and 1024 are shown fast as a block's width.
EMBED_COLUMNS = 4096


def embedding_rows(table: jax.Array, ids: jax.Array) -> jax.Array:
    """``table[ids]``, ``[..., d]``, gathered in column blocks of ``EMBED_COLUMNS`` side by
    side (one block, the plain gather, up to that width), so that the backward pass scatters
    rows no wider than that: the same rows and the same gradient to the last bit."""
    d = table.shape[1]
    if d <= EMBED_COLUMNS:
        return table[ids]
    return jnp.concatenate([table[:, c:c + EMBED_COLUMNS][ids]
                            for c in range(0, d, EMBED_COLUMNS)], axis=-1)


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    """The gated feed-forward's nonlinearity, ``silu(gate) · up``."""
    return jax.nn.silu(gate) * up


def gelu(x: jax.Array) -> jax.Array:
    """Gaussian-error linear unit (tanh approximation — the transformer-standard
    nonlinearity; XLA fuses it into the surrounding matmuls)."""
    return jax.nn.gelu(x, approximate=True)


def dropout(rng: jax.Array, x: jax.Array, rate: float, *, deterministic: bool) -> jax.Array:
    """Elementwise inverted dropout (``F.dropout``, reference ``src/model.py:20``).

    ``deterministic=True`` (eval mode) is the identity, mirroring ``model.eval()`` semantics
    at reference ``src/train.py:91`` / ``src/train_dist.py:93``.
    """
    if deterministic or rate == 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


def dropout2d(rng: jax.Array, x: jax.Array, rate: float, *, deterministic: bool) -> jax.Array:
    """Channelwise (spatial) dropout on NHWC: zeroes whole feature maps.

    Equivalent of ``nn.Dropout2d`` (reference ``src/model.py:11,17``), which drops entire
    channels rather than independent elements.
    """
    if deterministic or rate == 0.0:
        return x
    keep = 1.0 - rate
    mask_shape = (x.shape[0], 1, 1, x.shape[-1])
    mask = jax.random.bernoulli(rng, keep, mask_shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))
