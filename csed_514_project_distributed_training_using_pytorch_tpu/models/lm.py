"""Autoregressive pixel language model — the decoder family with KV-cache generation.

The reference has one model, a feed-forward MNIST classifier (reference
``src/model.py:4-22``); this module is beyond-parity surface that makes the framework's
CAUSAL machinery (causal attention, zig-zag rings, causal ring-of-flash) serve a real
autoregressive workload instead of an artificially-masked classifier:

- ``TransformerLM``: a decoder-only transformer over quantized pixel tokens. An MNIST
  image becomes a 784-token stream (``tokenize_images_to_ids``); training is standard
  teacher-forced next-token prediction (shift-right with BOS); the blocks are the SAME
  ``TransformerBlock`` as the classifier (same parameter layout, so the TP/FSDP/PP
  partition rules and the checkpoint format apply unchanged) with ``causal=True``.
- ``init_cache`` / ``decode_step`` / ``generate``: incremental decoding with per-layer
  K/V caches — plus ``decode_step_slots`` / ``reset_slots``, the PER-SLOT-position
  variant the continuous-batching serving engine (``serving/``) compiles exactly once
  and drives forever, and ``prefill_chunk``, the batched prefill that fills one
  slot's cache ``chunk`` prompt positions at a time (the engine's admission path;
  one compile per size in ``PREFILL_CHUNK_SIZES``) — one token's projections per step, attention against the cached prefix,
  cache append via ``lax.dynamic_update_slice``. The sampling loop is a handful of
  ``lax.scan`` segments under ``jit`` (compiler-friendly: static shapes, each segment
  attending over a static prefix that grows by ``DECODE_SEGMENT`` — masked prefix
  instead of dynamic slices), so generation runs on-device with no per-token Python
  dispatch and O(t)-amortized cache reads.

The decode path re-expresses the block math for a single position; its numerics are
pinned against the full teacher-forced forward at every position in
``tests/test_lm.py`` — the duplication is safe because the test fails if they drift.

TPU-first choices mirror the classifier: MXU-shaped denses, f32 softmax/LN statistics
under a ``dtype`` knob, pluggable ``attention_fn`` (ring/ulysses/flash cores drop in for
long-context training — S=784 divides an 8-way mesh).
"""

from __future__ import annotations

import functools
from typing import Callable

import flax.linen as fnn
import jax
import jax.numpy as jnp
from jax import lax

from csed_514_project_distributed_training_using_pytorch_tpu import ops
from csed_514_project_distributed_training_using_pytorch_tpu.models import Trainee
from csed_514_project_distributed_training_using_pytorch_tpu.ops import (
    quant as quant_ops,
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops.attention import (
    MASK_VALUE,
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops.rotary import (
    apply_rotary,
)
from csed_514_project_distributed_training_using_pytorch_tpu.models.transformer import (
    TransformerBlock,
    _normal_init,
    _ones_init,
    _zeros_init,
    remat_policy_fn,
)

# torchvision's MNIST normalization constants (reference src/train.py:28-30): the
# datasets store (x/255 - MEAN) / STD; the tokenizer inverts this to bin raw
# intensity. Imported from the data pipeline so the two can never drift.
from csed_514_project_distributed_training_using_pytorch_tpu.data.mnist import (
    MNIST_MEAN as _MNIST_MEAN,
    MNIST_STD as _MNIST_STD,
)


def tokenize_images_to_ids(x: jax.Array, *, num_levels: int = 16) -> jax.Array:
    """``[B, H, W, C]`` normalized images → ``[B, H·W·C]`` int32 token ids in
    ``[0, num_levels)``: un-normalize to raw [0, 1] intensity, then quantize to
    ``num_levels`` uniform gray levels (vocab ids ``0..num_levels-1``; the LM reserves
    id ``num_levels`` for BOS)."""
    b = x.shape[0]
    raw = x * _MNIST_STD + _MNIST_MEAN
    ids = jnp.clip(jnp.round(raw * (num_levels - 1)), 0, num_levels - 1)
    return ids.reshape(b, -1).astype(jnp.int32)


def ids_to_images(ids: jax.Array, *, num_levels: int = 16,
                  shape=(28, 28, 1)) -> jax.Array:
    """Invert ``tokenize_images_to_ids`` (up to quantization): token ids →
    ``[B, H, W, C]`` raw [0, 1] intensity images (for saving sampled digits)."""
    raw = ids.astype(jnp.float32) / (num_levels - 1)
    return raw.reshape((ids.shape[0],) + tuple(shape))


class TransformerLM(fnn.Module):
    """Decoder-only LM over pixel tokens: ``[B, S]`` ids → ``[B, S, vocab]`` log-probs.

    ``vocab_size`` counts the BOS id (``num_levels + 1`` for the pixel vocabulary).
    The input is the shift-right stream (BOS first); position ``t``'s output predicts
    the t-th target token. Blocks reuse ``TransformerBlock`` (``block_i`` naming), so
    TP/FSDP partition specs and the PP stack/unstack bridge apply as-is.
    """

    vocab_size: int = 17        # 16 gray levels + BOS
    seq_len: int = 784
    embed_dim: int = 64
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int | None = None  # < num_heads = GQA: smaller KV projection AND a
                                     # proportionally smaller decode KV cache
    mlp_ratio: int = 4
    dropout_rate: float = 0.0
    attention_fn: Callable = ops.full_attention
    attention_window: int = 0   # sliding-window causal attention over the pixel
                                # stream (0 = full); composes with the default dense
                                # core and ``ops.dispatch_attention`` only — the
                                # KV-cache decode path honors the same window,
                                # keeping the decode-parity invariant
    rope: bool = False          # rotary position embeddings on q/k; when set, the
                                # learned additive pos_embed is skipped (RoPE owns
                                # position) — decode rotates its single position by
                                # the same formula, keeping decode parity
    dtype: jnp.dtype = jnp.float32
    remat: bool = False
    remat_policy: str = ""      # see models.transformer.remat_policy_fn

    def _attention_fn(self) -> Callable:
        if not self.attention_window:
            return self.attention_fn
        if self.attention_fn not in (ops.full_attention, ops.dispatch_attention):
            raise ValueError(
                "attention_window composes with the dense core and the dispatcher "
                "only — bake the window into your custom attention_fn instead")
        ops.attention.validate_window(self.attention_window)
        return functools.partial(self.attention_fn, window=self.attention_window)

    @fnn.compact
    def __call__(self, ids: jax.Array, *, deterministic: bool = True) -> jax.Array:
        b, s = ids.shape
        if s != self.seq_len:
            raise ValueError(f"expected seq_len {self.seq_len}, got {s}")
        # Tolerate float zeros from shape-only init paths (train.step.create_train_state
        # initializes with jnp.zeros(sample_input_shape)).
        ids = ids.astype(jnp.int32)

        tok = self.param("tok_embed", _normal_init(0.02),
                         (self.vocab_size, self.embed_dim))
        h = tok.astype(self.dtype)[ids]
        if not self.rope:   # RoPE owns position; no additive embedding then
            pos = self.param("pos_embed", _normal_init(0.02),
                             (self.seq_len, self.embed_dim))
            h = h + pos.astype(self.dtype)[None]

        block_cls = TransformerBlock
        if self.remat:
            block_cls = fnn.remat(TransformerBlock, static_argnums=(2,),
                                  policy=remat_policy_fn(self.remat_policy))
        attention_fn = self._attention_fn()
        for i in range(self.num_layers):
            h = block_cls(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                mlp_ratio=self.mlp_ratio,
                dropout_rate=self.dropout_rate, attention_fn=attention_fn,
                causal=True, rope=self.rope, dtype=self.dtype,
                name=f"block_{i}")(h, deterministic)

        g = self.param("ln_f_scale", _ones_init, (self.embed_dim,))
        beta = self.param("ln_f_bias", _zeros_init, (self.embed_dim,))
        h = ops.layer_norm(h, g, beta)
        w_head = self.param("head_kernel", _normal_init(0.02),
                            (self.embed_dim, self.vocab_size))
        b_head = self.param("head_bias", _zeros_init, (self.vocab_size,))
        logits = ops.dense(h, w_head.astype(self.dtype), b_head.astype(self.dtype))
        return ops.log_softmax(logits.astype(jnp.float32))

    def shift_right(self, targets: jax.Array) -> jax.Array:
        """Teacher-forcing input stream: ``[BOS, t_0, …, t_{S-2}]`` (BOS id =
        ``vocab_size - 1``)."""
        bos = jnp.full((targets.shape[0], 1), self.vocab_size - 1, targets.dtype)
        return jnp.concatenate([bos, targets[:, :-1]], axis=1)

    def trainee(self, *, deterministic: bool = True, label_smoothing: float = 0.0) -> Trainee:
        """What ``train/lm.py`` trains and evaluates: ``next_token_loss`` under the trainer's
        two knobs, and the summed NLL of the ``seq_len`` targets a sequence has."""

        def eval_nll(params, batch):
            log_probs = self.apply({"params": params}, self.shift_right(batch))
            return -jnp.sum(jnp.take_along_axis(log_probs, batch[..., None], axis=-1))

        return Trainee(
            # the target stream IS the input stream, shifted inside the loss
            loss=lambda params, xs, ys, rng: next_token_loss(
                self, params, xs, rng, deterministic=deterministic,
                label_smoothing=label_smoothing),
            eval_nll=eval_nll, targets_per_seq=self.seq_len,
            attention_shape=(self.num_heads, self.embed_dim // self.num_heads, None))


def next_token_loss(model: TransformerLM, params, targets: jax.Array, rng,
                    *, deterministic: bool = False,
                    label_smoothing: float = 0.0) -> jax.Array:
    """Mean next-token NLL over all ``B·S`` positions (the LM training objective).
    ``label_smoothing`` follows torch ``CrossEntropyLoss`` semantics (the smoothed
    target ``(1−s)·onehot + s/V`` over the vocabulary)."""
    kwargs = {"deterministic": True} if deterministic else {"deterministic": False}
    rngs = {} if deterministic else {"dropout": rng}
    log_probs = model.apply({"params": params}, model.shift_right(targets),
                            rngs=rngs, **kwargs)
    picked = jnp.take_along_axis(log_probs, targets[..., None], axis=-1)[..., 0]
    if label_smoothing:
        smooth = jnp.mean(log_probs, axis=-1)
        picked = (1.0 - label_smoothing) * picked + label_smoothing * smooth
    return -jnp.mean(picked)


# =========================================================================================
# Incremental decoding (explicit functional KV cache)
# =========================================================================================


DECODE_SEGMENT = 128   # generate()'s static-prefix growth unit: segment j attends
                       # over min((j+1)·128, S) cache rows — small enough to halve
                       # the amortized cache re-read, big enough that the handful
                       # of per-segment scan bodies compile in seconds


# Axis SEMANTICS of the cache planes init_cache builds, by leaf name — the
# contract serving/shard.py maps onto a device mesh (slots are independent
# requests -> slot-DP; attention is embarrassingly parallel over KV heads ->
# TP). Kept here, next to the allocation, so a plane-layout change and its
# sharding rule can never drift apart.
KV_PLANE_AXES: dict[str, tuple[str, ...]] = {
    "k": ("slot", "position", "kv_head", "head_dim"),
    "v": ("slot", "position", "kv_head", "head_dim"),
    "k_scale": ("slot", "position", "kv_head"),
    "v_scale": ("slot", "position", "kv_head"),
}


def init_cache(model: TransformerLM, batch: int, *,
               kv_dtype: str | None = None) -> dict:
    """Zeroed per-layer K/V caches ``[B, seq_len, KV_H, Dh]`` in the model's
    activation dtype — a bf16 model decodes against a bf16 cache, halving the HBM
    read that dominates batched decode (the score/value einsums still accumulate
    in f32: mixed-dtype promotion upcasts on-chip, after the narrow HBM read).
    f32 models keep an f32 cache and bit-exact decode parity. Under GQA the cache
    holds only the ``num_kv_heads`` K/V heads — the decode-memory win.

    ``kv_dtype`` (an ``ops.quant.KV_DTYPES`` spec; ``None`` == ``"model"``, the
    bitwise-unchanged default) selects the plane dtype. ``"fp32"``/``"bf16"``
    are plain-cast planes. ``"int8"``/``"fp8"`` are QUANTIZE-ON-WRITE planes:
    every written row carries one symmetric scale per KV head, stored in
    ``k_scale``/``v_scale`` planes ``[B, seq_len, KV_H]`` (f32) alongside the
    narrow planes — the decode/prefill paths quantize rows as they write and
    dequantize inside the attention einsums, so HBM streams ~quarter the bytes
    while the scale adds 4 bytes per head per position."""
    head_dim = model.embed_dim // model.num_heads
    kvh = model.num_kv_heads or model.num_heads
    shape = (batch, model.seq_len, kvh, head_dim)
    dtype, scaled = quant_ops.resolve_kv_dtype(kv_dtype or "model", model.dtype)

    def layer():
        planes = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        if scaled:
            planes["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
            planes["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
        return planes

    return {f"block_{i}": layer() for i in range(model.num_layers)}


def decode_step(model: TransformerLM, params, cache: dict, ids_t: jax.Array,
                t: jax.Array, *, prefix_len: int | None = None
                ) -> tuple[dict, jax.Array]:
    """One incremental step: token ids at position ``t`` → log-probs for position
    ``t``'s prediction, with every layer's K/V appended to the cache.

    ``ids_t: [B]``, ``t``: int32 scalar (traced). Re-expresses the block math for a
    single position (pre-LN attn + MLP residuals) attending against the masked cached
    prefix — pinned equal to the full forward at every position in tests.

    ``prefix_len`` (a STATIC int, default the full ``seq_len``) bounds the cache
    region the attention reads: callers that know ``t < prefix_len`` (the segmented
    ``generate`` scan) slice the score/value einsums to ``cache[:, :prefix_len]``,
    cutting decode's dominant HBM term — the per-step cache re-read — from
    O(seq_len) to O(t) amortized, with every shape still static. Positions beyond
    ``t`` inside the prefix are masked exactly as before, so the math is unchanged.
    """
    if "k_scale" in cache.get("block_0", {}):
        # Quantized (int8/fp8) planes are a serving-path feature: the slot entry
        # points quantize-on-write and dequantize-in-kernel. This path would
        # astype raw values into the narrow dtype (no scale) and attend against
        # the codes — garbage, silently.
        raise ValueError(
            "decode_step reads raw K/V planes only — use decode_step_slots/"
            "prefill_chunk for a quantized cache, or init_cache() without "
            "kv_dtype")
    b = ids_t.shape[0]
    e, nh = model.embed_dim, model.num_heads
    hd = e // nh
    kvh = model.num_kv_heads or nh
    rep = nh // kvh
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    pl_ = model.seq_len if prefix_len is None else prefix_len
    if not 0 < pl_ <= model.seq_len:
        raise ValueError(f"prefix_len {pl_} outside (0, {model.seq_len}]")

    h = params["tok_embed"].astype(jnp.float32)[ids_t]           # [B, E]
    if not model.rope:
        h = h + params["pos_embed"].astype(jnp.float32)[t]

    for i in range(model.num_layers):
        p = params[f"block_{i}"]
        a = p["attn"]
        x = ops.layer_norm(h, p["ln1_scale"], p["ln1_bias"])
        if kvh == nh:
            qkv = ops.dense(x, a["qkv_kernel"], a["qkv_bias"])    # [B, 3E]
            q = qkv[:, :e].reshape(b, nh, hd)
            k = qkv[:, e:2 * e].reshape(b, kvh, hd)
            v = qkv[:, 2 * e:].reshape(b, kvh, hd)
        else:  # GQA: split projections, kvh-head K/V (the smaller cache)
            q = ops.dense(x, a["q_kernel"], a["q_bias"]).reshape(b, nh, hd)
            kv = ops.dense(x, a["kv_kernel"], a["kv_bias"]).reshape(b, 2, kvh, hd)
            k, v = kv[:, 0], kv[:, 1]
        if model.rope:
            q = apply_rotary(q, t)
            k = apply_rotary(k, t)
        layer = cache[f"block_{i}"]
        k_cache = lax.dynamic_update_slice(
            layer["k"], k[:, None].astype(layer["k"].dtype), (0, t, 0, 0))
        v_cache = lax.dynamic_update_slice(
            layer["v"], v[:, None].astype(layer["v"].dtype), (0, t, 0, 0))
        cache = {**cache, f"block_{i}": {"k": k_cache, "v": v_cache}}
        # Masked-prefix attention: full-length scores with positions > t masked out —
        # static shapes (scan/jit-friendly) instead of a dynamic-length slice. A
        # windowed model masks the same sliding band it trained with (the
        # decode-parity invariant covers windowed configs too). Query heads group
        # over their shared K/V head (GQA); rep == 1 degenerates to plain MHA.
        qg = q.reshape(b, kvh, rep, hd)
        scores = jnp.einsum("bgrd,bsgd->bgrs", qg * scale,
                            k_cache[:, :pl_])                 # [B,G,R,pl]
        pos = jnp.arange(pl_)[None, None, None]
        visible = pos <= t
        if model.attention_window:
            visible &= t - pos < model.attention_window
        scores = jnp.where(visible, scores, MASK_VALUE)
        weights = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bgrs,bsgd->bgrd", weights,
                          v_cache[:, :pl_]).reshape(b, e)
        h = h + ops.dense(attn, a["out_kernel"], a["out_bias"])

        x = ops.layer_norm(h, p["ln2_scale"], p["ln2_bias"])
        up = ops.gelu(ops.dense(x, p["mlp_up_kernel"], p["mlp_up_bias"]))
        h = h + ops.dense(up, p["mlp_down_kernel"], p["mlp_down_bias"])

    h = ops.layer_norm(h, params["ln_f_scale"], params["ln_f_bias"])
    logits = ops.dense(h, params["head_kernel"], params["head_bias"])
    return cache, ops.log_softmax(logits.astype(jnp.float32))


def decode_step_slots(model: TransformerLM, params, cache: dict,
                      ids_t: jax.Array, t: jax.Array
                      ) -> tuple[dict, jax.Array]:
    """One incremental step at PER-SLOT positions: ``ids_t: [B]``, ``t: [B]`` int32.

    The serving engine's decode program (``serving/engine.py``): batch row ``b`` is
    an independent decode SLOT at its own position ``t[b]``, so one fixed-shape
    program advances every in-flight request one token regardless of their mix of
    prompt/output lengths — the zero-retracing requirement of continuous batching.
    Same per-position math as ``decode_step`` (pinned token-identical to sequential
    ``generate`` in ``tests/test_serving.py``): each slot's K/V row is written at
    its own position via a vmapped ``lax.dynamic_update_index_in_dim``, the causal
    (and sliding-window) mask is per-slot ``pos <= t[b]``, and RoPE rotates each
    slot by its own position. No ``prefix_len`` narrowing: slots sit at arbitrary
    positions, so every step reads the full ``[B, S]`` cache — the serving cache
    re-read is O(S) per token by design (fixed shapes beat a per-mix recompile).

    A QUANTIZED cache (``init_cache(..., kv_dtype="int8"/"fp8")`` — detected by
    its ``k_scale`` planes) changes only the plane I/O, never the program count:
    the freshly projected K/V rows are quantized on write (one scale per KV
    head, written by the same vmapped row scatter), and the score/value einsums
    read the dequantized planes — an on-chip upcast fused into the einsum, so
    the per-step HBM read is the NARROW plane plus the scale vector. Params may
    likewise hold ``ops.quant.QuantizedTensor`` kernels (``quantize_params``);
    plain arrays take the exact ``ops.dense`` path, so the unquantized trace is
    bitwise identical to the pre-quantization code.
    """
    b = ids_t.shape[0]
    e, nh = model.embed_dim, model.num_heads
    hd = e // nh
    kvh = model.num_kv_heads or nh
    rep = nh // kvh
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    h = params["tok_embed"].astype(jnp.float32)[ids_t]           # [B, E]
    if not model.rope:
        h = h + params["pos_embed"].astype(jnp.float32)[t]       # gather per slot

    # [S, KV, Dh] cache, [KV, Dh] row, scalar position — batched over slots.
    write_row = jax.vmap(
        lambda c, row, pos: lax.dynamic_update_index_in_dim(c, row, pos, 0))
    pos = jnp.arange(model.seq_len)[None]                        # [1, S]
    tb = t[:, None]                                              # [B, 1]
    visible = pos <= tb
    if model.attention_window:
        visible &= tb - pos < model.attention_window
    visible = visible[:, None, None, :]                          # [B, 1, 1, S]

    for i in range(model.num_layers):
        p = params[f"block_{i}"]
        a = p["attn"]
        x = ops.layer_norm(h, p["ln1_scale"], p["ln1_bias"])
        if kvh == nh:
            qkv = quant_ops.dense_any(x, a["qkv_kernel"], a["qkv_bias"])  # [B, 3E]
            q = qkv[:, :e].reshape(b, nh, hd)
            k = qkv[:, e:2 * e].reshape(b, kvh, hd)
            v = qkv[:, 2 * e:].reshape(b, kvh, hd)
        else:  # GQA: split projections, kvh-head K/V (the smaller cache)
            q = quant_ops.dense_any(x, a["q_kernel"], a["q_bias"]).reshape(b, nh, hd)
            kv = quant_ops.dense_any(x, a["kv_kernel"],
                                     a["kv_bias"]).reshape(b, 2, kvh, hd)
            k, v = kv[:, 0], kv[:, 1]
        if model.rope:
            # positions [B] on [B, H, D]: the batch dim takes apply_rotary's
            # sequence slot, giving each slot its own rotation angle.
            q = apply_rotary(q, t)
            k = apply_rotary(k, t)
        layer = cache[f"block_{i}"]
        if "k_scale" in layer:   # quantize-on-write planes with per-head scales
            kq, ks = quant_ops.quantize_rows(k, layer["k"].dtype)
            vq, vs = quant_ops.quantize_rows(v, layer["v"].dtype)
            k_cache = write_row(layer["k"], kq, t)
            v_cache = write_row(layer["v"], vq, t)
            ks_cache = write_row(layer["k_scale"], ks, t)
            vs_cache = write_row(layer["v_scale"], vs, t)
            cache = {**cache, f"block_{i}": {
                "k": k_cache, "v": v_cache,
                "k_scale": ks_cache, "v_scale": vs_cache}}
            # Dequantize-in-kernel: the upcast/rescale fuses into the einsum
            # that consumes it — HBM streamed the narrow plane.
            k_read = quant_ops.dequantize_rows(k_cache, ks_cache)
            v_read = quant_ops.dequantize_rows(v_cache, vs_cache)
        else:
            k_cache = write_row(layer["k"], k.astype(layer["k"].dtype), t)
            v_cache = write_row(layer["v"], v.astype(layer["v"].dtype), t)
            cache = {**cache, f"block_{i}": {"k": k_cache, "v": v_cache}}
            k_read, v_read = k_cache, v_cache
        qg = q.reshape(b, kvh, rep, hd)
        scores = jnp.einsum("bgrd,bsgd->bgrs", qg * scale, k_read)   # [B,G,R,S]
        scores = jnp.where(visible, scores, MASK_VALUE)
        weights = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bgrs,bsgd->bgrd", weights, v_read).reshape(b, e)
        h = h + quant_ops.dense_any(attn, a["out_kernel"], a["out_bias"])

        x = ops.layer_norm(h, p["ln2_scale"], p["ln2_bias"])
        up = ops.gelu(quant_ops.dense_any(x, p["mlp_up_kernel"],
                                          p["mlp_up_bias"]))
        h = h + quant_ops.dense_any(up, p["mlp_down_kernel"],
                                    p["mlp_down_bias"])

    h = ops.layer_norm(h, params["ln_f_scale"], params["ln_f_bias"])
    logits = quant_ops.dense_any(h, params["head_kernel"], params["head_bias"])
    return cache, ops.log_softmax(logits.astype(jnp.float32))


def decode_nll(model: TransformerLM, params, targets: jax.Array, *,
               kv_dtype: str | None = None) -> jax.Array:
    """Teacher-forced mean next-token NLL scored through the SERVING decode
    path (``decode_step_slots``) — the accuracy-budget probe for quantized
    execution: run it with ``kv_dtype=None`` for the fp32 oracle and with
    ``kv_dtype="int8"`` (and/or quantized ``params``) for the policy under
    test, and the difference is the NLL cost of the policy, measured through
    the exact kernels the engine serves with (quantize-on-write rounding on
    every cached row included). ``targets``: ``[B, seq_len]`` token ids; wrap
    in ``jax.jit`` for repeated use — the scan traces once."""
    b, s = targets.shape
    if s != model.seq_len:
        raise ValueError(f"expected seq_len {model.seq_len}, got {s}")
    params = jax.tree_util.tree_map(jnp.asarray, params)
    targets = targets.astype(jnp.int32)
    cache = init_cache(model, b, kv_dtype=kv_dtype)
    inputs = jnp.transpose(model.shift_right(targets))        # [S, B]
    target_cols = jnp.transpose(targets)                      # [S, B]

    def step(cache, xs):
        t, ids_t, tgt_t = xs
        cache, logp = decode_step_slots(model, params, cache, ids_t,
                                        jnp.full((b,), t, jnp.int32))
        return cache, jnp.take_along_axis(logp, tgt_t[:, None], axis=-1)[:, 0]

    positions = jnp.arange(s, dtype=jnp.int32)
    _, picked = lax.scan(step, cache, (positions, inputs, target_cols))
    return -jnp.mean(picked)


PREFILL_CHUNK_SIZES = (32, 128, 512)   # the serving engine's default static chunk
                                       # set: admission of ANY prompt length
                                       # compiles at most one program per size


def prefill_chunk(model: TransformerLM, params, cache: dict, prompt: jax.Array,
                  slot: jax.Array, start: jax.Array, length: jax.Array,
                  fresh: jax.Array, *, chunk: int) -> dict:
    """Batched prefill: write ``length`` prompt positions of ONE slot's KV cache in
    a single ``[chunk]``-wide causal forward.

    The serving engine's answer to the one-token-per-step prompt tax: where
    prefill-as-decode pays one ``decode_step_slots`` invocation per prompt token,
    this runs full-sequence causal attention for ``chunk`` positions at once —
    MXU-shaped ``[chunk, E]`` matmuls instead of ``[B, E]`` single-token ones — and
    bulk-writes the chunk's K/V rows, so a length-P prompt costs
    ``ceil(P / chunk)`` program invocations. ``chunk`` is STATIC (one compile per
    size in the engine's small chunk set); everything else is data:

    - ``prompt``: the engine's device-resident ``[num_slots, S]`` prompt buffer;
    - ``slot``, ``start``, ``length``: traced int32 scalars — which slot, the first
      position of the chunk, and how many of the ``chunk`` rows are real (the tail
      chunk of a prompt pads up; padded rows' K/V writes are DROPPED, not clamped,
      so a partial chunk never clobbers live rows);
    - ``fresh``: traced bool — wipe the slot's planes first (recycled-slot hygiene,
      same contract as ``reset_slots``; False when a prefix-cache hit installed
      rows that must survive).

    Token-identity with the per-token path is by construction, not luck: the chunk
    writes its K/V into the slot's FULL ``[S]`` plane first and then attends
    against that plane under the same ``pos <= t`` (and sliding-window) mask and
    the same einsum/reduction structure as ``decode_step_slots`` — position ``t``
    reads exactly the rows (cached prefix + in-chunk causal) it would have seen
    one token at a time, at the same cache dtype rounding — including under a
    QUANTIZED cache (``k_scale`` planes present), where the chunk's rows are
    quantized on write with the identical per-head scale math as
    ``decode_step_slots`` and attention reads the dequantized plane. No logits:
    prompt tokens are forced, so prefill only has to leave the cache behind.
    """
    s = model.seq_len
    e, nh = model.embed_dim, model.num_heads
    hd = e // nh
    kvh = model.num_kv_heads or nh
    rep = nh // kvh
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if not 0 < chunk <= s:
        raise ValueError(f"chunk {chunk} outside (0, {s}]")

    positions = start + jnp.arange(chunk, dtype=jnp.int32)       # [C]
    valid = jnp.arange(chunk) < length
    # Padded rows may run past seq_len: every gather clips, every write drops.
    safe_pos = jnp.clip(positions, 0, s - 1)
    write_pos = jnp.where(valid, safe_pos, s)                    # s = dropped
    row = prompt[slot]                                           # [S]
    # Shift-right input stream: position 0 reads BOS, position p reads prompt[p-1].
    prev = row[jnp.clip(positions - 1, 0, s - 1)]
    inp = jnp.where(positions == 0, model.vocab_size - 1, prev)

    h = params["tok_embed"].astype(jnp.float32)[inp]             # [C, E]
    if not model.rope:
        h = h + params["pos_embed"].astype(jnp.float32)[safe_pos]

    pos_s = jnp.arange(s)[None]                                  # [1, S]
    visible = pos_s <= positions[:, None]
    if model.attention_window:
        visible &= positions[:, None] - pos_s < model.attention_window
    visible = visible[:, None, None, :]                          # [C, 1, 1, S]

    for i in range(model.num_layers):
        p = params[f"block_{i}"]
        a = p["attn"]
        x = ops.layer_norm(h, p["ln1_scale"], p["ln1_bias"])
        if kvh == nh:
            qkv = quant_ops.dense_any(x, a["qkv_kernel"], a["qkv_bias"])  # [C, 3E]
            q = qkv[:, :e].reshape(chunk, nh, hd)
            k = qkv[:, e:2 * e].reshape(chunk, kvh, hd)
            v = qkv[:, 2 * e:].reshape(chunk, kvh, hd)
        else:  # GQA: split projections, kvh-head K/V (the smaller cache)
            q = quant_ops.dense_any(x, a["q_kernel"],
                                    a["q_bias"]).reshape(chunk, nh, hd)
            kv = quant_ops.dense_any(x, a["kv_kernel"],
                                     a["kv_bias"]).reshape(chunk, 2, kvh, hd)
            k, v = kv[:, 0], kv[:, 1]
        if model.rope:
            q = apply_rotary(q, positions)
            k = apply_rotary(k, positions)
        layer = cache[f"block_{i}"]
        quantized = "k_scale" in layer
        if quantized:
            # Same quantize-on-write as decode_step_slots — a chunk-prefilled
            # row is bit-identical to the row the per-token path would have
            # cached, so the decode-parity argument carries over unchanged.
            k, ks = quant_ops.quantize_rows(k, layer["k"].dtype)
            v, vs = quant_ops.quantize_rows(v, layer["v"].dtype)
        plane_k, plane_v = layer["k"][slot], layer["v"][slot]    # [S, KV, Dh]
        # Wipe-then-write keeps a recycled slot bit-identical to a fresh one
        # (reset_slots' contract; fresh is False mid-plan and on prefix hits).
        zero = jnp.zeros((), plane_k.dtype)
        plane_k = jnp.where(fresh, zero, plane_k)
        plane_v = jnp.where(fresh, zero, plane_v)
        plane_k = plane_k.at[write_pos].set(k.astype(plane_k.dtype), mode="drop")
        plane_v = plane_v.at[write_pos].set(v.astype(plane_v.dtype), mode="drop")
        new_layer = {
            "k": lax.dynamic_update_index_in_dim(layer["k"], plane_k, slot, 0),
            "v": lax.dynamic_update_index_in_dim(layer["v"], plane_v, slot, 0)}
        if quantized:
            plane_ks = jnp.where(fresh, jnp.zeros((), jnp.float32),
                                 layer["k_scale"][slot])         # [S, KV]
            plane_vs = jnp.where(fresh, jnp.zeros((), jnp.float32),
                                 layer["v_scale"][slot])
            plane_ks = plane_ks.at[write_pos].set(ks, mode="drop")
            plane_vs = plane_vs.at[write_pos].set(vs, mode="drop")
            new_layer["k_scale"] = lax.dynamic_update_index_in_dim(
                layer["k_scale"], plane_ks, slot, 0)
            new_layer["v_scale"] = lax.dynamic_update_index_in_dim(
                layer["v_scale"], plane_vs, slot, 0)
            k_read = quant_ops.dequantize_rows(plane_k, plane_ks)
            v_read = quant_ops.dequantize_rows(plane_v, plane_vs)
        else:
            k_read, v_read = plane_k, plane_v
        cache = {**cache, f"block_{i}": new_layer}
        # Attend against the full written plane under the per-position mask —
        # decode_step_slots' exact score/value structure, batched over the chunk.
        qg = q.reshape(chunk, kvh, rep, hd)
        scores = jnp.einsum("cgrd,sgd->cgrs", qg * scale, k_read)    # [C,G,R,S]
        scores = jnp.where(visible, scores, MASK_VALUE)
        weights = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("cgrs,sgd->cgrd", weights, v_read).reshape(chunk, e)
        h = h + quant_ops.dense_any(attn, a["out_kernel"], a["out_bias"])

        x = ops.layer_norm(h, p["ln2_scale"], p["ln2_bias"])
        up = ops.gelu(quant_ops.dense_any(x, p["mlp_up_kernel"],
                                          p["mlp_up_bias"]))
        h = h + quant_ops.dense_any(up, p["mlp_down_kernel"],
                                    p["mlp_down_bias"])
    return cache


def verify_chunk(model: TransformerLM, params, cache: dict, ids: jax.Array,
                 t: jax.Array, draft: jax.Array, *, k: int
                 ) -> tuple[dict, jax.Array]:
    """Batched K-token verify: score ``k`` draft tokens per slot in ONE
    fixed-shape causal forward over the slot planes — the program that lets
    speculative decoding amortize each full-cache read over up to ``k + 1``
    emitted tokens instead of one.

    ``ids: [B]`` is each slot's last accepted token, ``t: [B]`` its position
    (``decode_step_slots`` conventions), ``draft: [B, k]`` the drafter's
    proposals for positions ``t+1 .. t+k``. ``k`` is the only STATIC argument
    (one compile per configured width — the engine pins ``verify_trace_counts``
    at <= 1 per ``k``); everything else is data. The chunk inputs are
    ``[ids, d_1, .., d_k]`` at positions ``t .. t+k``; row ``j``'s log-probs
    are the target distribution for the token AT position ``t+j`` — row 0
    re-derives plain decode, rows ``1..k`` score the drafts, and the last row
    is the bonus/correction distribution when every draft survives. Returns
    ``(cache, log_probs [B, k+1, V])``; ACCEPTANCE is the caller's (the
    engine's jitted verify program folds greedy prefix-match or rejection
    sampling on top, so the accept rule is data too).

    Cache semantics are ``prefill_chunk``'s, batched over slots: the chunk
    bulk-writes all ``k+1`` rows into each slot's full ``[S]`` plane FIRST
    (quantize-on-write with the identical per-head scale math when the planes
    carry ``k_scale`` — a verify-written row is bit-identical to the row the
    per-token path would have cached) and then attends against that plane
    under the same per-position ``pos <= t+j`` (and sliding-window) mask and
    einsum structure as ``decode_step_slots`` — token-identity of greedy
    acceptance with sequential decode is by construction. Rows past
    ``seq_len`` DROP (never clamp onto live rows). Rollback needs no cache
    surgery: rows written for REJECTED drafts sit at positions strictly
    beyond the new accepted position, and the next verify/decode step's
    write-before-attend covers every such row before any query can see it —
    accepted rows are never rewritten, rejected rows are never read.
    """
    s = model.seq_len
    e, nh = model.embed_dim, model.num_heads
    hd = e // nh
    kvh = model.num_kv_heads or nh
    rep = nh // kvh
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if not 1 <= k < s:
        raise ValueError(f"k {k} outside [1, {s})")
    w = k + 1                                                    # chunk width
    b = ids.shape[0]

    x = jnp.concatenate([ids[:, None], draft], axis=1).astype(jnp.int32)  # [B,W]
    positions = t[:, None] + jnp.arange(w, dtype=jnp.int32)      # [B, W]
    safe_pos = jnp.clip(positions, 0, s - 1)
    write_pos = jnp.where(positions < s, safe_pos, s)            # s = dropped
    slot_idx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, w))

    h = params["tok_embed"].astype(jnp.float32)[x]               # [B, W, E]
    if not model.rope:
        h = h + params["pos_embed"].astype(jnp.float32)[safe_pos]

    pos_s = jnp.arange(s)[None, None]                            # [1, 1, S]
    visible = pos_s <= positions[:, :, None]
    if model.attention_window:
        visible &= positions[:, :, None] - pos_s < model.attention_window
    visible = visible[:, :, None, None, :]                       # [B, W, 1, 1, S]

    def flat_dense(y, kern, bias):
        # The projections run in the [rows, E] 2-D shape decode/prefill use, so
        # the per-row numerics (and the w8a8 per-row activation quantization)
        # are position-for-position identical to the per-token path.
        return quant_ops.dense_any(y.reshape(b * w, -1), kern,
                                   bias).reshape(b, w, -1)

    for i in range(model.num_layers):
        p = params[f"block_{i}"]
        a = p["attn"]
        xln = ops.layer_norm(h, p["ln1_scale"], p["ln1_bias"])
        if kvh == nh:
            qkv = flat_dense(xln, a["qkv_kernel"], a["qkv_bias"])  # [B, W, 3E]
            q = qkv[..., :e].reshape(b, w, nh, hd)
            kk = qkv[..., e:2 * e].reshape(b, w, kvh, hd)
            v = qkv[..., 2 * e:].reshape(b, w, kvh, hd)
        else:  # GQA: split projections, kvh-head K/V (the smaller cache)
            q = flat_dense(xln, a["q_kernel"], a["q_bias"]).reshape(b, w, nh, hd)
            kv = flat_dense(xln, a["kv_kernel"],
                            a["kv_bias"]).reshape(b, w, 2, kvh, hd)
            kk, v = kv[:, :, 0], kv[:, :, 1]
        if model.rope:
            q = apply_rotary(q, safe_pos)
            kk = apply_rotary(kk, safe_pos)
        layer = cache[f"block_{i}"]
        quantized = "k_scale" in layer
        if quantized:
            kk, ks = quant_ops.quantize_rows(kk, layer["k"].dtype)
            v, vs = quant_ops.quantize_rows(v, layer["v"].dtype)
        # Bulk row scatter over (slot, position) pairs; out-of-range rows drop.
        k_cache = layer["k"].at[slot_idx, write_pos].set(
            kk.astype(layer["k"].dtype), mode="drop")
        v_cache = layer["v"].at[slot_idx, write_pos].set(
            v.astype(layer["v"].dtype), mode="drop")
        new_layer = {"k": k_cache, "v": v_cache}
        if quantized:
            ks_cache = layer["k_scale"].at[slot_idx, write_pos].set(
                ks, mode="drop")
            vs_cache = layer["v_scale"].at[slot_idx, write_pos].set(
                vs, mode="drop")
            new_layer["k_scale"] = ks_cache
            new_layer["v_scale"] = vs_cache
            k_read = quant_ops.dequantize_rows(k_cache, ks_cache)
            v_read = quant_ops.dequantize_rows(v_cache, vs_cache)
        else:
            k_read, v_read = k_cache, v_cache
        cache = {**cache, f"block_{i}": new_layer}
        qg = q.reshape(b, w, kvh, rep, hd)
        scores = jnp.einsum("bwgrd,bsgd->bwgrs", qg * scale,
                            k_read)                              # [B,W,G,R,S]
        scores = jnp.where(visible, scores, MASK_VALUE)
        weights = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bwgrs,bsgd->bwgrd", weights,
                          v_read).reshape(b, w, e)
        h = h + flat_dense(attn, a["out_kernel"], a["out_bias"])

        xln = ops.layer_norm(h, p["ln2_scale"], p["ln2_bias"])
        up = ops.gelu(flat_dense(xln, p["mlp_up_kernel"], p["mlp_up_bias"]))
        h = h + flat_dense(up, p["mlp_down_kernel"], p["mlp_down_bias"])

    h = ops.layer_norm(h, params["ln_f_scale"], params["ln_f_bias"])
    logits = flat_dense(h, params["head_kernel"], params["head_bias"])
    return cache, ops.log_softmax(logits.astype(jnp.float32))


def reset_slots(cache: dict, fresh: jax.Array) -> dict:
    """Zero the K/V rows of the slots where ``fresh`` (``[B]`` bool) is set — slot
    recycling for the serving engine. Correctness never depends on it (the per-slot
    ``pos <= t`` mask already hides rows beyond a slot's position), but wiping a
    recycled slot keeps its cache bit-identical to a freshly ``init_cache``'d one,
    so the decode-parity invariant is checkable slot-by-slot at any time. The
    wipe is rank-generic so a quantized cache's ``[B, S, KV_H]`` scale planes
    are wiped exactly like the ``[B, S, KV_H, Dh]`` K/V planes."""
    def wipe(x):
        mask = fresh.reshape(fresh.shape + (1,) * (x.ndim - 1))
        return jnp.where(mask, jnp.zeros((), x.dtype), x)
    return jax.tree_util.tree_map(wipe, cache)


# =============================================================================
# Paged KV cache (DESIGN.md §27): the serving cache as a fixed page pool
# =============================================================================
#
# The contiguous serving cache above prices every slot at worst-case context —
# ``[num_slots, S]`` planes whether a request uses 8 tokens or all S. The paged
# layout replaces those planes with per-layer PAGE POOLS
# ``[num_pages, page_size, KV_H, Dh]`` plus ONE page table ``[B, P_max]``
# (int32, ``P_max = ceil(S / page_size)``) carried as DATA into every jitted
# call: slot ``b``'s logical position ``p`` lives at
# ``pool[table[b, p // page_size], p % page_size]``. Slot count decouples from
# max context — the pool is sized for the tokens actually resident, and
# prefix-cache hits / park / resume become page refcount bumps in the host
# allocator (``serving/pagepool.py``) instead of whole-plane copies.
#
# The paged model functions below are ADAPTERS over the contiguous trio, not
# re-implementations: gather the table's view (``pool[table] → [B, S, ...]``),
# run the EXISTING function on that view, then scatter the rows it wrote back
# into the pool at their ``(page, offset)`` coordinates. Every arithmetic op —
# projections, quantize-on-write scales, masked einsums, softmax — is the same
# traced code, so greedy decode is token-IDENTICAL to the contiguous oracle by
# construction (pinned across the engine matrix in tests/test_paged_kv.py),
# and a math edit to the contiguous path cannot drift from the paged one.
# Masked garbage is the one place the layouts differ (a fresh slot's gathered
# view shows recycled-page junk where the contiguous plane shows zeros), and
# it is harmless by the same argument ``reset_slots`` documents: every masked
# score becomes ``MASK_VALUE`` exactly, its softmax weight underflows to 0.0,
# and ``0 · finite == 0`` — the pool never holds non-finite values (every page
# starts zeroed and only ever receives projected rows/scales). Paged mode
# therefore needs NO wipe-on-recycle at all.
#
# Unmapped table entries point at the allocator's reserved NULL page, so the
# fixed-shape programs' out-of-reservation writes (a parked slot's decode row,
# verify rows past a short reservation) land somewhere harmless instead of in
# a neighbour's page. The engine's reservation-at-admission invariant
# guarantees every position ``<= t`` of a LIVE slot is mapped, which is all
# the visibility mask ever reads.
#
# ``ops/paged_attention.py`` holds the TPU decode kernel (page-table-steered
# gather-attend with the dequant fused in, scalar-prefetch table); these
# adapters are its pure-XLA gather fallback and the tier-1 identity oracle.

# Axis semantics of the pool planes, by leaf name — the paged counterpart of
# KV_PLANE_AXES, mapped onto the serve mesh by serving/shard.py (pages are
# slot-owned -> slot-DP axis; KV heads -> TP axis, same as contiguous).
PAGE_PLANE_AXES: dict[str, tuple[str, ...]] = {
    "k": ("page", "offset", "kv_head", "head_dim"),
    "v": ("page", "offset", "kv_head", "head_dim"),
    "k_scale": ("page", "offset", "kv_head"),
    "v_scale": ("page", "offset", "kv_head"),
}


def pages_per_slot(seq_len: int, page_size: int) -> int:
    """P_max — the page-table width that can map a full-context slot."""
    if not 0 < page_size:
        raise ValueError(f"page_size must be positive, got {page_size}")
    return -(-seq_len // page_size)


def init_page_pool(model: TransformerLM, num_pages: int, *, page_size: int,
                   kv_dtype: str | None = None) -> dict:
    """Zeroed per-layer page pools ``[num_pages, page_size, KV_H, Dh]`` —
    ``init_cache``'s paged twin, same dtype/scale-plane rules (``kv_dtype``
    int8/fp8 adds ``k_scale``/``v_scale`` pools ``[num_pages, page_size,
    KV_H]`` f32). Total token capacity is ``num_pages * page_size`` split
    however the allocator hands out pages — the knob that decouples slot
    count from max context."""
    head_dim = model.embed_dim // model.num_heads
    kvh = model.num_kv_heads or model.num_heads
    shape = (num_pages, page_size, kvh, head_dim)
    dtype, scaled = quant_ops.resolve_kv_dtype(kv_dtype or "model", model.dtype)

    def layer():
        planes = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        if scaled:
            planes["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
            planes["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
        return planes

    return {f"block_{i}": layer() for i in range(model.num_layers)}


def pool_page_size(pool: dict) -> int:
    """The pool's static page size, read off a K plane (one owner — callers
    never carry it separately and drift)."""
    return pool["block_0"]["k"].shape[1]


def _gather_view(pool: dict, table: jax.Array, seq_len: int) -> dict:
    """Materialize each slot's logical ``[S]`` cache view through the table:
    ``pool[table] → [B, P_max·ps, ...]`` truncated to ``[B, S, ...]``. The
    view is positionally identical to the contiguous plane at every mapped
    position; unmapped positions show null/recycled-page garbage the masks
    hide (module comment above)."""
    b, p_max = table.shape

    def leaf(x):
        ps = x.shape[1]
        v = x[table]                                   # [B, P, ps, ...]
        return v.reshape((b, p_max * ps) + x.shape[2:])[:, :seq_len]

    return jax.tree_util.tree_map(leaf, pool)


def paged_decode_step_slots(model: TransformerLM, params, pool: dict,
                            table: jax.Array, ids_t: jax.Array, t: jax.Array
                            ) -> tuple[dict, jax.Array]:
    """``decode_step_slots`` through a page table: ``pool`` per
    ``init_page_pool``, ``table: [B, P_max]`` int32 (data — the zero-retrace
    property extends to ANY page assignment), ``ids_t``/``t`` as contiguous.

    Gathers the table's view, runs the contiguous step on it (identical math,
    including quantize-on-write when scale pools are present), then scatters
    each slot's one written row back to ``(table[b, t//ps], t % ps)``. Slots
    whose table rows are null-mapped (inactive/parked) write their row into
    the null page — harmless by the reservation invariant."""
    b = ids_t.shape[0]
    s = model.seq_len
    ps = pool_page_size(pool)
    view = _gather_view(pool, table, s)
    new_view, log_probs = decode_step_slots(model, params, view, ids_t, t)

    safe_t = jnp.clip(t, 0, s - 1)      # decode's write clamps the same way
    pages = table[jnp.arange(b), safe_t // ps]                   # [B]
    offs = safe_t % ps

    def put(pool_leaf, view_leaf):
        rows = view_leaf[jnp.arange(b), safe_t]                  # [B, ...]
        return pool_leaf.at[pages, offs].set(rows)

    new_pool = jax.tree_util.tree_map(put, pool, new_view)
    return new_pool, log_probs


def paged_prefill_chunk(model: TransformerLM, params, pool: dict,
                        table: jax.Array, prompt: jax.Array, slot: jax.Array,
                        start: jax.Array, length: jax.Array, *,
                        chunk: int) -> dict:
    """``prefill_chunk`` through a page table — gathers only the ONE slot's
    view (``[1, S, ...]``, so per-chunk cost stays O(S) not O(B·S)), runs the
    contiguous chunk on it at batch index 0, and scatters the chunk's valid
    rows to their pages. No ``fresh`` wipe: paged slots never need one
    (module comment above)."""
    s = model.seq_len
    ps = pool_page_size(pool)
    p_max = table.shape[1]
    row_table = table[slot]                                      # [P_max]

    def leaf(x):
        v = x[row_table]                                         # [P, ps, ...]
        return v.reshape((p_max * ps,) + x.shape[2:])[:s][None]  # [1, S, ...]

    view = jax.tree_util.tree_map(leaf, pool)
    new_view = prefill_chunk(model, params, view, prompt[slot][None],
                             jnp.int32(0), start, length,
                             jnp.asarray(False), chunk=chunk)

    positions = start + jnp.arange(chunk, dtype=jnp.int32)       # [C]
    valid = (jnp.arange(chunk) < length) & (positions < s)
    safe_pos = jnp.clip(positions, 0, s - 1)
    page_of = row_table[safe_pos // ps]                          # [C]
    offs = safe_pos % ps

    def put(pool_leaf, view_leaf):
        rows = view_leaf[0, safe_pos]                            # [C, ...]
        pages = jnp.where(valid, page_of, pool_leaf.shape[0])    # OOB → drop
        return pool_leaf.at[pages, offs].set(rows, mode="drop")

    return jax.tree_util.tree_map(put, pool, new_view)


def paged_verify_chunk(model: TransformerLM, params, pool: dict,
                       table: jax.Array, ids: jax.Array, t: jax.Array,
                       draft: jax.Array, *, k: int
                       ) -> tuple[dict, jax.Array]:
    """``verify_chunk`` through a page table: full gather (verify reads every
    slot's cache, like decode), contiguous verify on the view, then a bulk
    ``[B, k+1]``-row scatter. Rows past ``seq_len`` drop; rows past a slot's
    reservation land in the null page — both rewritten-before-visible, same
    rollback argument as the contiguous docstring."""
    b = ids.shape[0]
    s = model.seq_len
    ps = pool_page_size(pool)
    w = k + 1
    view = _gather_view(pool, table, s)
    new_view, log_probs = verify_chunk(model, params, view, ids, t, draft, k=k)

    positions = t[:, None] + jnp.arange(w, dtype=jnp.int32)      # [B, W]
    safe_pos = jnp.clip(positions, 0, s - 1)
    in_range = positions < s
    page_of = jnp.take_along_axis(table, safe_pos // ps, axis=1)  # [B, W]
    offs = safe_pos % ps
    slot_idx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, w))

    def put(pool_leaf, view_leaf):
        rows = view_leaf[slot_idx, safe_pos]                     # [B, W, ...]
        pages = jnp.where(in_range, page_of, pool_leaf.shape[0])
        return pool_leaf.at[pages, offs].set(rows, mode="drop")

    new_pool = jax.tree_util.tree_map(put, pool, new_view)
    return new_pool, log_probs


def filter_logits(log_probs: jax.Array, *, top_k: int = 0,
                  top_p: float = 1.0) -> jax.Array:
    """Mask ``[..., V]`` logits outside the top-k set and/or the top-p nucleus.

    ``top_k = 0`` disables the k filter; ``top_p = 1.0`` disables the nucleus filter.
    The nucleus is the smallest prefix of the probability-sorted vocabulary whose
    mass reaches ``top_p`` (the argmax always survives). Filters compose — both masks
    apply when both are set. Input need not be normalized (temperature-scaled
    log-probs are fine); masked entries become ``MASK_VALUE`` so a downstream
    ``jax.random.categorical`` renormalizes over the survivors.
    """
    if top_k:
        kth = lax.top_k(log_probs, top_k)[0][..., -1:]
        log_probs = jnp.where(log_probs < kth, MASK_VALUE, log_probs)
    if top_p < 1.0:
        sorted_lp = jnp.sort(log_probs, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_lp, axis=-1)
        # Exclusive cumulative mass: position j is kept while the mass BEFORE it is
        # still < top_p, i.e. it is needed to reach the target mass. j=0 (the
        # argmax) is always kept.
        before = jnp.cumsum(probs, axis=-1) - probs
        kept = before < top_p
        # Value threshold = smallest kept sorted logit; ties at the threshold all
        # survive (harmless: they carry identical probability).
        thresh = jnp.min(jnp.where(kept, sorted_lp, jnp.inf), axis=-1,
                         keepdims=True)
        log_probs = jnp.where(log_probs < thresh, MASK_VALUE, log_probs)
    return log_probs


def generate(model: TransformerLM, params, rng: jax.Array, *, batch: int = 1,
             temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
             prompt: jax.Array | None = None,
             prompt_len: int = 0) -> jax.Array:
    """Sample ``[batch, seq_len]`` token streams from BOS, autoregressively.

    ``temperature <= 0`` decodes greedily. ``top_k`` / ``top_p`` restrict sampling to
    the k most likely tokens / the smallest nucleus with ``top_p`` probability mass
    (applied AFTER temperature scaling, composing in that order — the common
    convention). The loop is ``ceil(S / DECODE_SEGMENT)`` ``lax.scan`` segments
    (wrap in ``jax.jit`` for repeated use); per-step work is the KV-cache
    ``decode_step`` reading a static prefix that grows per segment, so cost is
    O(S²·E) total instead of the O(S³·E) of re-running the full forward per
    position, and the dominant HBM term (the cache re-read) is O(t) amortized.

    ``prompt`` (``[batch, seq_len]`` token ids) with ``prompt_len = K`` conditions the
    sample: the first ``K`` output positions are teacher-forced to the prompt (their
    K/V still populate the cache), and positions ``K..S-1`` are sampled — e.g. digit
    COMPLETION from the top rows of a real image. ``prompt_len`` must be a Python int
    (it selects statically which scan steps force; the forced tokens themselves are
    traced data).
    """
    # Host (numpy) checkpoints decode too: numpy leaves can't be indexed by traced
    # token ids inside the scan.
    params = jax.tree_util.tree_map(jnp.asarray, params)
    if not 0 <= top_k <= model.vocab_size:
        raise ValueError(f"top_k {top_k} outside [0, {model.vocab_size}]")
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p {top_p} outside (0, 1]")
    if prompt is None:
        prompt = jnp.zeros((batch, model.seq_len), jnp.int32)
        prompt_len = 0
    if not 0 <= prompt_len <= model.seq_len:
        raise ValueError(f"prompt_len {prompt_len} outside [0, {model.seq_len}]")
    if prompt.shape != (batch, model.seq_len):
        # Explicit: a [1, S] prompt with batch > 1 would silently broadcast one
        # forced prefix across the whole batch.
        raise ValueError(f"prompt shape {prompt.shape} != (batch, seq_len) = "
                         f"({batch}, {model.seq_len})")
    bos = jnp.full((batch,), model.vocab_size - 1, jnp.int32)

    def step(carry, scan_in, *, prefix_len):
        t, prompt_t = scan_in
        cache, ids_t, key = carry
        cache, log_probs = decode_step(model, params, cache, ids_t, t,
                                       prefix_len=prefix_len)
        # BOS is an input-only symbol (the tokenizer never produces it): mask its
        # logit so samples stay in the pixel vocabulary ids_to_images can invert.
        log_probs = log_probs.at[:, model.vocab_size - 1].set(MASK_VALUE)
        key, sub = jax.random.split(key)
        if temperature > 0:
            scaled = filter_logits(log_probs / temperature,
                                   top_k=top_k, top_p=top_p)
            nxt = jax.random.categorical(sub, scaled, axis=-1)
        else:
            nxt = jnp.argmax(log_probs, axis=-1)
        # Teacher-force the prompt region. The forced token conditions later steps
        # through the NEXT step's cache write (it becomes ids_t at t+1; decode_step
        # at t cached the PREVIOUS position's token).
        nxt = jnp.where(t < prompt_len, prompt_t, nxt).astype(jnp.int32)
        return (cache, nxt, key), nxt

    # Segmented scan: segment j's steps attend over a static prefix of
    # min((j+1)·DECODE_SEGMENT, S) cache rows instead of all S, so the dominant
    # decode HBM term (the per-step cache re-read) is O(t) amortized — ~2× less
    # traffic at S=784 — while every shape stays static (one compiled scan body
    # per segment, no dynamic control flow).
    positions = jnp.arange(model.seq_len, dtype=jnp.int32)
    prompt_cols = jnp.transpose(prompt.astype(jnp.int32))
    carry = (init_cache(model, batch), bos, rng)
    chunks = []
    for start in range(0, model.seq_len, DECODE_SEGMENT):
        stop = min(start + DECODE_SEGMENT, model.seq_len)
        carry, toks = lax.scan(
            functools.partial(step, prefix_len=stop), carry,
            (positions[start:stop], prompt_cols[start:stop]))
        chunks.append(toks)
    tokens = jnp.concatenate(chunks, axis=0)
    return jnp.transpose(tokens)          # [S, B] -> [B, S]
