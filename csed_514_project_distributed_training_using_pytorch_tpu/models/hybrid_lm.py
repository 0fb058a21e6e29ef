"""Decoder LM built from a published configuration file: a stack of
short-convolution, Mamba-2, delta-rule (KDA, gated delta net), GQA, latent-attention
(MLA), EVA, parallel (Mamba-2 beside GQA) and expert layers.

``TransformerLM`` (``models/lm.py``) is the repo's own pixel decoder; this module is
how a catalog architecture trains through ``train.lm``: the keys of the model's
public ``config.json`` build the stack (``from_config`` dispatches on ``model_type``),
and a file that also states this chip's share of a deployment (``share``: which
experts of every sparse layer, which heads and groups of every mixer and which slice
of the vocabulary are held here, which published layer comes first) builds that
share. ``LFM2-24B-A2B`` (``lfm2_moe``: ``layer_types``, ``num_dense_layers``) was the
first such file, ``NVIDIA-Nemotron-3-Super-120B-A12B`` (``nemotron_h``:
``hybrid_override_pattern``) the second, ``Kimi-Linear-48B-A3B`` (``kimi_linear``:
``linear_attn_config``'s 1-based lists of layers) the third, ``EvaByte`` (``evabyte``:
every layer an EVA mixer and a dense feed-forward, no expert anywhere, eight prediction
heads over 320 byte ids) the fourth, ``Kanana-2-30B-A3B`` (``deepseek_v3``: latent
attention in every layer with a rotated shared key, ``first_k_dense_replace`` dense layers
and then fine-grained experts beside shared ones) the fifth, ``Qwen3-Next-80B-A3B``
(``qwen3_next``: three gated delta-rule layers to one gated softmax attention, every layer
with softmax-routed experts beside a sigmoid-gated shared one) the sixth,
``Falcon-H1-34B`` (``falcon_h1``: every block a Mamba-2 mixer and a rotated GQA attention
that read ONE normed input side by side, a dense feed-forward, and the family's fourteen
forward multipliers on the activations) the seventh.

A layer is a block of two sublayers (``LAYER_KINDS``: its mixer) or one sublayer
alone (``SUBLAYER_KINDS``). Each kind is written once, as a function of its
parameters, the normalized input and the positions:

    block          h = x + mixer(RMSNorm(x));  y = h + ff(RMSNorm(h))
    sublayer       y = x + sublayer(RMSNorm(x)): a mamba mixer, an attention or an
                   expert feed-forward
    conv_mixer     [B, C, X] = split3(W_in u);  c_t = Σ_j w[j] ⊙ (B ⊙ X)_{t-L+1+j}
                   (depthwise, causal, zeros before the start);  W_out (C ⊙ c)
    mamba_mixer    [z | xBC | dt] = W_in u;  xBC = silu(conv(xBC) + bias);  [X | B | C] =
                   split(xBC);  Δ = softplus(dt + dt_bias), A = −exp(A_log) in float32;
                   per head S_t = exp(Δ_t A) S_{t−1} + Δ_t X_t ⊗ B_t, Y_t = S_t C_t + D X_t
                   (``ops/ssm.py``, heads of a group read its B, C; D is the leaf
                   ``D_scale``, one at the start as published);
                   W_out (w ⊙ RMSNorm_group(Y ⊙ silu(z)))
    attention      q, k RMS-normed per head (or not) before RoPE (half-split pairing, over
                   all of a head's channels or its first ``rope_dim``; or no positions
                   at all), causal softmax(q·k/√D)·v in groups, through the pluggable
                   ``attention_fn``; with ``attention_gate`` W_q is twice as wide and the
                   heads' output is scaled by the sigmoid of its second half before W_o
    gdn_mixer      [q̃ | k̃ | ṽ | z] = W_qkvz u, [b | a] = W_ba u;  (q̃, k̃, ṽ) = silu(conv(·));
                   β = sigmoid(b), g = −exp(A_log) · softplus(a + dt_bias), one number a
                   token and value head, float32. ``ops/kda.py``'s kernels at a scalar
                   decay (``gdn_scan``): the unit norms, S_t = e^{g_t} S_{t−1} + β_t k_t
                   (v_t − e^{g_t} S_{t−1}ᵀ k_t)ᵀ, value head h on key head h // rep, ô = o /
                   rms_head(o). Then W_o (w ⊙ ô ⊙ silu(z)): normed, then gated
    kda_mixer      q̃, k̃, ṽ = silu(conv(W u)) each;  g = −exp(A_log) · softplus(W_f↑ W_f↓ u +
                   dt_bias) a channel, β = sigmoid(W_β u) a head, float32; all flat,
                   ``[B, S, H·128]``. In ``ops/kda.py``'s kernels, on a head's block:
                   q = q̃/‖q̃‖·K^-½, k = k̃/‖k̃‖;  S_t = (I − β_t k_t k_tᵀ) Diag(e^{g_t})
                   S_{t−1} + β_t k_t v_tᵀ, o_t = S_tᵀ q_t;  ô = o / rms_head(o). Then
                   W_o (w ⊙ ô ⊙ sigmoid(W_g↑ W_g↓ u)), w the head norm's scale tiled
    mla_mixer      q = W_q u, a head [nope | pe];  [c | k_pe] = W_kva u;  [k_nope | v] =
                   W_kvb RMSNorm(c) a head;  a head's key is [k_nope | k_pe], k_pe the
                   same for every head; no positions (``rope_theta`` None), or every
                   head's q_pe and the one k_pe ``[B, S, pe]`` rotated (RoPE over the pe
                   channels alone, in the file's pairing) before k_pe is handed to the
                   heads; causal softmax(q·k/√(nope + pe))·v
                   through ``attention_fn`` at a key width that is not the value width
    eva_mixer      q, k = R(W_q u), R(W_k u) (RoPE over all of a head's channels), v = W_v u;
                   ``ops/eva.py``: a summary (k̃, ṽ) a chunk by the head's learned φ, μ;
                   one float32 softmax over the keys of the query's own window up to the
                   query and the summaries of every chunk before that window; W_o
    parallel       u = RMSNorm(x) once;  x + m_ssm_out · mamba_mixer(u) + m_attn_out ·
                   attention(m_attn_in · u): two mixers of different kinds on one input.
                   With ``multipliers`` (``Multipliers``: a ``falcon_h1`` file's fourteen) the
                   embedding's rows, the logits, the mamba mixer's input and the five segments
                   [z | x | B | C | dt] of its in-projection's output, the attention's keys
                   before they turn, the feed-forward's gate and its result are each scaled
                   where the published code scales them, as activations: folded into a
                   weight, a multiplier would change that weight's gradient and AdamW's step
    dense_ff       W_2 (silu(W_1 u) ⊙ W_3 u), over the held columns of a share
    sparse_ff      ``ops/moe.py``: sigmoid router over all experts, top-k of s + b (or a
                   softmax router, top-k of p, no b),
                   the held experts' part of the result, dropless. Experts are gated
                   (three matrices) on the model's rows, or relu² (two) on a latent row
                   (W_fc2 Σ_e w_e W2_e relu(W1_e W_fc1 u)²), beside a shared expert
                   that every token passes, W_s2 relu(W_s1 u)² or the gated W_s2
                   (silu(W_s1 u) ⊙ W_s3 u), added as it is or times sigmoid(w_g · u)

A share holds a mixer's heads as it holds experts: the out-projection sums over the
held heads (of a Mamba-2 layer: whole groups, each with its own B, C and its own
group of the gated norm), the shared expert over its held columns, and the partial
result is what goes on. The head is the embedding, tied, or a matrix of its own,
over the held slice of the vocabulary; the loss is
the mean next-token NLL over the ``S - 1`` targets of each sequence (position ``t``
predicts token ``t + 1``; there is no BOS id). With ``num_pred_heads`` P > 1 the head is
one ``[d, P·vocab]`` matrix, head ``i`` of place ``t`` predicts token ``t + 1 + i``, and the
loss is the mean over the heads and places that have a target. ``norm_unit_offset``
reads a norm's leaf (``…_offset``, zero at the start) as ``1 + g``; ``fp32_residual`` keeps
the residual stream in float32 between blocks whose matmuls run in ``dtype``, and each
norm's output of such a stream stands behind an optimization barrier (``normed``: a
row-tiled kernel pair for these norms was faster alone and 3.5 % slower in the step,
``bench_results/hw_pr41/``).
Parameters are a plain dict; ``init``
and ``apply`` keep flax's calling convention so ``train/step.py`` builds the state
as for any other model. ``remat`` recomputes each block in the backward pass from
its input and from what ``kept`` names (``KEPT`` unless the family says otherwise):
the flash kernel's output and statistics,
the router's and the sort's products, and the matmul outputs that fit the chip beside
the cell's state (everything else of a block runs again). The head runs once whatever
``remat`` says: its loss has a differentiation rule of its own (``head_nll``) whose
forward pass, asked for a gradient, takes the loss's gradient from the ``[T, vocab]``
logits it has just computed, so they are neither kept nor computed again.
No serving path: a short-convolution state beside keys and
values in the slot engine is ROADMAP R4's.
"""

from __future__ import annotations

import dataclasses
import functools
import json
from typing import Callable

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from csed_514_project_distributed_training_using_pytorch_tpu import ops
from csed_514_project_distributed_training_using_pytorch_tpu.models import Trainee
from csed_514_project_distributed_training_using_pytorch_tpu.ops import eva, kda, moe, ssm
from csed_514_project_distributed_training_using_pytorch_tpu.ops.rotary import (
    apply_rotary,
    rotation_form,
)

# a block: this mixer, then a feed-forward
LAYER_KINDS = ("conv", "full_attention", "kda", "mla", "eva", "gdn", "parallel")
SUBLAYER_KINDS = ("mamba", "attention", "moe")  # a layer that is one sublayer
# What ``remat`` keeps of a block between its forward and its backward pass, beside
# the block's input: the names of ``jax.ad_checkpoint.checkpoint_name`` tags, set
# where each value is born (here, ``ops/pallas_attention.py``, ``ops/moe.py``).
KEPT = ("flash_out", "flash_lse", "moe_route", "moe_sort", "mixer_out",
        "attn_proj", "conv_in_proj", "ff_gate",
        "ssd_out", "ssd_state", "mamba_in_proj", "moe_latent", "moe_routed",
        "shared_hidden", "kda_out", "kda_state", "mla_latent")
# What an ``evabyte`` stack keeps: the attention's output and statistics (its forward
# kernels are not run again). Beside six layers' 9.9 GB of state and float32 block
# inputs of 0.5 GB each at 32k tokens the chip has no room for a projection's output.
EVA_KEPT = ("eva_out", "eva_lse")
# What a ``deepseek_v3`` stack keeps: all of ``KEPT`` that it tags but ``attn_proj``. Six
# latent-attention layers' q (201 MB at 16,384 tokens) and kv_b output (268 MB) under that
# name do not fit beside 11.0 GB of state: the two products run again (0.55 TFLOP a layer).
MLA_KEPT = ("flash_out", "flash_lse", "mla_latent", "moe_route", "moe_sort", "mixer_out",
            "shared_hidden", "ff_gate")


# What a ``qwen3_next`` stack keeps: all of ``KEPT`` that it tags, and the delta layers'
# one projection (``[T, 12288]``, 0.40 GB a layer at 16,384 tokens: 0.82 TFLOP a layer not
# run again), which fits beside 10.0 GB of state where the other stacks' would not.
GDN_KEPT = KEPT + ("gdn_in_proj",)


# What a ``falcon_h1`` stack keeps: all of ``KEPT`` that it tags, and the feed-forward's
# up-projection (``[T, 5376]``, 88 MB a layer at 8,192 tokens: 0.45 TFLOP a layer not run
# again), which fits beside 9.2 GB of state at one sequence a step.
H1_KEPT = KEPT + ("ff_up",)


@dataclasses.dataclass(frozen=True)
class Multipliers:
    """A ``falcon_h1`` file's forward multipliers, each by the file's key (``ssm``:
    ``ssm_multipliers``, one a segment ``[z | x | B | C | dt]`` of the Mamba-2 in-projection's
    output; ``mlp``: ``mlp_multipliers``, the feed-forward's gate and its result)."""

    embedding: float = 1.0
    lm_head: float = 1.0
    attention_in: float = 1.0
    attention_out: float = 1.0
    key: float = 1.0
    ssm_in: float = 1.0
    ssm_out: float = 1.0
    ssm: tuple[float, float, float, float, float] = (1.0,) * 5
    mlp: tuple[float, float] = (1.0, 1.0)


@dataclasses.dataclass(frozen=True)
class HybridLM:
    """The model, or one chip's share of it. Widths are the published ones; the
    share is ``held_experts`` (first id, how many, of ``router_experts``),
    ``vocab_size`` (the slice's width) and, where a deployment divides the mixers,
    the heads, groups and shared-expert columns held."""

    vocab_size: int
    seq_len: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    layer_types: tuple[str, ...]        # one kind a layer, of LAYER_KINDS + SUBLAYER_KINDS
    num_dense_layers: int               # leading layers with the dense feed-forward
    router_experts: int
    held_experts: tuple[int, int]
    num_experts_per_tok: int
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float | None = 1e6      # None: attention without positions
    rope_interleave: bool = False       # an MLA mixer's rotated channels 2i, 2i + 1 turn together
    rope_dim: int | None = None         # a GQA head's leading channels that turn; None: all
    qk_norm: bool = True
    attention_gate: bool = False        # W_q twice as wide: a head's output times sigmoid(gate)
    attention_head_dim: int | None = None   # None: hidden_size / num_attention_heads
    tied_head: bool = True
    routed_scaling_factor: float = 1.0
    router_eps: float = 1e-6            # beside the selected scores' sum
    router_bias_update_rate: float = 0.0    # of the selection's bias a step; 0: held fixed
    router_scoring: str = "sigmoid"     # or "softmax", which has no selection bias
    gated_experts: bool = True          # swiglu over W1, W3; else relu² over W1
    moe_latent_size: int = 0            # the experts' row width, where not the model's
    shared_expert_size: int = 0         # held columns of the shared expert
    gated_shared_expert: bool = False   # swiglu over W_s1, W_s3; else relu² over W_s1
    shared_expert_gate: bool = False    # the shared expert times sigmoid(w_g · u), a token
    kda_heads: int = 0                  # heads of a KDA mixer
    kda_head_dim: int = 128             # a KDA or gdn head's key width, and its value width
    gdn_heads: tuple[int, int] = (0, 0)     # key heads, value heads of a gated delta layer
    kda_tiling: tuple[int, int, int] | None = None  # None: ops.kda's own for the decay's kind
    kv_lora_rank: int = 0               # the key/value latent of an MLA mixer
    qk_nope_head_dim: int = 128         # a head's key channels from the latent
    qk_rope_head_dim: int = 64          # and those every head shares (rotated with rope_theta)
    v_head_dim: int = 128
    mamba_heads: int = 0                # held heads of a Mamba-2 mixer, in whole groups
    mamba_groups: int = 1
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = ssm.CHUNK
    dtype: jnp.dtype = jnp.float32
    remat: bool = False
    attention_fn: Callable = ops.full_attention
    expert_block: int | None = None     # rows of a kernel step (None: ops.moe.ROW_TILE)
    eva_window: int = 0                 # keys an EVA query sees exactly: its own window's
    eva_chunk: int = 0                  # tokens a summary stands for
    num_pred_heads: int = 1             # head i of place t predicts token t + 1 + i
    norm_unit_offset: bool = False      # a norm's weight is 1 + its leaf
    fp32_residual: bool = False         # the residual stream in float32 whatever ``dtype``
    kept: tuple[str, ...] = KEPT        # what ``remat`` keeps of a block
    multipliers: Multipliers | None = None  # None: no activation is scaled, nothing is traced

    def __post_init__(self):
        odd = sorted(set(self.layer_types) - set(LAYER_KINDS + SUBLAYER_KINDS))
        if odd:
            raise ValueError(f"layer_types {odd} are not of "
                             f"{LAYER_KINDS + SUBLAYER_KINDS}")
        if self._has_ssm and (
                self.mamba_heads < 1 or self.mamba_heads % self.mamba_groups):
            raise ValueError(f"{self.mamba_groups} groups do not divide the "
                             f"{self.mamba_heads} heads of a mamba layer")
        if "kda" in self.layer_types and self.kda_heads < 1:
            raise ValueError("a kda layer needs kda_heads")
        key_heads, value_heads = self.gdn_heads
        if "gdn" in self.layer_types and (key_heads < 1 or value_heads % key_heads):
            raise ValueError(f"a gdn layer needs key heads ({key_heads}) that divide its "
                             f"value heads ({value_heads})")
        if self.router_scoring not in moe.SCORINGS:
            raise ValueError(f"router_scoring {self.router_scoring!r} is not of "
                             f"{moe.SCORINGS}")
        if "mla" in self.layer_types and self.kv_lora_rank < 1:
            raise ValueError("an mla layer needs kv_lora_rank")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide num_attention_heads")
        if "eva" in self.layer_types and (
                self.eva_chunk < 1 or self.eva_window % self.eva_chunk
                or self.seq_len % self.eva_window):
            raise ValueError(
                f"an eva layer needs a window ({self.eva_window}) of whole chunks "
                f"({self.eva_chunk}) and a sequence ({self.seq_len}) of whole windows")
        first, count = self.held_experts
        if self.sparse_layers and not 0 <= first < first + count <= self.router_experts:
            raise ValueError(f"held experts {self.held_experts} are not a range of "
                             f"the router's {self.router_experts}")

    # -- shapes -------------------------------------------------------------------

    @property
    def head_dim(self) -> int:
        """An attention head's query and key width."""
        if "mla" in self.layer_types:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.attention_head_dim or self.hidden_size // self.num_attention_heads

    @property
    def value_head_dim(self) -> int:
        return self.v_head_dim if "mla" in self.layer_types else self.head_dim

    @property
    def _has_ssm(self) -> bool:
        """Whether any layer holds a Mamba-2 mixer, alone or beside an attention."""
        return bool({"mamba", "parallel"} & set(self.layer_types))

    def is_sparse(self, layer: int) -> bool:
        """Whether layer ``layer`` holds an expert feed-forward."""
        kind = self.layer_types[layer]
        return kind == "moe" or (kind in LAYER_KINDS and layer >= self.num_dense_layers)

    @property
    def _norm_leaf(self) -> str:
        """A norm's leaf: its weight, or with ``norm_unit_offset`` what is added to one."""
        return "norm_offset" if self.norm_unit_offset else "norm_scale"

    @property
    def holds_norms(self) -> bool:
        """Whether a norm's output is held behind a barrier: a float32 stream under
        matmuls of a narrower ``dtype``."""
        return self.fp32_residual and jnp.dtype(self.dtype).itemsize < 4

    def normed(self, x, p, which: str = ""):
        """``RMSNorm(x)`` by ``p``'s leaf ``<which>norm_scale`` (``<which>norm_offset``: weight
        one plus the leaf), in ``dtype`` where the residual stream is float32. There the
        output stands behind an optimization barrier, and so, by the barrier's transpose,
        does its cotangent: left free, the compiler folded the norm's two backward
        reductions over the float32 rows into the epilogue of the product that makes the
        cotangent (14.4 ms where the product alone takes 8.2, six times a step of the
        ``evabyte`` cell) and the scale and cast into the products that read the output
        (PERF.md section 6, PR 41). A stream in ``dtype`` is left to the compiler, which
        fuses its norms into their neighbours at 1-5 % of a step."""
        u = ops.rms_norm(x, p[which + self._norm_leaf], eps=self.norm_eps,
                         offset=1.0 if self.norm_unit_offset else 0.0)
        if self.fp32_residual:
            u = u.astype(self.dtype)
        return jax.lax.optimization_barrier(u) if self.holds_norms else u

    def norm_plan(self) -> dict:
        """The ``compile`` event's ``norm`` field: how a step takes its norms of the
        stream (``barrier``: each output, and its cotangent, materialised apart from the
        products beside it; ``xla``: left to the compiler) and how many a forward pass."""
        blocks = sum(kind in LAYER_KINDS for kind in self.layer_types)
        return {"impl": "barrier" if self.holds_norms else "xla",
                "calls": len(self.layer_types) + blocks + 1}

    def targets_per_seq(self, seq_len: int | None = None) -> int:
        """The (place, head) pairs of a sequence (of ``seq_len`` tokens) that have a
        target: place ``t``'s head ``i`` predicts token ``t + 1 + i``."""
        heads, s = self.num_pred_heads, seq_len or self.seq_len
        return heads * (s - 1) - heads * (heads - 1) // 2

    @property
    def sparse_layers(self) -> int:
        return sum(self.is_sparse(i) for i in range(len(self.layer_types)))

    def ssm_plan(self) -> dict | None:
        """What a step asks of each state-space layer (``ops.ssm.scan_plan``), or None
        for a stack with none."""
        if not self._has_ssm:
            return None
        return ssm.scan_plan(heads=self.mamba_heads, groups=self.mamba_groups,
                             head_dim=self.mamba_head_dim, state=self.ssm_state_size,
                             seq_len=self.seq_len, chunk=self.chunk_size,
                             kept=self.kept if self.remat else ())

    def kda_plan(self) -> dict | None:
        """What a step asks of each KDA layer (``ops.kda.scan_plan``), or None for a
        stack with none."""
        if "kda" not in self.layer_types:
            return None
        return kda.scan_plan(heads=self.kda_heads, key_dim=self.kda_head_dim,
                             value_dim=self.kda_head_dim, seq_len=self.seq_len,
                             **self._kda_tiles, kept=self.kept if self.remat else ())

    def gdn_plan(self) -> dict | None:
        """What a step asks of each gated delta-rule layer (``ops.kda.scan_plan`` with key
        heads: the decay a scalar), or None for a stack with none."""
        if "gdn" not in self.layer_types:
            return None
        return kda.scan_plan(heads=self.gdn_heads[1], key_heads=self.gdn_heads[0],
                             key_dim=self.kda_head_dim, value_dim=self.kda_head_dim,
                             seq_len=self.seq_len, **self._kda_tiles,
                             kept=self.kept if self.remat else ())

    def eva_plan(self) -> dict | None:
        """What a step asks of each EVA layer (``ops.eva.attention_plan``), or None for
        a stack with none."""
        if "eva" not in self.layer_types:
            return None
        return eva.attention_plan(heads=self.num_attention_heads, head_dim=self.head_dim,
                                  seq_len=self.seq_len, window=self.eva_window,
                                  chunk=self.eva_chunk,
                                  kept=self.kept if self.remat else ())

    def rotary_plan(self) -> dict:
        """The ``compile`` event's ``rope_dim``, ``rope_pairing``, ``rope_theta`` and
        ``rotation`` of its ``attention`` field: how many of a head's query and key
        channels turn by their position (a latent-attention head's shared ones; else
        all), in which pairing, at which base, and which form of ``ops/rotary.py`` turns
        a query of this model's shape (``rotary.rotation_form``). None each for attention
        without positions. With ``attention_gate`` also ``output_gate``, what scales a
        head's output."""
        gate = {"output_gate": "sigmoid"} if self.attention_gate else {}
        if self.rope_theta is None:
            return dict(dict.fromkeys(
                ("rope_dim", "rope_pairing", "rope_theta", "rotation")), **gate)
        latent = "mla" in self.layer_types
        width = self.qk_nope_head_dim + self.qk_rope_head_dim if latent else self.head_dim
        turning = self.qk_rope_head_dim if latent else self.rope_dim or self.head_dim
        return {"rope_dim": turning,
                "rope_pairing": "interleaved" if self.rope_interleave else "half_split",
                "rope_theta": self.rope_theta,
                "rotation": rotation_form(
                    (1, self.seq_len, self.num_attention_heads, width), self.dtype,
                    interleaved=self.rope_interleave,
                    channels=(width - turning if latent else 0, turning)), **gate}

    @property
    def _kda_tiles(self) -> dict:
        return dict(zip(("chunk", "sub", "group"), self.kda_tiling or ()))

    def expert_plan(self, tokens: int) -> dict | None:
        """What a step of ``tokens`` tokens asks of each sparse layer
        (``ops.moe.expert_plan``), or None for a stack with none."""
        if not self.sparse_layers:
            return None
        plan = moe.expert_plan(tokens, top_k=self.num_experts_per_tok,
                               held=self.held_experts, block=self.expert_block)
        if self.router_scoring != "sigmoid":
            plan["scoring"] = self.router_scoring
        if self.router_bias_update_rate:
            plan["bias_update_rate"] = self.router_bias_update_rate
        return plan

    def rebalance(self, params, arrived):
        """A training step's ``after_update`` where ``router_bias_update_rate`` is set:
        ``arrived`` is what ``loss`` handed out beside the loss, ``(counts, load)`` with
        ``load [sparse layers, router_experts]`` the tokens that chose each expert in
        the step's forward pass. Returns the parameters with every sparse layer's
        ``expert_bias_b`` moved by ``ops.moe.rebalanced_bias``, and ``counts``."""
        counts, load = arrived
        sparse = [i for i in range(len(self.layer_types)) if self.is_sparse(i)]
        rows = {f"layer_{i}": row for i, row in zip(sparse, load)}

        def leaf(path, value):
            if not is_frozen(path):
                return value
            return moe.rebalanced_bias(value, rows[path[0].key],
                                       self.router_bias_update_rate)

        return jax.tree_util.tree_map_with_path(leaf, params), counts

    def recompute_plan(self, jaxpr) -> dict | None:
        """The ``compile`` event's ``recompute`` field: the names ``remat`` keeps and
        the bytes held under them between a step's forward and its backward pass
        (the blocks' inputs, kept under any policy, are not in it), summed from the
        values tagged with those names in ``jaxpr``: that of a program which
        differentiates the loss once (a train step, an epoch that scans it), where a
        kept tag stands once, in the forward pass (the backward pass of a
        ``jax.checkpoint`` takes the value as an input). None without ``remat``."""
        if not self.remat:
            return None
        kept = [v.aval for eqn in _equations(getattr(jaxpr, "jaxpr", jaxpr))
                if eqn.primitive.name == "name" and eqn.params["name"] in self.kept
                for v in eqn.outvars]
        return {"kept": list(self.kept),
                "kept_bytes": sum(a.size * a.dtype.itemsize for a in kept)}

    def head_products(self, jaxpr, tokens: int) -> int:
        """The ``compile`` event's ``head_products``: the matrix products in ``jaxpr``
        (a program that differentiates the loss once, as ``recompute_plan``'s) with an
        operand or a result of the logits' shape ``[tokens, vocab]``, ``tokens`` the
        rows of a step (``vocab`` every prediction head's ids side by side). Three are the
        mathematics (the logits and the two gradients made of theirs); a fourth is the
        logits computed again."""
        logits = (tokens, self.vocab_size * self.num_pred_heads)
        return sum(eqn.primitive.name == "dot_general"
                   and any(v.aval.shape == logits for v in (*eqn.invars, *eqn.outvars))
                   for eqn in _equations(getattr(jaxpr, "jaxpr", jaxpr)))

    def plans(self, jaxpr, step_tokens: int) -> dict:
        """The ``compile`` event's fields of this model, which ``telemetry.compile_event``
        writes whole: the plans above, None each for a stack with no such layer, of a
        program (``jaxpr``) that differentiates the loss once over ``step_tokens`` tokens."""
        return {"experts": self.expert_plan(step_tokens),
                "recompute": self.recompute_plan(jaxpr), "ssm": self.ssm_plan(),
                "kda": self.kda_plan(), "gdn": self.gdn_plan(), "eva": self.eva_plan(),
                "norm": self.norm_plan(),
                "multipliers": self.multipliers and dataclasses.asdict(self.multipliers),
                "head_products": self.head_products(jaxpr, step_tokens)}

    def trainee(self, *, deterministic: bool = True, label_smoothing: float = 0.0) -> Trainee:
        """What ``train/lm.py`` trains and evaluates. The stack has no dropout and its loss
        no smoothing, so the trainer refuses either knob for a model from a file."""
        del deterministic, label_smoothing
        # whether any mixer calls ``attention_fn`` (an EVA mixer has a core of its own)
        dispatches = {"full_attention", "attention", "mla", "parallel"} \
            & set(self.layer_types)
        return Trainee(
            # (loss, rows that arrived at each held expert); the targets are the inputs
            loss=lambda params, xs, ys, rng: self.loss(params, xs),
            eval_nll=lambda params, batch: self.nll(params, batch)[0],
            targets_per_seq=self.targets_per_seq(), has_aux=True,
            after_update=self.rebalance if self.router_bias_update_rate else None,
            is_frozen=is_frozen,
            attention_shape=(self.num_attention_heads, self.head_dim, self.value_head_dim)
            if dispatches else None,
            attention_fields=self.rotary_plan(), plans=self.plans,
            expert_block=(self.expert_plan(1) or {}).get("block"))

    def param_shapes(self) -> dict:
        d, hd = self.hidden_size, self.head_dim
        heads, kv = self.num_attention_heads, self.num_key_value_heads
        held, f = self.held_experts[1], self.moe_intermediate_size
        norm = self._norm_leaf
        tree = {"embed_tokens": (self.vocab_size, d), f"final_{norm}": (d,)}
        if not self.tied_head:
            tree["lm_head_kernel"] = (d, self.vocab_size * self.num_pred_heads)
        attn = {"q_kernel": (d, heads * hd * (2 if self.attention_gate else 1)),
                "k_kernel": (d, kv * hd),
                "v_kernel": (d, kv * hd), "out_kernel": (heads * hd, d)}
        if self.qk_norm:
            attn.update({f"q_{norm}": (hd,), f"k_{norm}": (hd,)})
        # expert_bias_b: the selection's bias. Fixed (is_frozen): its gradient
        # is zero and no update rule is published.
        row = self.moe_latent_size or d
        experts = {"router_kernel": (d, self.router_experts),
                   "experts_w1_kernel": (row, held * f),
                   "experts_w2_kernel": (f, held * row)}
        if self.router_scoring == "sigmoid":
            experts["expert_bias_b"] = (self.router_experts,)
        if self.gated_experts:
            experts["experts_w3_kernel"] = (row, held * f)
        if self.moe_latent_size:
            experts.update(fc1_latent_kernel=(d, row), fc2_latent_kernel=(row, d))
        if self.shared_expert_size:
            experts.update(shared_w1_kernel=(d, self.shared_expert_size),
                           shared_w2_kernel=(self.shared_expert_size, d))
        if self.gated_shared_expert:
            experts["shared_w3_kernel"] = (d, self.shared_expert_size)
        if self.shared_expert_gate:
            experts["shared_gate_kernel"] = (d, 1)
        wide, low = self.kda_heads * self.kda_head_dim, self.kda_head_dim
        delta = {f"{name}_{leaf}": shape for name in "qkv" for leaf, shape in (
            ("kernel", (d, wide)), ("conv_kernel", (self.conv_kernel, wide)))}
        delta.update(f_a_kernel=(d, low), f_b_kernel=(low, wide), dt_bias=(wide,),
                     A_log=(self.kda_heads,), b_kernel=(d, self.kda_heads),
                     g_a_kernel=(d, low), g_b_kernel=(low, wide), o_norm_scale=(low,),
                     out_kernel=(wide, d))
        latent = {"q_kernel": (d, heads * self.head_dim),
                  "kv_a_kernel": (d, self.kv_lora_rank + self.qk_rope_head_dim),
                  "kv_a_norm_scale": (self.kv_lora_rank,),
                  "kv_b_kernel": (self.kv_lora_rank,
                                  heads * (self.qk_nope_head_dim + self.v_head_dim)),
                  "out_kernel": (heads * self.v_head_dim, d)}
        keys, values = (n * self.kda_head_dim for n in self.gdn_heads)
        gated_delta = {"qkvz_kernel": (d, 2 * keys + 2 * values),
                       "conv_kernel": (self.conv_kernel, 2 * keys + values),
                       "ba_kernel": (d, 2 * self.gdn_heads[1]),
                       "A_log": (self.gdn_heads[1],), "dt_bias": (self.gdn_heads[1],),
                       "o_norm_scale": (low,), "out_kernel": (values, d)}
        summarised = dict({name: shape for name, shape in attn.items() if "norm" not in name},
                          adaptive_phi=(heads, hd), adaptive_mu_k=(heads, hd))
        mixers = {"full_attention": ("attn", attn), "kda": ("kda", delta),
                  "mla": ("mla", latent), "eva": ("eva", summarised),
                  "gdn": ("gdn", gated_delta)}
        inner = self.mamba_heads * self.mamba_head_dim
        conv_width = inner + 2 * self.mamba_groups * self.ssm_state_size
        mamba = {"in_proj_kernel": (d, inner + conv_width + self.mamba_heads),
                 "conv_kernel": (self.conv_kernel, conv_width),
                 "conv_bias": (conv_width,), "dt_bias": (self.mamba_heads,),
                 "A_log": (self.mamba_heads,), "D_scale": (self.mamba_heads,),
                 "gate_norm_scale": (inner,), "out_proj_kernel": (inner, d)}
        alone = {"mamba": ("mamba", mamba), "attention": ("attn", attn),
                 "moe": ("moe", experts)}
        for i, kind in enumerate(self.layer_types):
            if kind in SUBLAYER_KINDS:
                group, leaves = alone[kind]
                tree[f"layer_{i}"] = {norm: (d,), group: dict(leaves)}
                continue
            layer = {f"mixer_{norm}": (d,), f"ff_{norm}": (d,)}
            if kind == "conv":
                layer["conv"] = {"in_proj_kernel": (d, 3 * d),
                                 "conv_kernel": (self.conv_L_cache, d),
                                 "out_proj_kernel": (d, d)}
            elif kind == "parallel":
                layer.update(mamba=dict(mamba), attn=dict(attn))
            else:
                group, leaves = mixers[kind]
                layer[group] = dict(leaves)
            if i < self.num_dense_layers:
                layer["ff"] = {"w1_kernel": (d, self.intermediate_size),
                               "w3_kernel": (d, self.intermediate_size),
                               "w2_kernel": (self.intermediate_size, d)}
            else:
                layer["moe"] = dict(experts)
            tree[f"layer_{i}"] = layer
        return tree

    # -- flax's calling convention --------------------------------------------------

    def init(self, rngs, sample=None) -> dict:
        """``{"params": tree}``: kernels normal(0, 1/sqrt(fan_in)), the embedding and
        a mamba or KDA layer's ``A_log`` normal(0, 0.02), norm scales and ``D_scale`` one,
        biases (the selection's among them) and a norm's ``…_offset`` zero."""
        del sample
        key = rngs["params"] if isinstance(rngs, dict) else rngs
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            self.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))
        leaves = []
        for n, (path, shape) in enumerate(flat):
            name = path[-1].key
            if name.endswith("scale"):
                leaves.append(jnp.ones(shape, jnp.float32))
            elif name == "expert_bias_b" or name.endswith(("bias", "norm_offset")):
                leaves.append(jnp.zeros(shape, jnp.float32))
            else:
                std = 0.02 if name == "embed_tokens" or len(shape) < 2 else shape[0] ** -0.5
                leaves.append(std * jax.random.normal(jax.random.fold_in(key, n),
                                                      shape, jnp.float32))
        return {"params": jax.tree_util.tree_unflatten(treedef, leaves)}

    def apply(self, variables, ids, **_):
        """``[B, S]`` ids -> ``[B, S, vocab]`` float32 log-probabilities of the
        next token (``[B, S, P, vocab]`` with P prediction heads: of the next P)."""
        hidden, _ = self.hidden_states(variables["params"], ids)
        logits = self._logits(self._head(variables["params"]), hidden)
        if self.num_pred_heads > 1:
            logits = logits.reshape(*ids.shape, self.num_pred_heads, self.vocab_size)
        return ops.log_softmax(logits)

    # -- forward --------------------------------------------------------------------

    def _blocks(self, params, ids, layers: int | None = None):
        """The embedding and the first ``layers`` blocks (all when None):
        ``(x, positions, [counts of each sparse layer run])``."""
        ids = ids.astype(jnp.int32)
        positions = jnp.arange(ids.shape[1])
        with jax.named_scope("embed"):
            x = ops.embedding_rows(params["embed_tokens"].astype(
                jnp.float32 if self.fp32_residual else self.dtype), ids)
            if self.multipliers:
                x = x * self.multipliers.embedding
        counts = []
        for i, kind in enumerate(self.layer_types[:layers]):
            fn = make_block(self, kind, self.is_sparse(i))
            if self.remat:
                fn = jax.checkpoint(
                    fn, policy=jax.checkpoint_policies.save_only_these_names(*self.kept))
            x, arrived = fn(params[f"layer_{i}"], x, positions)
            if arrived is not None:
                counts.append(arrived)
        return x, positions, counts

    def hidden_states(self, params, ids) -> tuple[jax.Array, jax.Array | None]:
        """``(final-normed hidden [B, S, d], counts [sparse layers, held] | None)``:
        the rows that arrived at each held expert of each sparse layer; with
        ``router_bias_update_rate`` set, ``(counts, load [sparse layers,
        router_experts])``, the tokens that chose each of the router's experts."""
        x, _, counts = self._blocks(params, ids)
        with jax.named_scope("final_norm"):
            x = self.normed(x, params, "final_")
        if not counts:
            return x, None
        return x, jax.tree_util.tree_map(lambda *layers: jnp.stack(layers), *counts)

    def router_choices(self, params, ids, layer: int) -> jax.Array:
        """The experts ``[B, S, k]`` (ids over all the router's experts) that sparse
        layer ``layer`` selects: a diagnostic, for tests and for the benchmark's
        count of selections a lower precision moves."""
        if not self.is_sparse(layer):
            raise ValueError(f"layer {layer} holds no expert layer")
        x, positions, _ = self._blocks(params, ids, layer)
        p, kind = params[f"layer_{layer}"], self.layer_types[layer]
        if kind in SUBLAYER_KINDS:
            u = self.normed(x, p)
        else:
            u = self.normed(mix(p, x, positions, kind, self), p, "ff_")
        return routed(p["moe"], u.reshape(-1, u.shape[-1]), self)[1].reshape(*ids.shape, -1)

    def _head(self, params):
        """The head's leaf: the embedding ``[vocab, d]``, tied, or ``[d, vocab]``."""
        return params["embed_tokens" if self.tied_head else "lm_head_kernel"]

    def _logits(self, head, hidden):
        """``hidden · head`` in float32, as one ``[B·S, d] x [d, vocab]`` product, so
        that the vocabulary is the logits' minor axis (as ``bsd,vd->bsv`` the compiler
        made it the sequence, and the step's temporaries 0.5 GB larger)."""
        b, s, d = hidden.shape
        rows = hidden.reshape(b * s, d)
        head = head.astype(self.dtype)
        flat = jnp.matmul(rows, head.T if self.tied_head else head,
                          preferred_element_type=jnp.float32)
        if self.multipliers:
            flat = flat * self.multipliers.lm_head
        return flat.reshape(b, s, -1)

    def nll(self, params, tokens) -> tuple[jax.Array, jax.Array | None]:
        """``(summed NLL over the B·targets_per_seq targets, counts)``."""
        hidden, counts = self.hidden_states(params, tokens)
        # Row t's target is token t + 1 (head i's: token t + 1 + i). The last rows have
        # none: their log-probabilities are computed and dropped, which keeps the head's
        # matmul at S rows.
        with jax.named_scope("head_loss"):
            ids = tokens.astype(jnp.int32)
            if self.num_pred_heads == 1:
                targets = jnp.roll(ids, -1, axis=1)[..., None]
            else:
                targets = jnp.stack([jnp.roll(ids, -(1 + i), axis=1)
                                     for i in range(self.num_pred_heads)], axis=-1)
            return head_nll(self, self._head(params), hidden, targets), counts

    def loss(self, params, tokens) -> tuple[jax.Array, jax.Array | None]:
        """``(mean NLL over the targets, counts)``: the training objective."""
        total, counts = self.nll(params, tokens)
        return total / (tokens.shape[0] * self.targets_per_seq(tokens.shape[1])), counts


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs among its equations' parameters."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def _summed_nll(model: HybridLM, table, hidden, targets):
    """``-Σ log softmax(hidden · table)[target]`` over every row but each sequence's
    last, by plain ``jax.numpy``; with P prediction heads over every (row t, head i) with
    ``t + 1 + i < S``, each head's softmax over its own ``vocab`` logits."""
    logits = model._logits(table, hidden)
    heads = model.num_pred_heads
    if heads > 1:       # [B, S, P, vocab] against targets [B, S, P, 1]
        logits = logits.reshape(*logits.shape[:-1], heads, model.vocab_size)
        targets = targets[..., None]
    # The row maximum behind a barrier: fused with the subtraction, the compiler took
    # it with a reduce-window as wide as the vocabulary (61 ms a pass on the v5e where
    # the logits' product needs 6).
    top = jax.lax.optimization_barrier(
        jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True)))
    shifted = logits - top
    picked = jnp.take_along_axis(shifted, targets, axis=-1)[..., 0] \
        - jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    if heads == 1:
        return -jnp.sum(picked[:, :-1])
    s = picked.shape[1]
    has_target = jnp.arange(s)[:, None] + 1 + jnp.arange(heads)[None, :] < s
    return -jnp.sum(jnp.where(has_target, picked, 0.0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def head_nll(model: HybridLM, table, hidden, targets):
    """The summed next-token NLL of ``hidden [B, S, d]`` under the head's ``table``
    (``model._head``'s leaf), ``targets [B, S, P]`` a row's next P tokens (P the prediction
    heads, 1 for most). Alone it is
    ``_summed_nll``. Differentiated, its forward pass also takes that function's
    gradient with respect to table and hidden states, at a cotangent of one, while it
    holds the ``[T, vocab]`` logits (the products autodiff makes of them: nothing of
    their size outlives the pass, and nothing of the head runs again), and its
    backward pass scales the two by the cotangent that arrives. One program on one
    device: ``train/lm.py`` refuses ``--model-config`` on a mesh of several, so no
    sharded logits reach the rule yet."""
    return _summed_nll(model, table, hidden, targets)


def _head_nll_fwd(model, table, hidden, targets):
    total, pull = jax.vjp(lambda t, h: _summed_nll(model, t, h, targets), table, hidden)
    d_table, d_hidden = pull(jnp.ones_like(total))
    # The table's gradient is held in the model's dtype, which ``_logits``' cast of the
    # table has rounded it to already, until the optimizer wants it. All three behind one
    # barrier: the hidden states' is wanted at once, the table's after every block and the
    # loss's value at the step's end, and left to the scheduler the table's product and
    # the gather of the targets' logits waited there with their [T, vocab] operand.
    total, d_table, d_hidden = jax.lax.optimization_barrier(
        (total, d_table.astype(model.dtype), d_hidden))
    return total, (d_table, d_hidden)


def _head_nll_bwd(model, held, cotangent):
    del model
    d_table, d_hidden = held
    # scaled in the cotangent's float32, which is a parameter's dtype (``init``)
    return cotangent * d_table, (cotangent * d_hidden).astype(d_hidden.dtype), None


head_nll.defvjp(_head_nll_fwd, _head_nll_bwd)


# The ``jax.named_scope`` of each kind of mixer. A sublayer's pre-norm and residual are
# inside its scope (``moe/norm`` and ``moe/residual`` for the experts, whose parts have
# scopes of their own), so that a trace joined to the scopes (``utils.profiling.scope_of``)
# leaves unnamed only what escaped: device time is read by kind, never by layer index.
MIXER_SCOPES = {"conv": "conv_mixer", "full_attention": "attention", "attention": "attention",
                "kda": "kda_mixer", "mla": "mla_attention", "mamba": "mamba_mixer",
                "eva": "eva_mixer", "gdn": "gdn_mixer", "parallel": "parallel_mixer"}


def make_block(model: HybridLM, kind: str, sparse: bool):
    """``block(p, x, positions) -> (y, counts | None)`` of one layer."""

    def experts(p, x, which):
        with jax.named_scope("moe/norm"):
            u = model.normed(x, p, which)
        out, counts = sparse_ff(p["moe"], u, model)
        with jax.named_scope("moe/residual"):
            return x + out, counts

    def sublayer(p, x, positions):
        if kind == "moe":
            return experts(p, x, "")
        with jax.named_scope(MIXER_SCOPES[kind]):
            u = model.normed(x, p)
            return x + (mamba_mixer(p["mamba"], u, model) if kind == "mamba"
                        else attention_mixer(p["attn"], u, positions, model)), None

    if kind in SUBLAYER_KINDS:
        return sublayer

    def block(p, x, positions):
        h = mix(p, x, positions, kind, model)
        if sparse:
            return experts(p, h, "ff_")
        with jax.named_scope("dense_ff"):
            u = model.normed(h, p, "ff_")
            return h + dense_ff(p["ff"], u, model.multipliers and model.multipliers.mlp), None

    return block


def mix(p, x, positions, kind: str, model: HybridLM):
    """``x + mixer(RMSNorm(x))``: the first half of a block."""
    with jax.named_scope(MIXER_SCOPES[kind]):
        u = model.normed(x, p, "mixer_")
        if kind == "conv":
            mixed = conv_mixer(p["conv"], u)
        elif kind == "kda":
            mixed = kda_mixer(p["kda"], u, model)
        elif kind == "gdn":
            mixed = gdn_mixer(p["gdn"], u, model)
        elif kind == "mla":
            mixed = mla_mixer(p["mla"], u, positions, model)
        elif kind == "eva":
            mixed = eva_mixer(p["eva"], u, positions, model)
        elif kind == "parallel":
            mixed = parallel_mixers(p, u, positions, model)
        else:
            mixed = attention_mixer(p["attn"], u, positions, model)
        return checkpoint_name(x + mixed, "mixer_out")


def _dense(x, kernel):
    return ops.dense(x, kernel.astype(x.dtype))


def causal_depthwise_conv(z: jax.Array, kernel: jax.Array) -> jax.Array:
    """``z [B, S, C]``, ``kernel [L, C]``: ``c_t = Σ_j kernel[j] ⊙ z_{t-L+1+j}``,
    zeros before the sequence's start."""
    taps, s = kernel.shape[0], z.shape[1]
    padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(kernel[j].astype(z.dtype) * padded[:, j:j + s] for j in range(taps))


def conv_mixer(p, u):
    b, c, x = jnp.split(
        checkpoint_name(_dense(u, p["in_proj_kernel"]), "conv_in_proj"), 3, axis=-1)
    return _dense(c * causal_depthwise_conv(b * x, p["conv_kernel"]),
                  p["out_proj_kernel"])


def mamba_mixer(p, u, model: HybridLM):
    b, s, _ = u.shape
    heads, groups = model.mamba_heads, model.mamba_groups
    hd, n = model.mamba_head_dim, model.ssm_state_size
    inner, bc = heads * hd, groups * n
    scaled = model.multipliers
    if scaled:
        u = u * scaled.ssm_in
    projected = checkpoint_name(_dense(u, p["in_proj_kernel"]), "mamba_in_proj")
    if scaled:      # one number a segment [z | x | B | C | dt] of the columns
        projected = projected * jnp.concatenate(
            [jnp.full((width,), value, projected.dtype)
             for width, value in zip((inner, inner, bc, bc, heads), scaled.ssm)])
    z, xbc, dt = jnp.split(projected, [inner, 2 * inner + 2 * bc], axis=-1)
    xbc = jax.nn.silu(causal_depthwise_conv(xbc, p["conv_kernel"])
                      + p["conv_bias"].astype(xbc.dtype))
    x, b_in, c_out = jnp.split(xbc, [inner, inner + bc], axis=-1)
    x = x.reshape(b, s, heads, hd)
    step = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    decay = -jnp.exp(p["A_log"].astype(jnp.float32))
    y = ssm.ssd_scan(x, step, step * decay, b_in.reshape(b, s, groups, n),
                     c_out.reshape(b, s, groups, n), chunk=model.chunk_size)
    y = y.astype(jnp.float32) + p["D_scale"].astype(jnp.float32)[:, None] * x
    normed = gated_group_norm(y.reshape(b, s, inner), z, p["gate_norm_scale"],
                              groups, model.norm_eps)
    return _dense(normed.astype(u.dtype), p["out_proj_kernel"])


def gated_group_norm(y, z, scale, groups: int, eps: float):
    """``scale ⊙ RMSNorm_group(y ⊙ silu(z))``, float32: gated first, then normed, a
    group of the norm being a group's channels."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = gated.reshape(*gated.shape[:-1], groups, -1)
    return ops.rms_norm(grouped, scale.reshape(groups, -1), eps=eps).reshape(gated.shape)


def attention_mixer(p, u, positions, model: HybridLM, core=None):
    """``core(q, k, v)`` in the model's ``attention_fn``'s place, where the mixer's
    attention is not plain causal softmax (``eva_mixer``)."""
    b, s, _ = u.shape
    heads, kv, hd = (model.num_attention_heads, model.num_key_value_heads,
                     model.head_dim)
    # Named as the matmuls wrote them, not after the norm and the rotation: the
    # norm's backward pass reads its input, so its output kept spares no matmul.
    # With ``attention_gate`` W_q's columns are every head's query and then every head's
    # gate: the gates are the ``heads`` further "heads" of the projection.
    q, k, v = (checkpoint_name(_dense(u, p[f"{name}_kernel"]), "attn_proj")
               .reshape(b, s, n, hd) for name, n in (
                   ("q", heads * (2 if model.attention_gate else 1)), ("k", kv), ("v", kv)))
    if model.multipliers:
        k = k * model.multipliers.key
    if model.attention_gate:
        q, gate = q[:, :, :heads], q[:, :, heads:]

    def turned(x):      # the rotation, over the whole head or its first ``rope_dim`` channels
        with jax.named_scope("rotary"):
            return apply_rotary(x, positions, base=model.rope_theta,
                                channels=(0, model.rope_dim) if model.rope_dim else None)

    def placed(x, which):       # per-head norm, then the rotation; either or neither
        if model.qk_norm:
            x = ops.rms_norm(x, p[f"{which}_{model._norm_leaf}"], eps=model.norm_eps,
                             offset=1.0 if model.norm_unit_offset else 0.0)
        return x if model.rope_theta is None else turned(x)

    q, k = placed(q, "q"), placed(k, "k")
    k, v = (jnp.repeat(x, heads // kv, axis=2) for x in (k, v))
    out = core(q, k, v) if core else model.attention_fn(q, k, v, causal=True)
    if model.attention_gate:
        out = out * jax.nn.sigmoid(gate)
    return _dense(out.reshape(b, s, heads * hd), p["out_kernel"])


def kda_mixer(p, u, model: HybridLM):
    """Flat from the projections to the output projection: a head's channels are 128
    lanes of ``[B, S, H·128]``, and what is one number a token and head (the unit norms
    of q and k, β, the output norm's statistic) is ``ops/kda.py``'s, inside its kernels."""
    heads, hd = model.kda_heads, model.kda_head_dim
    f32 = jnp.float32

    def branch(name):       # projection, short convolution, silu
        return jax.nn.silu(causal_depthwise_conv(_dense(u, p[f"{name}_kernel"]),
                                                 p[f"{name}_conv_kernel"]))

    low_rank = lambda name: _dense(_dense(u, p[f"{name}_a_kernel"]),
                                   p[f"{name}_b_kernel"]).astype(f32)
    rate = jax.nn.softplus(low_rank("f") + p["dt_bias"].astype(f32))
    decay = rate * jnp.repeat(-jnp.exp(p["A_log"].astype(f32)), hd)
    beta = jax.nn.sigmoid(_dense(u, p["b_kernel"]).astype(f32))
    normed = kda.kda_scan(branch("q"), branch("k"), branch("v"), decay, beta,
                          eps=model.norm_eps, **model._kda_tiles)
    scaled = normed.astype(f32) * jnp.tile(p["o_norm_scale"].astype(f32), heads)
    return _dense((scaled * jax.nn.sigmoid(low_rank("g"))).astype(u.dtype),
                  p["out_kernel"])


def gdn_mixer(p, u, model: HybridLM):
    """Flat as ``kda_mixer``: one projection to ``[q̃ | k̃ | ṽ | z]`` (the key heads' queries,
    their keys, the value heads' values and gates, in that order of columns), one
    convolution over the first three, and ``ops/kda.py``'s kernels at a scalar decay, in
    which value head ``h`` reads key head ``h // rep``. The output is normed a head (inside
    the kernels, the learned scale here) and THEN gated by ``silu(z)``."""
    (key_heads, value_heads), hd = model.gdn_heads, model.kda_head_dim
    keys, f32 = key_heads * hd, jnp.float32
    qkv, z = jnp.split(checkpoint_name(_dense(u, p["qkvz_kernel"]), "gdn_in_proj"),
                       [2 * keys + value_heads * hd], axis=-1)
    q, k, v = jnp.split(jax.nn.silu(causal_depthwise_conv(qkv, p["conv_kernel"])),
                        [keys, 2 * keys], axis=-1)
    b, a = jnp.split(_dense(u, p["ba_kernel"]).astype(f32), 2, axis=-1)
    decay = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(a + p["dt_bias"].astype(f32))
    normed = kda.gdn_scan(q, k, v, decay, jax.nn.sigmoid(b), key_heads=key_heads,
                          eps=model.norm_eps, **model._kda_tiles)
    scaled = normed.astype(f32) * jnp.tile(p["o_norm_scale"].astype(f32), value_heads)
    return _dense((scaled * jax.nn.silu(z.astype(f32))).astype(u.dtype), p["out_kernel"])


def mla_mixer(p, u, positions, model: HybridLM):
    """With ``rope_theta`` the last ``qk_rope_head_dim`` channels of every head's query and
    the shared key turn by their position, the key once, ``[B, S, 1, pe]``, before the heads
    are handed it; without, both are carried as they are (``mla_use_nope``)."""
    b, s, _ = u.shape
    heads, nope, rank = model.num_attention_heads, model.qk_nope_head_dim, model.kv_lora_rank
    q = checkpoint_name(_dense(u, p["q_kernel"]), "attn_proj").reshape(b, s, heads, -1)
    latent, shared_key = jnp.split(
        checkpoint_name(_dense(u, p["kv_a_kernel"]), "mla_latent"), [rank], axis=-1)
    latent = ops.rms_norm(latent, p["kv_a_norm_scale"], eps=model.norm_eps)
    own_key, v = jnp.split(
        checkpoint_name(_dense(latent, p["kv_b_kernel"]), "attn_proj")
        .reshape(b, s, heads, -1), [nope], axis=-1)
    shared_key = shared_key[:, :, None]         # one key, a head axis of one
    if model.rope_theta is not None:
        with jax.named_scope("rotary"):
            turn = functools.partial(apply_rotary, positions=positions,
                                     base=model.rope_theta,
                                     interleaved=model.rope_interleave)
            q = turn(q, channels=(nope, q.shape[-1] - nope))
            shared_key = turn(shared_key)
    k = jnp.concatenate([own_key, jnp.broadcast_to(
        shared_key, (b, s, heads, shared_key.shape[-1]))], axis=-1)
    out = model.attention_fn(q, k, v, causal=True)
    return _dense(out.reshape(b, s, -1), p["out_kernel"])


def eva_mixer(p, u, positions, model: HybridLM):
    """``attention_mixer``'s projections and rotation around ``ops/eva.py``'s attention
    with the held heads' φ and μ; the out-projection sums over the held heads."""
    return attention_mixer(p, u, positions, model, core=lambda q, k, v: eva.eva_attention(
        q, k, v, p["adaptive_phi"], p["adaptive_mu_k"],
        window=model.eva_window, chunk=model.eva_chunk))


def parallel_mixers(p, u, positions, model: HybridLM):
    """What a ``parallel`` block's two mixers add to the stream: both read the block's one
    normed input ``u``, and neither waits for the other."""
    scaled = model.multipliers or Multipliers()
    with jax.named_scope("ssm"):
        state_space = mamba_mixer(p["mamba"], u, model)
    with jax.named_scope("attention"):
        attended = attention_mixer(p["attn"], u * scaled.attention_in, positions, model)
    return scaled.ssm_out * state_space + scaled.attention_out * attended


def dense_ff(p, u, multipliers: tuple[float, float] | None = None):
    """``multipliers``: what scales the gate's product before its silu and the result."""
    # ``W1 u`` is kept and ``W3 u`` recomputed: beside the cell's state the chip
    # has room for one ``[T, intermediate]`` array more, not for two (PERF.md §6);
    # a stack that has room for both keeps ``ff_up`` too (``H1_KEPT``).
    gate = checkpoint_name(_dense(u, p["w1_kernel"]), "ff_gate")
    if multipliers:
        gate = gate * multipliers[0]
    up = checkpoint_name(_dense(u, p["w3_kernel"]), "ff_up")
    out = _dense(ops.swiglu(gate, up), p["w2_kernel"])
    return out * multipliers[1] if multipliers else out


def routed(p, flat, model: HybridLM, load: bool = False):
    """``ops.moe.route`` as this model's expert layers call it: ``sparse_ff`` and the
    diagnostic ``HybridLM.router_choices`` route alike."""
    return moe.route(flat, p["router_kernel"], p.get("expert_bias_b"),
                     top_k=model.num_experts_per_tok, scaling=model.routed_scaling_factor,
                     eps=model.router_eps, load=load, scoring=model.router_scoring)


def sparse_ff(p, u, model: HybridLM):
    b, s, d = u.shape
    flat = u.reshape(b * s, d)
    balanced = bool(model.router_bias_update_rate)
    weights, experts, *load = routed(p, flat, model, load=balanced)
    rows = flat
    if model.moe_latent_size:
        with jax.named_scope("moe/latent"):
            rows = checkpoint_name(_dense(flat, p["fc1_latent_kernel"]), "moe_latent")
    out, counts = moe.held_experts_ffn(
        rows, weights, experts, p["experts_w1_kernel"],
        p["experts_w3_kernel"] if model.gated_experts else None,
        p["experts_w2_kernel"], held=model.held_experts, block=model.expert_block)
    if model.moe_latent_size:
        with jax.named_scope("moe/latent"):
            # the experts' sum is an operand of W_fc2's gradient: not kept, the
            # sort's gather, ``moe_ffn_fwd`` and the combine would run again for it
            out = _dense(checkpoint_name(out, "moe_routed"), p["fc2_latent_kernel"])
    if model.shared_expert_size:
        with jax.named_scope("moe/shared"):
            hidden = checkpoint_name(_dense(flat, p["shared_w1_kernel"]), "shared_hidden")
            hidden = (ops.swiglu(hidden, _dense(flat, p["shared_w3_kernel"]))
                      if model.gated_shared_expert else jnp.square(jax.nn.relu(hidden)))
            shared = _dense(hidden, p["shared_w2_kernel"])
            if model.shared_expert_gate:
                shared = shared * jax.nn.sigmoid(_dense(flat, p["shared_gate_kernel"]))
            out = out + shared
    return out.reshape(b, s, d), ((counts, *load) if balanced else counts)


def is_frozen(path) -> bool:
    """Leaves the optimizer leaves alone (``optim.freeze``): the selection's bias,
    which only ``HybridLM.rebalance`` moves."""
    return str(getattr(path[-1], "key", path[-1])) == "expert_bias_b"


def from_config(config: dict, *, vocab_size: int, seq_len: int, **kwargs) -> HybridLM:
    """The model a configuration file describes: the published keys at the top
    level, and, for one chip's share of a deployment, the keys that count layers,
    experts, heads, groups, shared-expert or feed-forward columns and ids as held here,
    with ``share`` (``first_layer``, ``first_expert``, ``mlp_columns``) and ``published``
    (the router's width, the heads of the whole layer) beside
    them. ``model_type`` names the family (``lfm2_moe`` when absent). ``vocab_size``
    is the corpus's and has to be the file's."""
    if int(config["vocab_size"]) != int(vocab_size):
        raise ValueError(f"the corpus has {vocab_size} ids, the configuration's "
                         f"vocabulary (slice) has {config['vocab_size']}")
    family = config.get("model_type", "lfm2_moe")
    if family not in _FAMILIES:
        raise ValueError(f"model_type {family!r} is not of {sorted(_FAMILIES)}")
    first = int(config.get("share", {}).get("first_layer", 0))
    depth = int(config["num_hidden_layers"])
    pattern, fields = _FAMILIES[family](config)
    first = fields.pop("first_layer", first)    # a family that numbers its layers from 1
    if first < 0 or len(pattern[first:first + depth]) != depth:
        raise ValueError("the layer pattern is shorter than first_layer + "
                         "num_hidden_layers")
    for key, as_ in (("num_experts_per_tok", int), ("norm_eps", float),
                     ("intermediate_size", int), ("moe_intermediate_size", int)):
        if key not in fields:       # a family whose file names it otherwise hands it over
            fields[key] = as_(config[key])
    return HybridLM(vocab_size=int(vocab_size), seq_len=int(seq_len),
                    hidden_size=int(config["hidden_size"]),
                    num_attention_heads=int(config["num_attention_heads"]),
                    num_key_value_heads=int(config["num_key_value_heads"]),
                    layer_types=tuple(pattern[first:first + depth]),
                    routed_scaling_factor=float(config.get("routed_scaling_factor", 1.0)),
                    **fields, **kwargs)


def _held_experts(config: dict, count_key: str) -> dict:
    """``router_experts`` and ``held_experts`` from the key that counts the experts."""
    share, published = config.get("share", {}), config.get("published", {})
    return {"router_experts": int(published.get(count_key, config[count_key])),
            "held_experts": (int(share.get("first_expert", 0)), int(config[count_key]))}


def _refuse(unwritten: dict) -> None:
    """``unwritten``: what a file may state that this module does not compute, each by
    name with whether the file states it. The first that is stated raises."""
    for what, stated in unwritten.items():
        if stated:
            raise ValueError(f"{what} is not written here")


def _lfm2_moe(config: dict) -> tuple[list, dict]:
    """``layer_types`` whole; ``num_dense_layers`` leading blocks with the dense
    feed-forward, the others sparse; tied head."""
    if config.get("conv_bias"):
        raise ValueError("conv_bias true is not written here (no catalog model has it)")
    return list(config["layer_types"]), dict(
        _held_experts(config, "num_experts"),
        num_dense_layers=int(config["num_dense_layers"]),
        conv_L_cache=int(config["conv_L_cache"]),
        rope_theta=float(config["rope_parameters"]["rope_theta"]))


_NEMOTRON_LETTERS = {"M": "mamba", "*": "attention", "E": "moe"}


def _nemotron_h(config: dict) -> tuple[list, dict]:
    """``hybrid_override_pattern`` whole, a letter a layer, each layer one sublayer;
    attention without positions or q/k norm; relu² experts on a latent row beside a
    shared expert; an untied head. What the file states and this module does not
    compute is refused, not ignored."""
    odd = sorted(set(config["hybrid_override_pattern"]) - set(_NEMOTRON_LETTERS))
    unwritten = {
        f"layers {odd} of hybrid_override_pattern": bool(odd),
        "a bias on a projection": any(config.get(k) for k in (
            "attention_bias", "mlp_bias", "mamba_proj_bias", "use_bias")),
        "a convolution without bias": not config.get("use_conv_bias", True),
        "grouped expert selection":
            (config.get("n_group", 1), config.get("topk_group", 1)) != (1, 1),
        "an activation other than silu in the mixer and relu2 in the experts": (
            config.get("mamba_hidden_act", "silu"), config.get("mlp_hidden_act", "relu2"))
        != ("silu", "relu2"),
        "multi-token prediction (num_nextn_predict_layers > 0: the trainer has no "
        "such loss)": bool(config.get("num_nextn_predict_layers", 0)),
        "selected scores that are not normalised": not config.get("norm_topk_prob", True),
    }
    _refuse(unwritten)
    return [_NEMOTRON_LETTERS[c] for c in config["hybrid_override_pattern"]], dict(
        _held_experts(config, "n_routed_experts"), num_dense_layers=0,
        rope_theta=None, qk_norm=False, attention_head_dim=int(config["head_dim"]),
        tied_head=bool(config.get("tie_word_embeddings", False)),
        router_eps=1e-20, gated_experts=False,
        router_bias_update_rate=float(config.get("moe_router_bias_update_rate", 0.0)),
        moe_latent_size=int(config.get("moe_latent_size") or 0),
        shared_expert_size=int(config.get("share", {}).get(
            "shared_expert_columns", int(config["moe_shared_expert_intermediate_size"])
            * int(config.get("n_shared_experts", 1)))),
        mamba_heads=int(config["mamba_num_heads"]), mamba_groups=int(config["n_groups"]),
        mamba_head_dim=int(config["mamba_head_dim"]),
        ssm_state_size=int(config["ssm_state_size"]),
        conv_kernel=int(config["conv_kernel"]), chunk_size=int(config["chunk_size"]))


def _kimi_linear(config: dict) -> tuple[list, dict]:
    """``linear_attn_config``'s lists whole, layers numbered from 1 (so is
    ``share.first_layer``, 1 when absent: the pattern has a layer 0 that no share can
    start at): a KDA or a latent-attention mixer a layer, the first
    ``first_k_dense_replace`` layers with the dense feed-forward and the others with gated
    experts beside a gated shared expert; no positions; an untied head. What the file
    states and this module does not compute is refused, not ignored."""
    linear = config["linear_attn_config"]
    kinds = {**{int(i): "kda" for i in linear["kda_layers"]},
             **{int(i): "mla" for i in linear["full_attn_layers"]}}
    published = int(config.get("published", {}).get("num_hidden_layers",
                                                    config["num_hidden_layers"]))
    first = int(config.get("share", {}).get("first_layer", 1))
    unwritten = {
        "a query latent (q_lora_rank not null)": config.get("q_lora_rank") is not None,
        "rope_scaling not null": config.get("rope_scaling") is not None,
        "rotated shared key channels (mla_use_nope false)":
            not config.get("mla_use_nope", False),
        "grouped expert selection (num_expert_group, topk_group other than 1)":
            (config.get("num_expert_group", 1), config.get("topk_group", 1)) != (1, 1),
        "multi-token prediction (num_nextn_predict_layers > 0: the trainer has no "
        "such loss)": bool(config.get("num_nextn_predict_layers", 0)),
        "moe_layer_freq other than 1": config.get("moe_layer_freq", 1) != 1,
        "hidden_act other than silu": config.get("hidden_act", "silu") != "silu",
        "moe_router_activation_func other than sigmoid":
            config.get("moe_router_activation_func", "sigmoid") != "sigmoid",
        "selected scores that are not normalised (moe_renormalize false)":
            not config.get("moe_renormalize", True),
        "layers of linear_attn_config's lists that are not 1 to the published depth, "
        "each once": sorted(kinds) != list(range(1, published + 1))
        or len(kinds) != len(linear["kda_layers"]) + len(linear["full_attn_layers"]),
        "share.first_layer 0 (the file's lists count layers from 1)": first < 1,
    }
    _refuse(unwritten)
    return [None] + [kinds[i] for i in range(1, published + 1)], dict(
        _held_experts(config, "num_experts"), first_layer=first,
        num_dense_layers=max(0, int(config["first_k_dense_replace"]) - (first - 1)),
        num_experts_per_tok=int(config["num_experts_per_token"]),
        norm_eps=float(config["rms_norm_eps"]), rope_theta=None, qk_norm=False,
        tied_head=bool(config.get("tie_word_embeddings", False)), router_eps=1e-20,
        router_bias_update_rate=float(config.get("moe_router_bias_update_rate", 0.0)),
        shared_expert_size=int(config["moe_intermediate_size"])
        * int(config.get("num_shared_experts", 0)),
        gated_shared_expert=bool(config.get("num_shared_experts", 0)),
        kda_heads=int(linear["num_heads"]), kda_head_dim=int(linear["head_dim"]),
        conv_kernel=int(linear["short_conv_kernel_size"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]))


def _evabyte(config: dict) -> tuple[list, dict]:
    """Every layer an EVA mixer and a dense gated feed-forward; no expert layer, no
    leading layer of another kind; RoPE over a head's whole width; norms with the unit
    offset, the residual stream in float32, ``num_pred_heads`` heads in one untied matrix.
    A share holds ``num_attention_heads`` of ``published.num_attention_heads`` heads (a
    head's width is the published one's) and ``share.mlp_columns`` of the feed-forward's
    ``intermediate_size`` columns. What the file states and this module does not compute
    is refused, not ignored."""
    share, published = config.get("share", {}), config.get("published", {})
    heads = int(config["num_attention_heads"])
    window, chunk = int(config["window_size"]), int(config["chunk_size"])
    unwritten = {
        "attention_class other than eva": config.get("attention_class", "eva") != "eva",
        "num_chunks not null": config.get("num_chunks") is not None,
        "rope_scaling not null": config.get("rope_scaling") is not None,
        "window_size that is not a multiple of chunk_size": bool(window % chunk),
        "hidden_act other than silu": config.get("hidden_act", "silu") != "silu",
        "attention_bias true": bool(config.get("attention_bias", False)),
        "tie_word_embeddings true": bool(config.get("tie_word_embeddings", False)),
        "num_key_value_heads other than num_attention_heads":
            int(config["num_key_value_heads"]) != heads,
        "share.heads other than num_attention_heads":
            int(share.get("heads", heads)) != heads,
    }
    _refuse(unwritten)
    depth = int(published.get("num_hidden_layers", config["num_hidden_layers"]))
    return ["eva"] * depth, dict(
        num_dense_layers=depth, router_experts=0, held_experts=(0, 0),
        num_experts_per_tok=0, moe_intermediate_size=0,
        intermediate_size=int(share.get("mlp_columns", config["intermediate_size"])),
        norm_eps=float(config["rms_norm_eps"]), rope_theta=float(config["rope_theta"]),
        qk_norm=False, tied_head=False,
        attention_head_dim=int(config["hidden_size"])
        // int(published.get("num_attention_heads", heads)),
        eva_window=window, eva_chunk=chunk,
        num_pred_heads=int(config.get("num_pred_heads", 1)),
        norm_unit_offset=bool(config.get("norm_add_unit_offset", False)),
        fp32_residual=bool(config.get("fp32_skip_add", False)), kept=EVA_KEPT)


def _deepseek_v3(config: dict) -> tuple[list, dict]:
    """Every layer a latent-attention mixer whose shared key channels (and every head's
    last ``qk_rope_head_dim`` query channels) turn by RoPE at ``rope_theta``, in the
    interleaved pairing where ``rope_interleave`` says so; layers numbered from 0, the first
    ``first_k_dense_replace`` with the dense feed-forward and the others with gated experts
    (sigmoid scores, ``num_experts_per_tok`` of ``n_routed_experts`` by score + bias, no
    groups) beside ``n_shared_experts`` shared ones built as one gated expert of
    ``n_shared_experts × moe_intermediate_size`` columns; an untied head. ``head_dim`` is the
    rotary width again and read nowhere. What the file states and this module does not
    compute is refused, not ignored."""
    _refuse({
        "a query latent (q_lora_rank not null)": config.get("q_lora_rank") is not None,
        "rope_scaling not null": config.get("rope_scaling") is not None,
        "grouped expert selection (n_group, topk_group other than 1)":
            (config.get("n_group", 1), config.get("topk_group", 1)) != (1, 1),
        "scoring_func other than sigmoid": config.get("scoring_func", "sigmoid") != "sigmoid",
        "topk_method other than noaux_tc":
            config.get("topk_method", "noaux_tc") != "noaux_tc",
        "selected scores that are not normalised (norm_topk_prob false)":
            not config.get("norm_topk_prob", True),
        "moe_layer_freq other than 1": config.get("moe_layer_freq", 1) != 1,
        "hidden_act other than silu": config.get("hidden_act", "silu") != "silu",
        "attention_bias true": bool(config.get("attention_bias", False)),
        "num_key_value_heads other than num_attention_heads":
            int(config["num_key_value_heads"]) != int(config["num_attention_heads"]),
        "multi-token prediction (num_nextn_predict_layers > 0: the trainer has no "
        "such loss)": bool(config.get("num_nextn_predict_layers", 0)),
    })
    first = int(config.get("share", {}).get("first_layer", 0))
    depth = int(config.get("published", {}).get("num_hidden_layers",
                                                config["num_hidden_layers"]))
    shared = int(config.get("n_shared_experts") or 0)
    return ["mla"] * depth, dict(
        _held_experts(config, "n_routed_experts"),
        num_dense_layers=max(0, int(config["first_k_dense_replace"]) - first),
        norm_eps=float(config["rms_norm_eps"]), rope_theta=float(config["rope_theta"]),
        rope_interleave=bool(config.get("rope_interleave", False)), qk_norm=False,
        tied_head=bool(config.get("tie_word_embeddings", False)), router_eps=1e-20,
        router_bias_update_rate=float(config.get("moe_router_bias_update_rate", 0.0)),
        shared_expert_size=shared * int(config["moe_intermediate_size"]),
        gated_shared_expert=bool(shared),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]), kept=MLA_KEPT)


def _qwen3_next(config: dict) -> tuple[list, dict]:
    """Layers numbered from 0: softmax attention where ``(i + 1) % full_attention_interval``
    is 0 (grouped-query at ``head_dim``, an output gate in W_q's second half, ``1 + w`` head
    norms, RoPE over the first ``partial_rotary_factor`` of a head's channels), else the
    gated delta rule (``linear_*``: key heads shared by value heads, one decay a token and
    value head); every layer with gated experts (softmax over all, ``num_experts_per_tok`` of
    them renormalised, no selection bias) beside one shared expert scaled by a sigmoid
    gate; every stream norm ``1 + w``; an untied head. What the file states and this module
    does not compute is refused, not ignored."""
    turned = int(config["head_dim"]) * float(config.get("partial_rotary_factor", 1.0))
    key_heads, value_heads = (int(config[f"linear_num_{x}_heads"]) for x in ("key", "value"))
    _refuse({
        "rope_scaling not null": config.get("rope_scaling") is not None,
        "use_sliding_window true": bool(config.get("use_sliding_window", False)),
        "mlp_only_layers that is not empty": bool(config.get("mlp_only_layers")),
        "decoder_sparse_step other than 1": config.get("decoder_sparse_step", 1) != 1,
        "selected scores that are not normalised (norm_topk_prob false)":
            not config.get("norm_topk_prob", True),
        "hidden_act other than silu": config.get("hidden_act", "silu") != "silu",
        "attention_bias true": bool(config.get("attention_bias", False)),
        "a partial_rotary_factor whose rotated width is not an even number of channels":
            turned != int(turned) or int(turned) % 2 != 0,
        "linear_num_key_heads that do not divide linear_num_value_heads":
            bool(value_heads % key_heads),
        "linear_key_head_dim other than linear_value_head_dim":
            config["linear_key_head_dim"] != config["linear_value_head_dim"],
        "multi-token prediction (num_nextn_predict_layers or mtp_num_hidden_layers > 0: "
        "the trainer has no such loss)": bool(config.get("num_nextn_predict_layers", 0))
        or bool(config.get("mtp_num_hidden_layers", 0)),
    })
    depth = int(config.get("published", {}).get("num_hidden_layers",
                                                config["num_hidden_layers"]))
    every = int(config["full_attention_interval"])
    return ["gdn" if (i + 1) % every else "full_attention" for i in range(depth)], dict(
        _held_experts(config, "num_experts"), num_dense_layers=0,
        norm_eps=float(config["rms_norm_eps"]), norm_unit_offset=True,
        rope_theta=float(config["rope_theta"]), rope_dim=int(turned), qk_norm=True,
        attention_head_dim=int(config["head_dim"]), attention_gate=True,
        tied_head=bool(config.get("tie_word_embeddings", False)),
        router_scoring="softmax", router_eps=0.0,
        shared_expert_size=int(config["shared_expert_intermediate_size"]),
        gated_shared_expert=True, shared_expert_gate=True,
        gdn_heads=(key_heads, value_heads), kda_head_dim=int(config["linear_key_head_dim"]),
        conv_kernel=int(config["linear_conv_kernel_dim"]), kept=GDN_KEPT)


def _falcon_h1(config: dict) -> tuple[list, dict]:
    """Every layer a ``parallel`` block (a Mamba-2 mixer and a GQA attention rotated over a
    head's whole width, on one normed input) and a dense gated feed-forward; no expert layer;
    an untied head; the fourteen forward multipliers as ``Multipliers``. A share holds
    ``num_attention_heads`` query heads on ``num_key_value_heads`` key/value heads,
    ``mamba_n_heads`` heads in ``mamba_n_groups`` whole groups (``share.mamba_channels`` of the
    whole model's ``mamba_d_ssm`` channels, which like ``intermediate_size`` keeps its published
    value) and ``share.mlp_columns`` of the feed-forward's ``intermediate_size`` columns; a group
    of the gated norm is a held group's channels. What the file states and this module does not
    compute is refused, not ignored."""
    share, published = config.get("share", {}), config.get("published", {})
    heads, width = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    whole = int(published.get("mamba_n_heads", heads)) * width
    _refuse({
        "attn_layer_indices not null": config.get("attn_layer_indices") is not None,
        "mamba_use_mlp false": not config.get("mamba_use_mlp", True),
        "mamba_norm_before_gate true": bool(config.get("mamba_norm_before_gate", False)),
        "mamba_rms_norm false": not config.get("mamba_rms_norm", True),
        "a bias on a projection": any(config.get(k) for k in (
            "attention_bias", "mamba_proj_bias", "mlp_bias", "projectors_bias")),
        "a convolution without bias (mamba_conv_bias false)":
            not config.get("mamba_conv_bias", True),
        "rope_scaling not null": config.get("rope_scaling") is not None,
        "tie_word_embeddings true": bool(config.get("tie_word_embeddings", False)),
        "hidden_act other than silu": config.get("hidden_act", "silu") != "silu",
        "mamba_d_ssm other than the whole model's mamba_n_heads x mamba_d_head":
            int(config.get("mamba_d_ssm") or whole) != whole,
        "share.mamba_channels other than mamba_n_heads x mamba_d_head":
            int(share.get("mamba_channels", heads * width)) != heads * width,
        "ssm_multipliers that are not five, or mlp_multipliers that are not two":
            (len(config["ssm_multipliers"]), len(config["mlp_multipliers"])) != (5, 2),
    })
    depth = int(published.get("num_hidden_layers", config["num_hidden_layers"]))
    return ["parallel"] * depth, dict(
        num_dense_layers=depth, router_experts=0, held_experts=(0, 0),
        num_experts_per_tok=0, moe_intermediate_size=0,
        intermediate_size=int(share.get("mlp_columns", config["intermediate_size"])),
        norm_eps=float(config["rms_norm_eps"]), rope_theta=float(config["rope_theta"]),
        qk_norm=False, tied_head=False, attention_head_dim=int(config["head_dim"]),
        mamba_heads=heads, mamba_groups=int(config["mamba_n_groups"]), mamba_head_dim=width,
        ssm_state_size=int(config["mamba_d_state"]), conv_kernel=int(config["mamba_d_conv"]),
        chunk_size=int(config["mamba_chunk_size"]), kept=H1_KEPT,
        multipliers=Multipliers(
            ssm=tuple(map(float, config["ssm_multipliers"])),
            mlp=tuple(map(float, config["mlp_multipliers"])),
            **{name: float(config[f"{name}_multiplier"]) for name in (
                "embedding", "lm_head", "attention_in", "attention_out", "key",
                "ssm_in", "ssm_out")}))


_FAMILIES = {"lfm2_moe": _lfm2_moe, "nemotron_h": _nemotron_h,
             "kimi_linear": _kimi_linear, "evabyte": _evabyte,
             "deepseek_v3": _deepseek_v3, "qwen3_next": _qwen3_next,
             "falcon_h1": _falcon_h1}


def from_config_file(path: str, **kwargs) -> HybridLM:
    with open(path) as fh:
        return from_config(json.load(fh), **kwargs)
