"""Decoder LM built from a published configuration file: a stack of
short-convolution and QK-norm GQA blocks with dense or sparse gated feed-forwards.

``TransformerLM`` (``models/lm.py``) is the repo's own pixel decoder; this module is
how a catalog architecture trains through ``train.lm``: the keys of the model's
public ``config.json`` (``layer_types``, ``num_dense_layers``, widths, ``norm_eps``,
``rope_parameters``) build the stack, and a file that also states this chip's share
of a deployment (``share``: which experts of every sparse layer and which slice of
the vocabulary are held here, which published layer comes first) builds that share.
``LFM2-24B-A2B`` (``model_type`` ``lfm2_moe``) is the first such file.

Each block kind is written once, as a function of the block's parameters, the
normalized input and the positions:

    block          h = x + mixer(RMSNorm(x));  y = h + ff(RMSNorm(h))
    conv_mixer     [B, C, X] = split3(W_in u);  c_t = Σ_j w[j] ⊙ (B ⊙ X)_{t-L+1+j}
                   (depthwise, causal, zeros before the start);  W_out (C ⊙ c)
    attention      q, k RMS-normed per head before RoPE (half-split pairing), causal
                   softmax(q·k/√D)·v in groups, through the pluggable ``attention_fn``
    dense_ff       W_2 (silu(W_1 u) ⊙ W_3 u)
    sparse_ff      ``ops/moe.py``: sigmoid router over all experts, top-k of s + b,
                   the held experts' part of the result, dropless

The head is the embedding, tied, over the held slice of the vocabulary; the loss is
the mean next-token NLL over the ``S - 1`` targets of each sequence (position ``t``
predicts token ``t + 1``; there is no BOS id). Parameters are a plain dict; ``init``
and ``apply`` keep flax's calling convention so ``train/step.py`` builds the state
as for any other model. ``remat`` recomputes each block in the backward pass from
its input and from what ``KEPT`` names: the flash kernel's output and statistics,
the router's and the sort's products, and the matmul outputs that fit the chip beside
the cell's state (everything else of a block, and the head's logits, runs again).
No serving path: a short-convolution state beside keys and
values in the slot engine is ROADMAP R4's.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Callable

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from csed_514_project_distributed_training_using_pytorch_tpu import ops
from csed_514_project_distributed_training_using_pytorch_tpu.ops import moe
from csed_514_project_distributed_training_using_pytorch_tpu.ops.rotary import (
    apply_rotary,
)

LAYER_KINDS = ("conv", "full_attention")
# What ``remat`` keeps of a block between its forward and its backward pass, beside
# the block's input: the names of ``jax.ad_checkpoint.checkpoint_name`` tags, set
# where each value is born (here, ``ops/pallas_attention.py``, ``ops/moe.py``).
KEPT = ("flash_out", "flash_lse", "moe_route", "moe_sort", "mixer_out",
        "attn_proj", "conv_in_proj", "ff_gate")


@dataclasses.dataclass(frozen=True)
class HybridLM:
    """The model, or one chip's share of it. Widths are the published ones; the
    share is ``held_experts`` (first id, how many, of ``router_experts``) and
    ``vocab_size`` (the slice's width)."""

    vocab_size: int
    seq_len: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    layer_types: tuple[str, ...]        # one kind a layer, of LAYER_KINDS
    num_dense_layers: int               # leading layers with the dense feed-forward
    router_experts: int
    held_experts: tuple[int, int]
    num_experts_per_tok: int
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    routed_scaling_factor: float = 1.0
    dtype: jnp.dtype = jnp.float32
    remat: bool = False
    attention_fn: Callable = ops.full_attention
    expert_block: int | None = None     # rows of a kernel step (None: ops.moe.ROW_TILE)

    def __post_init__(self):
        odd = sorted(set(self.layer_types) - set(LAYER_KINDS))
        if odd:
            raise ValueError(f"layer_types {odd} are not of {LAYER_KINDS}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide num_attention_heads")
        first, count = self.held_experts
        if not 0 <= first < first + count <= self.router_experts:
            raise ValueError(f"held experts {self.held_experts} are not a range of "
                             f"the router's {self.router_experts}")

    # -- shapes -------------------------------------------------------------------

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def sparse_layers(self) -> int:
        return max(0, len(self.layer_types) - self.num_dense_layers)

    def expert_plan(self, tokens: int) -> dict | None:
        """What a step of ``tokens`` tokens asks of each sparse layer
        (``ops.moe.expert_plan``), or None for a stack with none."""
        if not self.sparse_layers:
            return None
        return moe.expert_plan(tokens, top_k=self.num_experts_per_tok,
                               held=self.held_experts, block=self.expert_block)

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
                if eqn.primitive.name == "name" and eqn.params["name"] in KEPT
                for v in eqn.outvars]
        return {"kept": list(KEPT),
                "kept_bytes": sum(a.size * a.dtype.itemsize for a in kept)}

    def param_shapes(self) -> dict:
        d, hd = self.hidden_size, self.head_dim
        heads, kv = self.num_attention_heads, self.num_key_value_heads
        held, f = self.held_experts[1], self.moe_intermediate_size
        tree = {"embed_tokens": (self.vocab_size, d), "final_norm_scale": (d,)}
        for i, kind in enumerate(self.layer_types):
            layer = {"mixer_norm_scale": (d,), "ff_norm_scale": (d,)}
            if kind == "conv":
                layer["conv"] = {"in_proj_kernel": (d, 3 * d),
                                 "conv_kernel": (self.conv_L_cache, d),
                                 "out_proj_kernel": (d, d)}
            else:
                layer["attn"] = {"q_kernel": (d, heads * hd), "k_kernel": (d, kv * hd),
                                 "v_kernel": (d, kv * hd), "out_kernel": (heads * hd, d),
                                 "q_norm_scale": (hd,), "k_norm_scale": (hd,)}
            if i < self.num_dense_layers:
                layer["ff"] = {"w1_kernel": (d, self.intermediate_size),
                               "w3_kernel": (d, self.intermediate_size),
                               "w2_kernel": (self.intermediate_size, d)}
            else:
                # expert_bias_b: the selection's bias. Fixed (is_frozen): its gradient
                # is zero and no update rule is published.
                layer["moe"] = {"router_kernel": (d, self.router_experts),
                                "expert_bias_b": (self.router_experts,),
                                "experts_w1_kernel": (d, held * f),
                                "experts_w3_kernel": (d, held * f),
                                "experts_w2_kernel": (f, held * d)}
            tree[f"layer_{i}"] = layer
        return tree

    # -- flax's calling convention --------------------------------------------------

    def init(self, rngs, sample=None) -> dict:
        """``{"params": tree}``: kernels normal(0, 1/sqrt(fan_in)), the embedding
        normal(0, 0.02), norm scales one, the selection's bias zero."""
        del sample
        key = rngs["params"] if isinstance(rngs, dict) else rngs
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            self.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))
        leaves = []
        for n, (path, shape) in enumerate(flat):
            name = path[-1].key
            if name.endswith("scale"):
                leaves.append(jnp.ones(shape, jnp.float32))
            elif name == "expert_bias_b":
                leaves.append(jnp.zeros(shape, jnp.float32))
            else:
                std = 0.02 if name == "embed_tokens" else shape[0] ** -0.5
                leaves.append(std * jax.random.normal(jax.random.fold_in(key, n),
                                                      shape, jnp.float32))
        return {"params": jax.tree_util.tree_unflatten(treedef, leaves)}

    def apply(self, variables, ids, **_):
        """``[B, S]`` ids -> ``[B, S, vocab]`` float32 log-probabilities of the
        next token."""
        hidden, _ = self.hidden_states(variables["params"], ids)
        return ops.log_softmax(self._logits(variables["params"]["embed_tokens"], hidden))

    # -- forward --------------------------------------------------------------------

    def _blocks(self, params, ids, layers: int | None = None):
        """The embedding and the first ``layers`` blocks (all when None):
        ``(x, positions, [counts of each sparse layer run])``."""
        ids = ids.astype(jnp.int32)
        positions = jnp.arange(ids.shape[1])
        x = params["embed_tokens"].astype(self.dtype)[ids]
        counts = []
        for i, kind in enumerate(self.layer_types[:layers]):
            fn = make_block(self, kind, i >= self.num_dense_layers)
            if self.remat:
                fn = jax.checkpoint(
                    fn, policy=jax.checkpoint_policies.save_only_these_names(*KEPT))
            x, arrived = fn(params[f"layer_{i}"], x, positions)
            if arrived is not None:
                counts.append(arrived)
        return x, positions, counts

    def hidden_states(self, params, ids) -> tuple[jax.Array, jax.Array | None]:
        """``(final-normed hidden [B, S, d], counts [sparse layers, held] | None)``:
        the rows that arrived at each held expert of each sparse layer."""
        x, _, counts = self._blocks(params, ids)
        x = ops.rms_norm(x, params["final_norm_scale"], eps=self.norm_eps)
        return x, (jnp.stack(counts) if counts else None)

    def router_choices(self, params, ids, layer: int) -> jax.Array:
        """The experts ``[B, S, k]`` (ids over all the router's experts) that sparse
        layer ``layer`` selects: a diagnostic, for tests and for the benchmark's
        count of selections a lower precision moves."""
        x, positions, _ = self._blocks(params, ids, layer)
        p = params[f"layer_{layer}"]
        h = mix(p, x, positions, self.layer_types[layer], self)
        u = ops.rms_norm(h, p["ff_norm_scale"], eps=self.norm_eps)
        _, experts = moe.route(u.reshape(-1, u.shape[-1]), p["moe"]["router_kernel"],
                               p["moe"]["expert_bias_b"],
                               top_k=self.num_experts_per_tok)
        return experts.reshape(*ids.shape, -1)

    def _logits(self, table, hidden):
        """Tied head: ``hidden · tableᵀ`` in float32, as one ``[B·S, d] x [d, vocab]``
        product, so that the vocabulary is the logits' minor axis (as
        ``bsd,vd->bsv`` the compiler made it the sequence, and the step's
        temporaries 0.5 GB larger)."""
        b, s, d = hidden.shape
        flat = jnp.matmul(hidden.reshape(b * s, d), table.astype(self.dtype).T,
                          preferred_element_type=jnp.float32)
        return flat.reshape(b, s, -1)

    def nll(self, params, tokens) -> tuple[jax.Array, jax.Array | None]:
        """``(summed next-token NLL over the B·(S-1) targets, counts)``."""
        hidden, counts = self.hidden_states(params, tokens)
        # Row t's target is token t + 1. The last row has none: its log-probabilities
        # are computed and dropped, which keeps the head's matmul at S rows.
        targets = jnp.roll(tokens.astype(jnp.int32), -1, axis=1)[..., None]

        def head(table, h):
            logits = self._logits(table, h)
            # The row maximum behind a barrier: fused with the subtraction, the
            # compiler took it with a reduce-window as wide as the vocabulary
            # (61 ms a pass on the v5e where the logits' product needs 6).
            top = jax.lax.optimization_barrier(
                jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True)))
            shifted = logits - top
            picked = jnp.take_along_axis(shifted, targets, axis=-1)[..., 0] \
                - jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
            return -jnp.sum(picked[:, :-1])

        if self.remat:          # the [B, S, vocab] float32 logits are not kept
            head = jax.checkpoint(head)
        with jax.named_scope("head_loss"):
            return head(params["embed_tokens"], hidden), counts

    def loss(self, params, tokens) -> tuple[jax.Array, jax.Array | None]:
        """``(mean next-token NLL, counts)``: the training objective."""
        total, counts = self.nll(params, tokens)
        return total / (tokens.shape[0] * (tokens.shape[1] - 1)), counts


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs among its equations' parameters."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def make_block(model: HybridLM, kind: str, sparse: bool):
    """``block(p, x, positions) -> (y, counts | None)`` of one layer."""

    def block(p, x, positions):
        h = mix(p, x, positions, kind, model)
        u = ops.rms_norm(h, p["ff_norm_scale"], eps=model.norm_eps)
        if not sparse:
            return h + dense_ff(p["ff"], u), None
        out, counts = sparse_ff(p["moe"], u, model)
        return h + out, counts

    return block


def mix(p, x, positions, kind: str, model: HybridLM):
    """``x + mixer(RMSNorm(x))``: the first half of a block."""
    u = ops.rms_norm(x, p["mixer_norm_scale"], eps=model.norm_eps)
    mixed = (conv_mixer(p["conv"], u) if kind == "conv"
             else attention_mixer(p["attn"], u, positions, model))
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
    with jax.named_scope("conv_mixer"):
        b, c, x = jnp.split(
            checkpoint_name(_dense(u, p["in_proj_kernel"]), "conv_in_proj"), 3, axis=-1)
        return _dense(c * causal_depthwise_conv(b * x, p["conv_kernel"]),
                      p["out_proj_kernel"])


def attention_mixer(p, u, positions, model: HybridLM):
    with jax.named_scope("attention"):
        b, s, _ = u.shape
        heads, kv, hd = (model.num_attention_heads, model.num_key_value_heads,
                         model.head_dim)
        # Named as the matmuls wrote them, not after the norm and the rotation: the
        # norm's backward pass reads its input, so its output kept spares no matmul.
        q, k, v = (checkpoint_name(_dense(u, p[f"{name}_kernel"]), "attn_proj")
                   .reshape(b, s, n, hd) for name, n in (("q", heads), ("k", kv), ("v", kv)))
        q = apply_rotary(ops.rms_norm(q, p["q_norm_scale"], eps=model.norm_eps),
                         positions, base=model.rope_theta)
        k = apply_rotary(ops.rms_norm(k, p["k_norm_scale"], eps=model.norm_eps),
                         positions, base=model.rope_theta)
        k, v = (jnp.repeat(x, heads // kv, axis=2) for x in (k, v))
        out = model.attention_fn(q, k, v, causal=True)
        return _dense(out.reshape(b, s, heads * hd), p["out_kernel"])


def dense_ff(p, u):
    with jax.named_scope("dense_ff"):
        # ``W1 u`` is kept and ``W3 u`` recomputed: beside the cell's state the chip
        # has room for one ``[T, intermediate]`` array more, not for two (PERF.md §6).
        gate = checkpoint_name(_dense(u, p["w1_kernel"]), "ff_gate")
        return _dense(ops.swiglu(gate, _dense(u, p["w3_kernel"])), p["w2_kernel"])


def sparse_ff(p, u, model: HybridLM):
    b, s, d = u.shape
    flat = u.reshape(b * s, d)
    weights, experts = moe.route(flat, p["router_kernel"], p["expert_bias_b"],
                                 top_k=model.num_experts_per_tok,
                                 scaling=model.routed_scaling_factor)
    out, counts = moe.held_experts_ffn(
        flat, weights, experts, p["experts_w1_kernel"], p["experts_w3_kernel"],
        p["experts_w2_kernel"], held=model.held_experts, block=model.expert_block)
    return out.reshape(b, s, d), counts


def is_frozen(path) -> bool:
    """Leaves the optimizer leaves alone (``optim.freeze``): the selection's bias."""
    return str(getattr(path[-1], "key", path[-1])) == "expert_bias_b"


def from_config(config: dict, *, vocab_size: int, seq_len: int, **kwargs) -> HybridLM:
    """The model a configuration file describes: the published keys at the top
    level (``layer_types`` whole), and, for one chip's share of a deployment,
    ``num_hidden_layers`` / ``num_dense_layers`` / ``num_experts`` / ``vocab_size`` as
    held here with ``share`` = ``{first_layer, first_expert}`` and ``published`` =
    ``{num_experts}`` beside them. ``vocab_size`` is the corpus's and has to be the
    file's."""
    share, published = config.get("share", {}), config.get("published", {})
    if int(config["vocab_size"]) != int(vocab_size):
        raise ValueError(f"the corpus has {vocab_size} ids, the configuration's "
                         f"vocabulary (slice) has {config['vocab_size']}")
    if config.get("conv_bias"):
        raise ValueError("conv_bias true is not written here (no catalog model has it)")
    first = int(share.get("first_layer", 0))
    kinds = tuple(config["layer_types"][first:first + int(config["num_hidden_layers"])])
    if len(kinds) != int(config["num_hidden_layers"]):
        raise ValueError("layer_types is shorter than first_layer + num_hidden_layers")
    return HybridLM(
        vocab_size=int(vocab_size), seq_len=int(seq_len),
        hidden_size=int(config["hidden_size"]),
        intermediate_size=int(config["intermediate_size"]),
        moe_intermediate_size=int(config["moe_intermediate_size"]),
        num_attention_heads=int(config["num_attention_heads"]),
        num_key_value_heads=int(config["num_key_value_heads"]),
        layer_types=kinds, num_dense_layers=int(config["num_dense_layers"]),
        router_experts=int(published.get("num_experts", config["num_experts"])),
        held_experts=(int(share.get("first_expert", 0)), int(config["num_experts"])),
        num_experts_per_tok=int(config["num_experts_per_tok"]),
        conv_L_cache=int(config["conv_L_cache"]), norm_eps=float(config["norm_eps"]),
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        routed_scaling_factor=float(config.get("routed_scaling_factor", 1.0)),
        **kwargs)


def from_config_file(path: str, **kwargs) -> HybridLM:
    with open(path) as fh:
        return from_config(json.load(fh), **kwargs)
