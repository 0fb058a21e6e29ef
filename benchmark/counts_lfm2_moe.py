"""Operations of the ``lfm2_moe`` decoder (or one chip's share of it) from shapes:
the least work the algorithm needs. ``m`` is the configuration file (the published
keys at its top level, the held experts and vocabulary slice in ``num_experts`` and
``vocab_size``, ``published.num_experts`` the router's width, ``share.first_layer``).

The expert term is the EXPECTED one: a token sends ``num_experts_per_tok`` rows to
the router's experts, so ``k · held / router`` of them (0.5 with 8 of 64 and k = 4)
land here a sparse layer, whatever the run's routing was; the per-layer metric
``expert_matmul_roofline_share`` counts the rows that did arrive instead. Matmuls
only (2 FLOPs a multiply-add): norms, the depthwise convolution's three taps, RoPE,
softmax, sigmoid and top-k are left out. No recomputation is counted.
"""

from __future__ import annotations


def _kinds(m: dict) -> list[str]:
    first = m.get("share", {}).get("first_layer", 0)
    return m["layer_types"][first:first + m["num_hidden_layers"]]


def expert_forward_flops_per_row(m: dict) -> float:
    """One row through one expert: W1, W3 and W2."""
    return 3 * 2.0 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_train_flops_per_row(m: dict) -> float:
    """Forward and backward (input and weight gradients) of one arrived row."""
    return 3 * expert_forward_flops_per_row(m)


def forward_flops_per_token(m: dict, context: float) -> dict:
    """By part, for one token attending over ``context`` keys."""
    d = m["hidden_size"]
    hd = d // m["num_attention_heads"]
    kinds = _kinds(m)
    sparse = len(kinds) - m["num_dense_layers"]
    router = m.get("published", {}).get("num_experts", m["num_experts"])
    here = m["num_experts_per_tok"] * m["num_experts"] / router
    attn = 2.0 * (2 * d * d + 2 * d * m["num_key_value_heads"] * hd) + 2 * 2 * context * d
    parts = {
        "conv_mixers": kinds.count("conv") * 2.0 * (3 * d * d + d * d),
        "attention_mixers": kinds.count("full_attention") * attn,
        "dense_ff": m["num_dense_layers"] * 3 * 2.0 * d * m["intermediate_size"],
        "routers": sparse * 2.0 * d * router,
        "experts": sparse * here * expert_forward_flops_per_row(m),
        "head": 2.0 * d * m["vocab_size"],
    }
    parts["total"] = sum(parts.values())
    return parts


def train_flops_per_example(m: dict, seq_len: int) -> float:
    """Forward + backward (3x forward) of one sequence of ``seq_len`` tokens under a
    causal mask ((S+1)/2 keys a query on average); the head runs on the S-1
    positions that have a target."""
    parts = forward_flops_per_token(m, (seq_len + 1) / 2.0)
    blocks = parts["total"] - parts["head"]
    return 3.0 * (seq_len * blocks + (seq_len - 1) * parts["head"])
