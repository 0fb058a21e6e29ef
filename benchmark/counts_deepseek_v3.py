"""Operations of the ``deepseek_v3`` decoder (or one chip's share of it) from shapes: the
least work the algorithm needs. ``m`` is the configuration file (the published keys at
its top level; layers, routed experts and ids as held here; ``published.n_routed_experts``
the router's width; ``share.first_layer``, numbered from 0).

Matmuls only (2 FLOPs a multiply-add): norms, the rotation of the 64 shared channels (no
matmul: four multiplies a rotated channel), silu, softmax, sigmoid and top-k are left
out. The expert term is the EXPECTED one: a token sends ``num_experts_per_tok`` rows to
the router's experts, so ``k · held / router`` of them (0.75 with 16 of 128 and k = 6)
land here a layer, whatever the run's routing was; ``kimi_expert_matmul_roofline_share``,
which this cell reports too, counts the rows that did arrive instead. No recomputation is
counted, and a backward pass is twice its forward.
"""

from __future__ import annotations


def _layers(m: dict) -> dict:
    """How many of the kept layers hold the dense feed-forward, how many experts."""
    first = m.get("share", {}).get("first_layer", 0)
    depth = m["num_hidden_layers"]
    dense = min(depth, max(0, m["first_k_dense_replace"] - first))
    return {"mla": depth, "dense": dense, "experts": depth - dense}


def expert_forward_flops_per_row(m: dict) -> float:
    """One row through one gated expert: W1, W3 and W2."""
    return 3 * 2.0 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_train_flops_per_row(m: dict) -> float:
    """Forward and backward (input and weight gradients) of one arrived row."""
    return 3 * expert_forward_flops_per_row(m)


def mla_attention_forward_flops_per_token(m: dict, context: float) -> float:
    """One MLA layer's scores (keys of nope + pe channels) and weighted values, for one
    token attending over ``context`` keys."""
    key = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return m["num_attention_heads"] * 2.0 * (key + m["v_head_dim"]) * context


def mla_attention_train_flops_per_example(m: dict, seq_len: int) -> float:
    """Forward + backward of every MLA layer's causal attention over one sequence
    ((S+1)/2 keys a query on average): the work of ``flash_fwd``, ``flash_dq`` and
    ``flash_dkv``."""
    return 3.0 * seq_len * _layers(m)["mla"] * mla_attention_forward_flops_per_token(
        m, (seq_len + 1) / 2.0)


def forward_flops_per_token(m: dict, context: float) -> dict:
    """By part, for one token attending over ``context`` keys."""
    d, layers = m["hidden_size"], _layers(m)
    heads, rank = m["num_attention_heads"], m["kv_lora_rank"]
    nope, pe, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    router = m.get("published", {}).get("n_routed_experts", m["n_routed_experts"])
    here = m["num_experts_per_tok"] * m["n_routed_experts"] / router
    shared = m["moe_intermediate_size"] * m["n_shared_experts"]
    parts = {
        "mla_projections": layers["mla"] * 2.0 * (
            d * heads * (nope + pe) + d * (rank + pe) + rank * heads * (nope + vd)
            + heads * vd * d),
        "mla_attention": layers["mla"] * mla_attention_forward_flops_per_token(m, context),
        "dense_ff": layers["dense"] * 3 * 2.0 * d * m["intermediate_size"],
        "routers": layers["experts"] * 2.0 * d * router,
        "shared_experts": layers["experts"] * 3 * 2.0 * d * shared,
        "experts": layers["experts"] * here * expert_forward_flops_per_row(m),
        "head": 2.0 * d * m["vocab_size"],
    }
    parts["total"] = sum(parts.values())
    return parts


def train_flops_per_example(m: dict, seq_len: int) -> float:
    """Forward + backward (3x forward) of one sequence of ``seq_len`` tokens under a
    causal mask ((S+1)/2 keys a query on average); the head runs on the S-1
    positions that have a target."""
    parts = forward_flops_per_token(m, (seq_len + 1) / 2.0)
    layers = parts["total"] - parts["head"]
    return 3.0 * (seq_len * layers + (seq_len - 1) * parts["head"])
