"""Operations of the ``kimi_linear`` decoder (or one chip's share of it) from shapes:
the least work the algorithm needs. ``m`` is the configuration file (the published
keys at its top level; layers, routed experts and ids as held here;
``published.num_experts`` the router's width; ``share.first_layer``, numbered from 1 as
``linear_attn_config``'s lists are).

Matmuls only (2 FLOPs a multiply-add): norms, the depthwise convolutions' four taps,
silu, softplus, the decays' exponentials, the gates, softmax, sigmoid and top-k are
left out. The delta-rule scan is counted as the chunked algorithm does it, at chunks of
``CHUNK`` tokens (C; keys of K channels, values of V), per chunk and head: the two score
matrices ``(βK⊙e^G)(K⊙e^-G)ᵀ`` and ``(Q⊙e^G)(K⊙e^-G)ᵀ`` (2 C² K each), the triangular
solve ``(I + A)⁻¹`` applied to its two right-hand sides by substitution (C² K + C² V: half a
product each), the state read for the correction and for the output (2 C K V each), the
scores times the corrected values (2 C² V) and the state's update (2 C K V). The
token-by-token recurrence would be 6 K V a token and head with no matmul in it. The expert
term is the EXPECTED one: a token sends ``num_experts_per_token`` rows to the router's
experts, so ``k · held / router`` of them (0.25 with 8 of 256 and k = 8) land here a layer,
whatever the run's routing was; ``kimi_expert_matmul_roofline_share`` counts the rows
that did arrive instead. No recomputation is counted, and a backward pass is twice its
forward.
"""

from __future__ import annotations

CHUNK = 64      # the published kernels' chunk, and the program's (ops/kda.py)


def _numbers(m: dict) -> range:
    first = m.get("share", {}).get("first_layer", 1)
    return range(first, first + m["num_hidden_layers"])


def _layers(m: dict) -> dict:
    """How many of the kept layers are of each kind."""
    linear, numbers = m["linear_attn_config"], _numbers(m)
    kda = sum(i in linear["kda_layers"] for i in numbers)
    dense = sum(i <= m["first_k_dense_replace"] for i in numbers)
    return {"kda": kda, "mla": len(numbers) - kda, "dense": dense,
            "experts": len(numbers) - dense}


def expert_forward_flops_per_row(m: dict) -> float:
    """One row through one gated expert: W1, W3 and W2."""
    return 3 * 2.0 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_train_flops_per_row(m: dict) -> float:
    """Forward and backward (input and weight gradients) of one arrived row."""
    return 3 * expert_forward_flops_per_row(m)


def kda_scan_forward_flops_per_token(m: dict) -> float:
    """One KDA layer's scan, a token: the chunk's work over its C tokens."""
    linear = m["linear_attn_config"]
    c, k, v = CHUNK, linear["head_dim"], linear["head_dim"]
    per_chunk = c * c * (5.0 * k + 3.0 * v) + 6.0 * c * k * v
    return linear["num_heads"] * per_chunk / c


def kda_scan_train_flops_per_example(m: dict, seq_len: int) -> float:
    """Forward + backward of every KDA layer's scan over one sequence: the work of
    ``kda_fwd`` and ``kda_bwd``."""
    return 3.0 * seq_len * _layers(m)["kda"] * kda_scan_forward_flops_per_token(m)


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
    d, layers, linear = m["hidden_size"], _layers(m), m["linear_attn_config"]
    wide, low = linear["num_heads"] * linear["head_dim"], linear["head_dim"]
    heads, rank = m["num_attention_heads"], m["kv_lora_rank"]
    nope, pe, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    router = m.get("published", {}).get("num_experts", m["num_experts"])
    here = m["num_experts_per_token"] * m["num_experts"] / router
    shared = m["moe_intermediate_size"] * m["num_shared_experts"]
    parts = {
        # q, k, v; the two low-rank pairs (decay, output gate); beta; the out-projection
        "kda_projections": layers["kda"] * 2.0 * (
            3 * d * wide + 2 * (d * low + low * wide) + d * linear["num_heads"] + wide * d),
        "kda_scan": layers["kda"] * kda_scan_forward_flops_per_token(m),
        "mla_projections": layers["mla"] * 2.0 * (
            d * heads * (nope + pe) + d * (rank + pe) + rank * heads * (nope + vd)
            + heads * vd * d),
        "mla_attention": layers["mla"] * mla_attention_forward_flops_per_token(m, context),
        "dense_ff": layers["dense"] * 3 * 2.0 * d * m["intermediate_size"],
        "routers": layers["experts"] * 2.0 * d * router,
        "shared_expert": layers["experts"] * 3 * 2.0 * d * shared,
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
