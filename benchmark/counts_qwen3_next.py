"""Operations of the ``qwen3_next`` decoder (or one chip's share of it) from shapes: the
least work the algorithm needs. ``m`` is the configuration file (the published keys at its
top level; layers, routed experts and ids as held here; ``published.num_experts`` the
router's width; ``share.first_layer``, numbered from 0).

Matmuls only (2 FLOPs a multiply-add): norms, the depthwise convolution's four taps, silu,
softplus, the decays' exponentials and their ``[C, C]`` mask, the gates, softmax, sigmoid,
the rotation and top-k are left out. The gated delta rule's scan is counted as the
chunked algorithm does it at a scalar decay, at chunks of ``CHUNK`` tokens (C; keys of K
channels, values of V), whatever implements it. Per chunk and KEY head: the two score
matrices ``K Kᵀ`` and ``Q Kᵀ`` (2 C² K each; a value head's ``A = Diag(β) (mask ⊙ K Kᵀ)`` is a
row scaling of the first). Per chunk and VALUE head: the triangular solve ``(I + A)⁻¹``
applied to its two right-hand sides by substitution (C² K + C² V: half a product each), the
state read for the correction and for the output (2 C K V each), the scores times the
corrected values (2 C² V) and the state's update (2 C K V). The token-by-token recurrence
would be 6 K V a token and head with no matmul in it. The expert term is the EXPECTED one:
a token sends ``num_experts_per_tok`` rows to the router's experts, so ``k · held / router``
of them (0.625 with 32 of 512 and k = 10) land here a layer, whatever the run's routing
was; ``kimi_expert_matmul_roofline_share`` counts the rows that did arrive instead. No
recomputation is counted, and a backward pass is twice its forward.
"""

from __future__ import annotations

CHUNK = 64      # the published kernels' chunk, and the program's (ops/kda.py)


def _layers(m: dict) -> dict:
    """How many of the kept layers are of each kind."""
    first = m.get("share", {}).get("first_layer", 0)
    numbers = range(first, first + m["num_hidden_layers"])
    attention = sum((i + 1) % m["full_attention_interval"] == 0 for i in numbers)
    return {"gdn": len(numbers) - attention, "attention": attention, "experts": len(numbers)}


def expert_forward_flops_per_row(m: dict) -> float:
    """One row through one gated expert: W1, W3 and W2."""
    return 3 * 2.0 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_train_flops_per_row(m: dict) -> float:
    """Forward and backward (input and weight gradients) of one arrived row."""
    return 3 * expert_forward_flops_per_row(m)


def gdn_scan_forward_flops_per_token(m: dict) -> float:
    """One gated delta layer's scan, a token: the chunk's work over its C tokens."""
    c, k, v = CHUNK, m["linear_key_head_dim"], m["linear_value_head_dim"]
    per_key_head = 4.0 * c * c * k
    per_value_head = c * c * (k + 3.0 * v) + 6.0 * c * k * v
    return (m["linear_num_key_heads"] * per_key_head
            + m["linear_num_value_heads"] * per_value_head) / c


def gdn_scan_train_flops_per_example(m: dict, seq_len: int) -> float:
    """Forward + backward of every gated delta layer's scan over one sequence: the work of
    ``gdn_fwd`` and ``gdn_bwd``."""
    return 3.0 * seq_len * _layers(m)["gdn"] * gdn_scan_forward_flops_per_token(m)


def attention_forward_flops_per_token(m: dict, context: float) -> float:
    """One attention layer's scores and weighted values (both ``head_dim`` wide), for one
    token attending over ``context`` keys."""
    return m["num_attention_heads"] * 2.0 * (2 * m["head_dim"]) * context


def attention_train_flops_per_example(m: dict, seq_len: int) -> float:
    """Forward + backward of every attention layer's causal attention over one sequence
    ((S+1)/2 keys a query on average): the work of ``flash_fwd`` and the backward's
    kernels."""
    return 3.0 * seq_len * _layers(m)["attention"] * attention_forward_flops_per_token(
        m, (seq_len + 1) / 2.0)


def forward_flops_per_token(m: dict, context: float) -> dict:
    """By part, for one token attending over ``context`` keys."""
    d, layers = m["hidden_size"], _layers(m)
    keys = m["linear_num_key_heads"] * m["linear_key_head_dim"]
    values = m["linear_num_value_heads"] * m["linear_value_head_dim"]
    heads, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    router = m.get("published", {}).get("num_experts", m["num_experts"])
    here = m["num_experts_per_tok"] * m["num_experts"] / router
    parts = {
        # W_qkvz, W_ba and the out-projection
        "gdn_projections": layers["gdn"] * 2.0 * (
            d * (2 * keys + 2 * values) + d * 2 * m["linear_num_value_heads"] + values * d),
        "gdn_scan": layers["gdn"] * gdn_scan_forward_flops_per_token(m),
        # W_q (queries and gates), W_k, W_v and the out-projection
        "attention_projections": layers["attention"] * 2.0 * (
            d * 2 * heads * hd + 2 * d * kv * hd + heads * hd * d),
        "attention": layers["attention"] * attention_forward_flops_per_token(m, context),
        "routers": layers["experts"] * 2.0 * d * router,
        "shared_expert": layers["experts"] * (
            3 * 2.0 * d * m["shared_expert_intermediate_size"] + 2.0 * d),
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
