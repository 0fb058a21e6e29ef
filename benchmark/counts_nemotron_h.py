"""Operations of the ``nemotron_h`` decoder (or one chip's share of it) from shapes:
the least work the algorithm needs. ``m`` is the configuration file (the published
keys at its top level; Mamba-2 heads and groups, attention heads, routed experts and
ids as held here; ``published.n_routed_experts`` the router's width;
``share.first_layer``, ``share.shared_expert_columns``).

Matmuls only (2 FLOPs a multiply-add): norms, the depthwise convolution's four taps,
softplus, the decays' exponentials, the gate, softmax, sigmoid and top-k are left out.
The scan is counted as the chunked algorithm at the file's ``chunk_size`` Q does it:
per chunk and group the scores ``C Bᵀ`` (2 Q² N), per chunk and head the part inside the
chunk (2 Q² P), the carried state read (2 Q N P) and written (2 Q N P); the token-by-token
recurrence would be 4 N P a token and head with no matmul in it. The expert term is the
EXPECTED one: a token sends ``num_experts_per_tok`` rows to the router's experts, so
``k · held / router`` of them (0.34 with 8 of 512 and k = 22) land here a layer, whatever
the run's routing was; ``latent_expert_matmul_roofline_share`` counts the rows that did
arrive instead. No recomputation is counted, and a backward pass is twice its forward.
"""

from __future__ import annotations


def _letters(m: dict) -> str:
    first = m.get("share", {}).get("first_layer", 0)
    return m["hybrid_override_pattern"][first:first + m["num_hidden_layers"]]


def expert_forward_flops_per_row(m: dict) -> float:
    """One latent row through one expert: W1 and W2, no gate."""
    return 2 * 2.0 * m["moe_latent_size"] * m["moe_intermediate_size"]


def expert_train_flops_per_row(m: dict) -> float:
    """Forward and backward (input and weight gradients) of one arrived row."""
    return 3 * expert_forward_flops_per_row(m)


def scan_forward_flops_per_token(m: dict) -> float:
    """One Mamba-2 layer's scan, a token: the chunk's work over its Q tokens."""
    q, n, p = m["chunk_size"], m["ssm_state_size"], m["mamba_head_dim"]
    per_chunk = m["n_groups"] * 2.0 * q * q * n \
        + m["mamba_num_heads"] * (2.0 * q * q * p + 4.0 * q * n * p)
    return per_chunk / q


def scan_train_flops_per_example(m: dict, seq_len: int) -> float:
    """Forward + backward of every Mamba-2 layer's scan over one sequence: the work
    of ``ssd_fwd`` and ``ssd_bwd``."""
    return 3.0 * seq_len * _letters(m).count("M") * scan_forward_flops_per_token(m)


def forward_flops_per_token(m: dict, context: float) -> dict:
    """By part, for one token attending over ``context`` keys."""
    d, letters = m["hidden_size"], _letters(m)
    inner = m["mamba_num_heads"] * m["mamba_head_dim"]
    in_proj = 2 * inner + 2 * m["n_groups"] * m["ssm_state_size"] + m["mamba_num_heads"]
    heads, kvh, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    router = m.get("published", {}).get("n_routed_experts", m["n_routed_experts"])
    here = m["num_experts_per_tok"] * m["n_routed_experts"] / router
    shared = m.get("share", {}).get(
        "shared_expert_columns",
        m["moe_shared_expert_intermediate_size"] * m.get("n_shared_experts", 1))
    mamba, attention, experts = (letters.count(c) for c in "M*E")
    parts = {
        "mamba_projections": mamba * 2.0 * d * (in_proj + inner),
        "mamba_scan": mamba * scan_forward_flops_per_token(m),
        "attention_mixers": attention * (2.0 * d * hd * (2 * heads + 2 * kvh)
                                         + 2 * 2 * context * heads * hd),
        "routers": experts * 2.0 * d * router,
        "latent_projections": experts * 2 * 2.0 * d * m["moe_latent_size"],
        "shared_expert": experts * 2 * 2.0 * d * shared,
        "experts": experts * here * expert_forward_flops_per_row(m),
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
