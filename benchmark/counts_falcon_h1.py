"""Operations of the ``falcon_h1`` decoder (or one chip's share of it) from shapes: the
least work the algorithm needs. ``m`` is the configuration file (the published keys at its
top level; layers, attention heads, Mamba-2 heads and groups and ids as held here;
``share.mlp_columns`` the held columns of the feed-forward).

Matmuls only (2 FLOPs a multiply-add): norms, the forward multipliers, the depthwise
convolution's four taps, softplus, the decays' exponentials, the gate, softmax and the
rotation are left out. The scan is counted as the chunked algorithm at the file's
``mamba_chunk_size`` Q does it, at heads of P channels and a state of N: per chunk and group
the scores ``C Bᵀ`` (2 Q² N), per chunk and head the part inside the chunk (2 Q² P), the
carried state read (2 Q N P) and written (2 Q N P); the token-by-token recurrence would be
4 N P a token and head with no matmul in it. Attention is counted under its causal mask,
(S + 1) / 2 keys a query on average. No recomputation is counted, and a backward pass is
twice its forward.
"""

from __future__ import annotations


def scan_forward_flops_per_token(m: dict) -> float:
    """One layer's scan, a token: the chunk's work over its Q tokens."""
    q, n, p = m["mamba_chunk_size"], m["mamba_d_state"], m["mamba_d_head"]
    per_chunk = m["mamba_n_groups"] * 2.0 * q * q * n \
        + m["mamba_n_heads"] * (2.0 * q * q * p + 4.0 * q * n * p)
    return per_chunk / q


def scan_train_flops_per_example(m: dict, seq_len: int) -> float:
    """Forward + backward of every layer's scan over one sequence: the work of
    ``ssd_fwd`` and ``ssd_bwd``."""
    return 3.0 * seq_len * m["num_hidden_layers"] * scan_forward_flops_per_token(m)


def attention_forward_flops_per_token(m: dict, context: float) -> float:
    """One layer's scores and weighted values (both ``head_dim`` wide), for one token
    attending over ``context`` keys."""
    return m["num_attention_heads"] * 2.0 * (2 * m["head_dim"]) * context


def attention_train_flops_per_example(m: dict, seq_len: int) -> float:
    """Forward + backward of every layer's causal attention over one sequence: the work
    of ``flash_fwd`` and the backward's kernels."""
    return 3.0 * seq_len * m["num_hidden_layers"] * attention_forward_flops_per_token(
        m, (seq_len + 1) / 2.0)


def forward_flops_per_token(m: dict, context: float) -> dict:
    """By part, for one token attending over ``context`` keys."""
    d, layers = m["hidden_size"], m["num_hidden_layers"]
    inner = m["mamba_n_heads"] * m["mamba_d_head"]
    in_proj = 2 * inner + 2 * m["mamba_n_groups"] * m["mamba_d_state"] + m["mamba_n_heads"]
    heads, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    columns = m.get("share", {}).get("mlp_columns", m["intermediate_size"])
    parts = {
        "mamba_projections": layers * 2.0 * d * (in_proj + inner),
        "mamba_scan": layers * scan_forward_flops_per_token(m),
        "attention_projections": layers * 2.0 * d * hd * (2 * heads + 2 * kv),
        "attention": layers * attention_forward_flops_per_token(m, context),
        "dense_ff": layers * 2.0 * 3 * d * columns,
        "head": 2.0 * d * m["vocab_size"],
    }
    parts["total"] = sum(parts.values())
    return parts


def train_flops_per_example(m: dict, seq_len: int) -> float:
    """Forward + backward (3x forward) of one sequence of ``seq_len`` tokens under a
    causal mask; the head runs on the S-1 positions that have a target."""
    parts = forward_flops_per_token(m, (seq_len + 1) / 2.0)
    layers = parts["total"] - parts["head"]
    return 3.0 * (seq_len * layers + (seq_len - 1) * parts["head"])
