"""Operations of the ``evabyte`` decoder (or one chip's share of it) from shapes: the
least work the algorithm needs. ``m`` is the configuration file (the published keys at
its top level; layers and heads as held here; ``published.num_attention_heads`` the whole
layer's heads, so a head is ``hidden_size`` over that wide; ``share.mlp_columns`` the held
columns of the feed-forward).

Matmuls only (2 FLOPs a multiply-add): norms, the rotation, silu, the softmaxes'
exponentials and the masks are left out. EVA attention is counted by the pairs a query
sees: with W = ``window_size``, c = ``chunk_size``, M = W / c and a sequence of S / W whole
windows, window w's W queries see W (W + 1) / 2 exact (query, key) pairs among themselves
and W · M · w (query, summary) pairs, each pair 4 d FLOPs forward (a score and a weighted
value); and by the summaries' own products, 6 d a token and head forward (φ · k and the two
weighted sums of a chunk's keys and values). No recomputation is counted, and a backward
pass is twice its forward. The head multiplies every row by all ``num_pred_heads · vocab``
columns: the (place, head) pairs without a target are a 28th of a thousandth of them at
32768 bytes and are not taken out.
"""

from __future__ import annotations


def head_dim(m: dict) -> int:
    heads = m.get("published", {}).get("num_attention_heads", m["num_attention_heads"])
    return m["hidden_size"] // heads


def attention_pairs_per_example(m: dict, seq_len: int) -> tuple[int, int]:
    """``(exact pairs, summary pairs)`` a head sees over one sequence."""
    window, per_window = m["window_size"], m["window_size"] // m["chunk_size"]
    windows = seq_len // window
    exact = windows * window * (window + 1) // 2
    summary = sum(window * per_window * w for w in range(windows))
    return exact, summary


def eva_attention_forward_flops_per_example(m: dict, seq_len: int) -> float:
    """One layer's held heads, forward: the pairs' scores and weighted values, and the
    summaries' own products."""
    d = head_dim(m)
    pairs = sum(attention_pairs_per_example(m, seq_len))
    return m["num_attention_heads"] * (4.0 * d * pairs + 6.0 * d * seq_len)


def eva_attention_train_flops_per_example(m: dict, seq_len: int) -> float:
    """Forward + backward of every layer's attention and summaries over one sequence:
    the work of ``flash_fwd``, ``flash_dq``, ``flash_dkv``, ``eva_fwd``, ``eva_dq``, ``eva_dkv``
    and of the pooling under ``eva/summaries``."""
    return 3.0 * m["num_hidden_layers"] * eva_attention_forward_flops_per_example(m, seq_len)


def forward_flops_per_example(m: dict, seq_len: int) -> dict:
    """By part, for one sequence of ``seq_len`` tokens."""
    d, layers = m["hidden_size"], m["num_hidden_layers"]
    wide = m["num_attention_heads"] * head_dim(m)
    columns = m.get("share", {}).get("mlp_columns", m["intermediate_size"])
    parts = {
        "eva_projections": layers * seq_len * 2.0 * 4 * d * wide,       # q, k, v, out
        "eva_attention": layers * eva_attention_forward_flops_per_example(m, seq_len),
        "dense_ff": layers * seq_len * 2.0 * 3 * d * columns,
        "head": seq_len * 2.0 * d * m["num_pred_heads"] * m["vocab_size"],
    }
    parts["total"] = sum(parts.values())
    return parts


def train_flops_per_example(m: dict, seq_len: int) -> float:
    """Forward + backward (3x forward) of one sequence of ``seq_len`` tokens."""
    return 3.0 * forward_flops_per_example(m, seq_len)["total"]
