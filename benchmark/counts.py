"""Operations from shapes: the least work the algorithm needs.

Nothing here asks XLA: ``cost_analysis()`` counts what the compiler chose to
recompute and sees no Pallas call. Every function takes the configuration's
``model`` group (a dict of widths) and returns plain numbers.
"""

from __future__ import annotations


def lm_param_counts(m: dict) -> dict:
    """Parameters of the decoder LM by group (GQA, biases and LayerNorms in)."""
    d, layers, vocab = m["embed_dim"], m["num_layers"], m["vocab_size"]
    head_dim = d // m["num_heads"]
    kv = 2 * m["kv_heads"] * head_dim
    hidden = m["mlp_ratio"] * d
    block = (d * d + d) + (d * kv + kv) + (d * d + d) \
        + (d * hidden + hidden) + (hidden * d + d) + 4 * d
    pos = 0 if m["rope"] else m["seq_len"] * d
    return {"block": block, "blocks": layers * block,
            "embed": vocab * d + pos, "head": d * vocab + vocab + 2 * d,
            "total": layers * block + vocab * d + pos + d * vocab + vocab + 2 * d}


def lm_forward_flops_per_token(m: dict, context: int) -> float:
    """Matmul FLOPs (2 per multiply-add) of one token's forward pass attending
    over ``context`` keys. Elementwise work, softmax and norms are left out."""
    d, layers, vocab = m["embed_dim"], m["num_layers"], m["vocab_size"]
    head_dim = d // m["num_heads"]
    kv = 2 * m["kv_heads"] * head_dim
    hidden = m["mlp_ratio"] * d
    dense = 2 * (d * d + d * kv + d * d + 2 * d * hidden)
    attn = 2 * 2 * context * d          # q.k and p.v over every query head
    return layers * (dense + attn) + 2 * d * vocab


def lm_train_flops_per_example(m: dict) -> float:
    """Forward + backward (3x forward) of one ``seq_len`` sequence under a
    causal mask: token t attends over t+1 keys, (S+1)/2 on average. No
    recomputation is counted."""
    s = m["seq_len"]
    return 3.0 * s * lm_forward_flops_per_token(m, (s + 1) / 2.0)
