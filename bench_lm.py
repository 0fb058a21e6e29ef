"""Pixel-LM throughput microbench: training steps/s AND KV-cache decode tokens/s.

Companion to ``bench_transformer.py`` for the decoder family (``models/lm.py``): the
training half measures teacher-forced next-token steps/s (the same scanned-program
protocol); the decode half measures the generation surface — ``lm.generate``'s
jit-compiled KV-cache sampling loop — in tokens/s, the number a serving user asks
first. GQA (``--kv-heads``) shrinks the decode cache ``heads/kv_heads``×; RoPE and
sliding windows (``--rope``/``--window``) bench the same knobs the trainer exposes.

Protocol: identical sync discipline to the other benches (the clock stops on a
device→host fetch of a value data-dependent on the full computation); one untimed
warmup per program, median of 3 timed runs. Prints exactly ONE JSON line on stdout.
CPU-drivable at tiny shapes (tests).
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--vocab", type=int, default=16, help="gray levels (BOS is +1)")
    p.add_argument("--seq", type=int, default=784)
    p.add_argument("--batch", type=int, default=64, help="training batch")
    p.add_argument("--gen-batch", type=int, default=8, help="decode batch")
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--kv-heads", type=int, default=0, help="GQA K/V heads (0 = MHA)")
    p.add_argument("--rope", action=argparse.BooleanOptionalAction, default=False)
    p.add_argument("--window", type=int, default=0,
                   help="sliding-window attention width (0 = full)")
    p.add_argument("--steps", type=int, default=20, help="training steps per run")
    p.add_argument("--bf16", action=argparse.BooleanOptionalAction, default=True)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from csed_514_project_distributed_training_using_pytorch_tpu.utils.benchmarks import (
        chained_diff_time,
        peak_flops,
        peak_hbm_bytes,
        timed_state_run,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()

    from csed_514_project_distributed_training_using_pytorch_tpu.models import (
        lm as lm_mod,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
        create_train_state, make_train_step,
    )

    model = lm_mod.TransformerLM(
        vocab_size=args.vocab + 1, seq_len=args.seq, embed_dim=args.d_model,
        num_layers=args.layers, num_heads=args.heads,
        num_kv_heads=args.kv_heads or None, rope=args.rope,
        attention_window=args.window or 0, dropout_rate=0.0,
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32)

    rng = np.random.default_rng(0)
    targets = jnp.asarray(rng.integers(
        0, args.vocab, size=(args.batch, args.seq)).astype(np.int32))

    state = create_train_state(model, jax.random.PRNGKey(1),
                               sample_input_shape=(1, args.seq))

    def lm_loss(params, xs, ys, rng_):
        del ys
        return lm_mod.next_token_loss(model, params, xs, None, deterministic=True)

    step = make_train_step(model, learning_rate=1e-3, momentum=0.0,
                           optimizer=None, loss_fn=lm_loss)
    key = jax.random.PRNGKey(2)

    @jax.jit
    def run_train(state):
        def body(st, _):
            st, loss = step(st, targets, targets[:, 0], key)
            return st, loss

        return lax.scan(body, state, None, length=args.steps)

    def timed_train(state):
        return timed_state_run(run_train, state)   # honest sync (module docstring)

    state, _, _ = timed_train(state)               # warmup
    train_times, last_loss = [], None
    for _ in range(3):
        state, dt, last_loss = timed_train(state)
        train_times.append(dt)
    train_median = float(np.median(train_times))
    steps_per_s = args.steps / train_median

    # Decode weights in the activation dtype: serving reads bf16 weights, and the
    # weight read is the term batch amortizes (master f32 stays in the train state).
    gen_params = jax.tree_util.tree_map(
        lambda x: x.astype(model.dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, state.params)

    # One dispatch per rep would charge every generate its launch and closing host
    # fetch. Chain R generates in one compiled scan (each fold_in's the previous
    # tokens, so none can be elided) and report the two-point difference, exactly
    # like bench_attention.py — the fixed per-dispatch cost cancels.
    def gen_chain(n):
        def body(k, _):
            ids = lm_mod.generate(model, gen_params, k, batch=args.gen_batch,
                                  temperature=1.0)
            return jax.random.fold_in(k, jnp.sum(ids)), ()

        def run(k):
            return lax.scan(body, k, None, length=n)[0]

        return jax.jit(run)

    def synced_gen_chain(n):
        compiled = gen_chain(n)
        return lambda: jax.device_get(compiled(jax.random.PRNGKey(3)))

    gen_median, (n1, t1), (n2, t2), gen_converged = chained_diff_time(
        synced_gen_chain, n1=1, grow=4, max_n=256)
    gen_times = [t1, t2]
    decode_tokens_per_s = args.gen_batch * args.seq / gen_median

    # Model-FLOPs accounting mirrors bench_transformer.py, adjusted for this bench's
    # knobs: GQA narrows the KV projection (4e²·kvh/H instead of 4e²) and a sliding
    # window caps the attended keys at W. The attention term charges the full causal
    # scan (upper bound — required work averages s/2; the dense masked implementation
    # executes the full s×s einsums either way), plus the vocab head (2·e·V);
    # embedding gathers are negligible. Training ≈ 3× forward.
    e = args.d_model
    kvh = args.kv_heads or args.heads
    proj_flops = (20 + 4 * kvh / args.heads) * e * e   # q/out/mlp 20e² + kv 4e²·kvh/H
    s_att = min(args.window, args.seq) if args.window else args.seq
    fwd_per_token = (args.layers * (proj_flops + 4 * s_att * e)
                     + 2 * e * (args.vocab + 1))
    train_flops_per_step = int(3 * fwd_per_token * args.seq * args.batch)
    achieved = steps_per_s * train_flops_per_step
    dev = jax.devices()[0]
    peak = peak_flops(getattr(dev, "device_kind", "")) if dev.platform == "tpu" else None

    # Decode HBM roofline: each step re-reads every layer's cached K+V prefix (the
    # segmented scan bounds it at ceil((t+1)/SEG)·SEG rows) and the decode weights
    # (amortized over the batch). Activations/cache-writes are negligible.
    hd = e // args.heads
    cache_itemsize = jnp.dtype(model.dtype).itemsize
    # generate()'s segmented scan reads a static prefix of ceil((t+1)/SEG)·SEG cache
    # rows at step t — average that exactly rather than charging the full length.
    seg = lm_mod.DECODE_SEGMENT
    avg_prefix = sum(min((t // seg + 1) * seg, args.seq)
                     for t in range(args.seq)) / args.seq
    cache_row_bytes = 2 * args.layers * avg_prefix * kvh * hd * cache_itemsize
    param_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(gen_params))
    decode_bytes_per_token = cache_row_bytes + param_bytes / args.gen_batch
    achieved_hbm = decode_tokens_per_s * decode_bytes_per_token
    hbm_peak = (peak_hbm_bytes(getattr(dev, "device_kind", ""))
                if dev.platform == "tpu" else None)
    print(json.dumps({
        "metric": (f"pixel-LM train steps/s + decode tokens/s (L={args.layers}, "
                   f"d_model={args.d_model}, seq={args.seq}, batch={args.batch}, "
                   f"heads={args.heads}"
                   f"{f', kv_heads={args.kv_heads}' if args.kv_heads else ''}"
                   f"{', rope' if args.rope else ''}"
                   f"{f', window={args.window}' if args.window else ''}, "
                   f"{'bf16' if args.bf16 else 'f32'})"),
        "value": round(steps_per_s, 2),
        "unit": "steps/s",
        "vs_baseline": None,       # beyond-parity surface: the reference has no LM
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "train_seconds_per_run_all": [round(t, 4) for t in train_times],
        "train_tokens_per_s": round(steps_per_s * args.batch * args.seq),
        "decode_seconds_all": [round(t, 4) for t in gen_times],
        "decode_chain_lengths": [n1, n2],
        # False ⇒ max_n exhausted before the chain added min_delta seconds: the
        # two-point difference is still jitter-dominated (r4 advisor finding).
        "decode_chain_converged": gen_converged,
        "decode_tokens_per_s": round(decode_tokens_per_s, 1),
        "decode_batch": args.gen_batch,
        "decode_bytes_per_token": round(decode_bytes_per_token),
        "decode_achieved_hbm_bytes_per_s": round(achieved_hbm),
        "decode_hbm_roofline_frac": (round(achieved_hbm / hbm_peak, 4)
                                     if hbm_peak else None),
        "model_train_flops_per_step": train_flops_per_step,
        "achieved_model_flops_per_s": round(achieved),
        "mfu_vs_bf16_peak": round(achieved / peak, 6) if peak else None,
        "final_train_loss": round(last_loss, 4),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
