"""Long-context attention microbench: flash (Pallas) vs dense (XLA) on one chip.

Measures forward+backward wall time of a causal multi-head self-attention at growing
sequence lengths. The dense path materializes the ``[H, S, S]`` score matrix (O(S²) HBM);
the flash kernels (``ops/pallas_attention.py``) stream K/V blocks through VMEM (O(S·D)),
so it keeps scaling after the dense path exhausts memory — the single-chip half of the
framework's long-context story (the cross-chip half is ``parallel/ring_attention.py``).

Timing: every dispatch pays a fixed launch + closing-host-fetch cost that can exceed a
whole fwd+bwd at short S, so a one-dispatch-per-rep protocol measures the dispatch, not
the kernel. Each measurement therefore runs the
op N times CHAINED inside one compiled ``lax.scan`` (each iteration's inputs nudged
by the previous grads, so nothing can be hoisted or dead-code-eliminated), fetches a
scalar data-dependent on the final iteration, and reports the two-point difference
``(t(N2) − t(N1)) / (N2 − N1)`` — the constant dispatch+sync cost cancels exactly.

Usage: ``python bench_attention.py [--out results.jsonl]`` — one JSON line per
(impl, seq_len); dense rows appear up to the longest S that fits/compiles.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys

import numpy as np

B, H, D = 1, 8, 64
SEQ_LENS = (1024, 2048, 4096, 8192, 16384)
DENSE_MAX_SCORE_BYTES = 2 << 30  # dense keeps [B, H, S, S] f32 score residuals;
                                 # 2 GiB (S=8192 at the default B=1, H=8) is the
                                 # measured comfort wall — the gate scales with
                                 # the --batch/--heads geometry, not S alone
WARMUP, REPS = 1, 3
MIN_DELTA = 0.25        # seconds of chained work the N2 run must add over N1


def _measure(fn, q, k, v):
    import jax
    import jax.numpy as jnp

    grad_fn = jax.grad(
        lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) ** 2), argnums=(0, 1, 2))
    # 1e-20 is representable in bf16's 8-bit exponent; the nudge rounds away in the
    # add (values stay fixed) but the compiler cannot prove that, so every
    # iteration's fwd+bwd stays live and serialized on the previous one.
    eps = jnp.asarray(1e-20, q.dtype)

    def chain(n):
        def body(carry, _):
            q, k, v = carry
            gq, gk, gv = grad_fn(q, k, v)
            return (q + eps * gq, k + eps * gk, v + eps * gv), ()

        def run(q, k, v):
            (q, _, _), _ = jax.lax.scan(body, (q, k, v), None, length=n)
            return q

        return jax.jit(run)

    from csed_514_project_distributed_training_using_pytorch_tpu.utils.benchmarks import (
        chained_diff_time,
    )

    def synced_chain(n):
        compiled = chain(n)
        return lambda: float(jnp.sum(compiled(q, k, v)[0, 0, 0]))  # grad-dep sync

    per_iter, _, _, converged = chained_diff_time(synced_chain, min_delta=MIN_DELTA,
                                                  reps=REPS, warmup=WARMUP)
    return per_iter, converged


def _attended_pairs(s: int, window: int | None) -> int:
    """Number of (query, key) pairs a CAUSAL attention over length ``s`` must score —
    query i attends ``min(i+1, W)`` keys under a sliding window of W (all i+1
    without one). The roofline below charges only these required pairs: the dense
    path executes the full S×S square anyway and the flash kernels skip
    above-diagonal/out-of-band blocks, but both are judged against the same
    model-required work (the MFU convention the trainer benches use)."""
    w = min(window or s, s)
    return w * (w + 1) // 2 + (s - w) * w


def _fwdbwd_model_flops(s: int, window: int | None, b: int = B, h: int = H,
                        d: int = D) -> int:
    """Required fwd+bwd FLOPs of causal MHA at b,h,d: 2 matmul FLOPs per attended
    pair per D for each of QKᵀ and PV forward (4·B·H·D·pairs), backward's four
    matmuls (dV, dP, dQ, dK) ≈ 2× forward; flash's in-backward forward recompute is
    real work but NOT credited — MFU counts model FLOPs, not implementation FLOPs.
    Softmax/mask flops are O(pairs) without the D factor and are omitted (<1%)."""
    return 3 * 4 * b * h * d * _attended_pairs(s, window)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default=None, help="also append JSONL here")
    parser.add_argument("--seq-lens", type=int, nargs="+", default=list(SEQ_LENS),
                        help="sequence lengths to measure (one that does not divide "
                             "by 128 is padded at the tail by the flash path, to a "
                             "multiple of --block where given: the mask is causal); "
                             "small values make the tool drivable on CPU interpret mode")
    parser.add_argument("--plot", default=None,
                        help="also save the flash-vs-dense curve PNG here")
    parser.add_argument("--block", type=int, default=None,
                        help="flash kernel block rows (multiple of 128; default 128) "
                             "— the r3 tuning knob for the S<=8k regime")
    parser.add_argument("--block-sweep", type=int, nargs="+", default=None,
                        help="measure flash at each of these block sizes per seq_len "
                             "(dense measured once); finds the per-S best block")
    parser.add_argument("--window", type=int, default=None,
                        help="sliding-window width: flash runs the BANDED grid "
                             "(O(S*W) compute), dense applies the same band mask — "
                             "the local-attention long-context comparison")
    parser.add_argument("--dtype", choices=("float32", "bfloat16"),
                        default="float32",
                        help="q/k/v dtype; bfloat16 is the training dtype and runs "
                             "the kernels' matmuls at the MXU's native rate")
    parser.add_argument("--batch", type=int, default=B)
    parser.add_argument("--heads", type=int, default=H)
    parser.add_argument("--head-dim", type=int, default=D,
                        help="per-head width; the default 64 runs the MXU's "
                             "contractions at half depth — 128 is the "
                             "full-depth geometry the trainer configs use")
    args = parser.parse_args()
    b_sz, h_ct, d_hd = args.batch, args.heads, args.head_dim
    if args.block is not None and args.block_sweep is not None:
        parser.error("--block and --block-sweep are mutually exclusive")

    import jax
    import jax.numpy as jnp

    from csed_514_project_distributed_training_using_pytorch_tpu import ops

    from csed_514_project_distributed_training_using_pytorch_tpu.utils.benchmarks import (
        peak_flops,
    )

    platform = jax.default_backend()
    device_kind = jax.devices()[0].device_kind
    # Roofline denominator (r4 verdict item 2): the chip's bf16 peak — conservative
    # for f32 runs, exact for --dtype bfloat16, None off-TPU.
    peak = peak_flops(device_kind) if platform == "tpu" else None
    all_rows = []
    for s in args.seq_lens:
        rng = np.random.default_rng(s)
        q, k, v = (jnp.asarray(
            rng.normal(size=(b_sz, s, h_ct, d_hd)).astype(np.float32),
            dtype=args.dtype) for _ in range(3))
        row = {"seq_len": s, "batch": b_sz, "heads": h_ct, "head_dim": d_hd,
               "platform": platform, "device_kind": device_kind, "causal": True,
               "dtype": args.dtype, "reps": REPS}
        if args.window is not None:
            row["window"] = args.window
        sweeping = args.block_sweep is not None
        blocks = (args.block_sweep if sweeping
                  else [args.block] if args.block is not None else [None])
        best_block = None
        row["flash_fwdbwd_s"] = None   # stays None if every block size fails
        for blk in blocks:
            # Sweep rows keep the per-block key schema even for one candidate, so
            # partial re-measurements append cleanly to an existing tune JSONL.
            key = f"flash_fwdbwd_s_b{blk}" if sweeping else "flash_fwdbwd_s"
            flash_kw = {}
            if blk is not None:
                flash_kw["block"] = blk
            if args.window is not None:
                flash_kw["window"] = args.window
            flash = (ops.flash_attention if not flash_kw else
                     functools.partial(ops.flash_attention, **flash_kw))
            try:
                # flash_attention validates blk itself (multiple of 128, divides S).
                t, conv = _measure(flash, q, k, v)
            except Exception as e:  # a memory/compile wall is a result, not a crash
                t, conv = None, None
                row[key.replace("fwdbwd_s", "error")] = (
                    f"{type(e).__name__}: {str(e)[:200]}")
            row[key] = t
            if sweeping and conv is not None:
                row[key.replace("fwdbwd_s", "converged")] = conv
            if t is not None and (best_block is None or t < row["flash_fwdbwd_s"]):
                best_block, row["flash_fwdbwd_s"] = (blk or 128), t
                row["flash_converged"] = conv
        if sweeping:
            row["flash_best_block"] = best_block
        # Roofline accounting (r4 verdict item 2): required causal fwd+bwd FLOPs over
        # measured seconds, judged against the chip's bf16 peak — the same discipline
        # the trainer benches carry, extended to where the kernels live.
        model_flops = _fwdbwd_model_flops(s, args.window, b_sz, h_ct, d_hd)
        row["fwdbwd_model_flops"] = model_flops

        def roofline(impl: str) -> None:
            achieved = model_flops / row[f"{impl}_fwdbwd_s"]
            row[f"{impl}_achieved_flops_per_s"] = round(achieved)
            row[f"{impl}_pct_of_bf16_peak"] = (round(100 * achieved / peak, 2)
                                               if peak else None)

        if row["flash_fwdbwd_s"]:
            roofline("flash")
        if b_sz * h_ct * s * s * 4 <= DENSE_MAX_SCORE_BYTES:
            try:
                dense = (ops.full_attention if args.window is None else
                         functools.partial(ops.full_attention,
                                           window=args.window))
                row["dense_fwdbwd_s"], row["dense_converged"] = _measure(dense, q,
                                                                         k, v)
                roofline("dense")
                if row["flash_fwdbwd_s"]:  # speedup needs a nonzero flash denominator
                    row["speedup_flash_vs_dense"] = round(
                        row["dense_fwdbwd_s"] / row["flash_fwdbwd_s"], 3)
            except Exception as e:  # OOM/compile failure: the dense wall, recorded
                row["dense_fwdbwd_s"] = None
                row["dense_error"] = f"{type(e).__name__}: {str(e)[:200]}"
        else:
            row["dense_fwdbwd_s"] = None
            row["dense_error"] = (
                f"skipped: B*H*S*S f32 scores exceed {DENSE_MAX_SCORE_BYTES} bytes")
        print(json.dumps(row), flush=True)
        all_rows.append(row)
        if args.out:  # append per row — a later-size failure must not lose earlier rows
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
        if args.plot:  # re-save per row for the same reason (overwrite-in-place)
            from csed_514_project_distributed_training_using_pytorch_tpu.utils.plotting import (
                save_attention_curve,
            )
            if save_attention_curve(all_rows, args.plot) is None:
                print(f"warning: --plot {args.plot} not written "
                      f"(matplotlib unavailable)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    sys.exit(main())
