"""Transformer training-throughput microbench: steps/s and MFU at an MXU-shaped config.

The CNN headline bench (bench.py) measures the reference's metric, but a 21.8k-param CNN
at batch 64 cannot load a TPU's systolic array (~0.5% MFU on v5e — RESULTS.md); it shows
end-to-end speed, not that the framework drives the MXU. This bench trains the
transformer family (models/transformer.py) at a configuration whose matmuls are
MXU-shaped — default ``d_model 256, seq 256, batch 64, 4 layers`` in bfloat16
activations — and reports steps/s, tokens/s, achieved model FLOP/s, and MFU against the
chip's bf16 peak (r2 verdict item 6).

Protocol: K training steps (SGD, the standard ``train.step`` machinery) as ONE scanned
jit program over a constant synthetic token batch (throughput is data-independent;
params still update sequentially so no step can be elided), one untimed warmup program
run for compile, then median of 3 timed runs, each closed by a device→host fetch of a
scalar data-dependent on the last step's loss AND parameter update (the same sync as
utils/benchmarks.py).

Model-FLOPs accounting (per token, forward): ``L·(24·e² + 4·s·e) + 2·f·e`` — the layer
matmuls (qkv 3e², out e², MLP 8e² weights → ×2 FLOPs/MAC) plus the two attention
einsums (QKᵀ and PV, 2·s·e each) plus the embed projection; training ≈ 3× forward.
Head/LayerNorm/softmax terms are negligible and excluded (conservative MFU).

Prints exactly ONE JSON line on stdout. CPU-drivable at tiny shapes (tests).
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--d-model", type=int, default=None)
    p.add_argument("--seq", type=int, default=None)
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--layers", type=int, default=None)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--steps", type=int, default=None)
    p.add_argument("--large", action="store_true",
                   help="MXU-saturating defaults (d_model 1024, seq 2048, batch 16, "
                        "8 layers, 10 steps) — the config the >=30%% MFU claim is "
                        "measured at; explicit flags still override")
    p.add_argument("--bf16", action=argparse.BooleanOptionalAction, default=True,
                   help="bfloat16 activations (f32 master weights) — the MXU dtype")
    p.add_argument("--flash", action=argparse.BooleanOptionalAction, default=False,
                   help="attention dispatched by the size of the float32 score "
                        "tensor (ops.dispatch_plan: dense while B*H*S*S*4 bytes stay "
                        "on-chip, Pallas flash once they would go through HBM — the "
                        "flag never regresses throughput)")
    args = p.parse_args(argv)
    _lg = args.large
    for name, small, large in (("d_model", 256, 1024), ("seq", 256, 2048),
                               ("batch", 64, 16), ("layers", 4, 8),
                               ("steps", 50, 10)):
        if getattr(args, name) is None:
            setattr(args, name, large if _lg else small)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from csed_514_project_distributed_training_using_pytorch_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()

    from csed_514_project_distributed_training_using_pytorch_tpu.models import (
        TransformerClassifier,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
        create_train_state, make_train_step,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.benchmarks import (
        peak_flops,
    )

    e, s, b, L = args.d_model, args.seq, args.batch, args.layers
    feat = 16                       # synthetic token feature width (embed input)
    model_kwargs = dict(seq_len=s, embed_dim=e, num_layers=L, num_heads=args.heads,
                        dropout_rate=0.0,
                        dtype=jnp.bfloat16 if args.bf16 else jnp.float32)
    attn_impl = "dense"
    if args.flash:
        from csed_514_project_distributed_training_using_pytorch_tpu.ops.pallas_attention import (
            dispatch_attention, dispatch_plan,
        )
        model_kwargs["attention_fn"] = dispatch_attention
        # Record what the dispatcher actually runs at this shape — a row labelled
        # "flash" must not have timed the dense path.
        attn_impl = dispatch_plan((b, s, args.heads, e // args.heads))["impl"]
    model = TransformerClassifier(**model_kwargs)

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.normal(size=(b, s, feat)).astype(np.float32))
    labels = jnp.asarray((np.arange(b) % 10).astype(np.int32))

    state = create_train_state(model, jax.random.PRNGKey(1),
                               sample_input_shape=(1, s, feat))
    step = make_train_step(model, learning_rate=0.01, momentum=0.5)
    key = jax.random.PRNGKey(2)

    @jax.jit
    def run(state):
        def body(st, _):
            st, loss = step(st, tokens, labels, key)
            return st, loss

        return lax.scan(body, state, None, length=args.steps)

    from csed_514_project_distributed_training_using_pytorch_tpu.utils.benchmarks import (
        timed_state_run,
    )

    def timed(state):
        return timed_state_run(run, state)        # honest sync (see module docstring)

    state, _, _ = timed(state)                    # warmup: compile + fault-in
    times, last_loss = [], None
    for _ in range(3):
        state, dt, last_loss = timed(state)
        times.append(dt)
    median = float(np.median(times))

    # Per-component accounting (per token, forward): qkv+out projections 8e²,
    # MLP 16e², attention einsums (QKᵀ + PV) 4se, embed 2fe — training ≈ 3× fwd.
    proj_per_token = L * 8 * e * e
    mlp_per_token = L * 16 * e * e
    attn_per_token = L * 4 * s * e
    embed_per_token = 2 * feat * e
    fwd_per_token = proj_per_token + mlp_per_token + attn_per_token + embed_per_token
    train_flops_per_step = 3 * fwd_per_token * s * b
    steps_per_s = args.steps / median
    achieved = steps_per_s * train_flops_per_step
    dev = jax.devices()[0]
    peak = peak_flops(getattr(dev, "device_kind", "")) if dev.platform == "tpu" else None

    print(json.dumps({
        "metric": (f"transformer train steps/s (L={L}, d_model={e}, seq={s}, "
                   f"batch={b}, heads={args.heads}, "
                   f"{'bf16' if args.bf16 else 'f32'}"
                   f"{f', attn-dispatch({attn_impl})' if args.flash else ''})"),
        "value": round(steps_per_s, 2),
        "unit": "steps/s",
        "vs_baseline": None,      # beyond-parity surface: the reference has no transformer
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "seconds_per_run_all": [round(t, 4) for t in times],
        "steps_per_run": args.steps,
        "tokens_per_s": round(steps_per_s * b * s),
        "examples_per_s": round(steps_per_s * b, 1),
        "model_train_flops_per_step": train_flops_per_step,
        "train_flops_per_step_by_component": {
            "attn_projections": 3 * proj_per_token * s * b,
            "mlp": 3 * mlp_per_token * s * b,
            "attention_einsums": 3 * attn_per_token * s * b,
            "embed": 3 * embed_per_token * s * b,
        },
        "achieved_model_flops_per_s": round(achieved),
        "mfu_vs_bf16_peak": round(achieved / peak, 6) if peak else None,
        "final_train_loss": round(last_loss, 4),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
