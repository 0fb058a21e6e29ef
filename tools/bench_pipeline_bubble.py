"""Pipeline bubble accounting: measured schedule idle vs the stated math.

``parallel/pipeline.py`` states the textbook bubble fraction ``(S-1)/(M+S-1)`` (M
microbatches, S stages) but never measured it (r4 verdict item 4). This tool does:
with the per-microbatch SIZE held fixed, a step costs ``t(M) = c*(M+S-1) + o`` —
``c`` the per-tick time (every device executes every tick in the SPMD formulation;
fill/drain ticks compute masked garbage, which IS the bubble), ``o`` fixed dispatch
overhead. Measuring ``t`` at several M and least-squares fitting (c, o) yields:

- ``per_tick_s``        — c
- ``measured_bubble_fraction``  at each M: ``c*(S-1) / (t(M) - o)``
- ``predicted_bubble_fraction`` at each M: ``(S-1)/(M+S-1)``

agreement of the two columns is the experimental verification of the schedule's
tick model; disagreement would mean ticks are NOT uniform (e.g. ppermute latency
scaling with load). Timing uses the chained two-point protocol
(``utils/benchmarks.chained_diff_time``) so the fixed per-dispatch cost cannot
masquerade as bubble.

Usage: ``python tools/bench_pipeline_bubble.py [--stages 4] [--schedule gpipe|1f1b]
[--out artifact.json]`` — prints ONE JSON document; CPU-drivable
(``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

# Script-mode import path: ``python tools/bench_pipeline_bubble.py`` puts tools/
# on sys.path, not the repo root the package lives in.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MB, SEQ, EMBED = 8, 8, 64      # microbatch size / tokens / width per tick (fixed)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--stages", type=int, default=4)
    parser.add_argument("--microbatch-counts", type=int, nargs="+",
                        default=[2, 4, 8, 16, 32])
    parser.add_argument("--schedule", choices=("gpipe", "1f1b"), default="gpipe")
    parser.add_argument("--backward", action="store_true",
                        help="time fwd+bwd (value_and_grad) instead of forward-only")
    parser.add_argument("--out", default=None, help="also write the JSON here")
    args = parser.parse_args()
    if len(set(args.microbatch_counts)) < 2:
        parser.error("--microbatch-counts needs >= 2 distinct values — the "
                     "t = c*(M+S-1) + o fit is underdetermined with one point")

    import jax
    import jax.numpy as jnp

    from csed_514_project_distributed_training_using_pytorch_tpu.models.transformer import (
        TransformerBlock,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.parallel import (
        make_mesh, pipeline as pp,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.benchmarks import (
        chained_diff_time,
    )

    S = args.stages
    mesh = make_mesh(S, axis_names=("stage",))
    block = TransformerBlock(num_heads=4, dropout_rate=0.0)
    x0 = jnp.zeros((1, SEQ, EMBED), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(0), S)
    stacked = pp.stack_stage_params(
        [block.init({"params": k}, x0)["params"] for k in keys])
    stage_fn = lambda p, x: block.apply({"params": p}, x)

    rows = []
    for m in args.microbatch_counts:
        xs = jnp.asarray(np.random.default_rng(m).normal(
            size=(m, MB, SEQ, EMBED)).astype(np.float32))

        def run_once(xs):
            y = pp.pipeline_apply(mesh, stage_fn, stacked, xs,
                                  schedule=args.schedule)
            return jnp.sum(y ** 2)

        if args.backward:
            val_fn = jax.value_and_grad(
                lambda sp, xs: jnp.sum(pp.pipeline_apply(
                    mesh, stage_fn, sp, xs, schedule=args.schedule) ** 2))

            def chain(n):
                def body(carry, _):
                    sp, acc = carry
                    v, g = val_fn(sp, xs)
                    # Serialize each iteration on the previous grads (1e-20 rounds
                    # away; the compiler cannot prove it, so nothing is elided).
                    sp = jax.tree_util.tree_map(lambda a, b: a + 1e-20 * b, sp, g)
                    return (sp, acc + v), None

                def run(sp):
                    (sp, acc), _ = jax.lax.scan(body, (sp, 0.0), None, length=n)
                    return acc + jax.tree_util.tree_leaves(sp)[0].ravel()[0]

                compiled = jax.jit(run)
                return lambda: float(compiled(stacked))
        else:
            def chain(n):
                def body(x, _):
                    y = pp.pipeline_apply(mesh, stage_fn, stacked, x,
                                          schedule=args.schedule)
                    return y + 1e-20 * x, None

                def run(x):
                    y, _ = jax.lax.scan(body, x, None, length=n)
                    return jnp.sum(y[0, 0, 0])

                compiled = jax.jit(run)
                return lambda: float(compiled(xs))

        per_iter, _, (n2, t2), converged = chained_diff_time(chain)
        rows.append({"microbatches": m, "ticks": m + S - 1,
                     "step_seconds": per_iter, "converged": converged,
                     "chain_n2": n2})
        print(f"M={m}: {per_iter:.6f} s/step (ticks={m + S - 1}, "
              f"converged={converged})", file=sys.stderr)

    # Least-squares t = c*ticks + o over the measured rows.
    ticks = np.array([r["ticks"] for r in rows], float)
    ts = np.array([r["step_seconds"] for r in rows], float)
    A = np.stack([ticks, np.ones_like(ticks)], axis=1)
    (c, o), residuals, *_ = np.linalg.lstsq(A, ts, rcond=None)
    for r, t in zip(rows, ts):
        r["predicted_bubble_fraction"] = round((S - 1) / r["ticks"], 4)
        r["measured_bubble_fraction"] = round(float(c * (S - 1) / (t - o)), 4)

    dev = jax.devices()[0]
    doc = {
        "metric": "pipeline schedule bubble (measured vs (S-1)/(M+S-1))",
        "stages": S, "schedule": args.schedule,
        "direction": "fwd+bwd" if args.backward else "fwd",
        "microbatch_size": MB, "seq": SEQ, "embed": EMBED,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "per_tick_s": float(c), "fixed_overhead_s": float(o),
        "fit_residual": float(residuals[0]) if len(residuals) else 0.0,
        "rows": rows,
    }
    print(json.dumps(doc))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
