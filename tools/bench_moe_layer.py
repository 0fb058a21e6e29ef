#!/usr/bin/env python
"""The expert layer alone on the chip: ``ops.moe.route`` + ``held_experts_ffn``, value and
every gradient, at a cell's shapes, under the profiler, at several arrival shares.

One JSON line a share (``--held`` of ``--router`` experts held, seeded near-uniform
routing): the arrived rows, the value and the gradients' norms (two checkouts given one
seed agree on them), the device's busy time a call, the self time of every
``moe_*`` kernel, and every other device op by XLA's instruction name with its shape
and the ``op_name`` the compiled program's text gives it (so a gather or a select is
named for what it is, not ``fusion``). Chip only: a time from the CPU would be the
interpreter's.

    python tools/bench_moe_layer.py --held 8,32,64 --out chiprun_out/hw/moe_layer.jsonl

``PYTHONPATH=<another checkout>`` measures that checkout's layer with this tool.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
sys.path.append(REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import xplane  # noqa: E402  (benchmark/: the trace reduction)
from csed_514_project_distributed_training_using_pytorch_tpu.ops import moe  # noqa: E402
from csed_514_project_distributed_training_using_pytorch_tpu.utils import profiling  # noqa: E402

KERNEL = re.compile(r"^moe_")


def instruction(text: str) -> str:
    return text.split(" = ", 1)[0].strip().lstrip("%")


def _brief(entry: dict | None) -> dict:
    """An instruction's shape, and the end of its ``op_name`` (the scope and the
    primitive: the front is the same for every op of the layer)."""
    if not entry:
        return {}
    return {"shape": entry["shape"], "op_name": (entry["op_name"] or "")[-90:] or None}


def measure(args, held: int, key) -> dict:
    t, d, f, router, k = args.tokens, args.d, args.f, args.router, args.k
    ks = jax.random.split(key, 6)
    dtype = jnp.bfloat16
    u = jax.random.normal(ks[0], (t, d), dtype)
    router_kernel = jax.random.normal(ks[1], (d, router), jnp.float32) * 0.02
    bias = jnp.zeros((router,), jnp.float32)
    w1, w3 = (jax.random.normal(ks[i], (d, held * f), jnp.float32) * 0.02 for i in (2, 3))
    w2 = jax.random.normal(ks[4], (f, held * d), jnp.float32) * 0.02
    target = jax.random.normal(ks[5], (t, d), dtype)

    def layer(u, router_kernel, w1, w3, w2):
        weights, experts = moe.route(u, router_kernel, bias, top_k=k)
        out, counts = moe.held_experts_ffn(u, weights, experts, w1, w3, w2,
                                           held=(0, held))
        return jnp.sum(out.astype(jnp.float32) * target.astype(jnp.float32)), counts

    step = jax.jit(jax.value_and_grad(layer, argnums=(0, 1, 2, 3, 4), has_aux=True))
    compiled = step.lower(u, router_kernel, w1, w3, w2).compile()
    # {instruction: {shape, op_name}}, by the repo's one parser of a program's text
    index = profiling.scope_table(compiled.as_text(), detail=True)["detail"]
    (loss, counts), grads = compiled(u, router_kernel, w1, w3, w2)      # warm-up
    norms = [float(jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))) for g in grads]
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for _ in range(args.calls):
            out = compiled(u, router_kernel, w1, w3, w2)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        profile = xplane.load(xplane.find_trace(trace_dir))
    plane = next(p for p in profile.planes if p.name.startswith(xplane.DEVICE_PREFIX))
    line = next(ln for ln in plane.lines if ln.name == xplane.OP_LINE)
    events = sorted(((int(e.start_ns), int(e.start_ns + e.duration_ns),
                      instruction(str(e.name))) for e in line.events),
                    key=lambda e: (e[0], -e[1]))
    busy_ns, _ = xplane.union_ns(events)
    per_call = {name: ns / 1e6 / args.calls for name, ns in xplane.self_times(events).items()}
    kernels = {name: ms for name, ms in per_call.items() if KERNEL.match(name)}
    by_kernel: dict[str, float] = {}
    for name, ms in kernels.items():
        by_kernel[xplane.op_name(name)] = by_kernel.get(xplane.op_name(name), 0.0) + ms
    products = sum(ms for name, ms in by_kernel.items() if name.startswith("moe_ffn_"))
    others = sorted(((ms, name) for name, ms in per_call.items() if name not in kernels),
                    reverse=True)
    arrived = int(counts.sum())
    dev = jax.devices()[0]
    return {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "tokens": t, "d": d, "f": f, "router": router, "k": k, "held": held,
        "calls": args.calls, "arrived_rows": arrived, "arrival_share": arrived / (t * k),
        "loss": float(loss), "grad_norms": dict(zip(("u", "router", "w1", "w3", "w2"), norms)),
        "busy_ms": busy_ns / 1e6 / args.calls,
        "product_kernels_ms": products,
        "outside_product_kernels_ms": busy_ns / 1e6 / args.calls - products,
        "kernels_ms": dict(sorted(by_kernel.items())),
        "other_ops_ms": [{"name": name, "ms": round(ms, 4), **_brief(index.get(name))}
                         for ms, name in others[:args.top]],
        "other_ops_rest_ms": sum(ms for ms, _ in others[args.top:]),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tokens", type=int, default=32768)
    ap.add_argument("--d", type=int, default=2048)
    ap.add_argument("--f", type=int, default=1536)
    ap.add_argument("--router", type=int, default=64)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--held", default="8,32,64", help="held experts, one run each")
    ap.add_argument("--calls", type=int, default=5, help="traced calls a run")
    ap.add_argument("--top", type=int, default=24, help="other device ops listed")
    ap.add_argument("--seed", type=int, default=27)
    ap.add_argument("--label", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("bench_moe_layer: no TPU: a time from this platform would be the "
              "interpreter's", file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as fh:
        for i, held in enumerate(int(h) for h in args.held.split(",")):
            row = {"label": args.label, **measure(args, held, jax.random.PRNGKey(args.seed + i))}
            fh.write(json.dumps(row) + "\n")
            fh.flush()
            brief = {k: row[k] for k in ("label", "held", "arrival_share", "busy_ms",
                                         "product_kernels_ms", "outside_product_kernels_ms",
                                         "kernels_ms")}
            print(json.dumps(brief), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
