"""The flash backward as Mosaic compiles it, on the chip, alone: fused (the tree) against
split (the same tree with the resident-dq budget at 0: the parent's two kernels), at the cells'
shapes and at backward blocks of 1024 and 512. (a) fused against split on the same operands
(largest absolute difference of dq, dk, dv; the walks the cells do not run too: banded, hop
offsets, traced offsets), and fused against the dense core's float32 vjp at S 2048;
(b) device time of each kernel from a profiler trace, per live block pair.
usage (chip only): python3 bench_results/hw_pr40/kernels_on_chip.py [out.jsonl]"""
import json, os, shutil, sys, tempfile
root = os.path.realpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path[:0] = [root, os.path.join(root, "benchmark")]
import jax, jax.numpy as jnp
import xplane
from csed_514_project_distributed_training_using_pytorch_tpu.ops import pallas_attention as pa
from csed_514_project_distributed_training_using_pytorch_tpu.ops.attention import full_attention

BUDGET = pa.FUSED_DQ_MAX_BYTES


def operands(bh, s, d, dv, block, dtype=jnp.bfloat16, seed=0, **kw):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k = (jax.random.normal(key, (bh, s, d), jnp.float32).astype(dtype) for key in keys[:2])
    v, g = (jax.random.normal(key, (bh, s, dv), jnp.float32).astype(dtype) for key in keys[2:])
    out, lse = pa._flash_forward(q, k, v, block=block, **kw)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), -1).reshape(
        bh, s // block, 1, block)
    return q, k, v, g, lse, delta


def backward(budget, **kw):
    def run(q, k, v, g, lse, delta, *traced_offset):
        pa.FUSED_DQ_MAX_BYTES = budget          # read while tracing
        try:
            return pa.flash_backward_blocks(q, k, v, g, lse, delta,
                                            q_offset_dyn=(traced_offset or (None,))[0], **kw)
        finally:
            pa.FUSED_DQ_MAX_BYTES = BUDGET
    return jax.jit(run)


def device_ms(fn, args, reps):
    """Device self time by op name (ms a call) over ``reps`` calls, from a trace."""
    jax.block_until_ready(fn(*args))
    work = tempfile.mkdtemp()
    try:
        with jax.profiler.trace(work):
            for _ in range(reps):
                out = fn(*args)
            jax.block_until_ready(out)
        events = xplane.device_op_events(xplane.load(xplane.find_trace(work)))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    (ev,) = events.values()
    return {name: ns / 1e6 / reps for name, ns in xplane.self_times(ev).items()}


def live_pairs(n, causal):
    return n * (n + 1) // 2 if causal else n * n


def agree(say):
    gap = lambda a, b: float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
    walks = [("full_causal", dict(causal=True), 192, 128), ("full", dict(causal=False), 128, 128),
             ("banded_causal", dict(causal=True, window=700), 64, 64),
             ("banded", dict(causal=False, window=300), 128, 128),
             ("banded_hop", dict(causal=False, window=700, q_offset=512), 128, 128),
             ("hop_back", dict(causal=False, window=700, q_offset=-512), 128, 128)]
    for name, kw, d, dv in walks:
        for block in (512, 256):
            args = operands(4, 4096, d, dv, block, seed=3, **kw)
            fused, split = (backward(b, block=block, **kw)(*args) for b in (BUDGET, 0))
            say({"agree": name, "block": block, "d": d,
                 **{n: gap(a, b) for n, a, b in zip(("dq", "dk", "dv"), fused, split)},
                 "largest": max(float(jnp.abs(x.astype(jnp.float32)).max()) for x in split)})
    for name, kw in [("traced_banded", dict(causal=False, window=300)),
                     ("traced_full", dict(causal=False))]:
        for off in (0, 384, -512):
            q, k, v, g = operands(4, 4096, 128, 128, 256, seed=4, **kw)[:4]
            _, lse = pa._flash_forward(q, k, v, block=256, q_offset_dyn=jnp.int32(off), **kw)
            delta = jnp.zeros_like(lse)
            fused, split = (backward(b, block=256, **kw)(q, k, v, g, lse, delta, jnp.int32(off))
                            for b in (BUDGET, 0))
            say({"agree": name, "offset": off,
                 **{n: gap(a, b) for n, a, b in zip(("dq", "dk", "dv"), fused, split)},
                 "largest": max(float(jnp.abs(x.astype(jnp.float32)).max()) for x in split)})
    # against the dense core, float32 at the highest precision, through the public op
    for d, dv in ((192, 128), (64, 64)):
        keys = jax.random.split(jax.random.PRNGKey(5), 4)
        q, k = (jax.random.normal(key, (2, 2048, 4, d), jnp.float32) for key in keys[:2])
        v, w = (jax.random.normal(key, (2, 2048, 4, dv), jnp.float32) for key in keys[2:])
        low = tuple(x.astype(jnp.bfloat16) for x in (q, k, v))
        loss = lambda attn: lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * w)
        with jax.default_matmul_precision("highest"):
            want = jax.grad(loss(lambda *a: full_attention(*a, causal=True)), (0, 1, 2))(
                *(x.astype(jnp.float32) for x in low))
        got = jax.grad(loss(lambda *a: pa.flash_attention(*a, causal=True)), (0, 1, 2))(*low)
        say({"against_dense": [d, dv], "block": pa.auto_block(2048),
             **{n: gap(a, b) / float(jnp.abs(b).max())
                for n, a, b in zip(("dq", "dk", "dv"), got, want)}})


def timed(say):
    cells = [("kanana2/kimi", 64, 8192, 192, 128), ("lfm2", 128, 8192, 64, 64),
             ("lm_b16", 128, 896, 128, 128), ("eva_windows", 256, 2048, 128, 128),
             ("s32768_d128", 2, 32768, 128, 128)]
    for name, bh, s, d, dv in cells:
        for block in sorted({pa.auto_block(s), min(512, s)} if s % 512 == 0 else {s}, reverse=True):
            args = operands(bh, s, d, dv, block, causal=True)
            pairs = bh * live_pairs(s // block, True)
            for label, budget in (("split", 0), ("fused", BUDGET), ("split", 0), ("fused", BUDGET)):
                ops = device_ms(backward(budget, causal=True, block=block), args, reps=5)
                flash = {n: round(t, 4) for n, t in ops.items() if n.startswith("flash")}
                total = sum(flash.values())
                say({"cell": name, "bh": bh, "s": s, "d": d, "dv": dv, "block": block,
                     "backward": label, **flash, "flash_ms": round(total, 4),
                     "us_a_block_pair": round(1e3 * total / pairs, 3),
                     "other_ms": round(sum(ops.values()) - total, 4)})


def recorder(argv):
    out = open(argv[1], "w") if len(argv) > 1 else None

    def say(row):
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return say


if __name__ == "__main__":
    if jax.default_backend() != "tpu":
        sys.exit("kernels_on_chip: no chip here")
    say = recorder(sys.argv)
    say({"device": jax.devices()[0].device_kind, "budget": BUDGET,
         "vmem_limit": pa.FUSED_VMEM_LIMIT})
    for phase in (timed, agree):
        if os.environ.get("ONLY", phase.__name__) == phase.__name__:
            phase(say)
