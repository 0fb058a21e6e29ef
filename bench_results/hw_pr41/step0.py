"""ISSUE 41's step 0, on the chip: one norm of the evabyte cell's stream alone, [1, 32768, 4096]
float32 -> bfloat16, unit offset, value and vjp, three ways: (a) HybridLM.normed's jax.numpy as
the parent has it, (b) the same with the statistic behind jax.lax.optimization_barrier, (c) the
kernels (stream_norm_kernels.py beside this file: they won here and lost 3.5 % inside the epoch
program, so the program does not have them), at row blocks of 128, 256 and 512, the stream
carried through the norm or ending at it, with and without the addend. Device self time by op from a profiler trace,
ms a call. Alone under jit an operand is row-major; inside the epoch program the compiler keeps
the stream token-minor ({0,1}) and fuses the statistic into the matmul before it, which this
script cannot see (compile_epoch.py --text shows it).
usage (chip only): python3 bench_results/hw_pr41/step0.py [out.jsonl]"""
import json, os, shutil, sys, tempfile
root = os.path.realpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path[:0] = [root, os.path.join(root, "benchmark"), os.path.dirname(os.path.abspath(__file__))]
import jax, jax.numpy as jnp
import xplane
from csed_514_project_distributed_training_using_pytorch_tpu import ops
import stream_norm_kernels as pn

T, D, EPS, BF = 32768, 4096, 1e-5, jnp.bfloat16
GB = lambda bytes_per_element: bytes_per_element * T * D / 1e9


def device_ms(fn, args, reps=5):
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
    return {name: round(ns / 1e6 / reps, 4) for name, ns in xplane.self_times(ev).items()}


def xla_norm(barrier):
    def norm(h, g):
        stat = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
        if barrier:
            stat = jax.lax.optimization_barrier(stat)
        return (h * jax.lax.rsqrt(stat + EPS) * (g + 1.0)).astype(BF)
    return norm if barrier else lambda h, g: ops.rms_norm(h, g, eps=EPS, offset=1.0).astype(BF)


def ways(which, addend, carry):
    """``(value, value_and_vjp)`` of one way: x, [a], g -> [h,] u and its pull-back."""
    if which == "kernel":
        fn = lambda x, a, g: pn.stream_norm(x, g, eps=EPS, dtype=BF, offset=1.0, addend=a,
                                            carry=carry)
    else:
        norm = xla_norm(which == "barrier")
        def fn(x, a, g):
            h = x if a is None else x + a.astype(jnp.float32)
            return (h, norm(h, g)) if carry or a is not None else norm(h, g)

    def both(x, a, g, ct):
        out, pull = jax.vjp(fn, x, a, g)
        return out, pull(ct)
    return jax.jit(fn), jax.jit(both)


def main(out_path):
    keys = jax.random.split(jax.random.PRNGKey(41), 5)
    x, dh = (jax.random.normal(k, (1, T, D), jnp.float32) for k in keys[:2])
    a, du = (jax.random.normal(k, (1, T, D), jnp.float32).astype(BF) for k in keys[2:4])
    g = 0.1 * jax.random.normal(keys[4], (D,), jnp.float32)
    rows = []
    def say(row):
        rows.append(row)
        print(json.dumps(row), flush=True)
    # bytes an element at the least: forward reads x (4) [+ a (2)], writes u (2) [+ h (4)];
    # backward reads h (4), du (2) [+ dh (4)], writes dx (4) [+ da (2)]
    for addend, carry in ((False, False), (False, True), (True, True)):
        least = {"fwd_ms_at_819GBs": GB(6 + 6 * addend) / 0.819,
                 "bwd_ms_at_819GBs": GB(10 + 4 * carry + 2 * addend) / 0.819}
        for which, blocks in (("xla", (None,)), ("barrier", (None,)), ("kernel", (128, 256, 512))):
            for block in blocks:
                if block:
                    pn.MAX_ROWS, pn.BLOCK_ELEMENTS = block, block * D
                    pn._make_op.cache_clear()
                value, both = ways(which, addend, carry)
                ct = (dh, du) if carry else du
                try:
                    fwd = device_ms(value, (x, a if addend else None, g))
                    all_ = device_ms(both, (x, a if addend else None, g, ct))
                except Exception as e:      # a block the chip's fast memory refuses
                    say({"way": which, "addend": addend, "carry": carry, "rows_block": block,
                         "error": repr(e)[:300]})
                    continue
                say({"way": which, "addend": addend, "carry": carry, "rows_block": block,
                     "value_ms": round(sum(fwd.values()), 4),
                     "value_and_vjp_ms": round(sum(all_.values()), 4),
                     "value_ops": fwd, "value_and_vjp_ops": all_,
                     **{k: round(v, 4) for k, v in least.items()}})
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as fh:
            fh.writelines(json.dumps(r) + "\n" for r in rows)


if __name__ == "__main__":
    if jax.default_backend() != "tpu":
        sys.exit("step0.py measures device time: chip only")
    main(sys.argv[1] if len(sys.argv) > 1 else None)
