"""ISSUE 48's step 0, on the chip: the rotation alone, three ways, at the shapes the cells run.
`present`: the parent's ops/rotary.py (0a86630) written out here, with the split and rejoin its
call sites put around a part of a head; `permutation`: ops.rotary.apply_rotary as committed,
x·C + (x P)·S with P a signed [D, D] permutation on the MXU, whole head in, whole head out (call
A ran it from the tree before the form was chosen, the same body without the barrier, which alone
has nothing before it to hold back); `roll`: the same sum with the partner read by a lane roll in a
Pallas kernel over [B, S, H·D] (roll_kernel.py beside this file). Forward alone, and
forward with the pull-back of a given cotangent; device self time by op from a profiler trace, ms
a call, beside the 2 x bytes / 819 GB/s of one read and one write of the operand (twice that for
forward with backward). Alone under jit the operand is row-major; inside an epoch program the
compiler keeps a projection's output token-minor, which this cannot see (PERF.md section 6, PR 48).
usage (chip): python3 bench_results/hw_pr48/rotary_alone.py [out.jsonl]
       COMPILE_ONLY=1 JAX_PLATFORMS=cpu ...: compile every form for a described v5e, run nothing"""
import json, os, shutil, sys, tempfile
os.environ.setdefault("TPU_LOG_DIR", "disabled")
root = os.path.realpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path[:0] = [root, os.path.join(root, "benchmark"), os.path.dirname(os.path.abspath(__file__))]
import jax, jax.numpy as jnp, numpy as np
from csed_514_project_distributed_training_using_pytorch_tpu.ops import rotary
import roll_kernel

COMPILE_ONLY = bool(os.environ.get("COMPILE_ONLY"))
BF = jnp.bfloat16
# cell's tensor: shape, interleaved, (first, width) of the channels that turn, base
SHAPES = {
    "evabyte q,k": ((1, 32768, 16, 128), False, None, 1e5),
    "lm_train_b16 q": ((16, 784, 8, 128), False, None, 1e4),
    "lm_train_b16 k": ((16, 784, 2, 128), False, None, 1e4),
    "lfm2 q": ((4, 8192, 32, 64), False, None, 1e6),
    "lfm2 k": ((4, 8192, 8, 64), False, None, 1e6),
    "qwen3_next q": ((2, 8192, 16, 256), False, (0, 64), 1e7),
    "qwen3_next k": ((2, 8192, 2, 256), False, (0, 64), 1e7),
    "kanana2 q": ((2, 8192, 32, 192), True, (128, 64), 1e6),
    "kanana2 shared key": ((2, 8192, 1, 64), True, None, 1e6),
    "falcon_h1 q": ((1, 8192, 5, 128), False, None, 1e11),
    "falcon_h1 k": ((1, 8192, 1, 128), False, None, 1e11),
}


def parent_apply_rotary(x, positions, *, base, interleaved=False):
    """ops/rotary.py::apply_rotary of the parent commit, line for line."""
    d = x.shape[-1]
    inv_freq = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (positions.astype(jnp.float32)[..., None] * inv_freq)[..., :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    if interleaved:
        lanes = np.arange(d)
        swap = np.zeros((d, d), np.float32)
        swap[lanes ^ 1, lanes] = np.where(lanes % 2 == 0, -1.0, 1.0)
        partner = jnp.matmul(x, jnp.asarray(swap, x.dtype),
                             precision=jax.lax.Precision.HIGHEST).astype(jnp.float32)
        cos, sin = jnp.repeat(cos, 2, axis=-1), jnp.repeat(sin, 2, axis=-1)
        return (xf * cos + partner * sin).astype(x.dtype)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def present(x, positions, base, interleaved, channels):
    if channels is None:
        return parent_apply_rotary(x, positions, base=base, interleaved=interleaved)
    first, width = channels         # hybrid_lm.attention_mixer.turned / mla_mixer of the parent
    return jnp.concatenate(
        [x[..., :first], parent_apply_rotary(x[..., first:first + width], positions, base=base,
                                             interleaved=interleaved),
         x[..., first + width:]], axis=-1)


def permutation(x, positions, base, interleaved, channels):
    return rotary.apply_rotary(x, positions, base=base, interleaved=interleaved, channels=channels)


def roll(x, positions, base, interleaved, channels):
    return roll_kernel.roll_rotary(x, positions, base, interleaved, channels)


FORMS = {"present": present, "permutation": permutation, "roll": roll}


def device_ms(fn, args, reps=5):
    import xplane
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


def main(out_path):
    if COMPILE_ONLY:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        roll_kernel._interpret = lambda: False
    rows = []
    for cell, (shape, interleaved, channels, base) in SHAPES.items():
        positions = jnp.arange(shape[1])
        least = 2 * 2 * int(np.prod(shape)) / 819e9 * 1e3
        reference = None
        for form, turn in FORMS.items():
            row = {"tensor": cell, "shape": list(shape), "interleaved": interleaved,
                   "channels": channels, "form": form, "read_and_write_ms_at_819GBs": round(least, 4)}
            if form == "roll" and roll_kernel.roll_plan(shape, interleaved, channels) is None:
                row["skipped"] = "the roll kernel does not take this shape (roll_kernel.roll_plan)"
                rows.append(row); print(json.dumps(row), flush=True)
                continue
            value = jax.jit(lambda x, turn=turn: turn(x, positions, base, interleaved, channels))
            def both(x, ct, turn=turn):
                out, pull = jax.vjp(lambda x: turn(x, positions, base, interleaved, channels), x)
                return pull(ct)[0], out
            both = jax.jit(both)
            try:
                if COMPILE_ONLY:
                    spec = jax.ShapeDtypeStruct(shape, BF, sharding=chip)
                    for name, fn, args in (("value", value, (spec,)), ("value_and_vjp", both, (spec, spec))):
                        text = fn.lower(*args).compile().as_text()
                        row[name + "_compiles"] = True
                        row[name + "_fusions_and_copies"] = text.count(" fusion(") + text.count(" copy(")
                else:
                    keys = jax.random.split(jax.random.PRNGKey(48), 2)
                    x, ct = (jax.random.normal(k, shape, jnp.float32).astype(BF) for k in keys)
                    out = np.asarray(value(x), np.float32)
                    grad = np.asarray(both(x, ct)[0], np.float32)
                    if reference is None:
                        reference = (out, grad)
                    row["max_abs_difference_from_present"] = [
                        float(np.abs(out - reference[0]).max()), float(np.abs(grad - reference[1]).max())]
                    fwd, all_ = device_ms(value, (x,)), device_ms(both, (x, ct))
                    row.update(value_ms=round(sum(fwd.values()), 4),
                               value_and_vjp_ms=round(sum(all_.values()), 4),
                               value_ops=fwd, value_and_vjp_ops=all_)
            except Exception as e:      # a form the compiler refuses does not stop the others
                row["error"] = f"{type(e).__name__}: {e}"[:400]
            rows.append(row)
            print(json.dumps(row), flush=True)
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as fh:
            fh.writelines(json.dumps(r) + "\n" for r in rows)


if __name__ == "__main__":
    if not COMPILE_ONLY and jax.default_backend() != "tpu":
        sys.exit("rotary_alone.py measures device time: chip only (COMPILE_ONLY=1 to rehearse)")
    main(sys.argv[1] if len(sys.argv) > 1 else None)
