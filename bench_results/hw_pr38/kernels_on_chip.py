"""Step 0 of PR 38: kda_fwd / kda_bwd as Mosaic compiles them, on the chip, at sub-blocks of
16, 8 and 4 (bench_results/hw_pr34/kernels_on_chip.py with ``sub`` a loop): (a) against the
token-by-token recurrence (tests/test_kimi_linear.py's own helpers) at the published tile,
float32 and bfloat16 operands, decays 1 and 8; (b) at the cell's shapes (2 x 8192 tokens, 32
heads of 128 x 128, bf16) timed alone. Then the same two for candidate forms of the far
blocks (``far``: "rows" is the tree's, "upto" forms the column operand for the rows before
the sub-block only, "levels" takes one product a doubling of the block, both operands
rescaled against the middle of the doubled block) and, timing only and wrong answers and
all, the ablations: the diagonals out, the far blocks out, the inverse out, the running
sum at the default precision.
usage (chip only): python3 bench_results/hw_pr38/kernels_on_chip.py [out.jsonl]"""
import functools, json, os, sys, time
root = os.path.realpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path[:0] = [root, os.path.join(root, "tests")]
import jax, jax.numpy as jnp
import test_kimi_linear as t
from csed_514_project_distributed_training_using_pytorch_tpu.ops import kda

# The forms below patch the parent's ops/kda.py (git d9f1d90), which is what this script and
# variants_on_chip.py measured; final_on_chip.py takes ``check`` and ``timed`` for the tree.
TREE = {"pair": kda._pair_scores, "inverse": getattr(kda, "_unit_lower_inverse", None),
        "chunk": getattr(kda, "_chunk", None)}


def pair_scores(q, k, kb, cum, sub, dtype, *, diagonals=True, far="rows"):
    """``kda._pair_scores`` with its two halves switchable."""
    c, d = k.shape
    row, col = kda._iota((c, c), 0), kda._iota((c, c), 1)
    within = kda._iota((c, d), 0) % sub
    rowsum = lambda x: jnp.sum(x, axis=1, keepdims=True)
    kk = jnp.zeros((c, c), jnp.float32)
    qk = jnp.where(row == col, rowsum(q * k), 0.0)
    for by in range(1, sub if diagonals else 1):
        near = within >= by
        decay = jnp.exp(jnp.where(near, cum - kda._shifted(cum, by), 0.0))
        kd = jnp.where(near, kda._shifted(k, by) * decay, 0.0)
        at = row - col == by
        kk = jnp.where(at, rowsum(kb * kd), kk)
        qk = jnp.where(at, rowsum(q * kd), qk)
    if far == "none":
        return kk, qk
    if far == "levels":
        half = sub
        while half < c:
            middle = jnp.concatenate([jnp.broadcast_to(cum[n + half:n + half + 1], (2 * half, d))
                                      for n in range(0, c, 2 * half)])
            later = kda._iota((c, d), 0) % (2 * half) >= half
            scale = jnp.exp(jnp.where(later, cum - middle, middle - cum))
            both = kda._dot(jnp.concatenate([kb * scale, q * scale]), k * scale, kda.NT, dtype)
            at = (row // (2 * half) == col // (2 * half)) & (col // half < row // half)
            kk, qk = jnp.where(at, both[:c], kk), jnp.where(at, both[c:], qk)
            half *= 2
        return kk, qk
    far_kk, far_qk = [jnp.zeros((sub, c), jnp.float32)], [jnp.zeros((sub, c), jnp.float32)]
    for n in range(sub, c, sub):
        first = cum[n:n + 1]
        down = jnp.exp(cum[n:n + sub] - first)
        if far == "upto":
            before = jnp.concatenate([k[:n] * jnp.exp(jnp.minimum(first - cum[:n], 0.0)),
                                      jnp.zeros((c - n, d), jnp.float32)])
        else:
            before = k * jnp.exp(jnp.minimum(first - cum, 0.0))
        both = kda._dot(jnp.concatenate([kb[n:n + sub] * down, q[n:n + sub] * down]),
                        before, kda.NT, dtype)
        far_kk.append(both[:sub])
        far_qk.append(both[sub:])
    mask = col // sub < row // sub
    return (jnp.where(mask, jnp.concatenate(far_kk), kk),
            jnp.where(mask, jnp.concatenate(far_qk), qk))


def no_inverse(a, sub, dtype):
    return (kda._iota(a.shape, 0) == kda._iota(a.shape, 1)).astype(jnp.float32) - a


def chunk_default_cum(q, k, kb, vb, g, state, sub, dtype):
    """``kda._chunk`` with the running sum's product at the default precision."""
    real = jax.lax.dot_general

    def dot_general(a, b, dims, precision=None, preferred_element_type=None):
        return real(a, b, dims, preferred_element_type=preferred_element_type)

    jax.lax.dot_general = dot_general
    try:
        return TREE["chunk"](q, k, kb, vb, g, state, sub, dtype)
    finally:
        jax.lax.dot_general = real


def install(pair=None, inverse=None, chunk=None):
    kda._pair_scores = pair or TREE["pair"]
    kda._unit_lower_inverse = inverse or TREE["inverse"]
    kda._chunk = chunk or TREE["chunk"]
    kda._make_op.cache_clear()


rel = lambda a, b: float(jnp.abs(a.astype(jnp.float32) - b).max() / jnp.abs(b).max())
WANT = {}


def check(sub, shape=(1, 640, 2, 128, 128), kinds=(jnp.float32, jnp.bfloat16)):
    out = []
    for dtype in kinds:
        for decay in (1.0, 8.0):
            q, k, v, g, beta = t.scan_inputs(*shape, decay, seed=11)
            low = tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)
            w = jax.random.normal(jax.random.PRNGKey(9), v.shape)
            scan = lambda *a: t.flat_scan(*a, sub=sub).astype(jnp.float32)
            got, grads = t.scan_and_gradients(scan, low, w)
            key = (jnp.dtype(dtype).name, decay)
            if key not in WANT:
                with jax.default_matmul_precision("highest"):
                    WANT[key] = t.scan_and_gradients(
                        t.normed_recurrence, tuple(x.astype(jnp.float32) for x in low), w)
            want, wants = WANT[key]
            out.append({"check": key[0], "decay": decay, "out": rel(got, want),
                        **{n: rel(a, b) for n, a, b in zip(t.OPERANDS, grads, wants)},
                        "finite": bool(all(jnp.isfinite(x.astype(jnp.float32)).all()
                                           for x in grads))})
    return out


def timed(sub, operands, reps=5):
    b, s, h = operands[4].shape
    scan = functools.partial(kda.kda_scan, eps=1e-5, sub=sub)
    forward = jax.jit(lambda *a: scan(*a))
    both = jax.jit(jax.grad(lambda *a: jnp.sum(scan(*a).astype(jnp.float32)),
                            argnums=(0, 1, 2, 3, 4)))
    out, chunks = {}, b * h * s // kda.KDA_TILING.chunk
    for name, fn in (("kda_fwd", forward), ("kda_fwd+kda_bwd", both)):
        t0 = time.perf_counter(); jax.block_until_ready(fn(*operands))
        out[f"{name}_first_call_s"] = round(time.perf_counter() - t0, 2)
        times = []
        for _ in range(reps):
            t0 = time.perf_counter(); jax.block_until_ready(fn(*operands))
            times.append(time.perf_counter() - t0)
        out[name] = {"ms": [round(1e3 * x, 3) for x in times],
                     "us_a_chunk_and_head": round(1e6 * min(times) / chunks, 3)}
    return out


def recorder(argv):
    """``say(record)``: a JSON line to stdout and, appended, to the file ``argv[1]`` names."""
    sink = None
    if len(argv) > 1:
        os.makedirs(os.path.dirname(os.path.abspath(argv[1])), exist_ok=True)
        sink = open(argv[1], "a")

    def say(record):
        line = json.dumps(record)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n"); sink.flush()
    return say


def sizes():
    """``(the timed operands at the cell's shapes, the checked shape)``; off the chip a tiny
    size of both: a rehearsal of the script, no measurement."""
    tiny = jax.default_backend() != "tpu"
    b, s, h, d = (1, 64, 2, 16) if tiny else (2, 8192, 32, 128)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = [jax.nn.silu(jax.random.normal(key, (b, s, h * d))).astype(jnp.bfloat16) for key in ks[:3]]
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, s, h * d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return (*x, g, beta), (1, 128, 2, 16, 16) if tiny else (1, 640, 2, 128, 128)


def measure(name, sub, say, operands, shape, kinds=(jnp.float32, jnp.bfloat16)):
    record = {"variant": name, "sub": sub, "device": jax.devices()[0].device_kind}
    try:
        if kinds:
            record["checks"] = check(sub, shape, kinds)
        record.update(timed(sub, operands, reps=5 if jax.default_backend() == "tpu" else 1))
    except Exception as e:                      # a form Mosaic refuses is a finding too
        record["error"] = f"{type(e).__name__}: {str(e)[:400]}"
    say(record)


def main(argv):
    say, (operands, shape) = recorder(argv), sizes()
    part = functools.partial
    runs = [("tree", sub, {}, True) for sub in (16, 8, 4)]
    runs += [(f"far={far}", sub, {"pair": part(pair_scores, far=far)}, True)
             for far, subs in (("upto", (8, 4)), ("levels", (16, 8, 4, 2, 1))) for sub in subs]
    for sub in (16, 8):
        runs += [("no diagonals", sub, {"pair": part(pair_scores, diagonals=False)}, False),
                 ("no far blocks", sub, {"pair": part(pair_scores, far="none")}, False),
                 ("no pair scores", sub, {"pair": part(pair_scores, diagonals=False, far="none")},
                  False),
                 ("no inverse", sub, {"inverse": no_inverse}, False),
                 ("running sum at default precision", sub, {"chunk": chunk_default_cum}, False)]
    for name, sub, patch, checked in runs:
        install(**patch)
        measure(name, sub, say, operands, shape, (jnp.float32, jnp.bfloat16) if checked else ())
    install()


if __name__ == "__main__":
    main(sys.argv)
