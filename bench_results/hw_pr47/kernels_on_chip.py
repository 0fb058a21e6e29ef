"""ssd_fwd / ssd_bwd alone on the chip at the falcon_h1 cell's shapes (B 1, S 8192, H 8, P 128,
N 256, one group, chunk 128) and, beside them, at nemotron_h_train_8k's (B 2, S 8192, H 16, P 64,
N 128): values and the five gradients against the token-by-token recurrence (float32, highest)
on bf16-rounded operands, then the time of the forward and of forward + backward.
usage (chip): python3 bench_results/hw_pr47/kernels_on_chip.py [out.jsonl]; on the CPU a tiny
rehearsal of the same code (S 256, interpret mode), which says nothing about time."""
import json, os, sys, time
ROOT = os.path.realpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp
from csed_514_project_distributed_training_using_pytorch_tpu.ops import ssm

ON_CHIP = jax.default_backend() == "tpu"
S = 8192 if ON_CHIP else 256
SHAPES = {"falcon_h1 (P 128, N 256, H 8)": (1, S, 8, 128, 1, 256),
          "nemotron_h (P 64, N 128, H 16)": (2 if ON_CHIP else 1, S, 16, 64, 1, 128)}
out = open(sys.argv[1], "w") if len(sys.argv) > 1 else None


def inputs(b, s, h, p, g, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    dt = 0.5 * jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    decay = -jnp.exp(0.5 * jax.random.normal(ks[2], (h,)))
    lo = lambda v: v.astype(jnp.bfloat16)
    return (lo(jax.random.normal(ks[0], (b, s, h, p))), dt, dt * decay,
            lo(0.3 * jax.random.normal(ks[3], (b, s, g, n))), lo(0.3 * jax.random.normal(ks[4], (b, s, g, n))))


def token_by_token(x, dt, a, b, c):
    rep = x.shape[2] // b.shape[2]
    x, b, c = x.astype(jnp.float32), *(jnp.repeat(v.astype(jnp.float32), rep, axis=2) for v in (b, c))

    def token(state, now):
        x_t, dt_t, a_t, b_t, c_t = now
        state = jnp.exp(a_t)[..., None, None] * state + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t, precision="highest")

    zero = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:])
    step = jax.checkpoint(lambda st, nows: jax.lax.scan(token, st, nows))
    cut = lambda v: jnp.moveaxis(v, 1, 0).reshape((v.shape[1] // 128, 128) + v.shape[:1] + v.shape[2:])
    _, y = jax.lax.scan(step, zero, tuple(map(cut, (x, dt, a, b, c))))
    return jnp.moveaxis(y.reshape((-1,) + y.shape[2:]), 0, 1)


def timed(fn, *args, reps=20):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps


for name, shape in SHAPES.items():
    args = inputs(*shape)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape, jnp.float32)
    scan = lambda *a: jnp.sum(w * ssm.ssd_scan(*a).astype(jnp.float32))
    plain = lambda *a: jnp.sum(w * token_by_token(*a))
    both = jax.jit(jax.value_and_grad(scan, argnums=(0, 1, 2, 3, 4)))
    (got, grads), (want, wants) = both(*args), jax.jit(jax.value_and_grad(plain, argnums=(0, 1, 2, 3, 4)))(*args)
    y_want = jax.jit(token_by_token)(*args)
    y_gap = float(jnp.abs(ssm.ssd_scan(*args).astype(jnp.float32) - y_want).max() / jnp.abs(y_want).max())
    gaps = {n: float(jnp.linalg.norm(g.astype(jnp.float32) - r.astype(jnp.float32)) / jnp.linalg.norm(r.astype(jnp.float32)))
            for n, g, r in zip("x dt a b c".split(), grads, wants)}
    row = {"shape": name, "b_s_h_p_g_n": shape, "device": jax.devices()[0].device_kind, "y_max_gap": y_gap,
           "value_gap": abs(float(got) - float(want)) / abs(float(want)), "gradient_norm_gaps": gaps}
    if ON_CHIP:
        fwd = timed(jax.jit(lambda *a: ssm.ssd_scan(*a)), *args)
        fb = timed(both, *args)
        b_, s_, h_, p_, g_, n_ = shape
        q = 128
        flops = b_ * s_ / q * (g_ * 2.0 * q * q * n_ + h_ * (2.0 * q * q * p_ + 4.0 * q * n_ * p_))
        row.update(fwd_ms=1e3 * fwd, fwd_bwd_ms=1e3 * fb, fwd_flops=flops,
                   fwd_share_of_197_tflops=flops / fwd / 197e12, fwd_bwd_share=3 * flops / fb / 197e12,
                   us_per_chunk_head_fwd=1e6 * fwd / (b_ * s_ / q * h_), us_per_chunk_head_fwd_bwd=1e6 * fb / (b_ * s_ / q * h_))
    print(json.dumps(row), flush=True)
    if out:
        out.write(json.dumps(row) + "\n")
