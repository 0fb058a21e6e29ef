"""The gradient through `route` with the pick's cotangent left to the compiler (`plain`: the k masked
sums alone) and written out behind an optimization barrier (`written`: this tree's `_pick`), beside the
parent's, at the three expert cells' shapes; ms a call, chip only. The reading behind `_pick`'s
docstring: 8.06 / 6.76 at 16,384 x 22 of 512, 2.00 / 1.98 at 8 of 256, 1.15 / 1.16 at 4 of 64
(my chip run, PR 36; the run was made before `_cotangent_written` was part of `_pick`).
usage: python bench_results/hw_pr36/barrier.py   (_scratch/parent = a `git archive` of the parent)"""
import importlib.util, json, os, statistics, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
import jax, jax.numpy as jnp
from csed_514_project_distributed_training_using_pytorch_tpu.ops import moe
spec = importlib.util.spec_from_file_location("parent_moe", os.path.join(sys.path[0], "_scratch/parent/csed_514_project_distributed_training_using_pytorch_tpu/ops/moe.py"))
parent = importlib.util.module_from_spec(spec); spec.loader.exec_module(parent)

written = moe._pick
def plain(scores, experts):
    of_expert = jnp.arange(scores.shape[-1], dtype=experts.dtype)[None]
    return jnp.stack([jnp.sum(jnp.where(experts[:, j:j + 1] == of_expert, scores, 0), axis=1)
                      for j in range(experts.shape[1])], axis=1)
def route_plain(*a, **kw):
    moe._pick = plain
    try: return moe.route(*a, **kw)
    finally: moe._pick = written

def timed(name, fn, *args, reps=20, rounds=5):
    fn = jax.jit(fn); out = jax.block_until_ready(fn(*args)); took = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(reps): last = fn(*args)
        jax.block_until_ready(last); took.append((time.perf_counter() - t0) / reps * 1e3)
    print(name, round(statistics.median(took), 4), flush=True)
    return out
small = jax.default_backend() != "tpu"
for cell, t, k, router, d in (("nemotron", 16384, 22, 512, 4096), ("kimi", 16384, 8, 256, 2304), ("lfm2", 32768, 4, 64, 2048)):
    if small: t, d = 256, 64
    ks = jax.random.split(jax.random.PRNGKey(36), 4)
    u = jax.random.normal(ks[0], (t, d), jnp.bfloat16); kernel = 0.02 * jax.random.normal(ks[1], (d, router), jnp.float32)
    bias = jnp.zeros((router,), jnp.float32); w = jax.random.normal(ks[2], (t, k), jnp.float32)
    outs = {}
    for name, route in (("parent", parent.route), ("plain", route_plain), ("written", moe.route)):
        outs[name] = timed(f"{cell} route_grad.{name}", lambda u, kernel, route=route: jax.grad(
            lambda u, kernel: jnp.sum(w * route(u, kernel, bias, top_k=k, scaling=5.0)[0]), argnums=(0, 1))(u, kernel), u, kernel)
    print("  written == plain:", all(bool((a == b).all()) for a, b in zip(outs["written"], outs["plain"])))
