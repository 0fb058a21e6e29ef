"""Step 0 of ISSUE 36: the routing's crossings by index, each alone, on the chip, at
nemotron_h_train_8k's shapes (T 16,384, k 22, E 512, held (0, 8), tm 256), the parent's forms
(ops/moe.py of a `git archive` of the parent at _scratch/parent) beside this tree's and beside
the variants this PR weighed. Host clock around `reps` back-to-back calls of one jitted function
that ends in block_until_ready, the median of five such rounds, ms a call; every pair is also
compared element for element. Chip only.
usage: python bench_results/hw_pr36/step0.py [--out chiprun_out/pr36/step0.jsonl]"""
import importlib.util, json, os, statistics, sys, time
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
import jax, jax.numpy as jnp, numpy as np
from csed_514_project_distributed_training_using_pytorch_tpu.ops import moe


def load_parent():
    path = os.path.join(REPO, "_scratch/parent/csed_514_project_distributed_training_using_pytorch_tpu/ops/moe.py")
    spec = importlib.util.spec_from_file_location("parent_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


parent = load_parent()
out_path = sys.argv[sys.argv.index("--out") + 1] if "--out" in sys.argv else None
if jax.default_backend() != "tpu" and "--anywhere" not in sys.argv:
    sys.exit("step0: no TPU here; a time from the CPU would be the interpreter's")
SMALL = "--anywhere" in sys.argv
lines = []


def timed(name, fn, *args, reps=20, rounds=5, **note):
    fn = jax.jit(fn)
    out = jax.block_until_ready(fn(*args))
    took = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(reps):
            last = fn(*args)
        jax.block_until_ready(last)
        took.append((time.perf_counter() - t0) / reps * 1e3)
    line = {"piece": name, "ms": round(statistics.median(took), 4), "min_ms": round(min(took), 4),
            "max_ms": round(max(took), 4), **note}
    lines.append(line)
    print(json.dumps(line), flush=True)
    return out


def same(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(x.shape == y.shape and x.dtype == y.dtype and bool((x == y).all())
                                     for x, y in zip(la, lb))


# ---- variants weighed ------------------------------------------------------------------------

def held_first_triangle(weights, experts, held):
    """cumsum(is_held) as a masked sum over [T, k, k] (no reduce-window)."""
    k, keep = experts.shape[1], min(experts.shape[1], held[1])
    local = experts - held[0]
    is_held = (local >= 0) & (local < held[1])
    upto = jnp.arange(k)[:, None] >= jnp.arange(k)[None]                   # [j, i]: i <= j
    ahead = jnp.sum(jnp.where(upto[None], is_held[:, None, :].astype(jnp.int32), 0), axis=2)
    others = jnp.arange(1, k + 1)[None] - ahead
    slot = jnp.where(is_held, ahead, ahead[:, -1:] + others) - 1
    front = lambda x: jnp.stack([jnp.sum(jnp.where(slot == s, x, 0), axis=1) for s in range(keep)], axis=1)
    return front(weights), front(experts)


def sort_rows_major(experts, held, tm):
    """This tree's _sort with `lands` as [a, n], the parent's orientation."""
    first, n = held
    t, k = experts.shape
    a = t * k
    local = experts.reshape(a) - first
    is_held = (local >= 0) & (local < n)
    key = jnp.where(is_held, local, n)
    lands = (key[:, None] == jnp.arange(n)[None]).astype(jnp.int32)
    running = jnp.cumsum(lands, axis=0)
    counts = running[-1]
    tiles = jnp.maximum(1, -(-counts // tm))
    tile_end = jnp.cumsum(tiles)
    seg_start = (tile_end - tiles) * tm
    pos = jnp.sum(lands * (seg_start[None] + running - 1), axis=1)
    order = jnp.argsort(key, stable=True)
    unaligned = jnp.cumsum(counts) - counts
    n_tiles = -(-a // tm) + n
    tile = jnp.arange(n_tiles)
    tile_expert = jnp.minimum(jnp.sum(tile[:, None] >= tile_end[None], axis=1), n - 1)
    of_expert = tile_expert[:, None] == jnp.arange(n)[None]
    start, count = (jnp.sum(jnp.where(of_expert, v[None], 0), axis=1) for v in (seg_start, counts))
    offset = (tile * tm - start)[:, None] + jnp.arange(tm)[None]
    valid = (offset < count[:, None]) & (tile < tile_end[-1])[:, None]
    room = n * tm
    padded = jnp.concatenate([jnp.zeros(room, order.dtype), order, jnp.zeros(n_tiles * tm - a, order.dtype)])
    source = jnp.zeros((n_tiles, tm), order.dtype)
    for e in range(n):
        moved = jax.lax.dynamic_slice(padded, (room - (seg_start[e] - unaligned[e]),), (n_tiles * tm,))
        source = jnp.where(of_expert[:, e:e + 1], moved.reshape(n_tiles, tm), source)
    source, valid = source.reshape(-1), valid.reshape(-1)
    token_tiles = -(-t // tm)
    of_tile = jnp.pad(lands, ((0, token_tiles * tm * k - a), (0, 0))).reshape(token_tiles, tm * k, n).sum(axis=1)
    before = jnp.concatenate([jnp.zeros((1, n), jnp.int32), jnp.cumsum(of_tile, axis=0)])
    return {"counts": counts, "num_tiles": tile_end[-1].astype(jnp.int32),
            "rows_of_tokens": (seg_start[None] + before).T.astype(jnp.int32),
            "tile_expert": tile_expert.astype(jnp.int32),
            "assignment_of_row": jnp.where(valid, source, 0).astype(jnp.int32),
            "token_of_row": jnp.where(valid, source // k, -1).astype(jnp.int32),
            "pos": pos.reshape(t, k).astype(jnp.int32), "is_held": is_held.reshape(t, k)}


def pick_dense(scores, experts):
    """The pick as k masked sums over [T, E]: autodiff transposes it into k selects by itself."""
    of_expert = jnp.arange(scores.shape[-1], dtype=experts.dtype)[None]
    return jnp.stack([jnp.sum(jnp.where(experts[:, j:j + 1] == of_expert, scores, 0), axis=1)
                      for j in range(experts.shape[1])], axis=1)


def route_with(pick):
    def route(*args, **kw):
        kept = moe._pick
        moe._pick = pick
        try:
            return moe.route(*args, **kw)
        finally:
            moe._pick = kept
    return route


# ---- the pieces -------------------------------------------------------------------------------

def run(cell, t, k, router, d, held, tm, seed=36):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    u = jax.random.normal(ks[0], (t, d), jnp.bfloat16)
    kernel = 0.02 * jax.random.normal(ks[1], (d, router), jnp.float32)
    bias = jnp.zeros((router,), jnp.float32)
    w = jax.random.normal(ks[2], (t, k), jnp.float32)
    shape = dict(cell=cell, T=t, k=k, E=router, held=list(held), tm=tm)
    weights, experts = jax.jit(lambda u, kernel: parent.route(u, kernel, bias, top_k=k))(u, kernel)

    if k > held[1]:
        old = timed("held_first.parent", lambda w, e: parent._held_first(w, e, held), weights, experts, **shape)
        new = timed("held_first.change", lambda w, e: moe._held_first(w, e, held), weights, experts, **shape)
        tri = timed("held_first.triangle", lambda w, e: held_first_triangle(w, e, held), weights, experts, **shape)
        print("  same values:", same(old, new), same(old, tri))
        grad = lambda f: (lambda w_, e: jax.grad(lambda w_: jnp.sum(f(w_, e, held)[0] * w[:, :held[1]]))(w_))
        gold = timed("held_first_grad.parent", grad(parent._held_first), weights, experts, **shape)
        gnew = timed("held_first_grad.change", grad(moe._held_first), weights, experts, **shape)
        print("  same gradient:", same(gold, gnew))
        cut = old[1]
    else:
        cut = experts
    old = timed("sort.parent", lambda e: parent._sort(e, held, tm), cut, **shape)
    new = timed("sort.change", lambda e: moe._sort(e, held, tm), cut, **shape)
    alt = timed("sort.rows_major", lambda e: sort_rows_major(e, held, tm), cut, **shape)
    print("  same values:", same(old, new), same(old, alt), "rows arrived", int(old["counts"].sum()))
    if k > held[1]:
        both_old = timed("held_first+sort.parent", lambda w, e: (lambda c: (c[0], parent._sort(c[1], held, tm)))(
            parent._held_first(w, e, held)), weights, experts, **shape)
        both_new = timed("held_first+sort.change", lambda w, e: (lambda c: (c[0], moe._sort(c[1], held, tm)))(
            moe._held_first(w, e, held)), weights, experts, **shape)
        print("  same values:", same(both_old, both_new))
    key = jnp.where((cut.reshape(-1) >= held[0]) & (cut.reshape(-1) < held[0] + held[1]), cut.reshape(-1) - held[0], held[1])
    timed("sort.argsort_alone", lambda key: jnp.argsort(key, stable=True), key, **shape)
    timed("sort.cumsum_alone[n,a]", lambda key: jnp.cumsum((jnp.arange(held[1])[:, None] == key[None]).astype(jnp.int32), axis=1), key, **shape)
    timed("sort.cumsum_alone[a,n]", lambda key: jnp.cumsum((key[:, None] == jnp.arange(held[1])[None]).astype(jnp.int32), axis=0), key, **shape)

    routes = {"parent": parent.route, "change": moe.route, "dense_pick": route_with(pick_dense)}
    value, gradient = {}, {}
    for name, route in routes.items():
        value[name] = timed(f"route_fwd.{name}", lambda u, kernel, route=route: route(u, kernel, bias, top_k=k, scaling=5.0),
                            u, kernel, **shape)
        gradient[name] = timed(f"route_grad.{name}", lambda u, kernel, route=route: jax.grad(
            lambda u, kernel: jnp.sum(w * route(u, kernel, bias, top_k=k, scaling=5.0)[0]), argnums=(0, 1))(u, kernel),
            u, kernel, **shape)
    print("  same values:", {n: same(value["parent"], v) for n, v in value.items()},
          "same gradients:", {n: same(gradient["parent"], g) for n, g in gradient.items()},
          "largest |difference|:", {n: max(float(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)).max())
                                           for x, y in zip(gradient["parent"], g)) for n, g in gradient.items()})
    scores = jax.nn.sigmoid(jax.random.normal(ks[3], (t, router), jnp.float32))
    for name, pick in (("scatter", lambda s, e: jnp.take_along_axis(s, e, axis=-1)), ("change", moe._pick), ("dense", pick_dense)):
        timed(f"pick_fwd.{name}", pick, scores, experts, **shape)
        timed(f"pick_grad.{name}", lambda s, e, pick=pick: jax.grad(lambda s: jnp.sum(w * pick(s, e)))(s), scores, experts, **shape)


print("device:", jax.devices()[0].device_kind, len(jax.devices()))
if SMALL:
    run("tiny", 512, 6, 16, 64, (0, 4), 16)
else:
    run("nemotron_h_train_8k", 16384, 22, 512, 4096, (0, 8), 256)
    run("kimi_linear_train_8k", 16384, 8, 256, 2304, (0, 8), 256)
    run("lfm2_moe_train_8k", 32768, 4, 64, 2048, (0, 8), 256)
if out_path:
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as fh:
        fh.writelines(json.dumps(line) + "\n" for line in lines)
