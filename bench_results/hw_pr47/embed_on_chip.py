"""The embedding's backward pass alone on the chip: the gradient of sum(w * (table.astype(bf16)[ids] * m))
with respect to the float32 table, as the step takes it (a scatter-add of T rows of D into [V, D]) and as
other formulations give it, at the falcon_h1 cell's sizes and at nemotron_h_train_8k's. The traced cell read
58 ms a step under transpose(jvp(embed)) where the other cells read 3-5.
usage (chip): python3 bench_results/hw_pr47/embed_on_chip.py [out.jsonl]; on the CPU a tiny rehearsal."""
import json, os, sys, time
import jax, jax.numpy as jnp
ON_CHIP = jax.default_backend() == "tpu"
M = 5.656854249492381
SIZES = [(32640, 5120, 8192), (32640, 4096, 8192), (16384, 4096, 16384), (20480, 2304, 16384)] if ON_CHIP \
    else [(640, 256, 128)]
out = open(sys.argv[1], "w") if len(sys.argv) > 1 else None


def as_is(table, ids, w):
    return jax.grad(lambda t: jnp.sum(((t.astype(jnp.bfloat16)[ids] * M) * w).astype(jnp.float32)))(table)


def no_multiplier(table, ids, w):
    return jax.grad(lambda t: jnp.sum((t.astype(jnp.bfloat16)[ids] * w).astype(jnp.float32)))(table)


def float32_rows(table, ids, w):        # gather float32 rows, cast after: the scatter-add is float32
    return jax.grad(lambda t: jnp.sum(((t[ids].astype(jnp.bfloat16) * M) * w).astype(jnp.float32)))(table)


def one_hot(table, ids, w):             # the same sums as one product on the MXU
    oh = jax.nn.one_hot(ids.reshape(-1), table.shape[0], dtype=jnp.bfloat16)
    return jnp.matmul(oh.T, (w * M).reshape(-1, w.shape[-1]), preferred_element_type=jnp.float32)


def halves(table, ids, w):              # rows of D/2: the table viewed [2V, D/2], ids 2i and 2i + 1
    v, d = table.shape
    wide = jnp.stack([2 * ids, 2 * ids + 1], axis=-1).reshape(ids.shape[0], -1)
    return jax.grad(lambda t: jnp.sum(((t.reshape(2 * v, d // 2).astype(jnp.bfloat16)[wide] * M)
                                       * w.reshape(w.shape[0], -1, d // 2)).astype(jnp.float32)))(table)


def columns_4096_and_1024(table, ids, w):     # two gathers, each of a power-of-two width
    cut = 4096 if table.shape[1] > 4096 else table.shape[1] // 2

    def loss(t):
        low = t.astype(jnp.bfloat16)
        x = jnp.concatenate([low[:, :cut][ids], low[:, cut:][ids]], axis=-1)
        return jnp.sum(((x * M) * w).astype(jnp.float32))

    return jax.grad(loss)(table)


def rows_of_1024(table, ids, w):        # the table viewed [V D/1024, 1024], D/1024 rows an id
    v, d = table.shape
    k = d // 1024 if d % 1024 == 0 and d >= 1024 else 1
    wide = (k * ids[..., None] + jnp.arange(k)).reshape(ids.shape[0], -1)
    return jax.grad(lambda t: jnp.sum(((t.reshape(k * v, d // k).astype(jnp.bfloat16)[wide] * M)
                                       * w.reshape(w.shape[0], -1, d // k)).astype(jnp.float32)))(table)


def sorted_segments(table, ids, w):     # sort the rows by id, sum runs of equal ids, scatter unique rows
    v = table.shape[0]
    flat, rows = ids.reshape(-1), (w * M).reshape(-1, w.shape[-1])
    order = jnp.argsort(flat)
    return jax.ops.segment_sum(rows[order].astype(jnp.float32), flat[order], num_segments=v,
                               indices_are_sorted=True)


def timed(fn, *args, reps=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps


for v, d, t in SIZES:
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    table = 0.02 * jax.random.normal(ks[0], (v, d), jnp.float32)
    p = jnp.arange(1, v + 1, dtype=jnp.float32) ** -1.1         # Zipf ids, as the cell's
    ids = jax.random.choice(ks[1], v, (t // 8192 or 1, min(t, 8192)), p=p / p.sum()).astype(jnp.int32)
    w = jax.random.normal(ks[2], ids.shape + (d,), jnp.bfloat16)
    want = None
    for fn in (as_is, no_multiplier, float32_rows, one_hot, halves, columns_4096_and_1024, rows_of_1024,
               sorted_segments):
        got = jax.jit(fn)(table, ids, w)
        if fn is as_is:
            want = got
        gap = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) if fn is not no_multiplier else None
        row = {"v_d_t": [v, d, t], "variant": fn.__name__, "gap_to_as_is": gap, "device": jax.devices()[0].device_kind}
        if ON_CHIP:
            row["ms"] = 1e3 * timed(jax.jit(fn), table, ids, w)
        print(json.dumps(row), flush=True)
        if out:
            out.write(json.dumps(row) + "\n")
