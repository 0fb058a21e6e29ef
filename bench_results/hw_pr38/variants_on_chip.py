"""Step 0's second call (PR 38): what the ablations pointed at, tried on the same script's
timer. The far blocks as levels throughout; then ``_group`` in two phases (what no chunk's
state enters, for every chunk of the grid step: running sums, pair scores, inverses, ``w``
and ``u``; then the chunks' states one after the other), the inverses' doublings taken a
step at a time across the chunks so that independent products stand next to each other;
products that share an operand merged; the running sum's product at ``high``.
usage (chip only): python3 bench_results/hw_pr38/variants_on_chip.py [out.jsonl]"""
import functools, os, sys
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import jax, jax.numpy as jnp
import kernels_on_chip as s0
from csed_514_project_distributed_training_using_pytorch_tpu.ops import kda

GROUP_OF_THE_TREE = kda._group
f32 = jnp.float32


def inverses(mats, sub, dtype, interleave):
    """``(I + a)⁻¹`` of every ``a`` of ``mats``; interleaved, each doubling is taken for every
    matrix before the next."""
    if not interleave:
        return [kda._unit_lower_inverse(a, sub, dtype) for a in mats]
    return list(_inverses(tuple(mats), sub, dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _inverses(mats, sub, dtype):
    c = mats[0].shape[0]
    row, col = kda._iota((c, c), 0), kda._iota((c, c), 1)
    eye = (row == col).astype(f32)
    mm = lambda xs, ys: [kda._dot(x, y, kda.NN, dtype) for x, y in zip(xs, ys)]

    def nilpotent(ns, index):
        invs, powers, reach = [eye - n for n in ns], ns, 2
        while reach < index:
            powers = mm(powers, powers)
            invs = [i + p for i, p in zip(invs, mm(invs, powers))]
            reach *= 2
        return invs

    inside = [jnp.where(row // sub == col // sub, a, 0.0) for a in mats]
    blocks = nilpotent(inside, sub)
    if c == sub:
        return tuple(blocks)
    rest = mm(blocks, [a - i for a, i in zip(mats, inside)])
    return tuple(mm(nilpotent(rest, c // sub), blocks))


def _inverses_fwd(mats, sub, dtype):
    out = _inverses(mats, sub, dtype)
    return out, out


def _inverses_bwd(sub, dtype, invs, ds):
    return (tuple(-kda._dot(kda._dot(i, d, kda.TN, dtype), i, kda.NT, dtype)
                  for i, d in zip(invs, ds)),)


_inverses.defvjp(_inverses_fwd, _inverses_bwd)


def _pieces(x):
    """``x`` float32 as three bfloat16 terms that sum to it (what ``highest`` splits both
    operands into; a triangle of ones needs no splitting)."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(f32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(f32)).astype(jnp.bfloat16)


def _triangle_product(x, contract):
    c = x.shape[0]
    ones = (kda._iota((c, c), 0) >= kda._iota((c, c), 1)).astype(jnp.bfloat16)
    return sum(jax.lax.dot_general(ones, piece, (contract, ((), ())), preferred_element_type=f32)
               for piece in _pieces(x))


@jax.custom_vjp
def running_sum(g):
    """The inclusive running sum down the rows in three passes of the MXU, not six."""
    return _triangle_product(g, kda.NN)


running_sum.defvjp(lambda g: (running_sum(g), None), lambda _, d: (_triangle_product(d, kda.TN),))


def group(q, k, v, g, beta, state, chunk, sub, eps, *, interleave=True, merge=False, split=False):
    dtype = q.dtype
    q = kda._unit(q.astype(f32), q.shape[1] ** -0.5)
    k = kda._unit(k.astype(f32))
    kb, vb = beta * k, beta * v.astype(f32)
    n, c = q.shape[0] // chunk, chunk
    split = lambda x: [x[i * c:(i + 1) * c] for i in range(n)]
    qs, ks, kbs, vbs, gs = map(split, (q, k, kb, vb, g))
    ones = (kda._iota((c, c), 0) >= kda._iota((c, c), 1)).astype(f32)
    cums = [running_sum(x) if split else
            jax.lax.dot_general(ones, x, (kda.NN, ((), ())), precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=f32) for x in gs]
    growns = [jnp.exp(x) for x in cums]
    scores = [kda._pair_scores(*x, sub, dtype) for x in zip(qs, ks, kbs, cums)]
    invs = inverses([kk for kk, _ in scores], sub, dtype, interleave)
    if merge:
        wus = [kda._dot(i, jnp.concatenate([kb_ * gr, vb_], axis=1), kda.NN, dtype)
               for i, kb_, gr, vb_ in zip(invs, kbs, growns, vbs)]
        ws, us = [x[:, :k.shape[1]] for x in wus], [x[:, k.shape[1]:] for x in wus]
    else:
        ws = [kda._dot(i, kb_ * gr, kda.NN, dtype) for i, kb_, gr in zip(invs, kbs, growns)]
        us = [kda._dot(i, vb_, kda.NN, dtype) for i, vb_ in zip(invs, vbs)]
    out = []
    for i in range(n):
        total = cums[i][c - 1:c]
        if merge:
            both = kda._dot(jnp.concatenate([ws[i], qs[i] * growns[i]]), state, kda.NT, dtype)
            fresh, carried = us[i] - both[:c], both[c:]
        else:
            fresh = us[i] - kda._dot(ws[i], state, kda.NT, dtype)
            carried = kda._dot(qs[i] * growns[i], state, kda.NT, dtype)
        out.append(carried + kda._dot(scores[i][1], fresh, kda.NN, dtype))
        state = state * jnp.exp(total) + kda._dot(fresh, ks[i] * jnp.exp(total - cums[i]),
                                                  kda.TN, dtype)
    o = jnp.concatenate(out)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps)
    return o.astype(v.dtype), state


def install(far="levels", **how):
    s0.install(pair=functools.partial(s0.pair_scores, far=far))
    kda._group = functools.partial(group, **how) if how else GROUP_OF_THE_TREE
    kda._make_op.cache_clear()


def main(argv):
    say, (operands, shape) = s0.recorder(argv), s0.sizes()
    runs = [("levels, the tree's group", 4, {}),
            ("two phases, inverses chunk by chunk", 4, dict(interleave=False)),
            ("two phases, inverses interleaved", 4, dict(interleave=True)),
            ("two phases, interleaved, merged products", 4, dict(interleave=True, merge=True)),
            ("two phases, interleaved, merged, running sum in three passes", 4,
             dict(interleave=True, merge=True, split=True)),
            ("two phases, interleaved, merged products", 2, dict(interleave=True, merge=True)),
            ("two phases, interleaved, merged products", 8, dict(interleave=True, merge=True)),
            ("two phases, interleaved, merged products", 16, dict(interleave=True, merge=True)),
            ("levels, the tree's group", 2, {})]
    for name, sub, how in runs:
        install(**how)
        s0.measure(name, sub, say, operands, shape, kinds=(jnp.bfloat16,))


if __name__ == "__main__":
    main(sys.argv)
