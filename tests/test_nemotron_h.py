"""The ``nemotron_h`` stack of ``models/hybrid_lm.py``, ``ops/ssm.py`` and the relu²
expert product of ``ops/moe.py`` against the plain reference
(``benchmark/reference/nemotron_h.py``, which imports nothing of the program) and
against hand-written loops: small sizes, float32, seeded weights; Pallas in interpret
mode."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import nemotron_h as ref  # noqa: E402
from reference import precision as prec  # noqa: E402
import head_rule  # noqa: E402
import weights as bench_weights  # noqa: E402

from csed_514_project_distributed_training_using_pytorch_tpu.models import (  # noqa: E402
    hybrid_lm,
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops import moe, ssm  # noqa: E402

CONFIG_FILE = os.path.join(BENCH, "configs", "nemotron3-super-120b-tp8-ep64.json")
SEQ, VOCAB = 24, 64
MM, ES = prec.matmul("highest"), prec.einsum("highest")


def tiny_config(**changes) -> dict:
    """The benchmark's configuration with its widths cut: 4 of 16 experts held, 6 of
    them a token (more than are held), 4 heads in 2 groups, chunks of 8 tokens."""
    with open(CONFIG_FILE) as fh:
        config = json.load(fh)
    config.update(hidden_size=32, head_dim=8, num_attention_heads=4, num_key_value_heads=2,
                  mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
                  chunk_size=8, moe_intermediate_size=24, moe_latent_size=16,
                  moe_shared_expert_intermediate_size=40, n_routed_experts=4,
                  num_experts_per_tok=6, vocab_size=VOCAB, num_hidden_layers=4,
                  hybrid_override_pattern="ME*E")
    config["published"] = dict(config["published"], n_routed_experts=16)
    config["share"] = dict(config["share"], first_layer=0, shared_expert_columns=20)
    config.update(changes)
    return config


def build(config, seed=20260929, **kw):
    model = hybrid_lm.from_config(config, vocab_size=config["vocab_size"], seq_len=SEQ,
                                  expert_block=8, **kw)
    return model, bench_weights.make(ref.param_shapes(config), seed)


def tokens(batch=2, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, VOCAB, (batch, SEQ)),
                       jnp.int32)


# (a) the scan ----------------------------------------------------------------------------


def scan_inputs(b, s, h, p, g, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    dt = 0.5 * jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    decay = -jnp.exp(0.5 * jax.random.normal(ks[2], (h,)))
    return (jax.random.normal(ks[0], (b, s, h, p)), dt, dt * decay,
            0.3 * jax.random.normal(ks[3], (b, s, g, n)),
            0.3 * jax.random.normal(ks[4], (b, s, g, n)))


def token_by_token(x, dt, a, b, c):
    """The definition: ``S_t = exp(a_t) S_{t-1} + dt_t x_t ⊗ b_t``, ``y_t = S_t c_t``, one
    token after the other from a zero state (``a`` an operand of its own, as the
    kernels take it)."""
    rep = x.shape[2] // b.shape[2]
    b, c = (jnp.repeat(v, rep, axis=2) for v in (b, c))

    def token(state, now):
        x_t, dt_t, a_t, b_t, c_t = now
        state = jnp.exp(a_t)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    zero = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:])
    _, y = jax.lax.scan(token, zero, tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, a, b, c)))
    return jnp.moveaxis(y, 0, 1)


def test_the_references_recurrence_is_the_definition():
    x, dt, a, b, c = scan_inputs(1, 40, 2, 8, 1, 8)
    with jax.default_matmul_precision("highest"):
        got = ref.recurrence(x[0], dt[0], a[0, 0] / dt[0, 0], jnp.repeat(b[0], 2, axis=1),
                             jnp.repeat(c[0], 2, axis=1), ES)
    np.testing.assert_allclose(got, token_by_token(x, dt, a, b, c)[0], atol=1e-5)


SCAN_SIZES = {"two groups, whole chunks": (2, 32, 4, 8, 2, 16, 8),
              "a ragged tail is padded": (1, 21, 2, 8, 1, 8, 8),
              "shorter than a chunk": (1, 5, 2, 8, 2, 8, 8),
              "published tile: chunk 128, P 64, N 128": (1, 256, 4, 64, 1, 128, 128),
              # a ``falcon_h1`` share's grid step: eight heads on one group, a state of 128 x 256
              "fourfold state: chunk 128, P 128, N 256, 8 heads": (1, 256, 8, 128, 1, 256, 128)}


@pytest.mark.parametrize("size", SCAN_SIZES)
def test_the_scan_kernels_match_the_recurrence(size):
    """``ssd_fwd`` and ``ssd_bwd`` (chunks, decay matrices, a carried state) against the
    token-by-token recurrence: the output and the gradient of every operand, across
    chunk edges, with several heads a group, and with a sequence that is not a whole
    number of chunks (padded, not refused)."""
    *shape, chunk = SCAN_SIZES[size]
    args = scan_inputs(*shape)
    with jax.default_matmul_precision("highest"):
        want = token_by_token(*args)
        got = ssm.ssd_scan(*args, chunk=chunk)
        np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))
        w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
        grads = jax.grad(lambda *a: jnp.sum(w * ssm.ssd_scan(*a, chunk=chunk)),
                         argnums=(0, 1, 2, 3, 4))(*args)
        wants = jax.grad(lambda *a: jnp.sum(w * token_by_token(*a)),
                         argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, r in zip(("x", "dt", "a", "b", "c"), grads, wants):
        np.testing.assert_allclose(g, r, atol=2e-5 * float(jnp.abs(r).max()), err_msg=name)


def test_the_scans_step_and_decay_gradients_hold_in_bfloat16():
    """``d a`` comes from ``rowsum(dy ⊙ y) − rowsum(xd ⊙ d xd)``, whose diagonal terms
    cancel: with bfloat16 operands both sides have to see the SAME rounded ``xd``, or
    what is left of the diagonal (1.4 % of ``d a`` at this size) stands in the result.
    Against the recurrence on the same rounded operands, at the published tile."""
    *shape, chunk = SCAN_SIZES["published tile: chunk 128, P 64, N 128"]
    x, dt, a, b, c = scan_inputs(*shape)
    low = lambda v: v.astype(jnp.bfloat16)
    back = lambda v: low(v).astype(jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    got = jax.grad(lambda dt, a: jnp.sum(w * ssm.ssd_scan(low(x), dt, a, low(b), low(c),
                                                          chunk=chunk)), argnums=(0, 1))(dt, a)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda dt, a: jnp.sum(w * token_by_token(back(x), dt, a, back(b),
                                                                 back(c))),
                        argnums=(0, 1))(dt, a)
    for name, g, r in zip(("dt", "a"), got, want):
        assert float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r)) < 0.006, name


def test_the_scan_refuses_groups_that_do_not_divide_the_heads():
    x, dt, a, b, c = scan_inputs(1, 8, 3, 8, 2, 8)
    with pytest.raises(ValueError, match="groups"):
        ssm.ssd_scan(x, dt, a, b, c, chunk=8)


def test_the_scan_plan_counts_the_states_a_sequence_keeps():
    plan = ssm.scan_plan(heads=16, groups=1, head_dim=64, state=128, seq_len=8192,
                         kept=hybrid_lm.KEPT)
    assert plan == {"heads": 16, "groups": 1, "head_dim": 64, "state": 128, "chunk": 128,
                    "chunks_per_sequence": 64, "state_bytes_per_sequence": 64 * 16 * 64 * 128 * 4,
                    "kept": ["ssd_out", "ssd_state"]}


# (b) the relu² expert product and the bound -------------------------------------------------


def latent_layer_inputs(router=16, d=16, f=24, t=40, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(l=jax.random.normal(ks[0], (t, d)),
                router_kernel=0.4 * jax.random.normal(ks[1], (d, router)),
                expert_bias_b=0.1 * jax.random.normal(ks[2], (router,)),
                w1=0.2 * jax.random.normal(ks[3], (d, router * f)),
                w2=0.2 * jax.random.normal(ks[4], (f, router * d)))


def loop_over_experts(x, weights, experts, w1, w2, first, count):
    """``Σ_{e held} w_e W2_e relu(W1_e x)²`` with masks."""
    f, d = w2.shape[0], x.shape[1]
    out = jnp.zeros_like(x)
    for e in range(count):
        w_e = jnp.sum(jnp.where(experts == first + e, weights, 0.0), axis=-1)
        hidden = jnp.square(jax.nn.relu(MM(x, w1[:, e * f:(e + 1) * f])))
        out = out + w_e[:, None] * MM(hidden, w2[:, e * d:(e + 1) * d])
    return out


@pytest.mark.parametrize("k", [2, 6])
def test_the_two_matrix_relu2_product_matches_a_loop_over_experts(k):
    """``held_experts_ffn`` with no ``w3``: value and gradients (rows, routing weights,
    both matrices) against the loop, with fewer (2) and more (6) assignments a token
    than the 4 experts held."""
    x, first, count, f, d = latent_layer_inputs(), 4, 4, 24, 16
    cut = lambda w, width: w[:, first * width:(first + count) * width]
    weights, experts = moe.route(x["l"], x["router_kernel"], x["expert_bias_b"], top_k=k,
                                 scaling=5.0, eps=1e-20)

    def program(l, weights, w1, w2):
        return moe.held_experts_ffn(l, weights, experts, w1, None, w2,
                                    held=(first, count), block=8)[0]

    loop = lambda l, weights, w1, w2: loop_over_experts(l, weights, experts, w1, w2,
                                                        first, count)
    args = (x["l"], weights, cut(x["w1"], f), cut(x["w2"], d))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(program(*args), loop(*args), atol=2e-5)
        w = jax.random.normal(jax.random.PRNGKey(1), x["l"].shape)
        got = jax.grad(lambda *a: jnp.sum(w * program(*a)), argnums=(0, 1, 2, 3))(*args)
        want = jax.grad(lambda *a: jnp.sum(w * loop(*a)), argnums=(0, 1, 2, 3))(*args)
    for name, g, r in zip(("rows", "weights", "w1", "w2"), got, want):
        np.testing.assert_allclose(g, r, atol=3e-5 * max(1.0, float(jnp.abs(r).max())),
                                   err_msg=name)


def test_the_row_bound_holds_when_every_token_sends_all_it_can():
    """6 assignments a token over 16 experts, 4 held: a token can send at most 4 rows
    here, so the bound is 4·T and not 6·T. With every token choosing all 4 held
    experts the buffers are full to the bound and every row is computed."""
    t, first, count, f, d, k = 24, 4, 4, 24, 16, 6
    x = latent_layer_inputs(t=t)
    plan = moe.expert_plan(t, top_k=k, held=(first, count), block=8)
    assert plan["row_bound"] == count * t and plan["rows_buffer"] == (count * t // 8 + count) * 8
    rng = np.random.default_rng(0)
    others = [e for e in range(16) if not first <= e < first + count]
    experts = np.stack([rng.permutation(
        np.concatenate([np.arange(first, first + count), rng.choice(others, 2, replace=False)]))
        for _ in range(t)]).astype(np.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (t, k)), jnp.float32)
    cut = lambda w, width: w[:, first * width:(first + count) * width]
    with jax.default_matmul_precision("highest"):
        out, counts = moe.held_experts_ffn(x["l"], weights, jnp.asarray(experts),
                                           cut(x["w1"], f), None, cut(x["w2"], d),
                                           held=(first, count), block=8)
        want = loop_over_experts(x["l"], weights, jnp.asarray(experts), cut(x["w1"], f),
                                 cut(x["w2"], d), first, count)
    assert counts.tolist() == [t] * count and int(counts.sum()) == plan["row_bound"]
    np.testing.assert_allclose(out, want, atol=2e-5)


def test_held_first_keeps_every_held_assignment_in_the_routers_order():
    experts = jnp.asarray([[9, 4, 1, 7, 12, 5], [0, 1, 2, 3, 8, 9]], jnp.int32)
    weights = jnp.arange(12, dtype=jnp.float32).reshape(2, 6)
    w, e = moe._held_first(weights, experts, (4, 4))
    assert e.tolist() == [[4, 7, 5, 9], [0, 1, 2, 3]]
    assert w.tolist() == [[1.0, 3.0, 5.0, 0.0], [6.0, 7.0, 8.0, 9.0]]
    same = moe._held_first(weights[:, :3], experts[:, :3], (4, 4))
    assert same[0] is not w and same[1].shape == (2, 3)     # k <= held: nothing is cut


# (b1) the routing's crossings against their index forms -----------------------------------
#
# What ``ops/moe.py`` computed until PR 36, a fact an assignment fetched by index: kept here
# as the references of the select-and-reduce forms, which give the same values bit for bit.


def index_held_first(weights, experts, held):
    k, keep = experts.shape[1], min(experts.shape[1], held[1])
    if keep == k:
        return weights, experts
    local = experts - held[0]
    is_held = (local >= 0) & (local < held[1])
    _, order = jax.lax.top_k(jnp.where(is_held, 2 * k, k) - jnp.arange(k), keep)
    return (jnp.take_along_axis(weights, order, axis=1),
            jnp.take_along_axis(experts, order, axis=1))


def index_sort(experts, held, tm):
    first, n = held
    t, k = experts.shape
    a = t * k
    local = experts.reshape(a) - first
    is_held = (local >= 0) & (local < n)
    key = jnp.where(is_held, local, n)
    lands = (key[:, None] == jnp.arange(n)[None]).astype(jnp.int32)
    running = jnp.cumsum(lands, axis=0)
    counts = running[-1]
    slot = jnp.minimum(key, n - 1)
    rank = jnp.take_along_axis(running, slot[:, None], axis=1)[:, 0] - 1
    tiles = jnp.maximum(1, -(-counts // tm))
    tile_end = jnp.cumsum(tiles)
    seg_start = (tile_end - tiles) * tm
    pos = jnp.where(is_held, seg_start[slot] + rank, 0)
    order = jnp.argsort(key, stable=True)
    unaligned = jnp.cumsum(counts) - counts
    n_tiles = -(-a // tm) + n
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles), side="right"), n - 1)
    rows = jnp.arange(n_tiles * tm)
    of_row = tile_expert[rows // tm]
    offset = rows - seg_start[of_row]
    valid = (offset < counts[of_row]) & (rows // tm < tile_end[-1])
    source = order[jnp.clip(unaligned[of_row] + offset, 0, a - 1)]
    token_tiles = -(-t // tm)
    of_tile = jnp.pad(lands, ((0, token_tiles * tm * k - a), (0, 0))).reshape(
        token_tiles, tm * k, n).sum(axis=1)
    before = jnp.concatenate([jnp.zeros((1, n), jnp.int32), jnp.cumsum(of_tile, axis=0)])
    return {"counts": counts, "num_tiles": tile_end[-1].astype(jnp.int32),
            "rows_of_tokens": (seg_start[None] + before).T.astype(jnp.int32),
            "tile_expert": tile_expert.astype(jnp.int32),
            "assignment_of_row": jnp.where(valid, source, 0).astype(jnp.int32),
            "token_of_row": jnp.where(valid, source // k, -1).astype(jnp.int32),
            "pos": pos.reshape(t, k).astype(jnp.int32),
            "is_held": is_held.reshape(t, k)}


def drawn_experts(t, k, router, seed=0, without=(), tokens_without=None):
    """``[t, k]`` distinct experts a token of ``router``, none of ``without``; the tokens
    ``tokens_without = (tokens, ids)`` choose among the experts outside ``ids``."""
    rng = np.random.default_rng(seed)
    allowed = [e for e in range(router) if e not in without]
    experts = np.stack([rng.choice(allowed, k, replace=False) for _ in range(t)])
    if tokens_without:
        rows, ids = tokens_without
        outside = [e for e in allowed if e not in ids]
        experts[rows] = np.stack([rng.choice(outside, k, replace=False) for _ in rows])
    return jnp.asarray(experts, jnp.int32)


# tokens, assignments a token, the router's experts, the held range, the row tile
ROUTINGS = {
    "k_under_held": dict(t=40, k=2, router=16, held=(4, 4), tm=8),
    "k_equals_held": dict(t=40, k=4, router=16, held=(4, 4), tm=8),
    "k_over_held": dict(t=40, k=6, router=16, held=(4, 4), tm=8),
    "k_far_over_held": dict(t=64, k=11, router=16, held=(8, 4), tm=16),
    "an_empty_held_expert": dict(t=40, k=6, router=16, held=(4, 4), tm=8, without=(5,)),
    "every_held_expert_empty": dict(t=24, k=3, router=16, held=(4, 4), tm=8,
                                    without=(4, 5, 6, 7)),
    "tokens_with_no_held_assignment": dict(t=40, k=6, router=16, held=(4, 4), tm=8,
                                           tokens_without=([0, 7, 8, 39], (4, 5, 6, 7))),
    "rows_not_a_multiple_of_the_tile": dict(t=37, k=3, router=16, held=(4, 4), tm=8),
    "tokens_not_a_multiple_of_the_tile": dict(t=37, k=6, router=16, held=(4, 4), tm=16),
    "held_from_0": dict(t=40, k=6, router=16, held=(0, 4), tm=8),
    "held_to_the_last": dict(t=40, k=6, router=16, held=(12, 4), tm=8),
    "every_token_sends_all_it_can": dict(t=24, k=4, router=4, held=(0, 4), tm=8),
    "one_expert_draws_every_token": dict(t=40, k=1, router=6, held=(2, 3), tm=8,
                                         without=(0, 1, 2, 4, 5)),
}


def routing_case(name):
    case = dict(ROUTINGS[name])
    held, tm = case.pop("held"), case.pop("tm")
    experts = drawn_experts(**case)
    weights = jax.random.uniform(jax.random.PRNGKey(2), experts.shape, minval=0.1)
    return weights, experts, held, tm


def assert_same(got, want, what=""):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape, what
    assert (got == want).all(), what


@pytest.mark.parametrize("name", ROUTINGS)
def test_held_first_is_the_index_form_and_so_is_its_transpose(name):
    weights, experts, held, _ = routing_case(name)
    got, pull = jax.vjp(lambda w: moe._held_first(w, experts, held), weights)
    want, index_pull = jax.vjp(lambda w: index_held_first(w, experts, held), weights)
    assert_same(got[0], want[0], "weights")
    assert_same(got[1], want[1], "experts")
    cotangent = (jax.random.normal(jax.random.PRNGKey(3), want[0].shape),
                 np.zeros(want[1].shape, jax.dtypes.float0))
    assert_same(pull(cotangent)[0], index_pull(cotangent)[0], "the weights' gradient")


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("name", ROUTINGS)
def test_the_sort_is_the_index_form(name, jit):
    _, experts, held, tm = routing_case(name)
    experts = index_held_first(experts, experts, held)[1]
    new, old = (jax.jit(f, static_argnums=(1, 2)) if jit else f
                for f in (moe._sort, index_sort))
    got, want = new(experts, held, tm), old(experts, held, tm)
    assert sorted(got) == sorted(want)
    for key in want:
        assert_same(got[key], want[key], key)


PICKS = dict(ROUTINGS, one_expert_twice_a_token=None)


@pytest.mark.parametrize("name", PICKS)
def test_the_pick_is_the_gather_and_its_transpose_the_scatter_add(name):
    """``_pick`` and ``jax.grad`` through it against ``take_along_axis`` and its own
    gradient, which scatters; an expert named twice by a token (``route`` never does)
    gets both gradients."""
    if PICKS[name] is None:
        experts, router = jnp.asarray([[3, 1, 3], [0, 0, 2], [1, 2, 3]], jnp.int32), 4
    else:
        experts, router = routing_case(name)[1], ROUTINGS[name]["router"]
    scores = jax.random.uniform(jax.random.PRNGKey(5), (experts.shape[0], router))
    cotangent = jax.random.normal(jax.random.PRNGKey(6), experts.shape)
    got, pull = jax.vjp(lambda s: moe._pick(s, experts), scores)
    want, index_pull = jax.vjp(lambda s: jnp.take_along_axis(s, experts, axis=-1), scores)
    assert_same(got, want)
    assert_same(pull(cotangent)[0], index_pull(cotangent)[0])


@pytest.mark.parametrize("k", [2, 6])
def test_routes_weights_and_gradients_are_those_of_the_index_pick(k, monkeypatch):
    x = latent_layer_inputs(t=50)
    w = jax.random.normal(jax.random.PRNGKey(7), (50, k))

    def value_and_gradients():
        value, gradients = jax.value_and_grad(lambda l, kernel: jnp.sum(w * moe.route(
            l, kernel, x["expert_bias_b"], top_k=k, scaling=2.5)[0]), argnums=(0, 1))(
                x["l"], x["router_kernel"])
        return (value, *gradients)

    got = value_and_gradients()
    monkeypatch.setattr(moe, "_pick", lambda s, e: jnp.take_along_axis(s, e, axis=-1))
    for g, r in zip(got, value_and_gradients()):
        assert float(jnp.abs(r).max()) > 0
        assert_same(g, r)


def _crossings_by_index(jaxpr, least):
    """The ``gather`` and ``scatter`` equations of ``jaxpr`` before its first Pallas call
    whose operand or indices hold ``least`` elements or more (a ``sort`` is none)."""
    found = []
    for eqn in hybrid_lm._equations(jaxpr.jaxpr):
        if eqn.primitive.name == "pallas_call":
            break
        if eqn.primitive.name == "gather" or eqn.primitive.name.startswith("scatter"):
            sizes = [int(np.prod(v.aval.shape)) for v in eqn.invars[:2]]
            if max(sizes) >= least:
                found.append((eqn.primitive.name, sizes))
    return found


@pytest.mark.parametrize("k", [3, 6], ids=["k_under_held", "k_over_held"])
def test_no_fact_of_the_routing_is_fetched_or_stored_by_index(k, monkeypatch):
    """The counter that says the mechanism engaged: up to the first kernel the expert
    layer's forward, and the gradient through ``route``, hold no ``gather`` and no
    ``scatter`` over ``T·k`` elements or more (``top_k`` and ``argsort`` are sorts). The
    index forms hold them, which is what says the search finds one."""
    x, t, held, f, d = latent_layer_inputs(t=48), 48, (4, 4), 24, 16
    cut = lambda w, width: w[:, held[0] * width:(held[0] + held[1]) * width]
    route = lambda l, kernel: moe.route(l, kernel, x["expert_bias_b"], top_k=k)
    weights, experts = route(x["l"], x["router_kernel"])

    def searches():
        forward = jax.make_jaxpr(lambda l, w: moe.held_experts_ffn(
            l, w, experts, cut(x["w1"], f), None, cut(x["w2"], d), held=held, block=8))(
                x["l"], weights)
        backward = jax.make_jaxpr(jax.grad(
            lambda l, kernel: jnp.sum(route(l, kernel)[0] ** 2), argnums=(0, 1)))(
                x["l"], x["router_kernel"])
        names = [eqn.primitive.name for eqn in hybrid_lm._equations(forward.jaxpr)]
        assert "pallas_call" in names and "sort" in names
        return (_crossings_by_index(forward, t * min(k, held[1])),
                _crossings_by_index(backward, t * k))

    assert searches() == ([], [])
    monkeypatch.setattr(moe, "_held_first", index_held_first)
    monkeypatch.setattr(moe, "_sort", index_sort)
    monkeypatch.setattr(moe, "_pick", lambda s, e: jnp.take_along_axis(s, e, axis=-1))
    in_the_sort, in_the_gradient = searches()
    assert in_the_sort and [name for name, _ in in_the_gradient] == ["gather", "scatter-add"]


# (b2) the selection bias's rule ------------------------------------------------------------


def test_route_counts_the_tokens_that_chose_each_expert():
    x = latent_layer_inputs(t=200)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (16,))
    weights, experts, load = moe.route(x["l"], x["router_kernel"], bias, top_k=6, load=True)
    plain = moe.route(x["l"], x["router_kernel"], bias, top_k=6)
    assert len(plain) == 2 and (plain[1] == experts).all()
    assert load.dtype == jnp.int32
    assert load.tolist() == np.bincount(np.asarray(experts).ravel(), minlength=16).tolist()


def test_the_rule_moves_each_bias_a_rate_toward_the_mean_load():
    load = jnp.asarray([10, 0, 4, 4, 7, 5, 2, 0], jnp.int32)       # mean 4
    bias = jnp.linspace(-0.1, 0.1, 8)
    moved = (moe.rebalanced_bias(bias, load, 0.003) - bias) / 0.003
    np.testing.assert_allclose(moved, [-1, 1, 0, 0, -1, -1, 1, 1], atol=1e-4)


def test_the_rule_spreads_a_skewed_routers_tokens():
    """A router whose scores carry an offset an expert, as a seeded one's do under
    Zipf ids: with the rule every expert's load comes within a few per cent of the
    mean; with the bias fixed the busiest keeps several times that."""
    rng = np.random.default_rng(0)
    t, n, k = 4096, 32, 4
    u = jnp.asarray(rng.standard_normal((t, 16)), jnp.float32)
    kernel = jnp.asarray(rng.standard_normal((16, n)) * 0.25, jnp.float32)
    kernel = kernel + jnp.asarray(rng.standard_normal(n) * 0.5)[None] * jnp.abs(u).mean() / 16
    u = jnp.abs(u)                                  # a common part: the offsets act
    bias = jnp.zeros((n,))
    step = jax.jit(lambda b: moe.route(u, kernel, b, top_k=k, load=True)[2])
    first = step(bias)
    for _ in range(300):
        bias = moe.rebalanced_bias(bias, step(bias), 0.003)
    last, mean = step(bias), t * k / n
    assert int(first.max()) > 2 * mean
    assert abs(int(last.max()) - mean) < 0.1 * mean and abs(int(last.min()) - mean) < 0.1 * mean


def balanced_steps(config, steps=3, rebalance=True):
    """The program's train step, three times, as ``train.lm`` builds it."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import optim
    from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
        TrainState, make_train_step,
    )
    model, params = build(config)
    optimizer = optim.freeze(optim.adamw(1e-3, weight_decay=0.01), hybrid_lm.is_frozen)
    step = jax.jit(make_train_step(
        model, learning_rate=1e-3, momentum=0.0, optimizer=optimizer, clip_grad_norm=1.0,
        loss_fn=lambda p, xs, ys, rng: model.loss(p, xs), loss_has_aux=True,
        after_update=model.rebalance if rebalance else None))
    state = TrainState(params=params, velocity=optimizer.init(params), step=jnp.int32(0),
                       ema=None, guard=None)
    losses, aux = [], None
    for i in range(steps):
        state, (loss, aux) = step(state, tokens(seed=i), None, jax.random.PRNGKey(0))
        losses.append(float(loss))
    return state.params, losses, aux


def test_three_steps_with_the_rule_match_the_references_step():
    """The benchmark's ``balanced_step`` (the reference's loss, AdamW and ``rebalanced``)
    and the program's step with ``HybridLM.rebalance``: the same losses, the same
    biases to the rate's multiple, every other leaf moved alike; and the step's second
    result is the arrived rows alone, as without the rule."""
    import harness
    driver = harness.load_module(os.path.join(BENCH, "drivers", "train_corpus_ssm.py"),
                                 "bench_driver_ssm_for_test")
    from reference import train as ref_train
    config = tiny_config()
    opt = {"name": "adamw", "learning_rate": 1e-3, "weight_decay": 0.01,
           "clip_grad_norm": 1.0}
    with jax.default_matmul_precision("highest"):
        got, losses, aux = balanced_steps(config)
        step = driver.balanced_step(ref, ref_train, config, "highest", opt)
        want = bench_weights.make(ref.param_shapes(config), 20260929)
        state = {"m": jax.tree_util.tree_map(jnp.zeros_like, want),
                 "v": jax.tree_util.tree_map(jnp.zeros_like, want)}
        for i in range(3):
            want, state, value = step(want, state, tokens(seed=i), jnp.int32(i))
            assert abs(float(value) - losses[i]) < 2e-5
    assert aux.shape == (2, 4)                      # [expert layers, held experts]
    start = bench_weights.make(ref.param_shapes(config), 20260929)
    for layer in ("layer_1", "layer_3"):
        b0 = start[layer]["moe"]["expert_bias_b"]
        moved = np.asarray(got[layer]["moe"]["expert_bias_b"] - b0) / 0.003
        np.testing.assert_allclose(
            moved, np.asarray(want[layer]["moe"]["expert_bias_b"] - b0) / 0.003, atol=1e-3)
        assert 0 < np.abs(np.round(moved)).max() <= 3     # a rate a step, whole steps
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=jax.tree_util.keystr(path))


def test_a_program_that_drops_the_rule_leaves_the_bias_where_the_reference_moves_it():
    config = tiny_config()
    with jax.default_matmul_precision("highest"):
        params, _, aux = balanced_steps(config, rebalance=False)
    counts, load = aux                              # nothing consumed the load
    assert counts.shape == (2, 4) and load.shape == (2, 16)
    start = bench_weights.make(ref.param_shapes(config), 20260929)
    moved = ref.rebalanced(start, load, config)
    for layer in ("layer_1", "layer_3"):
        same = params[layer]["moe"]["expert_bias_b"] - start[layer]["moe"]["expert_bias_b"]
        assert float(jnp.abs(same).max()) == 0.0
        there = moved[layer]["moe"]["expert_bias_b"] - start[layer]["moe"]["expert_bias_b"]
        assert float(jnp.abs(there).max()) > 0.002


def test_a_file_without_the_rate_keeps_the_bias_fixed_and_hands_out_rows_alone():
    config = tiny_config()
    del config["moe_router_bias_update_rate"]
    model, params = build(config)
    assert model.router_bias_update_rate == 0.0 and "bias_update_rate" not in model.expert_plan(64)
    _, counts = model.loss(params, tokens())
    assert counts.shape == (2, 4)


# (c) the model against the reference -------------------------------------------------------


def program_loss(model, params, ids):
    return model.loss(params, ids)[0]


def test_logits_match_the_reference():
    config = tiny_config()
    model, params = build(config)
    ids = tokens()
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, ids)
        want = jax.vmap(lambda row: jax.nn.log_softmax(ref.logits(params, row, config)))(ids)
    np.testing.assert_allclose(got, want, atol=3e-5)


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_leafs_gradient_match_the_reference(remat):
    config = tiny_config()
    model, params = build(config, remat=remat)
    ids = tokens()
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(lambda p: program_loss(model, p, ids))(params)
        want, wants = jax.value_and_grad(lambda p: ref.loss(p, ids, config))(params)
    assert abs(float(got) - float(want)) < 1e-5
    flat = lambda tree: {jax.tree_util.keystr(k): v
                         for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}
    got, want = flat(grads), flat(wants)
    assert got.keys() == want.keys()
    for name in want:
        scale = max(float(jnp.abs(want[name]).max()), 1e-3)
        np.testing.assert_allclose(got[name], want[name], atol=2e-4 * scale, err_msg=name)
    bias = [g for name, g in got.items() if "expert_bias_b" in name]
    assert bias and all(float(jnp.abs(g).max()) == 0.0 for g in bias)


@pytest.mark.parametrize("dtype", head_rule.DTYPES)
def test_the_untied_heads_rule_gives_the_plain_formulas_value_and_gradients(dtype):
    model, params = build(tiny_config(), dtype=head_rule.DTYPES[dtype])
    assert not model.tied_head and model._head(params).shape == (32, VOCAB)
    head_rule.check_value_and_gradients(model, params, tokens(), dtype)


@pytest.mark.parametrize("scale", [1 / (2 * (SEQ - 1)), 3.0], ids=["the mean", "times 3"])
@pytest.mark.parametrize("dtype", head_rule.DTYPES)
def test_a_cotangent_scales_the_untied_heads_two_gradients(dtype, scale):
    model, params = build(tiny_config(), dtype=head_rule.DTYPES[dtype])
    head_rule.check_a_cotangent_scales_both_gradients(model, params, tokens(), dtype, scale)


@pytest.mark.parametrize("dtype", head_rule.DTYPES)
def test_a_sequences_last_row_gets_no_gradient_from_the_untied_head(dtype):
    model, params = build(tiny_config(), dtype=head_rule.DTYPES[dtype])
    head_rule.check_the_last_row_gets_no_gradient(model, params, tokens())


@pytest.mark.parametrize("case", head_rule.PRODUCT_CASES)
def test_the_untied_heads_logits_are_multiplied_once_a_pass(case, monkeypatch):
    head_rule.check_head_products(lambda **kw: build(tiny_config(), **kw), tokens(),
                                  case, monkeypatch)


def test_router_choices_are_the_references():
    config = tiny_config()
    model, params = build(config)
    ids = tokens()
    with jax.default_matmul_precision("highest"):
        got = np.sort(np.asarray(model.router_choices(params, ids, 3)), axis=-1)
        want = np.sort(np.asarray(jax.vmap(
            lambda row: ref.router_choice(params, row, config, 3))(ids)), axis=-1)
    assert got.shape == (2, SEQ, 6) and (got == want).all()


FAULTS = ["state not carried across chunks", "5 of a token's 6 experts",
          "shared expert dropped", "gate applied after the norm"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_comparison(fault, monkeypatch):
    """Each fault moves the loss by far more than the 1e-5 the sound program is held
    to above."""
    config = tiny_config()
    model, params = build(config)
    if fault == "state not carried across chunks":
        whole = ssm.ssd_scan

        def chunk_by_chunk(x, dt, a, b, c, *, chunk):
            cut = lambda v: v.reshape((-1, chunk) + v.shape[2:])
            return whole(*map(cut, (x, dt, a, b, c)), chunk=chunk).reshape(x.shape)

        monkeypatch.setattr(hybrid_lm.ssm, "ssd_scan", chunk_by_chunk)
    elif fault == "5 of a token's 6 experts":
        model = dataclasses.replace(model, num_experts_per_tok=5)
    elif fault == "shared expert dropped":
        model = dataclasses.replace(model, shared_expert_size=0)
    else:
        def norm_then_gate(y, z, scale, groups, eps):
            grouped = y.astype(jnp.float32).reshape(*y.shape[:-1], groups, -1)
            normed = hybrid_lm.ops.rms_norm(grouped, scale.reshape(groups, -1), eps=eps)
            return normed.reshape(y.shape) * jax.nn.silu(z.astype(jnp.float32))

        monkeypatch.setattr(hybrid_lm, "gated_group_norm", norm_then_gate)
    ids = tokens()
    with jax.default_matmul_precision("highest"):
        got = float(program_loss(model, params, ids))
        want = float(ref.loss(params, ids, config))
    assert abs(got - want) > 1e-3, (got, want)


# (d) the share tied to the model -------------------------------------------------------------

WHOLE = dict(mamba_num_heads=8, n_groups=4, num_attention_heads=8, num_key_value_heads=4,
             n_routed_experts=16, num_experts_per_tok=6, num_hidden_layers=1)
CHIPS = 4       # tensor-parallel 4 and expert-parallel 4 over the same four chips


def whole_config(letter):
    config = tiny_config(hybrid_override_pattern=letter, **WHOLE)
    config["share"] = dict(config["share"], shared_expert_columns=40)
    return config


def share_of(params, config, chip):
    """Chip ``chip``'s configuration and its slices of the whole layer's leaves: its
    heads with their groups, its columns of the shared expert, its experts; the
    router and the latent projections whole."""
    m = dict(config, mamba_num_heads=8 // CHIPS, n_groups=4 // CHIPS,
             num_attention_heads=8 // CHIPS, num_key_value_heads=4 // CHIPS,
             n_routed_experts=16 // CHIPS)
    m["published"] = dict(config["published"], n_routed_experts=16)
    m["share"] = dict(config["share"], first_expert=chip * 4, shared_expert_columns=10)
    take = lambda v, width, axis: jax.lax.slice_in_dim(
        v, chip * width, (chip + 1) * width, axis=axis)
    (group, p), = ((k, v) for k, v in params["layer_0"].items() if k != "norm_scale")
    if group == "mamba":
        inner, bc, heads = 8 * 8, 4 * 16, 8
        z, x, b, c, dt = jnp.split(p["in_proj_kernel"],
                                   np.cumsum([inner, inner, bc, bc]), axis=1)
        cx, cb, cc = jnp.split(p["conv_kernel"], np.cumsum([inner, bc]), axis=1)
        bx, bb, bcc = jnp.split(p["conv_bias"], np.cumsum([inner, bc]))
        cut = dict(
            in_proj_kernel=jnp.concatenate(
                [take(z, inner // CHIPS, 1), take(x, inner // CHIPS, 1),
                 take(b, bc // CHIPS, 1), take(c, bc // CHIPS, 1),
                 take(dt, heads // CHIPS, 1)], axis=1),
            conv_kernel=jnp.concatenate([take(cx, inner // CHIPS, 1), take(cb, bc // CHIPS, 1),
                                         take(cc, bc // CHIPS, 1)], axis=1),
            conv_bias=jnp.concatenate([take(bx, inner // CHIPS, 0), take(bb, bc // CHIPS, 0),
                                       take(bcc, bc // CHIPS, 0)]),
            dt_bias=take(p["dt_bias"], 2, 0), A_log=take(p["A_log"], 2, 0),
            D_scale=take(p["D_scale"], 2, 0), gate_norm_scale=take(p["gate_norm_scale"], 16, 0),
            out_proj_kernel=take(p["out_proj_kernel"], 16, 0))
    elif group == "attn":
        cut = dict(q_kernel=take(p["q_kernel"], 16, 1), k_kernel=take(p["k_kernel"], 8, 1),
                   v_kernel=take(p["v_kernel"], 8, 1), out_kernel=take(p["out_kernel"], 16, 0))
    else:
        cut = dict(p, shared_w1_kernel=take(p["shared_w1_kernel"], 10, 1),
                   shared_w2_kernel=take(p["shared_w2_kernel"], 10, 0),
                   experts_w1_kernel=take(p["experts_w1_kernel"], 4 * 24, 1),
                   experts_w2_kernel=take(p["experts_w2_kernel"], 4 * 16, 1))
    return m, {"norm_scale": params["layer_0"]["norm_scale"], group: cut}


@pytest.mark.parametrize("letter", ["M", "*", "E"])
def test_the_shares_add_up_to_the_uncut_layer(letter):
    """Four chips divide a layer as the deployment does (heads in whole groups, query
    heads with their KV head, the shared expert's columns, the experts); what each
    adds to the residual, from its own configuration and leaves through the
    program, sums to what the uncut reference's layer adds: the router and the
    latent projections are computed alike on every chip and counted once, because
    the second latent projection is linear in the experts' sum."""
    config = whole_config(letter)
    params = bench_weights.make(ref.param_shapes(config), 7)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, 32))
    kind = ref.kinds(config)[0]
    with jax.default_matmul_precision("highest"):
        whole = jax.vmap(lambda row: ref._layer(params["layer_0"], row, config, kind,
                                                MM, ES)[0])(x) - x
        parts, rows = [], 0
        for chip in range(CHIPS):
            m, leaves = share_of(params, config, chip)
            model = hybrid_lm.from_config(m, vocab_size=VOCAB, seq_len=SEQ, expert_block=8)
            y, arrived = hybrid_lm.make_block(model, kind, kind == "moe")(
                leaves, x, jnp.arange(SEQ))
            parts.append(y - x)
            if arrived is not None:     # the router runs whole on every chip
                counts, load = arrived
                rows += int(counts.sum())
                assert load.shape == (16,) and int(load.sum()) == 6 * 2 * SEQ
    np.testing.assert_allclose(sum(parts), whole, atol=3e-5 * float(jnp.abs(whole).max()))
    if letter == "E":
        assert rows == 6 * 2 * SEQ              # every assignment computed on one chip
    assert float(jnp.abs(parts[0] - whole).max()) > 1e-2     # one share is not the layer


# (e) the configuration file ------------------------------------------------------------------


def test_the_configuration_is_one_period_of_one_chips_share():
    with open(CONFIG_FILE) as fh:
        config = json.load(fh)
    model = hybrid_lm.from_config(config, vocab_size=16384, seq_len=8192)
    letters = {"mamba": "M", "attention": "*", "moe": "E"}
    assert "".join(letters[k] for k in model.layer_types) == "MEMEMEMEM*E"
    assert ref.kinds(config) == list(model.layer_types)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))["params"]
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == count(ref.param_shapes(config)) == config["parameters"] \
        == 508_189_680
    assert count(shapes["layer_0"]) == 13_708_592            # Mamba-2
    assert count(shapes["layer_9"]) == 5_246_976             # attention
    assert count(shapes["layer_1"]) == 60_035_584            # latent expert layer
    assert jax.tree.map(lambda x: x.shape, shapes) == \
        jax.tree.map(lambda x: x.shape, ref.param_shapes(config))
    assert (model.router_experts, model.held_experts, model.num_experts_per_tok) == \
        (512, (0, 8), 22)
    assert model.expert_plan(2 * 8192)["row_bound"] == 8 * 2 * 8192
    assert model.ssm_plan()["chunks_per_sequence"] == 64
    assert (model.rope_theta, model.qk_norm, model.tied_head, model.head_dim) == \
        (None, False, False, 128)
    for key, value in config["published"].items():
        assert key in config["reduced"] and config[key] != value


@pytest.mark.parametrize("key, value, what", [
    ("num_nextn_predict_layers", 1, "multi-token prediction"),
    ("hybrid_override_pattern", "ME-E", "layers"),
    ("mlp_hidden_act", "silu", "activation"),
    ("n_group", 2, "grouped expert selection"),
    ("model_type", "mamba", "model_type")])
def test_what_the_file_states_and_the_module_does_not_compute_is_refused(key, value, what):
    with pytest.raises(ValueError, match=what):
        hybrid_lm.from_config(tiny_config(**{key: value}), vocab_size=VOCAB, seq_len=SEQ)


# (f) through train.lm -------------------------------------------------------------------------


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    from csed_514_project_distributed_training_using_pytorch_tpu.train import lm as train_lm
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import LMConfig
    work = tmp_path_factory.mktemp("nemotron_train")
    config_file = str(work / "tiny.json")
    with open(config_file, "w") as fh:
        json.dump(tiny_config(vocab_size=256, chunk_size=16), fh)
    tele = str(work / "t.jsonl")
    state, _ = train_lm.main(LMConfig(
        model_config=config_file, mesh="data=1", remat=True,
        corpus=os.path.join(REPO, "tests", "fixtures", "corpus_tiny"),
        epochs=2, batch_size=8, eval_batch=19, learning_rate=3e-3, seed=5,
        telemetry=tele, results_dir="", images_dir=str(work / "images"), generate=0))
    with open(tele) as fh:
        return state, [json.loads(line) for line in fh]


def test_main_trains_the_configuration_and_the_loss_falls(trained):
    _, events = trained
    epochs = [e for e in events if e["event"] == "epoch"]
    assert len(epochs) == 2 and epochs[1]["train_loss"] < epochs[0]["train_loss"]
    assert epochs[1]["val_loss"] < epochs[0]["val_loss"] < np.log(256) + 0.5
    for event in epochs:
        rows = np.asarray(event["expert_rows"])
        assert rows.shape == (event["steps"], 2)            # [steps, expert layers]
        assert 0 < rows.sum() <= 4 * 8 * 64 * rows.size     # under min(k, held)·T


def test_the_compile_event_says_what_the_new_layers_ask(trained):
    state, events = trained
    event = [e for e in events if e["event"] == "compile"][0]
    assert event["ssm"] == {"heads": 4, "groups": 2, "head_dim": 8, "state": 16,
                            "chunk": 16, "chunks_per_sequence": 4,
                            "state_bytes_per_sequence": 4 * 4 * 8 * 16 * 4,
                            "kept": ["ssd_out", "ssd_state"]}
    assert event["experts"]["row_bound"] == 4 * 8 * 64 and event["experts"]["held"] == [0, 4]
    assert event["recompute"]["kept_bytes"] > 0
    assert event["head_products"] == 3      # the [T, vocab] logits: once a pass
    assert event["experts"]["bias_update_rate"] == 0.003
    # the selection's bias: out of AdamW, moved by the balancing rule alone, a rate a step
    steps = sum(e["steps"] for e in events if e["event"] == "epoch")
    moved = np.asarray(state.params["layer_1"]["moe"]["expert_bias_b"]) / 0.003
    np.testing.assert_allclose(moved, np.round(moved), atol=1e-3)
    assert 0 < np.abs(moved).max() <= steps
