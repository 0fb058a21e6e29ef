"""Quantized execution: int8 KV cache, quantized weights/matmuls, byte accounting.

The quantization contract, pinned here (tier-1):

1. **Accuracy is a budget, not a vibe** — greedy-decode token-match rate vs the
   fp32 oracle across MHA/GQA/window/RoPE stays above an explicit bound, the
   teacher-forced NLL delta through the quantized serving path stays within an
   explicit bound, and temperature>0 sampling under the dequantized-logits path
   stays distribution-close to fp32.
2. **Policy off is bitwise off** — ``quantize_params`` returns the identical
   tree, ``init_cache`` builds the exact planes it always built, ``dense_any``
   on a plain kernel IS ``ops.dense``; the quantization code cannot perturb the
   fp32 path it sits next to.
3. **One program, still** — an int8-KV engine traces exactly one decode program
   and at most one prefill program per chunk size: scales are data, not shape.
4. **Bytes are measured, never assumed** — ``byte_accounting`` sums live
   buffers; int8 KV + int8 weights cut measured decode bytes/token >= 1.8x and
   multiply slots-per-HBM-budget >= 1.9x; a plane snapshot written under one
   layout can never install into an engine running another.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from csed_514_project_distributed_training_using_pytorch_tpu import ops
from csed_514_project_distributed_training_using_pytorch_tpu.models import lm
from csed_514_project_distributed_training_using_pytorch_tpu.ops import quant
from csed_514_project_distributed_training_using_pytorch_tpu.serving import (
    ContinuousBatchingEngine,
    Request,
    SamplingParams,
)
from csed_514_project_distributed_training_using_pytorch_tpu.serving.prefix_cache import (
    PrefixCache,
)

_REPO = os.path.join(os.path.dirname(__file__), os.pardir)

SMALL = dict(vocab_size=9, seq_len=16, embed_dim=32, num_layers=2, num_heads=4)

# The tier-1 accuracy budget for TINY RANDOM-INIT models (near-uniform logits —
# the hardest case for argmax stability): the share of steps at which the int8
# path, GIVEN THE FLOAT32 STREAM'S PREFIX, picks the float32 token (measured
# 0.958-1.0 over the four configs x three inits x two request sets; free-running
# streams read 0.896-1.0 on the same runs, because one flipped near-tie puts
# every later token on another prefix). The committed real-checkpoint artifact
# documents the trained-model budget, which is tighter.
TOKEN_MATCH_BOUND = 0.90
# What a flipped step may cost: the float32 log-prob of the int8 path's pick lies
# this close under the float32 best. int8 KV + w8 moved no log-prob of those runs
# by more than 0.0035, a flip needs a gap under twice that, and the largest seen
# was 0.0017; 0.01 is three times the largest move.
NEAR_TIE_LOGP_GAP = 0.01
NLL_DELTA_BOUND = 0.05


def _model(**kw):
    return lm.TransformerLM(**{**SMALL, **kw})


def _params(model, seed=0):
    ids = jnp.zeros((1, model.seq_len), jnp.int32)
    return model.init({"params": jax.random.PRNGKey(seed)}, ids)["params"]


def _mixed_requests(model, n, seed=0, temperature=0.0):
    rng = np.random.default_rng(seed)
    sampling = SamplingParams(temperature=temperature)
    return [Request(
        prompt=rng.integers(0, model.vocab_size - 2,
                            size=int(rng.integers(0, model.seq_len // 2)))
        .astype(np.int32),
        max_new_tokens=int(rng.integers(1, model.seq_len - 1)),
        sampling=sampling, request_id=i) for i in range(n)]


def _teacher_forced_logp(model, params, streams, kv_dtype=None):
    """``[B, S, V - 1]`` log-probs of the serving decode path
    (``decode_step_slots``, as ``lm.decode_nll`` scores it) with ``streams`` as
    the forced inputs, without the BOS column: the sampler never emits it."""
    b, s = streams.shape
    inputs = jnp.transpose(model.shift_right(jnp.asarray(streams)))     # [S, B]

    def step(cache, xs):
        t, ids_t = xs
        return lm.decode_step_slots(model, params, cache, ids_t,
                                    jnp.full((b,), t, jnp.int32))

    _, logp = jax.lax.scan(step, lm.init_cache(model, b, kv_dtype=kv_dtype),
                           (jnp.arange(s, dtype=jnp.int32), inputs))
    return np.asarray(jnp.transpose(logp, (1, 0, 2)))[..., :-1]


def _run_engine(model, params, reqs, **kw):
    eng = ContinuousBatchingEngine(model, params, num_slots=3, **kw)
    comps = {c.request.request_id: np.asarray(c.tokens)
             for c in eng.run(list(reqs))}
    return eng, comps


# -----------------------------------------------------------------------------------------
# Scale math: quant/dequant roundtrips and the int8 matmul paths
# -----------------------------------------------------------------------------------------


def test_quantize_rows_roundtrip_error_bound():
    """Per-row symmetric int8: |x - dequant(quant(x))| <= amax/127 per element
    (half-step rounding, exactly representable scales aside), zero rows exact."""
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 4, 32)) * \
        jnp.arange(1, 6)[:, None, None]          # heterogeneous row magnitudes
    q, scale = quant.quantize_rows(x, jnp.int8)
    assert q.dtype == jnp.int8 and scale.shape == (5, 4)
    err = jnp.abs(quant.dequantize_rows(q, scale) - x)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    assert float(jnp.max(err - amax / 127.0)) <= 1e-6
    # All-zero rows: scale 1.0, dequant exact zeros.
    qz, sz = quant.quantize_rows(jnp.zeros((3, 8)), jnp.int8)
    np.testing.assert_array_equal(np.asarray(sz), np.ones((3,), np.float32))
    np.testing.assert_array_equal(np.asarray(quant.dequantize_rows(qz, sz)),
                                  np.zeros((3, 8), np.float32))


@pytest.mark.skipif(quant.fp8_dtype() is None,
                    reason="no float8_e4m3fn in this jax build")
def test_quantize_rows_fp8_roundtrip():
    """fp8 planes quantize/dequantize within e4m3's ~2^-3 relative step."""
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16)) * 3.0
    q, scale = quant.quantize_rows(x, quant.fp8_dtype())
    rel = jnp.abs(quant.dequantize_rows(q, scale) - x) / (jnp.abs(x) + 1e-6)
    assert float(jnp.max(rel)) < 0.13


@pytest.mark.parametrize("mode,tol", [("w8", 0.02), ("w8a8", 0.05)])
def test_int8_matmul_paths_match_fp32_within_bound(mode, tol):
    """Weight-only and w8a8 matmuls track the fp32 product within a relative
    Frobenius bound — the trainer-usable int8 matmul paths."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(k1, (16, 64))
    w = jax.random.normal(k2, (64, 32)) * 0.1
    qt = quant.quantize_tensor(w, mode=mode)
    ref = x @ w
    got = quant.int8_matmul(x, qt)
    rel = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
    assert rel < tol
    # w8a8 really accumulates in int32 (int8 x int8 lane path).
    if mode == "w8a8":
        xq, _ = quant.quantize_rows(x, jnp.int8)
        acc = jax.lax.dot_general(xq, qt.q, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        assert acc.dtype == jnp.int32


def test_dense_any_plain_kernel_is_ops_dense_bitwise():
    """The policy-off pin at the op level: a plain array kernel takes the exact
    ``ops.dense`` path — same bits out."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, (8, 32))
    w = jax.random.normal(k2, (32, 16))
    b = jnp.arange(16, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(quant.dense_any(x, w, b)),
                                  np.asarray(ops.dense(x, w, b)))


def test_quantize_params_rewrites_kernels_only():
    """``quantize_params``: 2-D ``*_kernel`` leaves become QuantizedTensor,
    embeddings/LN/biases stay the same objects; ``weights='off'`` returns the
    identical tree (not a copy) — the bitwise-off guarantee."""
    model = _model()
    params = _params(model)
    off = quant.quantize_params(params, quant.QuantPolicy())
    assert off is params
    qp = quant.quantize_params(params, quant.QuantPolicy(weights="w8"))
    attn = qp["block_0"]["attn"]
    assert isinstance(attn["qkv_kernel"], quant.QuantizedTensor)
    assert isinstance(qp["head_kernel"], quant.QuantizedTensor)
    assert qp["head_kernel"].q.dtype == jnp.int8
    assert qp["tok_embed"] is params["tok_embed"]
    assert qp["block_0"]["ln1_scale"] is params["block_0"]["ln1_scale"]
    assert attn["qkv_bias"] is params["block_0"]["attn"]["qkv_bias"]
    # The quantized tree round-trips jax pytree plumbing (device_put, tree_map).
    moved = jax.tree_util.tree_map(jnp.asarray, qp)
    assert isinstance(moved["head_kernel"], quant.QuantizedTensor)
    assert moved["head_kernel"].mode == "w8"


def test_quant_policy_validation():
    with pytest.raises(ValueError):
        quant.QuantPolicy(kv_dtype="int4")
    with pytest.raises(ValueError):
        quant.QuantPolicy(weights="w4")
    assert quant.QuantPolicy().off


# -----------------------------------------------------------------------------------------
# Quantized KV-cache planes in the model layer
# -----------------------------------------------------------------------------------------


def test_init_cache_layouts():
    """Default cache is exactly the legacy structure (no scale planes); int8
    adds f32 ``k_scale``/``v_scale`` planes of per-head-per-position shape."""
    model = _model(num_kv_heads=2)
    legacy = lm.init_cache(model, 3)
    assert set(legacy["block_0"]) == {"k", "v"}
    assert legacy["block_0"]["k"].dtype == model.dtype
    q = lm.init_cache(model, 3, kv_dtype="int8")
    layer = q["block_0"]
    assert set(layer) == {"k", "v", "k_scale", "v_scale"}
    assert layer["k"].dtype == jnp.int8
    assert layer["k_scale"].shape == (3, model.seq_len, 2)
    assert layer["k_scale"].dtype == jnp.float32


def test_decode_step_rejects_quantized_cache():
    """decode_step reads raw planes only — it must refuse a quantized cache
    loudly (silently it would astype values into int8 codes with no scale and
    attend against garbage, and drop the scale planes from the returned tree)."""
    model = _model()
    params = _params(model)
    cache = lm.init_cache(model, 1, kv_dtype="int8")
    with pytest.raises(ValueError, match="decode_step_slots"):
        lm.decode_step(model, params, cache, jnp.array([1]), jnp.int32(0))


def test_reset_slots_wipes_scale_planes():
    model = _model()
    params = _params(model)
    cache = lm.init_cache(model, 2, kv_dtype="int8")
    cache, _ = lm.decode_step_slots(model, params, cache,
                                    jnp.array([1, 2]), jnp.array([0, 0]))
    assert float(jnp.sum(jnp.abs(cache["block_0"]["k_scale"]))) > 0
    wiped = lm.reset_slots(cache, jnp.array([True, False]))
    assert float(jnp.sum(jnp.abs(wiped["block_0"]["k_scale"][0]))) == 0.0
    assert float(jnp.sum(jnp.abs(wiped["block_0"]["k_scale"][1]))) > 0.0


def test_prefill_chunk_rows_bitwise_match_decode_path_int8():
    """Quantize-on-write parity: a chunk-prefilled int8 slot holds bit-identical
    quantized rows AND scales to the same prompt fed through the per-token
    decode path — prefill is a schedule change even under quantization."""
    model = _model()
    params = _params(model)
    prompt = jnp.zeros((2, model.seq_len), jnp.int32)
    prompt = prompt.at[0, :8].set(jnp.arange(8) % (model.vocab_size - 1))
    c_pre = lm.init_cache(model, 2, kv_dtype="int8")
    c_pre = lm.prefill_chunk(model, params, c_pre, prompt, jnp.int32(0),
                             jnp.int32(0), jnp.int32(8), jnp.asarray(True),
                             chunk=8)
    c_dec = lm.init_cache(model, 2, kv_dtype="int8")
    ids_t = jnp.full((2,), model.vocab_size - 1, jnp.int32)
    for t in range(8):
        c_dec, _ = lm.decode_step_slots(model, params, c_dec, ids_t,
                                        jnp.array([t, 0]))
        ids_t = jnp.array([prompt[0, t], 0])
    for name in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(
            np.asarray(c_pre["block_0"][name][0, :8]),
            np.asarray(c_dec["block_0"][name][0, :8]), err_msg=name)


def test_decode_nll_fp32_matches_teacher_forced_loss():
    """The NLL harness itself is pinned: scored through the fp32 decode path it
    reproduces ``next_token_loss`` to float tolerance — so a quantized delta
    measured with it is attributable to quantization, not the harness."""
    model = _model()
    params = _params(model)
    targets = jax.random.randint(jax.random.PRNGKey(5), (4, model.seq_len),
                                 0, model.vocab_size - 1)
    via_decode = float(lm.decode_nll(model, params, targets))
    ref = float(lm.next_token_loss(model, params, targets, None,
                                   deterministic=True))
    assert abs(via_decode - ref) < 1e-5


@pytest.mark.parametrize("kv,policy", [("int8", "off"), ("int8", "w8"),
                                       ("bf16", "off")])
def test_nll_delta_within_budget(kv, policy):
    """The LM-level accuracy budget: teacher-forced NLL through the quantized
    serving path moves < NLL_DELTA_BOUND vs the fp32 oracle."""
    model = _model()
    params = _params(model)
    qparams = quant.quantize_params(
        params, quant.QuantPolicy(kv_dtype=kv, weights=policy))
    targets = jax.random.randint(jax.random.PRNGKey(6), (4, model.seq_len),
                                 0, model.vocab_size - 1)
    base = float(lm.decode_nll(model, params, targets))
    quantized = float(lm.decode_nll(model, qparams, targets, kv_dtype=kv))
    assert abs(quantized - base) < NLL_DELTA_BOUND


# -----------------------------------------------------------------------------------------
# Engine-level accuracy budget + one-program pins
# -----------------------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [
    dict(), dict(num_kv_heads=2), dict(attention_window=5), dict(rope=True),
], ids=["mha", "gqa", "window", "rope"])
def test_engine_int8_greedy_token_match_budget(cfg):
    """Acceptance: given the fp32 engine's greedy stream as its prefix, the
    int8-KV + int8-weight serving path picks the fp32 token at TOKEN_MATCH_BOUND
    of the steps and a token within NEAR_TIE_LOGP_GAP of the fp32 best at all of
    them, across model configs; the int8 engine's own stream does as much where it
    first leaves the fp32 one (past that step the two are on different prefixes
    and say nothing about quantization). The decode program is still compiled
    exactly once and every prefill size at most once (quantization changes plane
    I/O, never shape)."""
    model = _model(**cfg)
    params = _params(model)
    qparams = quant.quantize_params(
        params, quant.QuantPolicy(kv_dtype="int8", weights="w8"))
    reqs = _mixed_requests(model, 6, seed=7)
    _, ref = _run_engine(model, params, reqs)
    eng, got = _run_engine(model, params, reqs,
                           kv_dtype="int8", quant_policy="w8")
    assert eng.trace_count == 1
    assert all(v <= 1 for v in eng.prefill_trace_counts.values())
    streams = np.zeros((len(reqs), model.seq_len), np.int32)
    for row, req in zip(streams, reqs):
        row[:len(ref[req.request_id])] = ref[req.request_id]
    logp_32 = _teacher_forced_logp(model, params, streams)
    logp_8 = _teacher_forced_logp(model, qparams, streams, kv_dtype="int8")
    agree = total = 0
    for i, req in enumerate(reqs):
        p = len(req.prompt)
        a, b = ref[req.request_id], got[req.request_id]
        # The teacher-forced prompt prefix survives bit-exactly regardless.
        np.testing.assert_array_equal(a[:p], b[:p])
        steps = np.arange(p, len(a))
        picks = logp_8[i, steps].argmax(-1)
        best = logp_32[i, steps].max(-1)
        # The fp32 stream is this path's own greedy stream (to float32 round-off).
        np.testing.assert_allclose(logp_32[i, steps, a[p:]], best, atol=1e-5)
        assert (best - logp_32[i, steps, picks]).max() <= NEAR_TIE_LOGP_GAP
        agree += int((picks == a[p:]).sum())
        total += len(steps)
        n = min(len(a), len(b))
        left = p + np.flatnonzero(a[p:n] != b[p:n])[:1]      # the engine's own stream
        assert (logp_32[i, left].max(-1) - logp_32[i, left, b[left]] <= NEAR_TIE_LOGP_GAP).all()
    assert total > 0
    assert agree / total >= TOKEN_MATCH_BOUND, \
        f"token match {agree / total:.3f} under budget {TOKEN_MATCH_BOUND}"


def test_engine_fp32_paths_bitwise_unchanged_when_policy_off():
    """Policy off ⇒ the engine is the legacy engine: same params object, same
    cache structure, token-identical output to a default-constructed engine."""
    model = _model()
    params = _params(model)
    reqs = _mixed_requests(model, 4, seed=9)
    eng_default, toks_default = _run_engine(model, params, reqs)
    eng_off, toks_off = _run_engine(model, params, reqs,
                                    kv_dtype="model", quant_policy="off")
    assert set(eng_off._cache["block_0"]) == {"k", "v"}
    for i in toks_default:
        np.testing.assert_array_equal(toks_default[i], toks_off[i])
    # And "fp32" (an explicit spec) on an fp32 model is the same planes too.
    eng_f32, toks_f32 = _run_engine(model, params, reqs, kv_dtype="fp32")
    for i in toks_default:
        np.testing.assert_array_equal(toks_default[i], toks_f32[i])


def test_engine_temperature_sampling_distribution_under_quant():
    """Distribution-level budget for temperature>0: sampling through the
    dequantized-logits path (same seed, same step schedule) yields a
    first-token distribution within small total-variation distance of fp32 —
    the sampler consumes quantized logits, not a different program."""
    model = _model()
    params = _params(model)
    n = 64
    sampling = SamplingParams(temperature=1.0, top_k=0, top_p=1.0)
    reqs = [Request(prompt=np.zeros(0, np.int32), max_new_tokens=1,
                    sampling=sampling, request_id=i) for i in range(n)]

    def first_tokens(**kw):
        eng = ContinuousBatchingEngine(model, params, num_slots=4, seed=123,
                                       **kw)
        return np.array([int(c.tokens[0]) for c in eng.run(list(reqs))])

    a = first_tokens()
    b = first_tokens(kv_dtype="int8", quant_policy="w8")
    v = model.vocab_size
    pa = np.bincount(a, minlength=v) / n
    pb = np.bincount(b, minlength=v) / n
    tv = 0.5 * float(np.abs(pa - pb).sum())
    assert tv <= 0.15, f"total-variation distance {tv:.3f} too large"


# -----------------------------------------------------------------------------------------
# Prefix-cache dtype/layout compatibility (satellite regression)
# -----------------------------------------------------------------------------------------


def test_prefix_cache_layout_mismatch_never_hits():
    """Unit guard: an entry stored under one plane layout is invisible to
    lookups under another — counted, not silently installed."""
    cache = PrefixCache(4, layout="fp32-layout")
    tokens = np.arange(8, dtype=np.int32)
    cache.insert(tokens, {"planes": "A"})
    hit, planes = cache.lookup(tokens, layout="fp32-layout")
    assert hit == 8 and planes is not None
    hit, planes = cache.lookup(tokens, layout="int8-layout")
    assert hit == 0 and planes is None
    assert cache.layout_rejects > 0
    assert cache.stats()["layout_rejects"] == cache.layout_rejects


def test_prefix_cache_written_at_fp32_never_installs_into_int8_engine():
    """The regression the satellite names: hand an fp32 engine's populated
    prefix cache to an int8 engine — every lookup must miss (layout reject),
    the engine chunk-prefills from scratch, and its output still matches its
    own fresh-cache output token-for-token."""
    model = _model()
    params = _params(model)
    prompt = np.arange(8, dtype=np.int32) % (model.vocab_size - 1)
    req = lambda i: Request(prompt=prompt, max_new_tokens=4, request_id=i)  # noqa: E731

    eng_f = ContinuousBatchingEngine(model, params, num_slots=2,
                                     prefix_cache_entries=4)
    eng_f.run([req(0)])
    assert len(eng_f.prefix_cache) == 1          # fp32-layout snapshot stored

    eng_q = ContinuousBatchingEngine(model, params, num_slots=2,
                                     kv_dtype="int8", prefix_cache_entries=4)
    ref = np.asarray(eng_q.run([req(1)])[0].tokens)   # own-cache baseline
    eng_q2 = ContinuousBatchingEngine(model, params, num_slots=2,
                                      kv_dtype="int8", prefix_cache_entries=4)
    eng_q2.prefix_cache = eng_f.prefix_cache          # the foreign cache
    comp = eng_q2.run([req(2)])[0]
    np.testing.assert_array_equal(np.asarray(comp.tokens), ref)
    assert eng_f.prefix_cache.layout_rejects > 0      # rejected, not installed
    # Sanity: the layouts really differ (that is what the guard keys on).
    assert eng_f.plane_layout != eng_q2.plane_layout


def test_prefix_cache_hit_roundtrip_same_layout_int8():
    """Same-layout int8 snapshots still hit and reproduce identical streams —
    the guard blocks cross-layout installs, not the feature."""
    model = _model()
    params = _params(model)
    prompt = (np.arange(10) % (model.vocab_size - 1)).astype(np.int32)
    eng = ContinuousBatchingEngine(model, params, num_slots=2,
                                   kv_dtype="int8", prefix_cache_entries=4)
    first = np.asarray(eng.run([Request(prompt=prompt, max_new_tokens=4,
                                        request_id=0)])[0].tokens)
    again = np.asarray(eng.run([Request(prompt=prompt, max_new_tokens=4,
                                        request_id=1)])[0].tokens)
    assert eng.prefix_cache.hits >= 1
    np.testing.assert_array_equal(first, again)


# -----------------------------------------------------------------------------------------
# Byte-true accounting
# -----------------------------------------------------------------------------------------


def test_byte_accounting_matches_live_buffers_and_hits_ratios():
    """The accounting is the sum of real leaf bytes, and at a serving-shaped
    config int8 KV (+ int8 weights) clears the committed ratios: >= 1.8x fewer
    measured decode bytes/token, >= 1.9x slots under the same HBM budget."""
    model = lm.TransformerLM(vocab_size=9, seq_len=128, embed_dim=32,
                             num_layers=2, num_heads=4)
    params = _params(model)
    eng_a = ContinuousBatchingEngine(model, params, num_slots=4)
    eng_b = ContinuousBatchingEngine(model, params, num_slots=4,
                                     kv_dtype="int8", quant_policy="w8")
    acct_a, acct_b = eng_a.byte_accounting(), eng_b.byte_accounting()
    # Byte-true: recompute from the engines' actual arrays.
    for eng, acct in ((eng_a, acct_a), (eng_b, acct_b)):
        assert acct["kv_bytes_resident"] == quant.tree_bytes(eng._cache)
        assert acct["params_bytes"] == quant.tree_bytes(eng.params)
    # int8 planes + f32 scales: 4 / (1 + 4/Dh) per element vs fp32.
    hd = model.embed_dim // model.num_heads
    expect = 4.0 / (1.0 + 4.0 / hd)
    assert acct_a["kv_bytes_per_slot"] / acct_b["kv_bytes_per_slot"] == \
        pytest.approx(expect, rel=0.01)
    assert acct_a["decode_bytes_per_token"] / \
        acct_b["decode_bytes_per_token"] >= 1.8
    assert acct_b["slots_at_budget"] / acct_a["slots_at_budget"] >= 1.9


def test_tree_bytes_counts_quantized_tensors_exactly():
    w = jnp.ones((64, 32))
    qt = quant.quantize_tensor(w)
    assert quant.tree_bytes({"w": qt}) == 64 * 32 * 1 + 32 * 4
    assert qt.nbytes == 64 * 32 * 1 + 32 * 4


def test_serve_summary_event_carries_byte_accounting():
    from csed_514_project_distributed_training_using_pytorch_tpu.utils import (
        telemetry as T,
    )

    ev = T.serve_summary_event(requests=1, ok=1, timeout=0, new_tokens=4,
                               wall_s=1.0,
                               byte_accounting={"kv_dtype": "int8",
                                                "decode_bytes_per_token": 10.0})
    assert ev["bytes"]["kv_dtype"] == "int8"


def test_estimate_mfu_reports_bytes_side():
    from csed_514_project_distributed_training_using_pytorch_tpu.utils import (
        telemetry as T,
    )

    ev = T.estimate_mfu(1e9, 0.01, bytes_per_step=1e6)
    assert ev["bytes_accessed_per_step"] == 1e6
    assert ev["achieved_bytes_per_s_per_device"] == pytest.approx(1e8)
    # Off-TPU the roofline fraction is None — never a guess.
    assert ev["hbm_frac"] is None
    # And the AOT path actually measures bytes on this backend.
    compiled = jax.jit(lambda x: (x @ x).sum()).lower(
        jnp.ones((32, 32))).compile()
    measured = T.compiled_bytes_accessed(compiled)
    assert measured is None or measured > 0


# -----------------------------------------------------------------------------------------
# CLI plumbing: loadgen flags, summary artifact, report rows
# -----------------------------------------------------------------------------------------


def _load_tool(name):
    import importlib.util

    path = os.path.join(_REPO, "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_loadgen_kv_dtype_flags_recorded_in_summary(tmp_path, capsys):
    """Satellite: --kv-dtype/--quant-policy plumb through engine construction
    and land in --summary-json, so A/B runs are one flag apart."""
    loadgen = _load_tool("serve_loadgen")
    summary = tmp_path / "quant_on.json"
    tele = tmp_path / "serve.jsonl"
    rc = loadgen.main([
        "--requests", "4", "--mode", "closed", "--concurrency", "2",
        "--seq-len", "16", "--embed-dim", "16", "--num-layers", "1",
        "--num-heads", "2", "--num-levels", "8", "--num-slots", "2",
        "--prompt-lens", "0,4", "--max-new-tokens", "4",
        "--prefill-chunks", "8", "--warmup", "0",
        "--kv-dtype", "int8", "--quant-policy", "w8",
        "--telemetry", str(tele), "--summary-json", str(summary)])
    assert rc == 0
    doc = json.loads(summary.read_text())
    assert doc["kv_dtype"] == "int8" and doc["quant_policy"] == "w8"
    assert doc["bytes"]["kv_dtype"] == "int8"
    assert doc["bytes"]["decode_bytes_per_token"] > 0
    assert doc["decode_compilations"] == 1
    out = capsys.readouterr().out
    assert "bytes (measured)" in out
    # The serve telemetry's summary event carries the same accounting.
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.metrics import (
        load_metrics_jsonl,
    )

    rows = load_metrics_jsonl(str(tele))
    summaries = [r for r in rows if r.get("event") == "serve_summary"]
    assert summaries and summaries[-1]["bytes"]["kv_dtype"] == "int8"


def test_telemetry_report_renders_bytes_ab_rows(tmp_path, capsys):
    """Satellite: the report CLI renders decode bytes/token, KV bytes/slot and
    slots-at-budget as A-vs-B rows — the quant artifact renders like the
    prefill and affinity ones."""
    report = _load_tool("telemetry_report")

    def write(path, dtype, bpt, per_slot, slots):
        with open(path, "w") as f:
            f.write(json.dumps({
                "event": "serve_summary", "requests": 4, "ok": 4, "timeout": 0,
                "new_tokens": 64, "wall_s": 1.0, "tokens_per_s": 64.0,
                "bytes": {"kv_dtype": dtype, "quant_policy": "off",
                          "decode_bytes_per_token": bpt,
                          "kv_bytes_per_slot": per_slot,
                          "slots_at_budget": slots}}) + "\n")

    a, b = str(tmp_path / "fp32.jsonl"), str(tmp_path / "int8.jsonl")
    write(a, "model", 1000.0, 4096, 100)
    write(b, "int8", 400.0, 1280, 320)
    assert report.main([a, b]) == 0
    out = capsys.readouterr().out
    assert "decode bytes/tok" in out and "kv bytes/slot" in out
    assert "slots @ budget" in out
    assert "bytes: kv model" in out and "bytes: kv int8" in out


@pytest.mark.slow
def test_bench_decode_analysis_quant_ab_smoke(tmp_path):
    """The --quant-ab artifact generator end to end at a tiny shape: ratios,
    accuracy fields and one-program pins all present and internally coherent."""
    import subprocess

    out = tmp_path / "quant_ab.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools",
                                      "bench_decode_analysis.py"),
         "--seq", "256", "--d-model", "32", "--layers", "1", "--heads", "2",
         "--gen-batch", "2", "--no-bf16", "--quant-ab", "--ab-requests", "4",
         "--ab-new-tokens", "8", "--ab-nll-batch", "2",
         "--curve-chunks", "32,128", "--out", str(out)],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = json.loads(out.read_text())
    ab = doc["quant_ab"]
    assert ab["decode_bytes_per_token_reduction"] >= 1.8
    assert ab["slots_at_budget_ratio"] >= 1.9
    assert ab["one_program_pins"]["decode_trace_count_ok"]
    assert ab["one_program_pins"]["prefill_trace_counts_ok"]
    assert abs(ab["nll_delta"]) <= ab["nll_delta_bound"]
    assert 0.0 <= ab["token_match_rate"] <= 1.0
