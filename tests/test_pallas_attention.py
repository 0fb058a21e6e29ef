"""Flash-attention Pallas kernels vs the dense oracle.

Interpret-mode (CPU) tests pin exact numerics of the forward and the recompute
backward (its fused kernel, and the two split ones past its budget) against
``ops.full_attention``; the TPU-gated test re-checks parity compiled through Mosaic on
hardware (looser tolerance: TPU matmuls run f32 via bf16 passes in both paths, so they
differ from each other at ~1e-3).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from csed_514_project_distributed_training_using_pytorch_tpu.ops.attention import (
    full_attention,
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops.pallas_attention import (
    BLOCK,
    flash_attention,
)


def _qkv(b=2, s=256, h=2, d=64, seed=0):
    """``d`` is the head width, or ``(key width, value width)`` where the two differ."""
    rng = np.random.default_rng(seed)
    dk, dv = d if isinstance(d, tuple) else (d, d)
    return tuple(jnp.asarray(rng.normal(size=(b, s, h, width)).astype(np.float32))
                 for width in (dk, dk, dv))


# one width for queries, keys and values (256: ``qwen3_next_train_8k``'s gated attention, two
# lane registers a head), and latent attention's (key, value) widths
WIDTHS = [64, 256, (192, 128)]


def _tol(tight_rtol, tight_atol):
    """Interpret mode (CPU) is exact to f32 round-off; on hardware both paths run f32
    matmuls as bf16 MXU passes and differ from each other at ~1e-3."""
    if jax.default_backend() == "tpu":
        return dict(rtol=2e-2, atol=2e-2)
    return dict(rtol=tight_rtol, atol=tight_atol)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("head_dim", WIDTHS)
def test_forward_matches_dense(causal, head_dim):
    q, k, v = _qkv(d=head_dim)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=causal)),
        np.asarray(full_attention(q, k, v, causal=causal)),
        **_tol(1e-5, 1e-5))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("window", [None, 160])
@pytest.mark.parametrize("head_dim", [64, 128, 256, (192, 128)])
def test_gradients_match_dense(causal, window, head_dim):
    """Forward and all three gradients against the dense oracle at the cells' head
    widths (64 is ``lfm2_moe_train_8k``'s, 128 ``lm_train_b16``'s: a whole lane
    register, 256 ``qwen3_next_train_8k``'s, keys of 192 and values of 128
    ``kimi_linear_train_8k``'s), with and without
    a band that straddles the two 128-row blocks, over an odd number of heads."""
    q, k, v = _qkv(b=2, s=256, h=3, d=head_dim, seed=13)
    kw = dict(causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, block=128, **kw)),
        np.asarray(full_attention(q, k, v, **kw)), **_tol(2e-5, 2e-5))

    def loss(attn):
        return lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v)))

    g_ref = jax.grad(loss(lambda q, k, v: full_attention(q, k, v, **kw)),
                     argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, block=128, **kw)), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   err_msg=name, **_tol(2e-4, 2e-5))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("s,block", [(256, 256), (512, 128)], ids=["one-block", "four-blocks"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("head_dim", [64, 128, (192, 128)])
def test_fused_backward_matches_dense_vjp_and_the_split_kernels(
        monkeypatch, head_dim, causal, s, block, dtype):
    """``flash_backward_blocks`` on its fused path (one kernel: dq resident in VMEM beside
    dk and dv) against ``jax.vjp`` of the dense core on the same operands, and against
    the two split kernels an oversize ``[S, D]`` still takes, to the last bit: the same
    products on the same operands, each gradient's blocks summed in the same order."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import (
        pallas_attention as pa,
    )
    q, k, v = (x.reshape(-1, s, x.shape[-1]).astype(dtype)
               for x in _qkv(b=3, s=s, h=1, d=head_dim, seed=17))
    g = jnp.asarray(np.random.default_rng(18).normal(size=v.shape), dtype)
    assert pa.backward_fused(s, q.shape[-1])
    out, lse = pa._flash_forward(q, k, v, causal=causal, block=block)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1) \
        .reshape(lse.shape)
    backward = lambda: pa.flash_backward_blocks(q, k, v, g, lse, delta, causal=causal,
                                                block=block)
    fused = backward()
    wide = lambda x: x.astype(jnp.float32)[:, :, None]          # [BH, S, 1, width]
    _, vjp = jax.vjp(lambda q, k, v: full_attention(q, k, v, causal=causal),
                     wide(q), wide(k), wide(v))
    tol = _tol(2e-4, 2e-5) if dtype == "float32" else dict(rtol=0.1, atol=0.05)
    for name, got, want in zip(("dq", "dk", "dv"), fused, vjp(wide(g))):
        assert got.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want[:, :, 0]),
                                   err_msg=name, **tol)
    monkeypatch.setattr(pa, "FUSED_DQ_MAX_BYTES", 4 * s * q.shape[-1] - 1)
    assert not pa.backward_fused(s, q.shape[-1])
    for name, got, want in zip(("dq", "dk", "dv"), fused, backward()):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32), err_msg=name)


def test_multi_block_sequence():
    """S spanning several 128-blocks exercises the online-softmax accumulation and the
    causal block-skip bounds."""
    q, k, v = _qkv(b=1, s=512, h=1, d=64, seed=2)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True)),
        np.asarray(full_attention(q, k, v, causal=True)),
        **_tol(1e-5, 1e-5))


@pytest.mark.parametrize("causal", [False, True])
def test_block_size_is_numerics_invariant(causal):
    """``block`` is a pure performance knob (r3 tuning surface): a 256-row block over
    a 512-sequence — forward AND gradients — equals both the dense oracle and the
    default-block kernel."""
    q, k, v = _qkv(b=1, s=512, h=2, d=64, seed=4)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=causal, block=256)),
        np.asarray(full_attention(q, k, v, causal=causal)),
        **_tol(1e-5, 1e-5))

    def loss(attn):
        return lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v, causal=causal)))

    g_ref = jax.grad(loss(full_attention), argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss(lambda q, k, v, causal: flash_attention(
        q, k, v, causal=causal, block=256)), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   err_msg=name, **_tol(1e-4, 2e-5))


def test_dense_window_matches_naive_mask():
    """full_attention(window=W) equals an explicit numpy band mask — the windowed
    semantics oracle (distance < W; causal restricts to the past side)."""
    q, k, v = _qkv(b=1, s=64, h=2, d=16, seed=6)
    w = 10
    for causal in (False, True):
        ref = np.asarray(full_attention(q, k, v, causal=causal, window=w))
        i = np.arange(64)[:, None]
        j = np.arange(64)[None, :]
        mask = (np.abs(i - j) < w) & ((i >= j) if causal else True)
        scores = np.einsum("bqhd,bkhd->bhqk", np.asarray(q),
                           np.asarray(k)) / np.sqrt(16.0)
        scores = np.where(mask[None, None], scores, -1e30)
        weights = np.exp(scores - scores.max(-1, keepdims=True))
        weights /= weights.sum(-1, keepdims=True)
        naive = np.einsum("bhqk,bkhd->bqhd", weights, np.asarray(v))
        # The oracle is numpy f32; on hardware the jax side runs its matmuls as
        # bf16 MXU passes, so the comparison needs the hardware tolerance.
        np.testing.assert_allclose(ref, naive, **_tol(1e-5, 1e-6),
                                   err_msg=f"causal={causal}")


@pytest.mark.parametrize("causal,s,w", [
    # s=512, w=160: causal runs the band-compressed grid (reach+1 = 3 < 4 blocks);
    # non-causal falls back to the full grid (2·reach+1 = 5 ≥ 4) — both paths covered.
    (False, 512, 160), (True, 512, 160),
    # s=1024 activates the band-compressed grid for the BIDIRECTIONAL walk too
    # (5 < 8 blocks) — offsets clamp at both sequence edges.
    (False, 1024, 160), (True, 1024, 160),
])
def test_flash_window_matches_dense(causal, s, w):
    """Banded flash (band-compressed grid + in-kernel band mask) equals dense windowed
    attention — forward AND gradients. window=160 straddles block boundaries (not a
    multiple of 128), exercising partial-band blocks on both sides."""
    q, k, v = _qkv(b=1, s=s, h=2, d=64, seed=7)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=causal, window=w)),
        np.asarray(full_attention(q, k, v, causal=causal, window=w)),
        **_tol(1e-5, 1e-5))

    def loss(attn):
        return lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v)))

    g_ref = jax.grad(loss(lambda q, k, v: full_attention(
        q, k, v, causal=causal, window=w)), argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, window=w)), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   err_msg=name, **_tol(1e-4, 2e-5))


def test_window_validation():
    q, k, v = _qkv(b=1, s=256, h=1, d=64, seed=8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, window=0)
    with pytest.raises(ValueError, match="window"):
        full_attention(q, k, v, window=-1)


def test_block_validation():
    q, k, v = _qkv(b=1, s=256, h=1, d=64, seed=5)
    with pytest.raises(ValueError, match="multiple of 128"):
        flash_attention(q, k, v, block=64)
    with pytest.raises(ValueError, match="divisible by block"):
        flash_attention(q, k, v, block=384)


def test_indivisible_sequence_rejected():
    q, k, v = _qkv(s=200)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("head_dim", WIDTHS)
def test_bf16_forward_and_gradients_match_f32_dense(causal, head_dim):
    """The r4 kernels keep matmul operands in the INPUT dtype (bf16 on the MXU's
    native path) with f32 accumulation — so the bf16 path must be pinned against
    the f32 dense oracle at bf16-resolution tolerance, not just exercised as the
    identity-astype f32 case the other tests cover."""
    q, k, v = _qkv(d=head_dim, seed=11)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ref = full_attention(qb.astype(jnp.float32), kb.astype(jnp.float32),
                         vb.astype(jnp.float32), causal=causal)
    out = flash_attention(qb, kb, vb, causal=causal)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=0.05, atol=0.03)

    def loss(attn, cast):
        return lambda q, k, v: jnp.sum(
            jnp.sin(attn(cast(q), cast(k), cast(v), causal=causal)
                    .astype(jnp.float32)))

    g_ref = jax.grad(loss(full_attention, lambda x: x.astype(jnp.float32)),
                     argnums=(0, 1, 2))(qb, kb, vb)
    g_flash = jax.grad(loss(flash_attention, lambda x: x),
                       argnums=(0, 1, 2))(qb, kb, vb)
    for name, a, b in zip("qkv", g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(b, np.float32),
                                   np.asarray(a, np.float32),
                                   err_msg=name, rtol=0.1, atol=0.05)


@pytest.mark.parametrize("q_offset", [256, -256])
@pytest.mark.parametrize("window", [100, 300])
def test_q_offset_block_pair_matches_manual(q_offset, window):
    """The ring hop building block: a q-block set attending a k-block set whose
    global positions differ by a static q_offset must equal the manually-masked
    dense computation on the same band (rows with no visible key normalize to 0 —
    the ring merge never consumes them). Exercises the offset-shifted band masks
    and the banded grid's shifted center in one shot."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops.pallas_attention import (
        flash_forward_with_lse,
    )

    bh, s, d = 2, 256, 32
    rng = np.random.default_rng(23)
    q3, k3, v3 = (jnp.asarray(rng.normal(size=(bh, s, d)).astype(np.float32))
                  for _ in range(3))
    out, _ = flash_forward_with_lse(q3, k3, v3, causal=False, window=window,
                                    q_offset=q_offset)

    rel = (q_offset + np.arange(s))[:, None] - np.arange(s)[None, :]
    visible = np.abs(rel) < window
    scores = np.einsum("bqd,bkd->bqk", np.asarray(q3),
                       np.asarray(k3)) / np.sqrt(d)
    scores = np.where(visible, scores, -np.inf)
    with np.errstate(invalid="ignore", over="ignore"):
        p = np.exp(scores - scores.max(-1, keepdims=True))
        p = np.nan_to_num(p, nan=0.0)
        denom = p.sum(-1, keepdims=True)
        ref = np.einsum("bqk,bkd->bqd", p / np.where(denom == 0, 1, denom),
                        np.asarray(v3))
    # _tol: hardware matmuls run bf16-multiply default precision vs numpy's exact
    # reference, so the TPU-gated pass needs the module's loose tolerance.
    np.testing.assert_allclose(np.asarray(out), ref, **_tol(1e-5, 1e-5))


def test_q_offset_validation():
    from csed_514_project_distributed_training_using_pytorch_tpu.ops.pallas_attention import (
        flash_forward_with_lse,
    )

    q3 = jnp.zeros((1, 256, 32))
    with pytest.raises(ValueError, match="multiple of block"):
        flash_forward_with_lse(q3, q3, q3, window=64, q_offset=100)


def test_auto_block_selection():
    from csed_514_project_distributed_training_using_pytorch_tpu.ops.pallas_attention import (
        auto_block,
    )

    assert auto_block(256) == 256
    assert auto_block(1024) == 1024
    assert auto_block(8192) == 1024      # capped at the measured sweet spot
    assert auto_block(1280) == 256       # largest divisor under the cap
    # One block holds a sequence up to the cap, banded or not (PR 25 hw sweep:
    # 896 at 896 beats the 128 that divides it 4.8x, 2.8x under a window of 256).
    assert auto_block(896) == 896
    assert auto_block(896, window=256) == 896
    # Windowed cap is W-dependent (r5 hw sweeps): narrow bands keep the 512
    # windowed cap; wide bands (W >= WIDE_WINDOW) amortize like the full walk.
    assert auto_block(8192, window=256) == 512
    assert auto_block(8192, window=4096) == 1024
    with pytest.raises(ValueError, match="divisible by 128"):
        auto_block(200)


def test_dispatch_attention_routes_by_crossover(monkeypatch):
    """Under FLASH_MIN_SCORE_BYTES of float32 scores, or FLASH_MIN_HEAD_SCORE_BYTES
    of them a (batch, head) (and for a non-causal unaligned S) dispatch is exactly
    the dense path; at and above both, the flash kernels (checked by matching each impl's own output bit-for-bit, which also
    pins the routing)."""
    import csed_514_project_distributed_training_using_pytorch_tpu.ops.pallas_attention as pa

    q, k, v = _qkv(s=256, seed=7)
    np.testing.assert_array_equal(
        np.asarray(pa.dispatch_attention(q, k, v, causal=True)),
        np.asarray(full_attention(q, k, v, causal=True)))
    qo, ko, vo = _qkv(s=200, seed=8)     # unaligned: must fall to dense, not raise
    np.testing.assert_array_equal(
        np.asarray(pa.dispatch_attention(qo, ko, vo)),
        np.asarray(full_attention(qo, ko, vo)))
    monkeypatch.setattr(pa, "FLASH_MIN_SCORE_BYTES", 4 * 2 * 2 * 256 * 256)
    monkeypatch.setattr(pa, "FLASH_MIN_HEAD_SCORE_BYTES", 4 * 256 * 256)
    np.testing.assert_array_equal(
        np.asarray(pa.dispatch_attention(q, k, v, causal=True)),
        np.asarray(flash_attention(q, k, v, causal=True)))


@pytest.mark.slow
def test_as_transformer_attention_core():
    """flash_attention plugs into the transformer family as attention_fn; one optimizer
    step from shared init matches the dense-core step."""
    from csed_514_project_distributed_training_using_pytorch_tpu.models import (
        TransformerClassifier,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
        create_train_state, make_train_step,
    )

    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.normal(size=(8, BLOCK, 8)).astype(np.float32))
    labels = jnp.asarray((np.arange(8) % 10).astype(np.int32))

    kwargs = dict(seq_len=BLOCK, embed_dim=32, num_layers=1, num_heads=2,
                  dropout_rate=0.0)
    dense_model = TransformerClassifier(**kwargs)
    flash_model = TransformerClassifier(attention_fn=flash_attention, **kwargs)
    state0 = create_train_state(dense_model, jax.random.PRNGKey(0),
                                sample_input_shape=(1, BLOCK, 8))

    results = []
    for m in (dense_model, flash_model):
        step = jax.jit(make_train_step(m, learning_rate=0.05, momentum=0.5))
        s1, loss = step(state0, tokens, labels, jax.random.PRNGKey(1))
        results.append((s1, float(loss)))
    (sa, la), (sb, lb) = results
    assert abs(la - lb) < (1e-2 if jax.default_backend() == "tpu" else 1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(sa.params),
                    jax.tree_util.tree_leaves(sb.params)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   **_tol(1e-4, 1e-5))


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="hardware Mosaic-compile smoke (FRAMEWORK_TEST_PLATFORM=tpu)")
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_on_tpu_matches_dense(causal, dtype):
    """Compiled-through-Mosaic parity on a real chip, in BOTH dtypes: a kernel
    form of r5 compiled for f32 and crashed the Mosaic compiler for bf16 (a
    sublane slice feeding an MXU dot), a break an f32-only smoke cannot see.
    Tolerance 2e-2: on the MXU the f32 paths run their matmuls as bf16 passes
    and differ from the dense oracle at ~1e-3; the bf16 paths carry bf16
    operands end-to-end."""
    q, k, v = (x.astype(dtype) for x in _qkv(seed=4))
    ref = full_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                         v.astype(jnp.float32), causal=causal)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=causal)).astype(np.float32),
        np.asarray(ref), rtol=2e-2, atol=2e-2)
    loss = lambda attn: lambda q, k, v: jnp.sum(
        jnp.sin(attn(q, k, v).astype(jnp.float32)))
    g_flash = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(lambda q, k, v: full_attention(q, k, v, causal=causal)),
                     argnums=(0, 1, 2))(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))
    # bf16 atol 5e-2: the measured on-chip worst-case |Δgrad| vs the f32 dense
    # oracle at these shapes is 0.018 (bf16 operand rounding through the sin
    # chain); 5e-2 pins with ~3× margin without being vacuous for O(1) grads.
    for a, b in zip(g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(b).astype(np.float32),
                                   np.asarray(a), rtol=2e-2, atol=5e-2 if
                                   dtype == "bfloat16" else 2e-2)


@pytest.mark.slow
@pytest.mark.parametrize("q_offset", [0, 256, -256])
def test_dyn_offset_banded_grid_matches_static(q_offset):
    """r5: a TRACED hop offset steers the banded walk through scalar-prefetch
    index maps — at sizes where banding engages (nq > 2*reach+1), the dynamic
    path's forward AND blockwise backward must equal the static-offset banded
    path exactly (same math, different grid steering)."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops.pallas_attention import (
        _band_reach, _banded, flash_backward_blocks, flash_forward_with_lse,
    )

    bh, s, d, window = 2, 1024, 32, 160
    assert _banded(window, False, s // 128, 128)   # the banded path is engaged
    rng = np.random.default_rng(31)
    q3, k3, v3, g = (jnp.asarray(rng.normal(size=(bh, s, d)).astype(np.float32))
                     for _ in range(4))

    out_s, lse_s = flash_forward_with_lse(q3, k3, v3, causal=False,
                                          window=window, q_offset=q_offset)
    out_d, lse_d = jax.jit(lambda off: flash_forward_with_lse(
        q3, k3, v3, causal=False, window=window, q_offset_dyn=off))(
        jnp.int32(q_offset))
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_s),
                               **_tol(1e-6, 1e-6))
    np.testing.assert_allclose(np.asarray(lse_d), np.asarray(lse_s),
                               **_tol(1e-6, 1e-6))

    delta = jnp.sum(g * out_s, axis=-1).reshape(bh, s // 128, 1, 128)
    grads_s = flash_backward_blocks(q3, k3, v3, g, lse_s, delta, causal=False,
                                    window=window, q_offset=q_offset)
    grads_d = jax.jit(lambda off: flash_backward_blocks(
        q3, k3, v3, g, lse_s, delta, causal=False, window=window,
        q_offset_dyn=off))(jnp.int32(q_offset))
    for name, a, b in zip("q k v".split(), grads_s, grads_d):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   err_msg=name, **_tol(1e-6, 1e-6))


@pytest.mark.slow
def test_dyn_offset_needs_no_block_quantization():
    """Unlike the static q_offset (rejected unless a block multiple), a TRACED
    offset may be arbitrary: the dynamic band is one block wider to absorb the
    sub-block remainder the floor-division steering discards. Pinned against the
    manual numpy band oracle at off=+100/-100 with banding engaged."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops.pallas_attention import (
        _dyn_banded, flash_forward_with_lse,
    )

    bh, s, d, window = 2, 1024, 32, 160
    assert _dyn_banded(window, s // 128, 128)
    rng = np.random.default_rng(37)
    q3, k3, v3 = (jnp.asarray(rng.normal(size=(bh, s, d)).astype(np.float32))
                  for _ in range(3))
    for q_offset in (100, -100):
        out, _ = jax.jit(lambda off: flash_forward_with_lse(
            q3, k3, v3, causal=False, window=window, q_offset_dyn=off))(
            jnp.int32(q_offset))
        rel = (q_offset + np.arange(s))[:, None] - np.arange(s)[None, :]
        visible = np.abs(rel) < window
        scores = np.einsum("bqd,bkd->bqk", np.asarray(q3),
                           np.asarray(k3)) / np.sqrt(d)
        scores = np.where(visible, scores, -np.inf)
        with np.errstate(invalid="ignore", over="ignore"):
            p = np.exp(scores - scores.max(-1, keepdims=True))
            p = np.nan_to_num(p, nan=0.0)
            denom = p.sum(-1, keepdims=True)
            ref = np.einsum("bqk,bkd->bqd", p / np.where(denom == 0, 1, denom),
                            np.asarray(v3))
        np.testing.assert_allclose(np.asarray(out), ref, err_msg=str(q_offset),
                                   **_tol(1e-5, 1e-5))
