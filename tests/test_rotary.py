"""Rotary position embeddings: the relative-position property, model wiring, and the
LM decode-parity invariant under RoPE."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from csed_514_project_distributed_training_using_pytorch_tpu.ops.rotary import (
    apply_rotary,
)


def test_relative_position_invariance():
    """THE RoPE property: ⟨R(p)q, R(p')k⟩ depends only on p − p' — shifting both
    positions by the same offset leaves every q·k score unchanged."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 8, 2, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 8, 2, 16)).astype(np.float32))

    def scores(shift):
        pos = jnp.arange(8) + shift
        qr, kr = apply_rotary(q, pos), apply_rotary(k, pos)
        return jnp.einsum("bqhd,bkhd->bhqk", qr, kr)

    np.testing.assert_allclose(np.asarray(scores(0)), np.asarray(scores(100)),
                               rtol=1e-4, atol=1e-4)


def test_scalar_position_matches_indexed_row():
    """Decode-style scalar-position rotation equals the corresponding row of the
    full-sequence rotation (the forward/decode consistency RoPE decode relies on)."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 8, 4, 16)).astype(np.float32))
    full = apply_rotary(x, jnp.arange(8))
    for t in (0, 3, 7):
        row = apply_rotary(x[:, t], jnp.asarray(t, jnp.int32))
        np.testing.assert_allclose(np.asarray(row), np.asarray(full[:, t]),
                                   rtol=1e-6, atol=1e-6)


def test_odd_head_dim_rejected():
    with pytest.raises(ValueError, match="even head dim"):
        apply_rotary(jnp.zeros((1, 4, 2, 15)), jnp.arange(4))


def test_rope_changes_classifier_output_same_params():
    """rope=True is a pure q/k transform: identical parameter tree, different
    function — the wiring sanity check."""
    from csed_514_project_distributed_training_using_pytorch_tpu.models import (
        build_model,
    )

    plain = build_model("transformer")
    roped = build_model("transformer", rope=True)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 28, 28, 1)).astype(np.float32))
    params = plain.init({"params": jax.random.PRNGKey(0)}, x)["params"]
    out_plain = plain.apply({"params": params}, x)
    out_roped = roped.apply({"params": params}, x)
    assert not np.allclose(np.asarray(out_plain), np.asarray(out_roped))


@pytest.mark.slow  # ~13 s: full train + KV-cache decode; the fast tier keeps
                   # the rotation-math and cache-parity unit pins
def test_lm_rope_decode_matches_full_forward():
    """The decode-parity invariant under RoPE (+GQA): the KV-cache path rotates its
    single position by the same formula as the teacher-forced forward."""
    from csed_514_project_distributed_training_using_pytorch_tpu.models import lm

    model = lm.TransformerLM(vocab_size=9, seq_len=16, embed_dim=32, num_layers=2,
                             num_heads=4, num_kv_heads=2, rope=True)
    ids0 = jnp.zeros((1, 16), jnp.int32)
    params = model.init({"params": jax.random.PRNGKey(3)}, ids0)["params"]
    assert "pos_embed" not in params            # RoPE owns position
    rng = np.random.default_rng(4)
    targets = jnp.asarray(rng.integers(0, 8, size=(2, 16)).astype(np.int32))
    inputs = model.shift_right(targets)
    ref = model.apply({"params": params}, inputs)

    cache = lm.init_cache(model, batch=2)
    for t in range(model.seq_len):
        cache, log_probs = lm.decode_step(model, params, cache, inputs[:, t],
                                          jnp.asarray(t, jnp.int32))
        np.testing.assert_allclose(np.asarray(log_probs), np.asarray(ref[:, t]),
                                   rtol=1e-5, atol=1e-5, err_msg=f"position {t}")


# -- the interleaved pairing (a deepseek_v3 checkpoint's decoupled rotary channels) ----------


def _published_interleaved(x, positions, base):
    """The published ``deepseek_v3`` code's rotation of interleaved channels, written out:
    each pair's halves moved apart (``x[0::2] | x[1::2]``), then ``x·cos + rotate_half(x)·sin``
    with the angles repeated over both halves."""
    d = x.shape[-1]
    x = x.reshape(*x.shape[:-1], d // 2, 2).swapaxes(-1, -2).reshape(x.shape)
    inv_freq = base ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    angles = np.asarray(positions, np.float32)[:, None] * inv_freq
    angles = jnp.asarray(np.concatenate([angles, angles], axis=-1))[:, None, :]
    rotate_half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(angles) + rotate_half * jnp.sin(angles)


def _qk(seed, heads=(3, 1), width=16):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(2, 8, n, width)).astype(np.float32)) for n in heads)


@pytest.mark.parametrize("base", [10000.0, 1e6])
def test_interleaved_pairing_gives_the_published_forms_scores(base):
    """Pairs turned where they lie against pairs moved apart and turned half-split: the
    rotated channels differ by one permutation, the same on both sides, so every q·k is the
    same (three query heads against one shared key)."""
    q, k = _qk(5)
    pos = jnp.arange(8) + 3
    ours = jnp.einsum("bqhd,bkgd->bhqk", apply_rotary(q, pos, base=base, interleaved=True),
                      apply_rotary(k, pos, base=base, interleaved=True))
    theirs = jnp.einsum("bqhd,bkgd->bhqk", _published_interleaved(q, pos, base),
                        _published_interleaved(k, pos, base))
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), rtol=1e-5, atol=1e-5)
    # and the channels themselves are the published ones, moved back together
    moved = _published_interleaved(q, pos, base)
    back = moved.reshape(*q.shape[:-1], 2, q.shape[-1] // 2).swapaxes(-1, -2).reshape(q.shape)
    np.testing.assert_allclose(np.asarray(apply_rotary(q, pos, base=base, interleaved=True)),
                               np.asarray(back), rtol=1e-5, atol=1e-5)


def test_interleaved_pairing_is_not_the_half_split_one():
    q, k = _qk(6)
    pos = jnp.arange(8)
    scores = lambda **kw: jnp.einsum("bqhd,bkgd->bhqk", apply_rotary(q, pos, **kw),
                                     apply_rotary(k, pos, **kw))
    assert float(jnp.abs(scores(interleaved=True) - scores()).max()) > 0.1


@pytest.mark.parametrize("interleaved", [False, True], ids=["half-split", "interleaved"])
def test_shift_invariance_over_a_part_of_a_heads_channels(interleaved):
    """A latent-attention head: 16 channels carried as they are, the last 8 rotated. The
    score's rotated part depends on p − p' alone, so the whole score does."""
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(1, 8, 2, 24)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 8, 2, 24)).astype(np.float32))

    def scores(shift):
        pos = jnp.arange(8) + shift
        turn = lambda x: jnp.concatenate(
            [x[..., :16], apply_rotary(x[..., 16:], pos, base=1e6, interleaved=interleaved)],
            axis=-1)
        return jnp.einsum("bqhd,bkhd->bhqk", turn(q), turn(k))

    np.testing.assert_allclose(np.asarray(scores(0)), np.asarray(scores(1000)),
                               rtol=2e-4, atol=2e-4)
    # the angles are over the 8 channels handed, not over the head's 24
    alone = apply_rotary(q[..., 16:], jnp.arange(8), base=1e6, interleaved=interleaved)
    whole = apply_rotary(q, jnp.arange(8), base=1e6, interleaved=interleaved)[..., 16:]
    assert float(jnp.abs(alone - whole).max()) > 1e-3


def test_the_half_split_path_is_unchanged_to_the_bit():
    """``interleaved=False`` is the function as it stood: the formula written out again
    here, bit for bit, in float32 and in bfloat16."""
    rng = np.random.default_rng(8)
    for dtype in (jnp.float32, jnp.bfloat16):
        x = jnp.asarray(rng.normal(size=(2, 8, 4, 16)).astype(np.float32)).astype(dtype)
        pos = jnp.arange(8)
        inv_freq = 1e4 ** (-jnp.arange(0, 16, 2, dtype=jnp.float32) / 16)
        ang = (pos.astype(jnp.float32)[..., None] * inv_freq)[:, None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        xf = x.astype(jnp.float32)
        x1, x2 = xf[..., :8], xf[..., 8:]
        want = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(dtype)
        for got in (apply_rotary(x, pos), apply_rotary(x, pos, interleaved=False)):
            assert got.dtype == dtype
            np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                          np.asarray(want.astype(jnp.float32)))


def test_interleaved_scalar_position_matches_indexed_row():
    x = jnp.asarray(np.random.default_rng(9).normal(size=(2, 8, 4, 16)).astype(np.float32))
    full = apply_rotary(x, jnp.arange(8), base=1e6, interleaved=True)
    row = apply_rotary(x[:, 5], jnp.asarray(5, jnp.int32), base=1e6, interleaved=True)
    np.testing.assert_allclose(np.asarray(row), np.asarray(full[:, 5]), rtol=1e-6, atol=1e-6)


# -- the lane-whole form against the formula it replaced (PR 48) -----------------------------


def _sliced_rotary(x, positions, *, base=10000.0, interleaved=False, channels=None):
    """The plain reference: ``ops/rotary.py`` as it stood until PR 48 and its call sites'
    split and rejoin around a part of a head, written out. The turning channels are cut
    out of the head, their halves (or their pairs' two lanes) are arrays of their own,
    and ``jnp.concatenate`` joins the products and the channels that do not turn."""
    first, width = channels or (0, x.shape[-1])
    part = x[..., first:first + width].astype(jnp.float32)
    inv_freq = base ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    if positions.ndim:
        ang = ang[..., :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleaved:
        x1, x2 = part[..., 0::2], part[..., 1::2]
        turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).reshape(part.shape)
    else:
        x1, x2 = part[..., :width // 2], part[..., width // 2:]
        turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([x[..., :first], turned.astype(x.dtype),
                            x[..., first + width:]], axis=-1)


def _part(which: str, width: int):
    """The cells' three cases: a whole head; ``qwen3_next``'s leading quarter (64 of 256);
    ``kanana2``'s trailing third (64 of 192)."""
    turning = min(64, width // 2)
    return {"whole": None, "leading": (0, turning), "trailing": (width - turning, turning)}[which]


@pytest.mark.parametrize("positions", ["row", "scalar"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("width", [64, 128, 192, 256])
@pytest.mark.parametrize("which", ["whole", "leading", "trailing"])
@pytest.mark.parametrize("interleaved", [False, True], ids=["half-split", "interleaved"])
def test_lane_whole_form_is_the_sliced_formula(interleaved, which, width, dtype, positions):
    """Values, and the gradient of a weighted sum, against the reference: the same two
    products and one sum a channel in float32 and one rounding, so float32 agrees to its
    rounding and bfloat16 to one step of its own."""
    rng = np.random.default_rng(width + 7 * interleaved)
    shape = (2, 6, 3, width) if positions == "row" else (2, 3, width)
    pos = jnp.arange(6) + 11 if positions == "row" else jnp.asarray(13, jnp.int32)
    x = jnp.asarray(rng.normal(size=shape).astype(np.float32)).astype(dtype)
    w = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    kw = dict(base=1e6, interleaved=interleaved, channels=_part(which, width))
    tol = dict(rtol=1e-6, atol=1e-6) if dtype == jnp.float32 else dict(rtol=8e-3, atol=8e-3)
    for fn in (lambda turn: turn(x, pos, **kw),
               lambda turn: jax.grad(lambda x: jnp.sum(
                   turn(x, pos, **kw).astype(jnp.float32) * w))(x)):
        got, want = fn(apply_rotary), fn(_sliced_rotary)
        assert got.dtype == dtype and got.shape == shape
        np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                                   np.asarray(want.astype(jnp.float32)), **tol)


def _cuts_of_the_last_axis(jaxpr) -> list[str]:
    """Every equation, in ``jaxpr`` or under it, that slices, pads or joins an array of
    three axes or more along its last axis."""
    found = []
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _cuts_of_the_last_axis(sub)
        shape = eqn.invars[0].aval.shape if eqn.invars else ()
        if len(shape) < 3:
            continue
        name, last = eqn.primitive.name, len(shape) - 1
        if (name == "slice" and (eqn.params["start_indices"][last] != 0
                                 or eqn.params["limit_indices"][last] != shape[last])) \
                or (name == "concatenate" and eqn.params["dimension"] == last) \
                or (name == "pad" and tuple(eqn.params["padding_config"][last]) != (0, 0, 0)) \
                or (name in ("dynamic_slice", "dynamic_update_slice")):
            found.append(str(eqn)[:200])
    return found


@pytest.mark.parametrize("shape,kw", [
    ((1, 256, 16, 128), {}),                                           # evabyte, falcon_h1, lm
    ((2, 256, 8, 64), {}),                                             # lfm2
    ((2, 256, 16, 256), {"channels": (0, 64)}),                        # qwen3_next
    ((2, 256, 32, 192), {"channels": (128, 64), "interleaved": True}),  # kanana2
    ((2, 256, 1, 64), {"interleaved": True}),                          # its one shared key
    ((4, 8, 128), {}),                                                 # serving: [B, H, D], one position a row
], ids=["128", "64", "first-64-of-256", "last-64-of-192-interleaved", "shared-key", "decode"])
def test_no_slice_or_join_of_the_last_axis_in_either_pass(shape, kw):
    """The copies cannot come back unseen: neither the rotation's jaxpr nor its
    gradient's cuts a head's channels apart or joins them (on the chip a slice at half a
    lane tile is an array of its own, and the transpose of slice-and-concatenate is
    pad-and-add). The reference's jaxpr holds both, which is what the walk looks for."""
    x = jnp.zeros(shape, jnp.bfloat16)
    pos = jnp.arange(shape[-3])
    value = lambda turn: lambda x: turn(x, pos, **kw)
    grad = lambda turn: jax.grad(lambda x: jnp.sum(turn(x, pos, **kw).astype(jnp.float32)))
    for make in (value, grad):
        assert _cuts_of_the_last_axis(jax.make_jaxpr(make(apply_rotary))(x).jaxpr) == []
        assert _cuts_of_the_last_axis(jax.make_jaxpr(make(_sliced_rotary))(x).jaxpr)


def test_backward_is_the_rotation_at_the_negated_angle():
    """A rotation's transpose is its inverse: the cotangent turned back by the same
    angles, and turning forward again returns it; nothing but ``positions`` is held."""
    rng = np.random.default_rng(48)
    x = jnp.asarray(rng.normal(size=(2, 8, 2, 192)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(2, 8, 2, 192)).astype(np.float32))
    pos = jnp.arange(8) + 40
    kw = dict(base=1e6, interleaved=True, channels=(128, 64))
    out, pull = jax.vjp(lambda x: apply_rotary(x, pos, **kw), x)
    (back,) = pull(g)
    np.testing.assert_allclose(np.asarray(apply_rotary(back, pos, **kw)), np.asarray(g),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(back[..., :128]), np.asarray(g[..., :128]))
    np.testing.assert_array_equal(np.asarray(out[..., :128]), np.asarray(x[..., :128]))
    residuals = jax.make_jaxpr(lambda x: jax.vjp(lambda x: apply_rotary(x, pos, **kw), x)[1])(x)
    assert all(v.aval.shape == pos.shape for v in residuals.jaxpr.outvars)


def test_channels_outside_the_head_are_refused():
    with pytest.raises(ValueError, match="do not lie in a head"):
        apply_rotary(jnp.zeros((1, 4, 2, 16)), jnp.arange(4), channels=(12, 8))
    with pytest.raises(ValueError, match="even head dim"):
        apply_rotary(jnp.zeros((1, 4, 2, 16)), jnp.arange(4), channels=(0, 7))
