"""Rotary position embeddings (RoPE) — relative positions by rotation.

Beyond-parity op (the reference has no attention at all, reference
``src/model.py:4-22``): RoPE in the half-split pairing — head dims ``i`` and
``i + D/2`` rotate together by ``pos / base^(2i/D)`` radians (the pairing of the
published decoders' checkpoints; ``base`` is their ``rope_theta``) — giving scores that
depend only on RELATIVE query/key distance (``⟨R(p)q, R(p')k⟩`` is a function of
``p - p'``; pinned as the shift-invariance property in ``tests/test_rotary.py``).

Applied to q/k AFTER projection and BEFORE the pluggable attention core, on the full
``[B, S, H, D]`` activations: the rotation is elementwise in the sequence dim, so under
GSPMD it shards with whatever layout the activations carry — RoPE composes with the
dense, flash, ring, and ulysses cores (and with GQA's broadcast K/V) with no
core-specific code. The LM decode path rotates its single position by the same formula
(``decode_step``), keeping the decode-parity invariant.

The interleaved pairing (``interleaved=True``: dims ``2i`` and ``2i + 1`` rotate
together, by the same angles) is the one a ``deepseek_v3`` checkpoint's decoupled rotary
channels are stored in (``rope_interleave``); the published code moves each pair's
halves apart and then rotates half-split, which gives the same q·k scores: the two
layouts differ by one permutation of both sides' channels. Here the channels stay where
they are. A caller that rotates a part of a head's channels (a latent-attention head's
last 64 of 192, a gated-attention head's first 64 of 256) hands the whole head and says
which channels turn (``channels=(first, width)``): the angles are over the width that
turns, and the other channels pass through the same pass untouched.

One body for every pairing and every part of a head: with ``P`` the signed ``[D, D]``
permutation that brings each channel its partner (``-x[i + w/2]`` to the pair's first
channel, ``+x[i - w/2]`` to its second; ``∓x[i ± 1]`` interleaved; a zero column for a
channel that does not turn) and ``C``, ``S`` the cos and sin tables over the whole head
width (one and zero where nothing turns), the rotated head is ``x·C + (x P)·S``: float32
products from the operand's own dtype, one sum, one rounding, the numbers of the older
slice-and-concatenate formula (``tests/test_rotary.py`` keeps it as the reference). The
product with ``P`` is exact in any dtype (an output is ± one input). The backward pass is
the same pass at the negated angle on the cotangent (a rotation's transpose is its
inverse): ``jax.custom_vjp``, nothing held but ``positions``.

TPU notes, as the v5e showed them (PR 48; ``bench_results/hw_pr48/``, ``PERF.md`` §6).
The chip keeps a head's channels on the 128 lanes of a tile, so the older form's
``x[..., :D/2]`` and ``x[..., D/2:]`` were arrays of their own (a slice at half a lane
tile is a copy), ``jnp.concatenate`` of the two products a third (``pad`` and
``maximum``), and autodiff's transpose of slice-and-concatenate pad-and-add: not "a fused
multiply-add on the VPU" but several float32 passes a tensor. What took their place, and
why, is beside ``rotation_form`` below.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=None)
def _partners(d: int, first: int, width: int, interleaved: bool) -> np.ndarray:
    """``P``, the signed ``[d, d]`` permutation: column ``j`` holds -1 at the row of its
    partner where ``j`` is its pair's first channel, +1 where its second, and nothing
    where channel ``j`` does not turn."""
    if width % 2:
        raise ValueError(f"RoPE needs an even head dim, got {width}")
    if not 0 <= first <= first + width <= d:
        raise ValueError(f"channels {first}..{first + width} do not lie in a head of {d}")
    lane = np.arange(width)
    if interleaved:
        leads, other = lane % 2 == 0, lane ^ 1
    else:
        leads, other = lane < width // 2, (lane + width // 2) % width
    swap = np.zeros((d, d), np.float32)
    swap[first + other, first + lane] = np.where(leads, -1.0, 1.0)
    return swap


def _angles(positions: jax.Array, dim: int, base: float) -> jax.Array:
    """``[*pos_shape, dim/2]`` rotation angles for head dim ``dim``."""
    inv_freq = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    return positions.astype(jnp.float32)[..., None] * inv_freq


def rotary_tables(positions: jax.Array, d: int, *, base: float, interleaved: bool = False,
                  channels: tuple[int, int] | None = None) -> tuple[jax.Array, jax.Array]:
    """``(C, S)``, float32 ``[*pos_shape, d]``: the cos and the sin of each channel's
    angle, one and zero over channels that do not turn. The ``width/2`` angles are the
    older form's; a pair's two channels read the same one."""
    first, width = channels or (0, d)
    ang = _angles(positions, width, base)
    both = (lambda t: jnp.repeat(t, 2, axis=-1)) if interleaved else (
        lambda t: jnp.concatenate([t, t], axis=-1))
    outside = [(0, 0)] * positions.ndim + [(first, d - first - width)]
    return (jnp.pad(both(jnp.cos(ang)), outside, constant_values=1.0),
            jnp.pad(both(jnp.sin(ang)), outside))


def rotation_form(shape, dtype, *, interleaved: bool = False,
                  channels: tuple[int, int] | None = None) -> str:
    """The form ``apply_rotary`` takes for an operand of this shape and dtype, chosen from
    what the function sees and from nothing else; the ``compile`` event's
    ``attention.rotation`` (``HybridLM.rotary_plan``) says it.

    ``"permutation"``: ``x·C + (x P)·S`` with ``P`` on the MXU, one fusion that reads the
    head once and writes it once. It is the one form: alone on one v5e (builder, PR 48,
    ``bench_results/hw_pr48/rotary_alone.jsonl``: device ms of the forward pass with its
    pull-back, bf16; the older slices / this form / a lane roll in a Pallas kernel, beside
    twice the tensor's read-and-write at 819 GB/s) it reads

        [1, 32768, 16, 128]   evabyte q, k      5.76 / 2.11 / 2.57   (0.66)
        [16, 784, 8, 128]     lm_train_b16 q    0.58 / 0.19 / 0.34   (0.13)
        [16, 784, 2, 128]     lm_train_b16 k    0.12 / 0.06 / 0.22   (0.03)
        [4, 8192, 32, 64]     lfm2 q            3.49 / 1.18 / 2.51   (0.66)
        [4, 8192, 8, 64]      lfm2 k            0.54 / 0.25 / 0.41   (0.16)
        [2, 8192, 16, 256]    qwen3_next q      4.02 / 2.05 / 2.53   (0.66)  first 64
        [2, 8192, 2, 256]     qwen3_next k      0.40 / 0.39 / 0.28   (0.08)  first 64
        [2, 8192, 32, 192]    kanana2 q         1.90 / 1.86 / 3.85   (0.98)  last 64, interleaved
        [2, 8192, 1, 64]      kanana2 key       0.03 / 0.02 / --     (0.01)  interleaved
        [1, 8192, 5, 128]     falcon_h1 q       0.10 / 0.08 / 0.17   (0.05)
        [1, 8192, 1, 128]     falcon_h1 k       0.05 / 0.07 / 0.04   (0.01)

    so it beats or draws with the slices everywhere but on ``falcon_h1``'s one key head
    (0.02 ms a call), and the roll kernel wins only on the two smallest key tensors. Inside
    ``evabyte_train_32k``'s step (same seed, traced) the slices take 67.3 ms of rotation a
    step, this form 31.9, the roll kernel 38.6 and 23 ms more of feed-forward beside it (a
    ``pallas_call`` fixes its operand's layout); the step goes 1293.1 → 1233.1 ms. The
    arguments stay so that a shape which wants another form can be given one here.
    """
    del shape, dtype, interleaved, channels
    return "permutation"


def _turned(x, positions, base, interleaved, channels, direction):
    """``x·C + (x P)·(direction · S)`` in float32, rounded once to ``x``'s dtype."""
    d = x.shape[-1]
    swap = _partners(d, *(channels or (0, d)), bool(interleaved))    # refuses a misfit first
    cos, sin = rotary_tables(positions, d, base=base, interleaved=interleaved,
                             channels=channels)
    if positions.ndim:                                # [S] → broadcast over H
        cos, sin = cos[..., :, None, :], sin[..., :, None, :]
    if x.dtype != jnp.float32:
        # Without it the compiler takes the cast to float32 into whatever made ``x`` (a
        # projection), which then writes the head twice, and a transposing float32 copy
        # feeds this pass (PERF.md §6, PR 48): behind the barrier the head is written
        # once, in its own dtype, and read here once.
        x = jax.lax.optimization_barrier(x)
    partner = jnp.matmul(x, jnp.asarray(swap, x.dtype), precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * cos + partner * (direction * sin)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rotate(x, positions, base, interleaved, channels):
    return _turned(x, positions, base, interleaved, channels, 1.0)


def _rotate_fwd(x, positions, base, interleaved, channels):
    return _rotate(x, positions, base, interleaved, channels), positions


def _rotate_bwd(base, interleaved, channels, positions, g):
    nothing = (np.zeros(positions.shape, jax.dtypes.float0)
               if jnp.issubdtype(positions.dtype, jnp.integer) else jnp.zeros_like(positions))
    return _turned(g, positions, base, interleaved, channels, -1.0), nothing


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def apply_rotary(x: jax.Array, positions: jax.Array, *, base: float = 10000.0,
                 interleaved: bool = False,
                 channels: tuple[int, int] | None = None) -> jax.Array:
    """Rotate ``x: [..., S, H, D]`` by per-position angles (``positions: [S]`` or a
    scalar for single-token decode on ``[..., H, D]``).

    Half-split layout (GPT-NeoX style): the first D/2 dims pair with the last D/2 —
    ``x1' = x1·cos − x2·sin``, ``x2' = x2·cos + x1·sin``. ``interleaved``: dims ``2i`` and
    ``2i + 1`` pair instead, each pair left in place. ``channels = (first, width)``: those
    of the head's channels turn, paired among themselves, and the rest come back as they
    are. Runs in f32 and casts back. ``positions`` carry no gradient.
    """
    return _rotate(x, jnp.asarray(positions), float(base), bool(interleaved),
                   channels and tuple(map(int, channels)))
