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
last 64 of 192) hands that part alone: the angles are over the width it hands.

TPU notes: the rotation is a fused multiply-add on the VPU (cos/sin tables are
``[S, D/2]`` f32, computed inline — XLA hoists them out of the scan); no gather, no
complex numbers (the half-split formulation avoids interleaved strides; the interleaved
one reads each lane's neighbour through one ``[D, D]`` signed permutation on the MXU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _angles(positions: jax.Array, dim: int, base: float) -> jax.Array:
    """``[*pos_shape, dim/2]`` rotation angles for head dim ``dim``."""
    if dim % 2:
        raise ValueError(f"RoPE needs an even head dim, got {dim}")
    inv_freq = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    return positions.astype(jnp.float32)[..., None] * inv_freq


def apply_rotary(x: jax.Array, positions: jax.Array, *, base: float = 10000.0,
                 interleaved: bool = False) -> jax.Array:
    """Rotate ``x: [..., S, H, D]`` by per-position angles (``positions: [S]`` or a
    scalar for single-token decode on ``[..., H, D]``).

    Half-split layout (GPT-NeoX style): the first D/2 dims pair with the last D/2 —
    ``x1' = x1·cos − x2·sin``, ``x2' = x2·cos + x1·sin``. ``interleaved``: dims ``2i`` and
    ``2i + 1`` pair instead, each pair left in place. Runs in f32 and casts back.
    """
    d = x.shape[-1]
    ang = _angles(positions, d, base)                 # [..., D/2]
    if positions.ndim:                                # [S] → broadcast over H
        ang = ang[..., :, None, :]                    # [S, 1, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    if interleaved:
        # Lane j's partner is lane j ^ 1, negated for the pair's first lane: one product
        # with a signed permutation, exact in any dtype (an output is one input). Written
        # as lane rolls and a select, the compiler kept each roll's two slices as arrays
        # of their own, five float32 passes over the rotated channels.
        lanes = np.arange(d)
        swap = np.zeros((d, d), np.float32)
        swap[lanes ^ 1, lanes] = np.where(lanes % 2 == 0, -1.0, 1.0)
        partner = jnp.matmul(x, jnp.asarray(swap, x.dtype),
                             precision=jax.lax.Precision.HIGHEST).astype(jnp.float32)
        cos, sin = jnp.repeat(cos, 2, axis=-1), jnp.repeat(sin, 2, axis=-1)
        return (xf * cos + partner * sin).astype(x.dtype)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
