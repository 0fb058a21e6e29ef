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

TPU notes: the rotation is a fused multiply-add on the VPU (cos/sin tables are
``[S, D/2]`` f32, computed inline — XLA hoists them out of the scan); no gather, no
complex numbers (the half-split formulation avoids interleaved strides).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _angles(positions: jax.Array, dim: int, base: float) -> jax.Array:
    """``[*pos_shape, dim/2]`` rotation angles for head dim ``dim``."""
    if dim % 2:
        raise ValueError(f"RoPE needs an even head dim, got {dim}")
    inv_freq = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    return positions.astype(jnp.float32)[..., None] * inv_freq


def apply_rotary(x: jax.Array, positions: jax.Array, *,
                 base: float = 10000.0) -> jax.Array:
    """Rotate ``x: [..., S, H, D]`` by per-position angles (``positions: [S]`` or a
    scalar for single-token decode on ``[..., H, D]``).

    Half-split layout (GPT-NeoX style): the first D/2 dims pair with the last D/2 —
    ``x1' = x1·cos − x2·sin``, ``x2' = x2·cos + x1·sin``. Runs in f32 and casts back.
    """
    d = x.shape[-1]
    ang = _angles(positions, d, base)                 # [..., D/2]
    if positions.ndim:                                # [S] → broadcast over H
        ang = ang[..., :, None, :]                    # [S, 1, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
