"""The rotation with the partner channel read by a lane roll, as a Pallas kernel over
``x [B, S, H, D]`` flat as ``[B, S, H·D]``: the third form ISSUE 48 asked to be timed alone
(rotary_alone.py beside this file). It is not in ``ops/rotary.py``: alone on the chip it lost to the permutation form at eight of the
ten shapes it takes (rotary_alone.jsonl), and inside evabyte_train_32k's step it left the rotation
at 38.6 ms for the permutation's 31.9 and slowed the feed-forward beside it by 23 ms a step.
``roll_turn(x, positions, base=, interleaved=, channels=)`` is the forward pass; the backward is
the same call at the negated angle, as ``ops.rotary`` does it."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from csed_514_project_distributed_training_using_pytorch_tpu.ops.rotary import (
    _partners, rotary_tables)

LANES = 128
BLOCK_ELEMENTS = 512 * 1024       # of a block of the roll kernel: 1 MiB of bfloat16 in, 1 out


def _interpret() -> bool:
    """Compiled on TPU; interpret mode on CPU (the test platform)."""
    return jax.default_backend() != "tpu"


def roll_plan(shape, interleaved: bool = False, channels=None):
    """How the roll kernel takes ``x [B, S, H, D]``, flat as ``[B, S, H·D]``, or None where
    it cannot: a partner must lie in its channel's own tile of 128 lanes, and the heads
    must fill whole tiles. ``(rows, period, shift, turning)``: rows of a block, the
    tables' period in lane tiles, the partner's distance in lanes, which tiles of a period
    hold a channel that turns."""
    if len(shape) != 4:
        return None
    _, s, h, d = shape
    first, width = channels or (0, d)
    swap = _partners(d, first, width, bool(interleaved))
    sign = swap.sum(axis=0)
    period = int(np.lcm(d, LANES)) // LANES
    if (h * d) % (period * LANES):
        return None
    heads = period * LANES // d
    lane = np.arange(heads * d)
    partner = (lane // d) * d + np.abs(swap).argmax(axis=0)[lane % d]
    turns = np.tile(sign != 0, heads)
    if np.any((partner // LANES != lane // LANES) & turns):
        return None
    rows = next((r for r in (512, 256, 128, 64, 32, 16)
                 if s % r == 0 and r * h * d <= BLOCK_ELEMENTS), s)
    return (rows, period, 1 if interleaved else width // 2,
            tuple(bool(t.any()) for t in turns.reshape(period, LANES)))


def _roll_kernel(*refs, shift: int, period: int, turning: tuple):
    """One block ``[rows, H·D]`` of positions by lanes, a tile of 128 lanes at a time:
    ``x·C + roll(x, shift)·S⁺ + roll(x, −shift)·S⁻``, where ``S⁺`` is the sin of the
    channels whose partner lies ``shift`` lanes below them and ``S⁻`` of those whose
    partner lies above, zero elsewhere, so a channel sums two products as in every other
    form. At ``shift`` 64 the two rolls are one roll and the two tables one."""
    x_ref, c_ref, *s_refs, o_ref = refs
    roll = (lambda v, by: jnp.roll(v, by, axis=1)) if _interpret() else (
        lambda v, by: pltpu.roll(v, by, 1))
    for tile in range(x_ref.shape[-1] // LANES):
        at, of = pl.ds(tile * LANES, LANES), pl.ds((tile % period) * LANES, LANES)
        if not turning[tile % period]:
            o_ref[:, at] = x_ref[:, at]
            continue
        x = x_ref[:, at].astype(jnp.float32)
        out = x * c_ref[:, of] + roll(x, shift) * s_refs[0][:, of]
        if len(s_refs) > 1:
            out = out + roll(x, LANES - shift) * s_refs[1][:, of]
        o_ref[:, at] = out.astype(o_ref.dtype)


def _roll_turn(x, cos, sin, sign, plan):
    """``cos``, ``sin`` ``[S, D]`` with the pair's sign on the sin, ``sign`` the sign alone."""
    rows, period, shift, turning = plan
    b, s, h, d = x.shape
    heads = period * LANES // d
    tables = [cos, sin] if 2 * shift == LANES else [
        cos, jnp.where(sign > 0, sin, 0.0), jnp.where(sign < 0, sin, 0.0)]
    tables = [jnp.tile(t, (1, heads)) for t in tables]
    block = pl.BlockSpec((None, rows, h * d), lambda i, j: (i, j, 0))
    table = pl.BlockSpec((rows, period * LANES), lambda i, j: (j, 0))
    out = pl.pallas_call(
        functools.partial(_roll_kernel, shift=shift, period=period, turning=turning),
        name="rotary_roll", interpret=_interpret(), grid=(b, s // rows),
        in_specs=[block] + [table] * len(tables), out_specs=block,
        out_shape=jax.ShapeDtypeStruct((b, s, h * d), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
    )(x.reshape(b, s, h * d), *tables)
    return out.reshape(x.shape)


def roll_turn(x, positions, *, base, interleaved=False, channels=None, direction=1.0):
    d = x.shape[-1]
    plan = roll_plan(x.shape, interleaved, channels)
    if plan is None:
        raise ValueError(f"the roll kernel does not take {x.shape}")
    sign = _partners(d, *(channels or (0, d)), bool(interleaved)).sum(axis=0)
    cos, sin = rotary_tables(positions, d, base=base, interleaved=interleaved, channels=channels)
    return _roll_turn(x, cos, direction * sin * sign, sign, plan)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def roll_rotary(x, positions, base, interleaved, channels):
    return roll_turn(x, positions, base=base, interleaved=interleaved, channels=channels)


roll_rotary.defvjp(
    lambda x, positions, base, interleaved, channels: (
        roll_rotary(x, positions, base, interleaved, channels), None),
    lambda positions, base, interleaved, channels, _, g: (
        roll_turn(g, positions, base=base, interleaved=interleaved, channels=channels,
                  direction=-1.0),))
