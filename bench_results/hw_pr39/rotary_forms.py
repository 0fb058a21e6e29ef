"""The interleaved rotation of a latent-attention head's last 64 query channels, three ways,
alone on the chip at the cell's shape (q [2, 8192, 32, 192] bf16, the 128 unrotated channels
joined back as `mla_mixer` joins them): `ops.rotary.apply_rotary(interleaved=True)` as
committed (the partner lane through a signed [64, 64] permutation on the MXU), the partner
lane by two lane rolls and a select, and the pairs split by a reshape to [..., 32, 2] and
stacked back; the half-split rotation beside them as the floor. Forward alone and forward
with backward, median of 20 calls. A step runs the forward twice (recomputed) and the
backward once in each of six layers, and the compiler may fuse a form into its neighbours
there, which this does not see.
ROTARY_FORMS_S: a shorter sequence, to rehearse on the CPU.
usage: python rotary_forms.py > rotary_forms.jsonl"""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from csed_514_project_distributed_training_using_pytorch_tpu.ops.rotary import (
    _angles, apply_rotary)

B, S, H, NOPE, PE, BASE = 2, int(os.environ.get("ROTARY_FORMS_S", 8192)), 32, 128, 64, 1e6


def _tables(positions, x):
    ang = _angles(positions, x.shape[-1], BASE)[..., :, None, :]
    return jnp.cos(ang), jnp.sin(ang), x.astype(jnp.float32)


def rolls(x, positions):
    cos, sin, xf = _tables(positions, x)
    even = (jnp.arange(x.shape[-1]) % 2 == 0)
    partner = jnp.where(even, -jnp.roll(xf, -1, axis=-1), jnp.roll(xf, 1, axis=-1))
    cos, sin = jnp.repeat(cos, 2, axis=-1), jnp.repeat(sin, 2, axis=-1)
    return (xf * cos + partner * sin).astype(x.dtype)


def reshape_stack(x, positions):
    cos, sin, xf = _tables(positions, x)
    pairs = xf.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


FORMS = {
    "mxu_permutation (committed)": lambda x, p: apply_rotary(x, p, base=BASE, interleaved=True),
    "lane_rolls_and_select": rolls,
    "reshape_and_stack": reshape_stack,
    "half_split (the other pairing)": lambda x, p: apply_rotary(x, p, base=BASE),
}


def _time(line: dict, forward, loss, q, first: list) -> None:
    for what, fn in (("forward_ms", jax.jit(forward)),
                     ("forward_backward_ms", jax.jit(jax.value_and_grad(loss)))):
        out = jax.block_until_ready(fn(q))
        times = []
        for _ in range(20):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(q))
            times.append(1e3 * (time.perf_counter() - t0))
        line[what] = float(np.median(times))
        if what == "forward_ms" and "half_split" not in line["form"]:
            first.append(np.asarray(out, np.float32))
            line["max_abs_difference_from_committed"] = float(
                np.abs(first[-1] - first[0]).max())
    line["a_step_ms"] = 6 * (line["forward_ms"] + line["forward_backward_ms"])


def main():
    rng = np.random.default_rng(39)
    q = jnp.asarray(rng.standard_normal((B, S, H, NOPE + PE)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((B, S, H, NOPE + PE)), jnp.bfloat16)
    positions, first = jnp.arange(S), []
    for name, turn in FORMS.items():
        def forward(q, turn=turn):
            return jnp.concatenate([q[..., :NOPE], turn(q[..., NOPE:], positions)], axis=-1)

        def loss(q, forward=forward):
            return jnp.sum(forward(q).astype(jnp.float32) * w.astype(jnp.float32))

        line = {"form": name, "device": jax.devices()[0].device_kind}
        try:
            _time(line, forward, loss, q, first)
        except Exception as e:      # a form the compiler refuses does not stop the others
            line["error"] = f"{type(e).__name__}: {e}"[:300]
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
