"""How a reference multiplies: the stated precision, or the step below it.

``highest`` is the reference proper. The others are the *controls*: the same
mathematics with every matmul operand rounded to a narrower type first, which
is what a later PR would be tempted to do. ``correct`` has to fail on them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


ROUNDERS = {
    "highest": lambda x: x,
    "fp8": lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32),
}


def matmul(precision: str):
    """``mm(a, b)``: contract a's last axis with b's first, operands rounded by
    ``precision``, products accumulated in float32 at ``highest``."""
    r = ROUNDERS[precision]

    def mm(a, b):
        return jnp.tensordot(r(a), r(b), axes=1,
                             precision=jax.lax.Precision.HIGHEST)

    return mm


def einsum(precision: str):
    r = ROUNDERS[precision]

    def es(spec, a, b):
        return jnp.einsum(spec, r(a), r(b), precision=jax.lax.Precision.HIGHEST)

    return es
