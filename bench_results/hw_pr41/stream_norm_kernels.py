"""RMSNorm of a float32 residual stream, one pass over the rows forward and one backward: the
kernel pair ISSUE 41 asked for, kept here as the record of what was measured and not in the
program. Alone they beat the compiler's norm (step0.py: 1.18 ms forward for 1.89, 2.0 backward
for 2.34); wired into ``HybridLM.normed`` the evabyte cell lost 3.5 % (0.6802 examples/s for
0.7048, PERF.md section 6, PR 41): inside the epoch program the compiler computes a norm's
statistic in the epilogue of the matmul before it and never writes ``h``, which no kernel can do.
In interpret mode on the CPU they equal ``ops.rms_norm`` and the cast to one ulp of bfloat16 and
its vjp to 1e-5 (rows 256 and 1024, widths 128 and 512, either offset, with and without addend).


``models/hybrid_lm.py`` keeps the stream ``[T, d]`` float32 between blocks whose matmuls
run in a narrower dtype (``fp32_residual``). There a norm reads float32 rows and hands the
next matmul rows of the model's dtype:

    h = x (+ addend)        the stream; ``addend`` a mixer's output, in the model's dtype
    r = (mean_d h² + eps)^-½
    u = (h · r · w).astype(dtype)               w = offset + gamma, ``[d]`` float32

``stream_norm_fwd`` computes ``r``, the scale and the cast for a block of rows while it
holds them (and, with an addend, writes ``h`` beside ``u``); ``stream_norm_bwd`` reads
``h``, the cotangent of ``u`` and, where the stream goes on past the norm (``carry``), the
stream's own float32 cotangent, and writes

    dx = dh + r · (gw − h · r² · mean_d(gw · h))    gw = du · w, all float32
    dw = Σ_rows du · h · r                          summed over the grid in a resident tile

so that the stream's cotangent is read and written once a norm. ``r`` is one number a row
and is computed again from ``h``, which the backward pass reads anyway. Statistics, ``dx``
and ``dw`` are float32 whatever ``dtype`` is: the kernels change the order of a row's sum
and nothing else of ``ops.rms_norm`` followed by the cast.

The rows are tiled alone (a block holds whole rows of ``d`` lanes), so ``d`` is a multiple
of 128 and ``T`` of 16 (a bfloat16 tile's rows); ``uses_kernel`` says so, and what it
refuses keeps ``ops.rms_norm``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MIN_ROWS = 16               # a bfloat16 tile's rows
BLOCK_ELEMENTS = 1 << 20    # of a row block: 4 MiB of float32, 256 rows at 4096 lanes
MAX_ROWS = 256
# The backward pass holds three float32 blocks and two narrower ones, each twice, beside
# what it computes with: 60 MiB at 256 x 4096, of the chip's 128.
VMEM_LIMIT = 100 << 20


def _interpret() -> bool:
    """Compiled on TPU; interpret mode on CPU (the test platform)."""
    return jax.default_backend() != "tpu"


def uses_kernel(x, dtype) -> bool:
    """Whether a norm of ``x`` into ``dtype`` runs the kernels: a float32 stream under a
    narrower dtype, whole lanes and whole tiles of rows."""
    rows = math.prod(x.shape[:-1])
    return (x.dtype == jnp.float32 and jnp.dtype(dtype).itemsize < 4
            and x.shape[-1] % LANES == 0 and rows % MIN_ROWS == 0)


def rows_block(rows: int, width: int) -> int:
    """Rows of a grid step: the largest power of two under ``BLOCK_ELEMENTS / width`` and
    ``MAX_ROWS`` that divides ``rows``."""
    cap = max(MIN_ROWS, min(MAX_ROWS, BLOCK_ELEMENTS // width))
    return math.gcd(rows, 1 << (cap.bit_length() - 1))


def _rstd(h, eps: float):
    return jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)


def _fwd_kernel(*refs, eps: float, with_addend: bool):
    if with_addend:
        x_ref, a_ref, w_ref, h_ref, u_ref = refs
        h = x_ref[...] + a_ref[...].astype(jnp.float32)
        h_ref[...] = h
    else:
        x_ref, w_ref, u_ref = refs
        h = x_ref[...]
    u_ref[...] = (h * _rstd(h, eps) * w_ref[...]).astype(u_ref.dtype)


def _bwd_kernel(*refs, eps: float, with_addend: bool, carry: bool):
    h_ref, du_ref = refs[:2]
    w_ref, dx_ref = refs[2 + carry:4 + carry]
    dw_ref = refs[-1]

    @pl.when(pl.program_id(0) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    h, du = h_ref[...], du_ref[...].astype(jnp.float32)
    r = _rstd(h, eps)
    gw = du * w_ref[...]
    dx = r * (gw - h * (r * r * jnp.mean(gw * h, axis=-1, keepdims=True)))
    if carry:
        dx = dx + refs[2][...]
    dx_ref[...] = dx
    if with_addend:
        refs[-2][...] = dx.astype(refs[-2].dtype)
    # eight rows of partial sums, the sublanes of a float32 tile: added without a shuffle
    dw_ref[...] += jnp.sum((du * h * r).reshape(-1, *dw_ref.shape), axis=0)


def _call(kernel, name, in_rows, w, out_shapes):
    """``kernel`` over blocks of rows: the ``[T, d]`` operands ``in_rows`` and every
    ``[T, d]`` output tiled alike, ``w [1, d]`` and any other output held whole."""
    rows, width = in_rows[0].shape
    r = rows_block(rows, width)
    tiled = pl.BlockSpec((r, width), lambda i: (i, 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    return pl.pallas_call(
        kernel, name=name, interpret=_interpret(), grid=(rows // r,),
        in_specs=[tiled] * len(in_rows) + [whole(w.shape)],
        out_specs=[tiled if o.shape == (rows, width) else whole(o.shape)
                   for o in out_shapes],
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             vmem_limit_bytes=VMEM_LIMIT),
    )(*in_rows, w)


def _forward(x, addend, w, *, eps, dtype):
    """``(h, u)``; ``h`` is ``x`` itself without an addend."""
    like = lambda dt: jax.ShapeDtypeStruct(x.shape, dt)
    kernel = functools.partial(_fwd_kernel, eps=eps, with_addend=addend is not None)
    if addend is None:
        return x, _call(kernel, "stream_norm_fwd", [x], w, [like(dtype)])[0]
    return _call(kernel, "stream_norm_fwd", [x, addend], w, [like(jnp.float32), like(dtype)])


def _backward(h, w, dh, du, *, eps, addend_dtype):
    """``(dx, da | None, dw [1, d])``; ``dh`` None where the stream ends at the norm."""
    with_addend, carry = addend_dtype is not None, dh is not None
    dx, *da, dw = _call(
        functools.partial(_bwd_kernel, eps=eps, with_addend=with_addend, carry=carry),
        "stream_norm_bwd", [h, du] + [dh] * carry, w,
        [jax.ShapeDtypeStruct(h.shape, dt)
         for dt in [jnp.float32] + [addend_dtype] * with_addend]
        + [jax.ShapeDtypeStruct((8, h.shape[1]), jnp.float32)])
    return dx, (da[0] if da else None), jnp.sum(dw, axis=0, keepdims=True)


@functools.lru_cache(maxsize=None)
def _make_op(eps: float, dtype, addend_dtype, carry: bool):
    # Jitted halves behind a cached factory, as the attention kernels': every layer calls
    # the same two functions, lowered once a program.
    forward = jax.jit(functools.partial(_forward, eps=eps, dtype=dtype))
    backward = jax.jit(functools.partial(_backward, eps=eps, addend_dtype=addend_dtype))
    out = (lambda h, u: (h, u)) if carry else (lambda h, u: u)

    @jax.custom_vjp
    def op(x, addend, w):
        return out(*forward(x, addend, w))

    def fwd(x, addend, w):
        h, u = forward(x, addend, w)
        return out(h, u), (h, w)

    def bwd(held, cotangents):
        dh, du = cotangents if carry else (None, cotangents)
        return backward(*held, dh, du)

    op.defvjp(fwd, bwd)
    return op


def stream_norm(x, gamma, *, eps: float, dtype, offset: float = 0.0, addend=None,
                carry: bool = False):
    """``ops.rms_norm(x + addend, gamma, eps=eps, offset=offset).astype(dtype)`` of a
    float32 ``x [..., d]`` that ``uses_kernel`` admits, by the kernels. With ``carry`` (or
    an ``addend``, which implies it) ``(h, u)``, ``h = x + addend`` the stream that goes on
    (``x`` itself without an addend): what flows back into ``h`` joins the norm's own
    gradient inside ``stream_norm_bwd``. Without, ``u`` alone: the stream ends here."""
    carry = carry or addend is not None
    flat = lambda a: a.reshape(-1, x.shape[-1])
    w = gamma.astype(jnp.float32)[None]
    if offset:
        w = w + offset
    op = _make_op(float(eps), jnp.dtype(dtype),
                  None if addend is None else jnp.dtype(addend.dtype), carry)
    out = op(flat(x), None if addend is None else flat(addend), w)
    return jax.tree.map(lambda a: a.reshape(x.shape), out)
