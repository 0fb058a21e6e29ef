"""All-to-all (Ulysses-style) sequence/context parallelism.

Beyond-parity capability (the reference is DP-only — SURVEY.md §2c — and has no
attention op at all; reference ``src/model.py:4-22`` is a fixed-28×28 CNN): the second
of the two canonical sequence-parallel attention schedules, complementing the ring
family in ``parallel/ring_attention.py``.

Where ring attention keeps queries resident and rotates K/V blocks hop-by-hop
(n-1 ``ppermute`` rounds, online-softmax merges), the all-to-all schedule re-shards
ONCE: activations arrive sequence-sharded ``[B, S/n, H, D]``, one ``lax.all_to_all``
converts them to head-sharded ``[B, S, H/n, D]`` — every device now holds the FULL
sequence for its own head group — the unmodified single-device attention op runs
locally, and a second all-to-all restores the sequence sharding. Attention is
independent per head, so the result is exactly the dense oracle with no online-softmax
merge math at all.

Trade-offs (why both schedules exist — the published DeepSpeed-Ulysses vs
ring/blockwise comparison, re-derived for TPU):

- **Communication**: 2 all-to-alls of the activations per attention call vs the ring's
  n-1 K/V ppermute rounds. On a TPU mesh XLA lowers ``all_to_all`` onto ICI directly;
  for moderate n the single re-shard moves less data than the full ring rotation and
  has no per-hop latency chain.
- **Composability**: the local op is arbitrary — causal masking needs no global-position
  plumbing or hop-case analysis (the device sees the whole sequence), and the Pallas
  flash kernels drop in unchanged (``use_flash=True``), giving O(S·D) local memory.
- **Limits**: parallelism is bounded by the head count (``H_local % n == 0`` required),
  and peak activation memory holds the full S per device for the attention input —
  the ring never materializes full-S activations, so for the longest contexts at small
  head counts the ring (and zig-zag ring-of-flash) remains the scaling path.

Differentiability is structural: ``all_to_all`` transposes to the inverse all-to-all,
and the local op is the already-differentiable dense einsum or flash custom-VJP — no
custom VJP needed here. Pinned against ``ops.full_attention`` forward AND gradients in
``tests/test_ulysses.py``.

No backend strings, no explicit sends: the collective schedule is the compiler's job
(same philosophy as ``parallel/collectives.py``).
"""

from __future__ import annotations

from functools import partial

import jax
from jax import lax, shard_map
from jax.sharding import Mesh

from csed_514_project_distributed_training_using_pytorch_tpu import ops
from csed_514_project_distributed_training_using_pytorch_tpu.parallel.ring_attention import (
    _qkv_spec,
)


def ulysses_attention(mesh: Mesh, q: jax.Array, k: jax.Array, v: jax.Array, *,
                      axis_name: str = "seq", causal: bool = False,
                      use_flash: bool = False, window: int = 0) -> jax.Array:
    """Sequence-parallel attention via head-scatter all-to-all.

    ``q, k, v: [B, S, H, D]`` with S sharded over ``axis_name``; drop-in equivalent of
    ``ops.full_attention`` (same signature modulo the mesh), callable under ``jax.jit``
    (the mesh is static). Requirements: ``S % n == 0`` and the per-device head count
    must divide by ``n`` (heads are what the all-to-all scatters). With
    ``use_flash=True`` the local op is the Pallas flash kernel, which additionally
    needs ``S % 128 == 0`` (the full sequence is local after the first all-to-all).

    On a composed mesh the batch/head dims co-shard over ``data``/``model``
    (``_qkv_spec``, shared with the ring family) — the head-divisibility requirement
    then applies to the model-sharded local head count ``H / model_axis``.

    ``window=W`` (r4) is sliding-window attention: the device holds the full
    sequence after the first all-to-all, so the band needs no hop-offset plumbing —
    it binds straight into the local op (the banded flash grid or the dense band
    mask), same semantics as ``ops.full_attention(window=W)``.
    """
    n = mesh.shape[axis_name]
    b, s, h, d = q.shape
    if s % n:
        raise ValueError(
            f"sequence length {s} not divisible by mesh axis {axis_name!r} size {n} "
            f"— ulysses attention shards the sequence evenly")
    spec = _qkv_spec(mesh, q.shape, axis_name)
    h_local = h if spec[2] is None else h // mesh.shape[spec[2]]
    if h_local % n:
        raise ValueError(
            f"ulysses attention scatters heads over the {axis_name!r} axis: local "
            f"head count {h_local} must divide by its size {n} (use ring attention "
            f"when heads are scarcer than sequence shards)")
    if use_flash:
        from csed_514_project_distributed_training_using_pytorch_tpu.ops import (
            pallas_attention as pa,
        )
        if s % pa.BLOCK:
            raise ValueError(
                f"ulysses attention with use_flash=True runs the flash kernel over "
                f"the full sequence locally — S must divide by BLOCK = {pa.BLOCK}, "
                f"got {s}")
        local_op = pa.flash_attention
    else:
        local_op = ops.full_attention
    if window:
        local_op = partial(local_op, window=window)

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
             check_vma=False)
    def _ulysses(ql, kl, vl):
        # [B_l, S/n, H_l, D] → [B_l, S, H_l/n, D]: head chunk i lands on device i,
        # sequence pieces concatenate in source-device (= global position) order.
        gather_seq = lambda x: lax.all_to_all(x, axis_name, split_axis=2,
                                              concat_axis=1, tiled=True)
        # Inverse: sequence chunk i returns to device i, head pieces concatenate in
        # source order, restoring the original head layout.
        scatter_seq = lambda x: lax.all_to_all(x, axis_name, split_axis=1,
                                               concat_axis=2, tiled=True)
        out = local_op(gather_seq(ql), gather_seq(kl), gather_seq(vl),
                       causal=causal)
        return scatter_seq(out)

    return _ulysses(q, k, v)


def make_ulysses_attention_fn(mesh: Mesh, *, axis_name: str = "seq",
                              use_flash: bool = False, window: int = 0):
    """Bind a mesh into a ``(q, k, v, *, causal) -> out`` callable with
    ``ops.full_attention``'s exact signature — the injection point for
    ``models/transformer.py``'s pluggable ``attention_fn``, mirroring
    ``make_ring_attention_fn``. ``window`` binds sliding-window masking into the
    local op (see ``ulysses_attention``)."""

    def attention_fn(q, k, v, *, causal: bool = False):
        return ulysses_attention(mesh, q, k, v, axis_name=axis_name,
                                 causal=causal, use_flash=use_flash,
                                 window=window)

    return attention_fn
