"""Collective/p2p primitives over the mesh.

The reference exercises three distributed primitives (SURVEY.md §5 "communication backend"):
TCP-store rendezvous, DDP's bucketed ring all-reduce (``src/train_dist.py:63,83``), and
blocking point-to-point ``dist.send``/``dist.recv`` (``src/run1.py:13,16``). Rendezvous lives
in ``parallel.mesh``; the all-reduce is normally *implicit* — XLA inserts it from sharding
annotations inside the compiled train step — but explicit wrappers are provided here for the
smoke test and for ad-hoc use. All are ``shard_map``-wrapped XLA collectives: the transport
(ICI vs DCN) is the compiler's/runtime's job, never a user-visible backend string.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def ring_pass(mesh: Mesh, values: jax.Array, *, axis_name: str = "data",
              shift: int = 1) -> jax.Array:
    """Rotate per-device values one step around the mesh axis ring.

    The ``lax.ppermute`` analog of the reference's rank0→rank1 ``dist.send``/``dist.recv``
    smoke test (``src/run1.py:8-17``): device ``i``'s value lands on device
    ``(i + shift) % n``. ``values`` must have leading dim == mesh axis size (one value per
    device); returns the rotated array, which callers can check against the expected
    permutation to validate cross-device/host connectivity.
    """
    n = mesh.shape[axis_name]
    perm = [(i, (i + shift) % n) for i in range(n)]

    @partial(shard_map, mesh=mesh, in_specs=P(axis_name), out_specs=P(axis_name),
             check_vma=False)
    def _shift(x):
        return lax.ppermute(x, axis_name, perm)

    return _shift(values)


def all_reduce_sum(mesh: Mesh, values: jax.Array, *, axis_name: str = "data") -> jax.Array:
    """Explicit all-reduce-sum of per-device leading-dim shards (the gloo ring-allreduce
    analog, ≙ what DDP's Reducer does per gradient bucket at ``src/train_dist.py:83``).

    Provided for diagnostics; the train step never calls this — its all-reduce is fused in by
    XLA from sharding annotations (see ``parallel/data_parallel.py``).
    """

    @partial(shard_map, mesh=mesh, in_specs=P(axis_name), out_specs=P(None),
             check_vma=False)
    def _sum(x):
        return lax.psum(jnp.sum(x, axis=0, keepdims=True), axis_name)

    return _sum(values)[0]
