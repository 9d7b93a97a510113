"""Named-axis collectives — the TPU replacement for the reference's comm stack.

Reference: `src/kvstore/comm.h:43-103` (`Comm::Reduce/Broadcast`),
`kvstore_nccl.h:285-402` (ncclReduce/ncclBcast), ps-lite push/pull
(`kvstore_dist.h`).  Here every collective is an XLA op over a named mesh axis
inside `jax.shard_map` (or under `pjit`, where GSPMD inserts them implicitly).
These wrappers exist so framework code has one audited vocabulary, and so the
KVStore facade (`mxnet_tpu/kvstore.py`) can speak collectives without
importing lax everywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map

__all__ = ["allreduce", "allgather", "reduce_scatter", "ppermute_shift",
           "all_to_all", "axis_index", "axis_size", "pmean", "broadcast",
           "shard_map"]


def allreduce(x, axis_name, op="sum"):
    """psum/pmax/pmin over a mesh axis (reference: kvstore push+pull)."""
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    raise ValueError("unknown reduce op %r" % op)


def pmean(x, axis_name):
    return lax.pmean(x, axis_name)


def allgather(x, axis_name, axis=0, tiled=True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, axis=0):
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def ppermute_shift(x, axis_name, shift=1):
    """Rotate shards around a ring (the ring-attention primitive)."""
    n = lax.psum(1, axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name, split_axis, concat_axis, tiled=True):
    return lax.all_to_all(x, axis_name, split_axis, concat_axis, tiled=tiled)


def axis_index(axis_name):
    return lax.axis_index(axis_name)


def axis_size(axis_name):
    return lax.psum(1, axis_name)


def broadcast(x, axis_name, src=0):
    """Every shard gets shard ``src``'s value (reference: Comm::Broadcast)."""
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)
