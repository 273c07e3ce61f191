"""Collective wrappers.

The reference's communication backend is copy+sum through the engine
(intra-node, comm.h) and ps-lite ZPush/ZPull (inter-node, kvstore_dist.h).
Here every collective is an XLA collective over the mesh: these wrappers
are the thin naming layer used inside ``shard_map``-ped functions (outside
jit, they fall back to host equivalents so the same code runs everywhere).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def shard_map(f=None, **kwargs):
    """``jax.shard_map`` in both call styles: decorator factory
    (``@shard_map(mesh=...)``) and direct (``shard_map(fn, mesh=...)``)."""
    if f is None:
        return lambda fn: jax.shard_map(fn, **kwargs)
    return jax.shard_map(f, **kwargs)


def axis_size(axis_name):
    """Size of a mesh axis from inside shard_map."""
    return lax.axis_size(axis_name)


def all_reduce(x, axis_name="data", op="sum"):
    """psum/pmean/pmax over a mesh axis (inside shard_map/jit)."""
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    raise ValueError(f"unknown op {op}")


def all_gather(x, axis_name="data", axis=0, tiled=True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name="data", axis=0):
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis,
                            tiled=True)


def ppermute(x, axis_name, perm):
    """Neighbor exchange — the primitive under ring attention / pipeline."""
    return lax.ppermute(x, axis_name, perm)


def barrier(name="barrier"):
    """Host-level barrier across processes (DCN)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)
