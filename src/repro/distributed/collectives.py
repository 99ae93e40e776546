"""Explicit collectives for the cross-pod data-parallel path (shard_map).

Under plain pjit, gradient reductions are GSPMD-inserted and always run at
the accumulation dtype.  For the *cross-pod* hop (slow DCI links) we expose
an explicit quantized all-reduce: int8 payload + per-shard scale, error
feedback handled by the caller (optim.grad_compress).  Used by the
``--grad-compress`` training mode and tested on a host-device mesh.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def quantized_psum(x: jax.Array, axis_name: str) -> jax.Array:
    """All-reduce mean with int8 wire format (inside shard_map)."""
    absmax = jnp.max(jnp.abs(x)) + 1e-12
    scale = absmax / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    # wire payload is int8; sum in int32 to avoid overflow across shards
    total = jax.lax.psum(q.astype(jnp.int32), axis_name)
    scale_sum = jax.lax.psum(scale, axis_name)          # scales are tiny
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    # average of dequantized shards (per-shard scale ~ shared scale regime)
    return (total.astype(jnp.float32) * (scale_sum / n) / n).astype(x.dtype)


def make_quantized_allreduce(mesh: Mesh, axis_name: str = "pod"):
    """Tree-level quantized mean-all-reduce over ``axis_name``."""

    def one(x):
        f = jax.shard_map(
            functools.partial(quantized_psum, axis_name=axis_name),
            mesh=mesh, in_specs=P(axis_name, *([None] * (x.ndim - 1))),
            out_specs=P(None, *([None] * (x.ndim - 1))), check_vma=False)
        return f(x)

    def allreduce(tree: Any) -> Any:
        return jax.tree_util.tree_map(one, tree)

    return allreduce


def collective_wire_bytes(tree, compressed: bool) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    if compressed:
        return sum(l.size + 4 for l in leaves)
    return sum(l.size * l.dtype.itemsize for l in leaves)
