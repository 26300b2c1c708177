"""Pytree <-> flat-vector utilities.

The reference flattens all parameters of a model into ONE contiguous 1-D
tensor so the distributed optimizer can update per-partition slices
(``AllReduceParameter`` keys weight/grad slices by partition id,
parameters/AllReduceParameter.scala:155-328; replicas share the flat
storage, utils/Util.scala:95).  On TPU, parameters stay as sharded
pytrees; the flat view is still needed for (a) sharded-optimizer (ZeRO-1)
slice semantics, (b) global-norm gradient clipping parity, and (c) flat
checkpoint formats.
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

tree_map = jax.tree_util.tree_map


def tree_size(tree: Any) -> int:
    """Total number of scalar elements in the pytree."""
    return sum(x.size for x in jax.tree_util.tree_leaves(tree))


def tree_zeros_like(tree: Any) -> Any:
    return tree_map(jnp.zeros_like, tree)


def cast_floating(tree: Any, dtype) -> Any:
    """Cast the floating-point leaves of ``tree`` to ``dtype``; integer
    leaves (token ids, labels) pass through."""
    return tree_map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def ravel_pytree(tree: Any) -> Tuple[jnp.ndarray, Callable[[jnp.ndarray], Any]]:
    """Flatten ``tree`` to one 1-D array; return it and an unflattener.

    The unflattener restores the exact structure/dtypes/shapes.  This is
    the TPU analog of the reference's ``Module.getParameters()`` compact
    storage (nn/abstractnn/AbstractModule.scala — parameters flattened to
    a single Storage shared by all replicas).
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    sizes = [int(jnp.size(l)) for l in leaves]
    if leaves:
        flat = jnp.concatenate(
            [jnp.ravel(l).astype(jnp.result_type(*dtypes)) for l in leaves]
        )
    else:
        flat = jnp.zeros((0,), jnp.float32)

    def unravel(vec: jnp.ndarray) -> Any:
        out = []
        offset = 0
        for shape, dtype, size in zip(shapes, dtypes, sizes):
            out.append(jnp.reshape(vec[offset : offset + size], shape).astype(dtype))
            offset += size
        return jax.tree_util.tree_unflatten(treedef, out)

    return flat, unravel


def global_norm(tree: Any) -> jnp.ndarray:
    """L2 norm over every element of the pytree (for clipping / LARS)."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros(())
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))
