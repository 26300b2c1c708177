"""Mesh partitioning for the Pallas kernels.

Mosaic custom calls cannot be auto-partitioned: under any sharded mesh
(dp batch sharding, tp head sharding) GSPMD refuses with "Mosaic
kernels cannot be automatically partitioned.  Please wrap the call in a
shard_map."  Every kernel here is embarrassingly parallel over its
*batch-like* dims (flash attention over batch x heads, the fused
matmul/conv kernels over rows/images), so each call site does exactly
what the error asks: wraps the kernel in a trace-time ``shard_map``
manual over the mesh axes that shard those dims, leaving every other
axis auto so the surrounding layer math still partitions via GSPMD.
Cross-row reduction outputs (BatchNorm ssum/ssq) are ``psum``-ed over
the manual axes inside the body, so the sharded result is bit-identical
in structure to the unsharded one; shard_map's transpose then yields
the distributed backward (gradient psums for replicated weights) for
free.

``jax.experimental.custom_partitioning`` would be the declarative
alternative, but its partition callbacks cannot run under deviceless
AOT compilation ("Custom emitter for CustomSPMDPartitioning not
found"), which would break tools/tpu_aot_check.py — the between-chip-
windows gate this repo relies on.  shard_map lowers fine there (the
pipeline schedule proved it in round 4).

Mesh discovery at trace time (:func:`current_kernel_mesh`):

* inside a ``shard_map`` body the ambient abstract mesh
  (``jax.sharding.get_abstract_mesh()``) reports which axes are
  already Manual — the kernel may nest a shard_map over the remaining
  Auto axes only (e.g. flash over ``model`` inside a pipeline stage
  whose ``pipe``/``data`` are manual), and a fully-manual region
  (ring/Ulysses bodies) yields no candidates, so the kernel runs as a
  plain per-device call;
* under plain ``jit`` no region is being traced — the engine
  (``build_dp_train_step``) publishes its mesh via
  :func:`kernel_mesh_scope` around the traced step instead.

This is the TPU analog of how the reference's fused mkldnn primitives
stayed usable under its data-parallel engine: each worker ran the
primitive on its partition and the engine reduced the statistics
(nn/mkldnn/*, parameters/AllReduceParameter.scala); here the same
reduction is an ICI collective placed by shard_map.
"""
from __future__ import annotations

import contextlib
import contextvars
import math
from typing import Callable, Optional, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec as P


_KERNEL_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "bigdl_tpu_kernel_mesh", default=None)


@contextlib.contextmanager
def kernel_mesh_scope(mesh):
    """Publish ``mesh`` to Pallas kernels traced in this scope (the
    engine wraps its train/eval step bodies in this)."""
    token = _KERNEL_MESH.set(mesh)
    try:
        yield
    finally:
        _KERNEL_MESH.reset(token)


def current_kernel_mesh():
    """-> (mesh, shardable_axes, remaining_axes) or None at trace time.

    ``mesh`` is the ambient abstract mesh inside a ``shard_map`` body
    (nested kernel shard_maps must be built on it, not on a concrete
    ``Mesh``), else the engine-published concrete mesh.

    ``shardable_axes``: mesh axes a kernel may shard its batch dims
    over (size > 1, not already manual in the ambient region).
    ``remaining_axes``: EVERY axis not already manual — Mosaic custom
    calls only lower when the surrounding region is manual over ALL
    mesh axes (jax/_src/tpu_custom_call.py raises on partial-manual),
    so a kernel shard_map must take all of these, sharding over the
    shardable ones and replicating along the rest.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        mesh = _KERNEL_MESH.get()
        if mesh is None:
            return None
        manual = frozenset()
    else:
        manual = frozenset(mesh.manual_axes)
    remaining = frozenset(n for n in mesh.axis_names if n not in manual)
    avail = frozenset(n for n in remaining if mesh.shape[n] > 1)
    return mesh, avail, remaining


def shard_kernel_call(
    fn: Callable,
    args: Sequence,
    dim_axes: Sequence[Tuple[Optional[str], ...]],
    out_dim_axes: Sequence[Tuple[Optional[str], ...]],
    reduce_outputs: Tuple[int, ...] = (),
    single_output: bool = False,
):
    """Run ``fn(*args)`` under a kernel shard_map, or plainly when no
    mesh axis applies.

    ``dim_axes[i][d]``: the mesh axis that conventionally shards dim d
    of operand i (None = never sharded into the kernel).  An axis is
    kept only when it is available (see :func:`current_kernel_mesh`)
    and divides the dim; otherwise that dim enters the kernel
    replicated — correct, GSPMD inserts the gather.  ``out_dim_axes``
    mirrors this for outputs; ``reduce_outputs`` are cross-row
    reductions, psum'd over ALL kept axes and returned replicated.
    """
    # reduce_outputs would be silently ignored on the single-output
    # path (the body returns before the psum loop) — refuse loudly
    assert not (single_output and reduce_outputs), (
        "shard_kernel_call: reduce_outputs is not supported with "
        "single_output=True")
    info = current_kernel_mesh()
    if info is None:
        return fn(*args)
    mesh, avail, remaining = info
    # fully-manual ambient region (ring/Ulysses bodies): the kernel is
    # already a plain per-device call
    if not remaining:
        return fn(*args)
    # single-device mesh under plain jit: ShardingContext(num_devices=1)
    # lowers as-is; inside a partially-manual region we must still wrap
    # (Mosaic refuses partial-manual even over size-1 auto axes)
    ambient_manual = len(remaining) < len(mesh.axis_names)
    if not ambient_manual and \
            math.prod(mesh.shape[a] for a in remaining) == 1:
        return fn(*args)

    def keep(axis, dim_size):
        return (axis is not None and axis in avail
                and dim_size % mesh.shape[axis] == 0)

    kept = frozenset(
        a for x, dims in zip(args, dim_axes)
        for d, a in enumerate(dims) if keep(a, x.shape[d]))

    def spec(dims):
        return P(*[a if a in kept else None for a in dims])

    in_specs = tuple(spec(dims) for dims in dim_axes)
    out_specs_l = [
        P() if j in reduce_outputs else spec(dims)
        for j, dims in enumerate(out_dim_axes)
    ]
    out_specs = out_specs_l[0] if single_output else tuple(out_specs_l)

    def body(*local_args):
        out = fn(*local_args)
        if single_output:
            return out
        out = list(out)
        if kept:  # without sharded dims the local result is global
            for j in reduce_outputs:
                out[j] = jax.lax.psum(out[j], tuple(sorted(kept)))
        return tuple(out)

    # manual over EVERY remaining axis (the Mosaic full-manual rule),
    # sharded over the kept ones, replicated along the rest
    # (an ambient abstract mesh is taken from the context, mesh=None)
    return jax.shard_map(
        body, mesh=mesh if isinstance(mesh, jax.sharding.Mesh) else None,
        in_specs=in_specs, out_specs=out_specs,
        axis_names=remaining, check_vma=False,
    )(*args)
