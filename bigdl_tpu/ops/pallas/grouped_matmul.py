"""Grouped matmul over rows sorted by group - Pallas TPU kernel.

``out[r] = x[r] @ w[g(r)]`` where the rows of ``x`` (m, K) come sorted by
group and group ``g`` holds the next ``counts[g]`` of them: the expert
products of a routed layer (nn/routed.py), ``jax.lax.ragged_dot``'s
contract with bf16 (or f32) operands and f32 accumulation.  Rows past
``counts.sum()`` belong to no group and are never computed: what the
result holds there is undefined, and the caller masks it.

The grid walks ``(n tile, visit)``.  A *visit* is one row tile ``tm`` of
one group: a group visits the tiles its rows touch, so a tile that two
groups share is visited twice, each time storing only its own rows, and
tiles that hold no landed row are not visited at all (the number of
visits is computed on the device from ``counts`` and is the grid's
second extent).  Visits come in row order, so a group's visits are
consecutive.  The whole of K is one tile, and the ``(K, tn)`` weight
block of a group is fetched by hand into one of two buffers: on a
group's first visit the next group's block starts streaming, so it
arrives under all of this group's visits and not under one step's (a
pipeline that looks one visit ahead leaves the weights' stream waiting
for the compute whenever a group spans two tiles).  The weights of the
groups that got a row are read once a ``tn`` column.

**The tile rule** (``tiling_for``).  ``tm`` is the expected rows a group
at the call site rounded down to a power of two, between one sublane
tile of the dtype and 256: a group then spans about one tile and its
share of straddled tiles is small.  The routed layer passes
``T * k / n_routed``: 64 rows in the latent cell's chunk (2048 tokens, 8
of 256 experts), 128 in the window cell's (8 of 128), 16-32 at a prefill
bucket of 512, and 1-2 at a 32-row decode tick, where ``tm`` is one bf16
tile of 16 and a tile holds the rows of several groups.  ``tn`` is the
widest divisor of N in whole lanes whose double-buffered ``(K, tn)``
block fits ``WEIGHT_VMEM_BYTES``.

**Bounds** (v5e: 819 GB/s, 197 TFLOP/s bf16).  At the window cell's
shapes (128 groups, 16384 rows, K x N = 2048 x 1024 and 1024 x 2048,
``tn`` = N) a product reads 537 MB of weights, 0.66 ms, and does 2 x
16384 x 2048 x 1024 = 68.7 GFLOP, 0.35 ms, plus what straddled tiles
recompute (up to a tile a group: 1.5-2x the rows at ``tm`` 64-128).  At
the latent cell's (16 held groups, about 1024 of 4096 rows landed, 7168
x 2048 with ``tn`` 1024 and 2048 x 7168 with ``tn`` 3584) a product
reads 470 MB, 0.57 ms, for 30 GFLOP: the weights bound it.  A 32-row
tick is all weights: in the window cell 256 rows over 128 groups, of
which about 111 get a row, read 111 blocks of 4 MiB, 466 MB, 0.57 ms
(the 17 idle groups are never read), for 1.1 GFLOP; in the latent cell
about 16 of the 256 rows land and touch about 10 of the 16 held groups,
300 MB, 0.37 ms (470 MB, 0.57 ms, where all 16 are touched), in about
11 visits a column.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.pallas import report as _report

MAX_TM = 256
# the two (K, tn) weight buffers
WEIGHT_VMEM_BYTES = 32 * 1024 * 1024


def _sublanes(dtype) -> int:
    return 32 // jnp.dtype(dtype).itemsize


def tiling_for(rows_per_group: int, k: int, n: int,
               dtype=jnp.bfloat16) -> Optional[Tuple[int, int]]:
    """``(tm, tn)`` by the rule in the module docstring; nothing where
    N is not whole lanes or no weight block of K rows fits."""
    size = jnp.dtype(dtype).itemsize
    tm = _sublanes(dtype)
    while tm * 2 <= min(rows_per_group, MAX_TM):
        tm *= 2
    if n % 128:
        return None
    for parts in range(1, n // 128 + 1):
        tn = n // parts
        if n % parts == 0 and tn % 128 == 0 \
                and 2 * k * tn * size <= WEIGHT_VMEM_BYTES:
            return tm, tn
    return None


def routes(x_shape, w_shape, dtype,
           rows_per_group: int) -> Optional[Tuple[int, int]]:
    """Trace-time routing on what the caller sees: bf16 or f32 rows
    whose count the row tile divides, a weight block that fits, on the
    TPU.  The tiling to run at, or nothing for XLA.  Recorded like every
    kernel's route, an eligible shape that stays on XLA in
    tools/kernel_shapes.GROUPED_MATMUL's form."""
    m, k = x_shape
    g, _, n = w_shape
    tiling = tiling_for(rows_per_group, k, n, dtype) \
        if jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)) else None
    if tiling is None or m % tiling[0]:
        return None
    if _report.force_pallas() or jax.default_backend() == "tpu":
        _report.record("grouped_matmul", "pallas")
        return tiling
    _report.record("grouped_matmul", "xla", (m, k, n, g))
    return None


def visits(counts, m: int, tm: int):
    """The grid's schedule, from ``counts`` on the device: for each
    visit its group, its row tile, the group's place among the groups
    visited and the next group visited after it (the last one's own for
    the last), padded to the most visits ``m // tm + G - 1`` can be;
    the groups' row offsets (G+1,); and ``[visits, groups visited]``."""
    g = counts.shape[0]
    counts = counts.astype(jnp.int32)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    tiles = jnp.where(counts > 0, (ends + tm - 1) // tm - starts // tm, 0)
    first = jnp.cumsum(tiles) - tiles
    most = m // tm + g - 1
    group = jnp.repeat(jnp.arange(g, dtype=jnp.int32), tiles,
                       total_repeat_length=most)
    tile = starts[group] // tm + jnp.arange(most, dtype=jnp.int32) \
        - first[group]
    visited = tiles > 0
    place = jnp.cumsum(visited.astype(jnp.int32)) - 1
    # the first visited group after each group: a reverse running min
    ids = jnp.where(visited, jnp.arange(g, dtype=jnp.int32), g)
    after = jax.lax.cummin(jnp.concatenate(
        [ids[1:], jnp.full((1,), g, jnp.int32)]), reverse=True)
    after = jnp.where(after < g, after, jnp.arange(g, dtype=jnp.int32))
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    sizes = jnp.stack([tiles.sum(), visited.sum()]).astype(jnp.int32)
    return (group, jnp.minimum(tile, m // tm - 1), place[group],
            after[group], offsets, sizes)


def _kernel(group_ref, tile_ref, place_ref, after_ref, offsets_ref,
            sizes_ref, x_ref, w_hbm, o_ref, w_buf, sems, *, tm: int,
            tn: int, n_tiles: int):
    j, v = pl.program_id(0), pl.program_id(1)
    group, groups = group_ref[v], sizes_ref[1]
    # the weight blocks in the order they are used: (n tile, group)
    block = j * groups + place_ref[v]
    slot = block % 2

    def fetch(grp, col, into):
        return pltpu.make_async_copy(
            w_hbm.at[grp, :, pl.ds(col * tn, tn)], w_buf.at[into],
            sems.at[into])

    @pl.when((j == 0) & (v == 0))
    def _():
        fetch(group, j, slot).start()

    # on a group's first visit, fetch the next group's block (the next
    # n tile's first where this is the last group) and wait for its own:
    # the next block streams in under all of this group's visits
    @pl.when((v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != group))
    def _():
        last = place_ref[v] == groups - 1

        @pl.when(~last)
        def _():
            fetch(after_ref[v], j, 1 - slot).start()

        @pl.when(last & (j + 1 < n_tiles))
        def _():
            fetch(group_ref[0], j + 1, 1 - slot).start()

        fetch(group, j, slot).wait()

    acc = jnp.dot(x_ref[...], w_buf[slot],
                  preferred_element_type=jnp.float32)
    # only this group's rows: a straddled tile's other rows are the
    # neighbouring visit's to store
    row = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
    mine = (row >= offsets_ref[group]) & (row < offsets_ref[group + 1])
    o_ref[...] = jnp.where(mine, acc, o_ref[...].astype(jnp.float32)
                           ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def grouped_matmul(x, w, counts, *, tiling: Tuple[int, int],
                   interpret: bool = False):
    """``x`` (m, K) sorted by group, ``w`` (G, K, N), ``counts`` (G,)
    int32 rows a group -> (m, N) in ``x``'s dtype; rows past
    ``counts.sum()`` undefined.  ``tiling`` ``(tm, tn)``, as
    :func:`routes` or :func:`tiling_for` give it."""
    m, k = x.shape
    n = w.shape[2]
    tm, tn = tiling
    schedule = visits(counts, m, tm)
    size = x.dtype.itemsize
    vmem = 2 * (tm * k + k * tn + tm * tn) * size + tm * tn * 4
    kernel = functools.partial(_kernel, tm=tm, tn=tn, n_tiles=n // tn)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(n // tn, schedule[-1][0]),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, gr, ti, *_: (ti[v], 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, gr, ti, *_: (ti[v], j)),
            scratch_shapes=[pltpu.VMEM((2, k, tn), x.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(vmem + (8 << 20), 32 << 20)),
        interpret=interpret,
        name="grouped_matmul",  # the device trace finds the kernel by it
    )(*schedule, x, w.astype(x.dtype))
