"""Int8 x int8 -> int32 matmul with fused dequant epilogue — Pallas TPU.

The reference's int8 speedup comes from BigQuant's VNNI gemms
(nn/quantized/Desc.scala:125-143 + the bigquant JNI, SURVEY.md §2.9).
On TPU, XLA's emitter keeps integer dots off the MXU (PERF.md: int8
conv measured ~2x SLOWER than bf16), but the v5e MXU natively runs
s8 x s8 -> s32 at 2x the bf16 rate (394 vs 197 TOPS peak).  This kernel
issues the int8 dot directly and applies the per-output-channel dequant
scale while the accumulator tile is still in VMEM, so the int32
accumulator never exists in HBM:

    y[m, n] = (sum_k x_q[m, k] * w_q[k, n]) * scale_row[n]

``scale_row`` folds the activation's dynamic per-tensor scale and the
weight's per-channel scale (computed in-graph by nn/quantized.py).
Whether Mosaic lowers the s8 dot onto the MXU is chip-verified by
tools/kernel_smoke.py; trace-time fallback keeps the XLA path on any
shape the kernel cannot take.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from bigdl_tpu.ops.pallas import report as _report

__all__ = ["int8_matmul_dequant"]


def _pick_bm(m: int, k: int, n: int) -> Optional[int]:
    # x tile (bm, K) int8 + int32 acc (bm, N) + bf16 out (bm, N),
    # double-buffered by the pipeline; weights counted separately
    budget = 6 * 1024 * 1024
    for bm in (1024, 768, 512, 384, 256, 128, 64, 32, 16, 8):
        if m % bm:
            continue
        if bm * k + bm * n * 6 <= budget:
            return bm
    return None


def candidate_params(shape) -> list:
    """Declared tuning candidate space (ISSUE 13): row tiles past the
    conservative dispatch budget are included — the deviceless Mosaic
    compile in tools/autotune.py is the real feasibility check."""
    m, k, n = shape
    if k % 128 or n % 128 or k * n > 8 * 1024 * 1024:
        return []  # routed to XLA regardless of tile choice
    budget = 12 * 1024 * 1024
    return [{"bm": bm}
            for bm in (2048, 1024, 768, 512, 384, 256, 128, 64, 32, 16, 8)
            if m % bm == 0 and bm * k + bm * n * 6 <= budget]


def _kernel(x_ref, w_ref, s_ref, y_ref):
    acc = jax.lax.dot_general(
        x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    y_ref[:] = (acc.astype(jnp.float32) * s_ref[0:1, :]).astype(
        y_ref.dtype)


def _pallas(x_q, w_q, scale_row, out_dtype, bm, interpret):
    m, k = x_q.shape
    n = w_q.shape[1]
    s8 = jnp.broadcast_to(scale_row.astype(jnp.float32)[None, :], (8, n))
    return pl.pallas_call(
        _kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
            pl.BlockSpec((8, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(x_q, w_q, s8)


def int8_matmul_dequant(x_q: jnp.ndarray, w_q: jnp.ndarray,
                        scale_row: jnp.ndarray, out_dtype=jnp.bfloat16,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """(M, K) s8 @ (K, N) s8 -> (M, N) ``out_dtype``, scaled per column.

    Falls back to the XLA integer dot when off-TPU, disabled via
    ``BIGDL_TPU_INT8_PALLAS_DISABLE``, or when no block shape fits.
    """
    assert x_q.dtype == jnp.int8 and w_q.dtype == jnp.int8
    scale_row = scale_row.reshape(-1)  # accept (N,) or (1, N)
    m, k = x_q.shape
    n = w_q.shape[1]
    on_tpu = (_report.force_pallas()
              or jax.default_backend() == "tpu")
    if interpret is None:
        if not on_tpu or os.environ.get("BIGDL_TPU_INT8_PALLAS_DISABLE"):
            _report.record("int8_matmul", "xla", (m, k, n))
            acc = jax.lax.dot_general(
                x_q, w_q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            return (acc.astype(jnp.float32)
                    * scale_row.astype(jnp.float32)[None, :]).astype(
                        out_dtype)
        interpret = False
    from bigdl_tpu.ops.pallas import tuning as _tuning

    bm = _tuning.resolve("int8_matmul", (m, k, n),
                         {"bm": _pick_bm(m, k, n)})["bm"]
    if bm is None or k % 128 or n % 128 or k * n > 8 * 1024 * 1024:
        _report.record("int8_matmul", "xla", (m, k, n))
        acc = jax.lax.dot_general(
            x_q, w_q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return (acc.astype(jnp.float32)
                * scale_row.astype(jnp.float32)[None, :]).astype(out_dtype)
    _report.record("int8_matmul", "pallas")
    # dp-sharded serving: rows shard over 'data' inside a shard_map
    # (Mosaic custom calls can't be auto-partitioned), per-shard bm
    from bigdl_tpu.ops.pallas.partition import shard_kernel_call
    from bigdl_tpu.parallel.mesh import DATA_AXIS

    def _pallas_local(x_, w_, s_):
        m_l = x_.shape[0]
        bm_l = bm if m_l == m else _tuning.resolve(
            "int8_matmul", (m_l, k, n), {"bm": _pick_bm(m_l, k, n)})["bm"]
        if bm_l is None:  # local rows no longer tileable
            _report.record("int8_matmul", "pallas_local_xla",
                           (m_l, k, n))
            acc = jax.lax.dot_general(
                x_, w_, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            return (acc.astype(jnp.float32)
                    * s_.astype(jnp.float32)[None, :]).astype(out_dtype)
        return _pallas(x_, w_, s_, out_dtype, bm_l, interpret)

    return shard_kernel_call(
        _pallas_local, (x_q, w_q, scale_row),
        dim_axes=((DATA_AXIS, None), (None, None), (None,)),
        out_dim_axes=((DATA_AXIS, None),),
        single_output=True,
    )
