"""Fused matmul with BN prologue/epilogue — Pallas TPU kernels.

The TPU analog of the reference's fused mkldnn backend (conv+bn /
conv+relu fusion, nn/mkldnn/Fusion.scala:36-219, compiled per phase by
nn/mkldnn/DnnGraph.scala:310-415).  On TPU the convolutions themselves
already run at ~95% of MXU peak under XLA (PERF.md); what the fused
backend must eliminate is the *HBM traffic around BatchNorm* — the
separate stats-reduction and normalize passes that dominate the ResNet
step profile.  ResNet bottleneck blocks are 2/3 1x1 convolutions, and a
1x1 convolution over NHWC is exactly a ``(N*H*W, Cin) @ (Cin, Cout)``
matmul, so the fusion is expressed as a matmul kernel with:

- **prologue**: the *previous* BatchNorm's normalize+ReLU applied
  per-input-channel while the raw activation tile is already in VMEM
  (``u = relu(x * scale + bias)``) — the deferred-normalization trick:
  conv k writes only its raw output; its BN's apply never touches HBM.
- **epilogue**: per-output-channel ``sum`` / ``sum-of-squares`` of the
  raw output accumulated across row-tiles while the output tile is
  still in VMEM — BatchNorm statistics cost zero extra HBM passes.

Backward is two more kernels behind a ``custom_vjp``:

- ``dgrad``: ``dx = (dy + dstats-terms) @ W^T`` with the prologue's
  ReLU/affine backward applied in-tile and the per-input-channel
  reductions (``d_scale``, ``d_bias``) accumulated in the epilogue, and
- ``wgrad``: ``dW = relu(x*scale+bias)^T @ (dy + dstats-terms)`` which
  *recomputes* the prologue in VMEM instead of materialising the
  normalized activation in HBM (rematerialisation a la jax.checkpoint).

Stats cotangents fold into the matmul operand on the fly:
``ssum = sum_m y`` and ``ssq = sum_m y^2`` mean a cotangent
``(dssum, dssq)`` contributes ``dssum + 2*y*dssq`` to every row of
``dy`` — computed from the saved ``y`` tile inside both backward
kernels, never materialised.

Grid design: a single row-tile axis.  The full (K, N) weight block has
a constant index map so it stays resident in VMEM, and the stats /
d_scale / d_bias outputs accumulate at a constant block index across
consecutive grid steps (the canonical Pallas accumulation pattern).
Stats buffers are (8, N) lane-replicated to satisfy Mosaic's
(8k, 128k) trailing-dims rule (same lesson as the flash-attention lse
block, PERF.md).
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_matmul_bn", "fused_conv3x3_bn", "bn_constants",
           "fused_path_taken"]


from bigdl_tpu.ops.pallas import report as _report
from bigdl_tpu.ops.pallas import tuning as _tuning


def fused_path_taken() -> dict:
    """Counters of trace-time path decisions since process start."""
    return _report.report().get("fused_matmul", {"pallas": 0, "xla": 0})


def _pick_bm(m: int, k: int, n: int, itemsize: int = 2) -> Optional[int]:
    """Largest row-tile that divides M, is sublane-aligned, and keeps the
    working set (x, y-acc, y-out tiles; weights counted separately)
    within a conservative VMEM budget."""
    budget = 6 * 1024 * 1024
    for bm in (1024, 768, 512, 448, 384, 256, 192, 128, 64, 32, 16, 8):
        if m % bm:
            continue
        if bm * k * itemsize + bm * n * (itemsize + 4) <= budget:
            return bm
    return None


def _weights_fit(k: int, n: int, itemsize: int = 2) -> bool:
    # resident weight block (f32 wgrad accumulator is K-tiled separately)
    return k * n * itemsize <= 8 * 1024 * 1024


# --------------------------------------------------------------------------
# declared tuning candidate spaces (ops/pallas/tuning.py, ISSUE 13)
# --------------------------------------------------------------------------
# the sweep's row-tile menu: the hand picker's list widened upward —
# candidates past the conservative budgets are allowed because the
# deviceless Mosaic compile (tools/autotune.py) is the real feasibility
# check; the estimates below only prune candidates that cannot possibly
# fit, so "zero Mosaic rejections among ACCEPTED candidates" stays true
_TUNE_BM = (2048, 1024, 768, 512, 448, 384, 256, 192, 128, 64, 32, 16, 8)
_TUNE_BIMG = (32, 16, 8, 4, 2)


def candidate_params(kernel: str, shape) -> list:
    """The finite candidate space for one of this module's kernel
    families at ``shape`` — enumerated by the autotune sweep and the
    membership test :func:`bigdl_tpu.ops.pallas.tuning.resolve` applies
    to injected table params (stale entries fall back, recorded)."""
    itemsize = 2  # bf16 activations everywhere in the fused pipeline
    if kernel == "fused_matmul":
        m, k, n = shape
        if not _weights_fit(k, n, itemsize):
            return []
        budget = 12 * 1024 * 1024  # 2x the dispatch default
        return [{"bm": bm} for bm in _TUNE_BM
                if m % bm == 0
                and bm * k * itemsize + bm * n * (itemsize + 4) <= budget]
    if kernel == "fused_matmul_dgrad":
        m, k, n = shape
        # the scoped f32 temporaries (see _dgrad_pallas) must stay under
        # Mosaic's 16MB cap; 15MB lets the search probe past the
        # dispatch's conservative 14MB halving threshold
        return [{"bm": bm} for bm in _TUNE_BM
                if m % bm == 0
                and 4 * bm * (5 * k + 2 * n) <= 15 * 1024 * 1024]
    if kernel == "fused_matmul_wgrad":
        m, k, n = shape
        out = []
        bk = k
        while bk >= 8:
            # bk is the LAST dim of the (bm, bk) x block: Mosaic wants
            # a 128-multiple there unless the block spans the whole axis
            if (k % bk == 0 and (bk == k or bk % 128 == 0)
                    and bk * n * 4 <= 8 * 1024 * 1024):
                out.append({"bk": bk})
            if bk % 2:
                break
            bk //= 2
        return out
    if kernel == "fused_conv3x3":
        b, h, w, c, co = shape
        if 9 * c * co * itemsize > 8 * 1024 * 1024:
            return []
        per = _conv3_per_img(h, w, c, co, itemsize)
        budget = (_conv3_limits()[0] * 3) // 2
        return [{"bimg": bi} for bi in _TUNE_BIMG
                if b % bi == 0 and bi * per <= budget]
    if kernel == "fused_conv3x3_dgrad":
        b, h, w, ci, co = shape
        per = _conv3_dgrad_per_img(h, w, ci, co, itemsize)
        budget = (_conv3_limits()[0] * 3) // 2
        return [{"bimg": bi} for bi in _TUNE_BIMG
                if b % bi == 0 and bi * per <= budget]
    raise KeyError(f"unknown fused_matmul family '{kernel}'")


def _row8(v: jnp.ndarray) -> jnp.ndarray:
    """(N,) f32 -> (8, N) sublane-replicated buffer."""
    return jnp.broadcast_to(v.astype(jnp.float32)[None, :], (8, v.shape[0]))


# --------------------------------------------------------------------------
# forward kernel
# --------------------------------------------------------------------------
def _fwd_kernel(x_ref, w_ref, ps_ref, pb_ref, y_ref, ssum_ref, ssq_ref,
                *, prologue: bool, relu: bool):
    i = pl.program_id(0)
    u = x_ref[:]
    if prologue:
        uf = u.astype(jnp.float32) * ps_ref[0:1, :] + pb_ref[0:1, :]
        if relu:
            uf = jnp.maximum(uf, 0.0)
        u = uf.astype(w_ref.dtype)
    acc = jax.lax.dot_general(
        u, w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # (bm, N)
    y_ref[:] = acc.astype(y_ref.dtype)
    ts = jnp.sum(acc, axis=0)
    tq = jnp.sum(acc * acc, axis=0)

    @pl.when(i == 0)
    def _():
        ssum_ref[:] = jnp.zeros_like(ssum_ref)
        ssq_ref[:] = jnp.zeros_like(ssq_ref)

    ssum_ref[:] = ssum_ref[:] + ts[None, :]
    ssq_ref[:] = ssq_ref[:] + tq[None, :]


def _fwd_pallas(x, w, ps, pb, prologue, relu, bm, interpret):
    m, k = x.shape
    n = w.shape[1]
    kernel = functools.partial(_fwd_kernel, prologue=prologue, relu=relu)

    y, ssum, ssq = pl.pallas_call(
        kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
            pl.BlockSpec((8, k), lambda i: (0, 0)),
            pl.BlockSpec((8, k), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((8, n), lambda i: (0, 0)),
            pl.BlockSpec((8, n), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((8, n), jnp.float32),
            jax.ShapeDtypeStruct((8, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x, w, _row8(ps), _row8(pb))
    return y, ssum[0], ssq[0]


# --------------------------------------------------------------------------
# backward kernels
# --------------------------------------------------------------------------
def _dgrad_kernel(dy_ref, y_ref, dss_ref, dsq_ref, w_ref, x_ref, ps_ref,
                  pb_ref, dx_ref, dps_ref, dpb_ref,
                  *, prologue: bool, relu: bool):
    i = pl.program_id(0)
    ytot = (dy_ref[:].astype(jnp.float32)
            + dss_ref[0:1, :]
            + 2.0 * y_ref[:].astype(jnp.float32) * dsq_ref[0:1, :])
    g_out = jax.lax.dot_general(
        ytot.astype(w_ref.dtype), w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # (bm, K)

    @pl.when(i == 0)
    def _():
        dps_ref[:] = jnp.zeros_like(dps_ref)
        dpb_ref[:] = jnp.zeros_like(dpb_ref)

    if prologue:
        xf = x_ref[:].astype(jnp.float32)
        if relu:
            pre = xf * ps_ref[0:1, :] + pb_ref[0:1, :]
            g = jnp.where(pre > 0.0, g_out, 0.0)
        else:
            g = g_out
        dx_ref[:] = (g * ps_ref[0:1, :]).astype(dx_ref.dtype)
        dps_ref[:] = dps_ref[:] + jnp.sum(g * xf, axis=0)[None, :]
        dpb_ref[:] = dpb_ref[:] + jnp.sum(g, axis=0)[None, :]
    else:
        dx_ref[:] = g_out.astype(dx_ref.dtype)


def _dgrad_pallas(dy, y, dssum, dssq, w, x, ps, pb, prologue, relu, bm,
                  interpret):
    m, k = x.shape
    n = w.shape[1]
    # Mosaic stack budget: the kernel's f32 temporaries are ~5 (bm, K)
    # arrays with the prologue (ytot/g_out/xf/pre/g) and must fit the
    # 16MB scoped-vmem limit — at bm=1024, K=1024 they don't (18.4MB,
    # caught by tools/tpu_aot_check.py).  Halve the row tile until the
    # estimate fits; bm_eff | bm keeps the grid exact.
    def scoped(bmx):
        per_row = (5 * k + 2 * n) if prologue else (k + 2 * n)
        return 4 * bmx * per_row

    bm_eff = bm
    while bm_eff % 2 == 0 and scoped(bm_eff) > 14 * 1024 * 1024:
        bm_eff //= 2
    # tuned-table injection: a searched dgrad tile (validated deviceless
    # by the sweep) replaces the halved estimate outright
    bm = _tuning.resolve("fused_matmul_dgrad", (m, k, n),
                         {"bm": bm_eff})["bm"]
    kernel = functools.partial(_dgrad_kernel, prologue=prologue, relu=relu)

    dx, dps, dpb = pl.pallas_call(
        kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((8, n), lambda i: (0, 0)),
            pl.BlockSpec((8, n), lambda i: (0, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((8, k), lambda i: (0, 0)),
            pl.BlockSpec((8, k), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((8, k), lambda i: (0, 0)),
            pl.BlockSpec((8, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), x.dtype),
            jax.ShapeDtypeStruct((8, k), jnp.float32),
            jax.ShapeDtypeStruct((8, k), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(dy, y, _row8(dssum), _row8(dssq), w, x, _row8(ps), _row8(pb))
    return dx, dps[0], dpb[0]


def _wgrad_kernel(x_ref, ps_ref, pb_ref, dy_ref, y_ref, dss_ref, dsq_ref,
                  dw_ref, *, prologue: bool, relu: bool):
    i = pl.program_id(1)  # inner (row-tile) axis
    u = x_ref[:]
    if prologue:
        uf = u.astype(jnp.float32) * ps_ref[0:1, :] + pb_ref[0:1, :]
        if relu:
            uf = jnp.maximum(uf, 0.0)
        u = uf.astype(dy_ref.dtype)
    ytot = (dy_ref[:].astype(jnp.float32)
            + dss_ref[0:1, :]
            + 2.0 * y_ref[:].astype(jnp.float32) * dsq_ref[0:1, :])
    acc = jax.lax.dot_general(
        u, ytot.astype(u.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # (bk, N)

    @pl.when(i == 0)
    def _():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    dw_ref[:] = dw_ref[:] + acc


def _wgrad_pallas(x, ps, pb, dy, y, dssum, dssq, prologue, relu, bm,
                  interpret):
    m, k = x.shape
    n = dy.shape[1]
    # K-tiling keeps the f32 dW accumulator block within VMEM even for
    # the widest (K, N) in the model (e.g. a 1024x2048 projection)
    bk = k
    while bk * n * 4 > 4 * 1024 * 1024 and bk % 2 == 0:
        bk //= 2
    bk = _tuning.resolve("fused_matmul_wgrad", (m, k, n), {"bk": bk})["bk"]
    kernel = functools.partial(_wgrad_kernel, prologue=prologue, relu=relu)

    dw = pl.pallas_call(
        kernel,
        grid=(k // bk, m // bm),  # dW block constant over the inner axis
        in_specs=[
            pl.BlockSpec((bm, bk), lambda j, i: (i, j)),
            pl.BlockSpec((8, bk), lambda j, i: (0, j)),
            pl.BlockSpec((8, bk), lambda j, i: (0, j)),
            pl.BlockSpec((bm, n), lambda j, i: (i, 0)),
            pl.BlockSpec((bm, n), lambda j, i: (i, 0)),
            pl.BlockSpec((8, n), lambda j, i: (0, 0)),
            pl.BlockSpec((8, n), lambda j, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bk, n), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((k, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(x, _row8(ps), _row8(pb), dy, y, _row8(dssum), _row8(dssq))
    return dw


# --------------------------------------------------------------------------
# XLA reference path (CPU default, fallback, and parity oracle)
# --------------------------------------------------------------------------
def _xla_fwd(x, w, ps, pb, prologue, relu):
    if prologue:
        uf = x.astype(jnp.float32) * ps[None, :] + pb[None, :]
        if relu:
            uf = jnp.maximum(uf, 0.0)
        u = uf.astype(w.dtype)
    else:
        u = x
    yf = jax.lax.dot_general(
        u, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    y = yf.astype(x.dtype)
    ssum = jnp.sum(yf, axis=0)
    ssq = jnp.sum(yf * yf, axis=0)
    return y, ssum, ssq


# --------------------------------------------------------------------------
# custom_vjp wrapper
# --------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _fused(x, w, ps, pb, prologue, relu, bm, interpret):
    if bm is None:
        return _xla_fwd(x, w, ps, pb, prologue, relu)
    return _fwd_pallas(x, w, ps, pb, prologue, relu, bm, interpret)


def _fused_fwd(x, w, ps, pb, prologue, relu, bm, interpret):
    out = _fused(x, w, ps, pb, prologue, relu, bm, interpret)
    y, ssum, ssq = out
    return out, (x, w, ps, pb, y)


def _fused_bwd(prologue, relu, bm, interpret, res, cots):
    x, w, ps, pb, y = res
    dy, dssum, dssq = cots
    if bm is None:
        # XLA reference backward — same math, compiler-scheduled
        yf = y.astype(jnp.float32)
        ytot = (dy.astype(jnp.float32) + dssum[None, :]
                + 2.0 * yf * dssq[None, :])
        g_out = jax.lax.dot_general(
            ytot.astype(w.dtype), w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if prologue:
            xf = x.astype(jnp.float32)
            pre = xf * ps[None, :] + pb[None, :]
            uf = jnp.maximum(pre, 0.0) if relu else pre
            g = jnp.where(pre > 0.0, g_out, 0.0) if relu else g_out
            dx = (g * ps[None, :]).astype(x.dtype)
            dps = jnp.sum(g * xf, axis=0)
            dpb = jnp.sum(g, axis=0)
            u = uf.astype(w.dtype)
        else:
            dx = g_out.astype(x.dtype)
            dps = jnp.zeros_like(ps)
            dpb = jnp.zeros_like(pb)
            u = x
        dw = jax.lax.dot_general(
            u, ytot.astype(u.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dx, dw.astype(w.dtype), dps, dpb
    dx, dps, dpb = _dgrad_pallas(dy, y, dssum, dssq, w, x, ps, pb,
                                 prologue, relu, bm, interpret)
    dw = _wgrad_pallas(x, ps, pb, dy, y, dssum, dssq, prologue, relu, bm,
                       interpret)
    if not prologue:
        dps = jnp.zeros_like(ps)
        dpb = jnp.zeros_like(pb)
    return dx, dw.astype(w.dtype), dps, dpb


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_matmul_bn(
    x: jnp.ndarray,
    w: jnp.ndarray,
    prologue_scale: Optional[jnp.ndarray] = None,
    prologue_bias: Optional[jnp.ndarray] = None,
    relu: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``y = [relu](x * scale + bias) @ w`` plus per-column stats of y.

    Args:
      x: (M, K) activations (bf16 on TPU).
      w: (K, N) weights, same dtype as x.
      prologue_scale/bias: optional per-K f32 normalize constants from
        the previous BatchNorm (see :func:`bn_constants`); ``None``
        feeds x straight to the MXU.
      relu: apply ReLU after the prologue affine (ignored without one).

    Returns:
      (y, ssum, ssq): y is (M, N) in x.dtype; ssum/ssq are f32 (N,)
      sums of y and y**2 over rows, computed from the f32 accumulator
      (one fewer rounding than a separate stats pass over bf16 y).
    """
    m, k = x.shape
    kw, n = w.shape
    assert k == kw, (x.shape, w.shape)
    prologue = prologue_scale is not None
    if prologue_scale is None:
        prologue_scale = jnp.ones((k,), jnp.float32)
        prologue_bias = jnp.zeros((k,), jnp.float32)
    elif prologue_bias is None:
        prologue_bias = jnp.zeros((k,), jnp.float32)

    on_tpu = (_report.force_pallas()
              or jax.default_backend() == "tpu")
    if interpret is None:
        if not on_tpu or os.environ.get("BIGDL_TPU_FUSED_DISABLE"):
            _report.record("fused_matmul", "xla", (m, k, n))
            return _fused(x, w, prologue_scale, prologue_bias, prologue,
                          relu, None, False)
        interpret = False
    itemsize = jnp.dtype(x.dtype).itemsize
    # hand-picked default, overridden by the tuned table when it has a
    # still-valid entry for this shape (ops/pallas/tuning.py) — a table
    # entry can also rescue a shape the conservative picker rejected
    bm = _tuning.resolve("fused_matmul", (m, k, n),
                         {"bm": _pick_bm(m, k, n, itemsize)})["bm"]
    if bm is None or not _weights_fit(k, n, itemsize):
        _report.record("fused_matmul", "xla", (m, k, n))
        return _fused(x, w, prologue_scale, prologue_bias, prologue,
                      relu, None, False)
    _report.record("fused_matmul", "pallas")
    # under a dp-sharded mesh the kernel must run inside a shard_map
    # (Mosaic custom calls can't be auto-partitioned); rows shard over
    # 'data', the per-column stats are psum'd back to global sums, and
    # shard_map's transpose psums dw/dps/dpb in the backward.  The row
    # tile is re-picked for the LOCAL m inside the body.
    from bigdl_tpu.ops.pallas.partition import shard_kernel_call
    from bigdl_tpu.parallel.mesh import DATA_AXIS

    def _pallas_local(x_, w_, ps_, pb_):
        m_l = x_.shape[0]
        bm_l = bm if m_l == m else _tuning.resolve(
            "fused_matmul", (m_l, k, n),
            {"bm": _pick_bm(m_l, k, n, itemsize)})["bm"]
        if bm_l is None:
            # per-shard fallback: the GLOBAL shape routed to Pallas but
            # the local rows no longer tile — record it so the kernel
            # report / AOT gate / graft-lint can see it
            _report.record("fused_matmul", "pallas_local_xla",
                           (m_l, k, n))
        return _fused(x_, w_, ps_, pb_, prologue, relu, bm_l, interpret)

    return shard_kernel_call(
        _pallas_local, (x, w, prologue_scale, prologue_bias),
        dim_axes=((DATA_AXIS, None), (None, None), (None,), (None,)),
        out_dim_axes=((DATA_AXIS, None), (None,), (None,)),
        reduce_outputs=(1, 2),
    )


# --------------------------------------------------------------------------
# 3x3 stride-1 SAME convolution with the same prologue/epilogue
# --------------------------------------------------------------------------
def _conv3_kernel(x_ref, w_ref, ps_ref, pb_ref, y_ref, ssum_ref, ssq_ref,
                  *, prologue: bool, relu: bool):
    """One grid step = a block of whole images: the padded activation
    lives entirely in VMEM, so the 3x3 taps are 9 shifted matmuls over
    in-register windows — no halo exchange, no im2col in HBM."""
    i = pl.program_id(0)
    u = x_ref[:]  # (B, H, W, C)
    if prologue:
        uf = u.astype(jnp.float32) * ps_ref[0:1, :] + pb_ref[0:1, :]
        if relu:
            uf = jnp.maximum(uf, 0.0)
        u = uf.astype(w_ref.dtype)
    b, h, w, c = u.shape
    n = w_ref.shape[3]
    up = jnp.pad(u, ((0, 0), (1, 1), (1, 1), (0, 0)))
    acc = jnp.zeros((b * h * w, n), jnp.float32)
    for dh in range(3):
        for dw in range(3):
            win = up[:, dh:dh + h, dw:dw + w, :].reshape(b * h * w, c)
            acc = acc + jax.lax.dot_general(
                win, w_ref[dh, dw], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    y_ref[:] = acc.reshape(b, h, w, n).astype(y_ref.dtype)
    ts = jnp.sum(acc, axis=0)
    tq = jnp.sum(acc * acc, axis=0)

    @pl.when(i == 0)
    def _():
        ssum_ref[:] = jnp.zeros_like(ssum_ref)
        ssq_ref[:] = jnp.zeros_like(ssq_ref)

    ssum_ref[:] = ssum_ref[:] + ts[None, :]
    ssq_ref[:] = ssq_ref[:] + tq[None, :]


def _rup(v: int, m: int) -> int:
    return -(-v // m) * m


# Mosaic's default scoped-vmem cap is 16 MB; v4/v5/v6-class chips have
# 128 MB of VMEM.  The conv3 kernels hold whole padded images on the
# stack, so on those chips they raise the per-kernel cap and budget
# against it with a tile-aware estimate.  v2/v3 (16-32 MB VMEM) keep a
# cap-shaped budget so every approved kernel can actually lower; shapes
# over it fall back to XLA exactly as before.
@functools.lru_cache(maxsize=1)
def _conv3_limits() -> Tuple[int, int]:
    """-> (stack_budget_bytes, vmem_limit_bytes_or_0) for this backend."""
    kind = ""
    # under force_pallas (offline AOT check) the running backend is the
    # CPU; the v4/v5 default limits below match the v5e AOT target
    if not _report.force_pallas() and jax.default_backend() == "tpu":
        kind = jax.devices()[0].device_kind.lower()
    if "v2" in kind or "v3" in kind:
        return 10 * 1024 * 1024, 0
    return 60 * 1024 * 1024, 100 * 1024 * 1024


def _conv3_compiler_params():
    kw = dict(dimension_semantics=("arbitrary",))
    lim = _conv3_limits()[1]
    if lim:
        kw["vmem_limit_bytes"] = lim
    return pltpu.CompilerParams(**kw)


def _conv3_per_img(h: int, w: int, c: int, n_out: int,
                   itemsize: int = 2) -> int:
    """Tile-aware stack bytes per image for the forward conv3 kernel
    (shared by the dispatch picker and the tuning candidate space)."""
    c_r = _rup(c, 128)
    n_r = _rup(n_out, 128)
    return (
        (h + 2) * _rup(w + 2, 8) * c_r * itemsize      # padded input copy
        + h * _rup(w, 8) * c_r * (itemsize + 4)        # u + f32 prologue
        + h * w * (9 * c_r * itemsize + n_r * 4)       # windows + f32 acc
    )


# The conv3 kernels reshape every (W, C) row slab of every shifted
# window to matmul rows; where W is not a sublane multiple (28, 14, 7)
# each slab is an unrolled relayout, and under libtpu 0.0.34 Mosaic's
# compile time grows faster than linearly in their number (deviceless,
# 28x28x128 forward: bimg 2/4/8/16 -> 3/12/107/418 s; 224 slabs at
# 14x14x256: 47 s).  Blocks of unaligned images stay under this many
# slabs, where a kernel still compiles in ~10 s.
_MAX_UNALIGNED_SLABS = 128


def _compiles_in_seconds(bimg: int, h: int, w: int) -> bool:
    return w % 8 == 0 or bimg * h <= _MAX_UNALIGNED_SLABS


def _pick_bimg(n_img: int, h: int, w: int, c: int, n_out: int,
               itemsize: int = 2):
    """Images per block, tile-aware.

    Mosaic lane-pads the channel (last) dim to 128 and sublane-pads the
    second-minor to 8, and keeps ~all nine shifted windows live across
    the unrolled tap loop — so the stack estimate must use padded
    channels and the full window set.  Validated against the compiler's
    scoped-vmem report on the v5e: 56x56x64 at bimg=2 is 21.2M actual
    vs 25.1M estimated here (the old unpadded formula said 3.3M and the
    kernel failed to lower at the default 16M cap).
    """
    per_img = _conv3_per_img(h, w, c, n_out, itemsize)
    budget = _conv3_limits()[0]
    for b in (16, 8, 4, 2):
        if n_img % b == 0 and b * per_img <= budget \
                and _compiles_in_seconds(b, h, w):
            return b
    # bimg=1 measured pathological on chip (93 ms vs 3.9 ms XLA at
    # 56x56x64 batch 256) — prefer the XLA path outright.
    return None


def _conv3_pallas(x, w, ps, pb, prologue, relu, bimg, interpret):
    n_img, h, wd, c = x.shape
    n = w.shape[3]
    kernel = functools.partial(_conv3_kernel, prologue=prologue, relu=relu)

    y, ssum, ssq = pl.pallas_call(
        kernel,
        grid=(n_img // bimg,),
        in_specs=[
            pl.BlockSpec((bimg, h, wd, c), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((3, 3, c, n), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((8, c), lambda i: (0, 0)),
            pl.BlockSpec((8, c), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bimg, h, wd, n), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((8, n), lambda i: (0, 0)),
            pl.BlockSpec((8, n), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_img, h, wd, n), x.dtype),
            jax.ShapeDtypeStruct((8, n), jnp.float32),
            jax.ShapeDtypeStruct((8, n), jnp.float32),
        ],
        compiler_params=_conv3_compiler_params(),
        interpret=interpret,
    )(x, w, _row8(ps), _row8(pb))
    return y, ssum[0], ssq[0]


def _conv3_xla(x, w, ps, pb, prologue, relu):
    if prologue:
        uf = x.astype(jnp.float32) * ps[None, None, None, :] \
            + pb[None, None, None, :]
        if relu:
            uf = jnp.maximum(uf, 0.0)
        u = uf.astype(w.dtype)
    else:
        u = x
    # f32 accumulation + stats from the UNROUNDED result: the same
    # contract as _xla_fwd, so toggling the fallback cannot drift BN
    # statistics relative to the Pallas path
    yf = jax.lax.conv_general_dilated(
        u, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)
    y2 = yf.reshape(-1, yf.shape[-1])
    return yf.astype(x.dtype), jnp.sum(y2, axis=0), jnp.sum(y2 * y2, axis=0)


def _conv3_dgrad_kernel(dy_ref, y_ref, dss_ref, dsq_ref, w_ref, x_ref,
                        ps_ref, pb_ref, dx_ref, dps_ref, dpb_ref,
                        *, prologue: bool, relu: bool):
    """dgrad of the fused 3x3 conv with everything folded in-tile:
    the stats cotangents (dssum + 2*y*dssq) on the dy read, the 9-tap
    transposed conv, the prologue's ReLU/affine backward, and the
    d_scale/d_bias per-channel reductions — one read of (dy, y, x), one
    write of dx, no materialized intermediate."""
    i = pl.program_id(0)
    ytot = (dy_ref[:].astype(jnp.float32)
            + dss_ref[0:1, :]
            + 2.0 * y_ref[:].astype(jnp.float32) * dsq_ref[0:1, :]
            ).astype(dy_ref.dtype)
    b, h, w, co = ytot.shape
    ci = w_ref.shape[2]
    yp = jnp.pad(ytot, ((0, 0), (1, 1), (1, 1), (0, 0)))
    acc = jnp.zeros((b * h * w, ci), jnp.float32)
    for dh in range(3):
        for dw in range(3):
            win = yp[:, dh:dh + h, dw:dw + w, :].reshape(b * h * w, co)
            # transposed conv: tap (dh, dw) of the flipped kernel is
            # w[2-dh, 2-dw] contracted over its OUTPUT channels
            acc = acc + jax.lax.dot_general(
                win, w_ref[2 - dh, 2 - dw], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(i == 0)
    def _():
        dps_ref[:] = jnp.zeros_like(dps_ref)
        dpb_ref[:] = jnp.zeros_like(dpb_ref)

    if prologue:
        xf = x_ref[:].astype(jnp.float32).reshape(b * h * w, ci)
        pre = xf * ps_ref[0:1, :] + pb_ref[0:1, :]
        g = jnp.where(pre > 0.0, acc, 0.0) if relu else acc
        dx_ref[:] = (g * ps_ref[0:1, :]).reshape(b, h, w, ci).astype(
            dx_ref.dtype)
        dps_ref[:] = dps_ref[:] + jnp.sum(g * xf, axis=0)[None, :]
        dpb_ref[:] = dpb_ref[:] + jnp.sum(g, axis=0)[None, :]
    else:
        dx_ref[:] = acc.reshape(b, h, w, ci).astype(dx_ref.dtype)


def _conv3_dgrad_per_img(h, w, ci, co, itemsize: int = 2) -> int:
    """Per-image stack bytes for the dgrad kernel (~2.5x the forward's;
    shared with the tuning candidate space)."""
    ci_r = _rup(ci, 128)
    co_r = _rup(co, 128)
    return (
        h * _rup(w, 8) * co_r * itemsize * 2           # dy, y
        + (h + 2) * _rup(w + 2, 8) * co_r * itemsize   # padded ytot
        + h * _rup(w, 8) * ci_r * itemsize * 2         # x, dx
        + h * w * (9 * co_r * itemsize + ci_r * 8)     # windows + acc + xf
    )


def _pick_bimg_dgrad(n_img, h, w, ci, co, itemsize):
    """Block size for the dgrad kernel, whose working set (dy, y, x, dx
    blocks + padded ytot + f32 accumulator and xf) is ~2.5x the
    forward's — the forward bimg must not be reused blindly.  Same
    tile-aware padding rules as :func:`_pick_bimg`."""
    per_img = _conv3_dgrad_per_img(h, w, ci, co, itemsize)
    budget = _conv3_limits()[0]
    for b in (16, 8, 4, 2):
        if n_img % b == 0 and b * per_img <= budget \
                and _compiles_in_seconds(b, h, w):
            return b
    return None


def _conv3_dgrad_pallas(dy, y, dssum, dssq, w, x, ps, pb, prologue, relu,
                        bimg, interpret):
    n_img, h, wd, ci = x.shape
    co = w.shape[3]
    kernel = functools.partial(_conv3_dgrad_kernel, prologue=prologue,
                               relu=relu)

    dx, dps, dpb = pl.pallas_call(
        kernel,
        grid=(n_img // bimg,),
        in_specs=[
            pl.BlockSpec((bimg, h, wd, co), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((bimg, h, wd, co), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((8, co), lambda i: (0, 0)),
            pl.BlockSpec((8, co), lambda i: (0, 0)),
            pl.BlockSpec((3, 3, ci, co), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((bimg, h, wd, ci), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((8, ci), lambda i: (0, 0)),
            pl.BlockSpec((8, ci), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bimg, h, wd, ci), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((8, ci), lambda i: (0, 0)),
            pl.BlockSpec((8, ci), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_img, h, wd, ci), x.dtype),
            jax.ShapeDtypeStruct((8, ci), jnp.float32),
            jax.ShapeDtypeStruct((8, ci), jnp.float32),
        ],
        compiler_params=_conv3_compiler_params(),
        interpret=interpret,
    )(dy, y, _row8(dssum), _row8(dssq), w, x, _row8(ps), _row8(pb))
    return dx, dps[0], dpb[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _conv3(x, w, ps, pb, prologue, relu, bimg, interpret):
    if bimg is None:
        return _conv3_xla(x, w, ps, pb, prologue, relu)
    return _conv3_pallas(x, w, ps, pb, prologue, relu, bimg, interpret)


def _conv3_fwd(x, w, ps, pb, prologue, relu, bimg, interpret):
    out = _conv3(x, w, ps, pb, prologue, relu, bimg, interpret)
    y, ssum, ssq = out
    return out, (x, w, ps, pb, y)


def _conv3_bwd(prologue, relu, bimg, interpret, res, cots):
    """Backward of the fused 3x3 conv.  dgrad runs the fused Pallas
    kernel (stats cotangents + prologue backward + d_scale/d_bias
    reductions in-tile) when available — opt-in on chip via
    BIGDL_TPU_FUSED_CONV3_BWD=1, always under interpret mode so tests
    cover it; wgrad stays an XLA conv with the prologue rematerialized
    (a VMEM-resident (3,3,C,C) f32 accumulator does not fit for the
    widest stages)."""
    x, w, ps, pb, y = res
    dy, dssum, dssq = cots
    bimg_d = None
    if bimg is not None and (
            interpret or os.environ.get("BIGDL_TPU_FUSED_CONV3_BWD")):
        bimg_d = _tuning.resolve(
            "fused_conv3x3_dgrad",
            (x.shape[0], x.shape[1], x.shape[2], x.shape[3], w.shape[3]),
            {"bimg": _pick_bimg_dgrad(
                x.shape[0], x.shape[1], x.shape[2], x.shape[3],
                w.shape[3], jnp.dtype(x.dtype).itemsize)})["bimg"]
    use_pallas_dgrad = bimg_d is not None
    _report.record("fused_conv3x3_dgrad",
                   "pallas" if use_pallas_dgrad else "xla",
                   x.shape + w.shape[3:])
    ytot = (dy.astype(jnp.float32)
            + dssum[None, None, None, :]
            + 2.0 * y.astype(jnp.float32) * dssq[None, None, None, :]
            ).astype(x.dtype)
    if prologue:
        xf = x.astype(jnp.float32)
        pre = xf * ps[None, None, None, :] + pb[None, None, None, :]
        uf = jnp.maximum(pre, 0.0) if relu else pre
        u = uf.astype(x.dtype)
    else:
        u = x
    # wgrad: correlate input with cotangent — channels as batch, batch
    # as the contracting feature dim; pad (1,1) so the full-size
    # "kernel" (= ytot) sweeps exactly the 3x3 tap offsets
    dw = jax.lax.conv_general_dilated(
        u.transpose(3, 1, 2, 0), ytot.transpose(1, 2, 0, 3),
        window_strides=(1, 1), padding=((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    ).transpose(1, 2, 0, 3)
    if use_pallas_dgrad:
        dx, dps, dpb = _conv3_dgrad_pallas(
            dy, y, dssum, dssq, w.astype(x.dtype), x, ps, pb, prologue,
            relu, bimg_d, interpret)
        if not prologue:
            dps = jnp.zeros_like(ps)
            dpb = jnp.zeros_like(pb)
        return dx, dw.astype(w.dtype), dps, dpb
    # dgrad: conv of ytot with spatially-flipped, io-swapped weights
    du = jax.lax.conv_general_dilated(
        ytot, jnp.flip(w, (0, 1)).swapaxes(2, 3).astype(x.dtype),
        window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if prologue:
        duf = du.astype(jnp.float32)
        g = jnp.where(pre > 0.0, duf, 0.0) if relu else duf
        dx = (g * ps[None, None, None, :]).astype(x.dtype)
        dps = jnp.sum(g * xf, axis=(0, 1, 2))
        dpb = jnp.sum(g, axis=(0, 1, 2))
    else:
        dx = du.astype(x.dtype)
        dps = jnp.zeros_like(ps)
        dpb = jnp.zeros_like(pb)
    return dx, dw.astype(w.dtype), dps, dpb


_conv3.defvjp(_conv3_fwd, _conv3_bwd)


def fused_conv3x3_bn(
    x: jnp.ndarray,
    w: jnp.ndarray,
    prologue_scale: Optional[jnp.ndarray] = None,
    prologue_bias: Optional[jnp.ndarray] = None,
    relu: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """3x3 stride-1 SAME conv with BN prologue/epilogue fusion.

    ``x``: (N, H, W, C) NHWC; ``w``: (3, 3, C, Cout) HWIO.  Same
    contract as :func:`fused_matmul_bn` — the conv2 analog: reads the
    previous conv's RAW output, applies its BN's normalize+ReLU in the
    prologue, writes its own raw output with statistics accumulated in
    the epilogue.  Strided convs fall back to the XLA path (computing
    the full-res conv just to subsample would cost more than the fused
    passes save).
    """
    assert w.shape[:2] == (3, 3), w.shape
    c = x.shape[3]
    prologue = prologue_scale is not None
    if prologue_scale is None:
        prologue_scale = jnp.ones((c,), jnp.float32)
        prologue_bias = jnp.zeros((c,), jnp.float32)
    elif prologue_bias is None:
        prologue_bias = jnp.zeros((c,), jnp.float32)

    conv_shape = (x.shape[0], x.shape[1], x.shape[2], c, w.shape[3])
    on_tpu = (_report.force_pallas()
              or jax.default_backend() == "tpu")
    if interpret is None:
        if (not on_tpu or os.environ.get("BIGDL_TPU_FUSED_DISABLE")
                or os.environ.get("BIGDL_TPU_FUSED_CONV3_DISABLE")):
            _report.record("fused_conv3x3", "xla", conv_shape)
            return _conv3(x, w, prologue_scale, prologue_bias, prologue,
                          relu, None, False)
        interpret = False
    bimg = _tuning.resolve("fused_conv3x3", conv_shape, {
        "bimg": _pick_bimg(x.shape[0], x.shape[1], x.shape[2], c,
                           w.shape[3], jnp.dtype(x.dtype).itemsize)
    })["bimg"]
    if bimg is None or w.size * jnp.dtype(w.dtype).itemsize > 8 * 1024 * 1024:
        _report.record("fused_conv3x3", "xla", conv_shape)
        return _conv3(x, w, prologue_scale, prologue_bias, prologue,
                      relu, None, False)
    _report.record("fused_conv3x3", "pallas")
    # same sharding contract as fused_matmul_bn: images shard over
    # 'data' (H/W/C replicated — the in-VMEM halo needs whole images),
    # stats psum to global sums, per-shard bimg re-pick; the fused
    # dgrad's bimg_d is picked inside _conv3_bwd from the local batch
    from bigdl_tpu.ops.pallas.partition import shard_kernel_call
    from bigdl_tpu.parallel.mesh import DATA_AXIS

    def _pallas_local(x_, w_, ps_, pb_):
        if x_.shape[0] == x.shape[0]:
            bimg_l = bimg  # unsharded: already resolved above
        else:
            bimg_l = _tuning.resolve(
                "fused_conv3x3",
                (x_.shape[0], x_.shape[1], x_.shape[2], c, w_.shape[3]),
                {"bimg": _pick_bimg(
                    x_.shape[0], x_.shape[1], x_.shape[2], c,
                    w_.shape[3], jnp.dtype(x_.dtype).itemsize)})["bimg"]
        if bimg_l is None:  # local image count no longer blocks
            _report.record("fused_conv3x3", "pallas_local_xla",
                           x_.shape + w_.shape[3:])
        return _conv3(x_, w_, ps_, pb_, prologue, relu, bimg_l,
                      interpret)

    return shard_kernel_call(
        _pallas_local, (x, w, prologue_scale, prologue_bias),
        dim_axes=((DATA_AXIS, None, None, None), (None,) * 4, (None,),
                  (None,)),
        out_dim_axes=((DATA_AXIS, None, None, None), (None,), (None,)),
        reduce_outputs=(1, 2),
    )


def bn_constants(ssum, ssq, count, gamma, beta, eps: float):
    """Per-channel (scale, bias) so ``y*scale + bias`` equals BatchNorm.

    ``mean = ssum/count``, ``var = ssq/count - mean**2`` (the one-pass
    form; f32 accumulation keeps the cancellation benign — same
    reasoning as nn/norm.py).  Returns (scale, bias, mean, var) in f32.
    """
    mean = ssum / count
    var = jnp.maximum(ssq / count - jnp.square(mean), 0.0)
    inv = jax.lax.rsqrt(var + eps)
    scale = inv * gamma.astype(jnp.float32)
    bias = beta.astype(jnp.float32) - mean * scale
    return scale, bias, mean, var
