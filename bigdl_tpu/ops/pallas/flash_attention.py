"""Fused (flash) attention — Pallas TPU kernel.

The performance layer the reference delegated to MKL-DNN JNI primitives
(nn/mkldnn/*, SURVEY.md §2.2/§7.8) becomes, on TPU, a small set of
Pallas kernels for what XLA does not already fuse; attention's
softmax(QK^T)V chain is the headline case — materialising the (T, S)
score matrix in HBM is the bandwidth cliff for long sequences.

Forward: one kernel instance per (batch*head, q-block); K/V stream
through VMEM in blocks under an online-softmax accumulator (running max
``m``, running sum ``l``, rescaled output accumulator) — O(T) memory.
Backward (custom VJP, FlashAttention-2): one Pallas kernel,
``flash_bwd``, recomputes the probabilities block by block from the
saved logsumexp (no (T, S) residual, no score-sized block in HBM) and
in one pass over (k block, q block) accumulates a k block's dK and dV
and the whole sequence's dQ in VMEM; under ``causal`` the pairs above
the diagonal are neither computed nor fetched.  Operands stay in the
input dtype, every product accumulates in f32.  A shape it does not
tile takes the blockwise XLA backward (``_bwd_blockwise``, a
``lax.scan``).

``flash_attention(q, k, v, causal=..., sm_scale=...)`` expects
``(B, H, T, D)`` and picks the Pallas path on TPU, falling back to the
XLA-fused reference implementation elsewhere (or under
``interpret=True`` for CPU tests).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                 acc_ref, *, bq: int, bk: int, causal: bool,
                 sm_scale: float):
    """Grid (batch*head, q-block, k-block); K/V stream one block per
    program through VMEM; online-softmax carry lives in VMEM scratch
    which persists across the (sequential, innermost) k-block axis."""
    q_idx = pl.program_id(1)
    kb = pl.program_id(2)
    num_kb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # with causal masking, blocks strictly above the diagonal contribute
    # nothing — skip their compute entirely
    live = (q_idx + 1) * bq > kb * bk if causal else True

    @pl.when(live)
    def _():
        q = q_ref[:] * sm_scale
        s = jax.lax.dot_general(
            q, k_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (bq, bk)
        if causal:
            q_pos = q_idx * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = kb * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m = m_ref[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(kb == num_kb - 1)
    def _():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[:] = (acc_ref[:] / l).astype(o_ref.dtype)
        # lse block is (8, bq): Mosaic requires the last two block dims
        # to be (8k, 128k)-shaped, so the row is replicated over 8
        # sublanes and sliced back to one after the call
        lse = (m_ref[:] + jnp.log(l))[:, 0]
        lse_ref[:] = jnp.broadcast_to(lse[None, :], lse_ref.shape)


def _flash_fwd_pallas(q, k, v, causal, sm_scale, bq, bk, interpret):
    b, h, t, d = q.shape
    s = k.shape[2]
    bq = min(bq, t)
    bk = min(bk, s)
    assert t % bq == 0 and s % bk == 0, (
        f"seq lengths ({t},{s}) must divide block sizes ({bq},{bk}); "
        "pad the sequence")
    qr = q.reshape(b * h, t, d)
    kr = k.reshape(b * h, s, d)
    vr = v.reshape(b * h, s, d)
    kernel = functools.partial(_attn_kernel, bq=bq, bk=bk, causal=causal,
                               sm_scale=sm_scale)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, t // bq, s // bk),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((None, bk, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((None, bk, d), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((None, 8, bq), lambda g, i, j: (g, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 8, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",  # the device trace finds the kernel by it
    )(qr, kr, vr)
    return out.reshape(b, h, t, d), lse[:, 0, :].reshape(b, h, t)


# ----------------------------------------------------------------------
# reference XLA path + logsumexp (used for fallback and for the VJP)
# ----------------------------------------------------------------------

def _xla_attention_lse(q, k, v, causal, sm_scale):
    # f32 score accumulation regardless of input dtype — this path is
    # both the off-TPU default (auto use_flash) and the VJP reference,
    # so it must match the f32-softmax promise of ops/attention.py
    s = jnp.einsum("bhtd,bhsd->bhts", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        t, ss = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((t, ss), bool), k=ss - t)
        s = jnp.where(mask, s, _NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    return jnp.einsum("bhts,bhsd->bhtd", p.astype(v.dtype), v), lse


def _bwd_blockwise(q, k, v, o, lse, g, causal, sm_scale, bq):
    """Recompute-probabilities backward in plain XLA, scanned over q
    blocks: the oracle of the Pallas backward and its fallback for
    shapes that kernel does not tile."""
    b, h, t, d = q.shape
    s_len = k.shape[2]
    bq = min(bq, t)
    nblk = t // bq
    delta = jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32), -1)

    def one_block(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, 2)
        gs = jax.lax.dynamic_slice_in_dim(g, i * bq, bq, 2)
        ls = jax.lax.dynamic_slice_in_dim(lse, i * bq, bq, 2)
        ds_ = jax.lax.dynamic_slice_in_dim(delta, i * bq, bq, 2)
        sc = jnp.einsum("bhtd,bhsd->bhts", qs, k) * sm_scale
        if causal:
            q_pos = i * bq + jnp.arange(bq)[:, None]
            k_pos = jnp.arange(s_len)[None, :]
            sc = jnp.where(q_pos >= k_pos, sc, _NEG_INF)
        p = jnp.exp(sc - ls[..., None])
        dp = jnp.einsum("bhtd,bhsd->bhts", gs.astype(jnp.float32),
                        v.astype(jnp.float32))
        dscore = p * (dp - ds_[..., None]) * sm_scale
        dq_blk = jnp.einsum("bhts,bhsd->bhtd", dscore, k)
        dk_blk = jnp.einsum("bhts,bhtd->bhsd", dscore, qs)
        dv_blk = jnp.einsum("bhts,bhtd->bhsd", p, gs.astype(jnp.float32))
        return dq_blk, dk_blk, dv_blk

    def scan_fn(carry, i):
        dk, dv = carry
        dq_blk, dk_blk, dv_blk = one_block(i)
        return (dk + dk_blk, dv + dv_blk), dq_blk

    (dk, dv), dq_blocks = jax.lax.scan(
        scan_fn,
        (jnp.zeros_like(k, jnp.float32), jnp.zeros_like(v, jnp.float32)),
        jnp.arange(nblk))
    dq = jnp.moveaxis(dq_blocks, 0, 2).reshape(b, h, t, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ----------------------------------------------------------------------
# Pallas backward (FlashAttention-2, one pass for dK, dV and dQ)
# ----------------------------------------------------------------------
_NT = (((1,), (1,)), ((), ()))   # A @ B^T
_NN = (((1,), (0,)), ((), ()))   # A @ B


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dqt_ref, dk_acc, dv_acc, kt_ref, dqt_acc,
                *, bq: int, bk: int, causal: bool, sm_scale: float):
    """Grid (batch*head, k block, q block), both sequential.  A k block's
    dK and dV accumulate in f32 VMEM over the (innermost) q blocks; the
    whole sequence's dQ, transposed to (q block, D, bq), accumulates in
    f32 VMEM over every k block and is written once.  Scores are held
    transposed, (bk, bq): the saved logsumexp and delta are rows
    broadcast down the sublanes, and with K^T kept a k block every
    product is A @ B or A @ B^T."""
    kb = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(jnp.logical_and(kb == 0, qi == 0))
    def _():
        dqt_acc[:] = jnp.zeros_like(dqt_acc)

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        kt_ref[:] = jnp.transpose(
            k_ref[:].astype(jnp.float32)).astype(kt_ref.dtype)

    def accumulate(masked):
        q = q_ref[:] * sm_scale   # scaled as the forward scales it
        do = do_ref[:]
        s = jax.lax.dot_general(k_ref[:], q, _NT,
                                preferred_element_type=jnp.float32)
        if masked:
            k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[:])                            # (bk, bq)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[:], do, _NT,
                                 preferred_element_type=jnp.float32)
        # dS / sm_scale; q carries the scale into dK, dQ takes it at the end
        ds = (p * (dp - delta_ref[:])).astype(q.dtype)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, _NN, preferred_element_type=jnp.float32)
        dqt_acc[qi] += jax.lax.dot_general(
            kt_ref[:], ds, _NN, preferred_element_type=jnp.float32)

    if causal:
        # a pair wholly above the diagonal runs nothing; only a pair
        # that straddles it applies the mask
        live = (qi + 1) * bq > kb * bk
        below = qi * bq >= (kb + 1) * bk - 1   # every query sees every key
        pl.when(below)(lambda: accumulate(False))
        pl.when(jnp.logical_and(live, jnp.logical_not(below)))(
            lambda: accumulate(True))
    else:
        accumulate(False)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)

        @pl.when(kb == pl.num_programs(1) - 1)
        def _():
            dqt_ref[:] = (dqt_acc[:] * sm_scale).astype(dqt_ref.dtype)


# a sequence's dQ stays in VMEM: its f32 accumulator and the output's
# two buffers (Mosaic refused 32 MiB of them on the v5e, took 24)
_DQ_VMEM_BYTES = 16 * 2 ** 20


def bwd_blocks(t: int, s: int, d: int, itemsize: int, block_q: int = 512,
               block_k: int = 512):
    """The (bq, bk) the Pallas backward tiles ``t`` queries against
    ``s`` keys of width ``d`` with, or nothing: where no legal pair
    exists (both are lane dims somewhere, the q block of the logsumexp
    rows and of dQ^T, the k block of K^T: 128-multiples or whole) or
    where the sequence's dQ does not fit the fast memory."""
    bq, bk = fit_block(t, block_q), fit_block(s, block_k)
    if not (bq and bk) or t * d * (4 + 2 * itemsize) > _DQ_VMEM_BYTES:
        return None
    return bq, bk


def _flash_bwd_pallas(q, k, v, o, lse, g, causal, sm_scale, bq, bk,
                      interpret):
    b, h, t, d = q.shape
    s = k.shape[2]
    n, nq = b * h, t // bq
    delta = jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32), -1)

    def first_live_q(j, i):
        # under causal, the q blocks above k block j hold the first live
        # one, so the skipped steps fetch nothing
        return jnp.maximum(i, (j * bk) // bq) if causal else i

    q_spec = pl.BlockSpec((None, bq, d),
                          lambda m, j, i: (m, first_live_q(j, i), 0))
    row_spec = pl.BlockSpec((None, 1, bq),
                            lambda m, j, i: (m, 0, first_live_q(j, i)))
    k_spec = pl.BlockSpec((None, bk, d), lambda m, j, i: (m, j, 0))
    dk, dv, dqt = pl.pallas_call(
        functools.partial(_bwd_kernel, bq=bq, bk=bk, causal=causal,
                          sm_scale=sm_scale),
        grid=(n, s // bk, nq),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=[k_spec, k_spec,
                   pl.BlockSpec((None, nq, d, bq),
                                lambda m, j, i: (m, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, s, d), k.dtype),
                   jax.ShapeDtypeStruct((n, s, d), v.dtype),
                   jax.ShapeDtypeStruct((n, nq, d, bq), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((d, bk), k.dtype),
                        pltpu.VMEM((nq, d, bq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="flash_bwd",  # the device trace finds the kernel by it
    )(q.reshape(n, t, d), k.reshape(n, s, d), v.reshape(n, s, d),
      g.reshape(n, t, d), lse.reshape(n, 1, t), delta.reshape(n, 1, t))
    dq = jnp.swapaxes(dqt, 2, 3).reshape(b, h, t, d)
    return dq, dk.reshape(b, h, s, d), dv.reshape(b, h, s, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, sm_scale, bq, bk, interpret):
    o, _ = _flash_fwd_pallas(q, k, v, causal, sm_scale, bq, bk, interpret)
    return o


def _flash_fwd(q, k, v, causal, sm_scale, bq, bk, interpret):
    o, lse = _flash_fwd_pallas(q, k, v, causal, sm_scale, bq, bk, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, sm_scale, bq, bk, interpret, res, g):
    """The Pallas backward at the forward's blocks in interpret mode
    (tests) and at :func:`bwd_blocks` on the chip; a shape those do not
    tile takes the blockwise XLA backward."""
    from bigdl_tpu.ops.pallas import report as _report

    q, k, v, o, lse = res
    blocks = (bq, bk) if interpret else bwd_blocks(
        q.shape[2], k.shape[2], q.shape[3], q.dtype.itemsize)
    if blocks is None:
        _report.record("flash_attention_bwd", "xla",
                       q.shape[:3] + k.shape[2:])
        return _bwd_blockwise(q, k, v, o, lse, g, causal, sm_scale, bq)
    _report.record("flash_attention_bwd", "pallas")
    return _flash_bwd_pallas(q, k, v, o, lse, g, causal, sm_scale, *blocks,
                             interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def fit_block(n: int, cap: int, multiple: int = 128) -> Optional[int]:
    """Largest block <= cap that divides n and satisfies Mosaic's
    block constraint for the axis it tiles: a ``multiple``-multiple, or
    the whole axis.  The routing precheck — shared with the graft-lint
    pallas-routing rule so the static audit can never drift from the
    dispatch.

    q blocks need ``multiple=128``: the (8, bq) lse output block makes
    bq a *lane* dim, where Mosaic wants 128k or whole-axis.  k/v blocks
    only ever appear as second-minor dims ((bk, d) refs; the (bq, bk)
    score matrix is an unblocked intermediate), so ``multiple=8`` is
    legal there — the fix for the shape classes PERF.md saw fall back
    ("don't meet Mosaic block constraints") when a smaller legal block
    existed, e.g. s=1032 has no 128-multiple divisor but tiles at
    bk=344."""
    if n <= cap:
        return n
    b = (cap // multiple) * multiple
    while b >= multiple:
        if n % b == 0:
            return b
        b -= multiple
    return None


def candidate_params(shape) -> list:
    """Declared tuning candidate space for ``(b, h, t, s, d)`` (ISSUE
    13): the legal (bq, bk) pairs the autotune sweep enumerates and the
    only values dispatch will accept from a tuned table."""
    _, _, t, s, _ = shape
    caps = (2048, 1024, 768, 512, 384, 256, 128)

    def blocks(n, multiple):
        out = []
        for cap in caps:
            b = fit_block(n, cap, multiple=multiple)
            if b is not None and b not in out:
                out.append(b)
        return out

    return [{"bq": bq, "bk": bk}
            for bq in blocks(t, 128) for bk in blocks(s, 8)]


def flash_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    causal: bool = False, sm_scale: Optional[float] = None,
    block_q: int = 1024, block_k: int = 1024,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Fused attention over ``(B, H, T, D)`` tensors.

    On TPU this is the Pallas online-softmax kernel; elsewhere it runs
    in interpreter mode (tests) unless shapes don't divide the blocks,
    in which case the XLA reference path is used.

    ``k``/``v`` with fewer heads ``(B, G, S, D)`` are grouped: query
    head ``h`` reads K/V head ``h // (H/G)``.  ``window`` keeps, of the
    causal keys, those at ``i - j < window``.  Both go through the
    banded kernel (:func:`banded_flash_attention`), forward only.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    t, s = q.shape[2], k.shape[2]
    if causal and t != s:
        raise ValueError("causal flash attention needs matching q/kv "
                         f"lengths, got {t} vs {s}")
    from bigdl_tpu.ops.pallas import report as _report

    key_shape = (q.shape[0], q.shape[1], t, s, q.shape[3])  # tuning key
    if window is not None or k.shape[1] != q.shape[1]:
        if not causal:
            raise ValueError("a band and grouped heads are causal only")
        if interpret:
            blocks = (min(block_q, t), min(block_k, s))
            blocks = None if t % blocks[0] or s % blocks[1] else blocks
        elif _report.force_pallas() or jax.default_backend() == "tpu":
            blocks = band_blocks(t, s, q.shape[1] // k.shape[1])
        else:
            blocks = None
        if blocks is None:
            _report.record("flash_attention", "xla", key_shape)
            return band_attention_reference(
                q, k, v, jnp.arange(t)[None, :], window, sm_scale)
        _report.record("flash_attention", "pallas")
        return banded_flash_attention(
            q, k, v, jnp.zeros((q.shape[0],), jnp.int32),
            sm_scale=sm_scale, window=window, blocks=blocks,
            name="flash_fwd", interpret=bool(interpret))

    on_tpu = (_report.force_pallas()
              or jax.default_backend() == "tpu")
    if interpret is None:
        if not on_tpu:
            # off TPU the interpreter would be orders of magnitude slower
            # than plain XLA — use the fused-einsum reference path unless
            # the caller explicitly opts into interpret mode (tests)
            _report.record("flash_attention", "xla", key_shape)
            out, _ = _xla_attention_lse(q, k, v, causal, sm_scale)
            return out.astype(q.dtype)
        interpret = False
    if interpret:
        # interpreter mode (CPU tests) has no Mosaic tiling rules —
        # honor the requested blocks so the kernel itself is exercised
        bq, bk = min(block_q, t), min(block_k, s)
        if t % bq or s % bk:
            _report.record("flash_attention", "xla", key_shape)
            out, _ = _xla_attention_lse(q, k, v, causal, sm_scale)
            return out.astype(q.dtype)
    else:
        # k/v blocks are second-minor dims, so 8-multiples are legal
        # (see fit_block); the tuned table overrides both when it has a
        # still-valid entry for this shape
        from bigdl_tpu.ops.pallas import tuning as _tuning

        bq, bk = fit_block(t, block_q), fit_block(s, block_k, multiple=8)
        tp = _tuning.resolve("flash_attention", key_shape,
                             {"bq": bq, "bk": bk})
        bq, bk = tp["bq"], tp["bk"]
        if bq is None or bk is None:
            _report.record("flash_attention", "xla", key_shape)
            out, _ = _xla_attention_lse(q, k, v, causal, sm_scale)
            return out.astype(q.dtype)
    _report.record("flash_attention", "pallas")
    # Mosaic custom calls can't be auto-partitioned: under a sharded
    # mesh (dp batch / tp heads) the kernel runs inside a shard_map
    # manual over those axes, with T and D replicated in (see
    # ops/pallas/partition.py); the custom_vjp backward's kernels run
    # inside the same shard_map, so dq/dk/dv come back with the same
    # batch/head sharding
    from bigdl_tpu.ops.pallas.partition import shard_kernel_call
    from bigdl_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    qkv_axes = (DATA_AXIS, MODEL_AXIS, None, None)
    return shard_kernel_call(
        lambda q_, k_, v_: _flash(q_, k_, v_, causal, sm_scale, bq, bk,
                                  interpret),
        (q, k, v),
        dim_axes=(qkv_axes, qkv_axes, qkv_axes),
        out_dim_axes=(qkv_axes,),
        single_output=True,
    )


# ----------------------------------------------------------------------
# causal attention of a chunk against a longer cached extent
# ----------------------------------------------------------------------
def _prefix_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, heads: int, bq: int, bk: int,
                   sm_scale: float):
    """``_attn_kernel`` with the queries at absolute positions
    ``off + i``: key block ``kb`` is live while it starts at or before
    the q block's last position."""
    off = off_ref[pl.program_id(0) // heads]
    q_idx = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(off + (q_idx + 1) * bq > kb * bk)
    def _():
        q = q_ref[:] * sm_scale
        s = jax.lax.dot_general(
            q, k_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (bq, bk)
        q_pos = off + q_idx * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m = m_ref[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        o_ref[:] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(
            o_ref.dtype)


def prefix_blocks(t: int, s: int, block_q: int = 512, block_k: int = 1024):
    """The (bq, bk) :func:`prefix_flash_attention` tiles ``t`` queries
    against ``s`` keys with, or nothing where no legal pair exists."""
    bq, bk = fit_block(t, block_q), fit_block(s, block_k, multiple=8)
    return (bq, bk) if bq and bk else None


@functools.partial(jax.jit, static_argnames=("sm_scale", "blocks",
                                             "interpret", "window"))
def prefix_flash_attention(q, k, v, offset, *, sm_scale: float, blocks,
                           interpret: bool = False,
                           window: Optional[int] = None):
    """A chunk of queries against the extent that holds them: ``q``
    (B, H, T, D) at absolute positions ``offset[b] + i`` attends ``k``,
    ``v`` (B, H, S, D) at positions ``<=`` its own (forward only).  Key
    blocks past a q block's last position are neither computed nor
    fetched again (their index is held at the last live block).  A
    ``window`` or fewer K/V heads than query heads take the banded
    kernel under this one's name (:func:`banded_flash_attention`)."""
    b, h, t, d = q.shape
    s = k.shape[2]
    if window is not None or k.shape[1] != h:
        return banded_flash_attention(
            q, k, v, offset, sm_scale=sm_scale, window=window,
            blocks=blocks, name="flash_prefix", interpret=interpret)
    bq, bk = blocks
    kernel = functools.partial(_prefix_kernel, heads=h, bq=bq, bk=bk,
                               sm_scale=sm_scale)

    def kv_index(g, i, j, off):
        return (g, jnp.minimum(j, (off[g // h] + (i + 1) * bq - 1) // bk), 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h, t // bq, s // bk),
            in_specs=[
                pl.BlockSpec((None, bq, d), lambda g, i, j, off: (g, i, 0)),
                pl.BlockSpec((None, bk, d), kv_index),
                pl.BlockSpec((None, bk, d), kv_index),
            ],
            out_specs=pl.BlockSpec((None, bq, d),
                                   lambda g, i, j, off: (g, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),   # running max
                pltpu.VMEM((bq, 1), jnp.float32),   # running sum
                pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_prefix",  # the device trace finds the kernel by it
    )(offset.astype(jnp.int32), q.reshape(b * h, t, d),
      k.reshape(b * h, s, d), v.reshape(b * h, s, d))
    return out.reshape(b, h, t, d)


# ----------------------------------------------------------------------
# a band of the causal keys, grouped heads: forward only
# ----------------------------------------------------------------------
def band_attention_reference(q, k, v, q_pos, window: Optional[int],
                             sm_scale: float):
    """The banded kernel's result by plain XLA: ``q`` (B, H, T, D) at
    absolute ``q_pos`` (B|1, T) against ``k``, ``v`` (B, G, S, D) at
    positions ``0..S-1``; f32 scores and softmax."""
    b, h, t, d = q.shape
    g, s = k.shape[1], k.shape[2]
    qg = q.reshape(b, g, h // g, t, d)
    sc = jnp.einsum("bgrtd,bgsd->bgrts", qg, k,
                    preferred_element_type=jnp.float32) * sm_scale
    behind = q_pos[:, :, None] - jnp.arange(s)[None, None, :]  # (B, T, S)
    seen = behind >= 0
    if window is not None:
        seen &= behind < window
    p = jax.nn.softmax(jnp.where(seen[:, None, None], sc, _NEG_INF), -1)
    out = jnp.einsum("bgrts,bgsd->bgrtd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, t, d).astype(q.dtype)


def _band_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                 acc_ref, *, groups: int, bq: int, bk: int, last_kb: int,
                 window: Optional[int], sm_scale: float):
    """``_prefix_kernel`` for the ``rep`` query heads that share one
    K/V head at once (their rows lie one under the other, so a K/V block
    is fetched once for all of them), over the key blocks a q block's
    band touches only: step ``j`` of the innermost axis is key block
    ``lo + j``, live while it is not past ``hi`` (:func:`_band_blocks`)."""
    off = off_ref[pl.program_id(0) // groups]
    q_idx = pl.program_id(1)
    j = pl.program_id(2)
    lo, hi = _band_blocks(off, q_idx, bq, bk, last_kb, window)
    rep = q_ref.shape[0]

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(lo + j <= hi)
    def _():
        q = q_ref[:].reshape(rep * bq, q_ref.shape[-1]) * sm_scale
        s = jax.lax.dot_general(
            q, k_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (rep*bq, bk)
        q_pos = off + q_idx * bq + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) % bq
        k_pos = (lo + j) * bk + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        seen = q_pos >= k_pos
        if window is not None:
            seen &= q_pos - k_pos < window
        s = jnp.where(seen, s, _NEG_INF)
        m = m_ref[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        # a row whose keys all lie in later blocks has seen nothing yet
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        out = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
        o_ref[:] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def _band_blocks(off, q_idx, bq: int, bk: int, last_kb: int,
                 window: Optional[int]):
    """The first and the last key block that q block ``q_idx`` (queries
    at ``off + q_idx*bq ...``) reads."""
    first_q = off + q_idx * bq
    hi = jnp.minimum((first_q + bq - 1) // bk, last_kb)
    lo = 0 if window is None \
        else jnp.maximum(first_q - window + 1, 0) // bk
    return lo, hi


def band_blocks(t: int, s: int, rep: int = 1, block_q: int = 1024,
                block_k: int = 512):
    """The (bq, bk) :func:`banded_flash_attention` tiles with (``rep``
    query heads share a q block's rows), or nothing where no legal pair
    exists."""
    bq = fit_block(t, max(block_q // rep, 128))
    bk = fit_block(s, block_k, multiple=128)
    return (bq, bk) if bq and bk else None


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "window", "blocks", "name", "interpret"))
def banded_flash_attention(q, k, v, offset, *, sm_scale: float,
                           window: Optional[int] = None, blocks=None,
                           name: str = "flash_prefix",
                           interpret: bool = False):
    """Queries ``q`` (B, H, T, D) at absolute positions ``offset[b] +
    i`` against ``k``, ``v`` (B, G, S, D), ``G`` dividing ``H``: query
    head ``h`` reads K/V head ``h // (H/G)`` at positions ``<=`` its
    own and, with ``window``, less than ``window`` behind it.  Key
    blocks outside a q block's band are not in the grid (a band) or
    held at the last live block's index (past the diagonal): none is
    fetched to be masked.  Forward only."""
    b, h, t, d = q.shape
    g, s = k.shape[1], k.shape[2]
    rep = h // g
    bq, bk = blocks or band_blocks(t, s, rep)
    last_kb = s // bk - 1
    steps = s // bk if window is None \
        else min(s // bk, (window + bq - 2) // bk + 2)
    kernel = functools.partial(_band_kernel, groups=g, bq=bq, bk=bk,
                               last_kb=last_kb, window=window,
                               sm_scale=sm_scale)

    def kv_index(n, i, j, off):
        lo, hi = _band_blocks(off[n // g], i, bq, bk, last_kb, window)
        return (n, jnp.minimum(lo + j, hi), 0)

    q_spec = pl.BlockSpec((None, rep, bq, d),
                          lambda n, i, j, off: (n, 0, i, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * g, t // bq, steps),
            in_specs=[q_spec,
                      pl.BlockSpec((None, bk, d), kv_index),
                      pl.BlockSpec((None, bk, d), kv_index)],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((rep * bq, 1), jnp.float32),   # running max
                pltpu.VMEM((rep * bq, 1), jnp.float32),   # running sum
                pltpu.VMEM((rep * bq, d), jnp.float32),   # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((b * g, rep, t, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,  # the device trace finds the kernel by it
    )(offset.astype(jnp.int32), q.reshape(b * g, rep, t, d),
      k.reshape(b * g, s, d), v.reshape(b * g, s, d))
    return out.reshape(b, h, t, d)
