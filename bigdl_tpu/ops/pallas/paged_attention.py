"""Single-token attention over the paged K/V pool — Pallas TPU kernel.

The decode tick's query is one token a slot; its keys and values lie in
the pool (ops/paged_kv.py, ``(P, Q, H*D)``: a page is one contiguous
run of ``Q`` token rows).  Gathering every slot's full extent first
costs ``S*M*Q`` rows written and read back for slots that hold a few
hundred tokens; this kernel instead leaves the pool in HBM and DMAs
only the pages a slot holds, ``pages_per_step`` to a step, double
buffered, under an f32 online softmax.

Work is a flat list of (slot, chunk) steps the kernel first writes
into scalar memory from the lengths (a few hundred scalar stores), so
one loop runs over the chunks of every active slot back to back and the
next chunk's pages — the next slot's first ones included — are in
flight while the current one is computed.  Slots with nothing to attend
(inactive, ``kv_len == 0``) have no step and read zeros.

A page row holds all heads side by side (``H*D`` lanes), so the query
row is spread over ``H`` rows of a block-diagonal matrix — row ``h``
keeps head ``h``'s lanes — and ``scores = Qmat @ K^T`` is one MXU
contraction over ``H*D`` whose zero blocks contribute exactly nothing;
``P @ V`` gives ``(H, H*D)`` of which each head keeps its own lanes.
Precision is the XLA path's: operands rounded to bf16, f32
accumulation, f32 softmax statistics.

Grouped heads (``kv_heads`` < ``num_heads``): a page row holds the
``G`` K/V heads, and the ``H`` query heads (given as ``(H, D)`` rows)
are laid over the block of the K/V head they share (query head ``h``
reads K/V head ``h // (H/G)``), so a page is fetched once for all the
query heads that read it.  A first row to read (``kv_first``, a window
layer's band): chunks and pages wholly before it are neither listed nor
fetched, and rows before it are masked.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.pallas import report as _report

_NEG_INF = -1e30
PAGES_PER_STEP = 8


def _kernel(len_ref, first_ref, table_ref, q_ref, k_hbm, v_hbm, o_ref,
            row_ref, chunk_ref, kbuf, vbuf, sems, m_ref, l_ref, acc_ref, *,
            head_dim: int, page: int, pages_per_step: int,
            pages_per_slot: int, sm_scale: float, rep: int):
    step_tokens = pages_per_step * page
    hp, hd = acc_ref.shape
    # row h of the block-diagonal query keeps the lanes of the K/V head
    # it reads (its own where every head has one)
    lane = jax.lax.broadcasted_iota(jnp.int32, (hp, hd), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (hp, hd), 0) // rep
    own = (lane >= head * head_dim) & (lane < (head + 1) * head_dim)

    def copies(g, slot):
        """The page DMAs of step ``g`` into buffer ``slot``, each with
        the condition it is issued (and waited for) under: a page past
        the slot's length or wholly before its first row is neither
        fetched nor waited for."""
        row, first = row_ref[g], chunk_ref[g] * pages_per_step
        out = []
        for j in range(pages_per_step):
            held = ((first + j) * page < len_ref[row]) & (
                (first + j + 1) * page > first_ref[row])
            phys = table_ref[row * pages_per_slot
                             + jnp.minimum(first + j, pages_per_slot - 1)]
            dst = pl.ds(j * page, page)
            out.append((held, (
                pltpu.make_async_copy(k_hbm.at[phys], kbuf.at[slot, dst],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[phys], vbuf.at[slot, dst],
                                      sems.at[1, slot]))))
        return out

    def start(g, slot):
        for held, pair in copies(g, slot):
            @pl.when(held)
            def _():
                for c in pair:
                    c.start()

    def wait(g, slot):
        for held, pair in copies(g, slot):
            @pl.when(held)
            def _():
                for c in pair:
                    c.wait()

    def list_row(row, g):
        def put(chunk, g):
            row_ref[g] = row
            chunk_ref[g] = chunk
            return g + 1

        return jax.lax.fori_loop(
            first_ref[row] // step_tokens,
            pl.cdiv(len_ref[row], step_tokens), put, g)

    total = jax.lax.fori_loop(0, q_ref.shape[0], list_row, 0)
    o_ref[...] = jnp.zeros_like(o_ref)
    # a page never fetched leaves its rows as they were: finite (zero
    # here, an older page later), so a zero weight times them is zero
    vbuf[...] = jnp.zeros_like(vbuf)

    @pl.when(total > 0)
    def _():
        start(0, 0)

    def body(g, _):
        slot = g % 2
        row, chunk, kv_len = row_ref[g], chunk_ref[g], len_ref[row_ref[g]]

        @pl.when(g + 1 < total)
        def _():
            start(g + 1, 1 - slot)

        @pl.when(chunk == first_ref[row] // step_tokens)
        def _():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[row]        # (1, H*D), or (H, D) over hd // D blocks
        if rep > 1:
            q = jnp.concatenate([q] * (hd // head_dim), axis=1)
        qmat = jnp.where(own, q, 0.0).astype(jnp.bfloat16)
        wait(g, slot)
        s = jax.lax.dot_general(
            qmat, kbuf[slot].astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (Hp, T)
        pos = chunk * step_tokens + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where((pos < kv_len) & (pos >= first_ref[row]), s,
                      _NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(jnp.bfloat16), vbuf[slot].astype(jnp.bfloat16),
            preferred_element_type=jnp.float32)             # (Hp, H*D)
        m_ref[...] = m_new

        @pl.when((chunk + 1) * step_tokens >= kv_len)
        def _():
            out = jnp.where(own, acc_ref[...] / l_ref[...], 0.0)
            if rep > 1:       # each query head's own block of D lanes
                out = sum(out[:, g * head_dim:(g + 1) * head_dim]
                          for g in range(hd // head_dim))
            else:
                out = jnp.sum(out, axis=0, keepdims=True)
            o_ref[row] = out.astype(o_ref.dtype)

        return 0

    jax.lax.fori_loop(0, total, body, 0)


def routes(q_shape, k_pool, table, num_heads: int) -> bool:
    """Trace-time routing on what the caller sees: one query token a
    slot against an f32 or bf16 pool whose page is whole (8, 128) tiles
    ((16, 128) in bf16: a page DMA and the (T, H*D) view of a chunk
    then need no relayout), on the TPU.  ``q_shape`` is ``(S, Tq, lanes
    of a pool row)`` and ``num_heads`` the heads a pool row holds.
    Recorded like every kernel's route; an eligible shape that stays on
    XLA is recorded in tools/kernel_shapes.PAGED_ATTN's form."""
    s, tq, hd = q_shape
    rows = {jnp.dtype(jnp.float32): 8, jnp.dtype(jnp.bfloat16): 16}.get(
        jnp.dtype(k_pool.dtype))
    if not (tq == 1 and rows and k_pool.shape[1] % rows == 0
            and hd % 128 == 0):
        return False
    if _report.force_pallas() or jax.default_backend() == "tpu":
        _report.record("paged_attention", "pallas")
        return True
    _report.record("paged_attention", "xla",
                   (s, num_heads, hd // num_heads, k_pool.shape[1],
                    table.shape[1]))
    return False


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "kv_heads", "sm_scale", "pages_per_step", "interpret"))
def paged_attn(q, k_pool, v_pool, table, kv_len, kv_first=None, *,
               num_heads: int, kv_heads: Optional[int] = None,
               sm_scale: Optional[float] = None,
               pages_per_step: int = PAGES_PER_STEP,
               interpret: bool = False):
    """``q`` (S, 1, H*D) against each slot's first ``kv_len`` (S,)
    tokens of the pool ``k_pool``/``v_pool`` (P, Q, H*D) through the
    block ``table`` (S, M): returns (S, 1, H*D), zeros where ``kv_len``
    is 0.  ``kv_len`` is clipped to the table's extent.  With
    ``kv_heads`` < ``num_heads`` the pool rows hold ``kv_heads * D``
    lanes, ``q`` is (S, H, D) and so is the result.  ``kv_first`` (S,)
    is each slot's first row to read (default 0)."""
    s = q.shape[0]
    _, page, hd = k_pool.shape
    m = table.shape[1]
    kv_heads = kv_heads or num_heads
    rep = num_heads // kv_heads
    head_dim = hd // kv_heads
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)
    pages_per_step = min(pages_per_step, m)
    step_tokens = pages_per_step * page
    # whole bf16 sublane tiles; grouped queries come as their own rows
    hp = -(-num_heads // 16) * 16 if rep == 1 else num_heads
    kv_len = jnp.clip(kv_len.astype(jnp.int32), 0, m * page)
    kv_first = jnp.zeros_like(kv_len) if kv_first is None \
        else jnp.clip(kv_first.astype(jnp.int32), 0, kv_len)
    steps = s * -(-m // pages_per_step)      # every slot at full extent
    kernel = functools.partial(
        _kernel, head_dim=head_dim, page=page,
        pages_per_step=pages_per_step, pages_per_slot=m,
        sm_scale=sm_scale, rep=rep)
    whole = pl.BlockSpec(q.shape, lambda i, *_: (0, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[whole,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[
                pltpu.SMEM((steps,), jnp.int32),     # step -> slot
                pltpu.SMEM((steps,), jnp.int32),     # step -> chunk
                pltpu.VMEM((2, step_tokens, hd), k_pool.dtype),
                pltpu.VMEM((2, step_tokens, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hp, 1), jnp.float32),    # running max
                pltpu.VMEM((hp, 1), jnp.float32),    # running sum
                pltpu.VMEM((hp, hd), jnp.float32),   # output accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attn",  # the device trace finds the kernel by it
    )(kv_len, kv_first, table.reshape(-1).astype(jnp.int32), q, k_pool,
      v_pool)
