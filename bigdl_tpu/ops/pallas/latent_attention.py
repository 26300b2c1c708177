"""Single-token absorbed attention over latent pages - Pallas TPU kernel.

The decode tick of a latent-attention layer (nn/latent.py) holds, per
token, one row ``[ckv ; k_pe]`` of ``C`` numbers that every head
shares; the pool is ``(P, Q, C)`` (ops/paged_kv.py).  The query comes
absorbed, ``(S, H, C)``: a head's score against a token is one dot
product with the token's row, and the head's value is the row's first
``value_width`` lanes.  So a chunk of pages is fetched once and serves
all ``H`` heads: ``scores = Q_row (H, C) @ rows^T (C, T)`` and
``acc += P (H, T) @ rows[:, :value_width]`` are two plain MXU
contractions with no per-head layout at all.

Scheduling is ops/pallas/paged_attention.py's: a flat list of (slot,
chunk) steps written into scalar memory from the lengths, one loop over
the chunks of every active slot back to back, the next chunk's pages
(the next slot's first ones included) in flight while the current one
is computed, an f32 online softmax.  Only the pages a slot holds are
read; slots with nothing to attend read zeros.  Operands are rounded to
bf16, accumulation and the softmax statistics are f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.pallas import report as _report

_NEG_INF = -1e30
PAGES_PER_STEP = 16


def _kernel(len_ref, table_ref, q_ref, pool_hbm, o_ref,
            row_ref, chunk_ref, buf, sems, m_ref, l_ref, acc_ref, *,
            page: int, pages_per_step: int, pages_per_slot: int,
            value_width: int, sm_scale: float):
    step_tokens = pages_per_step * page

    def copies(g, slot):
        """The page DMAs of step ``g`` into buffer ``slot``, each with
        the condition it is issued (and waited for) under: a page past
        the slot's length is neither fetched nor waited for."""
        row, first = row_ref[g], chunk_ref[g] * pages_per_step
        out = []
        for j in range(pages_per_step):
            held = (first + j) * page < len_ref[row]
            phys = table_ref[row * pages_per_slot
                             + jnp.minimum(first + j, pages_per_slot - 1)]
            out.append((held, pltpu.make_async_copy(
                pool_hbm.at[phys], buf.at[slot, pl.ds(j * page, page)],
                sems.at[slot])))
        return out

    def start(g, slot):
        for held, copy in copies(g, slot):
            @pl.when(held)
            def _():
                copy.start()

    def wait(g, slot):
        for held, copy in copies(g, slot):
            @pl.when(held)
            def _():
                copy.wait()

    def list_row(row, g):
        def put(chunk, g):
            row_ref[g] = row
            chunk_ref[g] = chunk
            return g + 1

        return jax.lax.fori_loop(
            0, pl.cdiv(len_ref[row], step_tokens), put, g)

    total = jax.lax.fori_loop(0, q_ref.shape[0], list_row, 0)
    o_ref[...] = jnp.zeros_like(o_ref)
    # a page never fetched leaves its rows as they were: finite (zero
    # here, an older page later), so a zero weight times them is zero
    buf[...] = jnp.zeros_like(buf)

    @pl.when(total > 0)
    def _():
        start(0, 0)

    def body(g, _):
        slot = g % 2
        row, chunk, kv_len = row_ref[g], chunk_ref[g], len_ref[row_ref[g]]

        @pl.when(g + 1 < total)
        def _():
            start(g + 1, 1 - slot)

        @pl.when(chunk == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[row].astype(jnp.bfloat16)                  # (H, C)
        wait(g, slot)
        rows = buf[slot].astype(jnp.bfloat16)                # (T, C)
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (H, T)
        pos = chunk * step_tokens + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < kv_len, s, _NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(jnp.bfloat16), rows[:, :value_width],
            preferred_element_type=jnp.float32)              # (H, Vw)
        m_ref[...] = m_new

        @pl.when((chunk + 1) * step_tokens >= kv_len)
        def _():
            o_ref[row] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

        return 0

    jax.lax.fori_loop(0, total, body, 0)


def routes(x_shape, pool, table) -> bool:
    """Trace-time routing on what the caller sees: one query token a
    slot against a float pool whose page is whole sublane tiles of its
    dtype (so a page DMA needs no relayout), on the TPU.  Recorded like
    every kernel's route."""
    s, tq, _ = x_shape
    tile = 32 // pool.dtype.itemsize               # rows of one tile
    if not (tq == 1 and pool.dtype in (jnp.bfloat16, jnp.float32)
            and pool.shape[1] % tile == 0):
        return False
    if _report.force_pallas() or jax.default_backend() == "tpu":
        _report.record("latent_paged_attention", "pallas")
        return True
    _report.record("latent_paged_attention", "xla",
                   (s, pool.shape[1], pool.shape[2], table.shape[1]))
    return False


@functools.partial(jax.jit, static_argnames=(
    "value_width", "sm_scale", "pages_per_step", "interpret"))
def latent_paged_attn(q, pool, table, kv_len, *, value_width: int,
                      sm_scale: float,
                      pages_per_step: int = PAGES_PER_STEP,
                      interpret: bool = False):
    """Absorbed queries ``q`` (S, H, C) against each slot's first
    ``kv_len`` (S,) rows of ``pool`` (P, Q, C) through the block
    ``table`` (S, M): returns (S, H, value_width), zeros where
    ``kv_len`` is 0.  ``kv_len`` is clipped to the table's extent."""
    s, h, c = q.shape
    _, page, _ = pool.shape
    m = table.shape[1]
    pages_per_step = min(pages_per_step, m)
    step_tokens = pages_per_step * page
    kv_len = jnp.clip(kv_len.astype(jnp.int32), 0, m * page)
    steps = s * -(-m // pages_per_step)      # every slot at full extent
    kernel = functools.partial(
        _kernel, page=page, pages_per_step=pages_per_step,
        pages_per_slot=m, value_width=value_width, sm_scale=sm_scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec((s, h, c), lambda i, *_: (0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((s, h, value_width),
                                   lambda i, *_: (0, 0, 0)),
            scratch_shapes=[
                pltpu.SMEM((steps,), jnp.int32),     # step -> slot
                pltpu.SMEM((steps,), jnp.int32),     # step -> chunk
                pltpu.VMEM((2, step_tokens, c), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((h, 1), jnp.float32),     # running max
                pltpu.VMEM((h, 1), jnp.float32),     # running sum
                pltpu.VMEM((h, value_width), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((s, h, value_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_paged_attn",  # the device trace finds it by this
    )(kv_len, table.reshape(-1).astype(jnp.int32), q, pool)
