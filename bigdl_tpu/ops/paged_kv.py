"""Paged (and optionally int8-quantized) KV-cache array ops.

A layer declares the decode state it keeps per token as
``{leaf name: (heads, width)}`` (``decode_state()`` of the attention
layers: ``{"k": (H, D), "v": (H, D)}`` for multi-head attention,
``{"latent": (1, 640)}`` for latent attention), and everything here and
in serving/ (decode_programs.py, paging.py) allocates, writes, appends
and meters by that declaration: a dense cache leaf is ``(N, heads, T,
width)``, a pool leaf ``(P, Q, heads*width)`` (:func:`init_cache`,
:func:`init_pool`, :func:`state_leaves`).

A layer may also declare a leaf it keeps *a slot* and not a token: a
:class:`Block` (the state of a recurrent or state-space layer: a fixed
block whatever the slot's length).  Such a leaf is ``(N, *shape)`` in
the dense cache and ``(S, *shape)`` in the pool alike: it is indexed by
slot, never paged, and costs no page (:func:`page_bytes` leaves it
out).  The slot write copies a prefilled row's block whole into its
slot; the tick updates it in place.

The dense decode cache (``init_cache``) reserves
``max_len`` rows per slot up front — worst-case HBM whether or not a
request ever grows that long.  The paged layout breaks each layer's
cache into fixed-size pages,

    pool  {"k": (P, Q, H*D), "v": (P, Q, H*D), "length": (S,)}
          [+ "k_scale"/"v_scale": (P, Q*H8) f32 when int8-quantized;
             H8 = H rounded up to 8]

with a per-slot *block table* ``(S, M)`` int32 mapping each slot's
logical page ``0..M-1`` to a physical page in the pool.  The table is
host-managed (serving/paging.py) and enters the compiled tick as a
plain device argument — its *values* change as pages are allocated and
freed, but its shape never does, so the one-compiled-tick discipline
(docs/decoding.md) is preserved while retirement returns pages to the
free list at token granularity.

The shape is the layout.  The TPU stores an array by a layout it
derives from the shape: ``(P, Q, H, D)`` with minor dims (12, 64) gets
the page axis in the lanes (``{0,3,2,1:T(8,128)}``), into which no
token row can be scattered, so every program touching the pool copied
each leaf to row-major and back — four whole-pool copies a layer.  With
the heads side by side a page is ``Q`` rows of ``H*D`` lanes, stored
row-major in whole (8, 128) tiles: the ``(P*Q, H*D)`` view a token row
is scattered into is a bitcast, the scatter updates a donated leaf in
place, and a page is one contiguous run a DMA can fetch — or a slot
write can store (:func:`write_pages`: a prefilled row goes in as whole
pages, a sixteenth of the updates at ``Q`` = 16).  The scales
take the same form — one row of whole lanes a page (``Q*H8``), a
token's ``H`` scales a window of it.  The leading axis is always the
page (the HbmLedger's bytes per page: serving/paging.PagedCache).

Reading (nn/attention.py ``apply_paged`` chooses on what it sees): one
query token on a float pool on the TPU goes through the ``paged_attn``
kernel (ops/pallas/paged_attention.py), which fetches only the pages a
slot holds; longer queries (speculative verify, prefill chunks), the
int8 pool and other backends gather the full extent page by page
(:func:`paged_gather`) for the stock attention core.

Physical page 0 is reserved as the *trash page*: it is never allocated,
unmapped block-table entries point at it, and writes for inactive slots
are redirected to it.  That makes the scatter safe by construction — a
retired slot whose (stale) table still names freed pages can never
corrupt a page that was reassigned to another slot.

int8 mode stores K/V as int8 with a per-(token, head) scale
(``amax/127``, the symmetric scheme of ops/pallas/int8_matmul.py) for
~2x cache bytes.  On the read side the QK^T contraction against the
quantized K *is* the ``int8_matmul_dequant`` contract — int8 operand,
per-output-column scale — so when shapes are Pallas-eligible on TPU the
scores route through that kernel (and therefore through the PR-13
autotuner's ``int8_matmul`` family); everywhere else an XLA
dequantize-then-dot computes the identical result.  The speculative
verify pass (Tq == draft_k + 1) is the realistic customer, and its
shapes are registered in tools/kernel_shapes.INT8 for the autotuner
sweep and the pallas-routing lint rule.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class Block(NamedTuple):
    """A leaf kept whole a slot: ``shape`` of one slot's block, in
    ``dtype`` (None: the cache's)."""
    shape: tuple
    dtype: Optional[str] = None


def is_block(spec) -> bool:
    return isinstance(spec, Block)


def _block_zeros(spec: Block, batch: int, dtype):
    return jnp.zeros((batch,) + tuple(spec.shape), spec.dtype or dtype)


def num_logical_pages(max_len: int, page_size: int) -> int:
    """Block-table width: logical pages covering ``max_len`` tokens."""
    return -(-max_len // page_size)


# ---------------------------------------------------------------- int8
def quantize_kv(x):
    """Symmetric per-(..., row) int8 quantization over the last axis.

    Returns ``(q int8, scale f32)`` with ``scale.shape == x.shape[:-1]``
    and ``dequant = q * scale`` — the amax/127 scheme shared with
    ops/pallas/int8_matmul.py so the dequant matmul can reuse that
    kernel's scale epilogue.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
            ).astype(dtype)


# ---------------------------------------------------------------- pool
def state_leaves(cache) -> list:
    """The per-token leaves of one layer's cache or pool, by name (what
    the layer declared): all but ``length`` and the int8 scales."""
    return [k for k in cache if k != "length"
            and not k.endswith("_scale")]


def init_cache(leaves: dict, batch: int, max_len: int,
               dtype=jnp.float32):
    """One layer's dense cache for the declared ``leaves``
    ``{name: (heads, width)}``: ``(batch, heads, max_len, width)`` each
    and a per-row ``length``."""
    cache = {name: _block_zeros(spec, batch, dtype) if is_block(spec)
             else jnp.zeros((batch, spec[0], max_len, spec[1]), dtype)
             for name, spec in leaves.items()}
    cache["length"] = jnp.zeros((batch,), jnp.int32)
    return cache


def init_pool(num_pages: int, page_size: int, leaves: dict, batch: int,
              dtype=jnp.float32, quantized: bool = False):
    """One layer's paged pool for the declared ``leaves``
    ``{name: (heads, width)}`` (page 0 = reserved trash page).

    ``length`` is per *slot* (the serving grid's batch dim), exactly as
    in the dense cache, so retirement/length bookkeeping is layout-
    independent in the engine.
    """
    store = jnp.int8 if quantized else dtype
    pool = {name: _block_zeros(spec, batch, dtype) if is_block(spec)
            else jnp.zeros((num_pages, page_size, spec[0] * spec[1]), store)
            for name, spec in leaves.items()}
    pool["length"] = jnp.zeros((batch,), jnp.int32)
    if quantized:
        for name, (h, _) in ((n, v) for n, v in leaves.items()
                             if not is_block(v)):
            pool[name + "_scale"] = jnp.zeros(
                (num_pages, page_size * _scale_width(h)), jnp.float32)
    return pool


def _scale_width(num_heads: int) -> int:
    """Lanes a token's ``H`` scales take in a page's scale row: ``H``
    rounded up to 8, so a page of 16 tokens is whole 128-lane tiles."""
    return -(-num_heads // 8) * 8


def is_quantized(pool) -> bool:
    return any(name.endswith("_scale") for name in pool)


def page_bytes(page_size: int, leaves: dict, dtype=jnp.float32,
               quantized: bool = False) -> int:
    """Bytes one physical page costs in one layer's pool (every
    declared leaf + scales) — the unit the HbmLedger resident lane
    reports in."""
    per_tok = 0
    for h, w in (v for v in leaves.values() if not is_block(v)):
        per_tok += h * w + _scale_width(h) * 4 if quantized \
            else h * w * jnp.dtype(dtype).itemsize
    return page_size * per_tok


def flat_positions(table, pos, active, page_size, max_len):
    """Map logical positions to physical flat indices.

    ``table`` (S, M) int32, ``pos`` (S, T) int32, ``active`` (S,) bool.
    Returns ``idx`` (S, T) int32 into the pool's flattened (P*Q, ...)
    view.  Unsafe positions — inactive rows, positions beyond the
    logical extent — land on the trash page (flat indices [0, Q)).
    """
    m = table.shape[1]
    logical = pos // page_size                            # (S, T)
    ok = (pos >= 0) & (pos < max_len) & active[:, None]
    phys = jnp.take_along_axis(
        table, jnp.clip(logical, 0, m - 1), axis=1)       # (S, T)
    idx = phys * page_size + pos % page_size
    return jnp.where(ok, idx, pos % page_size)            # trash page 0


def write_pages(pool, name, table_row, vals):
    """Write a slot's first token rows ``vals`` (T, H, D) into leaf
    ``name`` of ``pool`` (a dict, updated in place) as whole pages
    through the slot's block-table row (M,): ``ceil(T / Q)`` contiguous
    page runs instead of ``T`` row updates (the TPU's scatter is a loop
    over its updates).  Rows that pad ``T`` to whole pages lie past any
    length the slot can have yet; unmapped entries name the trash
    page."""
    t, h, d = vals.shape
    _, page, hd = pool[name].shape
    n = -(-t // page)
    at = table_row[:n]
    if is_quantized(pool):
        vals, scale = quantize_kv(vals)
        leaf = pool[name + "_scale"]
        width = leaf.shape[1] // page
        scale = jnp.pad(scale, ((0, n * page - t), (0, width - h)))
        pool[name + "_scale"] = leaf.at[at].set(
            scale.reshape(n, page * width))
    rows = jnp.pad(vals.reshape(t, hd), ((0, n * page - t), (0, 0)))
    pool[name] = pool[name].at[at].set(
        rows.reshape(n, page, hd).astype(pool[name].dtype))


@jax.named_scope("paged_append")
def paged_append(pool, table, active, new, page_size, max_len):
    """Scatter the new token rows ``new`` ``{leaf: (S, H, T, D)}`` into
    the pool at each slot's current ``length``..``length + T - 1``;
    returns the updated pool (donation-friendly: pure ``.at[].set`` on
    the pool leaves).  ``length`` itself is NOT advanced here — the
    model layer owns the length bookkeeping so dense and paged advance
    identically."""
    s, _, t, _ = next(iter(new.values())).shape
    pos = pool["length"][:, None] + jnp.arange(t)[None]   # (S, T)
    idx = flat_positions(table, pos, active, page_size, max_len)
    flat = idx.reshape(s * t)
    pool = dict(pool)
    for name, rows in new.items():
        h, d = rows.shape[1], rows.shape[3]
        vals = rows.transpose(0, 2, 1, 3).reshape(s * t, h, d)
        store = pool[name].shape
        if is_quantized(pool):
            # a token's H scales are a window of its page's scale row
            vals, scale = quantize_kv(vals)
            leaf = pool[name + "_scale"]
            width = leaf.shape[1] // page_size
            at = jnp.stack([flat // page_size,
                            flat % page_size * width], axis=1)
            pool[name + "_scale"] = jax.lax.scatter(
                leaf, at, scale, jax.lax.ScatterDimensionNumbers(
                    update_window_dims=(1,), inserted_window_dims=(0,),
                    scatter_dims_to_operand_dims=(0, 1)))
        # the flat view is a bitcast of the row-major pool and the
        # scatter updates a donated leaf's buffer in place
        pool[name] = pool[name].reshape(-1, h * d).at[flat].set(
            vals.reshape(s * t, h * d).astype(pool[name].dtype)
        ).reshape(store)
    return pool


def gather_pages(leaf, table, page_size):
    """A slot-major view of the pages the table names: ``leaf``
    (P, Q, C) or (P, Q*C) -> (S, M*Q, C).  The gather moves whole
    pages, each one contiguous run of the row-major pool."""
    s, m = table.shape
    return jnp.take(leaf, table, axis=0).reshape(s, m * page_size, -1)


def _gather_heads(pool, name, table, num_heads):
    """Leaf ``name`` gathered and split by head: ``(x (S, H, L, D) in
    the pool's dtype, scale (S, H, L) or None)``."""
    page = pool[name].shape[1]
    rows = gather_pages(pool[name], table, page)         # (S, L, H*D)
    s, l, hd = rows.shape
    x = rows.reshape(s, l, num_heads, hd // num_heads).transpose(
        0, 2, 1, 3)
    if not is_quantized(pool):
        return x, None
    scale = gather_pages(pool[name + "_scale"], table, page)
    return x, scale[:, :, :num_heads].transpose(0, 2, 1)


@jax.named_scope("paged_gather")
def paged_gather(pool, table, num_heads, dtype):
    """Gather each slot's full logical extent out of the pool:
    returns ``(k, v)`` each (S, H, M*Q, D) in ``dtype`` (dequantized
    when the pool is int8).  Entries past a slot's ``length`` come from
    unmapped/trash pages and carry garbage — callers mask by length,
    the same stale-above-length invariant the dense cache relies on."""
    out = []
    for name in ("k", "v"):
        x, scale = _gather_heads(pool, name, table, num_heads)
        out.append(x.astype(dtype) if scale is None
                   else dequantize_kv(x, scale, dtype))
    return out[0], out[1]


@jax.named_scope("paged_gather")
def paged_gather_q(pool, table, num_heads):
    """Raw gather for the int8 Pallas score path: returns
    ``(k_q (S, H, L, D) int8, k_scale (S, H, L) f32, v (S, H, L, D)
    f32)`` — K stays quantized (the kernel dequantizes via its scale
    epilogue), V is dequantized for the XLA PV contraction whose
    per-contraction-row scale has no ``int8_matmul_dequant`` analogue."""
    k_q, k_s = _gather_heads(pool, "k", table, num_heads)
    v = dequantize_kv(*_gather_heads(pool, "v", table, num_heads),
                      jnp.float32)
    return k_q, k_s, v


# ------------------------------------------------- int8 kernel routing
def _int8_eligible(tq: int, length: int, head_dim: int) -> bool:
    """Static trace-time check: may the quantized QK^T / PV matmuls
    route through ops/pallas/int8_matmul.py on this backend?  Mirrors
    that kernel's own eligibility (128-aligned contraction/output dims,
    a block size that divides Tq) plus a hard TPU-backend gate — the
    CPU tier always takes the XLA dequant path."""
    if jax.default_backend() != "tpu":
        return False
    from bigdl_tpu.ops.pallas import int8_matmul as i8

    return (bool(i8.candidate_params((tq, head_dim, length)))
            and bool(i8.candidate_params((tq, length, head_dim))))


def int8_scores(q, k_q, k_scale, out_dtype):
    """QK^T against int8 K via the Pallas dequant-matmul path.

    ``q`` (S, H, Tq, D) float, ``k_q`` (S, H, L, D) int8, ``k_scale``
    (S, H, L).  The query is quantized per-tensor and its scalar scale
    folded into the kernel's per-output-column scale row — exactly the
    ``(x_q @ w_q) * scale_row`` contract of int8_matmul_dequant, with
    cache positions as the output columns.  Registered shapes live in
    tools/kernel_shapes.INT8 so the autotuner sweeps them.
    """
    from bigdl_tpu.ops.pallas.int8_matmul import int8_matmul_dequant

    qmax = jnp.maximum(jnp.max(jnp.abs(q.astype(jnp.float32))), 1e-8)
    q_scale = qmax / 127.0
    q_q = jnp.clip(jnp.round(q.astype(jnp.float32) / q_scale),
                   -127, 127).astype(jnp.int8)

    def one(qr, kr, sr):                # (Tq, D) x (L, D) -> (Tq, L)
        return int8_matmul_dequant(
            qr, kr.T, (sr * q_scale).astype(jnp.float32),
            out_dtype=jnp.float32)

    scores = jax.vmap(jax.vmap(one))(q_q, k_q, k_scale)
    return scores.astype(out_dtype)
