"""Continuous-batching cached-decode engine (docs/decoding.md).

The autoregressive analog of :class:`~bigdl_tpu.serving.engine.
ServingEngine`: where the stateless engine amortizes dispatch across a
batch of independent forwards, this engine amortizes *decoding* across
a fixed grid of in-flight sequences.

Design:

* **Slot grid** — one static-shape KV cache pytree holds ``slots``
  independent sequences (per-row ``length``; see
  ``MultiHeadAttention.init_cache``).  ONE compiled decode step
  advances every occupied slot per tick; shapes never depend on
  occupancy, so steady-state decode never recompiles no matter how
  requests come and go.
* **Prefill through the BucketGrid** — prompts are padded onto the
  declared (batch x prompt-length) grid and run through a compiled
  prefill that returns the first generated token plus the prompt's
  KV rows; a compiled ``write_slot`` splices those rows into the grid
  cache (donated: the grid cache is rebound, never copied).
* **Continuous batching** — a finished sequence (EOS / token budget /
  deadline) retires at TOKEN granularity and frees its slot
  immediately; the next waiting request prefills into it while the
  other slots keep decoding.  ``continuous=False`` degrades to static
  run-to-completion waves (admit only into an empty grid) — the
  baseline arm of ``bench.py --decode-ab``.
* **Deadline semantics** — a request whose deadline expires before its
  prefill fails fast with :class:`DeadlineExceededError` (same as the
  stateless engine); once decoding has started, an expiring deadline
  *truncates*: the tokens generated so far are delivered as the
  result.  Admission control (bounded queue -> ``QueueFullError``)
  and per-request exception delivery mirror :class:`ServingEngine`.
* **Metrics** — tokens/s, slot occupancy, prefill/decode split and
  per-tick (== per-token) latency percentiles on
  :class:`~bigdl_tpu.serving.metrics.ServingMetrics`, exportable to
  TensorBoard via ``ServingMetrics.write_summary``.
"""
from __future__ import annotations

import collections
import itertools
import queue
import threading
import time
from typing import List, Optional, Sequence

import jax
import numpy as np
from jax.profiler import StepTraceAnnotation

from bigdl_tpu.serving.bucketing import BucketGrid
from bigdl_tpu.serving.engine import (
    DeadlineExceededError,
    EngineClosedError,
    QueueFullError,
    ServingFuture,
)
from bigdl_tpu.serving.metrics import PeriodicMetricsLogger, ServingMetrics
from bigdl_tpu.telemetry import costmodel, programs
from bigdl_tpu.telemetry import requests as request_xray
from bigdl_tpu.telemetry import workload
from bigdl_tpu.telemetry.tracer import CAT_DECODE, get_tracer, set_correlation


def decode_tick_fn(model):
    """The raw whole-grid decode step (see :func:`build_decode_tick`).
    ``active`` gates bookkeeping only: inactive rows still flow through
    the compute (their outputs are ignored and their lengths frozen),
    which is what keeps the program occupancy-independent."""
    import jax.numpy as jnp

    def tick(params, state, cache, tokens, active):
        old_len = {lk: c["length"] for lk, c in cache.items()}
        logits, cache = model.decode_step(params, state, cache, tokens)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(active, nxt, tokens)
        # freeze retired rows at their final length so an idle slot's
        # length can never walk off the end of the cache
        cache = {lk: dict(c, length=jnp.where(active, c["length"],
                                              old_len[lk]))
                 for lk, c in cache.items()}
        return cache, nxt

    return tick


def build_decode_tick(model, **jit_kw):
    """The jitted whole-grid decode step — kept as a named top-level
    builder so graft-lint's ``decode_step`` target audits exactly the
    program every tick dispatches (donated cache, no host transfer,
    static shapes)."""
    import jax

    return jax.jit(decode_tick_fn(model), donate_argnums=(2,), **jit_kw)


def prefill_fn(model, max_len: int, dtype=None):
    """Raw prompt prefill: fresh cache rows for a padded prompt batch
    + the next-token logits at each row's true length."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32

    def prefill(params, state, ids, lengths):
        cache = model.init_cache(ids.shape[0], max_len, dtype)
        return model.prefill(params, state, ids, cache, lengths=lengths)

    return jax.named_scope("prefill")(prefill)


def build_prefill(model, max_len: int, dtype=None, **jit_kw):
    import jax

    return jax.jit(prefill_fn(model, max_len, dtype), **jit_kw)


def write_slot_fn():
    """Raw slot splice: copy prefill-batch row ``row`` into grid slot
    ``slot`` across every cache leaf."""
    import jax

    def write(grid_cache, batch_cache, row, slot):
        def upd(g, b):
            r = jax.lax.dynamic_slice_in_dim(b, row, 1, axis=0)
            return jax.lax.dynamic_update_slice_in_dim(
                g, r.astype(g.dtype), slot, axis=0)

        return jax.tree_util.tree_map(upd, grid_cache, batch_cache)

    return jax.named_scope("slot_write")(write)


def build_write_slot(**jit_kw):
    """Jitted slot splice; the grid cache is donated — admission
    rebinds it in place of copying the whole grid."""
    import jax

    return jax.jit(write_slot_fn(), donate_argnums=(0,), **jit_kw)


# ---------------------------------------------------------------------------
# in-tick sampling (ISSUE 14; docs/decoding.md §Sampling)
# ---------------------------------------------------------------------------
def sample_logits(logits, keys, temp, top_k, top_p):
    """Temperature / top-k / top-p sampling with fully static shapes.

    ``logits`` (S, V); ``keys`` (S, 2) raw uint32 threefry keys —
    per-slot PRNG state threaded through the slot grid as *data*, so
    request seeds never become compile-time constants (graft-lint's
    ``paged_decode_tick`` parity check is exactly this property);
    ``temp``/``top_p`` (S,) f32 and ``top_k`` (S,) int32 are per-slot.

    The filter runs in sorted space: rank < top_k (``top_k <= 0`` keeps
    all V), exclusive-cumsum < top_p (``top_p >= 1`` keeps all), the
    top-1 always kept; the draw is gumbel-argmax over the masked
    logits, unsorted back through the argsort permutation.  Rows with
    ``temp <= 0`` are the caller's greedy rows — it takes the exact
    ``argmax`` instead (the parity oracle stays bit-identical), and
    calls this only in a tick where an active row has ``temp > 0``
    (:func:`_next_tokens`).
    """
    import jax
    import jax.numpy as jnp

    v = logits.shape[-1]
    t = jnp.maximum(temp, 1e-6)[:, None]
    scaled = logits.astype(jnp.float32) / t
    order = jnp.argsort(-scaled, axis=-1)                  # (S, V)
    l_sorted = jnp.take_along_axis(scaled, order, axis=-1)
    ranks = jnp.arange(v)[None, :]
    k_eff = jnp.where(top_k > 0, top_k, v)[:, None]
    keep = ranks < k_eff
    probs = jax.nn.softmax(l_sorted, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep &= (cum - probs) < jnp.minimum(top_p, 1.0)[:, None]
    keep = keep.at[:, 0].set(True)
    masked = jnp.where(keep, l_sorted, -1e30)
    gumbel = jax.vmap(lambda k: jax.random.gumbel(k, (v,)))(keys)
    pick = jnp.argmax(masked + gumbel, axis=-1)
    return jnp.take_along_axis(order, pick[:, None],
                               axis=-1)[:, 0].astype(jnp.int32)


def _next_tokens(logits, tokens, active, keys, temp, top_k, top_p):
    """Shared tick epilogue: greedy rows take the exact argmax, sampled
    rows (temp > 0) the gumbel draw; inactive rows hold their token and
    their key (reproducibility: a slot's key chain advances once per
    tick it actually decodes).

    :func:`sample_logits` runs only in a tick where some active row
    samples (``lax.cond`` on data the tick already receives): its sort,
    softmax, cumulative sum and ``S x V`` noise draws cost 17 of 18 ms
    at V = 50272 and every row of a greedy grid threw them away.  A
    tick with one such row pays for the whole grid, as before; tokens
    and keys are the same either way."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sampled = jax.lax.cond(
            jnp.any(active & (temp > 0.0)),
            lambda: sample_logits(logits, keys, temp, top_k, top_p),
            lambda: jnp.zeros_like(greedy))
        nxt = jnp.where(temp > 0.0, sampled, greedy)
        nxt = jnp.where(active, nxt, tokens)
        split = jax.vmap(lambda k: jax.random.split(k, 2)[0])(keys)
        keys = jnp.where(active[:, None], split, keys)
    return nxt, keys


def sampling_tick_fn(model):
    """The whole-grid decode step with in-tick sampling — the engine's
    default tick.  Signature grows per-slot sampling state (keys, temp,
    top_k, top_p), all occupancy-independent (S,)-shaped device args;
    greedy requests ride along as temp == 0 rows."""
    import jax.numpy as jnp

    def tick(params, state, cache, tokens, active, keys, temp, top_k,
             top_p):
        old_len = {lk: c["length"] for lk, c in cache.items()}
        logits, cache = model.decode_step(params, state, cache, tokens)
        nxt, keys = _next_tokens(logits, tokens, active, keys, temp,
                                 top_k, top_p)
        cache = {lk: dict(c, length=jnp.where(active, c["length"],
                                              old_len[lk]))
                 for lk, c in cache.items()}
        return cache, nxt, keys

    return tick


def build_sampling_tick(model, **jit_kw):
    import jax

    return jax.jit(sampling_tick_fn(model), donate_argnums=(2,),
                   **jit_kw)


# ---------------------------------------------------------------------------
# paged KV tick + slot write (ISSUE 14; docs/decoding.md §Paged KV)
# ---------------------------------------------------------------------------
def paged_tick_fn(model):
    """The sampling tick over the paged pool: identical math with the
    host-managed block ``table`` (S, M) as one more device argument —
    its values change as pages move, its shape never does."""
    import jax.numpy as jnp

    def tick(params, state, cache, table, tokens, active, keys, temp,
             top_k, top_p):
        old_len = {lk: c["length"] for lk, c in cache.items()}
        # what the model counts inside its step (tokens per expert
        # held...) rides out with the tokens and reaches
        # ``loop/tick_dispatch`` while a trace is live
        logits, cache, counters = model.decode_step_paged(
            params, state, cache, table, tokens, active)
        nxt, keys = _next_tokens(logits, tokens, active, keys, temp,
                                 top_k, top_p)
        cache = {lk: dict(c, length=jnp.where(active, c["length"],
                                              old_len[lk]))
                 for lk, c in cache.items()}
        return cache, nxt, keys, counters

    return tick


def build_paged_tick(model, **jit_kw):
    """Jitted paged tick (donated pool) — graft-lint's
    ``paged_decode_tick`` target audits exactly this program."""
    import jax

    return jax.jit(paged_tick_fn(model), donate_argnums=(2,), **jit_kw)


def paged_write_slot_fn():
    """Splice one dense prefill-batch row into a slot's pages: every
    per-token leaf the layer declared (K and V, or a latent row;
    quantized when the pool is int8) goes through the slot's block-table
    row as whole pages.  Unmapped logical pages redirect to the trash
    page — only the pages the allocator granted are ever written."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops import paged_kv

    def write(pool_cache, table_row, batch_cache, row, slot):
        out = {}
        for lk, pool in pool_cache.items():
            bc = batch_cache[lk]
            new = dict(pool)
            for name in paged_kv.state_leaves(pool):
                r = jax.lax.dynamic_index_in_dim(
                    bc[name], row, axis=0, keepdims=False)  # (H,T,D)
                paged_kv.write_pages(new, name, table_row,
                                     r.transpose(1, 0, 2))
            lrow = jax.lax.dynamic_slice_in_dim(bc["length"], row, 1,
                                                axis=0)
            new["length"] = jax.lax.dynamic_update_slice_in_dim(
                pool["length"], lrow.astype(jnp.int32), slot, axis=0)
            out[lk] = new
        return out

    return jax.named_scope("slot_write")(write)


def build_paged_write_slot(**jit_kw):
    import jax

    return jax.jit(paged_write_slot_fn(), donate_argnums=(0,), **jit_kw)


def page_reset_fn():
    """Zero a batch of physical pages (the page-free program).  Purely
    hygienic — the stale-above-length invariant already makes freed
    bytes unreachable — and therefore off by default
    (``BIGDL_TPU_PAGE_ZERO=1``); page ids of 0 re-zero the trash page,
    so a short free list pads with 0."""
    import jax.numpy as jnp

    def reset(pool_cache, pages):
        out = {}
        for lk, pool in pool_cache.items():
            new = dict(pool)
            for name, leaf in pool.items():
                if name == "length":
                    continue
                z = jnp.zeros((pages.shape[0],) + leaf.shape[1:],
                              leaf.dtype)
                new[name] = leaf.at[pages].set(z)
            out[lk] = new
        return out

    return reset


def build_page_reset(**jit_kw):
    import jax

    return jax.jit(page_reset_fn(), donate_argnums=(0,), **jit_kw)


# ---------------------------------------------------------------------------
# chunked prefill (ISSUE 14; docs/decoding.md §Chunked prefill)
# ---------------------------------------------------------------------------
def prefill_chunk_fn(model):
    """One bounded prompt chunk through a batch-1 staging cache:
    ``model.extend`` appends at the staging cache's current length, so
    the same compiled program serves the first chunk (fresh cache) and
    every later one — a long prompt costs N dispatches of this program
    interleaved with grid ticks instead of one giant stalling prefill.
    ``advance`` (1,) is the chunk's true token count (the final chunk
    is padded); returns the last *valid* position's logits — only the
    final chunk's matter (they seed token 0)."""
    import jax.numpy as jnp

    def chunk(params, state, cache, ids, advance):
        logits, cache = model.extend(params, state, cache, ids,
                                     advance=advance)
        last = jnp.take_along_axis(
            logits,
            (jnp.maximum(advance, 1) - 1)[:, None, None].astype(
                jnp.int32), axis=1)[:, 0]
        return last, cache

    return chunk


def build_prefill_chunk(model, **jit_kw):
    import jax

    return jax.jit(prefill_chunk_fn(model), donate_argnums=(2,),
                   **jit_kw)


# ---------------------------------------------------------------------------
# speculative decoding (ISSUE 14; docs/decoding.md §Speculative)
# ---------------------------------------------------------------------------
def draft_propose_fn(draft_model, k: int):
    """k greedy draft steps in ONE compiled program (a ``lax.scan`` of
    ``decode_step`` — one dispatch + one host sync per round instead of
    k).  The scan runs k+1 steps so the cache also ingests the last
    proposal (needed when the verify accepts the whole draft); the
    extra step's output is discarded.

    Draft lengths are *set* from the host-tracked truth first: a verify
    rollback shortens the target cache, and syncing here self-heals the
    draft to the same prefix (entries above it are stale-above-length).
    """
    import jax
    import jax.numpy as jnp

    def propose(params, state, dcache, tokens, lengths, active):
        dcache = {lk: dict(c, length=lengths)
                  for lk, c in dcache.items()}

        def body(carry, _):
            cache, tok = carry
            logits, cache = draft_model.decode_step(params, state,
                                                    cache, tok)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            nxt = jnp.where(active, nxt, tok)
            return (cache, nxt), nxt

        (dcache, _), outs = jax.lax.scan(body, (dcache, tokens), None,
                                         length=k + 1)
        proposals = jnp.moveaxis(outs[:k], 0, 1)           # (S, k)
        dcache = {lk: dict(c, length=jnp.where(active, c["length"],
                                               lengths))
                  for lk, c in dcache.items()}
        return dcache, proposals

    return propose


def build_draft_propose(draft_model, k: int, **jit_kw):
    import jax

    return jax.jit(draft_propose_fn(draft_model, k),
                   donate_argnums=(2,), **jit_kw)


def spec_verify_fn(model, k: int, paged: bool = False):
    """One big-model pass over ``[t_last, d_0..d_{k-1}]`` (S, k+1):
    ``b = argmax`` of every position's logits, the accepted prefix is
    the longest run of drafts matching ``b``, and the emitted tokens
    ``b[:, :n_acc + 1]`` are ALWAYS the big model's own argmaxes — the
    speculative arm is exact-match with the plain greedy tick by
    construction.  Cache lengths roll back in-graph to
    ``old + n_emit``; rejected-draft rows above are stale-above-length.
    """
    import jax.numpy as jnp

    def verify(params, state, cache, tokens, draft, active):
        old_len = {lk: c["length"] for lk, c in cache.items()}
        x = jnp.concatenate([tokens[:, None], draft], axis=1)
        logits, cache = model.extend(params, state, cache, x)
        b = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        acc = jnp.cumprod((b[:, :k] == draft).astype(jnp.int32), axis=1)
        n_emit = jnp.where(active, acc.sum(axis=1) + 1, 0).astype(
            jnp.int32)
        cache = {lk: dict(c, length=old_len[lk] + n_emit)
                 for lk, c in cache.items()}
        emitted = jnp.where(active[:, None], b, tokens[:, None])
        return cache, emitted, n_emit

    def verify_paged(params, state, cache, table, tokens, draft,
                     active):
        old_len = {lk: c["length"] for lk, c in cache.items()}
        x = jnp.concatenate([tokens[:, None], draft], axis=1)
        logits, cache = model.extend_paged(params, state, cache, table,
                                           x, active)
        b = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        acc = jnp.cumprod((b[:, :k] == draft).astype(jnp.int32), axis=1)
        n_emit = jnp.where(active, acc.sum(axis=1) + 1, 0).astype(
            jnp.int32)
        cache = {lk: dict(c, length=old_len[lk] + n_emit)
                 for lk, c in cache.items()}
        emitted = jnp.where(active[:, None], b, tokens[:, None])
        return cache, emitted, n_emit

    return verify_paged if paged else verify


def build_spec_verify(model, k: int, paged: bool = False, **jit_kw):
    import jax

    return jax.jit(spec_verify_fn(model, k, paged=paged),
                   donate_argnums=(2,), **jit_kw)


def deviceless_decode_check(model, *, slots: int = 8, max_len: int = 160,
                            prompt_buckets: Sequence[int] = (8, 16, 32),
                            prefill_batch_sizes: Sequence[int] = (1, 4, 8),
                            dtype=None, topology: str = "v5e:1x1",
                            log=None,
                            page_size: Optional[int] = None,
                            num_pages: Optional[int] = None,
                            kv_dtype=None,
                            prefill_chunk: Optional[int] = None,
                            draft_model=None,
                            draft_k: int = 3) -> int:
    """Compile every program the decode engine dispatches — the grid
    tick (greedy and sampling), each declared prefill bucket, and the
    slot writes — against a deviceless TPU topology (the
    tools/tpu_aot_check.py machinery), so a decode rollout is
    Mosaic-lowering-proven before any chip window
    (``tools/serving_aot_check.py --decode``).  ``page_size`` adds the
    paged tick + paged slot write + page reset (``kv_dtype='int8'``
    compiles the quantized pool variant too), ``prefill_chunk`` the
    chunked-prefill program, and ``draft_model`` the speculative
    propose/verify pair.  Returns the failure count; ``log`` receives
    one line per program."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    dtype = dtype or jnp.float32
    log = log or (lambda s: None)
    topo = topologies.get_topology_desc(
        topology_name=topology, platform="tpu",
        chips_per_host_bounds=[1, 1, 1])
    mesh = Mesh(np.array(topo.devices), ("d",))
    sh = NamedSharding(mesh, P())
    var = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: model.init_cache(slots, max_len,
                                                    dtype))
    S = jax.ShapeDtypeStruct
    failures = 0

    def try_compile(tag, jitted, *args):
        nonlocal failures
        try:
            jitted.lower(*args).compile()
            log(f"{tag}: OK")
        except Exception as e:
            failures += 1
            log(f"{tag}: FAIL {str(e)[:200]}")

    shard = dict(in_shardings=sh, out_shardings=sh)
    tok = S((slots,), jnp.int32)
    act = S((slots,), jnp.bool_)
    samp = (S((slots, 2), jnp.uint32), S((slots,), jnp.float32),
            S((slots,), jnp.int32), S((slots,), jnp.float32))
    try_compile("decode tick", build_decode_tick(model, **shard),
                var["params"], var["state"], cache, tok, act)
    try_compile("sampling tick", build_sampling_tick(model, **shard),
                var["params"], var["state"], cache, tok, act, *samp)
    pf = build_prefill(model, max_len, dtype, **shard)
    grid = BucketGrid([(int(t),) for t in prompt_buckets],
                      prefill_batch_sizes, pad_value=0)
    for bucket in grid.declared_buckets():
        try_compile(f"prefill {bucket.batch}x{bucket.dims[0]}", pf,
                    var["params"], var["state"],
                    S((bucket.batch,) + bucket.dims, jnp.int32),
                    S((bucket.batch,), jnp.int32))
    wr = build_write_slot(**shard)
    for b in grid.batch_sizes:
        bcache = jax.eval_shape(lambda b=b: model.init_cache(b, max_len,
                                                             dtype))
        try_compile(f"write_slot batch={b}", wr, cache, bcache,
                    S((), jnp.int32), S((), jnp.int32))
    if page_size:
        from bigdl_tpu.serving import paging

        n_pages = num_pages or paging.default_num_pages(
            slots, max_len, page_size)
        m = -(-max_len // page_size)
        table = S((slots, m), jnp.int32)
        trow = S((m,), jnp.int32)
        variants = [("fp", None)]
        if kv_dtype:
            variants.append((str(kv_dtype), kv_dtype))
        for tag, kvd in variants:
            pcache = jax.eval_shape(
                lambda kvd=kvd: model.init_paged_cache(
                    n_pages, page_size, slots, dtype, kv_dtype=kvd))
            try_compile(f"paged tick [{tag}]",
                        build_paged_tick(model, **shard),
                        var["params"], var["state"], pcache, table,
                        tok, act, *samp)
            pwr = build_paged_write_slot(**shard)
            for b in grid.batch_sizes:
                bcache = jax.eval_shape(
                    lambda b=b: model.init_cache(b, max_len, dtype))
                try_compile(f"paged write_slot batch={b} [{tag}]", pwr,
                            pcache, trow, bcache, S((), jnp.int32),
                            S((), jnp.int32))
            try_compile(f"page reset [{tag}]",
                        build_page_reset(**shard), pcache,
                        S((m,), jnp.int32))
            if draft_model is not None:
                try_compile(
                    f"spec verify paged k={draft_k} [{tag}]",
                    build_spec_verify(model, draft_k, paged=True,
                                      **shard),
                    var["params"], var["state"], pcache, table, tok,
                    S((slots, draft_k), jnp.int32), act)
    if prefill_chunk:
        staging = jax.eval_shape(lambda: model.init_cache(1, max_len,
                                                          dtype))
        try_compile(f"prefill chunk C={prefill_chunk}",
                    build_prefill_chunk(model, **shard),
                    var["params"], var["state"], staging,
                    S((1, prefill_chunk), jnp.int32), S((1,), jnp.int32))
    if draft_model is not None:
        dvar = jax.eval_shape(
            lambda: draft_model.init(jax.random.PRNGKey(0)))
        dcache = jax.eval_shape(
            lambda: draft_model.init_cache(slots, max_len, dtype))
        try_compile(f"draft propose k={draft_k}",
                    build_draft_propose(draft_model, draft_k, **shard),
                    dvar["params"], dvar["state"], dcache, tok,
                    S((slots,), jnp.int32), act)
        try_compile(f"spec verify k={draft_k}",
                    build_spec_verify(model, draft_k, **shard),
                    var["params"], var["state"], cache, tok,
                    S((slots, draft_k), jnp.int32), act)
    return failures


class _DecodeRequest:
    __slots__ = ("prompt", "max_new", "fut", "t_submit", "deadline",
                 "rid", "temp", "top_k", "top_p", "key")

    def __init__(self, prompt, max_new, fut, t_submit, deadline, rid=0,
                 temp=0.0, top_k=0, top_p=1.0, key=None):
        self.prompt = prompt
        self.max_new = max_new
        self.fut = fut
        self.t_submit = t_submit
        self.deadline = deadline
        self.rid = rid  # correlation ID joining enqueue->deliver spans
        self.temp = temp
        self.top_k = top_k
        self.top_p = top_p
        # raw (2,) uint32 threefry key — derived from the request seed,
        # threaded through the tick as data (never a compile constant)
        self.key = key if key is not None else np.zeros((2,), np.uint32)


def _key_for_seed(seed: int) -> np.ndarray:
    """The raw uint32 pair ``jax.random.PRNGKey(seed)`` would hold —
    built host-side so submission never touches the device."""
    seed = int(seed)
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


def _host_sample(logits, req: "_DecodeRequest") -> int:
    """Host-side mirror of :func:`sample_logits` for token 0 (the
    prefill's next-token logits are already on the host at admission,
    so sampling them here costs no extra compiled program).  Greedy
    requests take the exact argmax; sampled requests draw from their
    own deterministic stream (seeded off the request key), independent
    of the device chain the tick advances."""
    logits = np.asarray(logits)
    if req.temp <= 0.0:
        return int(np.argmax(logits))
    l = logits.astype(np.float64) / max(float(req.temp), 1e-6)
    order = np.argsort(-l)
    ls = l[order]
    keep = np.arange(ls.size) < (req.top_k if req.top_k > 0 else ls.size)
    p = np.exp(ls - ls.max())
    p = p / p.sum()
    keep &= (np.cumsum(p) - p) < min(float(req.top_p), 1.0)
    keep[0] = True
    ls = np.where(keep, ls, -1e30)
    seed64 = (int(req.key[0]) << 32) | int(req.key[1])
    g = np.random.default_rng(seed64).gumbel(size=ls.size)
    return int(order[int(np.argmax(ls + g))])


class _Slot:
    __slots__ = ("req", "generated", "times")

    def __init__(self, req: _DecodeRequest, first_token: int,
                 t_first: float):
        self.req = req
        self.generated = [first_token]
        # perf_counter time of each generated token: the prefill token
        # at host_sample, later ones at the end of their tick's wait
        self.times = [t_first]


_CLOSE = object()  # queue sentinel


class DecodeEngine:
    """KV-cached incremental decoding with continuous batching.

    ``model`` must expose the cached-decode trio
    ``init_cache``/``prefill``/``decode_step`` (``nn.Transformer``).
    ``slots`` sequences decode concurrently from one compiled tick;
    ``max_len`` bounds each row's cache (prompt + generated - 1 must
    fit).  Per-request sampling (``temperature``/``top_k``/``top_p``/
    ``seed``) runs inside the compiled tick; the default is greedy and
    greedy rows take the exact argmax — beam search stays on
    ``model.generate``, which threads the same cache.

    ``kv_layout="paged"`` swaps the dense per-slot cache for the paged
    pool of ops/paged_kv.py (``page_size``/``num_pages``; retirement
    frees pages back to a host-side :class:`~bigdl_tpu.serving.paging.
    PageAllocator`), and ``kv_dtype="int8"`` stores the pool quantized.
    ``prefill_chunk=C`` feeds prompts longer than the largest declared
    bucket through a batch-1 chunked prefill, ``C`` tokens per loop
    iteration, instead of stalling the tick.  ``draft=(draft_model,
    draft_variables)`` turns on speculative decoding: each round the
    draft proposes ``draft_k`` tokens and one verify pass of the big
    model accepts the longest matching prefix (greedy-only; emitted
    tokens are exactly the big model's argmaxes).
    """

    def __init__(self, model, variables: dict, *,
                 slots: int = 8,
                 max_len: int = 160,
                 prompt_buckets: Sequence[int] = (8, 16, 32),
                 prefill_batch_sizes: Sequence[int] = (1, 4, 8),
                 eos_id: Optional[int] = None,
                 max_queue: int = 1024,
                 default_deadline_ms: Optional[float] = None,
                 continuous: bool = True,
                 warmup: bool = True,
                 start: bool = True,
                 metrics: Optional[ServingMetrics] = None,
                 metrics_log_every_s: Optional[float] = None,
                 kv_layout: str = "dense",
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 kv_dtype=None,
                 prefill_chunk: Optional[int] = None,
                 draft: Optional[tuple] = None,
                 draft_k: Optional[int] = None):
        import jax.numpy as jnp

        from bigdl_tpu.serving import paging as _paging

        self.model = model
        self.params = variables["params"]
        self.state = variables["state"]
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.eos_id = eos_id
        self.default_deadline_ms = default_deadline_ms
        self.continuous = continuous
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self.grid = BucketGrid([(int(t),) for t in prompt_buckets],
                               prefill_batch_sizes, pad_value=0)
        self._largest_bucket = max(int(t) for t in prompt_buckets)

        self._dtype = self.params["embed"]["weight"].dtype \
            if "embed" in self.params else jnp.float32
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout must be 'dense' or 'paged', "
                             f"got {kv_layout!r}")
        self.kv_layout = kv_layout
        self.paged = kv_layout == "paged"
        if kv_dtype is not None and not self.paged:
            raise ValueError("kv_dtype requires kv_layout='paged'")
        self._spec = draft is not None
        self.draft_k = 0
        if self._spec:
            self.draft_k = int(draft_k if draft_k is not None
                               else _paging.draft_k_default())
            if self.draft_k < 1:
                raise ValueError(f"draft_k must be >= 1, got "
                                 f"{self.draft_k}")

        if self.paged:
            self.page_size = int(page_size if page_size is not None
                                 else _paging.page_size_default())
            self.num_pages = int(
                num_pages if num_pages is not None
                else _paging.default_num_pages(self.slots, self.max_len,
                                               self.page_size))
            self.kv_dtype = kv_dtype if kv_dtype is not None \
                else _paging.kv_dtype_default()
            self._page_zero = _paging.page_zero_enabled()
            self._alloc = _paging.PageAllocator(
                self.num_pages, self.page_size, self.slots, self.max_len)
            self._cache = model.init_paged_cache(
                self.num_pages, self.page_size, self.slots, self._dtype,
                kv_dtype=self.kv_dtype)
            self._tick = build_paged_tick(model)
            self._write = build_paged_write_slot()
            self._reset = build_page_reset() if self._page_zero else None
        else:
            self.page_size = None
            self.num_pages = 0
            self.kv_dtype = None
            self._page_zero = False
            self._alloc = None
            self._cache = model.init_cache(self.slots, self.max_len,
                                           self._dtype)
            self._tick = build_sampling_tick(model)
            self._write = build_write_slot()
        self._prefill = build_prefill(model, self.max_len, self._dtype)
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk \
            else None
        if self.prefill_chunk:
            self._chunk_prog = build_prefill_chunk(model)
        if self._spec:
            dmodel, dvars = draft
            self._draft_model = dmodel
            self._draft_params = dvars["params"]
            self._draft_state = dvars["state"]
            self._ddtype = self._draft_params["embed"]["weight"].dtype \
                if "embed" in self._draft_params else jnp.float32
            # the draft's cache stays dense: it is small by construction
            # and its lengths self-heal from the host ledger each round
            self._dcache = dmodel.init_cache(self.slots, self.max_len,
                                             self._ddtype)
            self._propose = build_draft_propose(dmodel, self.draft_k)
            self._verify = build_spec_verify(model, self.draft_k,
                                             paged=self.paged)
            self._draft_prefill = build_prefill(dmodel, self.max_len,
                                                self._ddtype)
            self._draft_write = build_write_slot()
            if self.prefill_chunk:
                self._draft_chunk_prog = build_prefill_chunk(dmodel)
        self._seen: set = set()  # our compiled-program keys (recompiles)
        self._tick_cost = None  # ProgramCost, stamped before first tick
        self._warming = False  # declared-grid compiles skip forensics

        self._tokens = np.zeros((self.slots,), np.int32)
        self._active = np.zeros((self.slots,), bool)
        self._slot_state: List[Optional[_Slot]] = [None] * self.slots
        # per-slot sampling state: raw PRNG keys round-trip through the
        # tick as data; temp == 0 rows stay exact-greedy
        self._keys = np.zeros((self.slots, 2), np.uint32)
        self._temps = np.zeros((self.slots,), np.float32)
        self._topks = np.zeros((self.slots,), np.int32)
        self._topps = np.ones((self.slots,), np.float32)
        # host mirror of each slot's valid cache extent (prompt +
        # generated - 1): drives page budgeting and draft-length resync
        self._host_len = np.zeros((self.slots,), np.int32)
        self._chunking: Optional[dict] = None
        self._chunk_pending: "collections.deque[_DecodeRequest]" = \
            collections.deque()

        self._tracer = get_tracer()
        self._rids = itertools.count()
        self._tick_no = 0
        # request X-ray: exact per-request budget + p99 tail exemplars
        # (one attribute check per call while the plane is dark)
        self.xray = request_xray.RequestLedger(tracer=self._tracer)
        self.exemplars = request_xray.ExemplarReservoir(
            tracer=self._tracer)
        self._periodic = PeriodicMetricsLogger(
            self.log_line, every_s=metrics_log_every_s)

        self._rq: "queue.Queue" = queue.Queue(maxsize=max(1, max_queue))
        self._pending: "collections.deque[_DecodeRequest]" = \
            collections.deque()
        self._closed = False
        self._discard = False
        self._close_lock = threading.Lock()
        self._loop_thread = threading.Thread(
            target=self._loop, daemon=True, name="bigdl-decode-loop")
        self._started = False

        if warmup:
            self.warmup()
        if start:
            self.start()

    # ------------------------------------------------------------------
    # compiled-program cache (the recompile counter lives here)
    # ------------------------------------------------------------------
    @property
    def recompiles(self) -> int:
        return self.metrics.recompiles

    def _tracked(self, key, thunk, program=None, sig_fn=None, cost=None):
        """Run ``thunk``; first sight of ``key`` is counted (and timed)
        as a compile.  Params/state/dtype are fixed, so our key set is
        exactly jit's cache key set and the counter is exact.

        ``program``/``sig_fn`` feed the X-ray registry: the signature
        must be fingerprinted *before* the thunk runs (ticks/writes
        donate the cache buffers), and registration happens before
        ``record_recompile`` so the forensic instant precedes the
        recompile span the Watchdog pairs it with."""
        if key in self._seen:
            if program is not None:
                programs.get_program_registry().record_call(program)
            return thunk()
        sig = None
        if program is not None and sig_fn is not None:
            try:
                sig = sig_fn()
            except Exception:
                sig = None
        t0 = time.perf_counter()
        # which tick (ambient correlation) paid for which program
        with self._tracer.span("compile", CAT_DECODE,
                               args={"program": program or str(key[0])}):
            out = thunk()
        dt = time.perf_counter() - t0
        if program is not None:
            programs.get_program_registry().register_compile(
                program, sig, compile_s=dt, cost=cost,
                expected=self._warming)
        self.metrics.record_recompile(dt)
        self._seen.add(key)
        return out

    def declared_programs(self) -> int:
        """How many compiles a full warmup performs.  Base grid: one
        prefill per declared (batch, prompt) bucket plus one slot write
        per declared batch size; speculative engines compile a draft
        prefill/write mirror of the grid and replace the tick with the
        propose + verify pair; chunked prefill adds the chunk program
        (and a batch-1 write when 1 is not a declared batch); paged
        engines with page zeroing add the reset."""
        grid = (len(self.grid.declared_buckets())
                + len(self.grid.batch_sizes))
        n = grid + (2 if self._spec else 1)
        if self._spec:
            n += grid
        if self.prefill_chunk:
            n += 2 if self._spec else 1
            if 1 not in self.grid.batch_sizes:
                n += 2 if self._spec else 1
        if self.paged and self._page_zero:
            n += 1
        return n

    def warmup(self) -> int:
        """Pre-compile every declared program (tick or propose/verify
        pair, every prefill bucket, the slot writes, and the chunk/
        reset variants when configured) so no request ever waits on
        XLA; returns how many compiles ran (0 on a re-warm).  All
        warmup executions are safe by the stale-above-length invariant:
        caches are zero, ``active`` is all-False, and paged writes land
        on the trash page."""
        before = self.metrics.recompiles
        self._warming = True
        try:
            self._stamp_tick()
            if self._spec:
                props = self._run_propose()
                self._run_verify(props)
            else:
                self._run_tick()
            for bucket in self.grid.declared_buckets():
                ids = np.zeros((bucket.batch,) + bucket.dims, np.int32)
                lengths = np.ones((bucket.batch,), np.int32)
                _, pcache = self._run_prefill(ids, lengths)
                # the write's shape signature depends only on the batch
                # bucket (prompt length never survives into cache
                # shapes)
                self._run_write(pcache, 0, 0, batch=bucket.batch)
                if self._spec:
                    _, dpcache = self._run_draft_prefill(ids, lengths)
                    self._run_draft_write(dpcache, 0, 0,
                                          batch=bucket.batch)
            if self.prefill_chunk:
                ids = np.zeros((1, self.prefill_chunk), np.int32)
                adv = np.ones((1,), np.int32)
                staging = self.model.init_cache(1, self.max_len,
                                                self._dtype)
                _, staging = self._run_chunk(staging, ids, adv)
                if 1 not in self.grid.batch_sizes:
                    self._run_write(staging, 0, 0, batch=1)
                if self._spec:
                    dstaging = self._draft_model.init_cache(
                        1, self.max_len, self._ddtype)
                    _, dstaging = self._run_draft_chunk(dstaging, ids,
                                                        adv)
                    if 1 not in self.grid.batch_sizes:
                        self._run_draft_write(dstaging, 0, 0, batch=1)
            if self.paged and self._page_zero:
                self._run_page_reset([])
        finally:
            self._warming = False
        return self.metrics.recompiles - before

    def _table(self) -> np.ndarray:
        """The allocator's block table, passed into paged programs as a
        plain device argument each call (values change, shape never)."""
        return self._alloc.table

    def _tick_args(self):
        base = (self.params, self.state, self._cache)
        if self.paged:
            base = base + (self._table(),)
        return base + (self._tokens, self._active, self._keys,
                       self._temps, self._topks, self._topps)

    def _stamp_tick(self):
        """Stamp the grid tick's flops/bytes (re-trace only).  Must run
        while ``self._cache`` buffers are live — before a tick donates
        them — so stamping happens at warmup/start, never in the loop.
        Speculative engines stamp the verify pass — the program that
        touches the full cache each round."""
        if self._tick_cost is not None:
            return
        if self._spec:
            draft = np.zeros((self.slots, self.draft_k), np.int32)
            args = (self.params, self.state, self._cache)
            if self.paged:
                args = args + (self._table(),)
            args = args + (self._tokens, draft, self._active)
            cost = costmodel.stamp_jitted("spec_verify", self._verify,
                                          *args)
        else:
            cost = costmodel.stamp_jitted("decode_tick", self._tick,
                                          *self._tick_args())
        if cost is not None:
            self._tick_cost = cost

    def _pages_held(self):
        """``loop/tick_dispatch``'s counter: the pages the slots hold
        (allocator, host side) — the share of the ``S * M`` extent this
        tick's attention has to read."""
        return {"pages_held": self._alloc.pages_in_use} \
            if self.paged else None

    def _run_tick(self):
        def thunk():
            # the paged tick also hands out the model's counters
            cache, nxt, keys, *counters = self._tick(*self._tick_args())
            self._cache = cache
            return nxt, keys, counters[0] if counters else {}

        # the tick's own predicate, known here without asking the
        # device: the rows whose sampling epilogue this tick runs
        sampled_rows = int(((self._temps > 0) & self._active).sum())
        if sampled_rows:
            self.metrics.inc_sampled_ticks()
        args = dict(self._pages_held() or {}, sampled_rows=sampled_rows)
        with self._tracer.span("loop/tick_dispatch", CAT_DECODE,
                               args=args):
            out = self._tracked(
                ("tick",), thunk, program="decode_tick",
                sig_fn=lambda: programs.signature_of(
                    {"params": self.params, "state": self.state,
                     "cache": self._cache, "tokens": self._tokens,
                     "active": self._active, "keys": self._keys,
                     "temp": self._temps, "top_k": self._topks,
                     "top_p": self._topps},
                    donated=("cache",)),
                cost=self._tick_cost)
        # the per-tick host sync point (writable copy: slots claimed
        # between ticks overwrite their token in place)
        with self._tracer.span("loop/tick_wait", CAT_DECODE):
            nxt, keys, counters = out
            if not self._tracer.enabled:
                counters = {}  # nobody to read them: not fetched
            # the model's counters come back in the tokens' own read
            nxt, keys, counters = jax.device_get((nxt, keys, counters))
            for name, value in counters.items():
                args[name] = np.asarray(value).tolist()
            self._keys = np.array(keys)
            return np.array(nxt)

    def _run_prefill(self, ids: np.ndarray, lengths: np.ndarray):
        return self._tracked(
            ("prefill", ids.shape),
            lambda: self._prefill(self.params, self.state, ids, lengths),
            program="decode_prefill",
            sig_fn=lambda: programs.signature_of(
                {"params": self.params, "state": self.state,
                 "ids": ids, "lengths": lengths}))

    def _run_write(self, pcache, row: int, slot: int, batch: int):
        if self.paged:
            def thunk():
                self._cache = self._write(
                    self._cache, self._alloc.table[slot], pcache, row,
                    slot)
        else:
            def thunk():
                self._cache = self._write(self._cache, pcache, row, slot)

        return self._tracked(
            ("write", batch), thunk, program="decode_write_slot",
            sig_fn=lambda: programs.signature_of(
                {"cache": self._cache, "prefill_cache": pcache},
                static={"batch": batch, "layout": self.kv_layout},
                donated=("cache",)))

    # -------------------------------------------------- paged/spec/chunk
    def _run_page_reset(self, pages):
        """Zero freed physical pages (hygiene knob, fixed arg shape:
        the page-id vector is padded with trash-page zeros)."""
        arr = np.zeros((self._alloc.pages_per_slot,), np.int32)
        ids = np.asarray(pages, np.int32)[:arr.size]
        arr[:ids.size] = ids

        def thunk():
            self._cache = self._reset(self._cache, arr)

        return self._tracked(
            ("page_reset",), thunk, program="page_reset",
            sig_fn=lambda: programs.signature_of(
                {"cache": self._cache, "pages": arr},
                donated=("cache",)))

    def _run_chunk(self, staging, ids: np.ndarray, adv: np.ndarray):
        def thunk():
            last, cache = self._chunk_prog(self.params, self.state,
                                           staging, ids, adv)
            return np.asarray(last), cache

        return self._tracked(
            ("chunk",), thunk, program="decode_prefill_chunk",
            sig_fn=lambda: programs.signature_of(
                {"params": self.params, "state": self.state,
                 "cache": staging, "ids": ids, "advance": adv},
                donated=("cache",)))

    def _run_draft_prefill(self, ids: np.ndarray, lengths: np.ndarray):
        return self._tracked(
            ("dprefill", ids.shape),
            lambda: self._draft_prefill(self._draft_params,
                                        self._draft_state, ids, lengths),
            program="draft_prefill",
            sig_fn=lambda: programs.signature_of(
                {"params": self._draft_params, "ids": ids,
                 "lengths": lengths}))

    def _run_draft_write(self, dpcache, row: int, slot: int, batch: int):
        def thunk():
            self._dcache = self._draft_write(self._dcache, dpcache, row,
                                             slot)

        return self._tracked(
            ("dwrite", batch), thunk, program="draft_write_slot",
            sig_fn=lambda: programs.signature_of(
                {"cache": self._dcache, "prefill_cache": dpcache},
                static={"batch": batch}, donated=("cache",)))

    def _run_draft_chunk(self, dstaging, ids: np.ndarray,
                         adv: np.ndarray):
        def thunk():
            last, cache = self._draft_chunk_prog(
                self._draft_params, self._draft_state, dstaging, ids,
                adv)
            return np.asarray(last), cache

        return self._tracked(
            ("dchunk",), thunk, program="draft_prefill_chunk",
            sig_fn=lambda: programs.signature_of(
                {"params": self._draft_params, "cache": dstaging,
                 "ids": ids, "advance": adv},
                donated=("cache",)))

    def _run_propose(self):
        def thunk():
            dcache, props = self._propose(
                self._draft_params, self._draft_state, self._dcache,
                self._tokens, self._host_len, self._active)
            self._dcache = dcache
            return props  # stays on device: the verify consumes it

        return self._tracked(
            ("propose",), thunk, program="draft_propose",
            sig_fn=lambda: programs.signature_of(
                {"params": self._draft_params, "cache": self._dcache,
                 "tokens": self._tokens, "lengths": self._host_len,
                 "active": self._active},
                donated=("cache",)))

    def _run_verify(self, props):
        """Dispatch the verify pass; returns the device ``(emitted,
        n_emit)`` — fetching them is the round's single host sync."""
        def thunk():
            args = (self.params, self.state, self._cache)
            if self.paged:
                args = args + (self._table(),)
            args = args + (self._tokens, props, self._active)
            cache, emitted, n_emit = self._verify(*args)
            self._cache = cache
            return emitted, n_emit

        return self._tracked(
            ("verify",), thunk, program="spec_verify",
            sig_fn=lambda: programs.signature_of(
                {"params": self.params, "state": self.state,
                 "cache": self._cache, "tokens": self._tokens,
                 "active": self._active},
                static={"draft_k": self.draft_k,
                        "layout": self.kv_layout},
                donated=("cache",)),
            cost=self._tick_cost)

    # ------------------------------------------------------------------
    # client API
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int,
               deadline_ms: Optional[float] = None, *,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0,
               seed: Optional[int] = None) -> ServingFuture:
        """Queue one prompt (1-D int array, len >= 1); returns a future
        resolving to the generated token ids (1-D ``int32``, EOS
        included when hit).  ``temperature > 0`` samples inside the
        tick (``top_k``/``top_p`` filter, ``seed`` makes the stream
        reproducible; defaults to the request id); ``temperature == 0``
        is exact greedy.  Raises :class:`QueueFullError` when the
        bounded queue is full, :class:`EngineClosedError` after
        ``close()``, and ``ValueError`` when the request cannot fit the
        cache."""
        if self._closed:
            raise EngineClosedError("submit on a closed decode engine")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt: cached decode needs at "
                             "least one prompt token to prefill")
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        temperature = float(temperature)
        top_k = int(top_k)
        top_p = float(top_p)
        if temperature > 0.0 and self._spec:
            raise ValueError(
                "speculative decoding is greedy-only: the verify pass "
                "accepts draft tokens by argmax match, which sampling "
                "would break")
        if temperature > 0.0 and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        # speculative rounds may write up to draft_k tokens past the
        # last emitted position before rollback — reserve the slack
        slack = self.draft_k if self._spec else 0
        if prompt.size + max_new_tokens - 1 + slack > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) - 1"
                + (f" + draft_k ({slack})" if slack else "")
                + f" exceeds the cache max_len ({self.max_len})")
        if self.paged:
            from bigdl_tpu.serving.paging import OutOfPagesError
            worst = int(prompt.size) + max_new_tokens - 1 + slack
            pages = min(-(-worst // self.page_size),
                        self._alloc.pages_per_slot)
            if pages > self.num_pages - 1:
                raise OutOfPagesError(
                    f"request needs {pages} pages at its longest but "
                    f"the pool only has {self.num_pages - 1} usable "
                    f"pages of {self.page_size} tokens")
        fut = ServingFuture()
        now = time.perf_counter()
        dl = deadline_ms if deadline_ms is not None \
            else self.default_deadline_ms
        rid = next(self._rids)
        req = _DecodeRequest(prompt, max_new_tokens, fut, now,
                             now + dl / 1e3 if dl is not None else None,
                             rid=rid, temp=temperature, top_k=top_k,
                             top_p=top_p,
                             key=_key_for_seed(rid if seed is None
                                               else seed))
        try:
            self._rq.put_nowait(req)
        except queue.Full:
            self.metrics.inc_rejected()
            self._tracer.instant("queue_full", CAT_DECODE,
                                 corr=f"req:{rid}",
                                 args={"max_queue": self._rq.maxsize})
            raise QueueFullError(
                f"decode queue full ({self._rq.maxsize}); retry later"
            ) from None
        self._tracer.instant("enqueue", CAT_DECODE, corr=f"req:{rid}",
                             args={"prompt_len": int(prompt.size),
                                   "max_new": max_new_tokens})
        self.xray.open(rid, now=now)
        rec = workload.recorder()
        if rec is not None:
            # the RESOLVED seed (rid default included): the recorded
            # stream replays bit-identically even when callers never
            # passed one
            rec.record_decode(rid, prompt, max_new_tokens,
                              temperature=temperature, top_k=top_k,
                              top_p=top_p,
                              seed=rid if seed is None else int(seed),
                              deadline_ms=dl)
        return fut

    def generate(self, prompt, max_new_tokens: int,
                 deadline_ms: Optional[float] = None,
                 timeout: Optional[float] = None, **sampling
                 ) -> np.ndarray:
        """Submit one prompt and wait for its generated tokens;
        ``**sampling`` forwards ``temperature``/``top_k``/``top_p``/
        ``seed`` to :meth:`submit`."""
        return self.submit(prompt, max_new_tokens,
                           deadline_ms=deadline_ms,
                           **sampling).result(timeout)

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def start(self):
        if not self._started:
            self._started = True
            self._stamp_tick()  # covers warmup=False constructions
            self._loop_thread.start()
            self._periodic.start()
            # live ops plane: host-side registration only (see
            # ServingEngine.start for the contract)
            from bigdl_tpu.telemetry import debug_server, flightrecorder
            self._detach_debug = debug_server.attach_engine(
                "decode", role="decode", metrics=lambda: self.metrics,
                status=lambda: {"queue_depth": self._rq.qsize(),
                                "xray": self.xray.summary(),
                                "exemplars": self.exemplars.summary()},
                exemplars=lambda: self.exemplars)
            flight = flightrecorder.get_flight_recorder()
            if flight is not None:
                flight.add_metrics("decode", lambda: self.metrics)
                flight.add_blob("exemplars-decode",
                                self.exemplars.as_blob)
            # HbmLedger resident lane: the paged engine reports bytes
            # proportional to pages actually in use — the readout that
            # retirement frees memory — while the dense engine reports
            # its fixed worst-case reservation for comparison
            ledger = programs.get_hbm_ledger()
            if self.paged:
                per_page = self._page_bytes_total()
                self._resident_name = "decode_kv_pages"
                ledger.add_resident(
                    self._resident_name,
                    lambda: self._alloc.pages_in_use * per_page)
            else:
                total = self._cache_bytes_total()
                self._resident_name = "decode_kv_cache"
                ledger.add_resident(self._resident_name, lambda: total)

    def close(self, drain: bool = True, timeout: float = 60.0):
        """Stop accepting requests and shut down.  ``drain=True``
        (default) decodes everything already queued/in flight to
        completion first; ``drain=False`` fails undelivered requests
        with :class:`EngineClosedError`.  Idempotent."""
        with self._close_lock:
            already = self._closed
            self._closed = True
        if already:
            return
        detach = getattr(self, "_detach_debug", None)
        if detach is not None:
            detach()
        name = getattr(self, "_resident_name", None)
        if name is not None:
            programs.get_hbm_ledger().remove_resident(name)
        self._periodic.close()
        self._discard = not drain
        if not self._started:
            self._fail_queued(EngineClosedError(
                "decode engine closed before start"))
            return
        self._rq.put(_CLOSE)
        self._loop_thread.join(timeout)

    def _fail_queued(self, exc):
        while True:
            try:
                req = self._rq.get_nowait()
            except queue.Empty:
                break
            if req is not _CLOSE:
                self.xray.drop(req.rid)
                req.fut.set_exception(exc)
        while self._pending:
            req = self._pending.popleft()
            self.xray.drop(req.rid)
            req.fut.set_exception(exc)
        while self._chunk_pending:
            req = self._chunk_pending.popleft()
            self.xray.drop(req.rid)
            req.fut.set_exception(exc)
        if self._chunking is not None:
            self.xray.drop(self._chunking["req"].rid)
            self._chunking["req"].fut.set_exception(exc)
            self._chunking = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------------------
    # engine loop: admit (prefill into free slots) then tick the grid
    # ------------------------------------------------------------------
    def _loop(self):
        """One turn: drain, admit, chunk, budget, tick, retire — each a
        top-level ``loop/*`` span, so together they tile the thread's
        time while the tracer is on (docs/observability.md)."""
        tr = self._tracer
        stopping = False
        while True:
            if tr.poll():
                # ambient correlation: every span of this turn carries
                # the index of the tick the turn runs
                set_correlation(f"tick:{self._tick_no + 1}")
            with tr.span("loop/drain_queue", CAT_DECODE):
                stopping = self._drain_queue(
                    block=(not np.any(self._active) and not self._pending
                           and self._chunking is None
                           and not self._chunk_pending
                           and all(st is None
                                   for st in self._slot_state)),
                    stopping=stopping)
            if stopping and self._discard:
                self._fail_queued(EngineClosedError(
                    "decode engine closed"))
                for s in range(self.slots):
                    st = self._slot_state[s]
                    if st is not None:
                        self.xray.drop(st.req.rid)
                        st.req.fut.set_exception(EngineClosedError(
                            "decode engine closed"))
                        self._free(s)
                return
            args = {}  # filled inside the span: the ring's copy
            with tr.span("loop/admit", CAT_DECODE, args=args):
                args["admitted"] = self._admit()
            args = {}
            with tr.span("loop/chunk_step", CAT_DECODE, args=args):
                args["tokens"] = self._chunk_step()
            if self.paged:
                # fund (and resume) occupied slots before the tick —
                # must run even when everything is paused
                with tr.span("loop/budget_pages", CAT_DECODE):
                    self._budget_pages()
            if not np.any(self._active):
                if stopping and not self._pending \
                        and self._chunking is None \
                        and not self._chunk_pending \
                        and all(st is None for st in self._slot_state):
                    return
                continue
            self._tick_no += 1
            if self._spec:
                self._spec_round()
                continue
            t0 = time.perf_counter()
            with StepTraceAnnotation("decode_tick",
                                     step_num=self._tick_no):
                nxt = self._run_tick()
            now = time.perf_counter()
            args = {}
            with tr.span("loop/retire", CAT_DECODE, args=args):
                self.metrics.record_tick(now - t0)
                self._tokens = nxt
                n_active = int(self._active.sum())
                self.metrics.record_decode_tokens(n_active)
                self.metrics.record_slot_occupancy(n_active / self.slots)
                self._host_len[self._active] += 1
                gaps = self._retire(nxt, now)
                args["active"] = n_active
                args["gaps_ms"] = [1e3 * g for g in gaps]

    def _drain_queue(self, block: bool, stopping: bool) -> bool:
        """Move queued requests into the admission deque; ``block``
        waits briefly when the engine is otherwise idle."""
        while True:
            try:
                req = self._rq.get(timeout=0.005) if block \
                    else self._rq.get_nowait()
            except queue.Empty:
                return stopping
            block = False
            if req is _CLOSE:
                stopping = True
                continue
            self._pending.append(req)

    def _free_slots(self) -> List[int]:
        reserved = self._chunking["slot"] if self._chunking else -1
        return [s for s in range(self.slots)
                if not self._active[s] and s != reserved]

    def _admit(self) -> int:
        """Prefill waiting requests into free slots; returns how many
        were bound to a slot this turn."""
        if self.prefill_chunk:
            # prompts longer than the largest declared bucket take the
            # chunked path instead of learning a one-off jumbo bucket
            keep: "collections.deque[_DecodeRequest]" = \
                collections.deque()
            while self._pending:
                r = self._pending.popleft()
                if r.prompt.size > self._largest_bucket:
                    self._chunk_pending.append(r)
                else:
                    keep.append(r)
            self._pending = keep
        free = self._free_slots()
        if not self._pending or not free:
            return 0
        if not self.continuous and len(free) < self.slots:
            # static run-to-completion baseline: wait for the whole
            # grid to drain before admitting the next wave
            return 0
        now = time.perf_counter()
        taken: List[_DecodeRequest] = []
        while self._pending and len(taken) < len(free):
            req = self._pending.popleft()
            if req.deadline is not None and now > req.deadline:
                self.metrics.inc_expired()
                self._tracer.instant("deadline_reject", CAT_DECODE,
                                     corr=f"req:{req.rid}")
                req.fut.set_exception(DeadlineExceededError(
                    f"deadline expired "
                    f"{1e3 * (now - req.deadline):.1f}ms before "
                    "prefill",
                    attribution=self.xray.close(req.rid, now=now)))
                continue
            taken.append(req)
        if self.paged and taken:
            # admission never evicts (an evicted request re-queues and
            # could evict its evictor right back — livelock): requests
            # whose prompt does not fit the current free list wait
            # until retirement frees pages
            fits: List[_DecodeRequest] = []
            free_pages = self._alloc.pages_free
            for i, req in enumerate(taken):
                need = min(-(-(int(req.prompt.size) + self._page_slack())
                             // self.page_size),
                           self._alloc.pages_per_slot)
                if need > free_pages:
                    self._pending.extendleft(reversed(taken[i:]))
                    break
                free_pages -= need
                fits.append(req)
            taken = fits
        if not taken:
            return 0
        admitted = 0
        groups: dict = {}
        for r in taken:
            dims, _ = self.grid.choose_dims(r.prompt.shape)
            groups.setdefault(dims, []).append(r)
        free_iter = iter(free)
        for dims, rs in groups.items():
            for lo in range(0, len(rs), self.grid.max_batch):
                chunk = rs[lo:lo + self.grid.max_batch]
                t0 = time.perf_counter()
                self.xray.to_many((r.rid for r in chunk),
                                  request_xray.PHASE_PREFILL, now=t0)
                try:
                    admitted += self._prefill_chunk(chunk, dims,
                                                    free_iter)
                except Exception as e:  # per-request delivery
                    for r in chunk:
                        self.xray.drop(r.rid)
                        r.fut.set_exception(e)
                    continue
                self.metrics.record_prefill(time.perf_counter() - t0)
        return admitted

    def _prefill_chunk(self, chunk: List[_DecodeRequest], dims,
                       free_iter) -> int:
        tr = self._tracer
        b = self.grid.choose_batch(len(chunk))
        with tr.span("prefill_dispatch", CAT_DECODE):
            ids = self.grid.pad_batch([r.prompt for r in chunk], dims, b,
                                      np.int32)
            lengths = np.ones((b,), np.int32)
            lengths[:len(chunk)] = [r.prompt.size for r in chunk]
            logits, pcache = self._run_prefill(ids, lengths)
        with tr.span("prefill_wait", CAT_DECODE):
            logits = np.asarray(logits)
        dpcache = None
        if self._spec:
            _, dpcache = self._run_draft_prefill(ids, lengths)
        admitted = 0
        for i, r in enumerate(chunk):
            self.xray.to(r.rid, request_xray.PHASE_SAMPLE)
            with tr.span("host_sample", CAT_DECODE, corr=f"req:{r.rid}"):
                tok0 = _host_sample(logits[i], r)
            t_tok = time.perf_counter()
            done = ((self.eos_id is not None and tok0 == self.eos_id)
                    or r.max_new <= 1)
            if done:
                self._finish(r, [tok0], [t_tok],
                             "eos" if (self.eos_id is not None
                                       and tok0 == self.eos_id)
                             else "length")
                continue
            slot = next(free_iter)
            if self.paged and not self._alloc.ensure(
                    slot, int(r.prompt.size) + self._page_slack()):
                # admission pre-filter reserved these pages; losing the
                # race is unexpected but recoverable — wait, don't evict
                self.xray.to(r.rid, request_xray.PHASE_PAGE_STALL)
                self._pending.appendleft(r)
                continue
            if self.paged:
                self.metrics.record_pages(self._alloc.pages_in_use)
            # dispatched without waiting: the write's device time is
            # paid inside the next tick's token fetch
            with tr.span("slot_write", CAT_DECODE, corr=f"req:{r.rid}"):
                self._run_write(pcache, i, slot, batch=b)
                if self._spec:
                    self._run_draft_write(dpcache, i, slot, batch=b)
            self._activate(slot, r, tok0, t_tok)
            admitted += 1
        return admitted

    def _activate(self, slot: int, req: _DecodeRequest, tok0: int,
                  t_tok: float):
        """Bind a prefilled request to its slot: token feed, sampling
        state, and the host length ledger."""
        self._tokens[slot] = tok0
        self._active[slot] = True
        self._slot_state[slot] = _Slot(req, tok0, t_tok)
        self._host_len[slot] = int(req.prompt.size)
        self._keys[slot] = req.key
        self._temps[slot] = req.temp
        self._topks[slot] = req.top_k
        self._topps[slot] = req.top_p
        # continuous-batching refill edge: request -> slot binding
        self._tracer.instant("slot_fill", CAT_DECODE,
                             corr=f"req:{req.rid}",
                             args={"slot": slot})
        self.xray.to(req.rid, request_xray.PHASE_RESIDENT)

    # ------------------------------------------------------------------
    # chunked prefill: one bounded chunk per loop iteration, so long
    # prompts never stall the occupied slots between ticks
    # ------------------------------------------------------------------
    def _chunk_step(self) -> int:
        """Run at most one chunk; returns the prompt tokens it held."""
        if not self.prefill_chunk:
            return 0
        if self._chunking is None and self._chunk_pending:
            free = self._free_slots()
            if free:
                req = self._chunk_pending.popleft()
                now = time.perf_counter()
                if req.deadline is not None and now > req.deadline:
                    self.metrics.inc_expired()
                    self._tracer.instant("deadline_reject", CAT_DECODE,
                                         corr=f"req:{req.rid}")
                    req.fut.set_exception(DeadlineExceededError(
                        f"deadline expired "
                        f"{1e3 * (now - req.deadline):.1f}ms before "
                        "prefill",
                        attribution=self.xray.close(req.rid, now=now)))
                    return 0
                self.xray.to(req.rid, request_xray.PHASE_PREFILL,
                             now=now)
                self._chunking = {
                    "req": req, "slot": free[0], "offset": 0,
                    "staging": self.model.init_cache(
                        1, self.max_len, self._dtype),
                    "dstaging": self._draft_model.init_cache(
                        1, self.max_len, self._ddtype)
                    if self._spec else None,
                }
        c = self._chunking
        if c is None:
            return 0
        if "tok0" in c:
            # prefill finished earlier but the page pool was full: keep
            # retrying as ticks retire slots and free pages
            self._finalize_chunk(c)
            return 0
        req = c["req"]
        now = time.perf_counter()
        if req.deadline is not None and now > req.deadline:
            # nothing reached the grid cache yet: fail fast, slot stays
            # clean
            self._chunking = None
            self.metrics.inc_expired()
            req.fut.set_exception(DeadlineExceededError(
                "deadline expired mid chunked prefill "
                f"({c['offset']}/{req.prompt.size} tokens in)",
                attribution=self.xray.close(req.rid, now=now)))
            return 0
        t0 = time.perf_counter()
        size = self.prefill_chunk
        lo = c["offset"]
        hi = min(lo + size, int(req.prompt.size))
        ids = np.zeros((1, size), np.int32)
        ids[0, :hi - lo] = req.prompt[lo:hi]
        adv = np.array([hi - lo], np.int32)
        last, c["staging"] = self._run_chunk(c["staging"], ids, adv)
        if self._spec:
            _, c["dstaging"] = self._run_draft_chunk(c["dstaging"], ids,
                                                     adv)
        self.metrics.inc_prefill_chunks()
        self.xray.note(req.rid, "prefill_chunks")
        self.metrics.record_prefill(time.perf_counter() - t0)
        self._tracer.instant("prefill_chunk", CAT_DECODE,
                             corr=f"req:{req.rid}",
                             args={"lo": lo, "hi": hi})
        c["offset"] = hi
        if hi < req.prompt.size:
            return hi - lo  # more chunks on later loop iterations
        self.xray.to(req.rid, request_xray.PHASE_SAMPLE)
        tok0 = _host_sample(last[0], req)
        c["t_tok"] = time.perf_counter()
        if (self.eos_id is not None and tok0 == self.eos_id) \
                or req.max_new <= 1:
            self._chunking = None
            self._finish(req, [tok0], [c["t_tok"]],
                         "eos" if (self.eos_id is not None
                                   and tok0 == self.eos_id)
                         else "length")
            return hi - lo
        c["tok0"] = tok0
        self._finalize_chunk(c)
        return hi - lo

    def _finalize_chunk(self, c: dict):
        """Splice a fully chunk-prefilled request into its reserved
        slot — deferred while the page pool is full (admission never
        evicts; see :meth:`_ensure_pages`)."""
        req, slot = c["req"], c["slot"]
        if self.paged and not self._alloc.ensure(
                slot, int(req.prompt.size) + self._page_slack()):
            self.xray.to(req.rid, request_xray.PHASE_PAGE_STALL)
            return  # retry next loop iteration
        if self.paged:
            self.metrics.record_pages(self._alloc.pages_in_use)
        self._chunking = None
        self._run_write(c["staging"], 0, slot, batch=1)
        if self._spec:
            self._run_draft_write(c["dstaging"], 0, slot, batch=1)
        self._activate(slot, req, c["tok0"], c["t_tok"])

    # ------------------------------------------------------------------
    # paged-pool budgeting
    # ------------------------------------------------------------------
    def _page_slack(self) -> int:
        """Tokens a slot may write beyond its current valid length in
        one round: the next tick's token, plus the speculative write-
        ahead window."""
        return 1 + (self.draft_k if self._spec else 0)

    def _budget_pages(self):
        """Before each tick, fund every occupied slot with pages for
        the tokens this round can write — oldest request first.  A slot
        the free list cannot fund may evict strictly *younger* requests
        (they re-queue and re-decode deterministically); with no
        younger donor it is *paused* — deactivated but keeping its
        pages and generated state — and resumes once retirement frees
        pages.  The oldest occupied slot can always be funded (submit
        guarantees every request fits an empty pool), so at least one
        slot always progresses: no evict/re-admit livelock."""
        order = sorted(
            (s for s in range(self.slots)
             if self._slot_state[s] is not None),
            key=lambda s: self._slot_state[s].req.rid)
        for s in order:
            st = self._slot_state[s]
            if st is None:
                continue  # evicted by an older slot earlier this round
            need = int(self._host_len[s]) + self._page_slack()
            if self._ensure_pages(s, need):
                if not self._active[s]:
                    # resuming a paused slot: the page stall ends here
                    self.xray.to(st.req.rid,
                                 request_xray.PHASE_RESIDENT)
                self._active[s] = True
            else:
                if self._active[s]:
                    self._tracer.instant("page_pause", CAT_DECODE,
                                         args={"slot": s})
                    self.xray.to(st.req.rid,
                                 request_xray.PHASE_PAGE_STALL)
                    self.xray.note(st.req.rid, "page_pauses")
                self._active[s] = False

    def _ensure_pages(self, slot: int, tokens: int) -> bool:
        """Grow ``slot`` to cover ``tokens``; when the free list runs
        short, evict the youngest occupied slot whose request is newer
        than this slot's.  Returns False when no such donor exists."""
        me = self._slot_state[slot].req.rid \
            if self._slot_state[slot] is not None else -1
        while not self._alloc.ensure(slot, tokens):
            victim, rid = None, me
            for s in range(self.slots):
                if s == slot or self._slot_state[s] is None:
                    continue
                r = self._slot_state[s].req.rid
                if r > rid:
                    victim, rid = s, r
            if victim is None:
                return False
            self._evict(victim)
        self.metrics.record_pages(self._alloc.pages_in_use)
        return True

    def _evict(self, victim: int):
        st = self._slot_state[victim]
        self.metrics.inc_page_evictions()
        self._tracer.instant("page_evict", CAT_DECODE,
                             args={"slot": victim,
                                   "pages": self._alloc.owned(victim)})
        if st is not None:
            # deterministic restart: greedy/seeded sampling re-decodes
            # to the same tokens, so eviction costs latency, not output
            # (the whole re-queue wait is charged to the eviction)
            self.xray.to(st.req.rid, request_xray.PHASE_PAGE_STALL)
            self.xray.note(st.req.rid, "page_evictions")
            self._pending.appendleft(st.req)
        self._free(victim)

    # ------------------------------------------------------------------
    # speculative rounds (replace the tick when a draft is configured)
    # ------------------------------------------------------------------
    def _spec_round(self):
        tr = self._tracer
        t0 = time.perf_counter()
        spec_rids: Sequence[int] = ()
        if self.xray.enabled:
            spec_rids = [self._slot_state[s].req.rid
                         for s in range(self.slots)
                         if self._active[s]
                         and self._slot_state[s] is not None]
            self.xray.to_many(spec_rids, request_xray.PHASE_SPEC,
                              now=t0)
        with StepTraceAnnotation("decode_tick", step_num=self._tick_no):
            with tr.span("loop/tick_dispatch", CAT_DECODE,
                         args=self._pages_held()):
                out = self._run_verify(self._run_propose())
            with tr.span("loop/tick_wait", CAT_DECODE):
                emitted, n_emit = jax.device_get(out)
        t1 = time.perf_counter()
        args = {}
        with tr.span("loop/retire", CAT_DECODE, args=args):
            self.metrics.record_tick(t1 - t0)
            # the draft+verify round itself is the spec_verify budget;
            # the gaps between rounds stay on the resident lane
            self.xray.to_many(spec_rids, request_xray.PHASE_RESIDENT,
                              now=t1)
            emitted = np.asarray(emitted)
            n_emit = np.asarray(n_emit)
            n_active = int(self._active.sum())
            self.metrics.record_slot_occupancy(n_active / self.slots)
            n_tok = 0
            gaps: List[float] = []
            for s in range(self.slots):
                if not self._active[s]:
                    continue
                n = int(n_emit[s])  # accepted prefix + the bonus token
                self.metrics.record_spec(self.draft_k, n - 1)
                self.xray.note(self._slot_state[s].req.rid,
                               "spec_rounds")
                self._host_len[s] += n
                self._tokens[s] = int(emitted[s, n - 1])
                st = self._slot_state[s]
                req = st.req
                finished = None
                for j in range(n):
                    tok = int(emitted[s, j])
                    st.generated.append(tok)
                    # a round's tokens all arrive with its fetch
                    gaps.append(t1 - st.times[-1])
                    st.times.append(t1)
                    n_tok += 1
                    if self.eos_id is not None and tok == self.eos_id:
                        finished = "eos"
                        break
                    if len(st.generated) >= req.max_new:
                        finished = "length"
                        break
                if finished is None and req.deadline is not None \
                        and t1 > req.deadline:
                    finished = "deadline"
                if finished is not None:
                    self._finish(req, st.generated, st.times, finished)
                    self._free(s)
            self.metrics.record_decode_tokens(n_tok)
            for g in gaps:
                self.metrics.record_token_gap(g)
            args["active"] = n_active
            args["gaps_ms"] = [1e3 * g for g in gaps]

    # ------------------------------------------------------------------
    # resident-bytes accounting for the HbmLedger lane
    # ------------------------------------------------------------------
    def _page_bytes_total(self) -> int:
        """Bytes one physical page costs across every layer's pool
        (K + V + scales)."""
        total = 0
        for pool in self._cache.values():
            for name, leaf in pool.items():
                if name == "length":
                    continue
                total += int(np.prod(leaf.shape[1:])) * leaf.dtype.itemsize
        return total

    def _cache_bytes_total(self) -> int:
        """The dense cache's fixed worst-case reservation."""
        import jax

        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree_util.tree_leaves(self._cache))

    def _retire(self, nxt: np.ndarray, now: float) -> List[float]:
        """Hand each active slot its token (fetched at ``now``) and
        retire the finished; returns the slots' token gaps (seconds)."""
        gaps = []
        for s in range(self.slots):
            if not self._active[s]:
                continue
            st = self._slot_state[s]
            st.generated.append(int(nxt[s]))
            gaps.append(now - st.times[-1])
            st.times.append(now)
            self.metrics.record_token_gap(gaps[-1])
            self.xray.note(st.req.rid, "ticks")
            req = st.req
            if self.eos_id is not None and int(nxt[s]) == self.eos_id:
                self._finish(req, st.generated, st.times, "eos")
            elif len(st.generated) >= req.max_new:
                self._finish(req, st.generated, st.times, "length")
            elif req.deadline is not None and now > req.deadline:
                # decoding already started: truncate, don't fail
                self._finish(req, st.generated, st.times, "deadline")
            else:
                continue
            self._free(s)
        return gaps

    def _finish(self, req: _DecodeRequest, tokens: List[int],
                times: List[float], reason: str):
        with self._tracer.span("deliver", CAT_DECODE,
                               corr=f"req:{req.rid}",
                               args={"reason": reason,
                                     "tokens": len(tokens)}):
            self.xray.to(req.rid, request_xray.PHASE_DELIVER)
            self.metrics.inc_finished(reason)
            self.metrics.inc_completed()
            self.metrics.record_latency(time.perf_counter()
                                        - req.t_submit)
            self.metrics.record_ttft(times[0] - req.t_submit)
            # set before the result: done-callbacks may read it
            req.fut.token_times = np.asarray(times, np.float64)
            req.fut.set_result(np.asarray(tokens, np.int32))
        self.exemplars.offer(self.xray.close(req.rid))

    def _free(self, slot: int):
        self._active[slot] = False
        self._slot_state[slot] = None
        self._temps[slot] = 0.0
        self._topks[slot] = 0
        self._topps[slot] = 1.0
        self._host_len[slot] = 0
        if self.paged:
            freed = self._alloc.release(slot)
            if freed and self._page_zero:
                self._run_page_reset(freed)
            self.metrics.record_pages(self._alloc.pages_in_use)
        self._tracer.instant("slot_free", CAT_DECODE,
                             args={"slot": slot})

    # ------------------------------------------------------------------
    def log_line(self) -> str:
        line = self.metrics.log_line()
        if self.xray.enabled:
            line = f"{line} | {self.xray.log_line()}"
        return line
