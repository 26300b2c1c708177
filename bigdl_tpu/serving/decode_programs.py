"""The compiled programs of the cached-decode engine (docs/decoding.md).

Pure builders: each ``*_fn`` returns the raw function of one program and
each ``build_*`` its jitted form with the donation the engine relies on.
Nothing here knows the engine: ``serving/paging.py`` (the cache managers)
and ``serving/decode.py`` (the lanes and the scheduler) pick programs
from this file, and graft-lint audits the same builders
(``analysis/targets.py``).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.serving.bucketing import BucketGrid


def build_prefill(model, max_len: int, dtype=None, **jit_kw):
    """Prompt prefill: fresh cache rows for a padded prompt batch + the
    next-token logits at each row's true length."""
    dtype = dtype or jnp.float32

    def prefill(params, state, ids, lengths):
        cache = model.init_cache(ids.shape[0], max_len, dtype)
        return model.prefill(params, state, ids, cache, lengths=lengths)

    return jax.jit(jax.named_scope("prefill")(prefill), **jit_kw)


def build_write_slot(**jit_kw):
    """Slot splice: copy prefill-batch row ``row`` into grid slot
    ``slot`` across every cache leaf.  The grid cache is donated —
    admission rebinds it in place of copying the whole grid."""
    def write(grid_cache, batch_cache, row, slot):
        def upd(g, b):
            r = jax.lax.dynamic_slice_in_dim(b, row, 1, axis=0)
            return jax.lax.dynamic_update_slice_in_dim(
                g, r.astype(g.dtype), slot, axis=0)

        return jax.tree_util.tree_map(upd, grid_cache, batch_cache)

    return jax.jit(jax.named_scope("slot_write")(write),
                   donate_argnums=(0,), **jit_kw)


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


def _freeze_inactive(cache, old_len, active):
    """Hold retired rows at their final length, so an idle slot's
    length can never walk off the end of the cache.  ``active`` gates
    bookkeeping only: inactive rows still flow through the compute
    (their outputs are ignored), which is what keeps the tick
    occupancy-independent."""
    return {lk: dict(c, length=jnp.where(active, c["length"],
                                         old_len[lk]))
            for lk, c in cache.items()}


def build_sampling_tick(model, **jit_kw):
    """The whole-grid decode step with in-tick sampling over the dense
    cache (donated) — graft-lint's ``decode_step`` target audits this
    program.  Per-slot sampling state (keys, temp, top_k, top_p) is
    occupancy-independent (S,)-shaped device data; greedy requests ride
    along as temp == 0 rows."""
    def tick(params, state, cache, tokens, active, keys, temp, top_k,
             top_p):
        old_len = {lk: c["length"] for lk, c in cache.items()}
        logits, cache = model.decode_step(params, state, cache, tokens)
        nxt, keys = _next_tokens(logits, tokens, active, keys, temp,
                                 top_k, top_p)
        return _freeze_inactive(cache, old_len, active), nxt, keys

    return jax.jit(tick, donate_argnums=(2,), **jit_kw)


# ---------------------------------------------------------------------------
# paged KV tick + slot write (ISSUE 14; docs/decoding.md §Paged KV)
# ---------------------------------------------------------------------------
def build_paged_tick(model, **jit_kw):
    """The sampling tick over the paged pool (donated): identical math
    with the host-managed block ``table`` (S, M) as one more device
    argument — its values change as pages move, its shape never does.
    graft-lint's ``paged_decode_tick`` target audits this program."""
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
        return _freeze_inactive(cache, old_len, active), nxt, keys, \
            counters

    return jax.jit(tick, donate_argnums=(2,), **jit_kw)


def build_paged_write_slot(windows: Optional[dict] = None,
                           band_pages: int = 0,
                           blocks: Optional[dict] = None, **jit_kw):
    """Splice one dense prefill-batch row into a slot's pages: every
    per-token leaf the layer declared (K and V, or a latent row;
    quantized when the pool is int8) goes through the slot's block-table
    row as whole pages.  Unmapped logical pages redirect to the trash
    page — only the pages the allocator granted are ever written.

    ``windows`` ``{layer: window}`` names the layers that keep a band:
    ``table_row`` is then the two extents' rows stacked (2, M), and of
    such a layer only the ``band_pages`` pages from the one that holds
    the band's first row (``length - window``) are written.

    ``blocks`` ``{layer: [leaf, ...]}`` names the leaves a layer keeps
    one block a slot of (ops/paged_kv.Block): the row's block is copied
    whole into ``slot``, over whatever the slot's last request left."""
    from bigdl_tpu.ops import paged_kv

    windows = {lk: w for lk, w in (windows or {}).items() if w}
    blocks = blocks or {}

    def write(pool_cache, table_row, batch_cache, row, slot):
        out = {}
        for lk, pool in pool_cache.items():
            bc = batch_cache[lk]
            new = dict(pool)
            leaves = paged_kv.state_leaves(pool)
            trow = table_row if not windows \
                else table_row[1 if lk in windows else 0]
            band = None
            if lk in windows:
                # the band's pages: from the one that holds its first row
                page = pool[leaves[0]].shape[1]
                length = jax.lax.dynamic_index_in_dim(
                    bc["length"], row, keepdims=False)
                first = jnp.clip((length - windows[lk]) // page, 0,
                                 trow.shape[0] - band_pages)
                short = trow.shape[0] * page - bc[leaves[0]].shape[2]
                band = (first * page, band_pages * page, max(short, 0))
                trow = jax.lax.dynamic_slice_in_dim(trow, first,
                                                    band_pages)
            for name in leaves:
                if name in blocks.get(lk, ()):
                    r = jax.lax.dynamic_slice_in_dim(bc[name], row, 1)
                    new[name] = jax.lax.dynamic_update_slice_in_dim(
                        pool[name], r.astype(pool[name].dtype), slot, 0)
                    continue
                r = jax.lax.dynamic_index_in_dim(
                    bc[name], row, axis=0, keepdims=False)  # (H,T,D)
                if band is not None:
                    start, rows, short = band
                    if short:  # the extent's last page is not whole
                        r = jnp.pad(r, ((0, 0), (0, short), (0, 0)))
                    r = jax.lax.dynamic_slice_in_dim(r, start, rows,
                                                     axis=1)
                paged_kv.write_pages(new, name, trow,
                                     r.transpose(1, 0, 2))
            lrow = jax.lax.dynamic_slice_in_dim(bc["length"], row, 1,
                                                axis=0)
            new["length"] = jax.lax.dynamic_update_slice_in_dim(
                pool["length"], lrow.astype(jnp.int32), slot, axis=0)
            out[lk] = new
        return out

    return jax.jit(jax.named_scope("slot_write")(write),
                   donate_argnums=(0,), **jit_kw)


# ---------------------------------------------------------------------------
# chunked prefill (ISSUE 14; docs/decoding.md §Chunked prefill)
# ---------------------------------------------------------------------------
def build_prefill_chunk(model, **jit_kw):
    """One bounded prompt chunk through a batch-1 staging cache:
    ``model.extend`` appends at the staging cache's current length, so
    the same compiled program serves the first chunk (fresh cache) and
    every later one — a long prompt costs N dispatches of this program
    interleaved with grid ticks instead of one giant stalling prefill.
    ``advance`` (1,) is the chunk's true token count (the final chunk
    is padded); returns the last *valid* position's logits — only the
    final chunk's matter (they seed token 0) — and the model applies
    its head to that one row alone."""
    def chunk(params, state, cache, ids, advance):
        last = (jnp.maximum(advance, 1) - 1).astype(jnp.int32)
        logits, cache = model.extend(params, state, cache, ids,
                                     advance=advance, rows=last[:, None])
        return logits[:, 0], cache

    return jax.jit(chunk, donate_argnums=(2,), **jit_kw)


# ---------------------------------------------------------------------------
# speculative decoding (ISSUE 14; docs/decoding.md §Speculative)
# ---------------------------------------------------------------------------
def build_draft_propose(draft_model, k: int, **jit_kw):
    """k greedy draft steps in ONE compiled program (a ``lax.scan`` of
    ``decode_step`` — one dispatch + one host sync per round instead of
    k).  The scan runs k+1 steps so the cache also ingests the last
    proposal (needed when the verify accepts the whole draft); the
    extra step's output is discarded.

    Draft lengths are *set* from the host-tracked truth first: a verify
    rollback shortens the target cache, and syncing here self-heals the
    draft to the same prefix (entries above it are stale-above-length).
    """
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

    return jax.jit(propose, donate_argnums=(2,), **jit_kw)


def build_spec_verify(model, k: int, paged: bool = False, **jit_kw):
    """One big-model pass over ``[t_last, d_0..d_{k-1}]`` (S, k+1):
    ``b = argmax`` of every position's logits, the accepted prefix is
    the longest run of drafts matching ``b``, and the emitted tokens
    ``b[:, :n_acc + 1]`` are ALWAYS the big model's own argmaxes — the
    speculative arm is exact-match with the plain greedy tick by
    construction.  Cache lengths roll back in-graph to
    ``old + n_emit``; rejected-draft rows above are stale-above-length.
    """
    def accept(logits, cache, old_len, tokens, draft, active):
        b = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        acc = jnp.cumprod((b[:, :k] == draft).astype(jnp.int32), axis=1)
        n_emit = jnp.where(active, acc.sum(axis=1) + 1, 0).astype(
            jnp.int32)
        cache = {lk: dict(c, length=old_len[lk] + n_emit)
                 for lk, c in cache.items()}
        emitted = jnp.where(active[:, None], b, tokens[:, None])
        return cache, emitted, n_emit

    def verify(params, state, cache, tokens, draft, active):
        old_len = {lk: c["length"] for lk, c in cache.items()}
        x = jnp.concatenate([tokens[:, None], draft], axis=1)
        logits, cache = model.extend(params, state, cache, x)
        return accept(logits, cache, old_len, tokens, draft, active)

    def verify_paged(params, state, cache, table, tokens, draft,
                     active):
        old_len = {lk: c["length"] for lk, c in cache.items()}
        x = jnp.concatenate([tokens[:, None], draft], axis=1)
        logits, cache = model.extend_paged(params, state, cache, table,
                                           x, active)
        return accept(logits, cache, old_len, tokens, draft, active)

    return jax.jit(verify_paged if paged else verify,
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
    tick, each declared prefill bucket, and the slot writes — against
    a deviceless TPU topology (the
    tools/tpu_aot_check.py machinery), so a decode rollout is
    Mosaic-lowering-proven before any chip window
    (``tools/serving_aot_check.py --decode``).  ``page_size`` adds the
    paged tick + paged slot write (``kv_dtype='int8'``
    compiles the quantized pool variant too), ``prefill_chunk`` the
    chunked-prefill program, and ``draft_model`` the speculative
    propose/verify pair.  Returns the failure count; ``log`` receives
    one line per program."""
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
