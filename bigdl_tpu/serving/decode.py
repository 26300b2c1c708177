"""Continuous-batching cached-decode engine (docs/decoding.md).

The autoregressive analog of :class:`~bigdl_tpu.serving.engine.
ServingEngine`: where the stateless engine amortizes dispatch across a
batch of independent forwards, this engine amortizes *decoding* across
a fixed grid of in-flight sequences.

Three parts, each with one job:

* **The scheduler** (:class:`DecodeEngine`) — the loop thread: drain the
  queue, admit (prefill into free slots), step a chunked prefill, fund
  the slots with room, dispatch one *round*, retire.  A round hands
  back ``(emitted (S, K), n_emit (S,))``: the grid tick is the round
  with K = 1, draft-propose + verify the round with K = ``draft_k`` +
  1; one ``_retire`` takes either.  The tick round keeps one tick in
  flight: tick n+1 is enqueued from tick n's device outputs before
  tick n's tokens are read (docs/decoding.md §One tick in flight).
  Continuous batching: a finished
  sequence (EOS / token budget / deadline) retires at TOKEN granularity
  and frees its slot at once; ``continuous=False`` degrades to static
  run-to-completion waves, the baseline arm of ``bench.py --decode-ab``.
* **A model lane** (:class:`_Lane`) — one model's parameters, its cache
  and its compiled programs (serving/decode_programs.py).  The target
  is one lane and a draft another; warm-up, admission and the chunk
  step run the same calls on each.  The lane counts compiles: shapes
  never depend on occupancy, so steady-state decode never recompiles.
* **A cache manager** (serving/paging.py) — the K/V layout, dense rows
  or a page pool: it builds the cache and the programs that depend on
  the layout and answers ``reserve`` / ``release``.  The page *policy*
  (oldest first, evict strictly younger, pause) is the scheduler's.

Prompts are padded onto the declared (batch x prompt-length)
BucketGrid and run through a compiled prefill that returns the first
token's logits plus the prompt's rows; a compiled slot write splices
those rows into the grid cache (donated: rebound, never copied).

**Deadline semantics** — a request whose deadline expires before its
prefill fails fast with :class:`DeadlineExceededError` (same as the
stateless engine); once decoding has started, an expiring deadline
*truncates*: the tokens generated so far are delivered as the result.
Admission control (bounded queue -> ``QueueFullError``) and
per-request exception delivery mirror :class:`ServingEngine`.

**Metrics** — tokens/s, slot occupancy, prefill/decode split and
per-tick latency percentiles on :class:`~bigdl_tpu.serving.metrics.
ServingMetrics`, exportable to TensorBoard via
``ServingMetrics.write_summary``.
"""
from __future__ import annotations

import collections
import functools
import inspect
import itertools
import queue
import threading
import time
from typing import (Callable, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import numpy as np
from jax.profiler import StepTraceAnnotation

from bigdl_tpu.ops import paged_kv
from bigdl_tpu.serving import paging
from bigdl_tpu.serving.bucketing import BucketGrid
from bigdl_tpu.serving.decode_programs import (
    build_draft_propose,
    build_prefill,
    build_prefill_chunk,
)
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


class _DecodeRequest:
    __slots__ = ("prompt", "max_new", "fut", "t_submit", "deadline",
                 "rid", "temp", "top_k", "top_p", "key", "keep_blocks")

    def __init__(self, prompt, max_new, fut, t_submit, deadline, rid=0,
                 temp=0.0, top_k=0, top_p=1.0, key=None,
                 keep_blocks=False):
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
        self.keep_blocks = keep_blocks


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


class _Flight(NamedTuple):
    """A tick enqueued and not yet read: its device outputs, the rows
    it ran (``mask``) with the :class:`_Slot` each was dispatched for
    (``owners``), and the ``args`` of its own ``loop/tick_dispatch``
    span, which the read fills with the model's counters."""

    nxt: jax.Array
    keys: jax.Array
    counters: dict
    mask: np.ndarray
    owners: List[Optional[_Slot]]
    args: dict


_CLOSE = object()  # queue sentinel



class _Lane:
    """One model on the slot grid: its parameters, its cache and its
    compiled programs.  The lane keeps the compiled-program keys (the
    recompile counter lives here): parameters, state and dtype are
    fixed, so its key set is exactly jit's cache key set and the count
    is exact."""

    def __init__(self, prefix: str, model, variables: dict, kv,
                 max_len: int, chunked: bool, metrics: ServingMetrics,
                 tracer):
        import jax.numpy as jnp

        self.prefix = prefix  # names its programs in the X-ray registry
        self.model = model
        self.params = variables["params"]
        self.state = variables["state"]
        self.dtype = self.params["embed"]["weight"].dtype \
            if "embed" in self.params else jnp.float32
        self.kv = kv
        self.max_len = max_len
        self.cache = kv.init_cache(model, self.dtype)
        self.programs = {
            "prefill": build_prefill(model, max_len, self.dtype),
            "write": kv.build_write()}
        if chunked:
            self.programs["chunk"] = build_prefill_chunk(model)
        self.warming = False  # declared-grid compiles skip forensics
        self._metrics = metrics
        self._tracer = tracer
        self._seen: set = set()

    def drop(self):
        """Let go of everything that lives on the device."""
        self.params = self.state = self.cache = None
        self.programs = {}

    def call(self, name: str, program: str, *args, key: tuple = (),
             cost=None):
        """Run the compiled ``name`` on ``args``; first sight of
        ``(name,) + key`` is counted (and timed) as a compile and
        registered with the X-ray registry as ``program``.  Nothing is
        fingerprinted on a seen key."""
        jitted = self.programs[name]
        key = (name,) + key
        if key in self._seen:
            programs.get_program_registry().record_call(program)
            return jitted(*args)
        # before the call (ticks and writes donate their cache), and
        # registered before ``record_recompile`` so the forensic instant
        # precedes the recompile span the Watchdog pairs it with
        sig = self._signature(jitted, args)
        t0 = time.perf_counter()
        # which tick (ambient correlation) paid for which program
        with self._tracer.span("compile", CAT_DECODE,
                               args={"program": program}):
            out = jitted(*args)
        dt = time.perf_counter() - t0
        programs.get_program_registry().register_compile(
            program, sig, compile_s=dt, cost=cost, expected=self.warming)
        self._metrics.record_recompile(dt)
        self._seen.add(key)
        return out

    @staticmethod
    def _signature(jitted, args):
        """Argument names from the program's own parameters, donation
        from its trace; None when either cannot be had (forensics are
        optional)."""
        try:
            names = list(inspect.signature(jitted).parameters)
            info = jitted.trace(*args).args_info[0]
            donated = [n for n, a in zip(names, info)
                       if any(leaf.donated
                              for leaf in jax.tree_util.tree_leaves(a))]
            return programs.signature_of(dict(zip(names, args)),
                                         donated=donated)
        except Exception:
            return None

    def declare(self, grid: BucketGrid, chunk: Optional[int]):
        """This lane's programs as ``(key, thunk)``: a prefill per
        declared bucket, a slot write per declared batch size, the
        chunk program and, when 1 is not a declared batch, the batch-1
        write of its staging cache.  ``held`` carries a prefilled batch
        to the write that follows it, and nothing longer than that (a
        batch of ``max_len`` rows is hundreds of MB at a cell's size)."""
        def prefill(held, ids, lengths, keep):
            pcache = self.prefill(ids, lengths)[1]
            if keep:
                held[self.prefix, len(ids)] = pcache

        def chunked(held, ids, adv, keep):
            staging = self.chunk(self.staging(), ids, adv)[1]
            if keep:
                held[self.prefix, 1] = staging

        def write(held, batch):
            self.write(held.pop((self.prefix, batch)), 0, 0, batch=batch)

        progs, batches = [], set()

        def declare(key, fill, batch, **args):
            """``fill`` and, for a batch size not seen yet, its write."""
            new = batch not in batches
            batches.add(batch)
            progs.append(((self.prefix,) + key,
                          functools.partial(fill, keep=new, **args)))
            if new:
                progs.append(((self.prefix, "write", batch),
                              functools.partial(write, batch=batch)))

        for bucket in grid.declared_buckets():
            ids = np.zeros((bucket.batch,) + bucket.dims, np.int32)
            declare(("prefill",) + ids.shape, prefill, bucket.batch,
                    ids=ids, lengths=np.ones((bucket.batch,), np.int32))
        if chunk:
            declare(("chunk",), chunked, 1,
                    ids=np.zeros((1, chunk), np.int32),
                    adv=np.ones((1,), np.int32))
        return progs

    # -------------------------------------- the calls every lane makes
    def prefill(self, ids: np.ndarray, lengths: np.ndarray):
        return self.call("prefill", f"{self.prefix}_prefill", self.params,
                         self.state, ids, lengths, key=(ids.shape,))

    def write(self, pcache, row: int, slot: int, batch: int):
        """Splice row ``row`` of a prefilled batch into ``slot``; the
        compiled shape depends only on the batch bucket (prompt length
        never survives into cache shapes)."""
        self.cache = self.call(
            "write", f"{self.prefix}_write_slot", self.cache,
            *self.kv.write_extra(slot), pcache, row, slot, key=(batch,))

    def staging(self):
        """A fresh batch-1 cache for a chunked prefill."""
        return self.model.init_cache(1, self.max_len, self.dtype)

    def chunk(self, staging, ids: np.ndarray, adv: np.ndarray):
        last, staging = self.call(
            "chunk", f"{self.prefix}_prefill_chunk", self.params,
            self.state, staging, ids, adv)
        return np.asarray(last), staging


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
    pool of ops/paged_kv.py (``page_size``, default 16 tokens;
    ``num_pages``, default every slot at ``max_len``; retirement frees
    pages back to the host-side allocator), and ``kv_dtype="int8"``
    stores the pool quantized.  ``prefill_chunk=C`` feeds prompts longer
    than the largest declared bucket through a batch-1 chunked prefill,
    ``C`` tokens per loop iteration, instead of stalling the tick.
    ``draft=(draft_model, draft_variables)`` turns on speculative
    decoding: each round the draft proposes ``draft_k`` (default 3)
    tokens and one verify pass of the big model accepts the longest
    matching prefix (greedy-only; emitted tokens are exactly the big
    model's argmaxes).
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
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.eos_id = eos_id
        self.default_deadline_ms = default_deadline_ms
        self.continuous = continuous
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self.grid = BucketGrid([(int(t),) for t in prompt_buckets],
                               prefill_batch_sizes, pad_value=0)
        self._largest_bucket = max(int(t) for t in prompt_buckets)
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk \
            else None
        self._tracer = get_tracer()

        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout must be 'dense' or 'paged', "
                             f"got {kv_layout!r}")
        if kv_dtype is not None and kv_layout != "paged":
            raise ValueError("kv_dtype requires kv_layout='paged'")
        self.kv_layout = kv_layout
        self.draft_k = 0
        if draft is not None:
            self.draft_k = int(draft_k if draft_k is not None else 3)
            if self.draft_k < 1:
                raise ValueError(f"draft_k must be >= 1, got "
                                 f"{self.draft_k}")
        if kv_layout == "paged":
            page_size = int(page_size if page_size is not None else 16)
            self._kv = paging.PagedCache(
                self.slots, self.max_len, page_size,
                int(num_pages if num_pages is not None
                    else paging.default_num_pages(self.slots,
                                                  self.max_len,
                                                  page_size)),
                kv_dtype, gauge=self.metrics.record_pages,
                step=self._page_slack())
        else:
            self._kv = paging.DenseCache(self.slots, self.max_len)

        def lane(prefix, model, variables, kv):
            return _Lane(prefix, model, variables, kv, self.max_len,
                         bool(self.prefill_chunk), self.metrics,
                         self._tracer)

        self._target = lane("decode", model, variables, self._kv)
        self._target.programs["tick"] = self._kv.build_tick(model)
        self._draft: Optional[_Lane] = None
        self._round = self._tick_round
        self._round_note = "ticks"  # the X-ray's count of a row's rounds
        if draft is not None:
            # the draft's cache stays dense: it is small by construction
            # and its lengths self-heal from the host ledger each round
            self._draft = lane("draft", draft[0], draft[1],
                               paging.DenseCache(self.slots,
                                                 self.max_len))
            self._draft.programs["propose"] = build_draft_propose(
                draft[0], self.draft_k)
            self._target.programs["verify"] = self._kv.build_verify(
                model, self.draft_k)
            self._round = self._spec_round
            self._round_note = "spec_rounds"
        self._lanes = [ln for ln in (self._target, self._draft) if ln]
        self._declared = self._declare()
        self._tick_cost = None  # ProgramCost, stamped before first tick

        self._tokens = np.zeros((self.slots,), np.int32)
        self._active = np.zeros((self.slots,), bool)
        self._slot_state: List[Optional[_Slot]] = [None] * self.slots
        # per-slot sampling state: raw PRNG keys round-trip through the
        # tick as data; temp == 0 rows stay exact-greedy
        self._keys = np.zeros((self.slots, 2), np.uint32)
        self._temps = np.zeros((self.slots,), np.float32)
        self._topks = np.zeros((self.slots,), np.int32)
        self._topps = np.ones((self.slots,), np.float32)
        # host mirror of each slot's cache extent, counting what the
        # rounds dispatched so far have written (prompt + generated - 1
        # once they are read): drives page budgeting and draft-length
        # resync.  The round advances it, not the retirement
        self._host_len = np.zeros((self.slots,), np.int32)
        # the extent at which a slot's budget ends (prompt + max_new -
        # 1): a budget end is known by count, ``_host_len`` reaching
        # it, a tick before its token is read
        self._limit = np.zeros((self.slots,), np.int32)
        # the tick pipeline (depth one): the tick enqueued and unread,
        # the rows of the tick last read that still held their slot,
        # the rows whose token and key the host wrote since a tick last
        # took both from the mirrors, and the host-decided tick inputs
        # as last uploaded, ``name -> (host copy, device array)``
        self._flight: Optional[_Flight] = None
        self._served = np.zeros((self.slots,), bool)
        self._host_rows = np.zeros((self.slots,), bool)
        self._uploads: dict = {}
        self._chunking: Optional[dict] = None
        self._chunk_pending: "collections.deque[_DecodeRequest]" = \
            collections.deque()

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
    # the programs: one declaration, which warm-up runs
    # ------------------------------------------------------------------
    @property
    def recompiles(self) -> int:
        return self.metrics.recompiles

    def _declare(self) -> List[Tuple[tuple, Callable[[dict], None]]]:
        """Every program this engine compiles, in an order that can
        run, as ``(key, thunk)``: the round's (the tick, or propose +
        verify), then each lane's (:meth:`_Lane.declare`).  A thunk
        takes the dict in which earlier thunks left what later ones
        consume."""
        if self._draft is None:
            progs = [(("tick",), lambda held: self._warm_tick())]
        else:
            progs = [(("propose",), lambda held: held.update(
                         props=self._run_propose())),
                     (("verify",), lambda held: self._run_verify(
                         held.pop("props")))]
        for lane in self._lanes:
            progs += lane.declare(self.grid, self.prefill_chunk)
        return progs

    def declared_programs(self) -> int:
        """How many compiles a full warm-up performs."""
        return len(self._declared)

    def warmup(self) -> int:
        """Pre-compile every declared program so no request ever waits
        on XLA; returns how many compiles ran (0 on a re-warm).  All
        warm-up executions are safe by the stale-above-length
        invariant: caches are zero, ``active`` is all-False, and paged
        writes land on the trash page."""
        before = self.metrics.recompiles
        for lane in self._lanes:
            lane.warming = True
        try:
            self._stamp_tick()
            held: dict = {}
            for _, thunk in self._declared:
                thunk(held)
        finally:
            for lane in self._lanes:
                lane.warming = False
        return self.metrics.recompiles - before

    def _sent(self, name: str, host: np.ndarray):
        """The device copy of a host-decided tick input, uploaded again
        only when the host's value differs from what was last sent."""
        last = self._uploads.get(name)
        if last is None or not np.array_equal(last[0], host):
            # the copy goes up, not the mirror: the host writes its
            # mirrors in place while the tick is in flight
            snap = host.copy()
            last = self._uploads[name] = (snap, jax.device_put(snap))
        return last[1]

    def _tick_args(self, mask: np.ndarray,
                   prev: Optional[_Flight] = None):
        """The tick's arguments for the rows of ``mask``: tokens and
        keys are ``prev``'s device outputs, or the host mirrors when
        nothing is in flight; the rest is what the host decides, kept
        on the device between the turns that change it."""
        t = self._target
        tokens, keys = (self._tokens.copy(), self._keys.copy()) \
            if prev is None else (prev.nxt, prev.keys)
        extra = tuple(self._sent(f"extra{i}", x)
                      for i, x in enumerate(self._kv.tick_extra()))
        return (t.params, t.state, t.cache) + extra + (
            tokens, self._sent("active", mask), keys,
            self._sent("temps", self._temps),
            self._sent("topks", self._topks),
            self._sent("topps", self._topps))

    def _verify_args(self, props):
        t = self._target
        return (t.params, t.state, t.cache) + self._kv.tick_extra() + (
            self._tokens, props, self._active)

    def _stamp_tick(self):
        """Stamp the round's flops/bytes (re-trace only).  Must run
        while the cache buffers are live — before a round donates them
        — so stamping happens at warmup/start, never in the loop.
        Speculative engines stamp the verify pass — the program that
        touches the full cache each round."""
        if self._tick_cost is not None:
            return
        if self._draft is None:
            cost = costmodel.stamp_jitted(
                "decode_tick", self._target.programs["tick"],
                *self._tick_args(self._active))
        else:
            cost = costmodel.stamp_jitted(
                "spec_verify", self._target.programs["verify"],
                *self._verify_args(np.zeros((self.slots, self.draft_k),
                                            np.int32)))
        if cost is not None:
            self._tick_cost = cost

    def _dispatch_tick(self, mask: np.ndarray,
                       prev: Optional[_Flight]) -> _Flight:
        """Enqueue the grid tick for the rows of ``mask`` and return it
        unread.  ``prev`` is the tick still unread whose device outputs
        feed this one (None: the host mirrors do)."""
        # the tick's own predicate, known here without asking the
        # device: the rows whose sampling epilogue this tick runs
        sampled_rows = int(((self._temps > 0) & mask).sum())
        args = dict(self._kv.span_args() or {}, sampled_rows=sampled_rows,
                    in_flight=int(prev is not None))
        if prev is None:
            self._host_rows[:] = False  # the mirrors go in whole
        t = self._target
        with self._tracer.span("loop/tick_dispatch", CAT_DECODE,
                               args=args):
            # the model's counters are kept only while somebody reads
            # them, decided here: a session can open or close before
            # this tick is read, and they belong on this tick's span
            lit = self._tracer.enabled
            # the paged tick also hands out the model's counters
            t.cache, nxt, keys, *counters = t.call(
                "tick", "decode_tick", *self._tick_args(mask, prev),
                cost=self._tick_cost)
        # the tick writes each row's token at its extent: known by
        # count, so the next turn funds and masks without the read
        self._host_len[mask] += 1
        return _Flight(nxt, keys, counters[0] if counters and lit else {},
                       mask, list(self._slot_state), args)

    def _read_tick(self, flight: _Flight, whole: bool
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """Wait for ``flight``'s ``(S,)`` tokens (the per-tick host sync
        point); returns them and the rows they are for: a token belongs
        to the ``_Slot`` it was dispatched for, and a slot that was
        evicted, truncated or freed since drops it.  ``whole`` (the
        pipeline drains) also fetches the keys and makes the host
        mirrors whole again, but for the rows the host itself wrote."""
        with self._tracer.span("loop/tick_wait", CAT_DECODE):
            # the model's counters come back in the tokens' own read
            nxt, keys, counters = jax.device_get(
                (flight.nxt, flight.keys if whole else None,
                 flight.counters))
            for name, value in counters.items():
                flight.args[name] = np.asarray(value).tolist()
            if whole:
                device = ~self._host_rows
                self._tokens[device] = nxt[device]
                self._keys[device] = keys[device]
            served = flight.mask.copy()
            for s in np.flatnonzero(served):
                served[s] = self._slot_state[s] is flight.owners[s]
        if served.any():
            # a tick the loop times: what it was is counted on the same
            # event, so the shares of the ticks timed stay within 0-1
            if flight.args["sampled_rows"]:
                self.metrics.inc_sampled_ticks()
            if flight.args["in_flight"]:
                self.metrics.inc_overlapped_ticks()
        return nxt, served

    def _rows_due(self) -> np.ndarray:
        """The rows the next tick runs: active, with a token of their
        budget still to dispatch (a budget end is known by count, so no
        token is computed past one)."""
        return self._active & (self._host_len < self._limit)

    def _warm_tick(self):
        """Run the tick as the loop will: once from the host mirrors
        and once from a tick's device outputs with the inputs already
        uploaded, so the first overlapped call of a run finds its
        executable.  No row is active: nothing is written or served."""
        idle = np.zeros((self.slots,), bool)
        first = self._dispatch_tick(idle, None)
        self._read_tick(self._dispatch_tick(idle, first), whole=True)

    def _run_tick(self):
        """One turn of the tick pipeline, whose depth is one: enqueue
        the next tick from the outputs of the one in flight, *then*
        read that one; returns its ``(S,)`` tokens, for the rows of
        ``_served`` (the others hold what the device or the host had).
        Two turns read first, so that the host mirrors are whole: one
        after an admission wrote a token and a key into them, which
        then dispatches from them, and one that has no row left to
        dispatch.  A turn that finds nothing in flight only enqueues
        and serves nothing."""
        prev = self._flight
        mask = self._rows_due()
        if prev is not None and (self._host_rows.any() or not mask.any()):
            self._flight = None
            nxt, self._served = self._read_tick(prev, whole=True)
            if mask.any():
                self._flight = self._dispatch_tick(mask, None)
            return nxt
        self._flight = self._dispatch_tick(mask, prev)
        if prev is None:
            self._served = np.zeros((self.slots,), bool)
            return self._tokens.copy()
        nxt, self._served = self._read_tick(prev, whole=False)
        return nxt

    def _run_propose(self):
        d = self._draft
        d.cache, props = d.call(
            "propose", "draft_propose", d.params, d.state, d.cache,
            self._tokens, self._host_len, self._active)
        return props  # stays on device: the verify consumes it

    def _run_verify(self, props):
        """Dispatch the verify pass; returns the device ``(emitted,
        n_emit)`` — fetching them is the round's single host sync."""
        t = self._target
        t.cache, emitted, n_emit = t.call(
            "verify", "spec_verify", *self._verify_args(props),
            cost=self._tick_cost)
        return emitted, n_emit

    # ------------------------------------------------------------------
    # rounds: what one loop turn dispatches.  Each hands back
    # ``(emitted (S, K), n_emit (S,))`` on the host
    # ------------------------------------------------------------------
    def _tick_round(self):
        """K = 1: every row the tick read was dispatched for, and that
        still holds its slot, emits the tick's one token."""
        return self._run_tick()[:, None], self._served

    def _spec_round(self):
        """K = ``draft_k`` + 1: the accepted prefix of the draft's
        proposals and the big model's bonus token."""
        with self._tracer.span("loop/tick_dispatch", CAT_DECODE,
                               args=self._kv.span_args()):
            out = self._run_verify(self._run_propose())
        with self._tracer.span("loop/tick_wait", CAT_DECODE):
            emitted, n_emit = jax.device_get(out)
        self._host_len += n_emit  # what the verify accepted stays
        return np.asarray(emitted), np.asarray(n_emit)

    # ------------------------------------------------------------------
    # client API
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int,
               deadline_ms: Optional[float] = None, *,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0,
               seed: Optional[int] = None,
               keep_blocks: bool = False) -> ServingFuture:
        """Queue one prompt (1-D int array, len >= 1); returns a future
        resolving to the generated token ids (1-D ``int32``, EOS
        included when hit).  ``temperature > 0`` samples inside the
        tick (``top_k``/``top_p`` filter, ``seed`` makes the stream
        reproducible; defaults to the request id); ``temperature == 0``
        is exact greedy.  ``keep_blocks`` leaves on the future, as
        ``fut.blocks`` ``{layer: {leaf: array}}``, the fixed blocks
        (ops/paged_kv.Block: a state-space layer's state) the request's
        slot holds when it finishes: the state after every token fed to
        the model, which is the prompt and every generated token but the
        last.  Raises :class:`QueueFullError` when the
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
        if temperature > 0.0 and self._draft is not None:
            raise ValueError(
                "speculative decoding is greedy-only: the verify pass "
                "accepts draft tokens by argmax match, which sampling "
                "would break")
        if temperature > 0.0 and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        # speculative rounds may write up to draft_k tokens past the
        # last emitted position before rollback — reserve the slack
        slack = self.draft_k
        worst = int(prompt.size) + max_new_tokens - 1 + slack
        if worst > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) - 1"
                + (f" + draft_k ({slack})" if slack else "")
                + f" exceeds the cache max_len ({self.max_len})")
        self._kv.check_servable(worst)
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
                                               else seed),
                             keep_blocks=keep_blocks)
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
            # HbmLedger resident lane: a paged cache reports bytes
            # proportional to pages actually in use — the readout that
            # retirement frees memory — a dense one its fixed
            # worst-case reservation
            self._resident_name = self._kv.resident_name
            programs.get_hbm_ledger().add_resident(
                self._resident_name, self._kv.resident_bytes)

    def close(self, drain: bool = True, timeout: float = 60.0):
        """Stop accepting requests and shut down.  ``drain=True``
        (default) decodes everything already queued/in flight to
        completion first; ``drain=False`` fails undelivered requests
        with :class:`EngineClosedError`.  Once the loop thread has
        ended the lanes let go of parameters, caches and compiled
        programs, so device memory is free when this returns and not
        when a collector gets to the engine.  Idempotent."""
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
        if self._started:
            self._rq.put(_CLOSE)
            self._loop_thread.join(timeout)
            if self._loop_thread.is_alive():
                return  # still decoding: the lanes are the loop's
        else:
            self._fail_queued(EngineClosedError(
                "decode engine closed before start"))
        self._chunking = None
        self._flight = None
        self._uploads = {}
        for lane in self._lanes:
            lane.drop()

    def _fail_queued(self, exc):
        while True:
            try:
                req = self._rq.get_nowait()
            except queue.Empty:
                break
            if req is not _CLOSE:
                self.xray.drop(req.rid)
                req.fut.set_exception(exc)
        for waiting in (self._pending, self._chunk_pending):
            while waiting:
                req = waiting.popleft()
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
    # engine loop: admit (prefill into free slots) then run a round
    # ------------------------------------------------------------------
    def _idle(self) -> bool:
        return (not np.any(self._active) and self._flight is None
                and not self._pending
                and self._chunking is None and not self._chunk_pending
                and all(st is None for st in self._slot_state))

    def _loop(self):
        """One turn: drain, admit, chunk, budget, round, retire — each a
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
                stopping = self._drain_queue(block=self._idle(),
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
            # fund (and resume) occupied slots before the round — must
            # run even when everything is paused
            with tr.span("loop/budget_pages", CAT_DECODE):
                self._budget_pages()
            if self._flight is None and not np.any(self._rows_due()):
                # nothing to enqueue and nothing to read
                if stopping and self._idle():
                    return
                continue
            self._tick_no += 1
            t0 = time.perf_counter()
            spec_rids: Sequence[int] = ()
            if self._draft is not None and self.xray.enabled:
                # the draft + verify round itself is the spec_verify
                # budget; the gaps between rounds stay on the resident
                # lane
                spec_rids = [self._slot_state[s].req.rid
                             for s in range(self.slots)
                             if self._active[s]
                             and self._slot_state[s] is not None]
                self.xray.to_many(spec_rids, request_xray.PHASE_SPEC,
                                  now=t0)
            with StepTraceAnnotation("decode_tick",
                                     step_num=self._tick_no):
                emitted, n_emit = self._round()
            now = time.perf_counter()
            args = {}
            with tr.span("loop/retire", CAT_DECODE, args=args):
                if spec_rids:
                    self.xray.to_many(spec_rids,
                                      request_xray.PHASE_RESIDENT, now=now)
                # the rows the round served: those of the tick it read,
                # none on the turn that only fills the pipeline
                n_active = int(np.count_nonzero(n_emit))
                if n_active:
                    self.metrics.record_tick(now - t0)
                    self.metrics.record_slot_occupancy(
                        n_active / self.slots)
                gaps = self._retire(emitted, n_emit, now)
                self.metrics.record_decode_tokens(len(gaps))
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

    def _expired(self, req: _DecodeRequest, now: float,
                 where: str) -> bool:
        """Fail ``req`` fast when its deadline passed before decoding
        started (nothing of it is in the grid cache yet)."""
        if req.deadline is None or now <= req.deadline:
            return False
        self.metrics.inc_expired()
        self._tracer.instant("deadline_reject", CAT_DECODE,
                             corr=f"req:{req.rid}")
        req.fut.set_exception(DeadlineExceededError(
            f"deadline expired {1e3 * (now - req.deadline):.1f}ms "
            f"{where}", attribution=self.xray.close(req.rid, now=now)))
        return True

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
            if not self._expired(req, now, "before prefill"):
                taken.append(req)
        # admission never evicts (an evicted request re-queues and
        # could evict its evictor right back — livelock): a request
        # whose prompt the cache manager has no room for waits, and
        # those behind it, until retirement frees some
        room = self._kv.pages_free
        for i, req in enumerate(taken):
            room -= self._kv.pages_for(int(req.prompt.size)
                                       + self._page_slack())
            if room < 0:
                self._pending.extendleft(reversed(taken[i:]))
                del taken[i:]
                break
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
        # the padded rows the prefill program runs (a bucket's batch x
        # length): what a reader of its traced device time divides by
        with tr.span("prefill_dispatch", CAT_DECODE,
                     args={"rows": b * int(np.prod(dims))}):
            ids = self.grid.pad_batch([r.prompt for r in chunk], dims, b,
                                      np.int32)
            lengths = np.ones((b,), np.int32)
            lengths[:len(chunk)] = [r.prompt.size for r in chunk]
            logits, pcache = self._target.prefill(ids, lengths)
        with tr.span("prefill_wait", CAT_DECODE):
            logits = np.asarray(logits)
        # one prefilled batch per lane, the target's first
        pcaches = [pcache] + [lane.prefill(ids, lengths)[1]
                              for lane in self._lanes[1:]]
        admitted = 0
        for i, r in enumerate(chunk):
            self.xray.to(r.rid, request_xray.PHASE_SAMPLE)
            with tr.span("host_sample", CAT_DECODE, corr=f"req:{r.rid}"):
                tok0 = _host_sample(logits[i], r)
            t_tok = time.perf_counter()
            if self._done_at_first(r, tok0, t_tok):
                continue
            slot = next(free_iter)
            if not self._kv.reserve(slot, int(r.prompt.size)
                                    + self._page_slack()):
                # admission counted this room in; losing it is
                # unexpected but recoverable — wait, don't evict
                self.xray.to(r.rid, request_xray.PHASE_PAGE_STALL)
                self._pending.appendleft(r)
                continue
            # dispatched without waiting: the write's device time is
            # paid inside the next tick's token fetch
            with tr.span("slot_write", CAT_DECODE, corr=f"req:{r.rid}"):
                for lane, pc in zip(self._lanes, pcaches):
                    lane.write(pc, i, slot, batch=b)
            self._activate(slot, r, tok0, t_tok)
            admitted += 1
        return admitted

    def _done_at_first(self, req: _DecodeRequest, tok0: int,
                       t_tok: float) -> bool:
        """Deliver a request its prefill token already finishes."""
        eos = self.eos_id is not None and tok0 == self.eos_id
        if not eos and req.max_new > 1:
            return False
        self._finish(req, [tok0], [t_tok], "eos" if eos else "length")
        return True

    def _activate(self, slot: int, req: _DecodeRequest, tok0: int,
                  t_tok: float):
        """Bind a prefilled request to its slot: token feed, sampling
        state, and the host length ledger."""
        self._tokens[slot] = tok0
        self._keys[slot] = req.key
        # the next tick takes both from the mirrors, once the tick in
        # flight has been read into them
        self._host_rows[slot] = True
        self._active[slot] = True
        self._slot_state[slot] = _Slot(req, tok0, t_tok)
        self._host_len[slot] = int(req.prompt.size)
        self._limit[slot] = int(req.prompt.size) + req.max_new - 1
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
                if self._expired(req, now, "before prefill"):
                    return 0
                self.xray.to(req.rid, request_xray.PHASE_PREFILL,
                             now=now)
                self._chunking = {
                    "req": req, "slot": free[0], "offset": 0,
                    "staging": [lane.staging() for lane in self._lanes]}
        c = self._chunking
        if c is None:
            return 0
        if "tok0" in c:
            # prefill finished earlier but the page pool was full: keep
            # retrying as ticks retire slots and free pages
            self._finalize_chunk(c)
            return 0
        req = c["req"]
        if self._expired(req, time.perf_counter(),
                         f"into a chunked prefill ({c['offset']}/"
                         f"{req.prompt.size} tokens in)"):
            self._chunking = None  # the slot stays clean
            return 0
        t0 = time.perf_counter()
        size = self.prefill_chunk
        lo = c["offset"]
        hi = min(lo + size, int(req.prompt.size))
        ids = np.zeros((1, size), np.int32)
        ids[0, :hi - lo] = req.prompt[lo:hi]
        adv = np.array([hi - lo], np.int32)
        lasts = []
        for i, lane in enumerate(self._lanes):
            last, c["staging"][i] = lane.chunk(c["staging"][i], ids, adv)
            lasts.append(last)
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
        tok0 = _host_sample(lasts[0][0], req)
        c["t_tok"] = time.perf_counter()
        if self._done_at_first(req, tok0, c["t_tok"]):
            self._chunking = None
            return hi - lo
        c["tok0"] = tok0
        self._finalize_chunk(c)
        return hi - lo

    def _finalize_chunk(self, c: dict):
        """Splice a fully chunk-prefilled request into its reserved
        slot — deferred while the cache manager has no room (admission
        never evicts; see :meth:`_ensure_pages`)."""
        req, slot = c["req"], c["slot"]
        if not self._kv.reserve(slot, int(req.prompt.size)
                                + self._page_slack()):
            self.xray.to(req.rid, request_xray.PHASE_PAGE_STALL)
            return  # retry next loop iteration
        self._chunking = None
        for lane, staging in zip(self._lanes, c["staging"]):
            lane.write(staging, 0, slot, batch=1)
        self._activate(slot, req, c["tok0"], c["t_tok"])

    # ------------------------------------------------------------------
    # the page policy, against the cache manager's reserve / release
    # ------------------------------------------------------------------
    def _page_slack(self) -> int:
        """Tokens a slot may write beyond its current valid length in
        one round: the next tick's token, plus the speculative write-
        ahead window."""
        return 1 + self.draft_k

    def _budget_pages(self):
        """Before each round, fund every occupied slot with room for
        the tokens this round can write — oldest request first.  A slot
        the cache manager cannot fund may evict strictly *younger*
        requests (they re-queue and re-decode deterministically); with
        no younger donor it is *paused* — deactivated but keeping its
        pages and generated state — and resumes once retirement frees
        pages.  The oldest occupied slot can always be funded (submit
        guarantees every request fits an empty pool), so at least one
        slot always progresses: no evict/re-admit livelock."""
        # a slot whose whole budget is dispatched writes nothing more
        order = sorted(
            (s for s in range(self.slots)
             if self._slot_state[s] is not None
             and self._host_len[s] < self._limit[s]),
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
        """Grow ``slot`` to cover ``tokens``; when the manager has no
        room, evict the youngest occupied slot whose request is newer
        than this slot's.  Returns False when no such donor exists."""
        me = self._slot_state[slot].req.rid \
            if self._slot_state[slot] is not None else -1
        while not self._kv.reserve(slot, tokens):
            victim, rid = None, me
            for s in range(self.slots):
                # a row whose last token is in flight frees its pages
                # at the next read: waiting a turn beats re-decoding it
                if s == slot or self._slot_state[s] is None \
                        or self._host_len[s] >= self._limit[s]:
                    continue
                r = self._slot_state[s].req.rid
                if r > rid:
                    victim, rid = s, r
            if victim is None:
                return False
            self._evict(victim)
        return True

    def _evict(self, victim: int):
        st = self._slot_state[victim]
        self.metrics.inc_page_evictions()
        self._tracer.instant("page_evict", CAT_DECODE,
                             args={"slot": victim,
                                   "pages": self._kv.owned(victim)})
        if st is not None:
            # deterministic restart: greedy/seeded sampling re-decodes
            # to the same tokens, so eviction costs latency, not output
            # (the whole re-queue wait is charged to the eviction)
            self.xray.to(st.req.rid, request_xray.PHASE_PAGE_STALL)
            self.xray.note(st.req.rid, "page_evictions")
            self._pending.appendleft(st.req)
        self._free(victim)

    # ------------------------------------------------------------------
    # retirement: one path for every round
    # ------------------------------------------------------------------
    def _retire(self, emitted: np.ndarray, n_emit: np.ndarray,
                now: float) -> List[float]:
        """Hand row ``s`` the first ``n_emit[s]`` tokens of
        ``emitted[s]`` (all fetched at ``now``: a round's tokens arrive
        with its one read) and retire the finished; a row that emitted
        nothing is not touched.  Tokens past an end of sequence or the
        request's budget are dropped.  Returns the token gaps
        (seconds), in slot order."""
        proposed = emitted.shape[1] - 1  # what a draft put forward
        gaps: List[float] = []
        for s, n in enumerate(n_emit.tolist()):
            if not n:
                continue
            st = self._slot_state[s]
            req = st.req
            if proposed:
                self.metrics.record_spec(proposed, n - 1)
            self._tokens[s] = emitted[s, n - 1]
            reason = None
            for tok in emitted[s, :n].tolist():
                st.generated.append(tok)
                gaps.append(now - st.times[-1])
                st.times.append(now)
                self.metrics.record_token_gap(gaps[-1])
                if self.eos_id is not None and tok == self.eos_id:
                    reason = "eos"
                    break
                if len(st.generated) >= req.max_new:
                    reason = "length"
                    break
            self.xray.note(req.rid, self._round_note)
            if reason is None and req.deadline is not None \
                    and now > req.deadline:
                # decoding already started: truncate, don't fail
                reason = "deadline"
            if reason is not None:
                if req.keep_blocks:
                    req.fut.blocks = self._slot_blocks(s)
                self._finish(req, st.generated, st.times, reason)
                self._free(s)
        return gaps

    def _slot_blocks(self, slot: int) -> dict:
        """Device copies of the fixed blocks ``slot`` holds.  The cache
        may already be the output of the tick in flight: a row at its
        budget's end was not dispatched in it, so its blocks are
        those its last token left."""
        cache = self._target.cache
        declared = getattr(self._target.model, "decode_state", dict)()
        return {lk: {name: cache[lk][name][slot]
                     for name, spec in leaves.items()
                     if paged_kv.is_block(spec)}
                for lk, leaves in declared.items()
                if any(paged_kv.is_block(v) for v in leaves.values())}

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
        self._limit[slot] = 0
        self._kv.release(slot)
        self._tracer.instant("slot_free", CAT_DECODE,
                             args={"slot": slot})

    # ------------------------------------------------------------------
    def log_line(self) -> str:
        line = self.metrics.log_line()
        if self.xray.enabled:
            line = f"{line} | {self.xray.log_line()}"
        return line
