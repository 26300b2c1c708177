"""High-throughput serving engine: bucketed batching + pipelined
dispatch (reference optim/PredictionService.scala:56-332, grown into a
first-class subsystem per the BigDL papers' end-to-end inference
pipelines).

Design (docs/serving.md):

* **Shape-bucketed compiled forwards** — requests are padded onto a
  declared/learned :class:`~bigdl_tpu.serving.bucketing.BucketGrid`
  and served by AOT-compiled executables cached per bucket, so
  steady-state traffic never recompiles; warmup pre-compiles every
  declared bucket and the recompile counter makes misses visible.
* **Continuous micro-batching with pipelined dispatch** — a dispatcher
  thread coalesces queued requests into bucket batches and *enqueues*
  device calls without waiting (JAX async dispatch), while a drain
  thread fetches results and delivers futures; the bounded in-flight
  queue keeps up to ``pipeline_depth`` batches on the device — the
  serving analog of the training loop's prefetch/deferred-sync design.
* **Admission control** — bounded request queue with fast
  ``QueueFullError`` rejection, per-request deadlines checked before
  dispatch, per-request exception delivery, and a ``close()``/context-
  manager shutdown that drains in-flight work.
* **Metrics** — p50/p95/p99 latency, batch occupancy, queue depth,
  recompile count, throughput (:class:`ServingMetrics`).
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

from bigdl_tpu.serving.bucketing import Bucket, BucketGrid
from bigdl_tpu.serving.metrics import PeriodicMetricsLogger, ServingMetrics
from bigdl_tpu.serving.warmup import build_forward
from bigdl_tpu.telemetry import costmodel, programs
from bigdl_tpu.telemetry import requests as request_xray
from bigdl_tpu.telemetry import workload
from bigdl_tpu.telemetry.tracer import CAT_SERVE, get_tracer


class ServingError(RuntimeError):
    """Base class of serving-engine request failures."""


class QueueFullError(ServingError):
    """Fast rejection: the bounded request queue is full."""


class DeadlineExceededError(ServingError):
    """The request's deadline expired.

    When request attribution is live (docs/observability.md §Request
    X-ray) ``attribution`` carries the exact per-phase budget and the
    message names the dominant phase — a deadline miss always says
    where the time went."""

    def __init__(self, msg: str = "",
                 attribution: Optional[request_xray.Attribution] = None):
        if attribution is not None:
            dom, dom_s = attribution.dominant()
            if dom:
                msg = (f"{msg} [dominant: {dom} {1e3 * dom_s:.1f}ms of "
                       f"{1e3 * attribution.latency:.1f}ms]")
        super().__init__(msg)
        self.attribution = attribution


class EngineClosedError(ServingError):
    """Submitted to (or abandoned by) a closed engine."""


class ServingFuture:
    """Single-request result slot: ``result()`` blocks; exceptions that
    failed the request (model error, deadline, shutdown) re-raise."""

    def __init__(self):
        self._ev = threading.Event()
        self._value: Any = None
        self._exc: Optional[BaseException] = None
        self._callbacks: List[Callable] = []
        self._lock = threading.Lock()
        # DecodeEngine: perf_counter time of each returned token, and
        # the slot's fixed blocks at the end where the request asked
        # for them (``submit(keep_blocks=True)``)
        self.token_times: Optional[Any] = None
        self.blocks: Optional[dict] = None

    def done(self) -> bool:
        return self._ev.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._ev.wait(timeout):
            raise TimeoutError("serving result not ready")
        if self._exc is not None:
            raise self._exc
        return self._value

    def exception(self, timeout: Optional[float] = None):
        if not self._ev.wait(timeout):
            raise TimeoutError("serving result not ready")
        return self._exc

    def add_done_callback(self, fn: Callable[["ServingFuture"], None]):
        with self._lock:
            if not self._ev.is_set():
                self._callbacks.append(fn)
                return
        fn(self)

    def _finish(self):
        with self._lock:
            self._ev.set()
            callbacks, self._callbacks = self._callbacks, []
        for fn in callbacks:
            try:
                fn(self)
            except Exception:
                pass  # a callback must not take down engine threads

    def set_result(self, value):
        self._value = value
        self._finish()

    def set_exception(self, exc: BaseException):
        self._exc = exc
        self._finish()


class _Request:
    __slots__ = ("x", "fut", "t_submit", "deadline", "rid")

    def __init__(self, x, fut, t_submit, deadline, rid=0):
        self.x = x
        self.fut = fut
        self.t_submit = t_submit
        self.deadline = deadline
        self.rid = rid  # correlation ID joining enqueue->deliver spans


_CLOSE = object()  # queue sentinel


class ServingEngine:
    """Bucketed, pipelined inference engine over one compiled forward.

    ``buckets`` declares the padded sample-shape grid (see
    :class:`BucketGrid` for the exactness rule); ``batch_sizes`` the
    batch buckets.  With ``warmup=True`` every declared bucket is
    AOT-compiled at construction.  Thread-safe: ``submit``/``predict``
    may be called from any number of client threads.
    """

    def __init__(self, model, variables: dict, *,
                 buckets: Optional[Sequence[Sequence[int]]] = None,
                 batch_sizes: Sequence[int] = (1, 8, 32),
                 batch_window_ms: float = 2.0,
                 max_queue: int = 1024,
                 pipeline_depth: int = 2,
                 default_deadline_ms: Optional[float] = None,
                 pad_value: float = 0.0,
                 input_dtype=np.float32,
                 warmup: bool = True,
                 start: bool = True,
                 metrics: Optional[ServingMetrics] = None,
                 metrics_log_every_s: Optional[float] = None):
        self.model = model
        self.params = variables["params"]
        self.state = variables["state"]
        self.grid = (buckets if isinstance(buckets, BucketGrid)
                     else BucketGrid(buckets, batch_sizes, pad_value))
        self.batch_window_ms = batch_window_ms
        self.default_deadline_ms = default_deadline_ms
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self._dtype = np.dtype(input_dtype)
        self._tracer = get_tracer()
        self._rids = itertools.count()
        # request X-ray: exact per-request latency budgets + tail
        # exemplars (docs/observability.md §Request X-ray); both are
        # one attribute check per call while the plane is dark
        self.xray = request_xray.RequestLedger(tracer=self._tracer)
        self.exemplars = request_xray.ExemplarReservoir(
            tracer=self._tracer)
        # periodic canonical log line (BIGDL_TPU_METRICS_EVERY_S,
        # default off) so long-running servers self-report
        self._periodic = PeriodicMetricsLogger(
            self.log_line, every_s=metrics_log_every_s)

        import jax

        # hot path: regular jit dispatch (C++ fast path; the AOT
        # Compiled.__call__ costs ~10x more per call in python arg
        # processing — measured, see PERF.md §serving).  The engine
        # tracks bucket keys itself: params/state/dtype are fixed, so
        # our (batch, dims) set is exactly jit's cache key set and the
        # recompile counter is exact.
        self._jit = jax.jit(build_forward(model))
        self._seen_buckets: set = set()
        self._compile_lock = threading.Lock()

        self._rq: "queue.Queue" = queue.Queue(maxsize=max(1, max_queue))
        self._fly: "queue.Queue" = queue.Queue(
            maxsize=max(1, pipeline_depth))
        self._closed = False
        self._discard = False
        self._close_lock = threading.Lock()
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, daemon=True,
            name="bigdl-serve-dispatch")
        self._drainer = threading.Thread(
            target=self._drain_loop, daemon=True, name="bigdl-serve-drain")
        self._started = False

        if warmup and self.grid.dims_grid:
            self.warmup()
        if start:
            self.start()

    # ------------------------------------------------------------------
    # compiled-forward cache (the recompile counter lives here)
    # ------------------------------------------------------------------
    @property
    def declared_buckets(self) -> Tuple[Bucket, ...]:
        return tuple(self.grid.declared_buckets())

    @property
    def recompiles(self) -> int:
        return self.metrics.recompiles

    def warmup(self) -> int:
        """Pre-compile every declared bucket (one traced+compiled+run
        zero batch per bucket) so no steady-state request ever waits on
        XLA; returns how many compiles ran (0 on a re-warm)."""
        before = self.metrics.recompiles
        # declared-grid compiles are expected specializations, not
        # steady-state misses: no forensic records for them
        self._warming = True
        try:
            for bucket in self.grid.declared_buckets():
                self._ensure_bucket(bucket.batch, bucket.dims)
        finally:
            self._warming = False
        return self.metrics.recompiles - before

    def _ensure_bucket(self, batch: int, dims: Tuple[int, ...]):
        """Compile (via the jit cache) the bucket's forward if unseen,
        counting it as a recompile."""
        key = (batch, tuple(dims))
        if key in self._seen_buckets:
            return
        with self._compile_lock:
            if key in self._seen_buckets:
                return
            t0 = time.perf_counter()
            x = np.zeros((batch,) + tuple(dims), self._dtype)
            np.asarray(self._jit(self.params, self.state, x))
            dt = time.perf_counter() - t0
            # stamp this bucket's flops/bytes (re-trace only, no
            # second compile) for the X-ray registry's program table
            cost = costmodel.stamp_jitted(
                f"serving_forward:{batch}x"
                + "x".join(map(str, dims)),
                self._jit, self.params, self.state, x)
            # the X-ray registration emits its forensic instant before
            # record_recompile's span so the Watchdog can pair them
            programs.get_program_registry().register_compile(
                "serving_forward",
                programs.signature_of(
                    {"params": self.params, "state": self.state,
                     "x": x}),
                compile_s=dt, cost=cost,
                expected=getattr(self, "_warming", False))
            self.metrics.record_recompile(dt)
            self._seen_buckets.add(key)

    def _run(self, xp: np.ndarray):
        """Enqueue the forward for a padded bucket batch (async
        dispatch); first sight of a bucket pays its compile here and is
        counted."""
        key = (xp.shape[0], tuple(xp.shape[1:]))
        self._ensure_bucket(*key)
        programs.get_program_registry().record_call("serving_forward")
        return self._jit(self.params, self.state, xp)

    # ------------------------------------------------------------------
    # client API
    # ------------------------------------------------------------------
    def submit(self, x, deadline_ms: Optional[float] = None
               ) -> ServingFuture:
        """Queue one sample (no batch dim); returns a future.  Raises
        :class:`QueueFullError` immediately when the bounded queue is
        full and :class:`EngineClosedError` after ``close()``."""
        if self._closed:
            raise EngineClosedError("submit on a closed engine")
        x = np.asarray(x, dtype=self._dtype)
        fut = ServingFuture()
        now = time.perf_counter()
        dl = deadline_ms if deadline_ms is not None \
            else self.default_deadline_ms
        rid = next(self._rids)
        req = _Request(x, fut, now,
                       now + dl / 1e3 if dl is not None else None,
                       rid=rid)
        try:
            self._rq.put_nowait(req)
        except queue.Full:
            self.metrics.inc_rejected()
            self._tracer.instant("queue_full", CAT_SERVE,
                                 corr=f"req:{rid}",
                                 args={"max_queue": self._rq.maxsize})
            raise QueueFullError(
                f"request queue full ({self._rq.maxsize}); retry later"
            ) from None
        self._tracer.instant("enqueue", CAT_SERVE, corr=f"req:{rid}")
        self.xray.open(rid, now=now)
        rec = workload.recorder()
        if rec is not None:
            rec.record_serve(rid, x.shape, str(x.dtype), deadline_ms=dl)
        return fut

    def predict(self, x, deadline_ms: Optional[float] = None,
                timeout: Optional[float] = None):
        """Submit one sample and wait for its (unpadded) result."""
        return self.submit(x, deadline_ms=deadline_ms).result(timeout)

    def predict_batch(self, x) -> np.ndarray:
        """Synchronous direct path for already-batched, same-shape
        input (axis 0 = batch): pads to the bucket grid, runs the
        cached executable, slices/crops back.  Bypasses the queue —
        thread-safe, used by the ``optim.PredictionService`` facade."""
        x = np.asarray(x, dtype=self._dtype)
        n = x.shape[0]
        dims, _ = self.grid.choose_dims(x.shape[1:])
        outs = []
        for lo in range(0, n, self.grid.max_batch):
            chunk = x[lo:lo + self.grid.max_batch]
            b = self.grid.choose_batch(len(chunk))
            xp = self.grid.pad_batch(chunk, dims, b, self._dtype)
            y = np.asarray(self._run(xp))
            outs.append(self.grid.unpad_batch(y[:len(chunk)],
                                              x.shape[1:], dims))
        return outs[0] if len(outs) == 1 else np.concatenate(outs)

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def start(self):
        if not self._started:
            self._started = True
            self._dispatcher.start()
            self._drainer.start()
            self._periodic.start()
            # live ops plane: host-side registration only — /metricsz
            # reads this engine's ServingMetrics, the black box gets a
            # fresh record per bundle (no-ops while the plane is dark)
            from bigdl_tpu.telemetry import debug_server, flightrecorder
            self._detach_debug = debug_server.attach_engine(
                "serve", role="serve", metrics=lambda: self.metrics,
                status=lambda: {"queue_depth": self._rq.qsize(),
                                "xray": self.xray.summary(),
                                "exemplars": self.exemplars.summary()},
                exemplars=lambda: self.exemplars)
            flight = flightrecorder.get_flight_recorder()
            if flight is not None:
                flight.add_metrics("serve", lambda: self.metrics)
                flight.add_blob("exemplars-serve",
                                self.exemplars.as_blob)

    def close(self, drain: bool = True, timeout: float = 30.0):
        """Stop accepting requests and shut down.  ``drain=True``
        (default) serves everything already queued/in flight first;
        ``drain=False`` fails queued requests with
        :class:`EngineClosedError`.  Idempotent."""
        with self._close_lock:
            already = self._closed
            self._closed = True
        if already:
            return
        detach = getattr(self, "_detach_debug", None)
        if detach is not None:
            detach()
        self._periodic.close()
        self._discard = not drain
        if not self._started:
            while True:
                try:
                    req = self._rq.get_nowait()
                except queue.Empty:
                    return
                req.fut.set_exception(
                    EngineClosedError("engine closed before start"))
        # FIFO: the sentinel lands behind every accepted request, so the
        # dispatcher drains (or discards) them all before exiting
        self._rq.put(_CLOSE)
        self._dispatcher.join(timeout)
        self._drainer.join(timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------------------
    # dispatcher thread: gather -> bucket -> pad -> enqueue device call
    # ------------------------------------------------------------------
    def _dispatch_loop(self):
        window = max(0.0, self.batch_window_ms) / 1e3
        stopping = False
        while not stopping:
            first = self._rq.get()
            if first is _CLOSE:
                break
            batch = [first]
            deadline = time.perf_counter() + window
            while len(batch) < self.grid.max_batch:
                remaining = deadline - time.perf_counter()
                try:
                    nxt = (self._rq.get(timeout=remaining)
                           if remaining > 0 else self._rq.get_nowait())
                except queue.Empty:
                    break
                if nxt is _CLOSE:
                    stopping = True
                    break
                batch.append(nxt)
            self.metrics.set_queue_depth(self._rq.qsize())
            self._dispatch(batch)
        # late submits that raced close(): never served, fail them
        while True:
            try:
                req = self._rq.get_nowait()
            except queue.Empty:
                break
            if req is not _CLOSE:
                req.fut.set_exception(EngineClosedError("engine closed"))
        self._fly.put(_CLOSE)

    def _dispatch(self, batch: List[_Request]):
        self._tracer.poll()
        now = time.perf_counter()
        live: List[_Request] = []
        for r in batch:
            if self._discard:
                self.xray.drop(r.rid)
                r.fut.set_exception(EngineClosedError("engine closed"))
            elif r.deadline is not None and now > r.deadline:
                self.metrics.inc_expired()
                self._tracer.instant("deadline_reject", CAT_SERVE,
                                     corr=f"req:{r.rid}")
                r.fut.set_exception(DeadlineExceededError(
                    f"deadline expired {1e3 * (now - r.deadline):.1f}ms "
                    "before dispatch",
                    attribution=self.xray.close(r.rid, now=now)))
            else:
                live.append(r)
        groups: dict = {}
        for r in live:
            dims, _ = self.grid.choose_dims(r.x.shape)
            groups.setdefault(dims, []).append(r)
        for dims, rs in groups.items():
            for lo in range(0, len(rs), self.grid.max_batch):
                chunk = rs[lo:lo + self.grid.max_batch]
                b = self.grid.choose_batch(len(chunk))
                self.xray.to_many((r.rid for r in chunk),
                                  request_xray.PHASE_PAD)
                try:
                    with self.metrics.time_dispatch():
                        xp = self.grid.pad_batch([r.x for r in chunk],
                                                 dims, b, self._dtype)
                        # enqueue-only: JAX async dispatch returns before
                        # the device finishes; the drain thread owns the
                        # fetch
                        y = self._run(xp)
                except Exception as e:  # per-request delivery, keep serving
                    for r in chunk:
                        self.xray.drop(r.rid)
                        r.fut.set_exception(e)
                    continue
                self.xray.to_many((r.rid for r in chunk),
                                  request_xray.PHASE_DEVICE)
                self.metrics.record_batch(len(chunk), b)
                if self._tracer.enabled:
                    # ONE batch-level instant naming its members: the
                    # per-request hop stays joinable (rids in args)
                    # without a per-request record on the hot path
                    self._tracer.instant(
                        "dispatch_batch", CAT_SERVE,
                        args={"bucket": [b, *dims],
                              "rids": [r.rid for r in chunk]})
                # bounded: blocks when pipeline_depth batches are already
                # in flight — backpressure instead of unbounded enqueue
                self._fly.put((y, dims, chunk))

    # ------------------------------------------------------------------
    # drain thread: fetch results, unpad, deliver futures
    # ------------------------------------------------------------------
    def _drain_loop(self):
        while True:
            item = self._fly.get()
            if item is _CLOSE:
                return
            y, dims, chunk = item
            try:
                with self.metrics.time_fetch():
                    ynp = np.asarray(y)  # blocks until the device is done
            except Exception as e:
                for r in chunk:
                    self.xray.drop(r.rid)
                    r.fut.set_exception(e)
                continue
            now = time.perf_counter()
            self.xray.to_many((r.rid for r in chunk),
                              request_xray.PHASE_DELIVER, now=now)
            for i, r in enumerate(chunk):
                r.fut.set_result(self.grid.unpad(ynp[i], r.x.shape, dims))
                self.metrics.record_latency(now - r.t_submit)
                self._tracer.instant("deliver", CAT_SERVE,
                                     corr=f"req:{r.rid}")
                self.exemplars.offer(self.xray.close(r.rid))
            self.metrics.inc_completed(len(chunk))

    # ------------------------------------------------------------------
    def log_line(self) -> str:
        line = self.metrics.log_line()
        if self.xray.enabled:
            line = f"{line} | {self.xray.log_line()}"
        return line
