"""Structured span/event tracer (docs/observability.md).

The shared timeline of the three async subsystems (training loop +
prefetch producer + checkpoint writer, the ServingEngine's dispatcher
and drain threads, the DecodeEngine's slot-grid loop): a
process-global, thread-safe ring buffer of spans that every
:class:`~bigdl_tpu.optim.metrics.Metrics` phase timer feeds, plus
explicit spans/instants where averages explain nothing (request
lifecycle edges, checkpoint writes, divergence drains).

* **One timeline** — the tracer records while a ``jax.profiler``
  session is live (:meth:`Tracer.poll`, called once per loop turn by
  every engine loop) or after an explicit :func:`enable`, and every
  :meth:`Tracer.span` also enters a ``jax.profiler.TraceAnnotation``,
  so the program's spans land on plane ``/host:CPU`` of the profiler's
  own trace, on the device planes' clock.
* **Near-zero overhead when off** — every recording call is one
  attribute check (``tracer.enabled``) before returning; nothing is
  allocated, no lock is taken.
* **Zero effect on compiled programs** — instrumentation is host-side,
  between dispatches, never inside a traced function (graft-lint
  target ``telemetry_step_parity``; fixture ``span_host_leak``).
* **Correlation IDs** — a free-form string (``step:42``, ``req:17``,
  ``tick:1024``, ``item:7``) joins one unit of work across the threads
  that touched it: ambient per thread (:func:`set_correlation`), or
  passed explicitly where an edge outlives a thread.

Env knobs: ``BIGDL_TPU_TRACE=1`` holds the global tracer on from
import, ``BIGDL_TPU_TRACE_BUFFER`` sizes the ring (default 65536).
"""
from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

DEFAULT_CAPACITY = 65536

# span categories used by the shipped instrumentation
CAT_TRAIN = "train"
CAT_DATA = "data"
CAT_SERVE = "serve"
CAT_DECODE = "decode"
CAT_HOST = "host"


class Span:
    """One completed host-side interval (or instant, when t0 == t1)."""

    __slots__ = ("name", "cat", "t0", "t1", "tid", "thread", "corr",
                 "args")

    def __init__(self, name: str, cat: str, t0: float, t1: float,
                 tid: int, thread: str, corr: Optional[str],
                 args: Optional[Dict[str, Any]]):
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.t1 = t1
        self.tid = tid
        self.thread = thread
        self.corr = corr
        self.args = args

    @property
    def duration(self) -> float:
        return self.t1 - self.t0

    @property
    def instant(self) -> bool:
        return self.t1 == self.t0

    def __repr__(self):
        return (f"Span({self.name!r}, cat={self.cat!r}, "
                f"dur={1e3 * self.duration:.3f}ms, corr={self.corr!r}, "
                f"thread={self.thread!r})")


_tls = threading.local()

# is a jax.profiler session live?  (module-level so tests can count calls)
_profiler_live = TraceAnnotation.is_enabled


def set_correlation(corr: Optional[str]):
    """Set this thread's ambient correlation ID (e.g. ``step:42``);
    spans recorded without an explicit ``corr`` pick it up."""
    _tls.corr = corr


def get_correlation() -> Optional[str]:
    return getattr(_tls, "corr", None)


@contextmanager
def correlate(corr: str):
    """Scope the ambient correlation ID to a block."""
    prev = get_correlation()
    set_correlation(corr)
    try:
        yield
    finally:
        set_correlation(prev)


class Tracer:
    """Thread-safe bounded span sink.

    The ring buffer is a plain list used circularly: appends under a
    lock, oldest spans overwritten when full (a long-running server
    keeps the recent window — exactly what a postmortem needs).
    Subscribers (:class:`~bigdl_tpu.telemetry.watchdog.Watchdog`) see
    every span at record time, outside the buffer lock.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 enabled: bool = False):
        self.capacity = max(1, int(capacity))
        self.enabled = bool(enabled)
        self._forced = bool(enabled)  # explicit enable(): outlives sessions
        self._session = False         # profiler state at the last poll()
        self._buf: List[Optional[Span]] = []
        self._head = 0  # next write index once the ring is full
        self._dropped = 0
        self._lock = threading.Lock()
        self._subs: List[Callable[[Span], None]] = []
        self.epoch = time.perf_counter()  # t=0 of the exported timeline

    # -- lifecycle -----------------------------------------------------
    def enable(self, capacity: Optional[int] = None) -> "Tracer":
        if capacity is not None and capacity != self.capacity:
            with self._lock:
                ordered = self._buf[self._head:] + self._buf[:self._head]
                self.capacity = max(1, int(capacity))
                self._buf = ordered[-self.capacity:]
                self._head = 0
        self.enabled = self._forced = True
        return self

    def disable(self) -> "Tracer":
        self.enabled = self._forced = False
        return self

    def poll(self) -> bool:
        """Follow the profiler: called once per loop turn (training
        iteration, decode loop turn, prefetch item, serving dispatch),
        flips ``enabled`` on at ``start_trace`` and off after
        ``stop_trace`` unless :meth:`enable` holds it on.  Every
        recording site keeps its one attribute check."""
        live = _profiler_live()
        if live != self._session:
            self._session = live
            self.enabled = live or self._forced
        return self.enabled

    def clear(self):
        with self._lock:
            self._buf = []
            self._head = 0
            self._dropped = 0
            self.epoch = time.perf_counter()

    @property
    def dropped(self) -> int:
        """Spans overwritten by ring wrap since the last clear()."""
        return self._dropped

    # -- subscription (the watchdog's feed) ----------------------------
    def subscribe(self, fn: Callable[[Span], None]):
        with self._lock:
            if fn not in self._subs:
                self._subs.append(fn)

    def unsubscribe(self, fn: Callable[[Span], None]):
        with self._lock:
            if fn in self._subs:
                self._subs.remove(fn)

    # -- recording -----------------------------------------------------
    def add_span(self, name: str, cat: str, t0: float, t1: float,
                 corr: Optional[str] = None,
                 args: Optional[Dict[str, Any]] = None):
        """Record a completed interval timed by the caller
        (``perf_counter`` timestamps).  The disabled path is ONE
        attribute check — callers may invoke this unconditionally."""
        if not self.enabled:
            return
        if self._session and not self._forced and not _profiler_live():
            return  # the session ended under this span: not part of it
        th = threading.current_thread()
        span = Span(name, cat, t0, t1, th.ident or 0, th.name,
                    corr if corr is not None else get_correlation(),
                    args)
        with self._lock:
            if len(self._buf) < self.capacity:
                self._buf.append(span)
            else:
                self._buf[self._head] = span
                self._head = (self._head + 1) % self.capacity
                self._dropped += 1
            subs = tuple(self._subs)
        for fn in subs:  # outside the lock; a slow watchdog must not
            try:         # serialize the engine threads on the buffer
                fn(span)
            except Exception:
                pass  # an observer must never take down engine threads

    def instant(self, name: str, cat: str = CAT_HOST,
                corr: Optional[str] = None,
                args: Optional[Dict[str, Any]] = None):
        """Zero-duration event (rejections, divergence, slot churn)."""
        if not self.enabled:
            return
        t = time.perf_counter()
        self.add_span(name, cat, t, t, corr=corr, args=args)

    @contextmanager
    def span(self, name: str, cat: str = CAT_HOST,
             corr: Optional[str] = None,
             args: Optional[Dict[str, Any]] = None):
        """Context manager measuring the enclosed block: the span goes
        into the ring and, as a ``TraceAnnotation`` carrying ``corr``
        and the scalar ``args`` it starts with, into a live profiler
        trace (what the block adds to ``args`` reaches the ring only).
        Cheap when disabled (no timestamps taken)."""
        if not self.enabled:
            yield
            return
        if corr is None:
            corr = get_correlation()
        stats = {k: v for k, v in args.items()
                 if isinstance(v, (int, float, str))} if args else {}
        if corr is not None:
            stats["corr"] = corr
        t0 = time.perf_counter()
        with TraceAnnotation(name, **stats):
            try:
                yield
            finally:
                self.add_span(name, cat, t0, time.perf_counter(),
                              corr=corr, args=args)

    # -- reading -------------------------------------------------------
    def spans(self) -> List[Span]:
        """Snapshot of the ring in record order (oldest first)."""
        with self._lock:
            return self._buf[self._head:] + self._buf[:self._head]

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)


_GLOBAL: Optional[Tracer] = None
_GLOBAL_LOCK = threading.Lock()


def _env_capacity() -> int:
    try:
        return max(1, int(os.environ.get("BIGDL_TPU_TRACE_BUFFER",
                                         DEFAULT_CAPACITY)))
    except ValueError:
        return DEFAULT_CAPACITY


def get_tracer() -> Tracer:
    """The process-global tracer every subsystem records into (one
    shared timeline is the point).  Created disabled unless
    ``BIGDL_TPU_TRACE=1``."""
    global _GLOBAL
    if _GLOBAL is None:
        with _GLOBAL_LOCK:
            if _GLOBAL is None:
                _GLOBAL = Tracer(
                    capacity=_env_capacity(),
                    enabled=os.environ.get("BIGDL_TPU_TRACE", "")
                    not in ("", "0"))
    return _GLOBAL


def enable(capacity: Optional[int] = None) -> Tracer:
    return get_tracer().enable(capacity)


def disable() -> Tracer:
    return get_tracer().disable()


@contextmanager
def enabled(capacity: Optional[int] = None):
    """Scope global tracing to a block (restores the prior state)."""
    tr = get_tracer()
    was = tr.enabled, tr._forced
    tr.enable(capacity)
    try:
        yield tr
    finally:
        tr.enabled, tr._forced = was
