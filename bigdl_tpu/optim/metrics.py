"""Per-iteration phase metrics (reference optim/Metrics.scala:31-123 —
Spark accumulators printed each step: get-weights/compute/aggregate/
put-gradient/send-weights).

On TPU the phases differ (h2d transfer, compiled step, d2h sync) but the
instrumentation shape is kept: named timers accumulated per window and
summarised as the reference's ``summary()`` does.

Phases *inside* the fused XLA step (the collective/allreduce time the
reference measured directly around its BlockManager calls,
DistriOptimizer.scala:188-196) are invisible to host timers; they are
surfaced as *gauges* — values computed elsewhere (e.g. the A/B
calibration in DistriOptimizer) that summary() prints alongside timers.

Async-engine phases (docs/async_engine.md): under the default async
loop ``data`` is the producer thread's per-batch host transform + H2D
time, ``data_stall`` is how long the loop blocked on the prefetcher,
``dispatch`` is enqueue-only step launch, and ``sync`` is time in the
deferred loss drains — the loop's only host<-device round-trips.  The
producer thread records concurrently with the loop thread, so updates
take a lock.

Serving phases (docs/serving.md): the serving engine additionally needs
tail latencies and event counts, so names opted in via :meth:`track`
keep a bounded window of raw samples for :meth:`percentile`, and
:meth:`inc`/:meth:`counter` hold plain integer event counters
(completed/rejected/expired requests) alongside the timers.

Telemetry (docs/observability.md): every ``Metrics`` is also a SPAN
SINK — each :meth:`time` block is a span of the global
:mod:`bigdl_tpu.telemetry` tracer (category = this instance's
``category``; ring and live profiler trace), and each :meth:`add`
leaves the reconstructed span in the ring, so the phase timers across
the training loop, prefetcher, and serving engines land on one shared
timeline for free.
Non-interval samples (latencies measured across threads, occupancy
fractions) opt out via :meth:`no_span`.  The disabled-tracer cost is
one attribute check per add.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Deque, Dict, Set

from bigdl_tpu.telemetry.tracer import get_tracer


class Metrics:
    def __init__(self, category: str = "train"):
        self._sums: Dict[str, float] = {}
        self._counts: Dict[str, int] = {}
        self._gauges: Dict[str, float] = {}
        self._last: Dict[str, float] = {}
        self._samples: Dict[str, Deque[float]] = {}
        self._counters: Dict[str, int] = {}
        self._values: Dict[str, float] = {}
        self._lock = threading.Lock()
        self.category = category
        self._no_span: Set[str] = set()
        self._tracer = get_tracer()

    def no_span(self, name: str) -> "Metrics":
        """Opt ``name`` out of span emission — for samples that are not
        intervals on the calling thread (cross-thread latencies,
        occupancy ratios)."""
        self._no_span.add(name)
        return self

    def _sample(self, name: str, seconds: float):
        with self._lock:
            self._sums[name] = self._sums.get(name, 0.0) + seconds
            self._counts[name] = self._counts.get(name, 0) + 1
            self._last[name] = seconds
            window = self._samples.get(name)
            if window is not None:
                window.append(seconds)

    def add(self, name: str, seconds: float):
        """Record a sample timed by the caller.  The ring gets the
        reconstructed span ``[now - seconds, now]``; only :meth:`time`
        can put a span into a live profiler trace."""
        self._sample(name, seconds)
        tr = self._tracer
        if tr.enabled and name not in self._no_span:
            t1 = time.perf_counter()
            tr.add_span(name, self.category, t1 - seconds, t1)

    @contextmanager
    def time(self, name: str):
        """Time the block as phase ``name``; while the tracer is on the
        block is also a :meth:`Tracer.span` (ring + profiler trace)."""
        span = nullcontext() if name in self._no_span \
            else self._tracer.span(name, self.category)
        with span:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self._sample(name, time.perf_counter() - t0)

    def get(self, name: str) -> float:
        if name in self._gauges:
            return self._gauges[name]
        c = self._counts.get(name, 0)
        return self._sums.get(name, 0.0) / c if c else 0.0

    def count(self, name: str) -> int:
        return self._counts.get(name, 0)

    def last(self, name: str) -> float:
        """Most recent sample (untainted by first-call compile time,
        unlike the running average ``get``)."""
        return self._last.get(name, 0.0)

    def set_gauge(self, name: str, seconds: float):
        """Set an instantaneous phase value (seconds) computed out-of-band."""
        with self._lock:
            self._gauges[name] = seconds

    # -- unitless values (MFU, bytes/s, records/s — not phase times) ---
    def set_value(self, name: str, value: float):
        """Set a non-time scalar (cost-model derived MFU, bytes/s,
        throughput).  Kept apart from gauges so ``summary()`` never
        prints it with an ms unit."""
        with self._lock:
            self._values[name] = float(value)

    def value(self, name: str, default: float = 0.0) -> float:
        return self._values.get(name, default)

    def values(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._values)

    # -- sample windows / percentiles (serving tail latencies) ---------
    def track(self, name: str, window: int = 4096):
        """Opt ``name`` into keeping its last ``window`` raw samples so
        :meth:`percentile` works; a no-op if already tracked."""
        with self._lock:
            if name not in self._samples:
                self._samples[name] = deque(maxlen=max(1, window))

    def percentile(self, name: str, q: float) -> float:
        """q-th percentile (0-100, nearest-rank) over the tracked sample
        window; 0.0 when untracked or empty."""
        with self._lock:
            xs = sorted(self._samples.get(name, ()))
        if not xs:
            return 0.0
        i = max(0, min(len(xs) - 1, int(round(q / 100.0 * (len(xs) - 1)))))
        return xs[i]

    # -- event counters (not timers) -----------------------------------
    def inc(self, name: str, n: int = 1):
        """Bump a plain integer event counter."""
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def counter(self, name: str) -> int:
        return self._counters.get(name, 0)

    def summary(self, unit_scale: float = 1e3) -> str:
        """One line, average ms per phase (reference Metrics.summary),
        with event counters appended as plain integers."""
        parts = [
            f"{k}: {self.get(k) * unit_scale:.2f}ms"
            for k in sorted(set(self._sums) | set(self._gauges))
        ]
        parts += [f"{k}: {v:.4g}" for k, v in sorted(self._values.items())]
        parts += [f"{k}: {v}" for k, v in sorted(self._counters.items())]
        return " | ".join(parts)

    def reset(self):
        self._sums.clear()
        self._counts.clear()
        self._gauges.clear()
        self._last.clear()
        self._counters.clear()
        self._values.clear()
        for window in self._samples.values():
            window.clear()
