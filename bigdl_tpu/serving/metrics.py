"""Serving-side metrics: tail latency, occupancy, queue depth,
recompiles, throughput.

Built on the thread-safe :class:`bigdl_tpu.optim.metrics.Metrics`
machinery (the async training engine's phase timers): latencies and
batch occupancy are tracked sample windows (percentiles), recompiles
are a timed phase whose *count* is the bucket-miss counter, and
completed/rejected/expired requests are plain event counters.  The
canonical one-liner is :meth:`ServingMetrics.log_line` — the serving
analog of ``Metrics.summary`` printed per training window.
"""
from __future__ import annotations

import logging
import os
import threading
import time
from typing import Callable, Optional

from bigdl_tpu.optim.metrics import Metrics

logger = logging.getLogger("bigdl_tpu.serving")

LATENCY = "latency"          # submit -> delivery, seconds, per request
OCCUPANCY = "occupancy"      # real rows / bucket batch, per dispatch
RECOMPILE = "recompile"      # compile seconds; count == bucket misses
DISPATCH = "serve_dispatch"  # pad + enqueue-only device call, per batch
FETCH = "serve_fetch"        # blocking device->host result fetch
# cached-decode engine phases (serving/decode.py, docs/decoding.md)
PREFILL = "decode_prefill"   # prompt forward + slot splice, per admit
TICK = "decode_tick"         # one whole-grid decode step (== per token)
SLOT_OCC = "slot_occupancy"  # active slots / grid size, per tick
TTFT = "ttft"                # submit -> first token, per request
TOKEN_GAP = "token_gap"      # a request's token -> its next token

#: ``le`` bounds (seconds) of the request-latency Prometheus histogram
#: exported on /metricsz — cumulative buckets a scraper can aggregate
#: across hosts, unlike the nearest-rank percentile gauges.
LATENCY_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                   0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


class ServingMetrics:
    """One engine's counters; safe to share across engine threads."""

    def __init__(self, base: Optional[Metrics] = None, window: int = 4096):
        self.base = base if base is not None else Metrics(category="serve")
        self.base.track(LATENCY, window)
        self.base.track(OCCUPANCY, window)
        self.base.track(TICK, window)
        self.base.track(SLOT_OCC, window)
        self.base.track(TTFT, window)
        self.base.track(TOKEN_GAP, window)
        # not intervals on the recording thread: latency spans a
        # request's whole life across threads, occupancy is a fraction —
        # they stay samples, not telemetry spans (docs/observability.md)
        self.base.no_span(LATENCY)
        self.base.no_span(OCCUPANCY)
        self.base.no_span(SLOT_OCC)
        self.base.no_span(TTFT)
        self.base.no_span(TOKEN_GAP)
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._queue_depth = 0
        self._pages_in_use = 0
        self._window_pages_in_use = 0
        # raw (non-cumulative) latency histogram counts; the last cell
        # is the +Inf overflow
        self._lat_buckets = [0] * (len(LATENCY_BUCKETS) + 1)
        self._lat_sum = 0.0
        self._lat_count = 0

    # -- recording (engine-internal) -----------------------------------
    def record_latency(self, seconds: float):
        self.base.add(LATENCY, seconds)
        with self._lock:
            self._lat_sum += seconds
            self._lat_count += 1
            for i, le in enumerate(LATENCY_BUCKETS):
                if seconds <= le:
                    self._lat_buckets[i] += 1
                    break
            else:
                self._lat_buckets[-1] += 1

    def record_batch(self, n_real: int, bucket_batch: int):
        self.base.add(OCCUPANCY, n_real / max(1, bucket_batch))

    def record_recompile(self, seconds: float):
        self.base.add(RECOMPILE, seconds)

    def time_dispatch(self):
        return self.base.time(DISPATCH)

    def time_fetch(self):
        return self.base.time(FETCH)

    def inc_completed(self, n: int = 1):
        self.base.inc("completed", n)

    def inc_rejected(self, n: int = 1):
        self.base.inc("rejected", n)

    def inc_expired(self, n: int = 1):
        self.base.inc("expired", n)

    # -- cached-decode engine (serving/decode.py) ----------------------
    def record_prefill(self, seconds: float):
        self.base.add(PREFILL, seconds)

    def record_tick(self, seconds: float):
        self.base.add(TICK, seconds)

    def record_decode_tokens(self, n: int):
        self.base.inc("decoded_tokens", n)

    def record_slot_occupancy(self, frac: float):
        self.base.add(SLOT_OCC, frac)

    def record_ttft(self, seconds: float):
        self.base.add(TTFT, seconds)

    def record_token_gap(self, seconds: float):
        self.base.add(TOKEN_GAP, seconds)

    def inc_finished(self, reason: str, n: int = 1):
        """Count a sequence retirement by reason: eos|length|deadline."""
        self.base.inc(f"finished_{reason}", n)

    def set_queue_depth(self, depth: int):
        with self._lock:
            self._queue_depth = depth

    # -- paged KV / chunked prefill / speculative (ISSUE 14) -----------
    def record_pages(self, in_use: int, window_in_use: int = 0):
        """Current physical KV pages allocated (gauge; paged engines
        call this on every allocation/release): the full extent's and,
        where window layers keep a pool of their own, that pool's."""
        with self._lock:
            self._pages_in_use = int(in_use)
            self._window_pages_in_use = int(window_in_use)

    def inc_page_evictions(self, n: int = 1):
        self.base.inc("page_evictions", n)

    def inc_prefill_chunks(self, n: int = 1):
        self.base.inc("prefill_chunks", n)

    def inc_sampled_ticks(self, n: int = 1):
        """A tick that ran its sampling epilogue: some active row had
        ``temperature > 0`` (a greedy grid skips it).  Counted where
        the tick is timed, at the read that serves a row."""
        self.base.inc("sampled_ticks", n)

    def inc_overlapped_ticks(self, n: int = 1):
        """A tick enqueued while its predecessor's tokens were still
        unread: the host's turn ran beside the device's.  Counted
        where the tick is timed, at the read that serves a row."""
        self.base.inc("overlapped_ticks", n)

    def record_spec(self, proposed: int, accepted: int):
        """One speculative round: ``proposed`` draft tokens scored,
        ``accepted`` of them kept (the bonus token is not counted —
        acceptance rate is a property of the draft, not the verify)."""
        self.base.inc("spec_proposed", proposed)
        self.base.inc("spec_accepted", accepted)

    # -- reading -------------------------------------------------------
    @property
    def recompiles(self) -> int:
        """Compiled-forward cache misses so far (== declared bucket
        count right after warmup; any growth is a bucket miss)."""
        return self.base.count(RECOMPILE)

    @property
    def completed(self) -> int:
        return self.base.counter("completed")

    @property
    def rejected(self) -> int:
        return self.base.counter("rejected")

    @property
    def expired(self) -> int:
        return self.base.counter("expired")

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return self._queue_depth

    def latency_ms(self, q: float) -> float:
        return 1e3 * self.base.percentile(LATENCY, q)

    def latency_histogram(self) -> dict:
        """The request-latency histogram in Prometheus form:
        ``buckets`` is the *cumulative* (le, count) series ending at
        +Inf, plus the classic ``sum``/``count`` pair."""
        with self._lock:
            raw = list(self._lat_buckets)
            s, n = self._lat_sum, self._lat_count
        cum, total = [], 0
        for i, le in enumerate(LATENCY_BUCKETS):
            total += raw[i]
            cum.append((le, total))
        cum.append((float("inf"), n))
        return {"buckets": cum, "sum": s, "count": n}

    def occupancy(self) -> float:
        """Mean real-rows / bucket-batch over the sample window."""
        return self.base.get(OCCUPANCY)

    def throughput(self) -> float:
        """Completed requests per second since engine start."""
        dt = time.perf_counter() - self._t0
        return self.completed / dt if dt > 0 else 0.0

    @property
    def decoded_tokens(self) -> int:
        return self.base.counter("decoded_tokens")

    def finished(self, reason: str) -> int:
        return self.base.counter(f"finished_{reason}")

    def tokens_per_sec(self) -> float:
        """Decoded tokens per second since engine start."""
        dt = time.perf_counter() - self._t0
        return self.decoded_tokens / dt if dt > 0 else 0.0

    def tick_ms(self, q: float) -> float:
        """Per-tick (== per-token) decode latency percentile."""
        return 1e3 * self.base.percentile(TICK, q)

    def slot_occupancy(self) -> float:
        """Mean active-slots / grid-size over the sample window."""
        return self.base.get(SLOT_OCC)

    def ttft_ms(self, q: float) -> float:
        """Time-to-first-token percentile (first token - submit)."""
        return 1e3 * self.base.percentile(TTFT, q)

    def token_gap_ms(self, q: float) -> float:
        """Percentile of the gap between a request's consecutive
        tokens: a tick plus whatever admission ran between them."""
        return 1e3 * self.base.percentile(TOKEN_GAP, q)

    @property
    def pages_in_use(self) -> int:
        with self._lock:
            return self._pages_in_use

    @property
    def window_pages_in_use(self) -> int:
        with self._lock:
            return self._window_pages_in_use

    @property
    def page_evictions(self) -> int:
        return self.base.counter("page_evictions")

    @property
    def prefill_chunks(self) -> int:
        return self.base.counter("prefill_chunks")

    @property
    def sampled_ticks(self) -> int:
        return self.base.counter("sampled_ticks")

    def sampled_tick_share(self) -> float:
        """Ticks that ran the sampling epilogue over all ticks timed
        (0.0 before the first tick)."""
        n = self.base.count(TICK)
        return self.sampled_ticks / n if n else 0.0

    @property
    def overlapped_ticks(self) -> int:
        return self.base.counter("overlapped_ticks")

    def overlapped_tick_share(self) -> float:
        """Ticks enqueued with their predecessor unread over all ticks
        timed (0.0 before the first tick)."""
        n = self.base.count(TICK)
        return self.overlapped_ticks / n if n else 0.0

    def spec_acceptance_rate(self) -> float:
        """Accepted / proposed draft tokens since engine start (0.0
        when the engine never ran a speculative round)."""
        p = self.base.counter("spec_proposed")
        return self.base.counter("spec_accepted") / p if p else 0.0

    def snapshot(self) -> dict:
        return {
            "completed": self.completed,
            "rejected": self.rejected,
            "expired": self.expired,
            "p50_ms": round(self.latency_ms(50), 3),
            "p95_ms": round(self.latency_ms(95), 3),
            "p99_ms": round(self.latency_ms(99), 3),
            "occupancy": round(self.occupancy(), 4),
            "queue_depth": self.queue_depth,
            "recompiles": self.recompiles,
            "req_per_sec": round(self.throughput(), 2),
            "tokens_per_sec": round(self.tokens_per_sec(), 2),
            "decoded_tokens": self.decoded_tokens,
            "slot_occupancy": round(self.slot_occupancy(), 4),
            "p50_tick_ms": round(self.tick_ms(50), 3),
            "p95_tick_ms": round(self.tick_ms(95), 3),
            "prefill_ms": round(1e3 * self.base.get(PREFILL), 3),
            "decode_ms": round(1e3 * self.base.get(TICK), 3),
            "p50_ttft_ms": round(self.ttft_ms(50), 3),
            "p95_ttft_ms": round(self.ttft_ms(95), 3),
            "p50_token_gap_ms": round(self.token_gap_ms(50), 3),
            "p95_token_gap_ms": round(self.token_gap_ms(95), 3),
            "pages_in_use": self.pages_in_use,
            "window_pages_in_use": self.window_pages_in_use,
            "page_evictions": self.page_evictions,
            "spec_acceptance_rate": round(self.spec_acceptance_rate(),
                                          4),
            "prefill_chunks": self.prefill_chunks,
            "sampled_tick_share": round(self.sampled_tick_share(), 4),
            "overlapped_tick_share": round(self.overlapped_tick_share(),
                                           4),
        }

    # scalar tags exported to TensorBoard (visualization satellite):
    # snapshot key -> summary tag
    SUMMARY_TAGS = {
        "req_per_sec": "Serving/ThroughputReqPerSec",
        "tokens_per_sec": "Serving/TokensPerSec",
        "p50_ms": "Serving/LatencyP50Ms",
        "p95_ms": "Serving/LatencyP95Ms",
        "p99_ms": "Serving/LatencyP99Ms",
        "occupancy": "Serving/BatchOccupancy",
        "slot_occupancy": "Serving/SlotOccupancy",
        "queue_depth": "Serving/QueueDepth",
        "recompiles": "Serving/Recompiles",
        "completed": "Serving/Completed",
        "rejected": "Serving/Rejected",
        "expired": "Serving/Expired",
        "p50_tick_ms": "Serving/TickP50Ms",
        "p95_tick_ms": "Serving/TickP95Ms",
        "pages_in_use": "Serving/PagesInUse",
        "page_evictions": "Serving/PageEvictions",
        "spec_acceptance_rate": "Serving/SpecAcceptanceRate",
        "prefill_chunks": "Serving/PrefillChunks",
    }

    def write_summary(self, summary, step: int) -> dict:
        """Export the snapshot through a ``bigdl_tpu.visualization``
        summary writer (e.g. :class:`~bigdl_tpu.visualization.
        ServingSummary`) so serving runs show up in TensorBoard next to
        training runs; returns the snapshot written."""
        snap = self.snapshot()
        for key, tag in self.SUMMARY_TAGS.items():
            if snap[key] is not None:
                summary.add_scalar(tag, float(snap[key]), step)
        return snap

    def log_line(self) -> str:
        """Canonical serving log line."""
        s = self.snapshot()
        line = (f"serving: ok={s['completed']} rej={s['rejected']} "
                f"exp={s['expired']} | p50={s['p50_ms']:.2f}ms "
                f"p95={s['p95_ms']:.2f}ms p99={s['p99_ms']:.2f}ms | "
                f"occ={100 * s['occupancy']:.0f}% | "
                f"qdepth={s['queue_depth']} | "
                f"recompiles={s['recompiles']} | "
                f"{s['req_per_sec']:.1f} req/s")
        if s["decoded_tokens"]:
            line += (f" | {s['tokens_per_sec']:.1f} tok/s | "
                     f"slots={100 * s['slot_occupancy']:.0f}% | "
                     f"tick p50={s['p50_tick_ms']:.2f}ms "
                     f"p95={s['p95_tick_ms']:.2f}ms | "
                     f"ttft p50={s['p50_ttft_ms']:.2f}ms "
                     f"p95={s['p95_ttft_ms']:.2f}ms | "
                     f"gap p50={s['p50_token_gap_ms']:.2f}ms "
                     f"p95={s['p95_token_gap_ms']:.2f}ms | "
                     f"sampled={100 * s['sampled_tick_share']:.0f}% | "
                     f"overlap={100 * s['overlapped_tick_share']:.0f}%")
        if s["pages_in_use"] or s["page_evictions"]:
            line += (f" | pages={s['pages_in_use']} "
                     + (f"window_pages={s['window_pages_in_use']} "
                        if s["window_pages_in_use"] else "")
                     + f"evict={s['page_evictions']}")
        if s["prefill_chunks"]:
            line += f" | chunks={s['prefill_chunks']}"
        if s["spec_acceptance_rate"]:
            line += f" | spec acc={100 * s['spec_acceptance_rate']:.0f}%"
        return line


# --------------------------------------------------------------------------
# periodic metrics log cadence (docs/observability.md)
# --------------------------------------------------------------------------

def metrics_log_every_s(default: float = 0.0) -> float:
    """Configured periodic-log cadence in seconds
    (``BIGDL_TPU_METRICS_EVERY_S`` env; 0 = off, the default)."""
    try:
        return max(0.0, float(os.environ.get("BIGDL_TPU_METRICS_EVERY_S",
                                             default)))
    except ValueError:
        return default


class PeriodicMetricsLogger:
    """Background cadence emitting an engine's canonical ``log_line()``
    — long-running servers get the reference's every-step Metrics
    printout (DistriOptimizer.scala:411-416 analog) without any caller
    code.  Off unless ``every_s`` (or ``BIGDL_TPU_METRICS_EVERY_S``)
    is positive; ``close()`` stops the thread and is idempotent —
    both serving engines call it from their own ``close()``."""

    def __init__(self, emit: Callable[[], str],
                 every_s: Optional[float] = None,
                 sink: Optional[Callable[[str], None]] = None):
        self.every_s = metrics_log_every_s() if every_s is None \
            else max(0.0, float(every_s))
        self._emit = emit
        self._sink = sink if sink is not None else logger.info
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "PeriodicMetricsLogger":
        if self.every_s > 0 and self._thread is None:
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="bigdl-metrics-log")
            self._thread.start()
        return self

    def _run(self):
        while not self._stop.wait(self.every_s):
            try:
                # the HBM ledger samples on the same cadence as the
                # metrics line (telemetry/programs.py; rate-limited by
                # its own BIGDL_TPU_HBM_EVERY_S knob)
                from bigdl_tpu.telemetry.programs import get_hbm_ledger
                get_hbm_ledger().maybe_sample()
            except Exception:
                pass
            try:
                self._sink(self._emit())
            except Exception:  # a log line must never kill an engine
                logger.debug("periodic metrics emit failed",
                             exc_info=True)

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def close(self, timeout: float = 5.0):
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout)
