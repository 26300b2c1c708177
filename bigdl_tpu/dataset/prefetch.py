"""Shared background prefetch (the async engine's input stage).

The reference overlapped host input work with compute via Spark task
pipelining plus its native ``PrefetchingRecordReader`` (BigDL paper
§4); the TPU-era analog is a bounded producer thread that keeps the
device queue non-empty:

* :class:`Prefetcher` — generic thread+queue iterator wrapper: pulls
  from the wrapped iterator on a daemon thread, preserves order, caps
  in-flight items at ``depth``, re-raises producer exceptions in the
  consumer, and shuts down cleanly when abandoned (``close``).
* :class:`DevicePrefetcher` — a :class:`Prefetcher` whose ``transform``
  runs on the producer thread; the training engine passes its
  host-transform + ``jax.device_put``/``put_batch`` placement function
  so H2D transfer itself overlaps device compute.

One queue/thread/shutdown implementation in the tree: the streaming
``ShardedFileDataSet`` path reuses :class:`Prefetcher` directly.
"""
from __future__ import annotations

import os
import queue
import threading
import time
from typing import Any, Callable, Iterator, Optional

from bigdl_tpu.telemetry.tracer import (
    CAT_DATA,
    get_tracer,
    set_correlation,
)

DEFAULT_DEPTH = 2


def prefetch_depth(default: int = DEFAULT_DEPTH) -> int:
    """Configured prefetch depth (``BIGDL_TPU_PREFETCH_DEPTH`` env)."""
    try:
        return max(1, int(os.environ.get("BIGDL_TPU_PREFETCH_DEPTH",
                                         default)))
    except ValueError:
        return default


class Prefetcher:
    """Background-thread iterator wrapper: keeps up to ``depth`` items
    ready so host-side item production overlaps the consumer's work.

    ``transform`` (optional) is applied to every item ON THE PRODUCER
    THREAD — the hook the engine uses for host transforms + device
    placement.  ``timer`` (optional) receives the seconds each item
    spent in production (pull + transform), e.g. ``metrics.add`` bound
    to a phase name.
    """

    def __init__(
        self,
        it: Iterator,
        depth: int = DEFAULT_DEPTH,
        transform: Optional[Callable[[Any], Any]] = None,
        timer: Optional[Callable[[float], None]] = None,
    ):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._done = object()
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self._finished = False

        def run():
            tracer = get_tracer()
            source = iter(it)
            idx = 0
            try:
                while not self._stop.is_set():
                    if tracer.poll():
                        set_correlation(f"item:{idx}")
                    # producer-thread span per item (pull + transform +
                    # device placement), correlated by item index so the
                    # shared timeline shows which batch the loop's
                    # data_stall waited on (docs/observability.md)
                    with tracer.span("prefetch_item", CAT_DATA):
                        t0 = time.perf_counter()
                        with tracer.span("host_transform", CAT_DATA):
                            item = next(source, self._done)
                        if item is self._done or self._stop.is_set():
                            return
                        if transform is not None:
                            with tracer.span("h2d", CAT_DATA):
                                item = transform(item)
                        if timer is not None:
                            timer(time.perf_counter() - t0)
                    idx += 1
                    # put AFTER the stop check so close() never strands
                    # a producer blocked on a full queue forever (close
                    # drains, letting this put complete, then the next
                    # loop iteration observes the flag)
                    if self._stop.is_set():
                        return
                    self._q.put(item)
            except BaseException as e:  # surface in the consumer thread
                self._error = e
            finally:
                # release the source's resources (open shard readers,
                # nested prefetchers) deterministically rather than at
                # some later GC pass
                close = getattr(it, "close", None)
                if close is not None:
                    try:
                        close()
                    except Exception:
                        pass
                self._q.put(self._done)

        self._t = threading.Thread(target=run, daemon=True,
                                   name="bigdl-prefetch")
        self._t.start()

    def __iter__(self):
        return self

    def __next__(self):
        if self._finished:
            raise StopIteration
        item = self._q.get()
        if item is self._done:
            self._finished = True
            if self._error is not None:
                raise self._error
            raise StopIteration
        return item

    def close(self, timeout: float = 5.0):
        """Stop the producer and release its resources.  Safe to call
        more than once, and safe while the producer is mid-item."""
        self._stop.set()
        self._finished = True  # a next() after close must not block
        # drain until the producer exits: it may be blocked in put()
        # (including the final done-sentinel put against a full queue),
        # and each get frees a slot for it to proceed and observe the
        # stop flag
        deadline = time.monotonic() + timeout
        while self._t.is_alive() and time.monotonic() < deadline:
            try:
                self._q.get_nowait()
            except queue.Empty:
                pass
            self._t.join(timeout=0.005)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class DevicePrefetcher(Prefetcher):
    """Prefetcher whose producer thread finishes each item with a
    device-placement function (``place(batch) -> placed``), issuing the
    ``jax.device_put`` with the step's input sharding off the hot path.
    Alias kept for intent at call sites; behavior is Prefetcher's."""

    def __init__(self, it, place: Callable[[Any], Any],
                 depth: Optional[int] = None,
                 timer: Optional[Callable[[float], None]] = None):
        super().__init__(it, depth=prefetch_depth() if depth is None
                         else depth, transform=place, timer=timer)
