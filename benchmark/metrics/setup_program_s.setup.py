"""Seconds of set-up inside the program's own set-up spans (category
``setup``: ``setup/import``, ``setup/build``, ``setup/engine`` and what
they hold, every ``compile``), each thread's covered time: a child is
not added again beside its parent.  The rest of ``setup_s`` is the
harness's: backend start-up, weights and data from the seed, the
lead-in.  Only what came before the program's first span of another
category counts: in a traced run that is the window's first turn, and
what the harness builds after the window (a second engine) is no part
of ``setup_s``.  Nothing where the ring holds no set-up span.

``setup_trace_s.setup`` and ``setup_compile_s.setup`` load this file
for ``seconds(names)``."""


def covered(intervals) -> float:
    """Length of the union of ``(t0, t1)`` intervals."""
    total, end = 0.0, float("-inf")
    for t0, t1 in sorted(intervals):
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total


def seconds(names=None):
    """Covered seconds of the set-up spans named ``names`` (all: None),
    summed over threads; None where the ring holds no set-up span."""
    from bigdl_tpu.telemetry import get_tracer

    spans = get_tracer().spans()
    cut = min((s.t0 for s in spans if s.cat != "setup"),
              default=float("inf"))
    setup = [s for s in spans if s.cat == "setup" and s.t0 < cut]
    if not setup:
        return None
    setup = [s for s in setup if names is None or s.name in names]
    return sum(covered((s.t0, min(s.t1, cut)) for s in setup
                       if s.tid == tid)
               for tid in {s.tid for s in setup})


def read(run):
    return seconds()
