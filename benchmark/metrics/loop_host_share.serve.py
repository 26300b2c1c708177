"""Share of the decode loop's time the host spent on anything but
waiting for the device: 100 x (1 - (``loop/tick_wait`` + ``prefill_wait``)
/ all top-level ``loop/*`` spans), over the program's own spans of the
traced span (the tracer's ring: it records only while the profiler
session is live)."""


def read(run):
    if run["kind"] != "decode":
        return None
    from bigdl_tpu.telemetry import get_tracer

    spans = get_tracer().spans()
    loop = sum(s.duration for s in spans if s.name.startswith("loop/"))
    if not loop:
        return None
    waits = sum(s.duration for s in spans
                if s.name in ("loop/tick_wait", "prefill_wait"))
    return 100.0 * (1.0 - waits / loop)
