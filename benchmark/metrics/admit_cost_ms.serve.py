"""Loop time one admitted request adds.  A turn of the decode loop
runs from the start of ``loop/drain_queue`` to the end of
``loop/retire``; the cost is (the length of the turns that admitted,
less what as many turns that admitted nothing take at their median)
over the requests admitted.  Measured by the turn and not by the
``loop/admit`` span: the slot write is dispatched without waiting, and
its device time surfaces in the ``loop/tick_wait`` of the same turn."""
import statistics


def read(run):
    if run["kind"] != "decode":
        return None
    from bigdl_tpu.telemetry import get_tracer

    loop = sorted((s for s in get_tracer().spans()
                   if s.name.startswith("loop/")), key=lambda s: s.t0)
    plain, admitting, admitted = [], [], 0
    start = n = None
    for s in loop:
        if s.name == "loop/drain_queue":
            start, n = s.t0, 0
        elif s.name == "loop/admit":
            n = (s.args or {}).get("admitted", 0)
        elif s.name == "loop/retire" and start is not None:
            if n:
                admitting.append(s.t1 - start)
                admitted += n
            else:
                plain.append(s.t1 - start)
            start = None
    if not admitted or not plain:
        return None
    base = statistics.median(plain)
    return 1e3 * (sum(admitting) - len(admitting) * base) / admitted
