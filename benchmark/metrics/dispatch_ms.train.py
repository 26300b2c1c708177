"""Mean host time of one step's dispatch (the training loop's
``dispatch`` span: the enqueue of the compiled step, not its run), over
the program's own spans of the traced span."""


def read(run):
    if run["kind"] != "train":
        return None
    from bigdl_tpu.telemetry import get_tracer

    spans = [s for s in get_tracer().spans()
             if s.name == "dispatch" and s.cat == "train"]
    if not spans:
        return None
    return 1e3 * sum(s.duration for s in spans) / len(spans)
