"""95th percentile of the gap between a request's consecutive tokens
(a tick plus whatever admission ran between them), over the ``gaps_ms``
that each ``loop/retire`` span of the traced span carries."""
from benchmark import check


def read(run):
    if run["kind"] != "decode":
        return None
    from bigdl_tpu.telemetry import get_tracer

    gaps = [g for s in get_tracer().spans() if s.name == "loop/retire"
            for g in (s.args or {}).get("gaps_ms", ())]
    return check.percentile(gaps, 95) if gaps else None
