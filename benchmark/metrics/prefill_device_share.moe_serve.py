"""Share of the traced window's busy device time inside the prefill
programs (the bucketed prefill and the chunk program, found by name):
how much of the chip the prompts take from the ticks."""
from benchmark import trace_reduce

PROGRAMS = r"prefill|chunk"


def read(run):
    if run["kind"] != "decode" or run["trace"] is None \
            or not run["trace"]["busy_s"]:
        return None
    seconds, _ = trace_reduce.seconds_matching(
        run["trace"]["by_module"], PROGRAMS)
    return 100.0 * seconds / run["trace"]["busy_s"]
