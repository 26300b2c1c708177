"""Share of the traced window device 0 spent in all-reduce /
reduce-scatter / all-gather operations."""
from benchmark import trace_reduce


def read(run):
    if run["kind"] != "train" or run["trace"] is None or run["chips"] < 2:
        return None
    seconds, calls = trace_reduce.seconds_matching(
        run["trace"]["by_name"], trace_reduce.COLLECTIVES.pattern)
    if not calls:
        return None
    return 100.0 * seconds / run["trace"]["window_s"]
