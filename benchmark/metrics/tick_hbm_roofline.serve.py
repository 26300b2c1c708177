"""The tick program against its bandwidth bound: the bytes a tick must
read (every weight once, plus the K and V of the tokens the active
slots really hold) over the chip's HBM bandwidth, over the tick
program's device time per run in the trace (found by its name).  The
tokens held are those of the traced ticks themselves (their
``pages_held``), not the window's mean, so bytes and time are of the
same ticks."""
from benchmark import flops, trace_reduce

PROGRAM = r"tick"


def read(run):
    if run["kind"] != "decode" or run["trace"] is None:
        return None
    seconds, calls = trace_reduce.seconds_matching(
        run["trace"]["by_module"], PROGRAM)
    tokens = flops.tokens_held_by_traced_ticks(run)
    if not calls or tokens is None:
        return None
    cost = flops.lm_tick_cost(run["config"]["model"], 1, tokens)
    least = cost["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / calls)
