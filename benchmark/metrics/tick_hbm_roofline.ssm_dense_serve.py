"""The tick program against its bandwidth bound, for the model of Mamba-2
and attention layers with a dense MLP in each (Granite 4.0-H): the bytes
the traced ticks had to read (every weight once, the tied head's matrix
among them, the K and V rows the attention layers hold, every live
state block and convolution history read and written:
benchmark/flops_granite_hybrid.py) over the chip's HBM bandwidth, over
the tick program's device time per run in the trace (found by its
name)."""
from benchmark import flops_granite_hybrid as counts
from benchmark import trace_reduce

PROGRAM = r"tick"


def read(run):
    if run["kind"] != "decode" or run["trace"] is None:
        return None
    seconds, calls = trace_reduce.seconds_matching(
        run["trace"]["by_module"], PROGRAM)
    tick = counts.mean_tick(run)
    if not calls or tick is None:
        return None
    cost = counts.tick_cost(run["config"]["model"], tick["active"],
                            tick["rows"], tick["blocks"])
    least = cost["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / calls)
