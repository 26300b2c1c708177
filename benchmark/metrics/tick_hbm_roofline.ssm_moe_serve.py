"""The tick program against its bandwidth bound, for the model with
Mamba-2 layers: the bytes the traced ticks had to read (every weight
outside the routed experts once, the experts that got a token, the K
and V rows the attention layer holds, every live state block read and
written) over the chip's HBM bandwidth, over the tick program's device
time per run in the trace (found by its name)."""
from benchmark import flops_hybrid_ssm as counts
from benchmark import trace_reduce

PROGRAM = r"tick"


def read(run):
    if run["kind"] != "decode" or run["trace"] is None:
        return None
    seconds, calls = trace_reduce.seconds_matching(
        run["trace"]["by_module"], PROGRAM)
    got = counts.mean_tick_cost(run)
    if not calls or got is None:
        return None
    least = got[1]["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / calls)
