"""The tick program against its bandwidth bound: the bytes the traced
ticks had to read (every weight outside the routed experts once, only
the held experts that got a token, the latent rows the slots hold) over
the chip's HBM bandwidth, over the tick program's device time per run
in the trace (found by its name).  Counting experts nobody chose would
read over 100%."""
from benchmark import flops_latent_moe as counts
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
                            tick["tokens_held"], tick["assignments"],
                            tick["touched"])
    least = cost["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / calls)
