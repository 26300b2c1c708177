"""The whole tick's share of the chip's peak: required operations of one
tick at the window's mean active slots and mean held context
(benchmark/flops.py) over the median tick time times the peak."""
from benchmark import flops


def read(run):
    if run["kind"] != "decode" or not run["ticks"] \
            or not run["tick_ms_p50"]:
        return None
    active = run["slot_occupancy"] * run["traffic"]["slots"]
    cost = flops.lm_tick_cost(run["config"]["model"], active,
                              run["mean_context"])
    return 100.0 * cost["flops"] / (
        run["tick_ms_p50"] * 1e-3 * run["peaks"]["flops_per_s"])
