"""The whole tick's share of the chip's peak, for the model with Mamba-2
layers: the operations the traced ticks required
(benchmark/flops_hybrid_ssm.py: their active rows, the K/V rows held,
the live state blocks, the token-expert assignments - all from the
ticks' own spans) over the traced ticks' median time times the peak."""
from benchmark import flops_hybrid_ssm as counts


def read(run):
    if run["kind"] != "decode":
        return None
    got = counts.mean_tick_cost(run)
    if got is None or not got[0]["median_seconds"]:
        return None
    tick, cost = got
    return 100.0 * cost["flops"] / (
        tick["median_seconds"] * run["peaks"]["flops_per_s"])
