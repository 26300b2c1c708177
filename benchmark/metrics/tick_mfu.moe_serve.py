"""The whole tick's share of the chip's peak: the operations the traced
ticks required (benchmark/flops_latent_moe.py: their active rows, the
latent tokens their slots held, the token-expert assignments that
landed on a held expert - all from the ticks' own spans) over the
traced ticks' median time times the peak."""
from benchmark import flops_latent_moe as counts


def read(run):
    if run["kind"] != "decode":
        return None
    tick = counts.mean_tick(run)
    if tick is None or not tick["median_seconds"]:
        return None
    cost = counts.tick_cost(run["config"]["model"], tick["active"],
                            tick["tokens_held"], tick["assignments"],
                            tick["touched"])
    return 100.0 * cost["flops"] / (
        tick["median_seconds"] * run["peaks"]["flops_per_s"])
