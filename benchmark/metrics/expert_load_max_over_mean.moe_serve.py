"""Over the traced ticks, the tokens at the busiest held expert over the
mean over the held experts (``loop/tick_dispatch``'s
``expert_tokens``, summed over the ticks by layer and expert): 1 is an
even load."""
import statistics

from benchmark import flops_latent_moe as counts


def read(run):
    if run["kind"] != "decode":
        return None
    ticks = counts.traced_ticks(run)
    if not ticks:
        return None
    totals = [sum(col) for layer in zip(*(t["expert_tokens"] for t in ticks))
              for col in zip(*layer)]
    mean = statistics.fmean(totals)
    return max(totals) / mean if mean else None
