"""Rows a window layer holds over rows the full layer holds, over the
traced ticks (``loop/tick_dispatch``'s ``window_pages_held`` and
``pages_held``, summed over the slots): what the second extent saves a
tick's reads and the pool.  100% if the band is lost (a window layer
keeping every row); a program that keeps one extent reads nothing."""
from benchmark import flops_window_moe as counts


def read(run):
    if run["kind"] != "decode":
        return None
    tick = counts.mean_tick(run)
    if tick is None or not tick["full_rows"]:
        return None
    return 100.0 * tick["window_rows_held"] / tick["full_rows"]
