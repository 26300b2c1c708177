"""Median of the engine's own tick timer (``ServingMetrics.tick_ms``)
over the window's ticks: one host round trip, ended by the token fetch."""


def read(run):
    if run["kind"] != "decode" or not run["ticks"]:
        return None
    return run["tick_ms_p50"]
