"""``tick_ms_p50.serve`` for a cell judged on ``decode_tokens_per_s``:
median of the engine's own tick timer (``ServingMetrics.tick_ms``) over
the window's ticks, the loop's period at full slots."""


def read(run):
    if run["kind"] != "decode" or not run["ticks"]:
        return None
    return run["tick_ms_p50"]
