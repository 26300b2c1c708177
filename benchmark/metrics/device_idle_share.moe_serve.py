"""``device_idle_share.serve`` for a cell judged on
``decode_tokens_per_s``: 1 - union of the device's operation intervals
over the traced window."""


def read(run):
    if run["kind"] != "decode" or run["trace"] is None:
        return None
    t = run["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
