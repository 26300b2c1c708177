"""Device-busy time of the traced span per iteration completed in it."""


def read(run):
    if run["kind"] != "train" or not run["trace_span"]["iterations"]:
        return None
    return 1e3 * run["trace"]["busy_s"] / run["trace_span"]["iterations"]
