"""Share of the traced span the training loop waited for a batch (the
program's own ``Metrics`` phase ``data_stall``, summed over the span;
read before the profiler stops, which itself stalls the loop)."""


def read(run):
    if run["kind"] != "train" or not run["trace_span"]:
        return None
    span = run["trace_span"]
    return 100.0 * span["data_stall_s"] / span["seconds"]
