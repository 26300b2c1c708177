"""The paged-attention kernel over both extents against its roofline:
the K and V rows the traced ticks' slots held at the full layers and
inside the band at the window layers (benchmark/flops_window_moe.py),
the larger of their bytes over the HBM bandwidth and their operations
over the peak, over the device time per tick of the tick program's
operations named ``paged_attn`` (one call a layer, under the scopes
``attention/window`` and ``attention/full``:
benchmark/trace_scopes.py)."""
from benchmark import flops, trace_scopes
from benchmark import flops_window_moe as counts

KERNEL = "paged_attn"


def read(run):
    if run["kind"] != "decode" or run["trace"] is None:
        return None
    per_tick = trace_scopes.seconds_per_run(run.get("program_ops"), "tick",
                                            kernel=KERNEL)
    tick = counts.mean_tick(run)
    if not per_tick or tick is None:
        return None
    model = run["config"]["model"]
    x = counts._dims(model)
    least = sum(
        layers * flops.roofline_seconds(
            counts.attn_decode_cost(model, tick["active"], rows),
            run["peaks"])
        for layers, rows in ((x["full_layers"], tick["full_rows"]),
                             (x["window_layers"], tick["window_rows"])))
    return 100.0 * least / per_tick
