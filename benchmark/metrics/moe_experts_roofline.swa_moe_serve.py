"""One routed layer's grouped expert products in the tick against their
roofline, at 128 held experts: operations of the assignments and bytes
of the experts they touched (the traced ticks' ``expert_tokens``), over
the device time per call (one call a routed layer and tick) of the tick
program's operations that lie under the named scope ``moe/experts`` or
are XLA's grouped-matmul kernels (``ragged-dot``: they keep their name
and lose the scope), each operation once (benchmark/trace_scopes.py)."""
from benchmark import flops, trace_scopes
from benchmark import flops_window_moe as counts

SCOPE = "moe/experts"
KERNEL = "ragged-dot"


def read(run):
    if run["kind"] != "decode" or run["trace"] is None:
        return None
    per_tick = trace_scopes.seconds_per_run(run.get("program_ops"), "tick",
                                            scope=SCOPE, kernel=KERNEL)
    tick = counts.mean_tick(run)
    if not per_tick or tick is None:
        return None
    model = run["config"]["model"]
    layers = counts._dims(model)["routed_layers"]
    cost = counts.experts_cost(model, tick["assignments"] / layers,
                               tick["touched"] / layers)
    least = flops.roofline_seconds(cost, run["peaks"])
    return 100.0 * least / (per_tick / layers)
