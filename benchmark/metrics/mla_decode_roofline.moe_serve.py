"""One layer's absorbed attention in the tick against its roofline: the
larger of operations over the peak and bytes over the HBM bandwidth
(benchmark/flops_latent_moe.py, for the latent rows the traced ticks'
slots held) over the device time of the tick program's operations
under the named scope ``mla_attention`` (benchmark/trace_scopes.py) per
call (one call a layer and tick)."""
from benchmark import flops, trace_scopes
from benchmark import flops_latent_moe as counts

SCOPE = "mla_attention"


def read(run):
    if run["kind"] != "decode" or run["trace"] is None:
        return None
    per_tick = trace_scopes.seconds_per_run(run.get("program_ops"), "tick",
                                            scope=SCOPE)
    tick = counts.mean_tick(run)
    if not per_tick or tick is None:
        return None
    model = run["config"]["model"]
    cost = counts.mla_decode_cost(model, tick["active"],
                                  tick["tokens_held"])
    least = flops.roofline_seconds(cost, run["peaks"])
    return 100.0 * least / (per_tick / model["num_hidden_layers"])
