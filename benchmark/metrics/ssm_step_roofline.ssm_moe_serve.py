"""The Mamba-2 layers' state step in the tick against its roofline: the
live state blocks (the traced ticks' ``state_blocks_held``) read and
written in f32 with x, B, C and delta read and y written
(benchmark/flops_hybrid_ssm.py), over the device time per tick of the
tick program's operations under the named scope ``mixer/ssm`` (one
multi-output fusion a layer: benchmark/trace_scopes.py)."""
from benchmark import flops, trace_scopes
from benchmark import flops_hybrid_ssm as counts

SCOPE = "mixer/ssm"


def read(run):
    if run["kind"] != "decode" or run["trace"] is None:
        return None
    per_tick = trace_scopes.seconds_per_run(run.get("program_ops"), "tick",
                                            scope=SCOPE)
    tick = counts.mean_tick(run)
    if not per_tick or tick is None:
        return None
    model = run["config"]["model"]
    layers = counts._dims(model)["mamba_layers"]
    least = layers * flops.roofline_seconds(
        counts.ssm_step_cost(model, tick["blocks"]), run["peaks"])
    return 100.0 * least / per_tick
