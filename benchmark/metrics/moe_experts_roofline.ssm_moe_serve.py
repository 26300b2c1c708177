"""One routed layer's two grouped expert products in the tick against
their roofline, at 128 held experts in a 1024-wide latent space:
operations of the assignments and bytes of the experts they touched
(the traced ticks' ``expert_tokens``), over the device time per call
(one call a routed layer and tick) of the tick program's operations
under the named scope ``moe/experts`` or named ``ragged-dot`` or
``grouped_matmul``, each once (benchmark/trace_scopes.py).  At 128 rows
a tick holds 2816 assignments, more than a short buffer's 512, so the
layer runs its buffer under ``lax.cond``: the ``conditional`` under the
routed layer's scope is counted whole, and with it the gather of the
rows and the combine, so the share reads low by their time, never
high."""
from benchmark import flops
from benchmark import flops_hybrid_ssm as counts

KERNELS = ("ragged-dot", "grouped_matmul")


def _routed(op: str, path: str) -> bool:
    return "/moe/experts/" in f"/{path}/" or any(
        k in op for k in KERNELS) or (op.startswith("conditional")
                                      and "/moe/" in f"/{path}/")


def read(run):
    if run["kind"] != "decode" or run["trace"] is None:
        return None
    seconds = runs = 0
    for name, rec in (run.get("program_ops") or {}).items():
        hit = [sec for op, path, sec in rec["ops"] if _routed(op, path)]
        if "tick" in name and hit:
            seconds += sum(hit)
            runs += rec["runs"]
    tick = counts.mean_tick(run)
    if not seconds or tick is None:
        return None
    model = run["config"]["model"]
    layers = counts._dims(model)["routed_layers"]
    cost = counts.experts_cost(model, tick["assignments"] / layers,
                               tick["touched"] / layers)
    least = flops.roofline_seconds(cost, run["peaks"])
    return 100.0 * least / (seconds / runs / layers)
