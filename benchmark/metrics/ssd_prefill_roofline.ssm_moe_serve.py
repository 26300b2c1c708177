"""The Mamba-2 layers' chunked scan in the prompt programs against its
roofline: the larger of its operations over the peak and its bytes over
the HBM bandwidth (benchmark/flops_hybrid_ssm.ssd_cost, at the rows each
traced run of the chunk program and of the bucketed prefill held), over
the device time of those programs' operations under the named scope
``mixer/ssd`` (benchmark/trace_scopes.py)."""
from benchmark import flops
from benchmark import flops_hybrid_ssm as counts

SCOPE = "/mixer/ssd/"


def read(run):
    if run["kind"] != "decode" or run["trace"] is None:
        return None
    seconds = sum(sec for program, rec in (run.get("program_ops") or {})
                  .items() if "chunk" in program or "prefill" in program
                  for _, path, sec in rec["ops"]
                  if SCOPE in f"/{path}/")
    runs = counts.ssd_runs(run)
    if not seconds or not runs:
        return None
    model = run["config"]["model"]
    layers = counts._dims(model)["mamba_layers"]
    least = layers * sum(flops.roofline_seconds(
        counts.ssd_cost(model, rows), run["peaks"]) for rows in runs)
    return 100.0 * least / seconds
