"""The whole step's share of the chips' peak: required forward +
backward operations per record (benchmark/flops.py, from the
configuration's shapes) times the traced span's records/s, over chips
times peak."""
from benchmark import flops


def read(run):
    if run["kind"] != "train" or not run["trace_span"]["iterations"]:
        return None
    kind = run["config"]["train"]["data"]["kind"]
    if kind == "lm_tokens":
        per_record = flops.lm_train_flops(
            run["config"]["model"], 1, run["traffic"]["seq_len"])["total"]
    elif kind == "images":
        per_record = flops.resnet50_train_flops(
            run["config"]["train"]["data"]["size"],
            run["config"]["train"]["data"]["classes"])
    else:
        return None
    span = run["trace_span"]
    rate = span["iterations"] * run["traffic"]["batch"] / span["seconds"]
    return 100.0 * per_record * rate / (
        run["chips"] * run["peaks"]["flops_per_s"])
