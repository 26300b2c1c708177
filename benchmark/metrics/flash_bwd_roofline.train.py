"""The flash-attention backward kernels against their roofline at the
cell's shapes: the least time the chip could take for one layer's
backward, twice the forward's cost (benchmark/flops.py: dQ, dK, dV and
dP at the causal half, the recomputed scores not counted; q, k, v, o
and dO read, dq, dk and dv written), over the device time of the
operations named ``flash_bwd`` per forward call (one a layer and
step).  Nothing where the trace holds no such kernel."""
from benchmark import flops, trace_reduce

BACKWARD = r"flash_bwd"
FORWARD = r"^flash_fwd"


def read(run):
    if run["kind"] != "train" or run["trace"] is None:
        return None
    by_name = run["trace"]["by_name"]
    seconds, _ = trace_reduce.seconds_matching(by_name, BACKWARD)
    _, calls = trace_reduce.seconds_matching(by_name, FORWARD)
    if not seconds or not calls:
        return None
    model = run["config"]["model"]
    fwd = flops.flash_fwd_cost(
        run["traffic"]["batch"] // run["chips"], model["num_heads"],
        run["traffic"]["seq_len"],
        model["hidden_size"] // model["num_heads"])
    cost = {key: 2 * value for key, value in fwd.items()}
    return 100.0 * flops.roofline_seconds(cost, run["peaks"]) / (
        seconds / calls)
