"""The flash-attention forward kernel against its roofline at the
cell's shapes: the least time the chip could take for one call
(benchmark/flops.py) over the kernel's device time per call in the
trace, found by the kernel's name."""
from benchmark import flops, trace_reduce

# the only Pallas kernel of the train step (ops/pallas/flash_attention.py)
# is the flash forward; the backward is blockwise XLA (while loops)
KERNEL = r"tpu_custom_call|flash_fwd"


def read(run):
    if run["kind"] != "train" or run["trace"] is None:
        return None
    seconds, calls = trace_reduce.seconds_matching(
        run["trace"]["by_name"], KERNEL)
    if not calls:
        return None
    model = run["config"]["model"]
    cost = flops.flash_fwd_cost(
        run["traffic"]["batch"] // run["chips"], model["num_heads"],
        run["traffic"]["seq_len"],
        model["hidden_size"] // model["num_heads"])
    return 100.0 * flops.roofline_seconds(cost, run["peaks"]) / (
        seconds / calls)
