"""The paged-attention kernel against its bandwidth bound: the K and V
bytes held in one layer's pool (benchmark/flops.py: a tick's bytes less
the same tick with no row, over the layers) over the chip's HBM
bandwidth, over the kernel's device time per call in the trace, found
by the kernel's name.  The tokens held are those of the traced ticks
themselves, not the window's mean: ``loop/tick_dispatch`` carries the
pages the slots hold (``args.pages_held``), so bytes and time are of
the same ticks.  A program without the kernel or the counter reads
nothing."""
from benchmark import flops, trace_reduce

KERNEL = r"paged_attn"  # ops/pallas/paged_attention.py: one call a layer


def read(run):
    if run["kind"] != "decode" or run["trace"] is None:
        return None
    seconds, calls = trace_reduce.seconds_matching(
        run["trace"]["by_name"], KERNEL)
    tokens = flops.tokens_held_by_traced_ticks(run)
    if not calls or tokens is None:
        return None
    model = run["config"]["model"]
    held = (flops.lm_tick_cost(model, 1, tokens)["bytes"]
            - flops.lm_tick_cost(model, 0, tokens)["bytes"])
    least = held / model["num_layers"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / calls)
