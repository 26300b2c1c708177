"""Every layer's dense MLP in the tick against its roofline, for the
Granite 4.0-H model: the three matrices of each layer read once and the
active rows in and out (benchmark/flops_granite_hybrid.mlp_cost, the
traced ticks' mean active rows), over the device time per tick of the
tick program's operations under the named scope ``mlp`` and of its
operations under no scope (benchmark/trace_scopes.py).  The second are
XLA's prefetches of weights into fast memory (``slice-start`` /
``slice-done``, ``copy-start`` / ``copy-done`` and their
``ConcatBitcast``), which carry no scope: at 64 rows most of a matrix's
stream runs there and not under its product's scope, so without them
the MLP's time would leave out most of its bytes (the share read 162%
on the chip).  They hold the mixers' and the head's prefetches too, so
the share reads low by those, never high."""
from benchmark import flops
from benchmark import flops_granite_hybrid as counts

SCOPE = "mlp"
UNSCOPED = "-"


def read(run):
    if run["kind"] != "decode" or run["trace"] is None:
        return None
    seconds = runs = 0
    for name, rec in (run.get("program_ops") or {}).items():
        if "tick" not in name or not any(
                f"/{SCOPE}/" in f"/{path}/" for _, path, _ in rec["ops"]):
            continue
        seconds += sum(sec for _, path, sec in rec["ops"]
                       if path == UNSCOPED or f"/{SCOPE}/" in f"/{path}/")
        runs += rec["runs"]
    tick = counts.mean_tick(run)
    if not runs or tick is None:
        return None
    least = flops.roofline_seconds(
        counts.mlp_cost(run["config"]["model"], tick["active"]),
        run["peaks"])
    return 100.0 * least / (seconds / runs)
