#!/usr/bin/env python3
"""Readings for the limits of ``correct`` (PERF.md section 2), on the
chip at the cell's own size.  The benchmark's own runs never call this.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--seconds 8]

A training cell: the plain reference is put in the program's place and
computed (a) in the nearest precision below the configuration's (fp8 for
bf16) and (b) with half of each batch left out; both are compared with
the reference proper, as a run compares the program.  A served model:
a short window at the cell's own load per seed; at each position of the
served prompts and tokens the gap of the token that the lower precision
(bf16 for f32) puts first.  Every number is printed beside its limit.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def train_controls(cell, seed: int) -> dict:
    """-> {control name: numbers} for one seed; no program involved."""
    from benchmark import check, traffic as gen, weights

    config, mix = cell["config"], cell["traffic"]
    spec = config["train"]
    reference = importlib.import_module(
        "benchmark.references." + config["reference"])
    x, y = gen.training_data(spec["data"], dict(mix, rows=3 * mix["batch"]),
                             seed)
    b = mix["batch"]
    first = [(x[i * b:(i + 1) * b], y[i * b:(i + 1) * b]) for i in range(3)]
    model = reference_model(config)
    params = weights.make_variables(model, config["init"], seed)["params"]
    ref = reference.train_steps(params, first, config["model"],
                                spec["optimizer"])
    out = {}
    for name, kw in (("lower_precision", {"precision": spec["control"]}),
                     ("half_batch", {"drop_half": True})):
        got = reference.train_steps(params, first, config["model"],
                                    spec["optimizer"], **kw)
        out[name] = check.training_numbers(got, ref)
    return out


def reference_model(config):
    """The program's model object, for the weights' tree structure only."""
    if config["reference"] == "lm":
        import bigdl_tpu.nn as nn

        return nn.Transformer(dropout=0.0, causal=True, **config["model"])
    if config["reference"] == "resnet50":
        from bigdl_tpu.models.resnet import ResNet

        return ResNet(config["model"]["classes"], config["model"]["depth"])
    raise ValueError(f"no model for reference {config['reference']!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("benchmark/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rate", default=None,
                    help="offer this many requests/s instead of the "
                         "mix's own (the sweep for the knee): one rate, "
                         "or one a seed, separated by commas")
    args = ap.parse_args(argv)

    from benchmark.device import CompileCount, enable_cache, find_device
    from benchmark.run import load_cell

    cell = load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rate.split(",")] if args.rate \
        else [cell["traffic"].get("rate")]
    device = find_device(cell["chips"])
    enable_cache()
    compiles = CompileCount()
    for i, seed in enumerate(seeds):
        rate = rates[i % len(rates)]
        if rate is not None:
            cell["traffic"]["rate"] = rate
        t0 = time.perf_counter()
        if cell["traffic"]["driver"] == "train":
            readings = train_controls(cell, seed)
        else:
            driver = importlib.import_module(
                "benchmark.drivers." + cell["traffic"]["driver"])
            run = driver.run(cell=cell, device=device, seed=seed,
                             seconds=args.seconds, trace=False,
                             t_start=t0, compiles=compiles,
                             control=cell["config"]["serve"]["control"])
            readings = {"served_tokens": run["served_tokens"],
                        "program": run["numbers"],
                        "lower_precision": run["control_numbers"],
                        "end_to_end": run["end_to_end"],
                        "completed_tokens_per_s":
                        run["completed_tokens_per_s"],
                        "rate": cell["traffic"].get("rate"),
                        "unanswered_at_open_and_close": run["waiting"],
                        "in_flight_mean": run["in_flight_mean"],
                        "lateness_mean_ms":
                        1e3 * float(run["lateness_s"].mean()),
                        "slot_occupancy": run["slot_occupancy"],
                        "tick_ms_p50": run["tick_ms_p50"],
                        "attempted": run["attempted"],
                        "failed": run["failed"]}
        print(json.dumps({"control": args.workload, "seed": seed,
                          "limits": cell["limits"], "readings": readings,
                          "seconds": round(time.perf_counter() - t0, 1)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
