#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, on the TPU or not at all.  The cell is looked up in
``BENCHMARK.json``; its configuration (``benchmark/configs``), traffic
mix (``benchmark/traffic``), limits (``benchmark/cells``) and per-layer
metric readers (``benchmark/metrics``) are files found by name, so a
later PR adds a cell or a metric by adding files and entries.  A traffic
file names its driver (``benchmark/drivers``): ``train`` drives
``models/*_train.build`` + ``Optimizer.optimize()``, ``decode`` drives
``serving.DecodeEngine.submit()``.

The last line of standard output is the result's JSON object; numbers
worth keeping that are no metric go on earlier lines.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()  # set-up is counted from here

import argparse
import importlib
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """Everything the files say about one cell."""
    bench = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == cell["config"])
    reports = lambda m: name in m.get("workloads", [name])
    return {
        "name": name, "chips": cell["chips"],
        "config": load_json(config_entry["file"]),
        "traffic": load_json("benchmark", "traffic",
                             cell["traffic"] + ".json"),
        "limits": load_json("benchmark", "cells", name + ".json")["limits"],
        "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
        "per_layer": [m for m in bench["per_layer"] if reports(m)],
    }


def read_metric(name: str, run: dict):
    """A per-layer metric's reader: ``benchmark/metrics/<name>.py`` with
    ``read(run)``; it returns nothing where it finds nothing to read."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(run)


def result_line(cell: dict, device: dict, run: dict, trace: bool) -> dict:
    from benchmark.check import judge

    metrics = {}
    if trace:
        for m in cell["per_layer"]:
            value = read_metric(m["name"], run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": run["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    verdict = judge(run["numbers"], cell["limits"], run["flags"])
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": run["memory_peak_bytes"]}
    line = {"correct": verdict["correct"], "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = run["trace"]["busy_s"]
        dev["window_s"] = run["trace"]["window_s"]
        if run["trace"]["device_ops"]:  # none where the device was idle
            line["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                                 "idle_gaps": run["trace"]["idle_gaps"]}
    line["compared"] = verdict["compared"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    from benchmark.device import CompileCount, enable_cache, find_device

    device = find_device(cell["chips"])
    cache = enable_cache()
    say(f"[run] cell {cell['name']} seed {args.seed} seconds "
        f"{args.seconds} trace {args.trace} device {device['kind']} x"
        f"{device['count']} cache {cache}")
    driver = importlib.import_module(
        "benchmark.drivers." + cell["traffic"]["driver"])
    run = driver.run(cell=cell, device=device, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     t_start=_T_START, compiles=CompileCount())

    from benchmark.check import print_compared

    if run.get("trace"):
        t = run["trace"]
        say(f"[trace] window {t['window_s']:.3f} s busy {t['busy_s']:.3f} s; "
            f"programs {sorted(t['by_module'].items(), key=lambda kv: -kv[1][0])[:8]}")
        say(f"[trace] top operations (seconds, calls): "
            f"{sorted(t['by_name'].items(), key=lambda kv: -kv[1][0])[:16]}")
    line = result_line(cell, device, run, bool(args.trace))
    print_compared({"correct": line["correct"],
                    "compared": line["compared"]})
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
