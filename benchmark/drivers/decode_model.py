"""Driver ``decode_model``: the model the configuration names
(``serve.entry``, ``module:Class``, built from the file's ``model``
section) in the program's paged ``serving.DecodeEngine``, with weights
in the configuration's serving dtype and the traffic file's
``prefill_chunk``.  A model family brings its driver file: the four
functions ``drivers/decode.run`` asks of a family; the feeders, the
window, the timing, the check and the ``run`` keys are ``decode``'s, so
the generic metric readers work unchanged.  A traced run also keeps the
device time by program, operation and named scope
(benchmark/trace_scopes.py) under ``program_ops``.
"""
from __future__ import annotations

import importlib
import sys

import jax.numpy as jnp

from benchmark.drivers import decode


def build_model(config: dict):
    module, _, cls = config["serve"]["entry"].partition(":")
    return getattr(importlib.import_module(module), cls)(**config["model"])


def make_variables(config: dict, model, seed: int):
    from benchmark import weights

    return weights.make_variables(
        model, config["serve"]["init"], seed,
        dtype=jnp.dtype(config["serve"].get("dtype", "float32")))


def engine_options(mix: dict) -> dict:
    return {"prefill_chunk": mix.get("prefill_chunk")}


def device_time_by_program(trace_dir) -> dict:
    """``benchmark/trace_scopes.read`` of the traced span, printed by
    program and scope (PERF.md section 5's tables)."""
    from benchmark import trace_scopes

    program_ops = trace_scopes.read(trace_dir)
    for program, scopes in sorted(
            trace_scopes.by_scope(program_ops).items(),
            key=lambda kv: -sum(kv[1].values())):
        runs = program_ops[program]["runs"]
        print(f"[trace] {program} x{runs}, ms a run by scope: "
              + ", ".join(f"{scope} {1e3 * sec / runs:.3f}"
                          for scope, sec in scopes.items()), flush=True)
    return program_ops


def run(cell, device, seed, seconds, trace, t_start, compiles,
        control: str = "") -> dict:
    return decode.run(cell, device, seed, seconds, trace, t_start,
                      compiles, control=control,
                      family=sys.modules[__name__])
