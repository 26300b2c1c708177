"""Driver ``decode_model``: the model the configuration names
(``serve.entry``, ``module:Class``, built from the file's ``model``
section) in the program's paged ``serving.DecodeEngine``, with weights
in the configuration's serving dtype and the traffic file's
``prefill_chunk``.  A model family brings its driver file; the
generator thread, the timing and the ``run`` keys are ``decode``'s, so
the generic metric readers work unchanged.  A traced run also keeps the
device time by program, operation and named scope
(benchmark/trace_scopes.py) under ``program_ops``.
"""
from __future__ import annotations

import gc
import importlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.drivers.decode import (ANSWER_WAIT_S, Request, offer,
                                      sleep_until)


def build_model(config: dict):
    module, _, cls = config["serve"]["entry"].partition(":")
    return getattr(importlib.import_module(module), cls)(**config["model"])


def make_variables(config: dict, model, seed: int):
    from benchmark import weights

    return weights.make_variables(
        model, config["serve"]["init"], seed,
        dtype=jnp.dtype(config["serve"].get("dtype", "float32")))


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


def served_numbers(gaps) -> dict:
    """What is compared of the served tokens' gaps below the
    reference's best: the widest, and the share of tokens that are not
    the reference's best at all.  A routed model needs both: where the
    program and the reference choose different held experts (a near-tie
    among the router's candidates, at about one position in twenty at
    the cell's size) that position's logits move by a whole expert's
    part in any precision, so a run's widest gap reads alike in bf16
    and fp8, while fp8 puts another token first at many times the
    positions (PERF.md section 2 has the counts)."""
    flat = np.concatenate([np.ravel(g) for g in gaps]) if gaps \
        else np.zeros((0,))
    if not flat.size:
        return {"served_logit_gap": float("nan"),
                "served_mismatch_share": float("nan")}
    return {"served_logit_gap": float(flat.max()),
            "served_mismatch_share": float((flat > 0).mean())}


def run(cell, device, seed, seconds, trace, t_start, compiles,
        control: str = "") -> dict:
    from bigdl_tpu.serving import DecodeEngine
    from bigdl_tpu.serving import metrics as serving_metrics

    from benchmark import check, trace_reduce, traffic as gen
    from benchmark.device import device_only, memory_peak_bytes

    config, mix = cell["config"], cell["traffic"]
    model_cfg = config["model"]
    stamp = lambda what: print(
        f"[decode] {time.perf_counter() - t_start:6.1f} s: {what}",
        flush=True)
    stamp("imports done")
    model = build_model(config)
    variables = make_variables(config, model, seed)
    jax.block_until_ready(variables)
    stamp("weights made")
    stream = gen.request_stream(mix, seed, seconds,
                                model_cfg["vocab_size"])
    eng = DecodeEngine(
        model, variables, slots=mix["slots"], max_len=mix["max_len"],
        prompt_buckets=mix["prompt_buckets"],
        prefill_batch_sizes=mix["prefill_batch_sizes"],
        kv_layout="paged", page_size=mix["page_size"],
        prefill_chunk=mix.get("prefill_chunk"),
        max_queue=len(stream) + 1,
        metrics=serving_metrics.ServingMetrics(window=1 << 16))
    trace_dir = trace_reduce.fresh_trace_dir() if trace else None
    try:
        declared = eng.declared_programs()
        warm_ok = eng.recompiles == declared
        print(f"[decode] warm-up compiled {eng.recompiles} programs "
              f"(declared {declared}) by "
              f"{time.perf_counter() - t_start:.1f} s", flush=True)
        lead = float(mix.get("lead_in_s", 0.0))
        t_open = time.perf_counter() + lead + 0.05
        requests = [Request(s, t_open) for s in stream]
        lateness = []
        feeder = threading.Thread(target=offer, name="bench-offer",
                                  args=(eng, requests, lateness))
        feeder.start()
        sleep_until(t_open)
        eng.metrics.base.reset()  # the window's own ticks and counters
        waiting_open = sum(1 for r in requests
                           if r.sent is not None and r.done is None)
        compiles0 = compiles.n
        setup_s = time.perf_counter() - t_start
        trace_span = None
        if trace_dir:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=device_only())
            sleep_until(t_open + min(mix.get("trace_seconds", 3), seconds))
            jax.profiler.stop_trace()
            trace_span = {"seconds": time.perf_counter() - t_open}
        sleep_until(t_open + seconds)
        t_close = time.perf_counter()
        window_s = t_close - t_open
        measured = [r for r in requests if r.measured]
        in_window = [r for r in requests
                     if r.tokens is not None and t_open <= r.done <= t_close]
        tokens_done = int(sum(r.tokens.size for r in in_window))
        tick_ms_p50 = eng.metrics.tick_ms(50)
        ticks = eng.metrics.base.count(serving_metrics.TICK)
        occupancy = eng.metrics.slot_occupancy()
        recompiles = eng.recompiles  # 0 after the reset, or a fault
        waiting_close = sum(1 for r in requests
                            if r.sent is not None and r.done is None)
        compiles1 = compiles.n
        feeder.join(ANSWER_WAIT_S)
        deadline = t_close + ANSWER_WAIT_S
        for r in measured:  # wait for every answer still due
            while r.done is None and time.perf_counter() < deadline:
                time.sleep(0.01)
        peak = memory_peak_bytes()
    finally:
        eng.close(drain=False, timeout=30.0)

    answered = [r for r in measured if r.tokens is not None]
    wrong_size = [r for r in answered if r.tokens.size != r.max_new]
    worst = (ANSWER_WAIT_S + window_s) * 1e3
    norm = [1e3 * (r.done - r.due) / r.tokens.size if r.tokens is not None
            else worst for r in measured]
    late = np.asarray(lateness)
    print(f"[decode] offered {len(measured)} requests in {window_s:.2f} s "
          f"({len(requests) - len(measured)} in the lead-in), answered "
          f"{len(answered)}; unanswered at open {waiting_open} at close "
          f"{waiting_close}; completed in the window {len(in_window)} "
          f"requests / {tokens_done} tokens; generator lateness mean "
          f"{1e3 * late.mean():.2f} ms max {1e3 * late.max():.2f} ms; ticks "
          f"{ticks} p50 {tick_ms_p50:.2f} ms occupancy {occupancy:.3f}; "
          f"norm latency p50 {check.percentile(norm, 50):.2f} p95 "
          f"{check.percentile(norm, 95):.2f} ms/token; set-up "
          f"{setup_s:.1f} s; memory_stats peak {peak / 2**30:.2f} GiB",
          flush=True)

    # token-weighted mean context held while the answers were decoded
    out = np.asarray([r.tokens.size for r in answered], np.float64)
    ctx = np.asarray([r.prompt.size + (r.tokens.size - 1) / 2.0
                      for r in answered])
    mean_context = float((out * ctx).sum() / max(out.sum(), 1.0))

    # the program's state is freed before the reference runs (the
    # engine sits in reference cycles with its threads and loggers, and
    # 9.5 GiB of weights and pool wait for the collector otherwise)
    eng = variables = None
    gc.collect()
    program_ops = device_time_by_program(trace_dir) if trace else None
    reduced = trace_reduce.reduce_and_remove(trace_dir) if trace else None

    t_ref = time.perf_counter()
    reference = importlib.import_module(
        "benchmark.references." + config["reference"])
    params = make_variables(config, model, seed)["params"]
    rng = gen.rng_for(seed, 2)
    longest = max(answered, key=lambda r: r.prompt.size + r.tokens.size,
                  default=None)
    sample = [longest] if longest is not None else []
    others = [r for r in answered if r is not longest]
    picks = rng.permutation(len(others))[:mix["check_requests"] - 1]
    sample += [others[i] for i in picks]
    read = [reference.served_gaps(params, r.prompt, r.tokens, model_cfg,
                                  control=control) for r in sample]
    gaps = [g["gaps"] for g in read]
    control_numbers = served_numbers(
        [g["control_gaps"] for g in read]) if control else None
    numbers = served_numbers(gaps)
    served = int(sum(g.size for g in gaps))
    widest = numbers["served_logit_gap"]
    exact = int(sum((g == 0).sum() for g in gaps))
    over = sorted((float(x) for g in gaps for x in g if x > 0),
                  reverse=True)[:8]
    print(f"[decode] reference over {len(sample)} requests / {served} "
          f"served tokens in {time.perf_counter() - t_ref:.1f} s: {exact} "
          f"are the reference's best, widest gap {widest:.5g}; the "
          f"largest gaps {[round(x, 4) for x in over]}", flush=True)

    return {
        "kind": "decode", "config": config, "traffic": mix,
        "peaks": device["peaks"], "chips": device["count"],
        "window_s": window_s, "trace": reduced, "trace_span": trace_span,
        "program_ops": program_ops,
        "tick_ms_p50": tick_ms_p50, "ticks": ticks,
        "slot_occupancy": occupancy, "mean_context": mean_context,
        "end_to_end": {
            "norm_latency_p95_ms": check.percentile(norm, 95),
            "setup_s": setup_s},
        "completed_tokens_per_s": tokens_done / window_s,
        "memory_peak_bytes": peak,
        "attempted": len(measured),
        "failed": len(measured) - len(answered) + len(wrong_size),
        "numbers": numbers,
        "control_numbers": control_numbers,
        "flags": {"warmup_compiled_declared": warm_ok,
                  "compiles_in_window": recompiles == 0
                  and compiles1 == compiles0,
                  "every_request_answered":
                  len(answered) == len(measured) and not wrong_size},
    }
