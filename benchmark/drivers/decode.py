"""Driver ``decode``: ``nn.Transformer`` in the program's paged
``serving.DecodeEngine``, fed by the seeded open-loop generator through
``DecodeEngine.submit()``.  Each request is timed from when it was due.
"""
from __future__ import annotations

import importlib
import threading
import time

import jax
import numpy as np

ANSWER_WAIT_S = 60.0  # past the window's close, for answers still due


class Request:
    def __init__(self, spec, t_open):
        self.prompt, self.max_new = spec["prompt"], spec["max_new"]
        self.measured = spec["measured"]
        self.due = t_open + spec["due"]
        self.sent = self.done = None
        self.tokens = self.error = None

    def finish(self, fut):
        self.done = time.perf_counter()
        try:
            self.tokens = np.asarray(fut.result(0))
        except Exception as e:  # the engine's answer was an error
            self.error = e


def offer(eng, requests, lateness):
    """The generator thread: send each request when it is due."""
    for r in requests:
        wait = r.due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        r.sent = time.perf_counter()
        lateness.append(r.sent - r.due)
        try:
            eng.submit(r.prompt, r.max_new).add_done_callback(r.finish)
        except Exception as e:  # refused: counts as missing
            r.done, r.error = time.perf_counter(), e


def sleep_until(t):
    wait = t - time.perf_counter()
    if wait > 0:
        time.sleep(wait)


def run(cell, device, seed, seconds, trace, t_start, compiles,
        control: str = "") -> dict:
    import bigdl_tpu.nn as nn
    from bigdl_tpu.serving import DecodeEngine
    from bigdl_tpu.serving import metrics as serving_metrics

    from benchmark import check, trace_reduce, traffic as gen, weights
    from benchmark.device import device_only, memory_peak_bytes

    config, mix = cell["config"], cell["traffic"]
    model_cfg = config["model"]
    stamp = lambda what: print(
        f"[decode] {time.perf_counter() - t_start:6.1f} s: {what}",
        flush=True)
    stamp("imports done")
    model = nn.Transformer(dropout=0.0, causal=True, **model_cfg)
    init = config.get("serve", {}).get("init", config["init"])
    variables = weights.make_variables(model, init, seed)
    jax.block_until_ready(variables)
    stamp("weights made")
    stream = gen.request_stream(mix, seed, seconds,
                                model_cfg["vocab_size"])
    eng = DecodeEngine(
        model, variables, slots=mix["slots"], max_len=mix["max_len"],
        prompt_buckets=mix["prompt_buckets"],
        prefill_batch_sizes=mix["prefill_batch_sizes"],
        kv_layout="paged", page_size=mix["page_size"],
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

    # the program's state is freed before the reference runs
    eng = variables = None
    reduced = trace_reduce.reduce_and_remove(trace_dir) if trace else None

    t_ref = time.perf_counter()
    reference = importlib.import_module(
        "benchmark.references." + config["reference"])
    params = weights.make_variables(model, init, seed)["params"]
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
    control_numbers = {"served_logit_gap": float(max(
        g["control_gaps"].max() for g in read))} if control else None
    served = int(sum(g.size for g in gaps))
    widest = float(max((g.max() for g in gaps), default=float("nan")))
    exact = int(sum((g == 0).sum() for g in gaps))
    print(f"[decode] reference over {len(sample)} requests / {served} "
          f"served tokens in {time.perf_counter() - t_ref:.1f} s: {exact} "
          f"are the reference's best, widest gap {widest:.5g}", flush=True)

    return {
        "kind": "decode", "config": config, "traffic": mix,
        "peaks": device["peaks"], "chips": device["count"],
        "window_s": window_s, "trace": reduced, "trace_span": trace_span,
        "tick_ms_p50": tick_ms_p50, "ticks": ticks,
        "slot_occupancy": occupancy, "mean_context": mean_context,
        "end_to_end": {
            "norm_latency_p95_ms": check.percentile(norm, 95),
            "setup_s": setup_s},
        "completed_tokens_per_s": tokens_done / window_s,
        "memory_peak_bytes": peak,
        "attempted": len(measured),
        "failed": len(measured) - len(answered) + len(wrong_size),
        "numbers": {"served_logit_gap": widest},
        "control_numbers": control_numbers,
        "flags": {"warmup_compiled_declared": warm_ok,
                  "compiles_in_window": recompiles == 0
                  and compiles1 == compiles0,
                  "every_request_answered":
                  len(answered) == len(measured) and not wrong_size},
    }
