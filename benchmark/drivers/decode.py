"""Driver ``decode``: ``nn.Transformer`` in the program's paged
``serving.DecodeEngine``, fed by the seeded generator through
``DecodeEngine.submit()``.  An open loop (``rate``) sends each request
when it is due and times it from then; a closed loop (``clients``)
sends a client's next request the moment its last one is answered.

A model family brings its driver file (``decode_model``), which hands
``run`` its own ``family``: the four functions below that build the
model and its weights, name the engine's further options and read a
traced span's programs.  The feeders, the window, the timing, the check
and the ``run`` keys are this file's for every family.
"""
from __future__ import annotations

import gc
import importlib
import queue
import sys
import threading
import time

import jax
import numpy as np

ANSWER_WAIT_S = 60.0  # past the window's close, for answers still due


class Request:
    def __init__(self, spec, t_open):
        self.prompt, self.max_new = spec["prompt"], spec["max_new"]
        self.measured = spec["measured"]
        self.due = t_open + spec["due"]  # a closed loop: set when sent
        self.sent = self.done = None
        self.tokens = self.token_times = self.error = None

    def finish(self, fut):
        self.done = time.perf_counter()
        try:
            self.tokens = np.asarray(fut.result(0))
            self.token_times = fut.token_times
        except Exception as e:  # the engine's answer was an error
            self.error = e

    def send(self, eng, then=None):
        def answered(fut):
            self.finish(fut)
            if then is not None:
                then(self)

        try:
            eng.submit(self.prompt, self.max_new).add_done_callback(
                answered)
        except Exception as e:  # refused: counts as missing
            self.done, self.error = time.perf_counter(), e
            if then is not None:
                then(self)


def offer(eng, requests, lateness):
    """The open loop's generator thread: send each request when it is
    due; ``lateness`` gets how late each was sent."""
    for r in requests:
        wait = r.due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        r.sent = time.perf_counter()
        lateness.append(r.sent - r.due)
        r.send(eng)


def offer_closed(eng, requests, clients, t_open, t_close, lateness):
    """The closed loop's generator thread: ``clients`` requests in
    flight, the next one sent the moment an answer frees a client, and
    nothing after the window's close.  A request is due when its client
    came free; ``lateness`` gets how long each client stood idle."""
    free = queue.Queue()
    for _ in range(clients):
        free.put(None)
    for r in requests:
        came_free = free.get()
        r.sent = r.due = time.perf_counter()
        if r.sent >= t_close:
            r.sent = None
            return
        r.measured = r.sent >= t_open
        if came_free is not None:
            lateness.append(r.sent - came_free.done)
        r.send(eng, then=free.put)


def sleep_until(t):
    wait = t - time.perf_counter()
    if wait > 0:
        time.sleep(wait)


def in_flight_mean(requests, t0, t1) -> float:
    """Time-weighted mean number of requests sent and unanswered over
    ``[t0, t1]``."""
    held = sum(max(0.0, min(r.done if r.done is not None else t1, t1)
                   - max(r.sent, t0))
               for r in requests if r.sent is not None)
    return held / (t1 - t0)


def tokens_stamped_in(requests, t0, t1) -> int:
    """Generated tokens whose own time stamps fall inside ``[t0, t1]``,
    of every request, whenever it was sent."""
    return int(sum(((r.token_times >= t0) & (r.token_times <= t1)).sum()
                   for r in requests if r.token_times is not None))


def served_numbers(gaps) -> dict:
    """Of the served tokens' gaps below the reference's best: the
    widest, the share of tokens that are not the reference's best at
    all, and the mean; a cell's limits name the ones it compares.  The
    widest is a maximum over some hundreds of positions and swings
    with the sample; the share and the mean are steady.  A routed model
    needs the share: where the program and the reference choose
    different held experts (a near-tie among the router's candidates,
    at about one position in twenty at the cell's size) that position's
    logits move by a whole expert's part in any precision, so a run's
    widest gap reads alike in bf16 and fp8, while fp8 puts another
    token first at many times the positions (PERF.md section 2)."""
    flat = np.concatenate([np.ravel(g) for g in gaps]) if gaps \
        else np.zeros((0,))
    if not flat.size:
        return dict.fromkeys(("served_logit_gap", "served_mismatch_share",
                              "served_mean_gap"), float("nan"))
    return {"served_logit_gap": float(flat.max()),
            "served_mismatch_share": float((flat > 0).mean()),
            "served_mean_gap": float(flat.mean())}


# ---- the family: nn.Transformer, whatever the configuration says ---------
def build_model(config: dict):
    import bigdl_tpu.nn as nn

    return nn.Transformer(dropout=0.0, causal=True, **config["model"])


def make_variables(config: dict, model, seed: int):
    from benchmark import weights

    init = config.get("serve", {}).get("init", config["init"])
    return weights.make_variables(model, init, seed)


def engine_options(mix: dict) -> dict:
    return {}


def device_time_by_program(trace_dir):
    return None


def run(cell, device, seed, seconds, trace, t_start, compiles,
        control: str = "", family=None) -> dict:
    from bigdl_tpu.serving import DecodeEngine
    from bigdl_tpu.serving import metrics as serving_metrics

    from benchmark import check, trace_reduce, traffic as gen
    from benchmark.device import device_only, memory_peak_bytes

    family = family or sys.modules[__name__]
    config, mix = cell["config"], cell["traffic"]
    model_cfg = config["model"]
    clients = mix.get("clients")
    stamp = lambda what: print(
        f"[decode] {time.perf_counter() - t_start:6.1f} s: {what}",
        flush=True)
    stamp("imports done")
    model = family.build_model(config)
    variables = family.make_variables(config, model, seed)
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
        metrics=serving_metrics.ServingMetrics(window=1 << 16),
        **family.engine_options(mix))
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
        feed, args = (offer_closed, (eng, requests, clients, t_open,
                                     t_open + seconds, lateness)) \
            if clients else (offer, (eng, requests, lateness))
        feeder = threading.Thread(target=feed, name="bench-offer",
                                  args=args)
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
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            trace_span = {"seconds": t_stop - t_open}
            stamp(f"traced {t_stop - t_open:.2f} s of the window; the "
                  f"profiler took {time.perf_counter() - t_stop:.1f} s "
                  f"to stop")
        sleep_until(t_open + seconds)
        t_close = time.perf_counter()
        window_s = t_close - t_open
        tick_ms_p50 = eng.metrics.tick_ms(50)
        ticks = eng.metrics.base.count(serving_metrics.TICK)
        occupancy = eng.metrics.slot_occupancy()
        recompiles = eng.recompiles  # 0 after the reset, or a fault
        waiting_close = sum(1 for r in requests
                            if r.sent is not None and r.done is None)
        compiles1 = compiles.n
        feeder.join(ANSWER_WAIT_S)
        measured = [r for r in requests if r.measured]
        deadline = t_close + ANSWER_WAIT_S
        for r in measured:  # wait for every answer still due
            while r.done is None and time.perf_counter() < deadline:
                time.sleep(0.01)
        peak = memory_peak_bytes()
    finally:
        eng.close(drain=False, timeout=30.0)

    in_window = [r for r in requests
                 if r.tokens is not None and t_open <= r.done <= t_close]
    tokens_done = int(sum(r.tokens.size for r in in_window))
    tokens_stamped = tokens_stamped_in(requests, t_open, t_close)
    in_flight = in_flight_mean(requests, t_open, t_close)
    # a closed loop's supply has to outlast the window
    supply_lasted = not clients or any(r.sent is None for r in requests)
    answered = [r for r in measured if r.tokens is not None]
    wrong_size = [r for r in answered if r.tokens.size != r.max_new]
    worst = (ANSWER_WAIT_S + window_s) * 1e3
    norm = [1e3 * (r.done - r.due) / r.tokens.size if r.tokens is not None
            else worst for r in measured]
    late = np.asarray(lateness or [0.0])
    sent = sum(1 for r in requests if r.sent is not None)
    loop = f"closed loop of {clients} clients" if clients else "open loop"
    print(f"[decode] {loop}: offered {len(measured)} requests in "
          f"{window_s:.2f} s ({sent - len(measured)} in the lead-in), "
          f"answered {len(answered)}; unanswered at open {waiting_open} "
          f"at close {waiting_close}, in flight {in_flight:.2f} at the "
          f"mean; completed in the window {len(in_window)} requests / "
          f"{tokens_done} tokens, {tokens_stamped} tokens stamped in it; "
          f"generator lateness mean {1e3 * late.mean():.2f} ms max "
          f"{1e3 * late.max():.2f} ms; ticks {ticks} p50 "
          f"{tick_ms_p50:.2f} ms occupancy {occupancy:.3f}; norm latency "
          f"p50 {check.percentile(norm, 50):.2f} p95 "
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
    # its weights and pool wait for the collector otherwise)
    eng = variables = None
    gc.collect()
    program_ops = family.device_time_by_program(trace_dir) if trace \
        else None
    reduced = trace_reduce.reduce_and_remove(
        trace_dir, span_s=trace_span["seconds"]) if trace else None

    t_ref = time.perf_counter()
    reference = importlib.import_module(
        "benchmark.references." + config["reference"])
    params = family.make_variables(config, model, seed)["params"]
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
    numbers = served_numbers(gaps)
    control_numbers = served_numbers(
        [g["control_gaps"] for g in read]) if control else None
    served = int(sum(g.size for g in gaps))
    exact = int(sum((g == 0).sum() for g in gaps))
    over = sorted((float(x) for g in gaps for x in g if x > 0),
                  reverse=True)[:8]
    print(f"[decode] reference over {len(sample)} requests / {served} "
          f"served tokens in {time.perf_counter() - t_ref:.1f} s: {exact} "
          f"are the reference's best, widest gap "
          f"{numbers['served_logit_gap']:.5g}; the largest gaps "
          f"{[round(x, 5) for x in over]}", flush=True)

    return {
        "kind": "decode", "config": config, "traffic": mix,
        "peaks": device["peaks"], "chips": device["count"],
        "window_s": window_s, "trace": reduced, "trace_span": trace_span,
        "program_ops": program_ops,
        "tick_ms_p50": tick_ms_p50, "ticks": ticks,
        "slot_occupancy": occupancy, "mean_context": mean_context,
        "in_flight_mean": in_flight, "waiting": [waiting_open,
                                                 waiting_close],
        "lateness_s": late,
        "end_to_end": {
            "norm_latency_p95_ms": check.percentile(norm, 95),
            "decode_tokens_per_s": tokens_stamped / window_s,
            "setup_s": setup_s},
        "completed_tokens_per_s": tokens_done / window_s,
        "memory_peak_bytes": peak,
        "attempted": len(measured),
        "failed": len(measured) - len(answered) + len(wrong_size),
        "numbers": numbers, "served_tokens": served,
        "control_numbers": control_numbers,
        "flags": {"warmup_compiled_declared": warm_ok,
                  "compiles_in_window": recompiles == 0
                  and compiles1 == compiles0,
                  "every_request_answered":
                  len(answered) == len(measured) and not wrong_size,
                  **({"supply_outlasted_window": supply_lasted}
                     if clients else {})},
    }
