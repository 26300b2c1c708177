"""Unified telemetry tests (ISSUE 5 tentpole; docs/observability.md):

* tracer semantics: disabled no-op, ring bound, thread/correlation
  capture, the ``Metrics`` span sink (phase timers become spans for
  free, ``no_span`` opt-out);
* the ACCEPTANCE trace: one async-training process (loop + prefetch
  producer + checkpoint writer threads) and one serving process
  (dispatcher + drain threads) each produce a single valid Chrome
  ``trace_event`` JSON file with named threads, monotonic spans, and
  correlation IDs joining a step / a request across threads;
* watchdog anomaly detectors (spikes, steady-state recompiles,
  prefetch starvation, queue saturation, deferred-NaN windows) and
  the TensorBoard round-trip of their counters;
* the periodic ``log_line()`` cadence (``BIGDL_TPU_METRICS_EVERY_S``)
  fires and stops at ``close()``;
* ``get_times_by_type`` reference parity;
* the program's spans in the profiler's own trace (plane /host:CPU,
  corr stats, the session as the switch), the four program_span
  readers of ``benchmark/metrics`` on hand-made rings, and the count
  guard of the off path;
* ``bench.telemetry_ab`` really records under every plane (counts).
"""
import functools
import json
import logging
import os
import time

import jax
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu import telemetry
from bigdl_tpu.dataset import DataSet
from bigdl_tpu.optim import SGD, Trigger
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.optim.optimizer import LocalOptimizer
from bigdl_tpu.serving import ServingEngine
from bigdl_tpu.serving.metrics import (
    PeriodicMetricsLogger,
    metrics_log_every_s,
)
from bigdl_tpu.telemetry.tracer import Span
from bigdl_tpu.telemetry.watchdog import Watchdog
from bigdl_tpu.visualization import TelemetrySummary


@pytest.fixture(autouse=True)
def clean_tracer():
    """Every test starts from a disabled, empty, default-capacity
    global tracer (tests may shrink the ring; undo it)."""
    tr = telemetry.get_tracer()
    tr.disable()
    tr.capacity = telemetry.tracer._env_capacity()
    tr.clear()
    yield tr
    tr.disable()
    tr.capacity = telemetry.tracer._env_capacity()
    tr.clear()


def _span(name, cat="train", dur=0.001, corr=None, args=None,
          thread="t", tid=1, t0=None):
    t0 = time.perf_counter() if t0 is None else t0
    return Span(name, cat, t0, t0 + dur, tid, thread, corr, args)


# ---------------------------------------------------------------- tracer
def test_disabled_tracer_records_nothing(clean_tracer):
    tr = clean_tracer
    tr.instant("x")
    with tr.span("y"):
        pass
    tr.add_span("z", "train", 0.0, 1.0)
    assert len(tr) == 0


def test_spans_capture_thread_correlation_and_ring_bound(clean_tracer):
    tr = clean_tracer
    tr.enable(capacity=8)
    with telemetry.correlate("step:7"):
        with tr.span("dispatch", "train"):
            pass
    tr.instant("enqueue", "serve", corr="req:3", args={"k": 1})
    spans = tr.spans()
    assert [s.name for s in spans] == ["dispatch", "enqueue"]
    assert spans[0].corr == "step:7"  # ambient correlation picked up
    assert spans[1].corr == "req:3" and spans[1].args == {"k": 1}
    assert spans[0].thread  # thread name captured
    assert spans[1].instant and not spans[0].instant
    for i in range(20):  # ring wraps, oldest dropped, order kept
        tr.instant(f"e{i}")
    assert len(tr) == 8
    assert [s.name for s in tr.spans()] == [f"e{i}" for i in range(12, 20)]
    assert tr.dropped > 0


def test_metrics_is_a_span_sink(clean_tracer):
    tr = clean_tracer
    m = Metrics(category="serve")
    m.no_span("latency")
    m.add("latency", 0.5)       # opted out: sample only
    assert len(tr) == 0         # tracer still disabled: nothing
    tr.enable()
    with m.time("serve_dispatch"):
        pass
    m.add("latency", 0.5)
    spans = tr.spans()
    assert [s.name for s in spans] == ["serve_dispatch"]
    assert spans[0].cat == "serve"
    assert m.get("latency") == 0.5  # metrics themselves unaffected


# ------------------------------------------------- acceptance: training
def test_training_trace_correlates_threads(clean_tracer, tmp_path):
    """ISSUE 5 acceptance: ONE process's trace shows correlated spans
    from the training-loop, prefetch-producer, and checkpoint-writer
    threads, and loads as valid Chrome trace_event JSON."""
    rs = np.random.RandomState(0)
    x = rs.randn(64, 8).astype(np.float32)
    y = rs.randint(0, 4, 64)
    model = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
    ds = DataSet.from_arrays(x, y, batch_size=16)
    engine = LocalOptimizer(model, ds, nn.ClassNLLCriterion(logits=True),
                            Trigger.max_iteration(12))
    engine.set_optim_method(SGD(0.1))
    engine.set_checkpoint(str(tmp_path / "ckpt"),
                          Trigger.several_iteration(4))
    telemetry.enable()
    engine.optimize()
    telemetry.disable()

    path = telemetry.write_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        blob = json.load(f)  # valid JSON or this raises
    events = blob["traceEvents"]
    complete = [e for e in events if e.get("ph") == "X"]
    meta = [e for e in events if e.get("ph") == "M"
            and e["name"] == "thread_name"]
    thread_names = {e["args"]["name"] for e in meta}
    # the three async-engine threads are all present and named
    assert any("prefetch" in n for n in thread_names), thread_names
    assert any("ckpt" in n for n in thread_names), thread_names
    assert len(thread_names) >= 3  # + the loop (main) thread

    # monotonic, non-negative timeline
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in complete)

    # correlation: loop-thread phases carry step IDs; the checkpoint
    # writer's span carries the step it persisted; producer items are
    # indexed — and step corr joins spans from MORE than one thread
    by_name = {}
    for e in complete:
        by_name.setdefault(e["name"], []).append(e)
    assert any(e.get("args", {}).get("corr", "").startswith("step:")
               for e in by_name["dispatch"])
    assert any(e.get("args", {}).get("corr", "").startswith("item:")
               for e in by_name["prefetch_item"])
    ckpt = by_name["checkpoint_write"]
    assert ckpt and all(
        e["args"]["corr"].startswith("step:") for e in ckpt)
    step_corrs = {e["args"]["corr"]: e["tid"] for e in by_name["dispatch"]
                  if "args" in e and "corr" in e["args"]}
    ckpt_tids = {e["tid"] for e in ckpt}
    assert ckpt_tids and not ckpt_tids & set(step_corrs.values()), \
        "checkpoint writes must come from their own thread"
    assert any(e["args"]["corr"] in step_corrs for e in ckpt), \
        "a checkpoint span must join a loop step by correlation ID"


# -------------------------------------------------- acceptance: serving
def test_serving_trace_joins_request_lifecycle(clean_tracer, tmp_path):
    model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 2))
    var = model.init(jax.random.PRNGKey(0))
    telemetry.enable()
    with ServingEngine(model, var, buckets=[(4, 4)], batch_sizes=(1, 4),
                       batch_window_ms=1.0) as engine:
        futs = [engine.submit(np.ones((3, 4), np.float32))
                for _ in range(6)]
        for f in futs:
            f.result(30)
    telemetry.disable()

    blob = telemetry.chrome_trace()
    events = blob["traceEvents"]
    thread_names = {e["args"]["name"] for e in events
                    if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert any("dispatch" in n for n in thread_names), thread_names
    assert any("drain" in n for n in thread_names), thread_names

    def corr_of(e):
        return e.get("args", {}).get("corr", "")

    enq = {corr_of(e): e["tid"] for e in events if e["name"] == "enqueue"}
    dlv = {corr_of(e): e["tid"] for e in events if e["name"] == "deliver"}
    assert len(enq) == 6 and len(dlv) == 6
    # every request's enqueue joins its deliver by correlation ID,
    # across different threads (client submit vs drain thread)
    assert set(enq) == set(dlv)
    assert all(c.startswith("req:") for c in enq)
    assert all(enq[c] != dlv[c] for c in enq)
    # json round-trip of the whole trace object
    json.loads(json.dumps(blob))


def test_decode_trace_ticks_and_slots(clean_tracer):
    from bigdl_tpu.serving import DecodeEngine

    model = nn.Transformer(vocab_size=16, hidden_size=16, num_heads=2,
                           filter_size=32, num_layers=1, dropout=0.0,
                           causal=True)
    var = model.init(jax.random.PRNGKey(0))
    telemetry.enable()
    with DecodeEngine(model, var, slots=2, max_len=16,
                      prompt_buckets=(4,), prefill_batch_sizes=(1, 2),
                      eos_id=None) as engine:
        outs = [engine.submit(np.array([1, 2, 3]), 4) for _ in range(3)]
        for f in outs:
            f.result(60)
    telemetry.disable()
    spans = telemetry.get_tracer().spans()
    names = {s.name for s in spans}
    assert {"enqueue", "slot_fill", "deliver", "slot_free",
            "decode_tick", "decode_prefill"} <= names
    ticks = [s for s in spans if s.name == "decode_tick"]
    assert all(s.corr and s.corr.startswith("tick:") for s in ticks)
    delivered = {s.corr for s in spans if s.name == "deliver"}
    enqueued = {s.corr for s in spans if s.name == "enqueue"}
    assert delivered == enqueued and len(delivered) == 3


# ------------------------------------- one timeline (ISSUE 26 tentpole)
def _host_plane_events(logdir):
    """Events of plane ``/host:CPU`` of the newest xplane under
    ``logdir`` (the benchmark's reader: jax.profiler.ProfileData)."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    (plane,) = [p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU"]
    return [ev for ln in plane.lines for ev in ln.events]


def test_span_lands_on_host_plane_of_profiler_trace(clean_tracer,
                                                    tmp_path,
                                                    monkeypatch):
    """Under the benchmark's own profiler options a tracer.span lands
    on /host:CPU of the written xplane with its corr stat; the tracer
    turns on at start_trace and off after stop_trace, with no env."""
    monkeypatch.delenv("BIGDL_TPU_TRACE", raising=False)
    tr = clean_tracer
    options = jax.profiler.ProfileOptions()  # benchmark.device.device_only
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    assert not tr.poll() and len(tr) == 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert tr.poll() and tr.enabled
        args = {"admitted": 2}
        with tr.span("loop/admit", "decode", corr="tick:7", args=args):
            args["gaps_ms"] = [1.5]  # filled inside: ring only
        with jax.profiler.StepTraceAnnotation("decode_tick", step_num=7):
            pass
        # a span the session ends under (the turn in flight while the
        # profiler stops and stalls the device) is not part of it
        with tr.span("loop/tick_wait", "decode"):
            jax.profiler.stop_trace()
    finally:
        if tr._session and jax.profiler.TraceAnnotation.is_enabled():
            jax.profiler.stop_trace()
    assert not tr.poll() and not tr.enabled
    with tr.span("after"):
        pass
    (span,) = tr.spans()  # nothing recorded once the session ended
    assert span.name == "loop/admit" and span.corr == "tick:7"
    assert span.args == {"admitted": 2, "gaps_ms": [1.5]}
    events = _host_plane_events(str(tmp_path))
    (ev,) = [e for e in events if e.name == "loop/admit"]
    stats = dict(ev.stats)
    assert stats["corr"] == "tick:7" and stats["admitted"] == 2
    # the session's clock: the span's length is the ring's, to the us
    assert abs(ev.duration_ns * 1e-9 - span.duration) < 1e-4
    (step,) = [e for e in events if e.name == "decode_tick"]
    assert dict(step.stats)["step_num"] == 7


def test_explicit_enable_outlives_a_profiler_session(clean_tracer,
                                                     monkeypatch):
    from bigdl_tpu.telemetry import tracer as tracer_mod

    tr = clean_tracer
    live = [False]
    monkeypatch.setattr(tracer_mod, "_profiler_live", lambda: live[0])
    tr.enable()
    live[0] = True
    assert tr.poll()
    live[0] = False
    assert tr.poll()  # enable() holds it on across the session's end
    tr.disable()
    assert not tr.poll()
    live[0] = True
    assert tr.poll()
    live[0] = False
    assert not tr.poll()


def test_off_path_training_loop_creates_no_span(clean_tracer,
                                                monkeypatch):
    """The guard that replaces the wall-clock overhead gates: with no
    session and the tracer off, 50 iterations create no Span and make
    at most one profiler-state check per loop turn (the training loop
    and the prefetch producer each poll once a turn)."""
    import threading

    from bigdl_tpu.telemetry import tracer as tracer_mod

    checks, made = {}, []

    def live():
        name = threading.current_thread().name
        checks[name] = checks.get(name, 0) + 1
        return False

    class CountedSpan(Span):
        def __init__(self, *a, **k):
            made.append(a[0])
            super().__init__(*a, **k)

    monkeypatch.setattr(tracer_mod, "_profiler_live", live)
    monkeypatch.setattr(tracer_mod, "Span", CountedSpan)
    rs = np.random.RandomState(0)
    x = rs.randn(64, 8).astype(np.float32)
    y = rs.randint(0, 4, 64)
    model = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
    engine = LocalOptimizer(model, DataSet.from_arrays(x, y, batch_size=16),
                            nn.ClassNLLCriterion(logits=True),
                            Trigger.max_iteration(50))
    engine.set_optim_method(SGD(0.1))
    engine.optimize()
    assert not made and len(clean_tracer) == 0
    loop = threading.current_thread().name
    assert checks[loop] == 50
    # the producer runs ahead by its queue's depth, plus the turn that
    # finds the source exhausted or the loop closed
    (producer,) = [n for n in checks if n != loop]
    assert "prefetch" in producer and checks[producer] <= 50 + 4


def _ring_span(name, t0, t1, cat="decode", args=None):
    return Span(name, cat, t0, t1, 1, "bigdl-decode-loop", None, args)


def _decode_ring():
    """Three turns of a decode loop, hand-made: 10 ms ticks of which
    8 ms wait; the second turn admits one request (2 ms of prefill, 1 ms
    of it waiting) and its tick waits 6 ms longer for the slot write."""
    spans, t = [], 0.0

    def put(name, dur, **args):
        nonlocal t
        spans.append(_ring_span(name, t, t + dur, args=args or None))
        t += dur

    for admitted, wait in ((0, 0.008), (1, 0.014), (0, 0.008)):
        put("loop/drain_queue", 0.0)
        t_admit = t
        put("loop/admit", 0.002 if admitted else 0.0, admitted=admitted)
        if admitted:
            spans.append(_ring_span("prefill_dispatch", t_admit,
                                    t_admit + 0.001))
            spans.append(_ring_span("prefill_wait", t_admit + 0.001,
                                    t_admit + 0.002))
        put("loop/tick_dispatch", 0.001)
        put("loop/tick_wait", wait)
        put("loop/retire", 0.001, active=2,
            gaps_ms=[10.0, 10.0] if not admitted else [18.0, 18.0])
    return spans


def _train_ring():
    return [Span("dispatch", "train", 0.0, 0.001, 1, "MainThread",
                 "step:1", None),
            Span("dispatch", "train", 0.2, 0.203, 1, "MainThread",
                 "step:2", None),
            Span("serve_dispatch", "serve", 0.0, 0.5, 2, "d", None, None),
            Span("data_stall", "train", 0.1, 0.2, 1, "MainThread",
                 "step:2", None)]


@pytest.mark.parametrize("metric,kind,ring,want", [
    # 3 turns of 10, 18, 10 ms; waits 8 + (14 + 1) + 8 = 31 of 38 ms
    ("loop_host_share.serve", "decode", _decode_ring,
     100.0 * (1.0 - 31.0 / 38.0)),
    # the admitting turn is 18 ms against a median of 10: 8 ms a request
    ("admit_cost_ms.serve", "decode", _decode_ring, 8.0),
    # gaps 10, 10, 18, 18, 10, 10: numpy's p95 interpolates to 18
    ("token_gap_p95_ms.serve", "decode", _decode_ring, 18.0),
    ("dispatch_ms.train", "train", _train_ring, 2.0),
])
def test_program_span_readers(clean_tracer, metric, kind, ring, want):
    """Each of the four readers on a hand-made ring returns the value
    worked out by hand, and nothing on an empty ring (which is what the
    parent commit, and a --trace 0 run, leave)."""
    from benchmark.run import read_metric

    run = {"kind": kind}
    assert read_metric(metric, run) is None
    tr = clean_tracer
    tr.enable()
    for s in ring():
        tr.add_span(s.name, s.cat, s.t0, s.t1, corr=s.corr, args=s.args)
    assert read_metric(metric, run) == pytest.approx(want)
    other = "train" if kind == "decode" else "decode"
    assert read_metric(metric, {"kind": other}) is None


def test_paged_attn_roofline_reads_the_traced_ticks_own_pages(clean_tracer):
    """``paged_attn_roofline.serve``: the kernel's time per call from the
    device trace against the K/V bytes of the pages the same ticks held
    (``loop/tick_dispatch``'s ``pages_held``); nothing where the kernel
    or the counter is missing (the parent commit has neither)."""
    from benchmark.run import read_metric

    model = {"hidden_size": 768, "filter_size": 3072, "num_layers": 12,
             "vocab_size": 50272}
    run = {"kind": "decode", "config": {"model": model},
           "traffic": {"page_size": 16},
           "peaks": {"hbm_bytes_per_s": 819e9},
           "trace": {"by_name": {"fusion.13": [2.0, 24]}}}
    read = functools.partial(read_metric, "paged_attn_roofline.serve")
    tr = clean_tracer
    tr.enable()
    for i, pages in enumerate((10, 30)):
        tr.add_span("loop/tick_dispatch", "decode", i, i + 0.001,
                    args={"pages_held": pages})
    assert read(run) is None                     # no kernel in the trace
    # 24 calls (2 ticks x 12 layers) of 10 us
    run["trace"]["by_name"]["paged_attn.7 tpu_custom_call"] = [240e-6, 24]
    # 20 pages of 16 tokens, K and V rows of 768 f32, at 819 GB/s
    least = 20 * 16 * 2 * 768 * 4 / 819e9
    assert read(run) == pytest.approx(100.0 * least / 10e-6)
    tr.clear()
    tr.add_span("loop/tick_dispatch", "decode", 0.0, 0.001)
    assert read(run) is None                     # no counter on the span
    assert read(dict(run, trace=None)) is None   # a --trace 0 run
    assert read(dict(run, kind="train")) is None


# -------------------------------------------------------------- watchdog
def test_watchdog_step_spike_and_report():
    wd = Watchdog(window=64, min_samples=10, spike_factor=3.0, log=None)
    for _ in range(30):
        wd.observe(_span("dispatch", dur=0.010))
    assert wd.counters["step_time_spikes"] == 0
    wd.observe(_span("dispatch", dur=0.200, corr="step:31"))
    assert wd.counters["step_time_spikes"] == 1
    rep = wd.report()
    assert rep["counters"]["step_time_spikes"] == 1
    (anom,) = [a for a in rep["anomalies"]
               if a["kind"] == "step_time_spikes"]
    assert "step:31" in anom["message"]
    assert "spike" in wd.log_line() or "step_time_spikes" in wd.log_line()


def test_watchdog_prefetch_starvation_window():
    wd = Watchdog(stall_ratio=0.5, stall_window=8, log=None)
    for _ in range(8):  # healthy: stall is 1% of step time
        wd.observe(_span("dispatch", dur=0.010))
        wd.observe(_span("data_stall", dur=0.0001))
    assert wd.counters["prefetch_starvation_windows"] == 0
    for _ in range(8):  # starved: the loop mostly waits on the producer
        wd.observe(_span("dispatch", dur=0.001))
        wd.observe(_span("data_stall", dur=0.009))
    assert wd.counters["prefetch_starvation_windows"] == 1


def test_watchdog_recompiles_queue_deadline_and_nan():
    wd = Watchdog(armed=False, log=None)
    wd.observe(_span("recompile", dur=0.5))  # warmup compile: not armed
    assert wd.counters["steady_state_recompiles"] == 0
    wd.arm()
    wd.observe(_span("recompile", dur=0.5))
    wd.observe(_span("queue_full", dur=0.0, corr="req:9"))
    wd.observe(_span("deadline_reject", dur=0.0, corr="req:10"))
    wd.observe(_span("loss_divergence", dur=0.0, corr="step:40",
                     args={"iteration": 40, "detected_at": 44,
                           "lag_steps": 4, "sync_window": 10}))
    assert wd.counters["steady_state_recompiles"] == 1
    assert wd.counters["queue_full"] == 1
    assert wd.counters["deadline_rejects"] == 1
    assert wd.counters["nan_windows"] == 1
    (nan,) = [a for a in wd.report()["anomalies"]
              if a["kind"] == "nan_windows"]
    # the anomaly names WHICH iteration diverged and how late
    assert "iteration 40" in nan["message"]
    assert "4 steps late" in nan["message"]


def test_watchdog_rolling_windows_are_bounded(monkeypatch):
    """ISSUE 8 satellite: the rolling-percentile deques clamp to the
    BIGDL_TPU_WATCHDOG_MAX_WINDOW knob so a long-lived federated
    watchdog can't grow its per-span history without bound."""
    from bigdl_tpu.telemetry.watchdog import (
        DEFAULT_MAX_WINDOW,
        _env_max_window,
    )

    # default cap applies even to an absurd ctor request
    wd = Watchdog(window=10 ** 9, stall_window=10 ** 9, log=None)
    assert wd._window == DEFAULT_MAX_WINDOW
    assert wd._stall_window == DEFAULT_MAX_WINDOW
    for d in wd._durations.values():
        assert d.maxlen == DEFAULT_MAX_WINDOW

    monkeypatch.setenv("BIGDL_TPU_WATCHDOG_MAX_WINDOW", "64")
    assert _env_max_window() == 64
    wd = Watchdog(window=10 ** 6, stall_window=10 ** 6, log=None)
    assert wd._window == 64 and wd._stall_window == 64
    for _ in range(500):  # history stays bounded under load
        wd.observe(_span("dispatch", dur=0.001))
    assert all(len(d) <= 64 for d in wd._durations.values())
    # smaller-than-cap requests pass through unclamped
    wd = Watchdog(window=16, log=None)
    assert wd._window == 16

    monkeypatch.setenv("BIGDL_TPU_WATCHDOG_MAX_WINDOW", "1")
    assert _env_max_window() == 8  # floor: percentiles need samples
    monkeypatch.setenv("BIGDL_TPU_WATCHDOG_MAX_WINDOW", "junk")
    assert _env_max_window() == DEFAULT_MAX_WINDOW


def test_watchdog_subscribes_to_tracer(clean_tracer):
    tr = clean_tracer
    tr.enable()
    with Watchdog(log=None) as wd:
        wd.attach(tr)
        tr.instant("queue_full", "serve", corr="req:1")
        assert wd.counters["queue_full"] == 1
    tr.instant("queue_full", "serve", corr="req:2")  # detached: ignored
    assert wd.counters["queue_full"] == 1


def test_watchdog_counters_tensorboard_round_trip(tmp_path):
    wd = Watchdog(log=None)
    wd.observe(_span("queue_full", dur=0.0))
    wd.observe(_span("loss_divergence", dur=0.0, args={}))
    summary = TelemetrySummary(str(tmp_path), "app")
    written = wd.write_summary(summary, step=5)
    summary.close()
    assert written["queue_full"] == 1 and written["nan_windows"] == 1
    assert summary.read_scalar("Watchdog/QueueFull") == [(5, 1.0)]
    assert summary.read_scalar("Watchdog/NanWindows") == [(5, 1.0)]
    assert summary.read_scalar("Watchdog/SteadyStateRecompiles") == \
        [(5, 0.0)]


def test_divergence_event_feeds_watchdog(clean_tracer, tmp_path):
    """The async loop's deferred-NaN drain emits the loss_divergence
    instant naming the diverged iteration (<= 1 window late)."""
    rs = np.random.RandomState(0)
    x = rs.randn(64, 8).astype(np.float32)
    y = rs.randint(0, 4, 64)
    model = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
    ds = DataSet.from_arrays(x, y, batch_size=16)
    engine = LocalOptimizer(model, ds, nn.ClassNLLCriterion(logits=True),
                            Trigger.max_iteration(6))
    engine.set_optim_method(SGD(float("nan")))  # guaranteed divergence
    telemetry.enable()
    wd = Watchdog(log=None).attach()
    with pytest.raises(FloatingPointError):
        engine.optimize()
    wd.close()
    telemetry.disable()
    assert wd.counters["nan_windows"] >= 1
    (ev,) = [s for s in telemetry.get_tracer().spans()
             if s.name == "loss_divergence"][:1]
    assert ev.args["detected_at"] - ev.args["iteration"] <= \
        engine.sync_window


# ------------------------------------------------- periodic metrics line
class _ListHandler(logging.Handler):
    """Direct handler on the package logger: ``bigdl_tpu`` sets
    propagate=False, so caplog's root handler never sees its lines."""

    def __init__(self):
        super().__init__()
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


def test_periodic_log_line_fires_and_close_stops():
    model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 2))
    var = model.init(jax.random.PRNGKey(0))
    handler = _ListHandler()
    lg = logging.getLogger("bigdl_tpu.serving")
    lg.addHandler(handler)
    engine = None
    try:
        engine = ServingEngine(model, var, buckets=[(4, 4)],
                               batch_sizes=(1, 4),
                               metrics_log_every_s=0.05)
        engine.predict(np.ones((3, 4), np.float32))
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if any("serving:" in ln for ln in handler.lines):
                break
            time.sleep(0.02)
        fired = [ln for ln in handler.lines if "serving:" in ln]
        assert fired, "periodic metrics line never fired"
        assert engine._periodic.running
        engine.close()
        assert not engine._periodic.running
        n_after_close = len([ln for ln in handler.lines
                             if "serving:" in ln])
        time.sleep(0.2)
        assert len([ln for ln in handler.lines
                    if "serving:" in ln]) == n_after_close, \
            "log cadence must stop at close()"
    finally:
        if engine is not None:
            engine.close()
        lg.removeHandler(handler)


def test_periodic_logger_env_and_default_off(monkeypatch):
    assert metrics_log_every_s() == 0.0  # default: off
    monkeypatch.setenv("BIGDL_TPU_METRICS_EVERY_S", "2.5")
    assert metrics_log_every_s() == 2.5
    monkeypatch.setenv("BIGDL_TPU_METRICS_EVERY_S", "junk")
    assert metrics_log_every_s() == 0.0
    lines = []
    lg = PeriodicMetricsLogger(lambda: "line", every_s=0.02,
                               sink=lines.append).start()
    time.sleep(0.2)
    lg.close()
    assert lines and not lg.running
    n = len(lines)
    time.sleep(0.1)
    assert len(lines) == n
    # every_s=0 never starts a thread
    off = PeriodicMetricsLogger(lambda: "x", every_s=0).start()
    assert not off.running
    off.close()


# ------------------------------------------------------ exporters / dump
def test_metrics_jsonl_round_trip(tmp_path):
    m = Metrics()
    with m.time("compute"):
        pass
    m.inc("completed", 3)
    rec = telemetry.metrics_record("unit", m, extra={"note": "x"})
    assert rec["phases"]["compute"]["count"] == 1
    assert rec["counters"]["completed"] == 3 and rec["note"] == "x"
    path = str(tmp_path / "m.jsonl")
    telemetry.write_metrics_jsonl(path, [rec])
    telemetry.write_metrics_jsonl(path, [rec])  # append-safe
    rows = telemetry.read_metrics_jsonl(path)
    assert len(rows) == 2 and rows[0]["record"] == "unit"


def test_write_scalars_and_profiling_trace_overlay(clean_tracer,
                                                   tmp_path):
    from bigdl_tpu.utils import profiling

    summary = TelemetrySummary(str(tmp_path), "app")
    telemetry.write_scalars(summary, {"A/B": 2.0}, step=3)
    summary.close()
    assert summary.read_scalar("A/B") == [(3, 2.0)]

    # one timeline, one file: the program's spans are in the profiler's
    # own trace, on plane /host:CPU — no host_trace.json beside it
    logdir = str(tmp_path / "prof")
    tr = clean_tracer
    with profiling.trace(logdir):
        assert tr.poll()  # the session is the switch
        m = Metrics()
        with m.time("compute"):
            pass
    assert not tr.poll()  # off again once the session ended
    assert not os.path.exists(os.path.join(logdir, "host_trace.json"))
    assert [ev for ev in _host_plane_events(logdir)
            if ev.name == "compute"]


# --------------------------------------------------- get_times_by_type
def test_get_times_by_type_reference_parity():
    from bigdl_tpu.utils.profiling import (
        format_times_by_type,
        get_times_by_type,
        get_times_grouped,
    )

    model = nn.Sequential(nn.Linear(6, 6), nn.Tanh(), nn.Linear(6, 6),
                          nn.Tanh(), nn.Linear(6, 3))
    var = model.init(jax.random.PRNGKey(0))
    x = np.ones((2, 6), np.float32)
    rows = get_times_by_type(model, var["params"], var["state"], x)
    assert rows["Linear"]["count"] == 3 and rows["Tanh"]["count"] == 2
    grouped = get_times_grouped(model, var["params"], var["state"], x)
    for typ, r in rows.items():
        assert r["fwd_total_s"] > 0
        assert r["fwd_mean_s"] == pytest.approx(
            r["fwd_total_s"] / r["count"])
        assert r["bwd_mean_s"] == pytest.approx(
            r["bwd_total_s"] / r["count"])
        assert set(grouped) == set(rows)
    table = format_times_by_type(rows)
    assert "Linear" in table and "fwd/ea" in table


# ----------------------------------------------------- the overhead gate
def test_telemetry_ab_overhead_under_3_percent(clean_tracer):
    """bench.py --telemetry-ab runs with the tracer toggled in session
    and really records.  The on/off ratio it reports is a wall-clock
    number from XLA:CPU beside the other test workers: printed by
    bench.py, never a gate (the off path is guarded by counts, in
    test_off_path_training_loop_creates_no_span)."""
    import bench

    rec = bench.telemetry_ab()
    # the traced session really recorded spans
    assert rec["detail"]["spans_in_ring"] > 0


def test_cluster_shipping_overhead_under_3_percent(clean_tracer):
    """ISSUE 8 acceptance: the same gate with a live cluster
    TelemetryShipper subscribed for the whole session (bench.py
    --telemetry-ab --ship): the per-span subscriber callback and the
    background segment flushes really ran (counts, not the ratio).
    Reduced sizes keep the tier-1 wall bounded."""
    import bench

    rec = bench.telemetry_ab(train_steps=160, n_chunks=48, ship=True)
    d = rec["detail"]
    assert d["ship"] and d["spans_in_ring"] > 0
    # the shipper really flushed segments during the session (close()
    # final-ships, so at least one is always on disk before cleanup)
    assert d["ship_segments"] >= 1


def test_xray_overhead_under_3_percent(clean_tracer):
    """ISSUE 9 acceptance: the same gate with the Program X-ray armed
    (bench.py --telemetry-ab --xray) — per-call registry bookkeeping on
    every train/serve dispatch plus HBM ledger samples at a forced
    aggressive cadence really ran (counts, not the ratio)."""
    import bench

    rec = bench.telemetry_ab(train_steps=160, n_chunks=48, xray=True)
    d = rec["detail"]
    assert d["xray"] and d["spans_in_ring"] > 0
    # the registry really tracked compiled programs and the ledger
    # really sampled during the traced arm
    assert d["xray_programs"] >= 1
    assert d["hbm_samples"] >= 1


def test_flight_overhead_under_3_percent(clean_tracer):
    """ISSUE 12 acceptance: the same gate with the live ops plane up —
    a port-0 debug server scraping the engine, an armed flight
    recorder observing every span, and one forced blackbox dump
    mid-run (bench.py --telemetry-ab --flight); counts, not the ratio."""
    import bench

    rec = bench.telemetry_ab(train_steps=160, n_chunks=48, flight=True)
    d = rec["detail"]
    assert d["flight"] and d["spans_in_ring"] > 0
    # the plane was really live: one forced bundle landed and the
    # mid-session HTTP scrape returned Prometheus text
    assert d["flight_bundles"] >= 1
    assert d["flight_scrape_bytes"] > 0


def test_request_xray_overhead_under_3_percent(clean_tracer):
    """ISSUE 15 acceptance: the same gate with the Request X-ray live
    (bench.py --telemetry-ab --requests) — the serving engine's
    per-request budget ledger and exemplar reservoir riding every
    submit/dispatch/deliver, plus the workload recorder armed for the
    traced chunks, really ran (counts, not the ratio)."""
    import bench

    rec = bench.telemetry_ab(train_steps=160, n_chunks=48, requests=True)
    d = rec["detail"]
    assert d["requests"] and d["spans_in_ring"] > 0
    # the plane was really live on the gated path: the ledger closed
    # the traced chunks' requests, the reservoir saw every close, and
    # the recorder captured the last traced chunk's submits
    assert d["request_xray"]["n_closed"] > 0
    assert d["request_exemplars"]["offered"] > 0
    assert d["requests_recorded"] >= 1
