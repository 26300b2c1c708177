"""Request X-ray tests (ISSUE 15 tentpole; docs/observability.md
§Request X-ray):

* the :class:`RequestLedger` partition is *exact by construction* —
  the per-phase budget sums to the measured end-to-end latency (the
  5% acceptance criterion is met with float-precision margin);
* a forced deadline miss carries a non-empty attribution naming the
  dominant phase, both on the exception object and in its message;
* :func:`assemble_request_trees` joins ``req:``/``rids``/``tick:``
  correlated spans into one connected tree per request, for live
  ``Span`` objects and shipped segment dicts alike — and through
  :meth:`ClusterAggregator.request_trees` a request that crossed
  hosts assembles into ONE tree with host-qualified threads;
* the :class:`ExemplarReservoir` retains p99+ span trees, evicts the
  fastest when full, and its capture renders in Perfetto as one
  connected ``request_flow`` arrow chain crossing threads;
* end to end on a live :class:`DecodeEngine`: per-request budgets in
  ``recent()``, the ``xray:`` log line, ``/statusz`` summaries, and
  the ``/tracez`` exemplar merge.
"""
import json
import urllib.request

import jax
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.serving import DecodeEngine
from bigdl_tpu.serving.engine import DeadlineExceededError
from bigdl_tpu.telemetry import requests as rx
from bigdl_tpu.telemetry.export import chrome_trace
from bigdl_tpu.telemetry.tracer import (
    Span,
    Tracer,
    enabled as tracing,
    get_tracer,
)

VOCAB = 24


def _lm(vocab=VOCAB, hidden=32, heads=2, filt=64, layers=2):
    return nn.Transformer(vocab_size=vocab, hidden_size=hidden,
                          num_heads=heads, filter_size=filt,
                          num_layers=layers, dropout=0.0, causal=True)


@pytest.fixture(scope="module")
def lm():
    model = _lm()
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model, var, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("prompt_buckets", (4, 8))
    kw.setdefault("prefill_batch_sizes", (1, 2))
    kw.setdefault("eos_id", None)
    return DecodeEngine(model, var, **kw)


def _att(rid, latency, phase="device", t0=0.0):
    """Hand-built Attribution with one dominant phase."""
    return rx.Attribution(rid, t0, t0 + latency, {phase: latency}, {})


# ------------------------------------------------------------- ledger
def test_ledger_partition_sums_exactly_to_latency():
    """The acceptance criterion asks for attribution within 5% of the
    end-to-end latency; the ledger is exact by construction — every
    transition charges ``now - t_last`` to the phase the request was
    in, so the phase sums ARE the latency to float precision."""
    tr = Tracer(capacity=16)
    tr.enable()
    led = rx.RequestLedger(tracer=tr)
    led.open(7, now=100.0)
    led.to(7, rx.PHASE_PAD, now=100.25)       # 0.25s in queue
    led.to(7, rx.PHASE_PREFILL, now=100.375)  # 0.125s padding
    led.to(7, rx.PHASE_RESIDENT, now=100.5)   # 0.125s prefill
    led.note(7, "ticks", 5)
    led.to(7, rx.PHASE_DELIVER, now=100.9)    # 0.4s resident
    att = led.close(7, now=101.0)             # 0.1s delivering
    assert att is not None and att.rid == 7
    assert att.latency == pytest.approx(1.0, rel=1e-9)
    assert sum(att.phases.values()) == pytest.approx(att.latency,
                                                     rel=1e-9)
    assert att.dominant() == (rx.PHASE_RESIDENT, pytest.approx(0.4))
    d = att.as_dict()
    assert d["phases_ms"][rx.PHASE_QUEUE] == pytest.approx(250.0)
    assert d["counters"] == {"ticks": 5}
    assert d["dominant"] == rx.PHASE_RESIDENT
    assert f"dominant={rx.PHASE_RESIDENT}" in att.summary()


def test_ledger_concurrent_requests_each_partition_exact():
    """to_many charges the same wall interval to every resident
    request; each request's own partition still sums exactly."""
    tr = Tracer(capacity=16)
    tr.enable()
    led = rx.RequestLedger(tracer=tr)
    for rid in (1, 2):
        led.open(rid, now=10.0)
    led.to_many((1, 2), rx.PHASE_RESIDENT, now=10.5)
    led.to_many((1, 2), rx.PHASE_SAMPLE, now=11.0)
    a1 = led.close(1, now=11.25)
    led.to(2, rx.PHASE_PAGE_STALL, now=11.5)
    a2 = led.close(2, now=12.0)
    assert sum(a1.phases.values()) == pytest.approx(a1.latency)
    assert sum(a2.phases.values()) == pytest.approx(a2.latency)
    assert a2.phases[rx.PHASE_PAGE_STALL] == pytest.approx(0.5)
    s = led.summary()
    assert s["n_closed"] == 2 and s["n_open"] == 0
    assert led.log_line().startswith("xray: n=2")


def test_ledger_follows_tracer_and_drop():
    tr = Tracer(capacity=16)  # disabled
    led = rx.RequestLedger(tracer=tr)
    assert not led.enabled
    led.open(1, now=0.0)
    assert led.close(1, now=1.0) is None  # dark plane: no accounting
    # the tracer's state is the only switch, followed dynamically
    tr.enable()
    assert led.enabled
    led.open(2, now=0.0)
    assert led.close(1, now=2.0) is None  # opened while dark: unknown
    tr.disable()
    assert not led.enabled
    # a request still open when the tracer goes off is dropped, not
    # left in the ledger for close() to skip
    assert led.open_count() == 0
    tr.enable()
    assert led.close(2, now=1.0) is None
    # drop: forget without accounting (queue_full rejections)
    led.open(3, now=0.0)
    led.drop(3)
    assert led.close(3, now=1.0) is None
    assert led.summary()["n_closed"] == 0


def test_ledger_follows_a_profiler_session(tmp_path):
    """No env: attribution is on for exactly the profiler session."""
    import jax

    tr = Tracer(capacity=16)
    led = rx.RequestLedger(tracer=tr)
    led.open(1, now=0.0)
    assert led.open_count() == 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tr.poll() and led.enabled
        led.open(2, now=0.0)
        led.open(3, now=0.0)
        att = led.close(2, now=0.5)
        assert att is not None and att.latency == pytest.approx(0.5)
    finally:
        jax.profiler.stop_trace()
    assert not tr.poll() and not led.enabled
    assert led.close(3, now=1.0) is None and led.open_count() == 0


# ------------------------------------------------------- tree assembly
def _span(name, t0, t1, corr, tid=1, thread="MainThread", args=None,
          cat="serve"):
    return Span(name, cat, t0, t1, tid, thread, corr, args)


def test_assemble_request_trees_joins_req_rids_and_ticks():
    spans = [
        _span("enqueue", 0.0, 0.0, "req:1"),
        _span("deliver", 0.9, 1.0, "req:1", tid=2, thread="dispatch"),
        _span("dispatch_batch", 0.1, 0.1, "batch:0", tid=2,
              thread="dispatch", args={"rids": [1]}),
        _span("tick", 0.4, 0.5, "tick:7", tid=2, thread="dispatch"),
        _span("tick", 5.0, 5.1, "tick:9", tid=2, thread="dispatch"),
        _span("unrelated", 0.2, 0.3, "step:3", tid=3, thread="train"),
    ]
    trees = rx.assemble_request_trees(spans)
    assert set(trees) == {1}
    t = trees[1]
    names = sorted(s.name for s in t["spans"])
    # the out-of-window tick:9 stays out; step:3 overlaps so joins
    assert names == ["deliver", "dispatch_batch", "enqueue", "tick",
                     "unrelated"]
    assert t["t0"] == 0.0 and t["t1"] == 1.0
    assert t["threads"] == ["MainThread", "dispatch", "train"]


def test_assemble_request_trees_accepts_shipped_dicts():
    """The cross-host form: the aggregator feeds plain dicts."""
    spans = [
        {"name": "submit", "t0": 0.0, "t1": 0.01, "corr": "req:4",
         "thread": "h0:MainThread", "args": None},
        {"name": "tick", "t0": 0.005, "t1": 0.008, "corr": "tick:1",
         "thread": "h1:decode", "args": None},
        {"name": "dispatch_batch", "t0": 0.002, "t1": 0.002,
         "corr": "batch:5", "thread": "h1:decode",
         "args": {"rids": [4, 9]}},
    ]
    trees = rx.assemble_request_trees(spans)
    assert set(trees) == {4}
    assert len(trees[4]["spans"]) == 3
    assert trees[4]["threads"] == ["h0:MainThread", "h1:decode"]


def test_cluster_aggregator_assembles_one_tree_across_hosts(tmp_path):
    """A request whose life crossed hosts (router submit on h0, decode
    ticks on h1, h1's clock 0.5s ahead) assembles into ONE connected
    tree on the shared timeline with host-qualified threads."""
    import os
    import time

    from bigdl_tpu.telemetry.cluster import ClusterAggregator

    now = time.time()

    def seg(host, offset, spans):
        lines = [json.dumps({
            "record": "segment_header", "host": host, "gen": 1,
            "pid": 1, "seq": 0, "t": now, "clock_offset_s": offset,
            "n_spans": len(spans), "n_events": 0})]
        for name, t0, t1, corr, args in spans:
            lines.append(json.dumps({
                "record": "span", "name": name, "cat": "serve",
                "t0": t0, "t1": t1, "tid": 1, "thread": "MainThread",
                "corr": corr, "args": args, "gen": 1}))
        p = os.path.join(str(tmp_path), f"seg-{host}-1-000000.jsonl")
        with open(p, "w") as f:
            f.write("\n".join(lines) + "\n")

    seg("h0", 0.0, [
        ("submit", now, now + 0.001, "req:11", None),
        ("deliver", now + 0.8, now + 0.9, "req:11", None)])
    seg("h1", 0.5, [  # h1 clock runs 0.5s ahead of shared time
        ("dispatch_batch", now + 0.6, now + 0.6, "batch:0",
         {"rids": [11]}),
        ("tick", now + 0.7, now + 0.75, "tick:3", None)])

    trees = ClusterAggregator(str(tmp_path)).load().request_trees()
    assert set(trees) == {11}
    t = trees[11]
    assert len(t["spans"]) == 4  # submit+deliver+batch+tick: ONE tree
    assert t["threads"] == ["h0:MainThread", "h1:MainThread"]
    # offset correction pulled h1's spans back onto the shared
    # timeline, inside the request's [t0, t1] window
    assert t["t0"] == pytest.approx(now, abs=1e-6)
    assert t["t1"] == pytest.approx(now + 0.9, abs=1e-6)
    batch = next(s for s in t["spans"]
                 if s["name"] == "dispatch_batch")
    assert batch["t0"] == pytest.approx(now + 0.1, abs=1e-6)


# ------------------------------------------------------ tail exemplars
def test_exemplar_reservoir_keeps_slowest_and_evicts():
    tr = Tracer(capacity=64)
    tr.enable()
    res = rx.ExemplarReservoir(capacity=2, min_samples=5, tracer=tr)
    assert res.enabled
    for i in range(4):  # below min_samples: never captures
        assert not res.offer(_att(i, 0.01 + 0.001 * i))
    tr.add_span("work", "serve", 0.0, 0.05, corr="req:50")
    assert res.offer(_att(50, 0.05))   # window max -> p99 capture
    tr.add_span("work", "serve", 0.0, 1.0, corr="req:51")
    assert res.offer(_att(51, 1.0))
    tr.add_span("work", "serve", 0.0, 2.0, corr="req:52")
    assert res.offer(_att(52, 2.0))    # evicts the fastest retained
    kept = res.exemplars()
    assert [e["rid"] for e in kept] == [52, 51]  # slowest first
    s = res.summary()
    assert s["kept"] == 2 and s["capacity"] == 2 and s["captured"] == 3
    assert s["slowest_ms"] == pytest.approx(2000.0)
    # a fast request never lands in the tail
    assert not res.offer(_att(53, 0.011))
    # the /tracez merge feed: synthesized roots + captured spans
    names = {s.name for s in res.spans()}
    assert "request:52" in names and "work" in names
    blob = json.loads(json.dumps(res.as_blob()))  # JSON-able
    assert blob["exemplars"][0]["rid"] == 52
    assert blob["exemplars"][0]["attribution"]["dominant"] == "device"


def test_exemplar_capacity_knob(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_EXEMPLARS", "0")
    res = rx.ExemplarReservoir(tracer=Tracer(capacity=8))
    assert not res.enabled
    assert not res.offer(_att(1, 9.9))
    monkeypatch.setenv("BIGDL_TPU_EXEMPLARS", "3")
    assert rx.exemplar_capacity() == 3
    monkeypatch.setenv("BIGDL_TPU_EXEMPLARS", "junk")
    assert rx.exemplar_capacity() == 8


def test_exemplar_renders_as_connected_perfetto_flow():
    """The acceptance criterion: a captured exemplar renders in
    Perfetto as ONE connected span tree crossing threads — the
    ``request_flow`` arrow chain shares one id, starts with ``s``,
    ends with ``f``/``bp=e``, and spans >= 2 tids."""
    tr = Tracer(capacity=64)
    tr.enable()
    e = tr.epoch
    spans = [
        _span("enqueue", e + 0.1, e + 0.1, "req:9", tid=11,
              thread="client"),
        _span("prefill", e + 0.2, e + 0.4, "req:9", tid=22,
              thread="decode-dispatch"),
        _span("deliver", e + 0.8, e + 0.9, "req:9", tid=33,
              thread="drain"),
    ]
    blob = chrome_trace(tr, spans=spans)
    flows = [ev for ev in blob["traceEvents"]
             if ev.get("cat") == "request_flow"]
    assert len(flows) == 3
    assert {ev["name"] for ev in flows} == {"req:9"}
    assert len({ev["id"] for ev in flows}) == 1  # one connected chain
    assert [ev["ph"] for ev in flows] == ["s", "t", "f"]
    assert flows[-1]["bp"] == "e"
    assert len({ev["tid"] for ev in flows}) == 3  # crosses threads


# ------------------------------------------------- engine end to end
def test_engine_deadline_miss_names_dominant_phase(lm):
    """A forced deadline miss must carry a non-empty attribution and
    name the dominant phase in the error message."""
    model, var = lm
    with tracing():
        with _engine(model, var) as eng:
            fut = eng.submit([1, 2], 4, deadline_ms=0.0)
            with pytest.raises(DeadlineExceededError) as ei:
                fut.result(60)
    err = ei.value
    assert err.attribution is not None
    assert err.attribution.phases  # non-empty budget
    dom, dom_s = err.attribution.dominant()
    assert dom in rx.PHASES and dom_s >= 0.0
    assert "[dominant:" in str(err) and dom in str(err)


def test_engine_xray_statusz_and_tracez_end_to_end(lm):
    """Live DecodeEngine under tracing: every closed request's budget
    partition is exact; the xray rollup reaches the log line,
    ``/statusz``, and the ``/tracez`` exemplar merge."""
    from bigdl_tpu.telemetry.debug_server import DebugServer, set_global

    model, var = lm
    rs = np.random.RandomState(0)
    srv = DebugServer(port=0).start()
    set_global(srv)
    try:
        with tracing():
            with _engine(model, var) as eng:
                # default reservoir needs >= 20 closed samples before
                # the p99 gate opens; 24 guarantees a capture
                futs = [eng.submit(rs.randint(0, VOCAB, (3 + i % 5,)),
                                   2 + i % 4) for i in range(24)]
                for f in futs:
                    f.result(120)
                assert eng.xray.enabled
                recents = eng.xray.recent(24)
                assert len(recents) == 24
                for att in recents:
                    assert sum(att.phases.values()) == pytest.approx(
                        att.latency, rel=1e-6)
                    assert att.phases.get(rx.PHASE_DELIVER, -1) >= 0
                s = eng.xray.summary()
                assert s["n_closed"] == 24 and s["phases_ms"]
                assert eng.xray.log_line().startswith("xray: n=24")
                ex = eng.exemplars.summary()
                assert ex["offered"] == 24 and ex["captured"] >= 1

                with urllib.request.urlopen(
                        srv.local_url("/statusz"), timeout=10) as r:
                    status = json.loads(r.read())
                (det,) = [e["detail"] for e in status["engines"]
                          if e["name"] == "decode"]
                assert det["xray"]["n_closed"] == 24
                assert det["exemplars"]["captured"] >= 1

                with urllib.request.urlopen(
                        srv.local_url("/tracez?secs=0"), timeout=10) \
                        as r:
                    trace = json.loads(r.read())
                roots = [ev for ev in trace["traceEvents"]
                         if ev.get("cat") == "request"
                         and ev.get("name", "").startswith("request:")]
                assert roots  # retained exemplar trees merged in
                flows = [ev for ev in trace["traceEvents"]
                         if ev.get("cat") == "request_flow"]
                assert flows  # and they arrive as connected flows
    finally:
        srv.close()
