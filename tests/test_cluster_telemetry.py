"""Cluster observability plane tests (ISSUE 8 tentpole;
docs/observability.md §Cluster telemetry):

* :class:`TelemetryShipper` — atomic newline-JSON segments tagged with
  host/generation/clock-offset, span wall-clock conversion, elastic
  events, metrics snapshots, cost-table records, events-only mode;
* clock alignment — offset sampling through the rendezvous-style
  callback, median estimate, the ``BIGDL_TPU_CLOCK_SYNC=0`` kill
  switch;
* :class:`ClusterAggregator` — one merged Perfetto trace with a
  process lane per host and offset-corrected timelines, cluster
  percentiles, world throughput, straggler skew;
* :class:`FederatedWatchdog` — stalled/straggler/saturated flags via
  ``Watchdog.peer_event`` on the *transition* only;
* the cost model — ``stamp_jitted`` flops/bytes on real programs, MFU
  math, ``CostTable`` persist/load, the ``BIGDL_TPU_COST_DISABLE``
  kill switch;
* ``tools/cluster_top.py`` — one-shot ``--json`` rollup, exit codes.

Everything here is single-process and CPU-fast (tier-1); the
two-process elastic run lives in tests/test_multihost.py (slow).
"""
import glob
import json
import os
import time

import jax
import numpy as np
import pytest

from bigdl_tpu.telemetry import costmodel
from bigdl_tpu.telemetry.cluster import (
    EVENT_GEN_BUMP,
    EVENT_PEER_DEAD,
    SEGMENT_GLOB,
    ClusterAggregator,
    FederatedWatchdog,
    TelemetryShipper,
    clock_sync_enabled,
    ship_every_s,
    telemetry_dir,
)
from bigdl_tpu.telemetry.tracer import Tracer
from bigdl_tpu.telemetry.watchdog import Watchdog


# ---------------------------------------------------------------- helpers
def _wall_skew() -> float:
    """perf_counter -> wall-clock skew (what the shipper applies)."""
    return time.time() - time.perf_counter()


def _ship_spans(run_dir, host, spans, *, offset=0.0, gen=1,
                metrics=None, events=()):
    """One real shipper flush: ``spans`` is [(name, wall_t0, dur,
    corr)] — wall-clock times, converted back to the tracer's
    perf_counter domain so the shipper's skew correction is exercised,
    not bypassed."""
    tr = Tracer(capacity=1024)
    tr.enable()
    shipper = TelemetryShipper(
        str(run_dir), host, gen=gen, tracer=tr, interval_s=0,
        clock_offset_fn=(lambda: offset) if offset else None)
    if metrics is not None:
        shipper.add_metrics("test", metrics)
    skew = _wall_skew()
    for name, t0, dur, corr in spans:
        tr.add_span(name, "train", t0 - skew, t0 + dur - skew, corr=corr)
    for kind, args in events:
        shipper.event(kind, **args)
    path = shipper.ship_now()
    shipper.close()
    return path


def _write_seg(run_dir, host, seq, t_header, *, spans=(), metrics=None,
               gen=1, offset=0.0):
    """Handcrafted segment (the aggregator reads files, not objects) —
    lets a test backdate a host's liveness beacon."""
    lines = [json.dumps({
        "record": "segment_header", "host": host, "gen": gen, "pid": 1,
        "seq": seq, "t": t_header, "clock_offset_s": offset,
        "n_spans": len(spans), "n_events": 0})]
    for name, t0, dur, corr in spans:
        lines.append(json.dumps({
            "record": "span", "name": name, "cat": "train", "t0": t0,
            "t1": t0 + dur, "tid": 1, "thread": "MainThread",
            "corr": corr, "args": None, "gen": gen}))
    if metrics is not None:
        lines.append(json.dumps({
            "record": "metrics", "name": "test", "host": host,
            "gen": gen, "t": t_header, "snapshot": metrics}))
    path = os.path.join(str(run_dir), f"seg-{host}-1-{seq:06d}.jsonl")
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    return path


# ---------------------------------------------------------------- shipper
def test_shipper_segments_atomic_and_tagged(tmp_path):
    tr = Tracer(capacity=64)
    tr.enable()
    shipper = TelemetryShipper(str(tmp_path), "h0", gen=3, tracer=tr,
                               interval_s=0)
    t0 = time.perf_counter()
    tr.add_span("dispatch", "train", t0, t0 + 0.01, corr="step:1")
    tr.instant("queue_full", "serve", corr="req:9")
    shipper.event(EVENT_PEER_DEAD, peer="h1", age_s=4.2)
    p1 = shipper.ship_now()
    p2 = shipper.ship_now()  # second flush: new segment, bumped seq

    segs = sorted(glob.glob(os.path.join(str(tmp_path), SEGMENT_GLOB)))
    assert [os.path.basename(p1), os.path.basename(p2)] == \
        [os.path.basename(s) for s in segs]
    # atomic discipline: no torn temp files left behind
    assert not glob.glob(os.path.join(str(tmp_path), "*.part"))

    with open(p1) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    header = recs[0]
    assert header["record"] == "segment_header"
    assert header["host"] == "h0" and header["gen"] == 3
    assert header["seq"] == 0 and header["n_spans"] == 2
    spans = [r for r in recs if r["record"] == "span"]
    assert {s["name"] for s in spans} == {"dispatch", "queue_full"}
    d = next(s for s in spans if s["name"] == "dispatch")
    # perf_counter stamps were converted to wall clock
    assert abs(d["t0"] - time.time()) < 60.0
    assert d["t1"] - d["t0"] == pytest.approx(0.01, abs=1e-6)
    assert d["corr"] == "step:1" and d["gen"] == 3
    (ev,) = [r for r in recs if r["record"] == "event"]
    assert ev["kind"] == EVENT_PEER_DEAD and ev["args"]["peer"] == "h1"

    with open(p2) as f:
        header2 = json.loads(f.readline())
    assert header2["seq"] == 1
    assert header2["n_spans"] == 0  # drained by the first flush
    shipper.set_generation(4)
    with open(shipper.ship_now()) as f:
        assert json.loads(f.readline())["gen"] == 4
    shipper.close()


def test_shipper_events_only_and_dict_metrics(tmp_path):
    """tracer=None: the agent-side shipper (events/metrics only) never
    touches the global tracer; dict sources pass through verbatim."""
    shipper = TelemetryShipper(str(tmp_path), "agent0", tracer=None,
                               interval_s=0)
    shipper.add_metrics("serve", {"queue_depth": 7, "occupancy": 0.5})
    shipper.event(EVENT_GEN_BUMP, gen=2, members=["h0", "h1"])
    with open(shipper.ship_now()) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    kinds = [r["record"] for r in recs]
    assert kinds[0] == "segment_header" and "span" not in kinds
    (ev,) = [r for r in recs if r["record"] == "event"]
    assert ev["kind"] == EVENT_GEN_BUMP and ev["args"]["gen"] == 2
    (m,) = [r for r in recs if r["record"] == "metrics"]
    assert m["snapshot"] == {"queue_depth": 7, "occupancy": 0.5}
    shipper.close()


def test_shipper_clock_offset_median_and_kill_switch(tmp_path,
                                                     monkeypatch):
    samples = iter([0.4, 0.6, 0.5])
    shipper = TelemetryShipper(str(tmp_path), "h0", tracer=None,
                               interval_s=0,
                               clock_offset_fn=lambda: next(samples))
    for _ in range(3):
        path = shipper.ship_now()
    with open(path) as f:
        assert json.loads(f.readline())["clock_offset_s"] == \
            pytest.approx(0.5)  # median of the samples so far
    shipper.close()

    monkeypatch.setenv("BIGDL_TPU_CLOCK_SYNC", "0")
    assert not clock_sync_enabled()
    off = TelemetryShipper(str(tmp_path), "h1", tracer=None,
                           interval_s=0,
                           clock_offset_fn=lambda: 9.9)
    with open(off.ship_now()) as f:
        assert json.loads(f.readline())["clock_offset_s"] == 0.0
    off.close()


def test_env_knob_defaults(monkeypatch):
    monkeypatch.delenv("BIGDL_TPU_TELEMETRY_DIR", raising=False)
    assert telemetry_dir() is None
    assert telemetry_dir("/fallback") == "/fallback"
    monkeypatch.setenv("BIGDL_TPU_TELEMETRY_DIR", "/run/t")
    assert telemetry_dir() == "/run/t"
    assert ship_every_s() == 2.0
    monkeypatch.setenv("BIGDL_TPU_SHIP_EVERY_S", "0.25")
    assert ship_every_s() == 0.25
    monkeypatch.setenv("BIGDL_TPU_SHIP_EVERY_S", "junk")
    assert ship_every_s() == 2.0


# ------------------------------------------------------------- aggregator
def test_aggregator_merges_lanes_and_corrects_clocks(tmp_path):
    """Two hosts whose wall clocks disagree by 0.5s: the merged trace
    puts each on its own process lane and the offset correction pulls
    their timelines back into alignment."""
    now = time.time()
    _ship_spans(tmp_path, "h0",
                [("dispatch", now + 0.5, 0.01, "step:1")],
                offset=0.5,  # h0's clock runs 0.5s ahead of shared
                events=[(EVENT_PEER_DEAD, {"peer": "h1"})])
    _ship_spans(tmp_path, "h1",
                [("dispatch", now, 0.01, "step:1")])

    agg = ClusterAggregator(str(tmp_path)).load()
    assert set(agg.hosts) == {"h0", "h1"}
    assert agg.clock_offset("h0") == pytest.approx(0.5, abs=0.05)

    trace = agg.merge_trace()
    json.loads(json.dumps(trace))  # valid JSON round-trip
    events = trace["traceEvents"]
    lanes = {e["args"]["name"]: e["pid"] for e in events
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert len(lanes) == 2  # one process lane per host
    pid_of = {name.split()[0]: pid for name, pid in lanes.items()}
    assert set(pid_of) == {"h0", "h1"}

    assert all(e["ts"] >= 0 for e in events if "ts" in e)
    xs = {e["pid"]: e["ts"] for e in events
          if e.get("ph") == "X" and e["name"] == "dispatch"}
    # both hosts stamped the SAME instant on their own (skewed) clocks;
    # after correction the lanes align far inside the 0.5s raw skew
    assert abs(xs[pid_of["h0"]] - xs[pid_of["h1"]]) < 0.1e6

    (dead,) = [e for e in events if e["name"] == EVENT_PEER_DEAD]
    assert dead["ph"] == "i" and dead["cat"] == "elastic"
    assert dead["pid"] == pid_of["h0"]

    path = agg.write_trace()
    with open(path) as f:
        assert json.load(f)["traceEvents"]


def test_cluster_summary_percentiles_throughput_and_skew(tmp_path):
    now = time.time()
    fast = [("dispatch", now + 0.1 * i, 0.010, f"step:{i}")
            for i in range(10)]
    slow = [("dispatch", now + 0.1 * i, 0.030, f"step:{i}")
            for i in range(10)]
    _ship_spans(tmp_path, "h0", fast, metrics={"throughput": 120.0})
    _ship_spans(tmp_path, "h1", slow, metrics={"throughput": 80.0})

    s = ClusterAggregator(str(tmp_path)).load().cluster_summary(now=now)
    ph = s["per_host"]
    assert ph["h0"]["n_steps"] == 10
    assert ph["h0"]["step_p50_ms"] == pytest.approx(10.0, abs=0.5)
    assert ph["h1"]["step_p50_ms"] == pytest.approx(30.0, abs=0.5)
    assert ph["h0"]["throughput"] == 120.0
    assert s["cluster"]["hosts"] == 2
    assert s["cluster"]["world_throughput"] == pytest.approx(200.0)
    lo, hi = sorted([s["cluster"]["step_p50_ms"],
                     s["cluster"]["step_p95_ms"]])
    assert 10.0 <= lo + 0.5 and hi <= 30.5
    # straggler skew: every step:N correlates across both hosts at
    # 30ms - 10ms = 20ms spread
    skew = s["cluster"]["straggler_skew_ms"]
    assert skew["n_steps"] == 10
    assert skew["mean"] == pytest.approx(20.0, abs=1.0)
    assert skew["max"] == pytest.approx(20.0, abs=1.0)


# ------------------------------------------------- federated watchdog
def test_federated_watchdog_flags_and_transition_dedupe(tmp_path):
    now = time.time()
    # h0: plenty of fast steps, fresh beacon — healthy
    _write_seg(tmp_path, "h0", 0, now,
               spans=[("dispatch", now - 1 + 0.01 * i, 0.010,
                       f"step:{i}") for i in range(30)])
    # h1: fresh but saturated serving replica
    _write_seg(tmp_path, "h1", 0, now,
               metrics={"queue_depth": 64, "occupancy": 0.99})
    # h2: straggling (p50 5x the cluster p50), fresh beacon
    _write_seg(tmp_path, "h2", 0, now,
               spans=[("dispatch", now - 1 + 0.05 * i, 0.050,
                       f"step:{i}") for i in range(10)])
    # h3: stalled — last beacon a minute ago
    _write_seg(tmp_path, "h3", 0, now - 60.0)

    wd = Watchdog(log=None)
    fed = FederatedWatchdog(str(tmp_path), watchdog=wd, stale_s=10.0,
                            straggler_factor=2.0, min_steps=8)
    flags = fed.check(now=now)
    assert "h0" not in flags
    assert flags["h1"] == ["saturated"]
    assert flags["h2"] == ["straggler"]
    assert flags["h3"] == ["stalled"]
    assert fed.flags() == flags
    n = wd.counters["peer_failures"]
    assert n == 3  # one peer_event per flagged host

    # steady state: same flags on the next poll, NO new anomalies
    assert fed.check(now=now) == flags
    assert wd.counters["peer_failures"] == n

    # recovery then relapse: the transition re-raises
    agg = ClusterAggregator(str(tmp_path)).load()
    del agg.hosts["h3"]
    assert "h3" not in fed.check(aggregator=agg, now=now)
    assert "h3" in fed.check(now=now)
    assert wd.counters["peer_failures"] == n + 1

    rep = fed.report()
    assert rep["flags"] == fed.flags()
    assert rep["summary"]["cluster"]["hosts"] == 4
    assert rep["watchdog"]["counters"]["peer_failures"] == n + 1


# -------------------------------------------------------------- cost model
def test_costmodel_stamps_real_program_and_mfu(tmp_path, monkeypatch):
    f = jax.jit(lambda a, b: (a @ b).sum())
    a = np.ones((32, 16), np.float32)
    b = np.ones((16, 8), np.float32)
    table = costmodel.CostTable()
    cost = costmodel.stamp_jitted("unit_matmul", f, a, b, table=table)
    if cost is None:  # backend without cost_analysis: tolerated path
        pytest.skip("backend returned no cost analysis")
    assert cost.flops >= 2 * 32 * 16 * 8  # at least the matmul MACs
    assert cost.bytes_accessed > 0
    assert cost.stamped_unix > 0

    # MFU math: a program at exactly peak is 1.0, halved by 2 devices
    assert costmodel.mfu(1e12, 1.0, peak=1e12) == pytest.approx(1.0)
    assert costmodel.mfu(1e12, 1.0, n_devices=2, peak=1e12) == \
        pytest.approx(0.5)
    assert costmodel.mfu(1.0, 0.0) == 0.0  # degenerate step time
    # one peaks table keyed by device kind: a CPU has no peak, an
    # unknown kind is an error, and a CPU run reports no MFU at all
    assert costmodel.mfu(1e12, 1.0) is None
    assert cost.mfu(1.0) is None
    with pytest.raises(LookupError, match="device_kind 'cpu'"):
        costmodel.peak_flops_per_device()

    class Dev:
        device_kind = "TPU v5 lite"

    assert costmodel.peak_flops_per_device(Dev()) == 197e12
    Dev.device_kind = "TPU v9 imaginary"
    with pytest.raises(LookupError, match="v9 imaginary"):
        costmodel.peak_flops_per_device(Dev())
    monkeypatch.setenv("BIGDL_TPU_PEAK_FLOPS", "2e12")
    assert costmodel.peak_flops_per_device() == 2e12
    assert cost.mfu(1.0, peak=cost.flops) == pytest.approx(1.0)
    assert cost.bytes_per_s(2.0) == pytest.approx(cost.bytes_accessed / 2)

    # table round-trip: the artifact tools/autotune.py will read
    assert table.get("unit_matmul") is cost
    path = table.persist(str(tmp_path / "costs.json"))
    loaded = costmodel.CostTable.load(path)
    got = loaded.get("unit_matmul")
    assert got is not None and got.flops == cost.flops
    assert got.bytes_accessed == cost.bytes_accessed
    rec = dict(got.as_dict())
    assert rec["name"] == "unit_matmul"

    # kill switch: stamping becomes a no-op, never an error
    monkeypatch.setenv("BIGDL_TPU_COST_DISABLE", "1")
    assert not costmodel.cost_accounting_enabled()
    assert costmodel.stamp_jitted("off", f, a, b) is None


def test_cost_table_load_tolerates_garbage(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text("not json at all")
    assert len(costmodel.CostTable.load(str(p))) == 0
    assert len(costmodel.CostTable.load(str(tmp_path / "absent.json"))) \
        == 0


def test_shipper_ships_cost_table(tmp_path):
    table = costmodel.CostTable()
    f = jax.jit(lambda x: x * 2)
    cost = costmodel.stamp_jitted("double", f,
                                  np.ones((4,), np.float32), table=table)
    if cost is None:
        pytest.skip("backend returned no cost analysis")
    shipper = TelemetryShipper(str(tmp_path), "h0", tracer=None,
                               interval_s=0, cost_table=table)
    with open(shipper.ship_now()) as fh:
        recs = [json.loads(ln) for ln in fh if ln.strip()]
    shipper.close()
    (c,) = [r for r in recs if r["record"] == "cost"]
    assert [p["name"] for p in c["programs"]] == ["double"]
    # the standalone per-host table landed next to the segments
    side = os.path.join(str(tmp_path), "cost-h0.json")
    assert os.path.exists(side)
    assert costmodel.CostTable.load(side).get("double") is not None
    # aggregator surfaces it per host
    agg = ClusterAggregator(str(tmp_path)).load()
    assert agg.hosts["h0"]["costs"][0]["name"] == "double"


# ------------------------------------------------------------- cluster_top
def test_cluster_top_json_table_and_exit_codes(tmp_path, capsys):
    from tools import cluster_top

    now = time.time()
    _write_seg(tmp_path, "h0", 0, now,
               spans=[("dispatch", now - 1 + 0.01 * i, 0.010,
                       f"step:{i}") for i in range(10)],
               metrics={"throughput": 64.0, "mfu": 0.41})

    assert cluster_top.main([str(tmp_path), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["summary"]["per_host"]["h0"]["throughput"] == 64.0
    assert out["summary"]["cluster"]["hosts"] == 1

    assert cluster_top.main([str(tmp_path)]) == 0
    text = capsys.readouterr().out
    assert "h0" in text and "p50" in text

    trace_out = str(tmp_path / "merged.json")
    assert cluster_top.main([str(tmp_path), "--trace", trace_out]) == 0
    capsys.readouterr()
    with open(trace_out) as f:
        assert json.load(f)["traceEvents"]

    assert cluster_top.main([str(tmp_path / "missing"), "--json"]) == 2
    capsys.readouterr()


def test_cluster_top_live_decode_columns():
    """The live table surfaces the decode-engine snapshot scalars —
    pages_in_use, spec_acceptance_rate, prefill_chunks — scraped from
    the ``bigdl_tpu_snapshot`` family, and renders '-' for hosts that
    run no decode engine."""
    from bigdl_tpu.telemetry.debug_server import DebugServer
    from tools import cluster_top

    snap = {"pages_in_use": 7, "spec_acceptance_rate": 0.625,
            "prefill_chunks": 12}
    with DebugServer(port=0) as srv:
        srv.add_metrics("decode", snap)
        row = cluster_top.poll_host(f"127.0.0.1:{srv.port}")
    assert row is not None
    assert row["pages_in_use"] == 7.0
    assert row["spec_acceptance_rate"] == 0.625
    assert row["prefill_chunks"] == 12.0

    text = cluster_top.render_live(
        {"h0": row, "h1": None},
        {"per_host": {"h1": {"n_steps": 3}}}, {})
    head = text.splitlines()[1]
    assert "pages" in head and "spec %" in head and "chunks" in head
    live_row = next(ln for ln in text.splitlines() if ln.startswith("h0"))
    assert " 7 " in live_row and "62.5" in live_row and " 12 " in live_row
    file_row = next(ln for ln in text.splitlines() if ln.startswith("h1"))
    assert "-" in file_row  # no decode engine -> dash columns


# ------------------------------------------------------------ program X-ray
def test_decode_cache_growth_files_forensic_naming_axis():
    """Growing the decode cache (max_len 16 → 24) between engine
    generations must surface as a steady-state ``decode_tick`` forensic
    naming the cache axis — the exact signal docs/observability.md
    promises for silent decode recompiles."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.serving import DecodeEngine
    from bigdl_tpu.telemetry import programs

    registry = programs.get_program_registry()
    registry.clear()
    model = nn.Transformer(vocab_size=16, hidden_size=16, num_heads=2,
                           filter_size=32, num_layers=1, dropout=0.0,
                           causal=True)
    var = model.init(jax.random.PRNGKey(0))
    e1 = DecodeEngine(model, var, slots=2, max_len=16,
                      prompt_buckets=(4,), prefill_batch_sizes=(1,),
                      eos_id=None, start=False)
    e1.close()
    assert registry.get("decode_tick") is not None
    assert not [f for f in registry.forensic_records()
                if f["program"] == "decode_tick"]  # warmup was expected

    e2 = DecodeEngine(model, var, slots=2, max_len=24,
                      prompt_buckets=(4,), prefill_batch_sizes=(1,),
                      eos_id=None, warmup=False, start=False)
    # steady state (_warming is False); a first turn of the tick round:
    # it compiles and enqueues the tick, nothing was in flight to read
    e2._run_tick()
    assert e2._flight is not None and not e2._served.any()
    e2.close()
    forensics = [f for f in registry.forensic_records()
                 if f["program"] == "decode_tick"]
    assert len(forensics) == 1
    cause = forensics[0]["cause"]
    assert "cache" in cause and "16 → 24" in cause
    registry.clear()


def test_shipper_ships_xray_table_and_cli_reads_it(tmp_path, capsys):
    from tools import xray
    from bigdl_tpu.telemetry import programs

    registry = programs.get_program_registry()
    registry.clear()
    registry.register_compile(
        "serving_forward",
        programs.signature_of({"x": np.zeros((1, 32, 16), np.float32)}),
        compile_s=0.2, expected=True)
    registry.register_compile(
        "serving_forward",
        programs.signature_of({"x": np.zeros((1, 48, 16), np.float32)}),
        compile_s=0.1)
    registry.record_call("serving_forward", 5)

    shipper = TelemetryShipper(str(tmp_path), "h0", tracer=None,
                               interval_s=0)
    with open(shipper.ship_now()) as fh:
        recs = [json.loads(ln) for ln in fh if ln.strip()]
    shipper.close()
    (x,) = [r for r in recs if r["record"] == "xray"]
    assert x["programs"][0]["name"] == "serving_forward"
    assert x["programs"][0]["calls"] == 5
    assert x["forensics"] and "32 → 48" in x["forensics"][0]["cause"]
    # per-host sidecar landed next to the segments
    side = os.path.join(str(tmp_path), "xray-h0.json")
    assert os.path.exists(side)
    # aggregator surfaces the table per host
    agg = ClusterAggregator(str(tmp_path)).load()
    assert agg.hosts["h0"]["xray"][0]["compiles"] == 2
    assert agg.hosts["h0"]["forensics"]
    # the console reads the same directory
    assert xray.main([str(tmp_path), "--json"]) == 0
    blob = json.loads(capsys.readouterr().out)
    assert blob["h0"]["programs"][0]["name"] == "serving_forward"
    registry.clear()
