"""Numerics observatory tests (ISSUE 11 tentpole;
docs/observability.md §Numerics):

* :func:`telemetry.numerics.collect` — per-layer/global norms,
  non-finite counts, and histogram subsamples computed in-graph;
* :class:`NumericsMonitor` — early-warning anomalies (grad spike /
  vanish, update-ratio band, non-finite) counted by the Watchdog;
* the async engine drain — stats ride the existing sync-window drain,
  feed metrics gauges, and never change the training math;
* the seeded-divergence acceptance run — a trap layer goes NaN mid-
  run: the Watchdog sees the non-finite anomaly BEFORE the loss drain
  raises, the provenance diagnostic names the injected layer, the
  ``divergence_recovery`` record books the rewind, and the whole
  recovery is deterministic (two identical runs end bit-equal);
* TrainSummary parameter export without full-tree device_get;
* Perfetto grad-norm counter lanes (single-host and merged cluster)
  plus the cluster grad-norm-skew rollup in ``cluster_top --json``;
* the < 3% in-graph stats overhead gate over ``bench.numerics_ab``.
"""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu import telemetry
from bigdl_tpu.dataset import DataSet, MiniBatch, Transformer
from bigdl_tpu.optim.optimizer import LocalOptimizer
from bigdl_tpu.telemetry import numerics
from bigdl_tpu.telemetry.cluster import ClusterAggregator, TelemetryShipper
from bigdl_tpu.telemetry.export import chrome_trace
from bigdl_tpu.telemetry.tracer import Tracer
from bigdl_tpu.telemetry.watchdog import Watchdog


@pytest.fixture(autouse=True)
def clean_tracer():
    tr = telemetry.get_tracer()
    tr.disable()
    tr.clear()
    yield tr
    tr.disable()
    tr.clear()


def _toy_problem(n=64, dim=10, classes=4, seed=3):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, dim).astype(np.float32)
    w = rs.randn(dim, classes).astype(np.float32)
    return x, (x @ w).argmax(-1)


def _mlp(dim=10, classes=4):
    return nn.Sequential(nn.Linear(dim, 16), nn.ReLU(),
                         nn.Linear(16, classes))


# ------------------------------------------------------------- collect
def test_collect_per_layer_and_global_stats():
    model = _mlp()
    var = model.init(jax.random.PRNGKey(0))
    params = var["params"]
    grads = jax.tree_util.tree_map(lambda p: jnp.full_like(p, 0.5), params)
    newp = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads)
    spec = numerics.spec_for(model)
    assert spec.layers == ("0", "1", "2")

    stats = jax.jit(lambda p, g, n: numerics.collect(p, g, n, spec))(
        params, grads, newp)
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    # grad of 0.5 everywhere: ||g|| = 0.5 * sqrt(N); update = lr * g
    assert float(stats["grad_norm"]) == pytest.approx(
        0.5 * math.sqrt(n_params), rel=1e-5)
    assert float(stats["update_norm"]) == pytest.approx(
        0.1 * float(stats["grad_norm"]), rel=1e-5)
    assert int(stats["nonfinite"]) == 0
    # the ReLU ('1') holds no parameters: only the Linears report
    assert set(stats["layers"]) == {"0", "2"}
    for name in ("0", "2"):
        layer = stats["layers"][name]
        assert float(layer["p"]) > 0 and float(layer["u"]) > 0
        assert int(layer["nf"]) == 0
        assert 0 < layer["hist"].shape[0] <= spec.hist
    # per-layer sumsq recomposes the global norm
    g2 = sum(float(stats["layers"][k]["g"]) ** 2 for k in ("0", "2"))
    assert math.sqrt(g2) == pytest.approx(float(stats["grad_norm"]),
                                          rel=1e-5)

    # non-finite gradients are counted where they live
    bad = jax.tree_util.tree_map(lambda g: g, grads)
    bad["2"]["weight"] = bad["2"]["weight"].at[0, 0].set(jnp.nan)
    stats = numerics.collect(params, bad, newp, spec)
    assert int(stats["nonfinite"]) == 1
    assert int(stats["layers"]["2"]["nf"]) == 1
    assert int(stats["layers"]["0"]["nf"]) == 0


def test_subsample_tree_budget_and_determinism():
    tree = {"a": jnp.arange(10000, dtype=jnp.float32),
            "b": jnp.ones((64, 64), jnp.float32)}
    s1 = numerics.subsample_tree(tree, 256)
    s2 = numerics.subsample_tree(tree, 256)
    assert s1.shape[0] <= 256
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


# ------------------------------------------------------------- monitor
def _stats(g=1.0, p=1.0, u=0.01, nf=0, layers=None):
    return {"grad_norm": g, "param_norm": p, "update_norm": u,
            "nonfinite": nf, "layers": layers or {}}


def test_monitor_anomalies_feed_watchdog(clean_tracer):
    tr = clean_tracer
    tr.enable()
    wd = Watchdog(log=None).attach(tr)
    mon = numerics.NumericsMonitor(
        numerics.NumericsSpec(layers=("0", "1", "2")),
        spike_factor=10.0, vanish_floor=1e-8, ratio_band=(1e-10, 0.5),
        warmup=4, log=None)

    for i in range(4):  # warmup: establish the rolling median
        assert mon.observe(i + 1, _stats()) == []
    assert mon.observe(5, _stats(g=50.0)) == ["grad_spike"]
    assert mon.observe(6, _stats(g=1e-12)) == ["grad_vanish"]
    assert mon.observe(7, _stats(u=0.9)) == ["update_ratio"]
    fired = mon.observe(
        8, _stats(nf=2, layers={"0": {"nf": 0}, "1": {"nf": 2}}))
    assert fired == ["nonfinite"]
    assert mon.anomaly_count == 4
    assert mon.last["iteration"] == 8 and mon.last["nonfinite"] == 2

    assert wd.counters["grad_norm_spikes"] == 1
    assert wd.counters["grad_norm_vanishes"] == 1
    assert wd.counters["update_ratio_bands"] == 1
    assert wd.counters["nonfinite_grads"] == 1
    # the nonfinite anomaly names the first offending layer in order
    anomalies = [s for s in tr.spans() if s.name == numerics.NUMERICS_EVENT]
    assert anomalies[-1].args["layer"] == "1"
    # every observation also left a `numerics` sample instant
    samples = [s for s in tr.spans() if s.name == numerics.NUMERICS_SAMPLE]
    assert len(samples) == 8 and samples[0].corr == "step:1"
    wd.close()


def test_monitor_env_knobs(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_NUMERICS_SPIKE", "3.5")
    monkeypatch.setenv("BIGDL_TPU_NUMERICS_VANISH", "1e-4")
    monkeypatch.setenv("BIGDL_TPU_NUMERICS_BAND", "1e-6:0.25")
    mon = numerics.NumericsMonitor(log=None)
    assert mon._spike == 3.5 and mon._vanish == 1e-4
    assert mon._band == (1e-6, 0.25)
    monkeypatch.setenv("BIGDL_TPU_NUMERICS", "1")
    assert numerics.enabled()
    monkeypatch.delenv("BIGDL_TPU_NUMERICS")
    assert not numerics.enabled()
    monkeypatch.setenv("BIGDL_TPU_NUMERICS_HIST", "64")
    assert numerics.spec_for(_mlp()).hist == 64


# ------------------------------------------------------- engine drain
def test_engine_drains_stats_on_sync_window_cadence():
    """set_numerics(True): stats ride the deferred-loss drain (no new
    host syncs), feed the grad_norm/update_ratio gauges, and appear as
    a `numerics` metrics phase."""
    x, y = _toy_problem()
    engine = LocalOptimizer(_mlp(), DataSet.from_arrays(x, y, 16),
                            nn.ClassNLLCriterion(logits=True),
                            optim.Trigger.max_epoch(3))
    engine.set_optim_method(optim.SGD(0.1)).set_numerics(True)
    engine.optimize()

    mon = engine._numerics_monitor
    assert mon is not None and mon.last is not None
    assert mon.last["iteration"] == 12  # every drained step was observed
    assert mon.last["grad_norm"] > 0
    assert engine.metrics.value("grad_norm") == pytest.approx(
        mon.last["grad_norm"], rel=1e-4)
    assert "numerics" in engine.metrics.summary()
    assert engine._numerics is not None


def test_numerics_does_not_change_training_math():
    """Stats are observers: identical runs with stats on vs off end in
    bit-equal parameters (the jaxpr-parity lint proves the off case is
    byte-identical to the seed; this proves the on case is exact)."""
    x, y = _toy_problem()

    def run(on):
        engine = LocalOptimizer(_mlp(), DataSet.from_arrays(x, y, 16),
                                nn.ClassNLLCriterion(logits=True),
                                optim.Trigger.max_epoch(3))
        engine.set_optim_method(optim.SGD(0.1, momentum=0.9))
        engine.set_numerics(on)
        engine.optimize()
        return engine.final_params

    for a, b in zip(jax.tree_util.tree_leaves(run(True)),
                    jax.tree_util.tree_leaves(run(False))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------- seeded divergence (acceptance)
class Trap(nn.Module):
    """Pass-through that goes NaN once its input magnitude exceeds the
    threshold — a synthetic overflow site with a known name."""

    def __init__(self, limit=1e6):
        super().__init__()
        self.limit = limit

    def apply(self, params, state, *inputs, training=False, rng=None):
        x = inputs[0]
        return jnp.where(jnp.abs(x) > self.limit,
                         jnp.float32(np.nan), x), state


class SentinelOnce(Transformer):
    """Replace the features of ONE batch with a large FINITE sentinel —
    upstream data is clean, the blow-up happens inside the model (at
    the Trap), so provenance must name the layer, not the input."""

    def __init__(self, at: int, value: float = 1e8):
        self.at, self.value = at, value
        self.count = 0

    def __call__(self, it):
        for b in it:
            self.count += 1
            if self.count == self.at:
                b = MiniBatch(np.full_like(b.get_input(), self.value),
                              b.get_target())
            yield b


def _trap_run(tmp_path, tag):
    x, y = _toy_problem()
    model = nn.Sequential(nn.Linear(10, 16), Trap(), nn.ReLU(),
                          nn.Linear(16, 4))
    ds = DataSet.from_arrays(x, y, batch_size=16).transform(
        SentinelOnce(6))
    engine = LocalOptimizer(model, ds, nn.ClassNLLCriterion(logits=True),
                            optim.Trigger.max_epoch(6))
    engine.set_optim_method(optim.SGD(0.1, momentum=0.9))
    engine.set_checkpoint(str(tmp_path / f"ck-{tag}"),
                          optim.Trigger.every_epoch())
    engine.set_numerics(True)
    engine.optimize()
    return engine


def test_seeded_divergence_early_warning_provenance_and_recovery(
        clean_tracer, tmp_path):
    tr = clean_tracer
    tr.enable(capacity=65536)
    wd = Watchdog(log=None).attach(tr)
    engine = _trap_run(tmp_path, "a")
    wd.close()

    # recovered and finished, with finite parameters
    assert engine._retries == 1
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree_util.tree_leaves(engine.final_params))

    # the Watchdog counted the numerics anomaly AND the divergence,
    # and the early warning landed BEFORE the loss drain saw the NaN
    assert wd.counters["nonfinite_grads"] >= 1
    assert wd.counters["nan_windows"] >= 1
    names = [s.name for s in tr.spans()]
    assert names.index(numerics.NUMERICS_EVENT) < \
        names.index("loss_divergence")
    (anom,) = [s for s in tr.spans()
               if s.name == numerics.NUMERICS_EVENT][:1]
    assert anom.args["kind"] == "nonfinite"

    # provenance names the injected Trap layer ('1'), found in forward
    (prov,) = [s for s in tr.spans()
               if s.name == numerics.PROVENANCE_EVENT]
    assert prov.args["layer"] == "1" and prov.args["site"] == "forward"
    assert prov.args["iteration"] == 6
    assert prov.args["input_nonfinite"] == 0  # sentinel was finite

    # the recovery record books the rewind: diverged at 6, rewound to
    # the epoch-1 checkpoint (iteration 4), replayed the difference
    (rec,) = [s for s in tr.spans() if s.name == numerics.RECOVERY_EVENT]
    assert rec.args["iteration"] == 6
    assert rec.args["restored_iteration"] == 4
    assert rec.args["detected_at"] - 4 == rec.args["replayed_steps"]
    assert rec.args["retry"] == 1
    assert rec.corr == "step:6"

    # kill-free bit-equal resume: the whole poisoned run (divergence,
    # rewind, replay) is deterministic end to end
    engine_b = _trap_run(tmp_path, "b")
    for a, b in zip(jax.tree_util.tree_leaves(engine.final_params),
                    jax.tree_util.tree_leaves(engine_b.final_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_nan_provenance_sites():
    model = nn.Sequential(nn.Linear(10, 16), Trap(), nn.ReLU(),
                          nn.Linear(16, 4))
    var = model.init(jax.random.PRNGKey(0))
    y = np.array([0, 1], np.int64)

    # poisoned input: named as such, never blamed on a layer walk
    bad_x = np.full((2, 10), np.nan, np.float32)
    rep = numerics.nan_provenance(model, var["params"], var["state"],
                                  bad_x, y)
    assert rep["site"] == "input" and rep["input_nonfinite"] > 0

    # finite input, forward blow-up at the Trap
    hot_x = np.full((2, 10), 1e8, np.float32)
    rep = numerics.nan_provenance(
        model, var["params"], var["state"], hot_x, y,
        criterion=nn.ClassNLLCriterion(logits=True))
    assert rep["site"] == "forward" and rep["layer"] == "1"
    assert rep["layers"]["1"]["out_nonfinite"] > 0

    # healthy batch: nothing to report
    ok_x = np.ones((2, 10), np.float32)
    rep = numerics.nan_provenance(
        model, var["params"], var["state"], ok_x, y,
        criterion=nn.ClassNLLCriterion(logits=True))
    assert rep["site"] is None and rep["layer"] is None
    assert math.isfinite(rep["loss"])


# ------------------------------------------------------- TrainSummary
def test_train_summary_parameters_without_full_transfer(
        tmp_path, monkeypatch):
    """maybe_add_parameters never fetches the full parameter tree: the
    fallback fetches one bounded subsample; the stats path fetches
    nothing (the drain already brought the histograms host-side)."""
    from bigdl_tpu.visualization import summary as summary_mod

    big = {"0": {"weight": jnp.ones((512, 512), jnp.float32)}}
    fetched = []
    real_asarray = summary_mod.np.asarray
    monkeypatch.setattr(
        summary_mod.np, "asarray",
        lambda a, *k, **kw: fetched.append(int(np.prod(np.shape(a))))
        or real_asarray(a, *k, **kw))

    ts = summary_mod.TrainSummary(str(tmp_path), "app")
    ts.set_summary_trigger("Parameters", 2)
    ts.maybe_add_parameters(big, 1)  # trigger not due: nothing at all
    assert fetched == []

    ts.maybe_add_parameters(big, 2)  # fallback: bounded subsample only
    assert fetched and max(fetched) <= numerics.DEFAULT_HIST
    assert sum(fetched) < 512 * 512

    fetched.clear()
    stats = {"layers": {"0": {"g": 1.5, "p": 2.5, "u": 0.1, "nf": 0,
                              "hist": np.zeros(32, np.float32)}}}
    ts.maybe_add_parameters(big, 4, stats=stats)
    assert fetched and max(fetched) <= 32  # only the drained subsample
    ts.close()
    assert ts.read_scalar("GradNorm/0") == [(4, 1.5)]
    assert ts.read_scalar("ParamNorm/0") == [(4, 2.5)]


# ------------------------------------------------------ Perfetto lanes
def test_chrome_trace_grad_norm_counter_lane(clean_tracer):
    tr = clean_tracer
    tr.enable()
    mon = numerics.NumericsMonitor(log=None)
    mon.observe(3, _stats(g=2.5, u=0.02))
    trace = chrome_trace(tracer=tr)
    (lane,) = [e for e in trace["traceEvents"]
               if e.get("ph") == "C" and e["name"] == "grad norm"]
    assert lane["args"]["grad_norm"] == pytest.approx(2.5)
    assert lane["args"]["update_ratio"] == pytest.approx(0.02)


def _ship_numerics(run_dir, host, gnorm):
    tr = Tracer(capacity=64)
    tr.enable()
    shipper = TelemetryShipper(str(run_dir), host, tracer=tr,
                               interval_s=0,
                               clock_offset_fn=lambda: 0.0)
    shipper.add_metrics("train", {
        "throughput": 100.0,
        "values": {"grad_norm": gnorm, "update_ratio": 0.01}})
    tr.instant(numerics.NUMERICS_SAMPLE, "train", corr="step:1",
               args={"iteration": 1, "grad_norm": gnorm,
                     "update_ratio": 0.01, "nonfinite": 0})
    shipper.ship_now()
    shipper.close()


def test_cluster_grad_norm_skew_and_merged_lanes(tmp_path, capsys):
    """Two hosts disagreeing on the post-allreduce grad norm: the
    rollup quantifies the skew, the merged trace grows one grad-norm
    counter lane per host, and cluster_top surfaces both."""
    from tools import cluster_top

    _ship_numerics(tmp_path, "h0", 1.0)
    _ship_numerics(tmp_path, "h1", 2.0)

    agg = ClusterAggregator(str(tmp_path)).load()
    s = agg.cluster_summary()
    assert s["per_host"]["h0"]["grad_norm"] == pytest.approx(1.0)
    assert s["per_host"]["h1"]["grad_norm"] == pytest.approx(2.0)
    skew = s["cluster"]["grad_norm_skew"]
    assert skew["hosts"] == 2
    assert skew["mean"] == pytest.approx(1.5)
    assert skew["max"] == pytest.approx(2.0)
    assert skew["rel_spread"] == pytest.approx(1.0 / 1.5, rel=1e-6)

    lanes = [e for e in agg.merge_trace()["traceEvents"]
             if e.get("ph") == "C" and e["name"] == "grad norm"]
    assert len(lanes) == 2 and len({e["pid"] for e in lanes}) == 2

    assert cluster_top.main([str(tmp_path), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["summary"]["cluster"]["grad_norm_skew"]["hosts"] == 2
    assert cluster_top.main([str(tmp_path)]) == 0
    text = capsys.readouterr().out
    assert "gnorm" in text and "spread=" in text


# ------------------------------------------------------- overhead gate
def test_numerics_overhead_under_3_percent(clean_tracer):
    """bench.py --telemetry-ab --numerics runs both arms over the spec's
    layers and samples each.  The on/off ratio it reports is a
    wall-clock number from a shared box: printed, never a gate."""
    bench = pytest.importorskip("bench")

    d = bench.numerics_ab(steps=60)["detail"]
    assert d["layers"] >= 1
    assert min(d["samples"]) > 0 and d["step_on_ms"] > 0
