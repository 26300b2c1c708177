"""Benchmark driver — ResNet-50 synthetic training throughput on one chip.

The TPU analog of the reference's perf driver
(models/utils/DistriOptimizerPerf.scala:82-140: iterations/sec of the
full train step on synthetic data).  Prints ONE JSON line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

``vs_baseline`` is MFU / 0.50 — the fraction of the BASELINE.md north
star (ResNet-50 data-parallel at >=50% MFU) achieved on this chip.

The default mode measures the attached TPU in this one process and
exits non-zero when jax finds no TPU: there is no CPU stand-in for a
device number.  The ``--*-ab`` modes below are host-side A/Bs that run
anywhere.
"""
from __future__ import annotations

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))


def _time_train_step(model, crit, batch: int, res: int, steps: int,
                     warmup: int):
    """Compile + time the ResNet-50 train step at one batch size.
    Returns (imgs_per_sec, step_time_s, flops_per_step) using XLA's own
    cost analysis for the FLOP count."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.utils import jax_compat

    step, methods = build_train_step(model, crit)

    variables = model.init(jax.random.PRNGKey(0))
    params, mstate = variables["params"], variables["state"]
    opt = {"__all__": methods["__all__"].init_state(params)}
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(batch, res, res, 3), jnp.bfloat16)
    t = jnp.asarray(rs.randint(0, 1000, (batch,)))
    lrs = [jnp.asarray(0.1, jnp.float32)]

    # AOT-compile once and reuse the executable for both cost analysis
    # and the timed loop
    step = step.lower(
        params, mstate, opt, jnp.asarray(0, jnp.int32),
        jax.random.PRNGKey(0), x, t, lrs,
    ).compile()
    flops_per_step = jax_compat.cost_analysis(step).get("flops")

    for i in range(max(warmup, 1)):
        params, mstate, opt, loss = step(
            params, mstate, opt, jnp.asarray(i, jnp.int32),
            jax.random.PRNGKey(i), x, t, lrs,
        )
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for i in range(steps):
        params, mstate, opt, loss = step(
            params, mstate, opt, jnp.asarray(i, jnp.int32),
            jax.random.PRNGKey(i), x, t, lrs,
        )
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / steps

    if not flops_per_step:
        # analytic count: ~8.2 GFLOP fwd/img (XLA-counted), bwd ~2x
        flops_per_step = 3 * 8.23e9 * batch * (res / 224.0) ** 2
    return batch / dt, dt, flops_per_step


def _flash_lowering_smoke():
    """Compile+run the flash-attention kernel on its real lowering path
    (interpret-mode tests once accepted a block shape Mosaic rejects;
    the bench must exercise the chip path)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.pallas import flash_attention

    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (1, 2, 1024, 128), jnp.bfloat16)
    out = jax.jit(lambda a: flash_attention(a, a, a, causal=True))(q)
    jax.block_until_ready(out)


def build_bench_model(fused: bool = True):
    """The bench's canonical model+criterion: ResNet-50 with the
    space_to_depth stem (computes the identical function to the 7x7
    stem — models/resnet.py fold_stem_to_s2d — but keeps the MXU input
    lanes full) and the fused Pallas conv+BN pipeline.  Shared with
    tools/tpu_aot_check.py --step so the offline compile cannot drift
    from the bench configuration."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.models import ResNet50

    return (ResNet50(class_num=1000, stem="space_to_depth", fused=fused),
            nn.ClassNLLCriterion(logits=True))


def build_train_step(model, crit, in_shardings=None, out_shardings=None):
    """The bench's canonical jitted train step: SGD 0.1 momentum 0.9,
    bf16 compute, params/state/opt donated.  Also shared with
    tools/tpu_aot_check.py --step (deviceless AOT compile)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import make_train_step

    methods = {"__all__": SGD(0.1, momentum=0.9)}
    kw = {}
    if in_shardings is not None:
        kw = {"in_shardings": in_shardings,
              "out_shardings": out_shardings}
    step = jax.jit(
        make_train_step(model, crit, methods, compute_dtype=jnp.bfloat16),
        donate_argnums=(0, 1, 2), **kw,
    )
    return step, methods


def main(res: int = 224, steps: int = 20, warmup: int = 3,
         batch: int = 256):
    """Default mode: fused ResNet-50 train step on the attached TPU, in
    this process.  No TPU is an error (exit non-zero, no record)."""
    import jax

    from bigdl_tpu.ops.pallas import report as kernel_report
    from bigdl_tpu.telemetry import costmodel
    from bigdl_tpu.utils.compile_cache import (cache_entries,
                                               enable_compile_cache)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py measures the chip: jax found platform "
                 f"{dev.platform!r}, not a TPU (the --*-ab modes are "
                 "the ones that run without one)")
    cache = enable_compile_cache()
    print(f"compile cache: {cache} ({cache_entries(cache)} entries)",
          file=sys.stderr, flush=True)
    peak = costmodel.peak_flops_per_device(dev)  # unknown kind raises

    # fused off via BIGDL_TPU_BENCH_UNFUSED=1 for A/B runs
    fused = not os.environ.get("BIGDL_TPU_BENCH_UNFUSED")
    model, crit = build_bench_model(fused)
    imgs_per_sec, dt, flops_per_step = _time_train_step(
        model, crit, batch, res, steps, warmup)
    mfu = imgs_per_sec / batch * flops_per_step / peak

    # kernel-lowering evidence: which path each Pallas entry point took
    # at trace time, plus a flash-attention compile smoke on chip
    paths = kernel_report.report()
    pallas_lowered = {
        k: paths.get(k, {}).get("pallas", 0) > 0 and fused
        for k in ("fused_matmul", "fused_conv3x3")
    }
    _flash_lowering_smoke()
    fa = kernel_report.report().get("flash_attention", {})
    pallas_lowered["flash_attention"] = fa.get("pallas", 0) > 0

    record = {
        "metric": "resnet50_synth_train_throughput",
        "value": round(imgs_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(mfu / 0.50, 4),
        "detail": {
            "batch": batch, "res": res, "steps": steps,
            "step_time_ms": round(1000 * dt, 2),
            "mfu": round(mfu, 4),
            "flops_per_img": round(flops_per_step / batch / 1e9, 2),
            "peak_tflops": round(peak / 1e12, 1),
            "device": {"platform": dev.platform,
                       "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "fused": fused,
            "kernel_paths": paths,
            "pallas_lowered": pallas_lowered,
        },
    }
    write_bench_last(record)
    print(json.dumps(record), flush=True)


def loop_ab(steps: int = 30, batch: int = 64, hidden: int = 512,
            depth: int = 6, max_sleep: float = 0.1) -> dict:
    """Driver-loop A/B: the async engine vs ``BIGDL_TPU_SYNC_LOOP=1``
    on a host-bound workload (docs/async_engine.md).  CPU-runnable.

    Calibrates a sleep-per-batch dataset to the measured compiled step
    time — the synchronous loop's worst case, data == compute, where a
    pipelined loop approaches max(data, compute) instead of their sum —
    then times ``LocalOptimizer.optimize`` end-to-end in both modes.
    Returns the timings plus the async run's phase summary.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, Transformer
    from bigdl_tpu.optim import SGD, Trigger
    from bigdl_tpu.optim.optimizer import LocalOptimizer, make_train_step

    rs = np.random.RandomState(0)
    x = rs.randn(4 * batch, hidden).astype(np.float32)
    y = rs.randint(0, 8, 4 * batch)
    layers = []
    for _ in range(depth):
        layers += [nn.Linear(hidden, hidden), nn.Tanh()]
    layers += [nn.Linear(hidden, 8)]
    model = nn.Sequential(*layers)
    crit = nn.ClassNLLCriterion(logits=True)

    # ONE compiled step shared by every run below (the engine's own
    # builder, same donation): the A/B compares the LOOPS around the
    # step, so XLA compile time — minutes of noise on a loaded box —
    # must not sit inside either timed region
    shared = {}

    class _SharedStepEngine(LocalOptimizer):
        def _build_step_fn(self, m):
            if "step" not in shared:
                shared["step"] = super()._build_step_fn(m)
            return shared["step"]

    # calibrate: measured per-step time of the compiled train step
    methods = {"__all__": SGD(0.1, momentum=0.9)}
    step = jax.jit(make_train_step(model, crit, methods))
    variables = model.init(jax.random.PRNGKey(0))
    opt = {"__all__": methods["__all__"].init_state(variables["params"])}
    xb = jnp.asarray(x[:batch])
    yb = jnp.asarray(y[:batch])
    lrs = [jnp.asarray(0.1, jnp.float32)]
    p, s = variables["params"], variables["state"]
    for i in range(2):  # compile + settle
        p, s, opt, loss = step(p, s, opt, jnp.asarray(i, jnp.int32),
                               jax.random.PRNGKey(i), xb, yb, lrs)
    float(loss)
    t0 = time.perf_counter()
    for i in range(5):
        p, s, opt, loss = step(p, s, opt, jnp.asarray(i, jnp.int32),
                               jax.random.PRNGKey(i), xb, yb, lrs)
    float(loss)
    step_s = (time.perf_counter() - t0) / 5
    sleep_s = min(max(step_s, 0.002), max_sleep)

    class SleepPerBatch(Transformer):
        """Artificially slow host pipeline: sleep per produced batch."""

        def __call__(self, it):
            for b in it:
                time.sleep(sleep_s)
                yield b

    def run(sync: bool, n_steps: int) -> tuple:
        ds = DataSet.from_arrays(x, y, batch_size=batch) \
            .transform(SleepPerBatch())
        engine = _SharedStepEngine(model, ds, crit,
                                   Trigger.max_iteration(n_steps))
        engine.set_optim_method(SGD(0.1, momentum=0.9))
        prev = os.environ.get("BIGDL_TPU_SYNC_LOOP")
        os.environ["BIGDL_TPU_SYNC_LOOP"] = "1" if sync else "0"
        try:
            t0 = time.perf_counter()
            engine.optimize()
            return time.perf_counter() - t0, engine.metrics
        finally:
            if prev is None:
                os.environ.pop("BIGDL_TPU_SYNC_LOOP", None)
            else:
                os.environ["BIGDL_TPU_SYNC_LOOP"] = prev

    run(sync=False, n_steps=2)  # warm the shared step's jit cache
    sync_s, _ = run(sync=True, n_steps=steps)
    async_s, async_metrics = run(sync=False, n_steps=steps)
    return {
        "metric": "driver_loop_async_speedup",
        "value": round(sync_s / async_s, 3),
        "unit": "x vs BIGDL_TPU_SYNC_LOOP=1",
        "detail": {
            "steps": steps, "batch": batch,
            "compiled_step_ms": round(1e3 * step_s, 2),
            "sleep_per_batch_ms": round(1e3 * sleep_s, 2),
            "sync_wall_s": round(sync_s, 3),
            "async_wall_s": round(async_s, 3),
            "async_phases": async_metrics.summary(),
        },
    }


def build_serve_model(feat: int = 16, hidden: int = 64, classes: int = 8):
    """The serving A/B's canonical model: a per-timestep MLP over
    ``(t, feat)`` sequences.  Shape-local (each output row depends only
    on its own input row), so bucket padding along both the batch and
    sequence axes is exact after cropping (docs/serving.md)."""
    import bigdl_tpu.nn as nn

    return nn.Sequential(nn.Linear(feat, hidden), nn.Tanh(),
                         nn.Linear(hidden, classes))


SERVE_FEAT = 16
SERVE_BUCKETS = ((8, SERVE_FEAT), (16, SERVE_FEAT), (24, SERVE_FEAT),
                 (32, SERVE_FEAT))
SERVE_BATCH_SIZES = (1, 4, 8, 16, 32)


def serve_ab(n_requests: int = 512, clients: int = 8,
             seq_lens=tuple(range(3, 33)),
             batch_window_ms: float = 2.0) -> dict:
    """Serving A/B: the bucketed pipelined :class:`ServingEngine` vs the
    seed ``PredictionService`` on a mixed-shape open-loop workload
    (docs/serving.md).  CPU-runnable, gated in CI like ``--loop-ab``.

    The seed service is reproduced inline (the tree's
    ``optim.PredictionService`` is now a facade over the engine): a bare
    ``jax.jit`` forward behind a semaphore — no buckets, no warmup — so
    every unseen request shape recompiles silently ON the request path,
    and every request is its own tiny device call.  Both services start
    cold, as deployed: the engine AOT-warms its declared grid before
    traffic (startup cost reported as ``warmup_s``, off the timed path —
    warmup is exactly the capability the seed lacks), then both serve
    the same shape-diverse open-loop workload.  The engine must hold
    ZERO steady-state recompiles (counter == declared buckets).

    ``detail.steady_state_speedup`` re-times a fully pre-warmed seed —
    the recompile-free residual (batching/pipelining only), which on a
    single-core CPU host is near parity since per-sample dispatch is
    cheap and padded batches cost real FLOPs; the batching term is a
    chip-side measurement (PERF.md §serving).
    """
    import queue
    import threading

    import jax
    import numpy as np

    from bigdl_tpu.serving import ServingEngine

    model = build_serve_model(feat=SERVE_FEAT)
    variables = model.init(jax.random.PRNGKey(0))

    rs = np.random.RandomState(0)
    lens = [seq_lens[i % len(seq_lens)] for i in range(n_requests)]
    rs.shuffle(lens)
    samples = [rs.rand(t, SERVE_FEAT).astype(np.float32) for t in lens]

    # --- seed baseline: the pre-engine PredictionService direct path --
    class _SeedPredictionService:
        def __init__(self, n_concurrent=4):
            self.params = variables["params"]
            self.state = variables["state"]
            self._sem = threading.Semaphore(n_concurrent)
            self._fwd = jax.jit(
                lambda p, s, x: model.apply(p, s, x, training=False)[0])

        def predict(self, x):
            with self._sem:
                return np.asarray(self._fwd(self.params, self.state,
                                            np.asarray(x)))

    def run_seed(svc) -> float:
        work: "queue.Queue" = queue.Queue()
        for s in samples:
            work.put(s)

        def client():
            while True:
                try:
                    s = work.get_nowait()
                except queue.Empty:
                    return
                svc.predict(s[None])

        ts = [threading.Thread(target=client) for _ in range(clients)]
        t0 = time.perf_counter()
        [t.start() for t in ts]
        [t.join() for t in ts]
        return time.perf_counter() - t0

    def run_engine(engine) -> tuple:
        after_warmup = engine.metrics.recompiles
        t0 = time.perf_counter()
        futs = [engine.submit(s) for s in samples]  # open loop
        outs = [f.result(60) for f in futs]
        wall = time.perf_counter() - t0
        # spot-check unpadding exactness against the direct forward
        for i in (0, n_requests // 2, n_requests - 1):
            direct = np.asarray(model.apply(
                variables["params"], variables["state"], samples[i][None],
                training=False)[0])[0]
            np.testing.assert_allclose(outs[i], direct, rtol=1e-5,
                                       atol=1e-6)
        steady = engine.metrics.recompiles - after_warmup
        return wall, steady

    # cold-start deployments: engine warms its declared grid up front...
    t0 = time.perf_counter()
    engine = ServingEngine(model, variables,
                           buckets=SERVE_BUCKETS,
                           batch_sizes=SERVE_BATCH_SIZES,
                           batch_window_ms=batch_window_ms,
                           max_queue=max(n_requests, 1024),
                           pipeline_depth=2)
    warmup_s = time.perf_counter() - t0
    # ...the seed meets the mixed shapes on the request path
    seed = _SeedPredictionService()
    seed_s = run_seed(seed)
    engine_s, steady = run_engine(engine)

    # recompile-free residual: same workload again, both sides now warm
    steady_seed_s = run_seed(seed)
    steady_engine_s, steady2 = run_engine(engine)

    snap = engine.metrics.snapshot()
    declared = len(engine.declared_buckets)
    recompiles = engine.metrics.recompiles
    engine.close()
    return {
        "metric": "serving_engine_speedup",
        "value": round(seed_s / engine_s, 3),
        "unit": "x vs seed PredictionService",
        "detail": {
            "n_requests": n_requests, "clients": clients,
            "distinct_shapes": len(set(lens)),
            "warmup_s": round(warmup_s, 3),
            "seed_wall_s": round(seed_s, 3),
            "engine_wall_s": round(engine_s, 3),
            "seed_rps": round(n_requests / seed_s, 1),
            "engine_rps": round(n_requests / engine_s, 1),
            "steady_state_speedup": round(steady_seed_s / steady_engine_s,
                                          3),
            "declared_buckets": declared,
            "recompiles": recompiles,
            "steady_state_recompiles": steady + steady2,
            "engine_metrics": snap,
        },
    }


def telemetry_ab(train_steps: int = 240, batch: int = 64,
                 hidden: int = 512, depth: int = 6,
                 n_chunks: int = 64, toggle_window: int = 5,
                 jsonl_path: str | None = None,
                 ship: bool = False, xray: bool = False,
                 flight: bool = False, requests: bool = False) -> dict:
    """Telemetry overhead A/B (docs/observability.md).  CPU-runnable,
    gated < 3% in tests/test_telemetry.py.

    Both arms toggle the global tracer WITHIN one live session (a
    :class:`~bigdl_tpu.telemetry.Watchdog` stays subscribed throughout
    — the worst case: every span also runs the anomaly detectors), and
    compare medians of on-steps vs off-steps:

    1. **Async training loop** — one ``LocalOptimizer.optimize`` run of
       ``train_steps`` iterations (the ``--loop-ab`` workload without
       the artificial host sleep); tracing flips every
       ``toggle_window`` steps inside the loop and the per-iteration
       entry timestamps give steady-state step intervals.
    2. **Serving steady state** — one warmed :class:`ServingEngine`
       session serving ``n_chunks`` fixed-shape request chunks (single
       bucket, zero recompiles), tracing flipped per chunk.

    Whole-run A/B measured +-10-40% run-to-run on this loaded box —
    engine startup/shutdown variance swamps a percent-level signal —
    so the measurement never leaves the session: drift cancels at
    window granularity and medians shrug off scheduler outliers.  The
    traced windows also produce the canonical newline-JSON metrics
    dump (``telemetry.write_metrics_jsonl``) when ``jsonl_path`` is
    set.

    With ``ship=True`` a live :class:`TelemetryShipper` stays
    subscribed to the same tracer for the whole session — its
    per-span subscriber callback and background segment flushes are
    then part of the traced-window cost, so the number bounds the
    FULL cluster-shipping path (docs/observability.md), not just
    in-process spans.

    With ``xray=True`` the Program X-ray ledger samples HBM inside
    every traced window (on top of the per-dispatch registry
    accounting both arms already pay), so the overhead number bounds
    the full X-ray path — program table, forensics, ledger — and the
    artifact gains the program-table + HBM-report records.

    With ``flight=True`` the live ops plane is up for the whole
    session — an ephemeral-port :class:`DebugServer` scraping the
    train engine and an armed :class:`FlightRecorder` (whose span
    subscriber runs on EVERY recorded span — part of the traced-window
    cost), plus one forced ``/flightz``-style dump at a toggle-window
    boundary mid-run — so the gate bounds the plane's passive cost
    (docs/observability.md §Live ops plane).

    With ``requests=True`` the Request X-ray rides the same toggle:
    the serving engine's per-request budget ledger and exemplar
    reservoir already follow ``tracer.enabled`` (one attribute check
    when dark), so their per-request cost lands in the traced windows
    by construction, and the workload recorder is armed for exactly
    the traced chunks — the same on-vs-off statistic then bounds the
    FULL request plane (ledger + p99 reservoir + record-to-JSONL),
    docs/observability.md §Request X-ray.
    """
    import jax
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu import telemetry
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.optim import SGD, Trigger
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.serving import ServingEngine

    import gc

    tracer = telemetry.get_tracer()
    was_enabled = tracer.enabled
    # timeit rationale: span allocations trigger collections, and an
    # allocation-triggered GC pause lands inside a TRACED window by
    # construction — aliasing amortizable cost onto one parity.  Both
    # arms run GC-disabled (the ring buffer bounds live spans).
    gc_was = gc.isenabled()
    gc.disable()

    def median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    # --- arm 1: async training loop -----------------------------------
    rs = np.random.RandomState(0)
    x = rs.randn(4 * batch, hidden).astype(np.float32)
    y = rs.randint(0, 8, 4 * batch)
    layers = []
    for _ in range(depth):
        layers += [nn.Linear(hidden, hidden), nn.Tanh()]
    layers += [nn.Linear(hidden, 8)]
    model = nn.Sequential(*layers)
    crit = nn.ClassNLLCriterion(logits=True)

    shared = {}

    class _ToggledEngine(LocalOptimizer):
        """One compiled step for every run (the A/B compares loop
        overhead, so XLA compile noise stays out), and the tracer
        toggled every ``toggle_window`` iterations from inside the
        loop with entry timestamps recorded per iteration."""

        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.step_t = []
            self.step_traced = []

        def _build_step_fn(self, m):
            if "step" not in shared:
                shared["step"] = super()._build_step_fn(m)
            return shared["step"]

        def _one_iteration(self, *a, **k):
            i = len(self.step_t)
            tracer.enabled = (i // toggle_window) % 2 == 1
            if ledger is not None and tracer.enabled:
                # X-ray ledger cost lands in the traced windows only,
                # so the existing on-vs-off statistic gates it
                ledger.maybe_sample()
            if flight_rec is not None and i == toggle_window * (
                    (train_steps // 2) // toggle_window):
                # forced dump ON a toggle boundary: that step is
                # dropped from the stats anyway, so the dump's wall
                # cost never contaminates a measured interval
                flight_rec.dump(trigger="flightz",
                                note="bench forced mid-run dump",
                                force=True)
            self.step_t.append(time.perf_counter())
            self.step_traced.append(tracer.enabled)
            super()._one_iteration(*a, **k)

    wd = telemetry.Watchdog(log=None).attach(tracer)

    ledger = None
    ledger_every_was = None
    if xray:
        from bigdl_tpu.telemetry import programs as _programs

        ledger = _programs.get_hbm_ledger()
        # sample on (nearly) every traced window so short gate runs
        # still exercise the full ledger path; restored below
        ledger_every_was = ledger.every_s
        ledger.every_s = 0.05

    flight_rec = None
    debug_srv = None
    flight_dir = None
    flight_bundles = 0
    flight_scrape_bytes = 0
    if flight:
        import shutil as _shutil
        import tempfile as _tempfile

        from bigdl_tpu.telemetry.debug_server import DebugServer
        from bigdl_tpu.telemetry.flightrecorder import FlightRecorder

        flight_dir = _tempfile.mkdtemp(prefix="bigdl-bench-flight-")
        flight_rec = FlightRecorder(out_dir=flight_dir,
                                    min_interval_s=0.0).arm()
        flight_rec.add_metrics(
            "train", lambda: getattr(engine, "metrics", None))
        debug_srv = DebugServer(port=0).start()
        debug_srv.add_metrics(
            "train", lambda: getattr(engine, "metrics", None))
        debug_srv.set_flight_recorder(flight_rec)

    shipper = None
    ship_dir = None
    ship_segments = 0
    if ship:
        import glob as _glob
        import shutil
        import tempfile

        from bigdl_tpu.telemetry.cluster import SEGMENT_GLOB, TelemetryShipper

        ship_dir = tempfile.mkdtemp(prefix="bigdl-bench-ship-")
        shipper = TelemetryShipper(ship_dir, "bench-host",
                                   clock_offset_fn=lambda: 0.0)
        # `engine` binds later in this scope; by the first flush the
        # loop is live and the closure resolves
        shipper.add_metrics("train",
                            lambda: getattr(engine, "metrics", None))
        shipper.start()

    ds = DataSet.from_arrays(x, y, batch_size=batch)
    engine = _ToggledEngine(model, ds, crit,
                            Trigger.max_iteration(train_steps))
    engine.set_optim_method(SGD(0.1, momentum=0.9))
    try:
        engine.optimize()
    finally:
        tracer.disable()

    if debug_srv is not None:
        # one real HTTP scrape against the session's own endpoint:
        # proves the plane was live while the engine trained
        import urllib.request as _urlreq

        with _urlreq.urlopen(debug_srv.local_url("/metricsz"),
                             timeout=5.0) as resp:
            flight_scrape_bytes = len(resp.read())

    # interval i = iteration i's wall (entry to next entry), labeled by
    # the tracing state it ran under; drop the first window (warmup)
    # and each window's first step (the toggle boundary)
    t, traced = engine.step_t, engine.step_traced
    steps = {False: [], True: []}
    for i in range(toggle_window, len(t) - 1):
        if i % toggle_window == 0:
            continue
        steps[traced[i]].append(t[i + 1] - t[i])
    train_off = median(steps[False])
    train_on = median(steps[True])
    train_overhead = train_on / train_off - 1

    # --- arm 2: serving steady state ----------------------------------
    # a realistically-sized forward (not the --serve-ab toy MLP): the
    # overhead gate is per-request instant cost RELATIVE to a model
    # whose compute resembles production serving, not a µs-scale toy
    # where any host-side work at all reads as a large fraction
    serve_layers = [nn.Linear(SERVE_FEAT, 512), nn.Tanh()]
    for _ in range(5):
        serve_layers += [nn.Linear(512, 512), nn.Tanh()]
    serve_model = nn.Sequential(*serve_layers, nn.Linear(512, 8))
    serve_var = serve_model.init(jax.random.PRNGKey(0))
    sample = rs.rand(32, SERVE_FEAT).astype(np.float32)  # one bucket
    serve_chunk = 32

    # a generous batch window: sub-ms submit-loop jitter must not flip
    # how the dispatcher coalesces a chunk (different batch splits move
    # chunk wall by ~1ms — an artifact that would drown the signal)
    serve_engine = ServingEngine(serve_model, serve_var,
                                 buckets=SERVE_BUCKETS,
                                 batch_sizes=SERVE_BATCH_SIZES,
                                 batch_window_ms=6.0,
                                 max_queue=4 * serve_chunk)

    def serve_one_chunk(latencies: list):
        # per-request latency, delivery stamped by a done-callback so
        # the sample is the request's true enqueue->deliver time
        pending = []
        for _ in range(serve_chunk):
            t0 = time.perf_counter()
            fut = serve_engine.submit(sample)
            slot = [t0, None]
            fut.add_done_callback(
                lambda f, s=slot: s.__setitem__(
                    1, time.perf_counter()))
            pending.append((fut, slot))
        for fut, slot in pending:
            fut.result(60)
            # the result is set before its callbacks run: a waiter can
            # get here first on a loaded box
            latencies.append((slot[1] or time.perf_counter()) - slot[0])

    req_dir = None
    req_recorded = 0
    if requests:
        import tempfile as _req_tempfile

        from bigdl_tpu.telemetry import workload as _workload

        req_dir = _req_tempfile.mkdtemp(prefix="bigdl-bench-req-")
        req_path = os.path.join(req_dir, "workload.jsonl")

    serve_one_chunk([])  # settle dispatch after construction warmup
    lats = {False: [], True: []}
    for i in range(n_chunks):
        tracer.enabled = i % 2 == 1
        if requests:
            # recorder armed for exactly the traced chunks, so its
            # per-submit JSONL write is part of the gated cost (each
            # arm() truncates — fine, the stream is a throwaway)
            if tracer.enabled:
                _workload.arm(req_path)
            else:
                _workload.disarm()
        if ledger is not None and tracer.enabled:
            ledger.maybe_sample()
        serve_one_chunk(lats[tracer.enabled])
    tracer.disable()
    req_xray = None
    req_exemplars = None
    if requests:
        import shutil as _req_shutil

        _workload.disarm()
        # the file holds the LAST traced chunk (each arm() truncates):
        # proof the recorder was live on the gated path
        req_recorded = max(
            0, sum(1 for ln in open(req_path) if ln.strip()) - 1)
        req_xray = serve_engine.xray.summary()
        req_exemplars = serve_engine.exemplars.summary()
        _req_shutil.rmtree(req_dir, ignore_errors=True)
    wd.close()
    if shipper is not None:
        shipper.close()  # final flush + unsubscribe
        ship_segments = len(
            _glob.glob(os.path.join(ship_dir, SEGMENT_GLOB)))
        shutil.rmtree(ship_dir, ignore_errors=True)
    if flight_rec is not None:
        flight_bundles = len(flight_rec.bundles())
        flight_rec.close()
        debug_srv.close()
        _shutil.rmtree(flight_dir, ignore_errors=True)
    # median request latency pools serve_chunk samples per chunk, so
    # the estimate rides on ~1000 samples per parity instead of ~30
    # chunk walls — the difference between +-2% and +-0.5% noise here
    serve_off = median(lats[False])
    serve_on = median(lats[True])
    serve_overhead = serve_on / serve_off - 1

    n_spans = len(tracer.spans())
    engine_snap = serve_engine.metrics.snapshot()
    serve_engine.close()

    # the canonical newline-JSON artifact: phase metrics of the traced
    # session, one self-describing record per line
    records = [
        telemetry.metrics_record(
            "telemetry_ab_train", engine.metrics,
            extra={"step_ms_traced": round(1e3 * train_on, 4)}),
        {"record": "telemetry_ab_serve", "unix_time": round(time.time(), 3),
         "snapshot": engine_snap},
    ]
    xray_programs = 0
    xray_samples = 0
    xray_forensics = 0
    if ledger is not None:
        from bigdl_tpu.telemetry import programs as _programs

        registry = _programs.get_program_registry()
        xray_programs = len(registry)
        xray_samples = ledger.report()["samples"]
        xray_forensics = len(registry.forensic_records())
        records.append({"record": "xray_programs",
                        "unix_time": round(time.time(), 3),
                        "programs": registry.records()})
        records.append(ledger.report())
        ledger.every_s = ledger_every_was
    if jsonl_path:
        telemetry.write_metrics_jsonl(jsonl_path, records)
    if gc_was:
        gc.enable()
        gc.collect()
    if was_enabled:
        tracer.enable()

    return {
        "metric": "telemetry_overhead",
        "value": round(max(train_overhead, serve_overhead), 4),
        "unit": "fraction of steady-state time, tracing on vs off",
        "detail": {
            "train_steps": train_steps, "toggle_window": toggle_window,
            "n_chunks": n_chunks, "serve_chunk": serve_chunk,
            "train_step_off_ms": round(1e3 * train_off, 4),
            "train_step_on_ms": round(1e3 * train_on, 4),
            "train_overhead": round(train_overhead, 4),
            "train_samples": [len(steps[False]), len(steps[True])],
            "serve_latency_off_ms": round(1e3 * serve_off, 4),
            "serve_latency_on_ms": round(1e3 * serve_on, 4),
            "serve_overhead": round(serve_overhead, 4),
            "serve_samples": [len(lats[False]), len(lats[True])],
            "spans_in_ring": n_spans,
            "watchdog": wd.counters,
            "jsonl_records": len(records) if jsonl_path else 0,
            "ship": ship,
            "ship_segments": ship_segments,
            "xray": xray,
            "xray_programs": xray_programs,
            "hbm_samples": xray_samples,
            "forensics": xray_forensics,
            "flight": flight,
            "flight_bundles": flight_bundles,
            "flight_scrape_bytes": flight_scrape_bytes,
            "requests": requests,
            "requests_recorded": req_recorded,
            "request_xray": req_xray,
            "request_exemplars": req_exemplars,
        },
    }


def numerics_ab(steps: int = 120, batch: int = 4096, hidden: int = 128,
                depth: int = 3, window: int = 10) -> dict:
    """In-graph numerics-statistics overhead A/B
    (docs/observability.md §Numerics).  CPU-runnable, gated < 3% in
    tests/test_numerics.py.

    Compiles the canonical train step twice from the same model —
    stats-free and with a :class:`~bigdl_tpu.telemetry.numerics
    .NumericsSpec` (per-layer norms, non-finite counts, histogram
    subsamples fused into the update) — and alternates ``window``-step
    bursts of each inside one process so clock drift cancels at window
    granularity.  Both arms donate and thread their own state through,
    exactly like the async engine does; the stats pytree stays on
    device (never fetched), so the number isolates the pure in-graph
    cost the ``BIGDL_TPU_NUMERICS=1`` knob adds to every step.

    Sizing rationale (same argument as the serve arm above): the stats
    cost is O(params) per step while the step's compute is
    O(batch x params), so the honest reference workload is the paper's
    large-batch regime (the reference scales to 8192 global batch) —
    on a µs-scale small-batch toy ANY O(params) work at all reads as
    tens of percent, an artifact of CPU arithmetic intensity, not a
    property of the stats graph.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import make_train_step
    from bigdl_tpu.telemetry import numerics as numerics_mod

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(batch, hidden).astype(np.float32))
    y = jnp.asarray(rs.randint(0, 8, batch).astype(np.int32))

    layers = []
    for _ in range(depth):
        layers += [nn.Linear(hidden, hidden), nn.Tanh()]
    model = nn.Sequential(*layers, nn.Linear(hidden, 8))
    crit = nn.ClassNLLCriterion(logits=True)
    optim_methods = {"__all__": SGD(0.1, momentum=0.9)}
    lrs = [jnp.float32(0.1)]
    spec = numerics_mod.spec_for(model)

    def fresh_state():
        var = model.init(jax.random.PRNGKey(0))
        params, state = var["params"], var["state"]
        opt = {name: m.init_state(
            params if name == "__all__" else {name: params[name]})
            for name, m in optim_methods.items()}
        return params, state, opt

    arms = {}
    for name, num in (("off", None), ("on", spec)):
        step = jax.jit(
            make_train_step(model, crit, optim_methods, numerics=num),
            donate_argnums=(0, 1, 2))
        p, s, o = fresh_state()
        # warmup: compile + settle allocator
        outs = step(p, s, o, jnp.int32(0), jax.random.PRNGKey(7), x, y,
                    lrs)
        jax.block_until_ready(outs[3])
        arms[name] = {"step": step, "state": outs[:3], "times": []}

    def burst(arm, base, n):
        step, (p, s, o) = arm["step"], arm["state"]
        t = []
        for i in range(n):
            t0 = time.perf_counter()
            outs = step(p, s, o, jnp.int32(base + i),
                        jax.random.PRNGKey(7), x, y, lrs)
            p, s, o = outs[:3]
            jax.block_until_ready(outs[3])
            t.append(time.perf_counter() - t0)
        arm["state"] = (p, s, o)
        # drop the burst's first step (cache/toggle boundary)
        arm["times"].extend(t[1:])

    it = 0
    while it < steps:
        for name in ("off", "on"):
            burst(arms[name], it, window)
        it += window

    def median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    off = median(arms["off"]["times"])
    on = median(arms["on"]["times"])
    overhead = on / off - 1
    return {
        "metric": "numerics_overhead",
        "value": round(overhead, 4),
        "unit": "fraction of steady-state step time, stats on vs off",
        "detail": {
            "steps": steps, "window": window, "batch": batch,
            "hidden": hidden, "depth": depth,
            "layers": len(spec.layers), "hist": spec.hist,
            "step_off_ms": round(1e3 * off, 4),
            "step_on_ms": round(1e3 * on, 4),
            "samples": [len(arms["off"]["times"]),
                        len(arms["on"]["times"])],
        },
    }


def build_decode_model():
    """The decode A/B's canonical model: a small causal Transformer LM
    with the cached-decode trio (prefill/decode_step/init_cache).  The
    config lives in tools/kernel_shapes.py (DECODE_MODEL) so the bench,
    the `decode_step` graft-lint target, and the deviceless AOT check
    (tools/serving_aot_check.py --decode) can never drift apart."""
    import bigdl_tpu.nn as nn
    from tools.kernel_shapes import DECODE_MODEL

    return nn.Transformer(**DECODE_MODEL)


def decode_ab(n_requests: int = 12, t_decode: int = 128,
              reps: int = 3, production_arms: bool = True) -> dict:
    """Cached-decode A/B (docs/decoding.md).  CPU-runnable, gated in
    tests/test_decode.py like ``--loop-ab``/``--serve-ab``.

    Two comparisons (plus the ISSUE-14 production arms, see
    :func:`decode_production_arms`; ``production_arms=False`` skips
    them):

    1. **Cached vs re-forward generate** — ``Transformer.generate``
       with the KV cache (one O(1) step per token) against the seed
       ``use_cache=False`` path (a full causal forward over the growing
       prefix per step, O(T^2)) at ``t_decode`` steps, both as single
       jitted programs, compile excluded.  Gate: >= 3x at T >= 128.
    2. **Continuous vs static batching** — the same ``DecodeEngine``
       serving mixed-length greedy traffic with token-granularity slot
       refill (``continuous=True``) against run-to-completion waves
       (``continuous=False``, admit only into an empty grid).  Gate:
       higher tokens/s, and ZERO steady-state recompiles in both arms
       across the occupancy churn.

    CPU caveat (PERF.md): this is a host-side A/B at a toy width; what
    a tick costs on the chip is not measured here.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.serving import DecodeEngine
    from tools.kernel_shapes import (DECODE_MAX_LEN, DECODE_PREFILL_BATCH,
                                     DECODE_PROMPT_BUCKETS, DECODE_SLOTS)

    model = build_decode_model()
    variables = model.init(jax.random.PRNGKey(0))
    params, state = variables["params"], variables["state"]

    # -- 1: single-stream cached vs re-forward generate ----------------
    ids0 = jnp.zeros((1,), jnp.int32)
    gen = {
        True: jax.jit(lambda ids: model.generate(
            params, state, ids, t_decode, beam_size=1, use_cache=True)),
        False: jax.jit(lambda ids: model.generate(
            params, state, ids, t_decode, beam_size=1, use_cache=False)),
    }
    seqs = {}
    times = {}
    for cached in (True, False):
        seqs[cached] = np.asarray(gen[cached](ids0)[0])  # compile+settle
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(gen[cached](ids0)[0])
            best = min(best, time.perf_counter() - t0)
        times[cached] = best
    # numerics spot-check rides along: same greedy sequence both paths
    np.testing.assert_array_equal(seqs[True], seqs[False])
    speedup_cached = times[False] / times[True]

    # -- 2: continuous vs static batching on mixed-length traffic ------
    rs = np.random.RandomState(0)
    lens = [DECODE_PROMPT_BUCKETS[i % len(DECODE_PROMPT_BUCKETS)] - 1 - (i % 3)
            for i in range(n_requests)]
    prompts = [rs.randint(1, 8, (t,)) for t in lens]
    budgets = [(16, 32, 64, 96)[i % 4] for i in range(n_requests)]

    def run(continuous: bool) -> dict:
        engine = DecodeEngine(
            model, variables, slots=DECODE_SLOTS, max_len=DECODE_MAX_LEN,
            prompt_buckets=DECODE_PROMPT_BUCKETS,
            prefill_batch_sizes=DECODE_PREFILL_BATCH,
            eos_id=None, continuous=continuous)
        declared = engine.declared_programs()
        after_warmup = engine.metrics.recompiles
        t0 = time.perf_counter()
        futs = [engine.submit(p, b) for p, b in zip(prompts, budgets)]
        outs = [f.result(300) for f in futs]
        wall = time.perf_counter() - t0
        tokens = sum(len(o) for o in outs)
        rec = {
            "wall_s": round(wall, 3),
            "tokens": tokens,
            "tokens_per_sec": round(tokens / wall, 1),
            "ticks": engine.metrics.base.count("decode_tick"),
            "slot_occupancy": round(engine.metrics.slot_occupancy(), 4),
            "p50_tick_ms": round(engine.metrics.tick_ms(50), 3),
            "p95_tick_ms": round(engine.metrics.tick_ms(95), 3),
            "declared_programs": declared,
            "steady_state_recompiles":
                engine.metrics.recompiles - after_warmup,
            "outs": outs,
        }
        engine.close()
        return rec

    cont = run(continuous=True)
    static = run(continuous=False)
    # both admission policies must produce identical greedy tokens
    for a, b in zip(cont.pop("outs"), static.pop("outs")):
        np.testing.assert_array_equal(a, b)

    production = decode_production_arms(model, variables) \
        if production_arms else None

    return {
        "metric": "cached_decode_speedup",
        "value": round(speedup_cached, 3),
        "unit": "x vs re-forward generate",
        "detail": {
            "t_decode": t_decode,
            "reforward_wall_s": round(times[False], 3),
            "cached_wall_s": round(times[True], 3),
            "n_requests": n_requests,
            "continuous": cont,
            "static": static,
            "continuous_vs_static": round(
                cont["tokens_per_sec"] / static["tokens_per_sec"], 3),
            "production": production,
        },
    }


def decode_production_arms(model=None, variables=None,
                           n_requests: int = 12) -> dict:
    """Leg 3 of the decode A/B (ISSUE 14): the production decode path
    on long-context mixed traffic — prompts past the largest declared
    bucket arrive alongside short ones, so every arm exercises chunked
    prefill.  Four A/B arms against the dense greedy baseline:

    * **sampling** — per-request temperature/top-k/top-p inside the
      tick; the seed-reproducibility probe submits the same seed twice.
    * **paged** — 2x the slots on the SAME HBM budget (the 4-slot
      worst-case page pool, tools/kernel_shapes.DECODE_PAGES); the
      HbmLedger resident lane is the meter proving peak paged bytes
      stay inside the dense arm's fixed reservation.
    * **int8_kv** — the paged pool quantized (ops/paged_kv.py):
      ~cache-bytes/2 or better, token parity within tolerance.
    * **speculative** — draft (DECODE_DRAFT_MODEL) proposes
      DECODE_DRAFT_K tokens, one verify pass accepts; outputs exactly
      match dense greedy, acceptance rate and tokens/s ratio recorded.

    Every arm must serve with ZERO steady-state recompiles.
    """
    import threading

    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.serving import DecodeEngine
    from bigdl_tpu.telemetry import programs as _programs
    from tools.kernel_shapes import (DECODE_CHUNK, DECODE_DRAFT_K,
                                     DECODE_DRAFT_MODEL, DECODE_MAX_LEN,
                                     DECODE_PAGE, DECODE_PAGES,
                                     DECODE_PREFILL_BATCH,
                                     DECODE_PROMPT_BUCKETS, DECODE_SLOTS)

    import jax

    if model is None:
        model = build_decode_model()
        variables = model.init(jax.random.PRNGKey(0))

    rs = np.random.RandomState(1)
    vocab = 8
    # long-context mix: two short bucket residents, one chunked long
    # prompt, one mid -- cycled over the request count
    lens = [(15, 12, 40, 7)[i % 4] for i in range(n_requests)]
    budgets = [(24, 48, 32, 40)[i % 4] for i in range(n_requests)]
    prompts = [rs.randint(1, vocab, (t,)) for t in lens]

    draft_model = nn.Transformer(**DECODE_DRAFT_MODEL)
    draft_var = draft_model.init(jax.random.PRNGKey(0))
    ledger = _programs.get_hbm_ledger()

    def run_arm(name, *, slots=DECODE_SLOTS, sampling=False, probe=None,
                **eng_kw):
        engine = DecodeEngine(
            model, variables, slots=slots, max_len=DECODE_MAX_LEN,
            prompt_buckets=DECODE_PROMPT_BUCKETS,
            prefill_batch_sizes=DECODE_PREFILL_BATCH,
            eos_id=None, prefill_chunk=DECODE_CHUNK, **eng_kw)
        after_warmup = engine.metrics.recompiles
        resident_name = engine._resident_name
        peak = {"resident": 0, "slots": 0, "pages": 0}
        stop = threading.Event()

        def sampler():
            while not stop.is_set():
                rec = ledger.sample()
                if rec and "resident" in rec:
                    peak["resident"] = max(
                        peak["resident"],
                        rec["resident"].get(resident_name, 0))
                peak["slots"] = max(peak["slots"],
                                    int(engine._active.sum()))
                peak["pages"] = max(peak["pages"],
                                    engine._kv.pages_in_use)
                stop.wait(0.002)

        th = threading.Thread(target=sampler, daemon=True)
        th.start()
        t0 = time.perf_counter()
        if sampling:
            futs = [engine.submit(p, b, temperature=0.8, top_k=8,
                                  top_p=0.95, seed=1000 + i)
                    for i, (p, b) in enumerate(zip(prompts, budgets))]
        else:
            futs = [engine.submit(p, b)
                    for p, b in zip(prompts, budgets)]
        outs = [f.result(600) for f in futs]
        wall = time.perf_counter() - t0
        stop.set()
        th.join(2)
        probe_rec = probe(engine) if probe else None
        m = engine.metrics
        tokens = sum(len(o) for o in outs)
        rec = {
            "wall_s": round(wall, 3),
            "tokens": tokens,
            "tokens_per_sec": round(tokens / wall, 1),
            "ticks": m.base.count("decode_tick"),
            "p50_tick_ms": round(m.tick_ms(50), 3),
            "p99_tick_ms": round(m.tick_ms(99), 3),
            "prefill_chunks": m.prefill_chunks,
            "pages_in_use": m.pages_in_use,
            "page_evictions": m.page_evictions,
            "peak_resident_bytes": peak["resident"],
            "peak_active_slots": peak["slots"],
            "spec_acceptance_rate": round(m.spec_acceptance_rate(), 4),
            "declared_programs": engine.declared_programs(),
            "steady_state_recompiles": m.recompiles - after_warmup,
            "outs": outs,
        }
        if probe_rec:
            rec.update(probe_rec)
        if engine.kv_layout == "paged":
            rec["peak_pages_in_use"] = peak["pages"]
            rec["page_bytes_per_page"] = engine._kv.page_bytes
            rec["pool_bytes"] = (engine._kv.num_pages
                                 * engine._kv.page_bytes)
        else:
            rec["cache_bytes"] = engine._kv.resident_bytes()
        engine.close()
        return rec

    def seed_probe(engine):
        # reproducibility: identical seed => identical stream
        a = engine.generate(prompts[0], 16, temperature=0.8, top_k=8,
                            top_p=0.95, seed=7, timeout=120)
        b = engine.generate(prompts[0], 16, temperature=0.8, top_k=8,
                            top_p=0.95, seed=7, timeout=120)
        return {"seed_reproducible": bool(np.array_equal(a, b))}

    dense = run_arm("dense")
    sampling = run_arm("sampling", sampling=True, probe=seed_probe)
    paged = run_arm("paged", slots=2 * DECODE_SLOTS, kv_layout="paged",
                    page_size=DECODE_PAGE, num_pages=DECODE_PAGES)
    int8_kv = run_arm("int8_kv", slots=2 * DECODE_SLOTS,
                      kv_layout="paged", page_size=DECODE_PAGE,
                      num_pages=DECODE_PAGES, kv_dtype="int8")
    spec = run_arm("speculative", draft=(draft_model, draft_var),
                   draft_k=DECODE_DRAFT_K)

    # paged + speculative greedy arms must reproduce dense greedy
    dense_outs = dense.pop("outs")
    for arm in (paged, spec):
        for a, b in zip(dense_outs, arm.pop("outs")):
            np.testing.assert_array_equal(a, b)
    # int8: token parity within tolerance (quantization may flip rare
    # near-tie argmaxes) -- report the agreement fraction
    agree = match = 0
    for a, b in zip(dense_outs, int8_kv.pop("outs")):
        n = min(len(a), len(b))
        agree += int(np.sum(np.asarray(a[:n]) == np.asarray(b[:n])))
        match += n
    int8_kv["token_agreement"] = round(agree / max(match, 1), 4)
    sampling.pop("outs")

    dense["outs_tokens"] = sum(len(o) for o in dense_outs)
    return {
        "traffic": {"n_requests": len(prompts), "prompt_lens": lens,
                    "budgets": budgets, "chunk": DECODE_CHUNK},
        "dense": dense,
        "sampling": sampling,
        "paged": paged,
        "int8_kv": int8_kv,
        "speculative": spec,
        "spec_speedup": round(spec["tokens_per_sec"]
                              / dense["tokens_per_sec"], 3),
        "paged_capacity_x": round(2 * DECODE_SLOTS / DECODE_SLOTS, 1),
        "paged_budget_ok": bool(paged["peak_resident_bytes"]
                                <= dense["cache_bytes"]),
        "int8_bytes_ratio": round(int8_kv["page_bytes_per_page"]
                                  / paged["page_bytes_per_page"], 4),
    }


def elastic_ab(steps: int = 40, warmup: int = 5,
               iters: int = 300, ckpt_every: int = 15) -> dict:
    """Elastic fault-tolerance A/B (CPU-runnable; PERF.md §elastic).

    Leg 1 — compressed-wire vs plain dp allreduce: the same LeNet5
    train step over the full local device set, plain fp32 gradient
    exchange vs bf16 wire + fp32 master accumulation
    (``bigdl_tpu.distributed.compression``).  On CPU both reductions
    run over shared memory, so the delta is the cast/accumulate
    overhead compression ADDS — the interconnect bytes it SAVES only
    show up on the chip (ROADMAP.md S6).

    Leg 2 — kill -9 recovery window: two single-host ElasticAgents
    (policy restart + shrink) drive the deterministic worker job;
    after the first COMMIT the shrink host's worker is SIGKILLed and
    the window from kill to the survivor generation's first recorded
    loss (re-rendezvous + restore + recompile) is measured.
    """
    import glob
    import shutil
    import signal
    import statistics
    import tempfile
    import threading
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu import models
    from bigdl_tpu.distributed.compression import (
        build_compressed_dp_train_step)
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.parallel.data_parallel import build_dp_train_step
    from bigdl_tpu.parallel.mesh import MeshConfig, make_mesh

    devices = jax.devices()
    ndata = len(devices)
    mesh = make_mesh(MeshConfig(data=ndata), devices)
    model = models.LeNet5()
    crit = nn.ClassNLLCriterion(logits=True)
    batch = 8 * ndata
    rs = np.random.RandomState(0)
    feats = rs.rand(batch, 28, 28, 1).astype(np.float32)
    targs = rs.randint(0, 10, batch).astype(np.int64)

    def run_leg(build) -> tuple:
        methods = {"__all__": SGD(1e-2, momentum=0.9)}
        step, placement = build(methods)
        variables = model.init(jax.random.PRNGKey(0))
        params = jax.device_put(variables["params"], placement["params"])
        state = jax.device_put(variables["state"],
                               placement["model_state"])
        opt = jax.device_put(
            {"__all__": methods["__all__"].init_state(
                variables["params"])},
            placement["opt_states"])
        x = jax.device_put(jnp.asarray(feats), placement["batch"])
        y = jax.device_put(jnp.asarray(targs), placement["target"])
        lrs = [jnp.float32(1e-2)]
        rng = jnp.zeros((2,), jnp.uint32)
        times = []
        for i in range(warmup + steps):
            t0 = time.perf_counter()
            params, state, opt, loss = step(
                params, state, opt, jnp.int32(i), rng, x, y, lrs)
            jax.block_until_ready((params, loss))
            if i >= warmup:
                times.append((time.perf_counter() - t0) * 1e3)
        return statistics.median(times), float(loss)

    # zero1=False: the compressed step keeps opt state replicated, so
    # the plain leg must too — otherwise the A/B also measures ZeRO-1
    plain_ms, plain_loss = run_leg(
        lambda m: build_dp_train_step(model, crit, m, mesh, zero1=False))
    comp_ms, comp_loss = run_leg(
        lambda m: build_compressed_dp_train_step(
            model, crit, m, mesh, wire_dtype="bf16"))

    # ---- leg 2: kill -9 the shrink host's worker, time the recovery
    from bigdl_tpu.distributed.elastic import ElasticAgent

    wd = tempfile.mkdtemp(prefix="elastic-ab-")
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO
    # the workers are pinned to the CPU backend: this process may hold
    # an accelerator, and a chip belongs to one process at a time
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["BIGDL_ELASTIC_ITERS"] = str(iters)
    env["BIGDL_ELASTIC_CKPT_EVERY"] = str(ckpt_every)

    results, threads = {}, []
    for host, policy in (("h0", "restart"), ("h1", "shrink")):
        agent = ElasticAgent(wd, host, policy=policy, env=env,
                             rendezvous_timeout_s=180.0)
        t = threading.Thread(
            target=lambda k=host, a=agent: results.__setitem__(
                k, a.run()),
            name=f"agent-{host}", daemon=True)
        t.start()
        threads.append(t)

    ckpt_root = os.path.join(wd, "ckpt")
    pid_file = os.path.join(wd, "worker-g1-h1.pid")
    deadline = time.monotonic() + 240
    while time.monotonic() < deadline:
        if os.path.isdir(ckpt_root) and any(
                os.path.exists(os.path.join(ckpt_root, d, "COMMIT"))
                for d in os.listdir(ckpt_root)) \
                and os.path.exists(pid_file):
            break
        time.sleep(0.02)
    else:
        raise RuntimeError("no COMMIT appeared before the kill window")
    kill_t = time.monotonic()
    os.kill(int(open(pid_file).read()), signal.SIGKILL)

    def survivor_gen_recording() -> bool:
        for path in glob.glob(os.path.join(wd, "losses-g*.jsonl")):
            gen = int(os.path.basename(path).split("-")[1][1:])
            if gen >= 2 and os.path.getsize(path) > 0:
                return True
        return False

    recovery_s = None
    deadline = time.monotonic() + 240
    while time.monotonic() < deadline:
        if survivor_gen_recording():
            recovery_s = time.monotonic() - kill_t
            break
        time.sleep(0.02)
    for t in threads:
        t.join(timeout=300)

    covered = set()
    for path in glob.glob(os.path.join(wd, "losses-g*.jsonl")):
        for line in open(path):
            rec = json.loads(line)
            if rec["rank"] == 0:
                covered.add(rec["it"])
    shutil.rmtree(wd, ignore_errors=True)

    return {
        "devices": ndata,
        "batch": batch,
        "steps": steps,
        "plain_step_ms": round(plain_ms, 3),
        "compressed_step_ms": round(comp_ms, 3),
        "compressed_over_plain_x": round(comp_ms / plain_ms, 3),
        "final_loss_plain": round(plain_loss, 5),
        "final_loss_compressed": round(comp_loss, 5),
        "kill9": {
            "iters": iters,
            "ckpt_every": ckpt_every,
            "recovery_s": (round(recovery_s, 2)
                           if recovery_s is not None else None),
            "statuses": results,
            "iterations_covered": len(covered),
        },
    }


def fused_ab(steps: int = 10, temps_batch: int = 256, temps_hw: int = 28,
             timing_batch: int = 16, timing_hw: int = 14,
             n_in: int = 256, planes: int = 64, n_blocks: int = 3) -> dict:
    """Fused-block remat A/B: ``BIGDL_TPU_FUSED_REMAT`` on vs off on a
    chain of :class:`nn.FusedBottleneck` blocks (docs/autotune.md §remat,
    PERF.md §fused-conv).  CPU-runnable.

    Fusion traded HBM bandwidth for capacity: every fused kernel saves
    its RAW conv output as a custom_vjp residual and XLA keeps all of
    them live across the backward (+4 GB of temps on the fused
    ResNet-50 step; batch 512 stopped fitting).  The remat gate wraps
    each block in ``jax.checkpoint`` so residuals drop at the block
    boundary.  Three train-step compiles at the wide stage shape —
    fused+remat, fused no-remat, and the unfused ``bottleneck_block``
    graph baseline — are stamped with XLA's ``memory_analysis`` temps
    and registered with the Program X-ray registry, so the HbmLedger's
    CPU ``source="estimate"`` sample attributes them; the acceptance
    line is remat's temps returning to within 1 GB of the unfused
    envelope.  Both remat arms then run a timed steady-state loop at a
    CPU-sized shape with the tuned table live
    (``tuning.table_path()``), asserting ZERO steady-state recompiles
    via the jit cache size, mirrored into the registry's forensics.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.models.resnet import bottleneck_block
    from bigdl_tpu.ops.pallas import tuning
    from bigdl_tpu.telemetry import costmodel
    from bigdl_tpu.telemetry import programs as _programs

    lr = 0.05

    def make_blocks():
        return [nn.FusedBottleneck(n_in, planes, stride=1)
                for _ in range(n_blocks)]

    def make_step(blocks):
        def loss_fn(params, states, x):
            new_states = []
            for blk, p, s in zip(blocks, params, states):
                x, ns = blk.apply(p, s, x, training=True)
                new_states.append(ns)
            return jnp.sum(x.astype(jnp.float32)), new_states

        def step(params, states, x):
            (loss, new_states), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, states, x)
            new_params = jax.tree_util.tree_map(
                lambda p, g: p - lr * g.astype(p.dtype), params, grads)
            return new_params, new_states, loss

        return step

    def graph_step(graph):
        def loss_fn(params, state, x):
            out, new_state = graph.apply(params, state, x, training=True)
            return jnp.sum(out.astype(jnp.float32)), new_state

        def step(params, state, x):
            (loss, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, state, x)
            new_params = jax.tree_util.tree_map(
                lambda p, g: p - lr * g.astype(p.dtype), params, grads)
            return new_params, new_state, loss

        return step

    def with_remat(on: bool, fn):
        # the gate is read at TRACE time inside _FusedResBlock.apply, so
        # the env toggle must bracket every lower/first-dispatch
        prev = os.environ.get("BIGDL_TPU_FUSED_REMAT")
        os.environ["BIGDL_TPU_FUSED_REMAT"] = "1" if on else "0"
        try:
            return fn()
        finally:
            if prev is None:
                os.environ.pop("BIGDL_TPU_FUSED_REMAT", None)
            else:
                os.environ["BIGDL_TPU_FUSED_REMAT"] = prev

    registry = _programs.get_program_registry()

    # ---- arm 1: compile-only temps at the wide stage shape -----------
    # (n, 28, 28, 256)/planes 64 is the fused model's widest residual
    # stage; compile cost is batch-independent so the full bench batch
    # stays CPU-feasible when only lowered+compiled, never dispatched
    def temps_of(name, step_fn, params, states):
        x = jax.ShapeDtypeStruct(
            (temps_batch, temps_hw, temps_hw, n_in), jnp.bfloat16)
        lowered = jax.jit(step_fn).lower(params, states, x)
        compiled = lowered.compile()
        cost = costmodel.program_cost(name, lowered=lowered,
                                      compiled=compiled)
        registry.register_compile(
            name, _programs.signature_of({"x": x}), cost=cost,
            expected=True)
        return cost

    blocks = make_blocks()
    fparams = [b.init_params(jax.random.PRNGKey(7 + i))
               for i, b in enumerate(blocks)]
    fstates = [b.init_state() for b in blocks]
    cost_remat = with_remat(True, lambda: temps_of(
        "fused_ab:fused_remat", make_step(blocks), fparams, fstates))
    cost_raw = with_remat(False, lambda: temps_of(
        "fused_ab:fused_noremat", make_step(blocks), fparams, fstates))

    inp = nn.Input()
    xg = inp
    for _ in range(n_blocks):
        xg = bottleneck_block(xg, n_in, planes, 1)
    graph = nn.Graph([inp], [xg])
    gvars = graph.init(jax.random.PRNGKey(7))
    cost_unfused = temps_of("fused_ab:unfused", graph_step(graph),
                            gvars["params"], gvars["state"])

    # the ledger's CPU fallback: no device_memory_stats, so the sample
    # comes from the registry footprints the stamps above just fed
    ledger = _programs.get_hbm_ledger()
    hbm = ledger.sample() or {}

    # ---- arm 2: timed steady state + zero-recompile assertion --------
    tuned_path = tuning.table_path()
    tuned_entries = 0
    if tuned_path:
        try:
            tuned_entries = len(tuning.TunedTable.load(tuned_path))
        except Exception:
            pass

    def timed_arm(on: bool) -> dict:
        def run():
            blocks = make_blocks()
            params = [b.init_params(jax.random.PRNGKey(7 + i))
                      for i, b in enumerate(blocks)]
            states = [b.init_state() for b in blocks]
            rs = np.random.RandomState(0)
            x = jnp.asarray(rs.randn(timing_batch, timing_hw, timing_hw,
                                     n_in), jnp.bfloat16)
            name = f"fused_ab:step_remat_{'on' if on else 'off'}"
            step = jax.jit(make_step(blocks))
            for _ in range(2):  # compile + settle
                params, states, loss = step(params, states, x)
            float(loss)
            registry.register_compile(
                name, _programs.signature_of({"x": x}), expected=True)
            cache0 = step._cache_size()
            t0 = time.perf_counter()
            for _ in range(steps):
                params, states, loss = step(params, states, x)
                registry.record_call(name)
            float(loss)  # sync point
            ms = 1e3 * (time.perf_counter() - t0) / steps
            recompiles = step._cache_size() - cache0
            if recompiles:
                # mirror the miss into the registry so the forensic
                # trail names the program, like the engines do
                registry.register_compile(
                    name, _programs.signature_of(
                        {"x": x, "cache_size": step._cache_size()}),
                    expected=False)
            return {"ms_per_step": round(ms, 3),
                    "steady_state_recompiles": int(recompiles)}

        return with_remat(on, run)

    arm_on = timed_arm(True)
    arm_off = timed_arm(False)
    steady = (arm_on["steady_state_recompiles"]
              + arm_off["steady_state_recompiles"])
    assert steady == 0, (
        f"{steady} steady-state recompile(s) in the fused A/B loop "
        f"(forensics: {registry.forensic_records()[-3:]})")

    gib = float(1 << 30)
    remat_vs_unfused_gb = (cost_remat.temp_bytes
                           - cost_unfused.temp_bytes) / gib

    def _mem(c):
        return {"temp_bytes": int(c.temp_bytes),
                "temp_gib": round(c.temp_bytes / gib, 4),
                "argument_bytes": int(c.argument_bytes),
                "output_bytes": int(c.output_bytes)}

    return {
        "metric": "fused_remat_temp_shrink",
        "value": round(cost_raw.temp_bytes / max(cost_remat.temp_bytes, 1),
                       3),
        "unit": "x XLA temp bytes, fused no-remat vs remat "
                f"({n_blocks} blocks, batch {temps_batch})",
        "detail": {
            "temps_shape": [temps_batch, temps_hw, temps_hw, n_in],
            "fused_remat": _mem(cost_remat),
            "fused_noremat": _mem(cost_raw),
            "unfused": _mem(cost_unfused),
            "remat_vs_unfused_gib": round(remat_vs_unfused_gb, 4),
            "remat_within_1gib_of_unfused": remat_vs_unfused_gb <= 1.0,
            "timing_shape": [timing_batch, timing_hw, timing_hw, n_in],
            "steps": steps,
            "remat_on": arm_on,
            "remat_off": arm_off,
            "steady_state_recompiles": steady,
            "hbm_sample": {k: hbm.get(k) for k in
                           ("source", "bytes_in_use", "top")},
            "tuned_table": {"path": tuned_path,
                            "entries": tuned_entries},
        },
    }


_LAST = os.path.join(_REPO, "BENCH_LAST.json")


def write_bench_last(record: dict) -> None:
    """Artifact of the last bench invocation, whatever mode ran: ONE
    well-known path (BENCH_LAST.json, git-ignored) stamped with the
    argv and UTC time.  Atomic (tmp + rename)."""
    rec = dict(record)
    rec["argv"] = sys.argv[1:]
    rec["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                       time.gmtime())
    tmp = _LAST + ".tmp"
    with open(tmp, "w") as f:
        json.dump(rec, f, indent=1, sort_keys=True)
    os.replace(tmp, _LAST)


if __name__ == "__main__":
    if "--loop-ab" in sys.argv:
        # driver-loop async-vs-sync A/B (CPU-runnable; PERF.md §async)
        out = loop_ab()
        write_bench_last(out)
        print(json.dumps(out), flush=True)
    elif "--serve-ab" in sys.argv:
        # serving engine-vs-seed A/B (CPU-runnable; PERF.md §serving)
        out = serve_ab()
        write_bench_last(out)
        print(json.dumps(out), flush=True)
    elif "--decode-ab" in sys.argv:
        # cached-decode + continuous-batching A/B (CPU-runnable;
        # PERF.md §decoding)
        out = decode_ab()
        write_bench_last(out)
        print(json.dumps(out), flush=True)
    elif "--fused-ab" in sys.argv:
        # fused-block remat on/off A/B: XLA temp bytes vs the unfused
        # baseline + zero-steady-state-recompile assertion with the
        # tuned table live (CPU-runnable; PERF.md §fused-conv)
        out = fused_ab()
        write_bench_last(out)
        print(json.dumps(out), flush=True)
    elif "--elastic-ab" in sys.argv:
        # compressed-wire vs plain dp step + kill -9 recovery window
        # (CPU-runnable; PERF.md §elastic)
        out = elastic_ab()
        write_bench_last(out)
        print(json.dumps(out), flush=True)
    elif "--telemetry-ab" in sys.argv:
        # tracing-on vs tracing-off overhead on the async loop and
        # serving steady state (CPU-runnable; PERF.md §telemetry);
        # the JSONL dump is the canonical machine-readable artifact.
        # --ship adds a live cluster TelemetryShipper to the session
        # so the same gate bounds the cross-host shipping path;
        # --xray samples the Program X-ray HBM ledger inside every
        # traced window and appends the program-table records.
        # --numerics adds the in-graph gradient-statistics A/B
        # (docs/observability.md §Numerics) to the same report.
        # --flight keeps the live ops plane (debug server + armed
        # flight recorder, one forced mid-run dump) up for the whole
        # session so the same gate bounds its passive cost.
        # --requests rides the Request X-ray (budget ledger + exemplar
        # reservoir + workload recorder) on the same toggle so the
        # gate bounds the request plane too (docs/observability.md
        # §Request X-ray).
        out = telemetry_ab(
            jsonl_path=os.path.join(_REPO, "BENCH_TELEMETRY.jsonl"),
            ship="--ship" in sys.argv,
            xray="--xray" in sys.argv,
            flight="--flight" in sys.argv,
            requests="--requests" in sys.argv)
        if "--numerics" in sys.argv:
            out["numerics"] = numerics_ab()
        write_bench_last(out)
        print(json.dumps(out), flush=True)
    else:
        main()
