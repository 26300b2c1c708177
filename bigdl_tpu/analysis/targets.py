"""graft-lint target registry: every zoo model and train-step plan the
linter audits, reduced to jaxprs with NO execution.

Each target builds lazily (models are only instantiated when linted)
and traces via ``jax.make_jaxpr`` over ``jax.eval_shape`` templates, so
a full-zoo lint runs on a CPU-only box in seconds-per-model with no
device allocation at all.  Train-step targets carry the metadata rules
key off: the declared :class:`~bigdl_tpu.parallel.mesh.PlanInfo`, the
intended compute dtype, and the donated-leaf expectation.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from bigdl_tpu.analysis.core import LintContext


@dataclass
class LintTarget:
    name: str
    kind: str  # "model" | "train_step" | "inventory"
    build: Callable[[], LintContext]
    note: str = ""


_TARGETS: List[LintTarget] = []


def target(name: str, kind: str, note: str = ""):
    """Decorator registering a LintContext builder."""

    def deco(fn):
        _TARGETS.append(LintTarget(name, kind, fn, note))
        return fn

    return deco


def all_targets() -> Tuple[LintTarget, ...]:
    return tuple(_TARGETS)


def get_target(name: str) -> LintTarget:
    for t in _TARGETS:
        if t.name == name:
            return t
    raise KeyError(
        f"unknown lint target '{name}' "
        f"(have: {', '.join(t.name for t in _TARGETS)})")


# --------------------------------------------------------------------------
# tracing helpers
# --------------------------------------------------------------------------

def _structs(*shape_dtypes):
    import jax
    import jax.numpy as jnp  # noqa: F401

    return tuple(jax.ShapeDtypeStruct(s, d) for s, d in shape_dtypes)


def model_context(name: str, model, x, training: bool = False,
                  meta: Optional[Dict] = None) -> LintContext:
    """Trace ``model.apply`` over shape templates -> LintContext."""
    import jax

    var = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))

    def fwd(params, state, x_, rng):
        out, _ = model.apply(params, state, x_, training=training,
                             rng=rng if training else None)
        return out

    rng = jax.ShapeDtypeStruct((2,), "uint32")
    jaxpr = jax.make_jaxpr(fwd)(var["params"], var["state"], x, rng)
    return LintContext(name=name, kind="model", jaxpr=jaxpr,
                       meta=dict(meta or {}))


def step_context(name: str, jitted_step, args, donate_expected: int,
                 plan=None, compute_dtype=None,
                 meta: Optional[Dict] = None) -> LintContext:
    """Trace a jitted train step -> LintContext with donation/plan meta."""
    import jax

    jaxpr = jax.make_jaxpr(jitted_step)(*args)
    m = dict(meta or {})
    m.setdefault("donate_expected", donate_expected)
    if plan is not None:
        m.setdefault("plan", plan)
    if compute_dtype is not None:
        m.setdefault("compute_dtype", compute_dtype)
    return LintContext(name=name, kind="train_step", jaxpr=jaxpr, meta=m)


def _leaf_count(*trees) -> int:
    import jax

    return sum(len(jax.tree_util.tree_leaves(t)) for t in trees)


def _step_args(model, optim_methods, batch, batch_dtype, tgt,
               tgt_dtype="int32"):
    """(params, state, opt, step, rng, features, targets, lrs) templates
    for the canonical train-step signature."""
    import jax
    import jax.numpy as jnp

    var = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    params, state = var["params"], var["state"]
    opt = jax.eval_shape(lambda: {
        name: m.init_state(
            jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                params if name == "__all__" else {name: params[name]}))
        for name, m in optim_methods.items()
    })
    S = jax.ShapeDtypeStruct
    args = (params, state, opt, S((), jnp.int32), S((2,), jnp.uint32),
            S(batch, batch_dtype), S(tgt, tgt_dtype),
            [S((), jnp.float32)] * len(optim_methods))
    return args, _leaf_count(params, state, opt)


def _mesh(**kw):
    import numpy as np
    import jax

    from bigdl_tpu.parallel.mesh import MeshConfig, make_mesh

    n = int(np.prod([max(v, 1) for v in kw.values()]))
    return make_mesh(MeshConfig(**kw), jax.devices()[:n])


# --------------------------------------------------------------------------
# zoo model targets (forward trace, eval mode)
# --------------------------------------------------------------------------

@target("lenet", "model", "LeNet-5 MNIST")
def _lenet():
    import jax.numpy as jnp

    from bigdl_tpu import models

    (x,) = _structs(((2, 28, 28, 1), jnp.float32))
    return model_context("lenet", models.LeNet5(), x)


@target("resnet20_cifar", "model", "ResNet-20 CIFAR")
def _resnet20():
    import jax.numpy as jnp

    from bigdl_tpu import models

    (x,) = _structs(((2, 32, 32, 3), jnp.float32))
    m = models.ResNet(class_num=10, depth=20, dataset="cifar10")
    return model_context("resnet20_cifar", m, x)


@target("resnet50", "model", "ResNet-50 (reduced res; res-agnostic)")
def _resnet50():
    import jax.numpy as jnp

    from bigdl_tpu import models

    (x,) = _structs(((1, 64, 64, 3), jnp.float32))
    return model_context("resnet50", models.ResNet50(class_num=1000), x)


@target("inception_v1", "model", "GoogLeNet v1")
def _inception():
    import jax.numpy as jnp

    from bigdl_tpu import models

    (x,) = _structs(((1, 224, 224, 3), jnp.float32))
    return model_context("inception_v1", models.Inception_v1(class_num=50),
                         x)


@target("vgg_cifar", "model", "VGG CIFAR-10 variant")
def _vgg():
    import jax.numpy as jnp

    from bigdl_tpu import models

    (x,) = _structs(((2, 32, 32, 3), jnp.float32))
    return model_context("vgg_cifar", models.VggForCifar10(), x)


@target("autoencoder", "model", "MNIST autoencoder")
def _autoenc():
    import jax.numpy as jnp

    from bigdl_tpu import models

    (x,) = _structs(((2, 28, 28, 1), jnp.float32))
    return model_context("autoencoder", models.Autoencoder(32), x)


@target("ptb_lm", "model", "PTB LSTM language model")
def _ptb():
    import jax.numpy as jnp

    from bigdl_tpu import models

    (ids,) = _structs(((2, 12), jnp.int32))
    m = models.PTBModel(vocab_size=100, embedding_size=16,
                        hidden_size=16, num_layers=2)
    return model_context("ptb_lm", m, ids)


@target("simple_rnn", "model", "SimpleRNN LM")
def _simple_rnn():
    import jax.numpy as jnp

    from bigdl_tpu import models

    (ids,) = _structs(((2, 7), jnp.int32))
    m = models.SimpleRNN(input_size=40, hidden_size=8, output_size=40)
    return model_context("simple_rnn", m, ids)


@target("textclassifier_cnn", "model", "text CNN")
def _text_cnn():
    import jax.numpy as jnp

    from bigdl_tpu import models

    (x,) = _structs(((2, 64, 32), jnp.float32))
    m = models.TextClassifierCNN(class_num=20, embedding_dim=32,
                                 sequence_len=64)
    return model_context("textclassifier_cnn", m, x)


@target("textclassifier_lstm", "model", "text LSTM")
def _text_lstm():
    import jax.numpy as jnp

    from bigdl_tpu import models

    (x,) = _structs(((2, 30, 32), jnp.float32))
    m = models.TextClassifierLSTM(class_num=20, embedding_dim=32)
    return model_context("textclassifier_lstm", m, x)


@target("seq2seq", "model", "LSTM encoder-decoder + attention")
def _seq2seq():
    import jax.numpy as jnp

    from bigdl_tpu import models

    src, tgt = _structs(((2, 6), jnp.int32), ((2, 6), jnp.int32))
    m = models.Seq2Seq(12, 12, embedding_size=24, hidden_size=48)
    return model_context("seq2seq", m, (src, tgt))


@target("transformer_lm", "model", "Transformer LM (flash-eligible)")
def _transformer_lm():
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn

    (ids,) = _structs(((2, 32), jnp.int32))
    m = nn.Transformer(vocab_size=128, hidden_size=64, num_heads=4,
                       filter_size=128, num_layers=2, dropout=0.0,
                       causal=True)
    return model_context("transformer_lm", m, ids)


@target("serving_forward", "model",
        "ServingEngine bucket forward via the engine's own builder")
def _serving_forward():
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import models
    from bigdl_tpu.serving.warmup import build_forward

    # trace THROUGH serving.warmup.build_forward so the audited jaxpr is
    # exactly what every compiled bucket dispatches (dtype hygiene, no
    # host transfer hiding inside the request hot path) — the serving
    # analog of the async_engine_step target, at a bucket-shaped batch
    model = models.LeNet5()
    fwd = build_forward(model)
    var = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    (x,) = _structs(((32, 28, 28, 1), jnp.float32))
    jaxpr = jax.make_jaxpr(fwd)(var["params"], var["state"], x)
    return LintContext(name="serving_forward", kind="model", jaxpr=jaxpr,
                       meta={})


# --------------------------------------------------------------------------
# train-step targets (the per-commit gates for the perf PRs)
# --------------------------------------------------------------------------

@target("lenet_train_step", "train_step", "local bf16 step, donated")
def _lenet_step():
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu import models
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.optim.optimizer import make_train_step

    model = models.LeNet5()
    methods = {"__all__": SGD(1e-2)}
    step = jax.jit(
        make_train_step(model, nn.ClassNLLCriterion(logits=True),
                        methods, compute_dtype=jnp.bfloat16),
        donate_argnums=(0, 1, 2))
    args, n = _step_args(model, methods, (8, 28, 28, 1), "float32",
                         (8,))
    return step_context("lenet_train_step", step, args, n,
                        compute_dtype="bfloat16")


@target("lm_train_step", "train_step", "Transformer-LM bf16 AdamW step")
def _lm_step():
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import AdamW
    from bigdl_tpu.optim.optimizer import make_train_step

    model = nn.Transformer(vocab_size=128, hidden_size=64, num_heads=4,
                           filter_size=128, num_layers=2, dropout=0.0,
                           causal=True)
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(logits=True))
    methods = {"__all__": AdamW(3e-4)}
    step = jax.jit(
        make_train_step(model, crit, methods,
                        compute_dtype=jnp.bfloat16),
        donate_argnums=(0, 1, 2))
    args, n = _step_args(model, methods, (2, 32), "int32", (2, 32))
    return step_context("lm_train_step", step, args, n,
                        compute_dtype="bfloat16")


@target("async_engine_step", "train_step",
        "LocalOptimizer async-loop step via the engine's own builder")
def _async_engine_step():
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu import models
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.optim.optimizer import LocalOptimizer

    # build THROUGH LocalOptimizer._build_step_fn so the audited jaxpr
    # is exactly what the reworked async loop dispatches: donation must
    # stay intact (the loop rebinds trees every step) and no host
    # transfer may hide in the step (the loop's only host<-device sync
    # is the deferred loss drain, outside this program)
    model = models.LeNet5()
    engine = LocalOptimizer(model, None, nn.ClassNLLCriterion(logits=True))
    engine.set_optim_method(SGD(1e-2))
    engine.set_compute_dtype(jnp.bfloat16)
    step = engine._build_step_fn(model)
    args, n = _step_args(model, engine.optim_methods, (8, 28, 28, 1),
                         "float32", (8,))
    return step_context("async_engine_step", step, args, n,
                        compute_dtype="bfloat16")


@target("telemetry_step_parity", "train_step",
        "async-loop step jaxpr byte-identical with tracing on vs off")
def _telemetry_parity():
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu import models, telemetry
    from bigdl_tpu.optim.metrics import Metrics
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.optim.optimizer import LocalOptimizer

    # the telemetry contract (docs/observability.md): instrumentation
    # is strictly host-side, so the program the loop dispatches must be
    # BYTE-IDENTICAL whether the tracer is enabled or not.  Trace the
    # engine's own step builder twice — tracing off, then on with a
    # live Metrics sink + watchdog attached (the worst case: any
    # instrumentation that reached the staged program would surface
    # here) — and hand both jaxprs to the jaxpr-parity rule.
    model = models.LeNet5()
    engine = LocalOptimizer(model, None, nn.ClassNLLCriterion(logits=True))
    engine.set_optim_method(SGD(1e-2))
    engine.set_compute_dtype(jnp.bfloat16)
    step = engine._build_step_fn(model)
    args, n = _step_args(model, engine.optim_methods, (8, 28, 28, 1),
                         "float32", (8,))
    bare = jax.make_jaxpr(step)(*args)
    with telemetry.enabled():
        with telemetry.Watchdog(log=None) as wd:
            wd.attach()
            sink = Metrics()  # a live span sink during staging
            with sink.time("dispatch"):
                instrumented = jax.make_jaxpr(step)(*args)
    return LintContext(
        name="telemetry_step_parity", kind="train_step",
        jaxpr=instrumented,
        meta={"parity_jaxpr": bare, "donate_expected": n,
              "compute_dtype": "bfloat16"})


@target("program_registry_parity", "train_step",
        "step jaxpr byte-identical with the X-ray program registry live")
def _program_registry_parity():
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu import models, telemetry
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.telemetry import programs

    # the X-ray contract (docs/observability.md §Program X-ray):
    # registration, forensics, and HBM-ledger samples are host-side
    # bookkeeping at compile sites only — none of it may reach the
    # staged program.  Trace the engine's step bare, then again with a
    # LIVE registry registering signatures (including a steady-state
    # miss that emits a forensic instant) and a ledger sampling around
    # the re-trace — the jaxprs must stay byte-identical.
    model = models.LeNet5()
    engine = LocalOptimizer(model, None, nn.ClassNLLCriterion(logits=True))
    engine.set_optim_method(SGD(1e-2))
    engine.set_compute_dtype(jnp.bfloat16)
    step = engine._build_step_fn(model)
    args, n = _step_args(model, engine.optim_methods, (8, 28, 28, 1),
                         "float32", (8,))
    bare = jax.make_jaxpr(step)(*args)
    with telemetry.enabled():
        registry = programs.ProgramRegistry()
        ledger = programs.HbmLedger(registry=registry,
                                    stats_fn=lambda: None, every_s=0.0)
        registry.register_compile(
            "lint_step", programs.signature_of({"args": args}),
            compile_s=0.0, expected=True)
        instrumented = jax.make_jaxpr(step)(*args)
        # a steady-state miss (forensic instant) + a ledger sample
        # bracketing the staging above/below
        registry.register_compile(
            "lint_step",
            programs.signature_of({"args": args},
                                  static={"probe": "changed"}))
        ledger.sample()
    return LintContext(
        name="program_registry_parity", kind="train_step",
        jaxpr=instrumented,
        meta={"parity_jaxpr": bare, "donate_expected": n,
              "compute_dtype": "bfloat16"})


@target("cluster_step_parity", "train_step",
        "step jaxpr byte-identical with cluster telemetry shipping on/off")
def _cluster_parity():
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu import models, telemetry
    from bigdl_tpu.optim.metrics import Metrics
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.telemetry.cluster import TelemetryShipper

    # the cluster plane extends the telemetry contract across hosts:
    # the shipper subscribes to the tracer, samples clock offsets and
    # snapshots metrics, but none of that may reach the staged program.
    # Trace the engine's step bare, then again with a LIVE shipper
    # (subscribed, metrics source attached, segments flushing to disk)
    # wrapped around the re-trace — the jaxprs must stay byte-identical.
    model = models.LeNet5()
    engine = LocalOptimizer(model, None, nn.ClassNLLCriterion(logits=True))
    engine.set_optim_method(SGD(1e-2))
    engine.set_compute_dtype(jnp.bfloat16)
    step = engine._build_step_fn(model)
    args, n = _step_args(model, engine.optim_methods, (8, 28, 28, 1),
                         "float32", (8,))
    bare = jax.make_jaxpr(step)(*args)
    run_dir = tempfile.mkdtemp(prefix="bigdl-lint-ship-")
    try:
        with telemetry.enabled():
            sink = Metrics()
            with TelemetryShipper(run_dir, "lint-host",
                                  clock_offset_fn=lambda: 0.0) as shipper:
                shipper.add_metrics("train", lambda: sink)
                with sink.time("dispatch"):
                    instrumented = jax.make_jaxpr(step)(*args)
                shipper.ship_now()  # segment write during staging
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
    return LintContext(
        name="cluster_step_parity", kind="train_step",
        jaxpr=instrumented,
        meta={"parity_jaxpr": bare, "donate_expected": n,
              "compute_dtype": "bfloat16"})


@target("debug_plane_parity", "train_step",
        "train/serve/decode jaxprs byte-identical with the debug "
        "server + flight recorder live vs absent")
def _debug_plane_parity():
    import shutil
    import tempfile
    import urllib.request

    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu import models, telemetry
    from bigdl_tpu.optim.metrics import Metrics
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.serving.decode_programs import build_sampling_tick
    from bigdl_tpu.serving.warmup import build_forward

    # the live ops plane (docs/observability.md §Live ops plane) is
    # pull-based: /metricsz scrapes and flight-recorder dumps can land
    # at ANY moment, including mid-staging on any engine.  So all three
    # program families — train step, serving bucket forward, decode
    # tick — are traced bare, then re-traced with the full plane live
    # (server answering a real scrape, recorder subscribed to the
    # tracer and forced to dump mid-staging).  Serve/decode pairs are
    # compared inline; the first divergent pair (or, when all is well,
    # the train pair) is handed to the jaxpr-parity rule.
    model = models.LeNet5()
    crit = nn.ClassNLLCriterion(logits=True)
    engine = LocalOptimizer(model, None, crit)
    engine.set_optim_method(SGD(1e-2))
    engine.set_compute_dtype(jnp.bfloat16)
    step = engine._build_step_fn(model)
    args, n = _step_args(model, engine.optim_methods, (8, 28, 28, 1),
                         "float32", (8,))

    fwd = build_forward(model)
    var = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    (x,) = _structs(((32, 28, 28, 1), jnp.float32))

    ks = _kernel_shapes()
    dec_model = nn.Transformer(**ks.DECODE_MODEL)
    tick = build_sampling_tick(dec_model)
    dec_var = jax.eval_shape(
        lambda: dec_model.init(jax.random.PRNGKey(0)))
    cache = jax.eval_shape(
        lambda: dec_model.init_cache(ks.DECODE_SLOTS, ks.DECODE_MAX_LEN))
    tick_args = (dec_var["params"], dec_var["state"], cache,
                 *_sampling_tick_structs(ks.DECODE_SLOTS))

    bare_train = jax.make_jaxpr(step)(*args)
    bare_serve = jax.make_jaxpr(fwd)(var["params"], var["state"], x)
    bare_decode = jax.make_jaxpr(tick)(*tick_args)

    out_dir = tempfile.mkdtemp(prefix="bigdl-lint-flight-")
    try:
        with telemetry.enabled():
            sink = Metrics()
            with telemetry.FlightRecorder(
                    out_dir=out_dir, min_interval_s=0.0) as flight:
                flight.add_metrics("train", lambda: sink)
                with telemetry.DebugServer(port=0) as srv:
                    srv.add_metrics("train", lambda: sink)
                    srv.set_flight_recorder(flight)
                    with sink.time("dispatch"):
                        live_train = jax.make_jaxpr(step)(*args)
                    # a real scrape + a forced dump mid-staging: the
                    # pull paths run between (never inside) programs
                    urllib.request.urlopen(
                        srv.local_url("/metricsz"), timeout=10).read()
                    flight.dump(trigger="lint", force=True)
                    live_serve = jax.make_jaxpr(fwd)(
                        var["params"], var["state"], x)
                    live_decode = jax.make_jaxpr(tick)(*tick_args)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)

    live, bare = live_train, bare_train
    for pair_live, pair_bare in ((live_serve, bare_serve),
                                 (live_decode, bare_decode)):
        if str(pair_live) != str(pair_bare):
            live, bare = pair_live, pair_bare  # rule names the diff
            break
    return LintContext(
        name="debug_plane_parity", kind="train_step",
        jaxpr=live,
        meta={"parity_jaxpr": bare, "donate_expected": n,
              "compute_dtype": "bfloat16"})


@target("request_trace_parity", "model",
        "serve/decode jaxprs byte-identical with the Request X-ray "
        "(budget ledger, exemplar reservoir, workload recorder) live "
        "vs absent")
def _request_trace_parity():
    import os
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu import models, telemetry
    from bigdl_tpu.serving.decode_programs import build_sampling_tick
    from bigdl_tpu.serving.warmup import build_forward
    from bigdl_tpu.telemetry import requests as request_xray
    from bigdl_tpu.telemetry import workload

    # the Request X-ray contract (docs/observability.md §Request
    # X-ray): per-request budget accounting, the p99 exemplar
    # reservoir, and the workload recorder are strictly host-side —
    # none of them may reach a staged program.  Trace the serving
    # bucket forward and the decode tick bare, then re-trace with the
    # full request plane LIVE between and around the traces: a ledger
    # walking a request through every phase, a reservoir capturing its
    # close, and an armed recorder writing the request to JSONL.
    model = models.LeNet5()
    fwd = build_forward(model)
    var = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    (x,) = _structs(((32, 28, 28, 1), jnp.float32))

    ks = _kernel_shapes()
    dec_model = nn.Transformer(**ks.DECODE_MODEL)
    tick = build_sampling_tick(dec_model)
    dec_var = jax.eval_shape(
        lambda: dec_model.init(jax.random.PRNGKey(0)))
    cache = jax.eval_shape(
        lambda: dec_model.init_cache(ks.DECODE_SLOTS, ks.DECODE_MAX_LEN))
    tick_args = (dec_var["params"], dec_var["state"], cache,
                 *_sampling_tick_structs(ks.DECODE_SLOTS))

    bare_serve = jax.make_jaxpr(fwd)(var["params"], var["state"], x)
    bare_decode = jax.make_jaxpr(tick)(*tick_args)

    rec_dir = tempfile.mkdtemp(prefix="bigdl-lint-xray-")
    try:
        with telemetry.enabled():
            tracer = telemetry.get_tracer()
            ledger = request_xray.RequestLedger(tracer=tracer)
            reservoir = request_xray.ExemplarReservoir(tracer=tracer)
            workload.arm(os.path.join(rec_dir, "workload.jsonl"))
            rec = workload.recorder()
            rec.record_decode(0, [1, 2, 3], 8, temperature=0.8,
                              top_k=5, top_p=0.9, seed=0)
            ledger.open(0)
            ledger.to(0, request_xray.PHASE_PREFILL)
            live_serve = jax.make_jaxpr(fwd)(
                var["params"], var["state"], x)
            ledger.to(0, request_xray.PHASE_RESIDENT)
            ledger.note(0, "ticks")
            live_decode = jax.make_jaxpr(tick)(*tick_args)
            ledger.to(0, request_xray.PHASE_DELIVER)
            reservoir.offer(ledger.close(0))
    finally:
        workload.disarm()
        shutil.rmtree(rec_dir, ignore_errors=True)

    live, bare = live_serve, bare_serve
    if str(live_decode) != str(bare_decode):
        live, bare = live_decode, bare_decode  # rule names the diff
    return LintContext(
        name="request_trace_parity", kind="model",
        jaxpr=live,
        meta={"parity_jaxpr": bare})


@target("numerics_step_parity", "train_step",
        "stats-off step jaxpr byte-identical to the numerics-free build")
def _numerics_parity():
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu import models, telemetry
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.optim.optimizer import LocalOptimizer, make_train_step
    from bigdl_tpu.telemetry import numerics

    # the numerics contract (docs/observability.md §Numerics): with
    # stats OFF (the default) the engine's step must stay byte-identical
    # to a make_train_step build that never heard of numerics — the
    # stats plumbing is a trace-time no-op — and the host-side monitor
    # digesting drained stats must not leak into the staged program.
    model = models.LeNet5()
    crit = nn.ClassNLLCriterion(logits=True)
    bare_step = jax.jit(
        make_train_step(model, crit, {"__all__": SGD(1e-2)},
                        compute_dtype=jnp.bfloat16),
        donate_argnums=(0, 1, 2))
    engine = LocalOptimizer(model, None, crit)
    engine.set_optim_method(SGD(1e-2))
    engine.set_compute_dtype(jnp.bfloat16)
    engine.set_numerics(False)  # explicit off, whatever the env says
    step = engine._build_step_fn(model)
    args, n = _step_args(model, engine.optim_methods, (8, 28, 28, 1),
                         "float32", (8,))
    bare = jax.make_jaxpr(bare_step)(*args)
    with telemetry.enabled():
        monitor = numerics.NumericsMonitor(numerics.spec_for(model),
                                           log=None)
        monitor.observe(1, {"layers": {}, "grad_norm": 1.0,
                            "param_norm": 1.0, "update_norm": 0.01,
                            "nonfinite": 0})  # live monitor during trace
        instrumented = jax.make_jaxpr(step)(*args)
    return LintContext(
        name="numerics_step_parity", kind="train_step",
        jaxpr=instrumented,
        meta={"parity_jaxpr": bare, "donate_expected": n,
              "compute_dtype": "bfloat16"})


@target("dp_train_step", "train_step", "data-parallel ZeRO-1 step, dp=8")
def _dp_step():
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu import models
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.parallel.data_parallel import build_dp_train_step

    mesh = _mesh(data=8)
    model = models.LeNet5()
    methods = {"__all__": SGD(1e-2)}
    step, placement = build_dp_train_step(
        model, nn.ClassNLLCriterion(logits=True), methods, mesh,
        compute_dtype=jnp.bfloat16)
    args, n = _step_args(model, methods, (8, 28, 28, 1), "float32",
                         (8,))
    return step_context("dp_train_step", step, args, n,
                        plan=placement["plan"],
                        compute_dtype="bfloat16")


@target("compressed_allreduce_step", "train_step",
        "bf16-wire compressed gradient allreduce step, dp=8")
def _compressed_step():
    import bigdl_tpu.nn as nn
    from bigdl_tpu import models
    from bigdl_tpu.distributed.compression import (
        build_compressed_dp_train_step)
    from bigdl_tpu.optim.optim_method import SGD

    mesh = _mesh(data=8)
    model = models.LeNet5()
    methods = {"__all__": SGD(1e-2)}
    step, placement = build_compressed_dp_train_step(
        model, nn.ClassNLLCriterion(logits=True), methods, mesh,
        wire_dtype="bf16")
    args, n = _step_args(model, methods, (8, 28, 28, 1), "float32",
                         (8,))
    # NO compute_dtype meta: the compressed step deliberately casts
    # f32 -> bf16 -> f32 around every reduction (that IS the
    # compression), which the convert-churn check would misread.  The
    # wire_dtype meta arms the over-wide-reduction check instead.
    return step_context("compressed_allreduce_step", step, args, n,
                        plan=placement["plan"],
                        meta={"wire_dtype": placement["wire_dtype"]})


@target("pp_train_step", "train_step",
        "pipeline x data parallel LM step (ppermute schedule)")
def _pp_step():
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import AdamW
    from bigdl_tpu.parallel.data_parallel import build_dp_train_step
    from bigdl_tpu.parallel.mesh import DATA_AXIS
    from bigdl_tpu.parallel.pipeline import pipelined_transformer_lm

    mesh = _mesh(data=2, pipe=2)
    model = pipelined_transformer_lm(
        vocab_size=64, hidden_size=32, num_heads=2, filter_size=64,
        num_layers=2, mesh=mesh, num_microbatches=2, dropout=0.0,
        causal=True, data_axis=DATA_AXIS)
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(logits=True))
    methods = {"__all__": AdamW(3e-4)}
    step, placement = build_dp_train_step(
        model, crit, methods, mesh,
        param_shardings=model.param_shardings(mesh),
        compute_dtype=jnp.bfloat16)
    args, n = _step_args(model, methods, (4, 16), "int32", (4, 16))
    return step_context("pp_train_step", step, args, n,
                        plan=placement["plan"],
                        compute_dtype="bfloat16")


@target("ring_attention", "model", "sequence-parallel ring attention")
def _ring():
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.parallel.mesh import plan_info
    from bigdl_tpu.parallel.sequence import ring_attention

    mesh = _mesh(data=2, seq=4)
    S = jax.ShapeDtypeStruct
    q = S((2, 2, 32, 8), jnp.float32)

    jaxpr = jax.make_jaxpr(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, mesh,
                                          causal=True))(q, q, q)
    return LintContext(name="ring_attention", kind="model", jaxpr=jaxpr,
                       meta={"plan": plan_info(mesh)})


@target("decode_step", "train_step",
        "DecodeEngine whole-grid cached-decode tick via the engine's "
        "own builder")
def _decode_step():
    import jax

    import bigdl_tpu.nn as nn
    from bigdl_tpu.serving.decode_programs import build_sampling_tick

    ks = _kernel_shapes()
    # build THROUGH decode_programs.build_sampling_tick so the audited
    # jaxpr is exactly the program every dense decode tick dispatches:
    # the grid cache must stay donated (the engine rebinds it per tick
    # — an undonated tick doubles the KV cache's HBM) and no host
    # transfer may hide inside the step (the loop's only host<-device
    # sync is the (slots,) next-token fetch, outside this program)
    model = nn.Transformer(**ks.DECODE_MODEL)
    step = build_sampling_tick(model)
    var = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    cache = jax.eval_shape(
        lambda: model.init_cache(ks.DECODE_SLOTS, ks.DECODE_MAX_LEN))
    args = (var["params"], var["state"], cache,
            *_sampling_tick_structs(ks.DECODE_SLOTS))
    return step_context("decode_step", step, args, _leaf_count(cache))


@target("paged_decode_tick", "train_step",
        "paged-KV sampling tick: donated pool, no host transfer, "
        "jaxpr invariant to the sampling seeds")
def _paged_decode_tick():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.serving.decode_programs import build_paged_tick

    ks = _kernel_shapes()
    # build THROUGH decode_programs.build_paged_tick: the audited jaxpr
    # is the paged engine's steady-state program.  The pool must stay
    # donated (it IS the KV cache), the block-table gather must not
    # smuggle a host sync (see the paged_tick_gather_leak fixture), and
    # the program must be byte-identical across different request seeds
    # — the per-slot PRNG keys are (S, 2) uint32 *data*, so admitting a
    # new seeded request can never recompile the tick.
    model = nn.Transformer(**ks.DECODE_MODEL)
    tick = build_paged_tick(model)
    var = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        ks.DECODE_PAGES, ks.DECODE_PAGE, ks.DECODE_SLOTS))
    S = jax.ShapeDtypeStruct
    s = ks.DECODE_SLOTS
    m = ks.DECODE_MAX_LEN // ks.DECODE_PAGE

    def trace(keys):
        return jax.make_jaxpr(tick)(
            var["params"], var["state"], cache,
            S((s, m), jnp.int32), S((s,), jnp.int32),
            S((s,), jnp.bool_), keys,
            S((s,), jnp.float32), S((s,), jnp.int32),
            S((s,), jnp.float32))

    rng = np.random.default_rng(0)
    live = trace(rng.integers(0, 2**32, (s, 2), dtype=np.uint32))
    bare = trace(rng.integers(0, 2**32, (s, 2), dtype=np.uint32))
    return LintContext(
        name="paged_decode_tick", kind="train_step", jaxpr=live,
        meta={"parity_jaxpr": bare,
              "donate_expected": _leaf_count(cache)})


def _sampling_tick_structs(slots: int):
    """The per-slot arguments of the sampling tick after the cache:
    tokens, active, keys, temperature, top-k, top-p."""
    import jax
    import jax.numpy as jnp

    S = jax.ShapeDtypeStruct
    return (S((slots,), jnp.int32), S((slots,), jnp.bool_),
            S((slots, 2), jnp.uint32), S((slots,), jnp.float32),
            S((slots,), jnp.int32), S((slots,), jnp.float32))


def _kernel_shapes():
    try:
        from tools import kernel_shapes
    except ImportError:  # analysis used outside the repo cwd
        import os
        import sys

        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        from tools import kernel_shapes

    return kernel_shapes


# --------------------------------------------------------------------------
# kernel-shape inventory (pallas-routing rule)
# --------------------------------------------------------------------------

@target("kernel_inventory", "inventory",
        "tools/kernel_shapes.py fused-path shapes + committed tuned "
        "tables")
def _inventory():
    # attach every committed tuned table (tools/autotune.py output —
    # dispatch loads the one of the running device kind): the
    # pallas-routing rule then audits every entry against the declared
    # candidate spaces, so a stale table fails lint instead of silently
    # downgrading dispatch to hand-picked params (ops/pallas/tuning.py
    # resolve records source=stale)
    import glob

    from bigdl_tpu.ops.pallas import tuning

    tables = [tuning.TunedTable.load(p) for p in sorted(
        glob.glob(os.path.join(tuning.tuned_dir(), "*.json")))]
    return LintContext(name="kernel_inventory", kind="inventory",
                       jaxpr=None,
                       meta={"inventory": _kernel_shapes(),
                             "tuned_tables": tables})


@target("fused_block_bwd", "model",
        "FusedBottleneck training backward with remat "
        "(BIGDL_TPU_FUSED_REMAT)")
def _fused_block_bwd():
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.nn.fused_block import FusedBottleneck

    # trace the BACKWARD of the fused bottleneck in training mode —
    # exactly the program whose residuals caused the +4 GB HBM-temps
    # regression (PERF.md §fused-conv).  expect_remat arms the
    # pallas-routing check that the jax.checkpoint wrapper is present,
    # and the generic jaxpr rules (dtype hygiene, host transfer) audit
    # the recomputed forward the same as any model.
    block = FusedBottleneck(n_in=64, planes=16, stride=1)
    var = jax.eval_shape(lambda: block.init(jax.random.PRNGKey(0)))

    def loss(params, state, x):
        out, _ = block.apply(params, state, x, training=True)
        return jnp.sum(out.astype(jnp.float32))

    x = jax.ShapeDtypeStruct((4, 8, 8, 64), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(
        var["params"], var["state"], x)
    return LintContext(name="fused_block_bwd", kind="model",
                       jaxpr=jaxpr, meta={"expect_remat": True})
