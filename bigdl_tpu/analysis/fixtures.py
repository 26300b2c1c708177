"""Seeded-defect fixtures: each plants exactly one misconfiguration a
rule must catch.  They double as the linter's own regression suite
(tests/test_graft_lint.py) and as CLI demos
(``python tools/graft_lint.py --fixture <name>`` must exit non-zero).

Every fixture mirrors a real shipped-bug class: the f64 literal is the
classic numpy-scalar promotion, the debug callback is a forgotten
``jax.debug.print``, the wrong-axis psum is the silent no-op reduction
over a degree-1 axis, the broken ppermute is a pipeline hop feeding
the wrong stage, the undonated step is the HBM-doubling jit, and the
bad kernel shape is a fused path that would silently run on XLA.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple, Union

from bigdl_tpu.analysis.core import LintContext

# expected_rule is one rule name, or a tuple when the defect rightly
# trips several rules (defense in depth: span_host_leak)
ExpectedRules = Union[str, Tuple[str, ...]]
_FIXTURES: Dict[str, Tuple[ExpectedRules, Callable[[], LintContext]]] = {}


def fixture(name: str, expected_rule: ExpectedRules):
    def deco(fn):
        _FIXTURES[name] = (expected_rule, fn)
        return fn

    return deco


def all_fixtures() -> Dict[str, Tuple[str, Callable[[], LintContext]]]:
    return dict(_FIXTURES)


def get_fixture(name: str):
    if name not in _FIXTURES:
        raise KeyError(f"unknown fixture '{name}' "
                       f"(have: {', '.join(sorted(_FIXTURES))})")
    return _FIXTURES[name]


@fixture("f64_literal", "dtype-hygiene")
def _f64_model():
    """A model whose apply picked up an np.float64 scale — traced under
    x64 so the wide constant survives into the jaxpr, exactly as it
    does in an x64-enabled research script pasted into the zoo."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    scale = np.float64(1.0000001)

    def fwd(x):
        return jnp.tanh(x * scale)

    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(fwd)(
            jax.ShapeDtypeStruct((4, 4), jnp.float32))
    return LintContext(name="fixture:f64_literal", kind="model",
                       jaxpr=jaxpr, meta={"compute_dtype": "bfloat16"})


@fixture("debug_callback", "host-transfer")
def _debug_cb_step():
    """A train step with a forgotten jax.debug.print — a host
    round-trip every iteration."""
    import jax
    import jax.numpy as jnp

    def step(params, x):
        loss = jnp.sum((x @ params) ** 2)
        jax.debug.print("loss={l}", l=loss)
        return loss

    jaxpr = jax.make_jaxpr(jax.jit(step))(
        jax.ShapeDtypeStruct((8, 8), jnp.float32),
        jax.ShapeDtypeStruct((4, 8), jnp.float32))
    # kind "model": a traced fragment — the donation rule is exercised
    # by the undonated_step fixture, this one isolates host-transfer
    return LintContext(name="fixture:debug_callback", kind="model",
                       jaxpr=jaxpr)


@fixture("wrong_collective_axis", "collective-axes")
def _wrong_axis_step():
    """Gradient psum over 'model' where the plan only declares data
    parallelism: the reduction runs over a degree-1 axis — a silent
    no-op, per-shard gradients never averaged."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.parallel.mesh import MeshConfig, make_mesh, plan_info

    mesh = make_mesh(MeshConfig(data=4), jax.devices()[:4])

    def body(g):
        return jax.lax.psum(g, ("model",))  # wrong: plan says 'data'

    f = jax.shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P(),
                      check_vma=False)
    jaxpr = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((8, 4), jnp.float32))
    return LintContext(name="fixture:wrong_collective_axis",
                       kind="model", jaxpr=jaxpr,
                       meta={"plan": plan_info(mesh)})


@fixture("broken_pipeline_permute", "collective-axes")
def _broken_permute():
    """A 4-stage pipeline hop whose permutation splits into two
    disconnected chains — stages 1->2 never hand off, half the
    microbatches are dropped."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.parallel.mesh import MeshConfig, make_mesh, plan_info

    mesh = make_mesh(MeshConfig(data=2, pipe=4), jax.devices()[:8])

    def body(x):
        # should be [(0,1),(1,2),(2,3)]
        return jax.lax.ppermute(x, "pipe", [(0, 1), (2, 3)])

    f = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                      out_specs=P("data"), check_vma=False)
    jaxpr = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((8, 4), jnp.float32))
    return LintContext(name="fixture:broken_pipeline_permute",
                       kind="model", jaxpr=jaxpr,
                       meta={"plan": plan_info(mesh)})


@fixture("undonated_step", "donation")
def _undonated_step():
    """The canonical train step jitted WITHOUT donate_argnums: old and
    new params/opt trees both live across the update."""
    import jax

    import bigdl_tpu.nn as nn
    from bigdl_tpu import models
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.optim.optimizer import make_train_step
    from bigdl_tpu.analysis.targets import _step_args, step_context

    model = models.LeNet5()
    methods = {"__all__": SGD(1e-2)}
    step = jax.jit(make_train_step(
        model, nn.ClassNLLCriterion(logits=True), methods))  # no donate
    args, n = _step_args(model, methods, (8, 28, 28, 1), "float32",
                         (8,))
    return step_context("fixture:undonated_step", step, args, n)


@fixture("decode_step_sync", "host-transfer")
def _decode_step_sync():
    """A cached-decode tick with a forgotten per-token debug sync — the
    decode analog of the debug_callback train-step leak.  In a decode
    loop this is a host round-trip EVERY generated token: invisible on
    CPU, a throughput cliff on the chip."""
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn

    model = nn.Transformer(vocab_size=16, hidden_size=16, num_heads=2,
                           filter_size=32, num_layers=1, dropout=0.0,
                           causal=True)
    var = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: model.init_cache(2, 8))

    def tick(params, state, cache, tokens):
        logits, cache = model.decode_step(params, state, cache, tokens)
        jax.debug.print("logit max={m}", m=logits.max())  # the defect
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    jaxpr = jax.make_jaxpr(tick)(
        var["params"], var["state"], cache,
        jax.ShapeDtypeStruct((2,), jnp.int32))
    # kind "model": the donation expectation is exercised by the real
    # decode_step target; this fixture isolates the hidden host sync
    return LintContext(name="fixture:decode_step_sync", kind="model",
                       jaxpr=jaxpr)


@fixture("paged_tick_gather_leak", "host-transfer")
def _paged_tick_gather_leak():
    """A paged tick that resolves its block table THROUGH THE HOST —
    "the allocator owns the table, just ask it" — instead of taking the
    table as a device argument.  The pure_callback looks harmless (the
    table is tiny) but it serializes every tick on a host round-trip
    and pins the dispatch thread; the production tick threads the
    (S, M) table in as data (serving/decode_programs.build_paged_tick) so page
    moves never touch the program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bigdl_tpu.nn as nn

    model = nn.Transformer(vocab_size=16, hidden_size=16, num_heads=2,
                           filter_size=32, num_layers=1, dropout=0.0,
                           causal=True)
    var = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: model.init_paged_cache(5, 4, 2))
    host_table = np.zeros((2, 2), np.int32)  # "the allocator's copy"

    def tick(params, state, cache, tokens, active):
        table = jax.pure_callback(          # the defect: host gather
            lambda: host_table,
            jax.ShapeDtypeStruct((2, 2), jnp.int32))
        logits, cache, _ = model.decode_step_paged(params, state, cache,
                                                   table, tokens, active)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    jaxpr = jax.make_jaxpr(tick)(
        var["params"], var["state"], cache,
        jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.bool_))
    return LintContext(name="fixture:paged_tick_gather_leak",
                       kind="model", jaxpr=jaxpr)


@fixture("span_host_leak", ("jaxpr-parity", "host-transfer"))
def _span_host_leak():
    """A span callback smuggled INTO the step: "close the span when the
    loss is ready" implemented as ``jax.debug.callback`` inside the
    traced function.  Trips BOTH telemetry guards — the jaxpr is no
    longer byte-identical to the bare step (jaxpr-parity) and the
    callback is a host round-trip per iteration (host-transfer)."""
    import jax
    import jax.numpy as jnp

    def make_step(leak_span_callback: bool):
        # one source of truth for both programs (same function name in
        # the jaxpr): the ONLY divergence is the seeded callback
        def step(params, x):
            loss = jnp.sum((x @ params) ** 2)
            if leak_span_callback:
                jax.debug.callback(lambda l: None, loss)
            return loss

        return step

    S = jax.ShapeDtypeStruct
    args = (S((8, 8), jnp.float32), S((4, 8), jnp.float32))
    return LintContext(
        name="fixture:span_host_leak", kind="model",
        jaxpr=jax.make_jaxpr(jax.jit(make_step(True)))(*args),
        meta={"parity_jaxpr": jax.make_jaxpr(jax.jit(make_step(False)))(
            *args)})


@fixture("ship_host_leak", ("jaxpr-parity", "host-transfer"))
def _ship_host_leak():
    """A cluster-telemetry callback smuggled INTO the step: "ship the
    loss with the next segment" implemented as ``jax.debug.callback``
    feeding a shipper's metrics from inside the traced function.  The
    shipping contract (docs/observability.md) is host-side only —
    snapshots are pulled by the shipper thread between steps, never
    pushed from the program — so this trips BOTH guards: the jaxpr
    diverges from the bare step (jaxpr-parity) and the callback is a
    host round-trip per iteration (host-transfer)."""
    import jax
    import jax.numpy as jnp

    def make_step(ship_from_step: bool):
        # one source of truth for both programs (same function name in
        # the jaxpr): the ONLY divergence is the seeded ship callback
        def step(params, x):
            loss = jnp.sum((x @ params) ** 2)
            if ship_from_step:
                # stand-in for shipper.add_metrics wired through a
                # traced callback instead of a host-side snapshot pull
                jax.debug.callback(lambda l: None, loss)
            return loss

        return step

    S = jax.ShapeDtypeStruct
    args = (S((8, 8), jnp.float32), S((4, 8), jnp.float32))
    return LintContext(
        name="fixture:ship_host_leak", kind="model",
        jaxpr=jax.make_jaxpr(jax.jit(make_step(True)))(*args),
        meta={"parity_jaxpr": jax.make_jaxpr(jax.jit(make_step(False)))(
            *args)})


@fixture("registry_host_leak", ("jaxpr-parity", "host-transfer"))
def _registry_host_leak():
    """Per-call program accounting pushed INTO the step: "count the
    dispatch when the loss lands" implemented as ``jax.debug.callback``
    feeding ``ProgramRegistry.record_call`` from inside the traced
    function.  The X-ray contract (docs/observability.md §Program
    X-ray) is host-side registration at compile/dispatch sites only —
    so this trips BOTH guards: the jaxpr diverges from the bare step
    (jaxpr-parity) and the callback is a host round-trip per iteration
    (host-transfer)."""
    import jax
    import jax.numpy as jnp

    def make_step(count_from_step: bool):
        # one source of truth for both programs (same function name in
        # the jaxpr): the ONLY divergence is the seeded count callback
        def step(params, x):
            loss = jnp.sum((x @ params) ** 2)
            if count_from_step:
                # stand-in for get_program_registry().record_call
                # wired through a traced callback instead of the
                # host-side dispatch site
                jax.debug.callback(lambda l: None, loss)
            return loss

        return step

    S = jax.ShapeDtypeStruct
    args = (S((8, 8), jnp.float32), S((4, 8), jnp.float32))
    return LintContext(
        name="fixture:registry_host_leak", kind="model",
        jaxpr=jax.make_jaxpr(jax.jit(make_step(True)))(*args),
        meta={"parity_jaxpr": jax.make_jaxpr(jax.jit(make_step(False)))(
            *args)})


@fixture("numerics_host_leak", ("jaxpr-parity", "host-transfer"))
def _numerics_host_leak():
    """A per-layer numerics stat fetched EAGERLY from inside the step:
    "observe the grad norm the moment it exists" implemented as
    ``jax.debug.callback`` feeding the NumericsMonitor from the traced
    function.  The numerics contract (docs/observability.md §Numerics)
    is that stats ride the step's OUTPUTS and are digested host-side at
    the sync-window drain — so this trips BOTH guards: the jaxpr
    diverges from the bare step (jaxpr-parity) and the callback is a
    host round-trip per iteration (host-transfer)."""
    import jax
    import jax.numpy as jnp

    def make_step(observe_from_step: bool):
        # one source of truth for both programs (same function name in
        # the jaxpr): the ONLY divergence is the seeded observe callback
        def step(params, x):
            loss = jnp.sum((x @ params) ** 2)
            gnorm = jnp.sqrt(jnp.sum(jnp.square(params)))
            if observe_from_step:
                # stand-in for NumericsMonitor.observe wired through a
                # traced callback instead of the drained stats output
                jax.debug.callback(lambda g: None, gnorm)
            return loss + 0.0 * gnorm
        return step

    S = jax.ShapeDtypeStruct
    args = (S((8, 8), jnp.float32), S((4, 8), jnp.float32))
    return LintContext(
        name="fixture:numerics_host_leak", kind="model",
        jaxpr=jax.make_jaxpr(jax.jit(make_step(True)))(*args),
        meta={"parity_jaxpr": jax.make_jaxpr(jax.jit(make_step(False)))(
            *args)})


@fixture("debug_hook_leak", ("jaxpr-parity", "host-transfer"))
def _debug_hook_leak():
    """A /metricsz gauge fed from INSIDE the step: "expose the live
    loss on the debug endpoint" implemented as ``jax.debug.callback``
    smuggled into the traced function to update a Prometheus gauge.
    The live ops plane contract (docs/observability.md §Live ops
    plane) is pull-only — endpoints read host-side state that the
    drains already produced, never the staged program — so this trips
    BOTH guards: the jaxpr diverges from the bare step (jaxpr-parity)
    and the callback is a host round-trip per iteration
    (host-transfer)."""
    import jax
    import jax.numpy as jnp

    gauges = {}

    def make_step(scrape_from_step: bool):
        # one source of truth for both programs (same function name in
        # the jaxpr): the ONLY divergence is the seeded endpoint hook
        def step(params, x):
            loss = jnp.sum((x @ params) ** 2)
            if scrape_from_step:
                # stand-in for a debug-server metrics source wired
                # through a traced callback instead of reading the
                # Metrics the sync-window drain already feeds
                jax.debug.callback(
                    lambda v: gauges.__setitem__("loss", v), loss)
            return loss
        return step

    S = jax.ShapeDtypeStruct
    args = (S((8, 8), jnp.float32), S((4, 8), jnp.float32))
    return LintContext(
        name="fixture:debug_hook_leak", kind="model",
        jaxpr=jax.make_jaxpr(jax.jit(make_step(True)))(*args),
        meta={"parity_jaxpr": jax.make_jaxpr(jax.jit(make_step(False)))(
            *args)})


@fixture("replay_clock_leak", ("jaxpr-parity", "host-transfer"))
def _replay_clock_leak():
    """A wall-clock phase stamp smuggled INTO the decode step: "charge
    the budget the instant the token exists" implemented as
    ``jax.debug.callback`` reading ``time.perf_counter`` from inside
    the traced function.  The Request X-ray contract
    (docs/observability.md §Request X-ray) is host-side only — the
    budget ledger stamps phases at the engine's own dispatch/drain
    sites, never from the program — and a clock inside the trace also
    breaks workload replay (the replayed program would diverge from
    the recording run's).  Trips BOTH guards: the jaxpr diverges from
    the bare step (jaxpr-parity) and the callback is a host round-trip
    per token (host-transfer)."""
    import time

    import jax
    import jax.numpy as jnp

    stamps = []

    def make_step(stamp_from_step: bool):
        # one source of truth for both programs (same function name in
        # the jaxpr): the ONLY divergence is the seeded clock callback
        def step(params, x):
            loss = jnp.sum((x @ params) ** 2)
            if stamp_from_step:
                # stand-in for RequestLedger.to() wired through a
                # traced callback instead of the host-side engine
                # transition sites
                jax.debug.callback(
                    lambda l: stamps.append(time.perf_counter()), loss)
            return loss

        return step

    S = jax.ShapeDtypeStruct
    args = (S((8, 8), jnp.float32), S((4, 8), jnp.float32))
    return LintContext(
        name="fixture:replay_clock_leak", kind="model",
        jaxpr=jax.make_jaxpr(jax.jit(make_step(True)))(*args),
        meta={"parity_jaxpr": jax.make_jaxpr(jax.jit(make_step(False)))(
            *args)})


@fixture("compressed_fp32_allreduce", "dtype-hygiene")
def _compressed_fp32_allreduce():
    """A "compressed" gradient exchange that psums the raw fp32 grads —
    the cast to the wire dtype was dropped in a refactor, so the step
    silently pays full-width interconnect bytes while the target's meta
    still declares a bf16 wire.  The over-wide-reduction check must
    catch the fp32 operand flowing into the psum."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.parallel.mesh import MeshConfig, make_mesh, plan_info

    mesh = make_mesh(MeshConfig(data=4), jax.devices()[:4])

    def body(g):
        # should be: psum(g.astype(bf16), ...).astype(f32) / ndata
        return jax.lax.psum(g, ("data",)) / 4.0

    f = jax.shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P(),
                      check_vma=False)
    jaxpr = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((8, 4), jnp.float32))
    # kind "model" (a traced fragment): donation is exercised elsewhere;
    # psum over data (degree 4) keeps collective-axes quiet
    return LintContext(name="fixture:compressed_fp32_allreduce",
                       kind="model", jaxpr=jaxpr,
                       meta={"plan": plan_info(mesh),
                             "wire_dtype": "bfloat16"})


@fixture("tuned_params_stale", "pallas-routing")
def _tuned_params_stale():
    """A tuned table whose fused_matmul entry drifted out of the
    declared candidate space (bm=100 divides no legal row tile — e.g.
    the budget math changed after the sweep ran): dispatch silently
    falls back to hand-picked params (recording source=stale), so the
    table is dead weight until re-swept.  The inventory itself is
    clean — the ONLY defect is the stale entry."""
    from bigdl_tpu.ops.pallas.tuning import TunedTable

    class _Inventory:
        __file__ = __file__
        BATCH = 256
        CONV3 = ()
        CONV3_BWD = ()
        MATMUL = ((802816, 64, 64),)
        INT8 = ()
        FLASH = (1, 2, 1024, 128)

    table = TunedTable(device_kind="fixture")
    table.add("fused_matmul", (802816, 64, 64), {"bm": 100})
    return LintContext(name="fixture:tuned_params_stale",
                       kind="inventory", jaxpr=None,
                       meta={"inventory": _Inventory,
                             "tuned_tables": [table]})


@fixture("bad_kernel_shape", "pallas-routing")
def _bad_kernel_shape():
    """An inventory whose matmul M=100 divides no row tile, whose int8
    K is not 128-aligned and whose flash lengths tile neither pass or
    only the forward: each would silently fall back to XLA."""

    class _Inventory:
        __file__ = __file__
        BATCH = 256
        CONV3 = ()
        CONV3_BWD = ()
        MATMUL = ((100, 64, 64),)
        INT8 = ((4096, 100, 256),)
        # no 128-multiple block divides 1025; 1000 is one forward block
        # but over the backward's cap; 32768 x 128 outgrows the
        # backward's VMEM for dQ
        FLASH = [(1, 2, 1025, 128), (1, 2, 1000, 128), (1, 2, 32768, 128)]

    return LintContext(name="fixture:bad_kernel_shape", kind="inventory",
                       jaxpr=None, meta={"inventory": _Inventory})
