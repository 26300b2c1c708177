"""Tracing / profiling (SURVEY.md §5 — reference per-module wall time
``AbstractModule.getTimes`` / ``getTimesGroupByModuleType``
AbstractModule.scala:168-186, and the per-iteration phase Metrics).

Two complementary tools:

* :func:`get_times` — per-module forward/backward wall time measured
  EAGERLY (each child dispatched and block_until_ready'd).  Numbers are
  un-fused upper bounds — XLA fuses across modules under jit — but they
  rank hot layers exactly like the reference's per-module timers did.
* :class:`trace` — context manager around ``jax.profiler`` emitting an
  XPlane trace viewable in TensorBoard/XProf, the real TPU-era answer
  to "where does the step time go" (per-op, per-fusion, HBM traffic);
  the program's host spans are in it (docs/observability.md).
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import Container, Module


def _block(x):
    jax.tree_util.tree_map(
        lambda a: a.block_until_ready() if hasattr(a, "block_until_ready")
        else a, x)


def get_times(model: Module, params, state, x, *, backward: bool = True,
              _prefix: str = "") -> List[Tuple[str, str, float, float]]:
    """[(path, type, forward_s, backward_s)] per leaf module.

    Containers recurse; Sequential children see the activation produced
    by their predecessors (so shapes are realistic).
    """
    rows: List[Tuple[str, str, float, float]] = []

    from bigdl_tpu.nn.module import Sequential

    if isinstance(model, Sequential):
        cur = x
        for key, child in zip(model.child_keys, model.children):
            rows.extend(get_times(
                child, params.get(key, {}), state.get(key, {}), cur,
                backward=backward,
                _prefix=f"{_prefix}{model.name}/"))
            cur, _ = child.apply(params.get(key, {}), state.get(key, {}),
                                 cur)
        return rows

    name = f"{_prefix}{model.name}"
    # forward timing (second call: first may pay compilation)
    model.apply(params, state, x)
    t0 = time.perf_counter()
    out, _ = model.apply(params, state, x)
    _block(out)
    fwd_s = time.perf_counter() - t0

    bwd_s = 0.0
    if backward and jax.tree_util.tree_leaves(params):
        def loss(p, inp):
            o, _ = model.apply(p, state, inp)
            return jnp.sum(jnp.asarray(
                jax.tree_util.tree_leaves(o)[0]) ** 2)

        g = jax.grad(loss)(params, x)  # warm
        t0 = time.perf_counter()
        g = jax.grad(loss)(params, x)
        _block(g)
        bwd_s = time.perf_counter() - t0
    rows.append((name, type(model).__name__, fwd_s, bwd_s))
    return rows


def get_times_grouped(model: Module, params, state, x,
                      **kw) -> Dict[str, Tuple[float, float, int]]:
    """Reference ``getTimesGroupByModuleType``: {type: (fwd_s, bwd_s, n)}."""
    grouped: Dict[str, Tuple[float, float, int]] = {}
    for _, typ, f, b in get_times(model, params, state, x, **kw):
        pf, pb, n = grouped.get(typ, (0.0, 0.0, 0))
        grouped[typ] = (pf + f, pb + b, n + 1)
    return grouped


def get_times_by_type(model: Module, params, state, x,
                      **kw) -> Dict[str, Dict[str, float]]:
    """Full reference-parity ``getTimesGroupByModuleType`` aggregate
    (AbstractModule.scala:180-186): per module TYPE, the instance
    count, total forward/backward seconds, and the per-instance means.

    ``{type: {"count", "fwd_total_s", "bwd_total_s",
              "fwd_mean_s", "bwd_mean_s"}}``
    """
    out: Dict[str, Dict[str, float]] = {}
    for typ, (f, b, n) in get_times_grouped(model, params, state, x,
                                            **kw).items():
        out[typ] = {
            "count": n,
            "fwd_total_s": f,
            "bwd_total_s": b,
            "fwd_mean_s": f / n,
            "bwd_mean_s": b / n,
        }
    return out


def format_times_by_type(grouped: Dict[str, Dict[str, float]]) -> str:
    """Table like the reference's grouped-times log dump, heaviest
    (fwd+bwd total) type first."""
    out = [f"{'type':28s} {'count':>5s} {'fwd ms':>9s} {'bwd ms':>9s} "
           f"{'fwd/ea':>9s} {'bwd/ea':>9s}"]
    rows = sorted(grouped.items(),
                  key=lambda kv: kv[1]["fwd_total_s"]
                  + kv[1]["bwd_total_s"], reverse=True)
    for typ, r in rows:
        out.append(
            f"{typ[:28]:28s} {r['count']:5d} "
            f"{r['fwd_total_s'] * 1e3:9.3f} {r['bwd_total_s'] * 1e3:9.3f} "
            f"{r['fwd_mean_s'] * 1e3:9.3f} {r['bwd_mean_s'] * 1e3:9.3f}")
    return "\n".join(out)


def format_times(rows) -> str:
    """Human-readable table like the reference's getTimes log dump."""
    out = [f"{'module':40s} {'type':28s} {'fwd ms':>9s} {'bwd ms':>9s}"]
    for name, typ, f, b in rows:
        out.append(f"{name[:40]:40s} {typ[:28]:28s} {f*1e3:9.3f} {b*1e3:9.3f}")
    return "\n".join(out)


@contextlib.contextmanager
def trace(logdir: str):
    """``with profiling.trace('/tmp/tb'):`` — wraps jax.profiler; open
    the result in TensorBoard's profile plugin / xprof.

    The :mod:`bigdl_tpu.telemetry` tracer follows the session, so the
    program's own spans (training-loop phases, prefetch producer,
    checkpoint writer, serving threads) are in the same trace, on plane
    ``/host:CPU`` and on the device planes' clock."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named region inside a traced step (jax.profiler.TraceAnnotation)."""
    return jax.profiler.TraceAnnotation(name)
