"""Numerics observatory: in-graph gradient/update statistics, NaN
provenance, and divergence early-warning (docs/observability.md
§Numerics).

The systems telemetry (spans, cost/MFU, HBM ledger, X-ray) watches the
*machine*; this module watches the *model*.  The reference framework
shipped model visibility as a first-class feature — TrainSummary
parameter/gradient histograms feeding TensorBoard (BigDL paper
§visualization) — and the async engine needs it twice over: the
deferred-NaN path names the iteration that diverged but never the
layer, and an adaptive runtime (ROADMAP §5) needs numerics sensors
before any controller can act on them.

Three pieces:

* :func:`collect` — traced INSIDE the compiled train step: per-layer
  gradient/parameter/update norms, non-finite counts, and a small
  deterministic parameter subsample (the TensorBoard histogram feed),
  reduced on device to one tiny f32/i32 pytree.  The stats ride the
  step's outputs and are fetched only at the existing
  ``BIGDL_TPU_SYNC_WINDOW`` drain — the async loop gains zero extra
  host sync points.  Stats OFF (the default) leaves the step jaxpr
  byte-identical (graft-lint target ``numerics_step_parity``).
* :class:`NumericsMonitor` — host-side consumer of drained stats:
  rolling thresholds raise early-warning ``numerics_anomaly`` instants
  (grad-norm spike/vanish, update/param ratio out-of-band, non-finite
  count > 0) that the Watchdog counts BEFORE the loss drain ever sees
  a NaN, plus the per-step ``numerics`` sample instant that renders as
  a Perfetto grad-norm counter lane and feeds the cluster grad-norm
  skew rollup.
* :func:`nan_provenance` — the one-shot diagnostic the retry-from-
  checkpoint handler runs after a ``loss_divergence``: re-run the
  failing batch (restored params, retained device batch) with
  per-layer finite masks and name the first offending layer/op in a
  ``nan_provenance`` instant.

Env knobs (all in the docs/observability.md knob table):
``BIGDL_TPU_NUMERICS=1`` turns stats on, ``BIGDL_TPU_NUMERICS_HIST``
sets the parameter-subsample budget (default 1024),
``BIGDL_TPU_NUMERICS_SPIKE`` / ``BIGDL_TPU_NUMERICS_VANISH`` /
``BIGDL_TPU_NUMERICS_BAND`` tune the early-warning thresholds.
"""
from __future__ import annotations

import logging
import math
import os
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.telemetry.tracer import CAT_TRAIN, get_tracer

logger = logging.getLogger("bigdl_tpu.telemetry")

# instant names (the Watchdog dispatches on NUMERICS_EVENT; the
# Perfetto exporters render NUMERICS_SAMPLE as a counter lane)
NUMERICS_SAMPLE = "numerics"
NUMERICS_EVENT = "numerics_anomaly"
PROVENANCE_EVENT = "nan_provenance"
RECOVERY_EVENT = "divergence_recovery"

DEFAULT_HIST = 1024
MIN_LAYER_HIST = 16


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


def enabled() -> bool:
    """``BIGDL_TPU_NUMERICS=1`` opts the engines in (default off: the
    compiled step stays byte-identical to the stats-free program)."""
    return os.environ.get("BIGDL_TPU_NUMERICS", "0") == "1"


@dataclass(frozen=True)
class NumericsSpec:
    """Static (trace-time) configuration of the in-graph stats.

    ``layers``: forward-order top-level layer names (container child
    keys) — the order "first offending layer" is resolved in; empty
    means sorted parameter-tree order.  ``hist``: total parameter-
    subsample budget shared by the per-layer histogram feeds.
    """

    layers: Tuple[str, ...] = ()
    hist: int = DEFAULT_HIST


def spec_for(model=None, hist: Optional[int] = None) -> NumericsSpec:
    """Build the spec for a model (captures forward layer order when
    the model is a container)."""
    keys = getattr(model, "child_keys", None) or ()
    h = int(hist) if hist is not None else int(
        _env_float("BIGDL_TPU_NUMERICS_HIST", DEFAULT_HIST))
    return NumericsSpec(layers=tuple(keys), hist=max(MIN_LAYER_HIST, h))


# --------------------------------------------------------------------------
# in-graph collection (traced inside the train step)
# --------------------------------------------------------------------------

def _top_key(path) -> str:
    e = path[0]
    for attr in ("key", "idx", "name"):
        v = getattr(e, attr, None)
        if v is not None:
            return str(v)
    return str(e)


def _layer_groups(params, layer_order) -> List[Tuple[str, List[int]]]:
    """[(layer name, [leaf index...])] grouped by the parameter tree's
    top-level key, in forward order when known (trace-time static)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    groups: Dict[str, List[int]] = {}
    for i, (path, _leaf) in enumerate(flat):
        groups.setdefault(_top_key(path) if path else "__root__",
                          []).append(i)
    order = [k for k in layer_order if k in groups]
    order += [k for k in sorted(groups) if k not in order]
    return [(k, groups[k]) for k in order]


def _subsample(leaves, budget: int):
    """Deterministic strided subsample totalling ~``budget`` f32
    points across ``leaves`` (shapes static: no host round trip)."""
    total = sum(int(np.prod(l.shape)) for l in leaves)
    if total == 0:
        return jnp.zeros((0,), jnp.float32)
    stride = max(1, total // max(1, budget))
    parts = [jnp.ravel(l)[::stride].astype(jnp.float32) for l in leaves
             if int(np.prod(l.shape))]
    out = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    return out[:budget]


def subsample_tree(tree, budget: int = DEFAULT_HIST):
    """Subsample a whole pytree (eager or traced) — the TrainSummary
    fallback when no in-graph stats are flowing."""
    return _subsample(jax.tree_util.tree_leaves(tree), budget)


def collect(params, grads, new_params, spec: NumericsSpec):
    """Per-layer + global stats pytree, computed inside the step.

    All reductions happen on device; the result is a handful of f32
    scalars, i32 non-finite counts, and the subsampled histogram
    vectors — a few KB however large the model.  ``new_params`` gives
    the update delta (``new - old``) without materializing it outside
    the update the optimizer already computed.
    """
    p_leaves = jax.tree_util.tree_leaves(params)
    g_leaves = jax.tree_util.tree_leaves(grads)
    n_leaves = jax.tree_util.tree_leaves(new_params)
    if not p_leaves:
        z = jnp.zeros((), jnp.float32)
        return {"layers": {}, "grad_norm": z, "param_norm": z,
                "update_norm": z, "nonfinite": jnp.zeros((), jnp.int32)}

    def sumsq(x):
        return jnp.sum(jnp.square(x.astype(jnp.float32)))

    def n_bad(x):
        return jnp.sum(~jnp.isfinite(x)).astype(jnp.int32)

    total = sum(int(np.prod(l.shape)) for l in p_leaves) or 1
    layers: Dict[str, Dict[str, Any]] = {}
    g_tot = p_tot = u_tot = None
    nf_tot = None
    for name, idxs in _layer_groups(params, spec.layers):
        gss = sum(sumsq(g_leaves[i]) for i in idxs)
        pss = sum(sumsq(p_leaves[i]) for i in idxs)
        uss = sum(sumsq(n_leaves[i] - p_leaves[i]) for i in idxs)
        nf = sum(n_bad(g_leaves[i]) for i in idxs)
        layer_n = sum(int(np.prod(p_leaves[i].shape)) for i in idxs)
        k = max(MIN_LAYER_HIST, spec.hist * layer_n // total)
        layers[name] = {
            "g": jnp.sqrt(gss), "p": jnp.sqrt(pss), "u": jnp.sqrt(uss),
            "nf": nf, "hist": _subsample([n_leaves[i] for i in idxs], k),
        }
        g_tot = gss if g_tot is None else g_tot + gss
        p_tot = pss if p_tot is None else p_tot + pss
        u_tot = uss if u_tot is None else u_tot + uss
        nf_tot = nf if nf_tot is None else nf_tot + nf
    return {
        "layers": layers,
        "grad_norm": jnp.sqrt(g_tot),
        "param_norm": jnp.sqrt(p_tot),
        "update_norm": jnp.sqrt(u_tot),
        "nonfinite": nf_tot,
    }


# --------------------------------------------------------------------------
# host-side monitor (drain-cadence thresholds -> early-warning instants)
# --------------------------------------------------------------------------

def _parse_band(raw: str) -> Tuple[float, float]:
    try:
        lo, hi = raw.split(":")
        return float(lo), float(hi)
    except (ValueError, AttributeError):
        return 1e-10, 0.5


class NumericsMonitor:
    """Consumes drained (host-side) stats on the sync-window cadence.

    Every observed step emits one ``numerics`` sample instant (the
    Perfetto counter-lane / cluster-skew feed) and, when a rolling
    threshold trips, a ``numerics_anomaly`` instant the Watchdog
    counts — fired from the drain, i.e. BEFORE the loss value of the
    same pending window is converted (a non-finite gradient count
    therefore always precedes the ``loss_divergence`` raise).
    """

    def __init__(self, spec: Optional[NumericsSpec] = None, *,
                 spike_factor: Optional[float] = None,
                 vanish_floor: Optional[float] = None,
                 ratio_band: Optional[Tuple[float, float]] = None,
                 history: int = 64, warmup: int = 8,
                 log=logger.warning):
        self.spec = spec or NumericsSpec()
        self._spike = spike_factor if spike_factor is not None else \
            _env_float("BIGDL_TPU_NUMERICS_SPIKE", 10.0)
        self._vanish = vanish_floor if vanish_floor is not None else \
            _env_float("BIGDL_TPU_NUMERICS_VANISH", 1e-8)
        self._band = ratio_band if ratio_band is not None else \
            _parse_band(os.environ.get("BIGDL_TPU_NUMERICS_BAND",
                                       "1e-10:0.5"))
        self._hist: deque = deque(maxlen=max(8, int(history)))
        self._warmup = max(0, int(warmup))
        self._log = log
        self.anomaly_count = 0
        self.last: Optional[Dict[str, Any]] = None  # scalar view
        self.last_stats: Optional[Dict[str, Any]] = None  # full host tree

    def first_nonfinite_layer(self, stats) -> Optional[str]:
        layers = stats.get("layers") or {}
        order = [k for k in self.spec.layers if k in layers]
        order += [k for k in sorted(layers) if k not in order]
        for name in order:
            if int(layers[name]["nf"]) > 0:
                return name
        return None

    def observe(self, iteration: int, stats) -> List[str]:
        """Digest one drained stats pytree; returns anomaly kinds."""
        tracer = get_tracer()
        g = float(stats["grad_norm"])
        p = float(stats["param_norm"])
        u = float(stats["update_norm"])
        nf = int(stats["nonfinite"])
        ratio = (u / p) if p > 0 else 0.0
        corr = f"step:{iteration}"
        self.last = {"iteration": iteration, "grad_norm": g,
                     "param_norm": p, "update_norm": u,
                     "update_ratio": ratio, "nonfinite": nf}
        self.last_stats = stats
        tracer.instant(
            NUMERICS_SAMPLE, CAT_TRAIN, corr=corr,
            args={"iteration": iteration, "grad_norm": g,
                  "update_ratio": ratio, "nonfinite": nf})
        fired: List[str] = []

        def fire(kind: str, message: str, **extra):
            fired.append(kind)
            self.anomaly_count += 1
            tracer.instant(
                NUMERICS_EVENT, CAT_TRAIN, corr=corr,
                args={"kind": kind, "iteration": iteration,
                      "message": message, **extra})
            if self._log is not None:
                try:
                    self._log("numerics: %s", message)
                except Exception:
                    pass

        if nf > 0 or not math.isfinite(g):
            layer = self.first_nonfinite_layer(stats)
            fire("nonfinite",
                 f"{nf} non-finite gradient value(s) at iteration "
                 f"{iteration}"
                 + (f" (first offending layer {layer!r})" if layer
                    else ""),
                 layer=layer, count=nf)
            return fired  # spike/ratio math is meaningless on NaN
        warm = len(self._hist) >= max(1, self._warmup)
        if warm:
            med = sorted(self._hist)[len(self._hist) // 2]
            if med > 0 and g > self._spike * med:
                fire("grad_spike",
                     f"grad norm {g:.3e} is x{g / med:.1f} the rolling "
                     f"median {med:.3e} at iteration {iteration}",
                     grad_norm=g, median=med)
            elif g < self._vanish:
                fire("grad_vanish",
                     f"grad norm {g:.3e} under the vanish floor "
                     f"{self._vanish:.1e} at iteration {iteration}",
                     grad_norm=g)
            lo, hi = self._band
            if p > 0 and not (lo <= ratio <= hi):
                fire("update_ratio",
                     f"update/param ratio {ratio:.3e} outside "
                     f"[{lo:.1e}, {hi:.1e}] at iteration {iteration}",
                     update_ratio=ratio)
        self._hist.append(g)
        return fired


# --------------------------------------------------------------------------
# NaN/Inf provenance (one-shot diagnostic off the hot path)
# --------------------------------------------------------------------------

def _tree_nonfinite(tree) -> int:
    return int(sum(int(np.sum(~np.isfinite(np.asarray(l))))
                   for l in jax.tree_util.tree_leaves(tree)))


def nan_provenance(model, params, model_state, features, targets,
                   criterion=None, compute_dtype=None,
                   rng=None) -> Dict[str, Any]:
    """Re-run a failing batch and localize the first non-finite
    layer/op.  Eager and one-shot: this runs on the recovery path,
    never on the hot loop.

    Resolution order: poisoned *input* data; the first layer (forward
    order) whose output goes non-finite on finite input (containers
    with per-child apply — ``Sequential`` — are walked layer by
    layer); else the LAST forward-order layer with non-finite grads
    (backward NaNs propagate toward the input, so the origin is the
    deepest layer still carrying them).
    """
    report: Dict[str, Any] = {"layer": None, "site": None, "loss": None,
                              "layers": {}}
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    report["input_nonfinite"] = _tree_nonfinite(features)
    if report["input_nonfinite"]:
        report["site"] = "input"

    cast = (lambda t: jax.tree_util.tree_map(
        lambda x: x.astype(compute_dtype), t)) if compute_dtype \
        else (lambda t: t)
    if compute_dtype:  # the step feeds float features in compute dtype
        from bigdl_tpu.utils.flatten import cast_floating

        features = cast_floating(features, compute_dtype)

    # forward walk (per-child apply) for ordered containers
    keys = getattr(model, "child_keys", None)
    if keys and hasattr(model, "_child_apply"):
        x = features
        prev_finite = report["input_nonfinite"] == 0
        cp = cast(params)
        for i, k in enumerate(keys):
            try:
                x, _ = model._child_apply(
                    i, cp, model_state, x, training=True, rng=rng)
            except Exception:
                break
            nf = _tree_nonfinite(x)
            report["layers"][k] = {"out_nonfinite": nf}
            if nf and report["layer"] is None and prev_finite:
                report["layer"], report["site"] = k, "forward"
            prev_finite = nf == 0

    # full backward: per-layer gradient finite masks
    def loss_fn(p):
        out, _ = model.apply(cast(p), model_state, features,
                             training=True, rng=rng)
        if criterion is not None:
            return criterion.forward(out, targets).astype(jnp.float32)
        return jnp.sum(out).astype(jnp.float32)

    try:
        loss, grads = jax.value_and_grad(loss_fn)(params)
        report["loss"] = float(loss)
        bad_layers = []
        for name, idxs in _layer_groups(params, tuple(keys or ())):
            g_leaves = jax.tree_util.tree_leaves(grads)
            nf = int(sum(_tree_nonfinite(g_leaves[i]) for i in idxs))
            report["layers"].setdefault(name, {})["grad_nonfinite"] = nf
            if nf:
                bad_layers.append(name)
        if report["site"] is None and bad_layers:
            # origin of a backward NaN = deepest layer still carrying it
            report["layer"], report["site"] = bad_layers[-1], "backward"
    except Exception as e:  # diagnostics must never kill recovery
        report["error"] = repr(e)
    return report


def emit_provenance(report: Dict[str, Any], iteration: int) -> None:
    """Publish a provenance report as the ``nan_provenance`` instant,
    correlated with the ``loss_divergence`` instant of the same step."""
    get_tracer().instant(
        PROVENANCE_EVENT, CAT_TRAIN, corr=f"step:{iteration}",
        args={"iteration": iteration,
              "layer": report.get("layer"),
              "site": report.get("site"),
              "input_nonfinite": report.get("input_nonfinite", 0),
              "loss": str(report.get("loss"))})
