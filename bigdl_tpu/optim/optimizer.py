"""Training engine (reference optim/Optimizer.scala:47-681,
DistriOptimizer.scala, LocalOptimizer.scala — SURVEY.md §2.5, §3.1).

:class:`Optimizer` is the fluent builder (validation/checkpoint/summary/
clipping/end-trigger config).  :class:`LocalOptimizer` runs the loop on
the local device(s) with ONE jitted train step:

    (params, model_state, opt_state, step, rng, batch, lr)
        -> (params', model_state', opt_state', loss)

Semantics carried over from the reference:
* triggers for end/validation/checkpoint (Trigger.scala)
* checkpoint + resume mid-epoch via OptimMethod.state epoch/neval
  bookkeeping (DistriOptimizer.scala:124-134, 875-879)
* retry-from-checkpoint fault recovery, rate-limited ``max_retry``
  (DistriOptimizer.scala:900-960)
* per-iteration metrics + the canonical throughput/loss log line
  (DistriOptimizer.scala:411-416)
* per-submodule optimizer methods (``set_optim_methods`` keyed by
  top-level parameter subtree, reference multi-optim Optimizer.scala)
* constant / L2-norm gradient clipping (Optimizer.scala:420-466)

Deliberately absent: gradient-drop straggler mitigation — SPMD lockstep
has no stragglers to drop (SURVEY.md §2.4 note).

Async engine (docs/async_engine.md): by default the driver loop never
forces a host round-trip on the hot path — batches are host-transformed
and device-placed by a background prefetch thread
(dataset/prefetch.py), the per-step loss stays a device array and is
drained only at the logging/trigger cadence (bounded window,
``BIGDL_TPU_SYNC_WINDOW``, default 10 — divergence is still detected,
up to one window late, and still feeds retry-from-checkpoint), and
checkpoint serialization/writes happen on a background writer thread.
``BIGDL_TPU_SYNC_LOOP=1`` restores the fully synchronous loop for A/B
and debugging.
"""
from __future__ import annotations

import contextlib
import logging
import math
import os
import sys
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.dataset.dataset import AbstractDataSet
from bigdl_tpu.dataset.prefetch import DevicePrefetcher
from bigdl_tpu.nn.criterion import Criterion
from bigdl_tpu.nn.module import Module
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.optim.optim_method import OptimMethod, SGD
from bigdl_tpu.optim.triggers import Trigger
from bigdl_tpu.optim.validation import ValidationMethod
from bigdl_tpu.telemetry import costmodel, numerics as numerics_mod, programs
from bigdl_tpu.telemetry import debug_server, flightrecorder
from bigdl_tpu.telemetry.tracer import CAT_TRAIN, get_tracer, set_correlation
from bigdl_tpu.utils import file_io
from bigdl_tpu.utils.flatten import cast_floating, global_norm
from bigdl_tpu.utils.serialization import load_pytree, save_pytree

logger = logging.getLogger("bigdl_tpu.optim")


class Optimizer:
    """Fluent training configuration + factory (reference Optimizer.scala)."""

    def __init__(
        self,
        model: Module,
        dataset: AbstractDataSet,
        criterion: Criterion,
        end_trigger: Optional[Trigger] = None,
        batch_size: Optional[int] = None,
    ):
        self.model = model
        self.dataset = dataset
        self.criterion = criterion
        self.end_trigger = end_trigger or Trigger.max_epoch(1)
        self.optim_methods: Dict[str, OptimMethod] = {"__all__": SGD(1e-2)}
        self.val_trigger: Optional[Trigger] = None
        self.val_dataset: Optional[AbstractDataSet] = None
        self.val_methods: Optional[List[ValidationMethod]] = None
        self.checkpoint_path: Optional[str] = None
        self.checkpoint_trigger: Optional[Trigger] = None
        self.overwrite_checkpoint = True
        self.train_summary = None
        self.val_summary = None
        self.grad_clip_const: Optional[Tuple[float, float]] = None
        self.grad_clip_norm: Optional[float] = None
        self.compute_dtype = None  # e.g. jnp.bfloat16 for mixed precision
        self.accum_steps = 1
        self.max_retry = 5
        self.retry_window_sec = 600.0
        self._resume_from: Optional[str] = None
        self._initial_variables: Optional[Dict[str, Any]] = None
        # -- async engine state (LocalOptimizer.optimize wires these) --
        self._sync_loop = False
        self._async_engine = False
        self.sync_window = 10
        # (iteration, device loss, n, device numerics stats or None)
        self._pending: "deque" = deque()
        self._ckpt_pool = None
        self._ckpt_future = None
        self._retries = 0
        self._last_failure = 0.0
        self._stop_requested = False
        # -- numerics observatory (telemetry/numerics.py) --
        self._numerics_requested: Optional[bool] = None  # None = env knob
        self._numerics = None  # NumericsSpec when the step carries stats
        self._numerics_monitor = None
        self._recent_batches = None  # (iteration, features, targets)
        self._diverged_at: Optional[int] = None

    def request_stop(self) -> None:
        """Ask the training loop to stop at the next iteration boundary:
        it drains the in-flight async window, forces a final checkpoint
        (when checkpointing is configured), joins the writer and returns.
        Signal-handler/thread safe — the elastic worker maps SIGTERM
        here so preemption leaves committed, restorable state."""
        self._stop_requested = True

    # -- fluent config (reference names) -------------------------------
    def set_optim_method(self, method: OptimMethod) -> "Optimizer":
        self.optim_methods = {"__all__": method}
        return self

    def set_optim_methods(self, methods: Dict[str, OptimMethod]) -> "Optimizer":
        """Per-top-level-submodule methods (reference multi-optim)."""
        self.optim_methods = methods
        return self

    def set_end_when(self, trigger: Trigger) -> "Optimizer":
        self.end_trigger = trigger
        return self

    def set_validation(
        self,
        trigger: Trigger,
        dataset: AbstractDataSet,
        methods: List[ValidationMethod],
    ) -> "Optimizer":
        self.val_trigger = trigger
        self.val_dataset = dataset
        self.val_methods = methods
        return self

    def set_checkpoint(self, path: str, trigger: Trigger) -> "Optimizer":
        self.checkpoint_path = path
        self.checkpoint_trigger = trigger
        return self

    def over_write_checkpoint(self, overwrite: bool = True) -> "Optimizer":
        self.overwrite_checkpoint = overwrite
        return self

    def set_train_summary(self, summary) -> "Optimizer":
        self.train_summary = summary
        return self

    def set_val_summary(self, summary) -> "Optimizer":
        self.val_summary = summary
        return self

    def set_constant_gradient_clipping(self, min_v: float, max_v: float) -> "Optimizer":
        self.grad_clip_const = (min_v, max_v)
        return self

    def set_gradient_clipping_by_l2_norm(self, clip_norm: float) -> "Optimizer":
        self.grad_clip_norm = clip_norm
        return self

    def set_compute_dtype(self, dtype) -> "Optimizer":
        self.compute_dtype = dtype
        return self

    def set_numerics(self, on: bool = True) -> "Optimizer":
        """Opt the compiled step in (or out) of in-graph numerics stats
        — per-layer grad/param/update norms + non-finite counts drained
        on the sync-window cadence (docs/observability.md §Numerics).
        Overrides the ``BIGDL_TPU_NUMERICS`` env knob."""
        self._numerics_requested = bool(on)
        return self

    def set_gradient_accumulation(self, steps: int) -> "Optimizer":
        """Split every batch into ``steps`` sequential micro-batches with
        f32 gradient accumulation (batch size must divide by it)."""
        assert steps >= 1
        self.accum_steps = int(steps)
        return self

    def resume_from(self, checkpoint: str) -> "Optimizer":
        self._resume_from = checkpoint
        return self

    def set_initial_variables(self, variables: Dict[str, Any]) -> "Optimizer":
        """Start from externally produced ``{"params", "state"}`` trees —
        e.g. a Caffe/TF-loaded snapshot (reference setModel/loadCaffe
        fine-tune path)."""
        self._initial_variables = variables
        return self

    def optimize(self) -> Module:
        raise NotImplementedError

    @staticmethod
    def apply(model, dataset, criterion, end_trigger=None, batch_size=None,
              **distri_kwargs):
        """Factory matching reference Optimizer.apply (Optimizer.scala:
        660-681, which dispatches Distri vs Local by dataset/topology):
        picks :class:`DistriOptimizer` when more than one device is
        visible (or a mesh is passed) AND the dataset's batches divide
        evenly over them, else :class:`LocalOptimizer`."""
        from bigdl_tpu.optim.distri_optimizer import DistriOptimizer

        if distri_kwargs.get("mesh") is not None:
            return DistriOptimizer(
                model, dataset, criterion, end_trigger, batch_size,
                **distri_kwargs,
            )
        n_dev = len(jax.devices())
        ds_batch = batch_size
        probe = dataset
        while ds_batch is None and probe is not None:
            # unwrap TransformedDataSet/DistributedDataSet chains so a
            # wrapped dataset is not silently demoted to LocalOptimizer
            ds_batch = getattr(probe, "batch_size", None)
            probe = getattr(probe, "base", None)
        if n_dev > 1 and ds_batch is not None and ds_batch % n_dev == 0:
            return DistriOptimizer(
                model, dataset, criterion, end_trigger, batch_size,
                **distri_kwargs,
            )
        return LocalOptimizer(model, dataset, criterion, end_trigger, batch_size)


def _clip_grads(grads, clip_const, clip_norm):
    if clip_const is not None:
        lo, hi = clip_const
        grads = jax.tree_util.tree_map(lambda g: jnp.clip(g, lo, hi), grads)
    if clip_norm is not None:
        norm = global_norm(grads)
        scale = jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-12))
        grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    return grads


def _aux_losses(state) -> list:
    """Collect auxiliary training losses a module surfaced through its
    state tree (key ``aux_loss`` — e.g. the MoE router's load-balance
    term, parallel/expert.py)."""
    out = []
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    for path, leaf in flat:
        last = path[-1]
        key = getattr(last, "key", None)
        if key == "aux_loss":
            out.append(leaf)
    return out


def make_train_step(
    model: Module,
    criterion: Criterion,
    optim_methods: Dict[str, OptimMethod],
    grad_clip_const=None,
    grad_clip_norm=None,
    compute_dtype=None,
    aux_loss_weight: float = 0.01,
    accum_steps: int = 1,
    numerics=None,
) -> Callable:
    """Build the pure train step shared by Local and Distri optimizers.

    ``accum_steps > 1``: the batch is split into that many micro-batches
    run sequentially under ``lax.scan`` with f32 gradient accumulation —
    the reference reaches its 8192 global batch by adding nodes
    (whitepaper fig 7); on a small mesh the same effective batch comes
    from accumulation at constant memory.

    ``numerics``: optional :class:`telemetry.numerics.NumericsSpec` —
    the step then returns a fifth output, the small on-device stats
    pytree (per-layer grad/param/update norms, non-finite counts,
    parameter subsamples), computed from the post-clip gradients the
    optimizer actually consumed.  ``None`` (default) leaves the step
    byte-identical to the stats-free program (graft-lint target
    ``numerics_step_parity``).
    """

    method_items = sorted(optim_methods.items())

    def select(tree, key):
        if key == "__all__":
            return tree
        return {key: tree[key]}

    def _loss_and_grad(params, model_state, rng, features, targets):
        if compute_dtype is not None:
            # the layers compute in their INPUT's dtype (weights are
            # cast to it), so float features must enter in the compute
            # dtype too — an f32 image batch would otherwise drag the
            # whole network back to f32 activations
            features = cast_floating(features, compute_dtype)

        def loss_fn(p):
            p_c = (
                jax.tree_util.tree_map(lambda x: x.astype(compute_dtype), p)
                if compute_dtype is not None
                else p
            )
            out, new_state = model.apply(
                p_c, model_state, features, training=True, rng=rng
            )
            with jax.named_scope("loss"):
                loss = criterion.forward(out, targets).astype(jnp.float32)
            # fold in module-surfaced auxiliary losses (MoE load balance)
            for aux in _aux_losses(new_state):
                loss = loss + aux_loss_weight * aux.astype(jnp.float32)
            return loss, new_state

        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    def train_step(params, model_state, opt_states, step, rng, features, targets, lrs):
        if accum_steps <= 1:
            (loss, new_model_state), grads = _loss_and_grad(
                params, model_state, rng, features, targets)
        else:
            k = accum_steps
            tm = jax.tree_util.tree_map
            bsz = jax.tree_util.tree_leaves(features)[0].shape[0]
            if bsz % k:
                raise ValueError(
                    f"batch size {bsz} is not divisible by "
                    f"gradient-accumulation steps {k}")
            micro_f = tm(lambda v: v.reshape((k, v.shape[0] // k)
                                             + v.shape[1:]), features)
            micro_t = tm(lambda v: v.reshape((k, v.shape[0] // k)
                                             + v.shape[1:]), targets)

            def micro(carry, xs):
                ms, gsum, lsum, i = carry
                f, t = xs
                (l, new_ms), g = _loss_and_grad(
                    params, ms, jax.random.fold_in(rng, i), f, t)
                gsum = tm(lambda a, b: a + b.astype(jnp.float32), gsum, g)
                return (new_ms, gsum, lsum + l, i + 1), None

            g0 = tm(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (new_model_state, gsum, lsum, _), _ = jax.lax.scan(
                micro,
                (model_state, g0, jnp.asarray(0.0, jnp.float32),
                 jnp.asarray(0, jnp.int32)),
                (micro_f, micro_t))
            scale = 1.0 / k
            grads = tm(lambda p, g: (g * scale).astype(p.dtype),
                       params, gsum)
            loss = lsum * scale
        with jax.named_scope("clip"):
            grads = _clip_grads(grads, grad_clip_const, grad_clip_norm)
        new_params = dict(params) if isinstance(params, dict) else params
        new_opt_states = {}
        for (name, method), lr in zip(method_items, lrs):
            sub_p = select(params, name)
            sub_g = select(grads, name)
            with jax.named_scope("optimizer"):
                upd, new_opt_states[name] = method.update(
                    sub_g, opt_states[name], sub_p, lr, step
                )
            if name == "__all__":
                new_params = upd
            else:
                new_params[name] = upd[name]
        if numerics is not None:
            stats = numerics_mod.collect(params, grads, new_params,
                                         numerics)
            return new_params, new_model_state, new_opt_states, loss, stats
        return new_params, new_model_state, new_opt_states, loss

    return train_step


class LocalOptimizer(Optimizer):
    """Single-process training loop (reference LocalOptimizer.scala:64-200;
    the intra-node replica cloning collapses into one XLA program over
    the full local batch)."""

    def optimize(self) -> Module:
        model, ds = self.model, self.dataset
        rng = jax.random.PRNGKey(42)
        variables = self._initial_variables or model.init(rng)
        self._template_variables = variables  # shape templates for step builders
        params, model_state = variables["params"], variables["state"]
        opt_states = {
            name: m.init_state(
                params if name == "__all__" else {name: params[name]}
            )
            for name, m in self.optim_methods.items()
        }
        driver_state: Dict[str, Any] = {
            "epoch": 0, "neval": 0, "loss": float("nan"),
            "score": float("-inf"), "records_processed": 0,
            "batch_in_epoch": 0, "epoch_finished": False,
        }
        self._driver_state = driver_state  # train_log_line reads it
        self._step_cost = None
        self._step_cost_tried = False
        # stable X-ray program name (DistriOptimizer narrows it to the
        # dp/compressed variant in its _build_step_fn)
        if not getattr(self, "_step_program", None):
            self._step_program = "train_step"
        # the step is built BEFORE any resume: sharded restore needs the
        # placement (target shardings) the builder computes
        step_fn = self._build_step_fn(model)
        if self._resume_from:
            params, model_state, opt_states = self._load_resume(
                params, model_state, opt_states, driver_state)
        params, model_state, opt_states = self._place(
            params, model_state, opt_states
        )

        self.metrics = metrics = Metrics()
        # epoch accounting is batch-based: a pass = batches_per_epoch
        # batches (record-count accounting drifts when size % batch != 0
        # or under per-host sharding)
        batches_per_epoch = max(1, ds.batches_per_epoch())
        wall_start = time.time()
        self._sync_loop = os.environ.get("BIGDL_TPU_SYNC_LOOP") == "1"
        self._async_engine = not self._sync_loop
        self.sync_window = max(
            1, int(os.environ.get("BIGDL_TPU_SYNC_WINDOW", "10")))
        self._pending = deque()
        self._numerics_monitor = None
        self._recent_batches = None
        self._diverged_at = None
        if self._numerics is not None:
            self._numerics_monitor = numerics_mod.NumericsMonitor(
                self._numerics)
            # failing batches stay referenced (batches are NOT donated)
            # long enough for the one-shot provenance replay after a
            # deferred divergence fires in the drain
            self._recent_batches = deque(maxlen=self.sync_window + 2)
        self._retries = 0
        self._last_failure = 0.0
        self._log_t0 = time.perf_counter()
        self._log_records = 0
        self._last_throughput = 0.0
        # live ops plane (docs/observability.md §Live ops plane): pure
        # host-side registration with the per-process debug server and
        # black box; nothing here reaches the compiled step (graft-lint
        # target debug_plane_parity holds the line)
        detach_debug = debug_server.attach_engine(
            "train", role="train", metrics=lambda: self.metrics,
            status=self.train_log_line)
        dbg = debug_server.get_debug_server(create=False)
        if dbg is not None and self._numerics_monitor is not None:
            dbg.set_numerics(self._numerics_monitor)
        flight = flightrecorder.get_flight_recorder()
        if flight is not None:
            flight.add_metrics("train", lambda: self.metrics)
            if self._numerics_monitor is not None:
                mon = self._numerics_monitor
                flight.add_blob(
                    "numerics",
                    lambda: {"last": dict(getattr(mon, "last", None)
                                          or {})})
        prefetcher = None
        if self._async_engine:
            # batches are host-transformed and device-placed on the
            # producer thread ('data' = producer time per batch); the
            # loop only ever blocks on an empty queue ('data_stall').
            # The producer's own 'prefetch_item' span already covers
            # this interval on the shared timeline (with the item's
            # correlation ID), so the 'data' phase stays metrics-only.
            metrics.no_span("data")
            prefetcher = DevicePrefetcher(
                ds.data(train=True), place=self._prefetch_place,
                timer=lambda dt: metrics.add("data", dt))
            data_iter = prefetcher
        else:
            data_iter = ds.data(train=True)
        ckpt_dir = self._prepare_ckpt_dir()

        try:
            tracer = get_tracer()
            while not self._stop_requested:
                with tracer.span("trigger", CAT_TRAIN):
                    if self.end_trigger(driver_state):
                        break
                try:
                    self._one_iteration(
                        step_fn, params, model_state, opt_states,
                        driver_state, data_iter, metrics,
                        batches_per_epoch, wall_start,
                    )
                    # pull updated trees back (rebound inside
                    # _one_iteration via the returned values)
                    params, model_state, opt_states = self._last_trees
                    if driver_state["epoch_finished"]:
                        for m in self.optim_methods.values():
                            m.state["epoch"] = driver_state["epoch"]
                    with tracer.span("trigger", CAT_TRAIN):
                        self._maybe_validate(
                            model, params, model_state, driver_state)
                    self._maybe_checkpoint(
                        ckpt_dir, params, model_state, opt_states,
                        driver_state)
                except (FloatingPointError, RuntimeError, ValueError) as e:
                    params, model_state, opt_states = \
                        self._recover_or_reraise(e, ckpt_dir, driver_state)
                    continue
                driver_state["epoch_finished"] = False
            # the final in-flight window: a divergence here still
            # restores the last good checkpoint instead of raising
            try:
                self._drain_losses(driver_state, metrics)
                if self._stop_requested:
                    # graceful stop (preemption): persist the exact
                    # iteration we stopped at so resume replays from it
                    self._maybe_checkpoint(
                        ckpt_dir, params, model_state, opt_states,
                        driver_state, force=True)
            except FloatingPointError as e:
                params, model_state, opt_states = \
                    self._recover_or_reraise(e, ckpt_dir, driver_state)
        finally:
            detach_debug()
            if prefetcher is not None:
                prefetcher.close()
            # an exception is already propagating: don't let a writer
            # failure mask it
            self._finish_checkpoints(
                raise_errors=sys.exc_info()[0] is None)

        model._variables = {"params": params, "state": model_state}
        self.final_params = params
        self.final_state = model_state
        return model

    def _recover_or_reraise(self, e, ckpt_dir, driver_state):
        """Retry-from-checkpoint (DistriOptimizer.scala:900-960): rate-
        limited restore of the latest checkpoint; re-raises when retries
        are exhausted or no checkpoint exists.  Returns restored trees."""
        now = time.time()
        if now - self._last_failure > self.retry_window_sec:
            self._retries = 0
        self._retries += 1
        self._last_failure = now
        if self._retries > self.max_retry or not ckpt_dir:
            raise e
        # ORDER MATTERS: the background writer must be joined before
        # anything restores (or a recovery tears the process/mesh down)
        # — a restore racing an in-flight write could read the very
        # step being replaced, and an abandoned writer can wedge the
        # sharded commit's fragment gather
        self._wait_writer()
        detected_at = driver_state["neval"]
        restored = self._load_latest(ckpt_dir, driver_state)
        if restored is None:  # failed before any checkpoint existed
            raise e
        logger.warning("Training failure (%s); retry %d from checkpoint",
                       e, self._retries)
        diverged_at, self._diverged_at = self._diverged_at, None
        if diverged_at is not None:
            # one-shot diagnostic, strictly off the hot path: replay the
            # failing batch with per-layer finite masks and name the
            # first offending layer (telemetry/numerics.py)
            self._maybe_diagnose_divergence(restored, diverged_at)
        # machine-readable recovery record, correlated with the
        # loss_divergence instant of the same step
        get_tracer().instant(
            numerics_mod.RECOVERY_EVENT, CAT_TRAIN,
            corr=f"step:{diverged_at if diverged_at is not None else detected_at}",
            args={"iteration": diverged_at,
                  "detected_at": detected_at,
                  "restored_iteration": driver_state["neval"],
                  "replayed_steps": detected_at - driver_state["neval"],
                  "checkpoint_dir": ckpt_dir,
                  "retry": self._retries})
        # black-box the failure window before the retry overwrites it;
        # rate-limited, so this dedupes against the dump the
        # loss_divergence instant already triggered via the tracer
        flight = flightrecorder.get_flight_recorder()
        if flight is not None:
            flight.dump(
                trigger="loss_divergence" if diverged_at is not None
                else "train_retry",
                note=f"retry {self._retries}: {e}"[:400])
        # in-flight losses were produced by the diverged trajectory
        self._pending.clear()
        driver_state["epoch_finished"] = False
        return restored

    def _maybe_diagnose_divergence(self, restored, diverged_at):
        """NaN/Inf provenance: when numerics is on and the failing batch
        is still retained, re-run it eagerly (restored params, the
        step's own fold_in rng) and emit the ``nan_provenance`` instant
        naming the first non-finite layer/op.  Diagnostics never raise
        into the recovery path."""
        if self._numerics is None or not self._recent_batches:
            return
        batch = next((b for b in self._recent_batches
                      if b[0] == diverged_at), None)
        self._recent_batches.clear()
        if batch is None:
            return
        _, features, targets = batch
        params, model_state, _opt = restored
        try:
            report = numerics_mod.nan_provenance(
                self.model, params, model_state, features, targets,
                criterion=self.criterion,
                compute_dtype=self.compute_dtype,
                rng=jax.random.fold_in(jax.random.PRNGKey(7),
                                       diverged_at - 1))
        except Exception:
            logger.warning("nan provenance diagnostic failed",
                           exc_info=True)
            return
        numerics_mod.emit_provenance(report, diverged_at)
        if report.get("layer") is not None:
            logger.warning(
                "nan provenance: first offending layer %r (site=%s) "
                "for the divergence at iteration %d",
                report["layer"], report.get("site"), diverged_at)

    def _wait_writer(self):
        """Join the in-flight background checkpoint write, swallowing
        its errors (the recovery path must proceed off the last COMMIT
        even when the newest write failed)."""
        fut, self._ckpt_future = self._ckpt_future, None
        if fut is None:
            return
        try:
            fut.result()
        except Exception:
            logger.warning("in-flight checkpoint write failed during "
                           "recovery; restoring an older checkpoint",
                           exc_info=True)

    def _load_latest(self, ckpt_dir, driver_state):
        """Restore the newest checkpoint under ``ckpt_dir`` (None when
        there is none), updating ``driver_state`` in place.  Overridden
        by the sharded path."""
        latest = self._latest_ckpt(ckpt_dir)
        if latest is None:
            return None
        blob = load_pytree(latest)
        driver_state.update(
            {k: v.item() if hasattr(v, "item") else v
             for k, v in blob["driver_state"].items()}
        )
        return blob["params"], blob["model_state"], blob["opt_states"]

    def _load_resume(self, params, model_state, opt_states, driver_state):
        """Start-of-run resume from ``self._resume_from``; returns the
        restored trees and rewinds the dataset cursor so the replayed
        batch stream matches the original run bit-for-bit."""
        blob = load_pytree(self._resume_from)
        params = blob["params"]
        model_state = blob["model_state"]
        opt_states = blob["opt_states"]
        driver_state.update(
            {k: v.item() if hasattr(v, "item") else v
             for k, v in blob["driver_state"].items()}
        )
        # restore schedule bookkeeping so LR resumes at the right step
        # (reference: epoch/neval live in OptimMethod.state,
        # DistriOptimizer.scala:124-134)
        for m in self.optim_methods.values():
            m.state["neval"] = driver_state["neval"]
            m.state["epoch"] = driver_state["epoch"]
        self._restore_data_cursor(driver_state)
        logger.info("Resumed from %s at iteration %d",
                    self._resume_from, driver_state["neval"])
        return params, model_state, opt_states

    def _restore_data_cursor(self, driver_state):
        """Deterministic iterator replay: datasets exposing
        ``restore_cursor(epoch, batch_in_epoch)`` rewind their shuffle
        state so the next batches are exactly the ones the original run
        would have produced after the checkpointed iteration."""
        rc = getattr(self.dataset, "restore_cursor", None)
        if rc is None:
            return
        rc(driver_state.get("epoch", 0),
           driver_state.get("batch_in_epoch", 0))

    def _step_n_devices(self) -> int:
        """Devices the compiled step spans (MFU denominator); the
        sharded path overrides with its mesh size."""
        return 1

    def train_log_line(self) -> str:
        """One-line training status for a periodic logger cadence
        (serving's ``PeriodicMetricsLogger`` emit contract)."""
        m = getattr(self, "metrics", None)
        ds = getattr(self, "_driver_state", None)
        if m is None or ds is None:
            return "train: starting"
        return (f"train: iter={ds.get('neval', 0)} "
                f"epoch={ds.get('epoch', 0)} "
                f"loss={ds.get('loss', float('nan')):.4f} | "
                f"{m.summary()}")

    # -- hooks overridden by DistriOptimizer -----------------------------
    def _numerics_spec(self, model):
        """Resolve (and cache) whether the compiled step carries the
        numerics stats pytree: the fluent ``set_numerics`` request wins,
        else the ``BIGDL_TPU_NUMERICS`` env knob."""
        on = self._numerics_requested
        if on is None:
            on = numerics_mod.enabled()
        self._numerics = numerics_mod.spec_for(model) if on else None
        return self._numerics

    def _build_step_fn(self, model):
        return jax.jit(
            make_train_step(
                model, self.criterion, self.optim_methods,
                self.grad_clip_const, self.grad_clip_norm, self.compute_dtype,
                accum_steps=self.accum_steps,
                numerics=self._numerics_spec(model),
            ),
            donate_argnums=(0, 1, 2),
        )

    def _place(self, params, model_state, opt_states):
        """Device placement for the training trees (replicated/sharded)."""
        return params, model_state, opt_states

    def _place_batch(self, features, targets):
        # features/targets may be pytrees (e.g. detection (boxes, labels))
        as_dev = lambda t: jax.tree_util.tree_map(jnp.asarray, t)
        return as_dev(features), as_dev(targets)

    def _prefetch_place(self, batch):
        """Producer-thread finisher for the device prefetcher: host
        transforms + H2D placement with the step's input sharding."""
        features, targets = self._place_batch(
            batch.get_input(), batch.get_target()
        )
        return features, targets, batch.size

    # -- pieces ---------------------------------------------------------
    def _drain_losses(self, driver_state, metrics, keep: int = 0):
        """Sync pending device losses to host (oldest first) until at
        most ``keep`` remain.  This is the ONLY host<-device round-trip
        of the async loop; divergence surfaces here — up to one window
        late — and raises into the retry-from-checkpoint path."""
        while len(self._pending) > keep:
            it, dev_loss, _n, num_stats = self._pending.popleft()
            if num_stats is not None and self._numerics_monitor is not None:
                # numerics stats for iteration `it` are digested BEFORE
                # its loss is converted: a non-finite gradient count
                # raises the early-warning numerics_anomaly (Watchdog-
                # counted) ahead of the loss_divergence below
                with metrics.time("numerics"):
                    self._numerics_monitor.observe(
                        it, jax.device_get(num_stats))
            with metrics.time("sync"):
                loss = float(dev_loss)
            if math.isnan(loss) or math.isinf(loss):
                self._diverged_at = it
                self._pending.clear()
                # machine-readable divergence event: WHICH iteration
                # produced the NaN and how late the deferred drain saw
                # it (<= 1 sync window, docs/async_engine.md) — the
                # telemetry watchdog counts these as nan_windows
                get_tracer().instant(
                    "loss_divergence", CAT_TRAIN, corr=f"step:{it}",
                    args={"iteration": it,
                          "detected_at": driver_state["neval"],
                          "lag_steps": driver_state["neval"] - it,
                          "sync_window": self.sync_window,
                          "loss": str(loss)})
                raise FloatingPointError(
                    f"loss diverged: {loss} (iteration {it}, detected "
                    f"at iteration {driver_state['neval']})")
            driver_state["loss"] = loss
            if self.train_summary is not None:
                # loss lands against ITS iteration, not the drain point
                self.train_summary.add_scalar("Loss", loss, it)

    def _one_iteration(
        self, step_fn, params, model_state, opt_states, driver_state,
        data_iter, metrics, batches_per_epoch, wall_start,
    ):
        tracer = get_tracer()
        if tracer.poll():
            # ambient correlation: every phase span this thread records
            # during the iteration carries its step index
            set_correlation(f"step:{driver_state['neval'] + 1}")
        if self._async_engine:
            # the batch arrives already device-placed (producer thread
            # did the transform + transfer); this timer measures only
            # how long the loop BLOCKED on the prefetcher
            with metrics.time("data_stall"):
                features, targets, n_records = next(data_iter)
        else:
            with metrics.time("data"):
                batch = next(data_iter)
                features, targets = self._place_batch(
                    batch.get_input(), batch.get_target()
                )
                n_records = batch.size
        step_idx = jnp.asarray(driver_state["neval"] + 1, jnp.int32)
        lrs = [
            jnp.asarray(m.current_rate(), jnp.float32)
            for _, m in sorted(self.optim_methods.items())
        ]
        it_rng = jax.random.fold_in(jax.random.PRNGKey(7), driver_state["neval"])
        xray_sig = None
        if not self._step_cost_tried:
            # one extra trace (no backend compile) before the first
            # dispatch stamps the step's flops/bytes; lowering must
            # happen while the donated input buffers are still live
            self._step_cost_tried = True
            self._step_cost = costmodel.stamp_jitted(
                self._step_program, step_fn, params, model_state,
                opt_states, step_idx, it_rng, features, targets, lrs,
                n_devices=self._step_n_devices())
            # fingerprint before dispatch too (donation frees buffers)
            xray_sig = programs.signature_of(
                {"params": params, "model_state": model_state,
                 "opt_states": opt_states, "step": step_idx,
                 "rng": it_rng, "features": features,
                 "targets": targets, "lrs": lrs},
                donated=("params", "model_state", "opt_states"))
        # async: 'dispatch' is enqueue-only — the device runs behind;
        # sync: 'compute' blocks on the scalar loss fetch as before
        if self._recent_batches is not None:
            # retained for the one-shot NaN-provenance replay (batches
            # are not donated, so holding them costs no extra copies)
            self._recent_batches.append(
                (driver_state["neval"] + 1, features, targets))
        # which step paid for the XLA compile
        compiling = tracer.setup_span(
            "compile", {"program": self._step_program}
        ) if xray_sig is not None else contextlib.nullcontext()
        with jax.profiler.StepTraceAnnotation(
                "train_step", step_num=driver_state["neval"] + 1), \
                compiling, \
                metrics.time("dispatch" if self._async_engine
                             else "compute"):
            outs = step_fn(
                params, model_state, opt_states, step_idx, it_rng,
                features, targets, lrs,
            )
            if self._numerics is not None:
                params, model_state, opt_states, loss, num_stats = outs
            else:
                (params, model_state, opt_states, loss), num_stats = \
                    outs, None
            if not self._async_engine:
                loss = float(loss)  # sync point
        if xray_sig is not None:
            # the first dispatch just paid the XLA compile; its span's
            # duration is the program's compile_s stamp
            programs.get_program_registry().register_compile(
                self._step_program, xray_sig,
                compile_s=compiling.duration,
                cost=self._step_cost, expected=True)
        else:
            programs.get_program_registry().record_call(
                self._step_program)
        if self._async_engine:
            self._pending.append(
                (driver_state["neval"] + 1, loss, n_records, num_stats))
        else:
            if num_stats is not None and self._numerics_monitor is not None:
                self._numerics_monitor.observe(
                    driver_state["neval"] + 1, jax.device_get(num_stats))
            if math.isnan(loss) or math.isinf(loss):
                self._diverged_at = driver_state["neval"] + 1
                raise FloatingPointError(f"loss diverged: {loss}")
            driver_state["loss"] = loss
        self._last_trees = (params, model_state, opt_states)

        driver_state["neval"] += 1
        driver_state["records_processed"] += n_records
        driver_state["batch_in_epoch"] += 1
        self._log_records += n_records
        for m in self.optim_methods.values():
            m.state["neval"] = driver_state["neval"]
        if driver_state["batch_in_epoch"] >= batches_per_epoch:
            driver_state["epoch"] += 1
            driver_state["records_processed"] = 0
            driver_state["batch_in_epoch"] = 0
            driver_state["epoch_finished"] = True

        log_due = (driver_state["neval"] % 10 == 1
                   or driver_state["epoch_finished"])
        if self._async_engine:
            # bounded in-flight window; full drain at the log cadence
            self._drain_losses(driver_state, metrics,
                               keep=0 if log_due else self.sync_window)
        if log_due:
            if self._async_engine:
                # the compute timer only saw dispatch; throughput must
                # come from wall clock between log points
                now = time.perf_counter()
                throughput = self._log_records / max(now - self._log_t0,
                                                     1e-9)
                self._log_t0, self._log_records = now, 0
                self._last_throughput = throughput
            else:
                throughput = n_records / max(metrics.get("compute"), 1e-9)
            # cost-model scalars ride the metrics values so they land in
            # summary() (this log line), metrics_record() JSONL, and the
            # shipped cluster segments without new plumbing
            metrics.set_value("throughput", round(throughput, 1))
            mon = self._numerics_monitor
            if mon is not None and mon.last is not None:
                # numerics scalars ride the same metrics-values channel:
                # summary() log line, JSONL metrics_record, and the
                # shipped cluster segments (per-host grad-norm skew)
                metrics.set_value(
                    "grad_norm", round(mon.last["grad_norm"], 6))
                metrics.set_value(
                    "update_ratio", round(mon.last["update_ratio"], 8))
            # (a lowered-stage stamp carries no flops on some backends:
            # then there is no MFU to print, not an MFU of zero)
            if self._step_cost is not None and self._step_cost.flops \
                    and throughput > 0 and n_records:
                step_s = n_records / throughput
                mfu = self._step_cost.mfu(step_s)
                if mfu is not None:  # None on CPU: no device metric
                    metrics.set_value("mfu", round(mfu, 5))
                    programs.get_program_registry().record_mfu(
                        self._step_program, mfu)
                metrics.set_value("bytes_per_sec", round(
                    self._step_cost.bytes_per_s(step_s), 1))
            # HBM ledger rides the training log cadence (rate-limited
            # by its own knob; no-op device query + dict merge on CPU)
            programs.get_hbm_ledger().maybe_sample()
            wall = time.time() - wall_start
            epoch_records = batches_per_epoch * n_records
            # canonical log line shape (DistriOptimizer.scala:411-416)
            logger.info(
                "[Epoch %d %d/%d][Iteration %d][Wall Clock %.3fs] "
                "Throughput is %.1f records/second. Loss is %.4f. %s",
                driver_state["epoch"] + (0 if driver_state["epoch_finished"] else 1),
                driver_state["records_processed"], epoch_records,
                driver_state["neval"], wall, throughput,
                driver_state["loss"],
                metrics.summary(),
            )
        if self.train_summary is not None:
            with tracer.span("trigger", CAT_TRAIN):
                self._write_train_summary(driver_state, metrics, params,
                                          n_records)

    def _write_train_summary(self, driver_state, metrics, params,
                             n_records):
        if not self._async_engine:
            # async-mode Loss scalars are written at drain time
            self.train_summary.add_scalar(
                "Loss", driver_state["loss"], driver_state["neval"])
        throughput = (
            self._last_throughput if self._async_engine
            else n_records / max(metrics.get("compute"), 1e-9))
        self.train_summary.add_scalar(
            "Throughput", throughput, driver_state["neval"],
        )
        lr0 = sorted(self.optim_methods.items())[0][1].current_rate()
        self.train_summary.add_scalar(
            "LearningRate", lr0, driver_state["neval"]
        )
        mon = self._numerics_monitor
        if mon is not None and mon.last is not None:
            self.train_summary.add_scalar(
                "GradNorm", mon.last["grad_norm"],
                mon.last["iteration"])
            self.train_summary.add_scalar(
                "UpdateRatio", mon.last["update_ratio"],
                mon.last["iteration"])
        if hasattr(self.train_summary, "maybe_add_parameters"):
            self.train_summary.maybe_add_parameters(
                params, driver_state["neval"],
                stats=mon.last_stats if mon is not None else None,
            )

    def _eval_batches(self, model, params, model_state):
        """Validation forward pass; overridden by DistriOptimizer for the
        sharded path.  Returns [(method, folded result)]."""
        return evaluate(
            model, params, model_state, self.val_dataset, self.val_methods
        )

    def _maybe_validate(self, model, params, model_state, driver_state):
        if not (self.val_trigger and self.val_trigger(driver_state)
                and self.val_dataset and self.val_methods):
            return
        # validation is already a device sync point: settle the deferred
        # losses first so a diverged trajectory is never "validated"
        self._drain_losses(driver_state, self.metrics)
        results = self._eval_batches(model, params, model_state)
        if any(res is None for _, res in results):
            # validation set smaller than one (global) batch yields no
            # results — warn rather than kill training
            logger.warning("validation produced no batches "
                           "(val set < batch size); skipping")
            return
        for method, res in results:
            v, n = res.result()
            logger.info("%s is %s", method.name, res)
            if self.val_summary is not None:
                self.val_summary.add_scalar(method.name, v, driver_state["neval"])
        driver_state["score"] = results[0][1].result()[0]
        for m in self.optim_methods.values():
            sched = getattr(m, "schedule", None)
            if sched is not None and hasattr(sched, "record"):
                sched.record(driver_state["score"], m.learning_rate)

    def _prepare_ckpt_dir(self) -> Optional[str]:
        if not self.checkpoint_path:
            return None
        if self.overwrite_checkpoint:
            d = self.checkpoint_path
        else:
            # timestamped subdir per run (DistriOptimizer.scala:875-879)
            d = file_io.join(
                self.checkpoint_path, time.strftime("%Y%m%d_%H%M%S")
            )
        file_io.makedirs(d)
        return d

    def _ckpt_file(self, d: str, it: int) -> str:
        name = "model" if self.overwrite_checkpoint else f"model.{it}"
        return file_io.join(d, name)

    def _latest_ckpt(self, d: str) -> Optional[str]:
        # only well-formed names: "model.npz" or "model.<iter>.npz" —
        # a leftover atomic-write temp ("model.npz.tmp" after a kill
        # mid-checkpoint) must not break fault recovery
        import re

        cands = [f for f in file_io.listdir(d)
                 if re.fullmatch(r"model(\.\d+)?\.npz", f)]
        if not cands:
            return None
        latest = sorted(
            cands,
            key=lambda f: int(f.split(".")[-2]) if f.count(".") > 1 else 1 << 60,
        )[-1]
        return file_io.join(d, latest[:-4])

    def _maybe_checkpoint(self, ckpt_dir, params, model_state, opt_states,
                          driver_state, force: bool = False):
        if not ckpt_dir:
            return
        if not force and not (self.checkpoint_trigger
                              and self.checkpoint_trigger(driver_state)):
            return
        # a checkpoint the retry path may later restore must never
        # persist a diverged state: settle every deferred loss first
        # (raises into the retry handler on NaN/Inf)
        self._drain_losses(driver_state, self.metrics)
        path = self._ckpt_file(ckpt_dir, driver_state["neval"])
        blob = {
            "params": params,
            "model_state": model_state,
            "opt_states": opt_states,
            # bools (epoch_finished) deliberately excluded: persisting a
            # True would re-fire epoch triggers right after resume
            "driver_state": {k: v for k, v in driver_state.items()
                             if isinstance(v, (int, float))
                             and not isinstance(v, bool)},
        }
        if self._sync_loop:
            save_pytree(path, blob)
            logger.info("Checkpoint saved to %s (iteration %d)",
                        path, driver_state["neval"])
            return
        # async: snapshot to host on the loop thread (the arrays' step
        # is already settled by the drain above), then serialize + write
        # on the background writer so file IO never stalls the device
        with get_tracer().span("checkpoint_snapshot", CAT_TRAIN):
            host_blob = jax.device_get(blob)
        self._submit_checkpoint(path, host_blob, driver_state["neval"])

    def _submit_checkpoint(self, path, host_blob, iteration):
        from concurrent.futures import ThreadPoolExecutor

        if self._ckpt_pool is None:
            self._ckpt_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="bigdl-ckpt")
        if self._ckpt_future is not None:
            # backpressure + error propagation: a failed write must not
            # pass silently (the retry path depends on these files), and
            # writes slower than the trigger cadence must not pile up
            self._ckpt_future.result()

        def write():
            # span on the WRITER thread: checkpoint IO shows up as its
            # own labeled track, correlated to the step it persisted
            with get_tracer().span("checkpoint_write", CAT_TRAIN,
                                   corr=f"step:{iteration}",
                                   args={"path": path}):
                save_pytree(path, host_blob)  # atomic (tmp + rename)
            logger.info("Checkpoint saved to %s (iteration %d)",
                        path, iteration)

        self._ckpt_future = self._ckpt_pool.submit(write)

    def _finish_checkpoints(self, raise_errors: bool = True):
        """Wait for the in-flight checkpoint write (if any) and tear the
        writer down.  Called on every optimize() exit path."""
        pool, fut = self._ckpt_pool, self._ckpt_future
        self._ckpt_pool = None
        self._ckpt_future = None
        if pool is None:
            return
        pool.shutdown(wait=True)
        if fut is not None:
            try:
                fut.result()
            except Exception:
                if raise_errors:
                    raise
                logger.warning("background checkpoint write failed",
                               exc_info=True)


def _jit_forward(model: Module):
    """Per-model cached jitted inference forward (recompiling a fresh
    lambda every evaluate() call would pay full XLA compilation per
    validation pass)."""
    fwd = getattr(model, "_cached_jit_fwd", None)
    if fwd is None:
        fwd = jax.jit(lambda p, s, x: model.apply(p, s, x, training=False)[0])
        model._cached_jit_fwd = fwd
    return fwd


def evaluate(
    model: Module,
    params,
    model_state,
    dataset: AbstractDataSet,
    methods: List[ValidationMethod],
    batch_to_device: bool = True,
):
    """Run validation methods over one pass of ``dataset`` (reference
    Evaluator.scala:40-100 / model.evaluate AbstractModule.scala:856).
    Returns [(method, folded ValidationResult)].

    ``batch_to_device=False`` skips the explicit host->device transfer —
    for callers whose dataset already yields device-resident (or
    prefetcher-placed) arrays, where a re-``asarray`` would be a wasted
    copy (or break a committed multi-device sharding)."""
    fwd = _jit_forward(model)
    totals = [None] * len(methods)
    for batch in dataset.data(train=False):
        x = batch.get_input()
        if batch_to_device:
            x = jnp.asarray(x)
        t = batch.get_target()
        out = fwd(params, model_state, x)
        for i, m in enumerate(methods):
            r = m(out, t)
            totals[i] = r if totals[i] is None else totals[i] + r
    return list(zip(methods, totals))


def predict(model: Module, params, model_state, dataset: AbstractDataSet):
    """Yield model outputs batch by batch (reference Predictor.scala:152)."""
    fwd = _jit_forward(model)
    for batch in dataset.data(train=False):
        yield np.asarray(fwd(params, model_state, jnp.asarray(batch.get_input())))
