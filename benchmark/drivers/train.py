"""Driver ``train``: the program's own trainer, built by
``models/<x>_train.build(argv)`` and run by ``Optimizer.optimize()``.

One ``optimize()`` call holds set-up and window: the end trigger (the
program's own hook, ``optim.Trigger``) is called before every iteration
on the loop thread, so it reads the state after steps 1 and 3 for the
comparison, opens the window after the warm-up steps, starts and stops
the profiler, and ends the run when the clock says so.  The data and the
weights are the benchmark's, from ``--seed``.
"""
from __future__ import annotations

import importlib
import time

import jax
import jax.numpy as jnp

from benchmark.device import device_only, memory_peak_bytes


class LossTrace:
    """A train summary that keeps ``Loss`` by iteration."""

    def __init__(self):
        self.losses = {}

    def add_scalar(self, tag, value, step):
        if tag == "Loss":
            self.losses[int(step)] = float(value)


def _norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


_leaf_norms = jax.jit(_norms)
_change_norms = jax.jit(lambda a, b: _norms(
    jax.tree_util.tree_map(lambda x, y: x.astype(jnp.float32)
                           - y.astype(jnp.float32), a, b)))


def first_gradient_norms(opt_states, optimizer: dict):
    """Norm of every leaf of the first gradient as the optimizer got it,
    worked out from its state after one step."""
    state = opt_states["__all__"]
    if optimizer["kind"] == "adam":  # m1 = (1 - beta1) g
        scale = 1.0 / (1.0 - optimizer["beta1"])
        return jax.tree_util.tree_map(lambda n: n * scale,
                                      _leaf_norms(state["m"]))
    if optimizer["kind"] == "lars":  # v1 = lr * ratio * (g + wd p)
        return _leaf_norms(state["velocity"])
    raise ValueError(f"unknown optimizer kind {optimizer['kind']!r}")


class Watch:
    """The end trigger: phases of one ``optimize()`` call."""

    def __init__(self, opt, p0, optimizer, seconds, warm_steps, trace_dir,
                 trace_seconds, compiles, t_start):
        self.opt, self.p0, self.optimizer = opt, p0, optimizer
        self.seconds, self.warm_steps = seconds, warm_steps
        self.trace_dir, self.trace_seconds = trace_dir, trace_seconds
        self.compiles, self.t_start = compiles, t_start
        self.grad1 = self.change = self.update1 = None
        self.t0 = self.t1 = None
        self.tracing = False
        self.trace_span = None

    def _settle(self):
        jax.block_until_ready(self.opt._last_trees[0])

    def _stall(self):
        m = self.opt.metrics
        return m.get("data_stall") * m.count("data_stall")

    def __call__(self, state) -> bool:
        n = state["neval"]
        if n == 1 and self.grad1 is None:
            print(f"[train] {time.perf_counter() - self.t_start:6.1f} s: "
                  f"first step dispatched", flush=True)
            state = self.opt._last_trees[2]
            self.grad1 = jax.device_get(first_gradient_norms(
                state, self.optimizer))
            if self.optimizer["kind"] == "lars":
                # LARS scales every leaf's update to lr * |w|, so its norm
                # is blind to the gradient: keep the update itself
                self.update1 = jax.device_get(state["__all__"]["velocity"])
        if n == 3 and self.change is None:
            self.change = jax.device_get(_change_norms(
                self.opt._last_trees[0], self.p0))
            self.p0 = None
        if self.t0 is None:
            if n < self.warm_steps:
                return False
            self._settle()
            self.n0, self.stall0 = n, self._stall()
            self.compiles0 = self.compiles.n
            self.setup_s = time.perf_counter() - self.t_start
            if self.trace_dir:
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=device_only())
                self.tracing = True
            self.t0 = time.perf_counter()
            return False
        now = time.perf_counter()
        if self.tracing and now - self.t0 >= self.trace_seconds:
            self._settle()
            span = time.perf_counter() - self.t0
            jax.profiler.stop_trace()
            self.tracing = False
            self.trace_span = {"seconds": span, "iterations": n - self.n0,
                               "data_stall_s": self._stall() - self.stall0}
        if now - self.t0 >= self.seconds:
            self._settle()
            self.t1 = time.perf_counter()
            self.n1, self.stall1 = n, self._stall()
            self.compiles1 = self.compiles.n
            return True
        return False


def run(cell, device, seed, seconds, trace, t_start, compiles) -> dict:
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset import DataSet

    from benchmark import check, trace_reduce, traffic as gen, weights

    config, mix = cell["config"], cell["traffic"]
    spec = config["train"]
    entry = importlib.import_module(spec["entry"])
    stamp = lambda what: print(
        f"[train] {time.perf_counter() - t_start:6.1f} s: {what}", flush=True)
    stamp("imports done")
    opt, _ = entry.build(list(spec["argv"]) + list(mix["argv"]))
    stamp("program built")
    if device["count"] > 1 and not isinstance(opt, optim.DistriOptimizer):
        raise SystemExit(f"train: {device['count']} chips but the program "
                         f"built {type(opt).__name__}")

    x, y = gen.training_data(spec["data"], mix, seed)
    first = [(x[i * mix["batch"]:(i + 1) * mix["batch"]],
              y[i * mix["batch"]:(i + 1) * mix["batch"]]) for i in range(3)]
    stamp("data made")
    opt.dataset = DataSet.from_arrays(x, y, batch_size=mix["batch"])
    opt.set_validation(optim.Trigger(lambda s: False, "never"),
                       opt.val_dataset, opt.val_methods)
    variables = weights.make_variables(opt.model, config["init"], seed)
    p0 = jax.tree_util.tree_map(jnp.copy, variables["params"])
    jax.block_until_ready(p0)
    stamp("weights made")
    opt.set_initial_variables(variables)
    losses = LossTrace()
    opt.set_train_summary(losses)

    trace_dir = trace_reduce.fresh_trace_dir() if trace else None
    watch = Watch(opt, p0, spec["optimizer"], seconds, mix["warm_steps"],
                  trace_dir, mix.get("trace_seconds", 3), compiles, t_start)
    del variables, p0
    opt.set_end_when(optim.Trigger(watch, "benchmark window"))
    opt.optimize()
    if watch.tracing:  # a window shorter than the trace span
        jax.profiler.stop_trace()
        watch.trace_span = {"seconds": watch.t1 - watch.t0,
                            "iterations": watch.n1 - watch.n0,
                            "data_stall_s": watch.stall1 - watch.stall0}

    window_s = watch.t1 - watch.t0
    iterations = watch.n1 - watch.n0
    records = iterations * mix["batch"]
    peak = memory_peak_bytes()
    print(f"[train] {type(opt).__name__} iterations {iterations} in "
          f"{window_s:.3f} s = {records / window_s:.2f} records/s; set-up "
          f"{watch.setup_s:.1f} s; data_stall {watch.stall1 - watch.stall0:.3f}"
          f" s; compile requests in the window "
          f"{watch.compiles1 - watch.compiles0}; memory_stats peak "
          f"{peak / 2**30:.2f} GiB", flush=True)
    program = {"losses": [losses.losses.get(i, float("nan"))
                          for i in (1, 2, 3)],
               "grad1_norms": watch.grad1, "change_norms": watch.change,
               "update1": watch.update1}

    # the program's state is freed before the reference runs
    model = opt.model
    opt._last_trees = None
    opt.final_params = opt.final_state = model._variables = None
    del opt

    reduced = trace_reduce.reduce_and_remove(
        trace_dir, span_s=watch.trace_span["seconds"]) if trace else None

    t_ref = time.perf_counter()
    reference = importlib.import_module(
        "benchmark.references." + config["reference"])
    params = weights.make_variables(model, config["init"], seed)["params"]
    ref = reference.train_steps(params, first, config["model"],
                                spec["optimizer"])
    numbers = check.training_numbers(program, ref)
    print(f"[train] reference followed 3 steps in "
          f"{time.perf_counter() - t_ref:.1f} s: program losses "
          f"{program['losses']} reference {ref['losses']} (loss_gap "
          f"{numbers.pop('loss_gap'):.5f}, not compared)", flush=True)

    return {
        "kind": "train", "config": config, "traffic": mix,
        "peaks": device["peaks"], "chips": device["count"],
        "window_s": window_s, "iterations": iterations, "records": records,
        "data_stall_s": watch.stall1 - watch.stall0,
        "trace": reduced, "trace_span": watch.trace_span,
        "end_to_end": {"train_records_per_s": records / window_s,
                       "setup_s": watch.setup_s},
        "memory_peak_bytes": peak,
        "attempted": iterations, "failed": 0,
        "numbers": numbers,
        "flags": {"compiles_in_window":
                  watch.compiles1 == watch.compiles0},
    }
