"""Pipeline parallelism — GPipe-style microbatched stages over a mesh
axis (beyond-reference: SURVEY.md §2.4 notes the reference has data
parallelism only; pp is the idiomatic TPU scaling of deep stacks).

Design (the scaling-book shard_map recipe):
* ``num_stages`` identical trunk stage modules with params STACKED along
  a leading axis, sharded over the ``pipe`` mesh axis — each device
  holds its stage's weights only;
* inside ``shard_map`` the schedule runs ``M + S - 1`` ticks; stage 0
  feeds a fresh microbatch each tick, activations hop to the next stage
  through ``lax.ppermute``, the last stage collects outputs;
* the whole schedule is differentiable (ppermute's transpose is the
  reverse ppermute), so ``jax.grad`` through :func:`pipeline_apply`
  yields pipeline-parallel backward for free;
* ``remat=True`` wraps each stage tick in ``jax.checkpoint`` so only
  microbatch boundaries are saved — the activation-memory profile 1F1B
  exists to fix, obtained here by rematerialisation instead of a
  hand-scheduled backward (XLA overlaps the recompute with the
  ppermute hops).  See PERF.md "Pipeline schedule" for the measured
  rationale.

Heterogeneous models use :class:`PipelinedLM`: unsharded ``head``
(embedding) and ``tail`` (unembedding/decoder) modules run replicated
around the pipelined homogeneous trunk — the embed/trunk/unembed split
of every transformer LM.  The module composes with the regular engine
(``make_train_step`` / ``Optimizer`` / ``DistriOptimizer``): its params
pytree is ``{"head", "trunk", "tail"}`` and :meth:`param_shardings`
places the trunk on the pipe axis.

Composition with data parallelism: pass ``data_axis`` — the microbatch
rows stay sharded over ``data`` while the schedule runs over ``pipe``
(each data-parallel group pipelines its own shard; shard_map's
transpose inserts the gradient psum over ``data`` automatically).

Composition with tensor/expert parallelism: the shard_map is manual
over ``pipe`` (+ ``data``) ONLY — every other mesh axis is an *auto*
axis (``shard_map(..., axis_names=...)``), so the stage body stays
plain jnp and GSPMD partitions it over ``model``/``expert`` exactly as
it would outside the pipeline.  Shard the stacked trunk params
``P("pipe", <tp dims>)`` (see :meth:`PipelinedLM.param_shardings`'s
``tp_rules``) and the per-layer tp collectives ride ICI inside each
pipeline tick.
"""
from __future__ import annotations

import functools
import logging
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.nn.module import Module

logger = logging.getLogger("bigdl_tpu.parallel")

PIPE_AXIS = "pipe"


def init_stacked_params(stage: Module, num_stages: int, rng,
                        dtype=jnp.float32):
    """Init ``num_stages`` independent stage params stacked on axis 0."""
    keys = jax.random.split(rng, num_stages)
    per_stage = [stage.init_params(k, dtype) for k in keys]
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *per_stage)


def stacked_param_sharding(mesh: Mesh, stacked_params,
                           axis: str = PIPE_AXIS):
    """NamedShardings placing stage i's slice on pipe device i."""
    spec = P(axis)
    return jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, spec), stacked_params)


def _collect_aux(state) -> jnp.ndarray:
    """Sum of every ``aux_loss`` leaf in a module state tree (the MoE
    load-balance signal)."""
    from bigdl_tpu.optim.optimizer import _aux_losses  # deferred: cycle

    total = jnp.zeros((), jnp.float32)
    for aux in _aux_losses(state):
        total = total + jnp.asarray(aux, jnp.float32)
    return total


def pipeline_apply(stage: Module, mesh: Mesh, num_microbatches: int,
                   axis: str = PIPE_AXIS,
                   data_axis: Optional[str] = None,
                   training: bool = False,
                   remat: bool = True,
                   collect_aux: bool = False) -> Callable:
    """Returns ``f(stacked_params, x) -> y`` running the pipeline.

    ``x``: (B, ...); microbatches are strided row groups (row j belongs
    to microbatch ``j % M``) so a batch dim sharded over ``data_axis``
    keeps its layout — no cross-device resharding at the split.  When B
    cannot carry ``num_microbatches`` over the data shards (e.g. a
    short final validation batch) the count is clamped to the largest
    feasible value for that call, with a warning — fewer microbatches
    means a bigger pipeline bubble, so size training batches to fit.
    Output matches x's leading layout.  Activation shapes must be
    identical across stages (homogeneous trunk; put embed/unembed in
    PipelinedLM's head/tail).

    ``collect_aux``: return ``(y, aux)`` where aux is the microbatch-
    averaged sum of the stages' ``aux_loss`` state leaves (MoE load
    balance), masked to real ticks (pipeline bubbles excluded) and
    reduced over pipe (+ averaged over data).
    """
    num_stages = mesh.shape[axis]
    m = num_microbatches
    # CPU-only workaround: a bf16 all-reduce at a partially-manual
    # shard_map boundary crashes XLA:CPU's AllReducePromotion pass
    # (combiner region root becomes a sharding custom-call -> copy), so
    # params/activations cross the boundary in f32 there.  TPU handles
    # bf16 collectives natively — no upcast, no extra HBM traffic.
    # Keyed on the MESH's platform, not the process backend: a
    # deviceless AOT compile (tools/tpu_aot_check.py --multichip) runs
    # in a CPU-backend process but targets TPU, and must see the real
    # bf16 boundary (HBM accounting + lowering evidence).
    try:
        platform = mesh.devices.flat[0].platform
    except Exception:  # AbstractMesh or exotic mesh: fall back
        platform = jax.default_backend()
    f32_boundary = platform == "cpu"

    def make_tick(use_rng: bool):
        def stage_tick(params, inp, key):
            out, new_state = stage.apply(params, stage.init_state(), inp,
                                         training=training,
                                         rng=key if use_rng else None)
            return out, _collect_aux(new_state)

        return jax.checkpoint(stage_tick) if remat else stage_tick

    def run(params_block, xm, key, *, use_rng: bool, param_dtypes,
            act_dtype):
        # params_block: stage subtree with leading axis 1 (this device's
        # stage); xm: (jb, M, ...) — this data-shard's microbatch rows
        params = jax.tree_util.tree_map(
            lambda a, d: a[0].astype(d), params_block, param_dtypes)
        xm = xm.astype(act_dtype)
        m = xm.shape[1]  # microbatches actually present in this call
        stage_id = jax.lax.axis_index(axis)
        stage_tick = make_tick(use_rng)
        mb_shape = (xm.shape[0],) + xm.shape[2:]
        carry = jnp.zeros(mb_shape, xm.dtype)
        out_buf = jnp.zeros_like(xm)
        aux_sum = jnp.zeros((), jnp.float32)

        perm_fwd = [(i, i + 1) for i in range(num_stages - 1)]

        for t in range(m + num_stages - 1):
            # stage 0 ingests microbatch t (while t < m)
            feed = xm[:, min(t, m - 1)]
            inp = jnp.where(stage_id == 0,
                            feed if t < m else jnp.zeros_like(feed),
                            carry)
            tick_key = jax.random.fold_in(
                jax.random.fold_in(key, t), stage_id)
            out, aux = stage_tick(params, inp, tick_key)
            # stage s holds microbatch t-s at tick t; ticks outside
            # [s, s+m) are bubbles running on zeros — mask their aux
            active = (stage_id <= t) & (t < stage_id + m)
            aux_sum = aux_sum + jnp.where(active, aux, 0.0)
            # last stage stores tick t - (S-1) = microbatch index
            mb_idx = t - (num_stages - 1)
            if mb_idx >= 0:
                out_buf = jnp.where(
                    (stage_id == num_stages - 1),
                    jax.lax.dynamic_update_slice(
                        out_buf, out[:, None],
                        (0, mb_idx) + (0,) * (out.ndim - 1)),
                    out_buf)
            # forward hop
            carry = jax.lax.ppermute(out, axis, perm_fwd)
        # broadcast the last stage's buffer to every pipe device so the
        # result is replicated (sum works: other stages contribute 0)
        out_buf = jnp.where(stage_id == num_stages - 1, out_buf, 0.0)
        if f32_boundary:
            out_buf = out_buf.astype(jnp.float32)
        y = jax.lax.psum(out_buf, axis)
        # sum over stages = sum over the model's layers; average over
        # microbatches (aux is scale-free in batch); average over data
        # shards to match the unpipelined dp semantics
        aux = jax.lax.psum(aux_sum, axis) / m
        if data_axis:
            aux = jax.lax.pmean(aux, data_axis)
        return y, aux

    xspec = P(data_axis) if data_axis else P()
    # manual over pipe (+data) only; model/seq/expert stay auto axes so
    # GSPMD partitions the stage body (tp/ep compose inside the pipe)
    manual = frozenset({axis} | ({data_axis} if data_axis else set()))
    # cache jitted shard_maps so repeated eager calls (eval loops) hit
    # the compile cache instead of rebuilding jit objects per call
    jitted: dict = {}

    def get_jitted(use_rng, act_dtype, param_dtypes, dtypes_key):
        key = (use_rng, jnp.dtype(act_dtype).name, dtypes_key)
        if key not in jitted:
            smapped = shard_map(
                functools.partial(run, use_rng=use_rng,
                                  param_dtypes=param_dtypes,
                                  act_dtype=act_dtype),
                mesh=mesh, in_specs=(P(axis), xspec, P()),
                out_specs=(xspec, P()), axis_names=manual,
                check_vma=False)
            # partially-manual shard_map (axis_names ⊊ mesh axes) only
            # lowers under jit — the eager impl path re-enters shard_map
            # with full-mesh specs and rejects them; jit inlines when
            # already inside an outer trace
            jitted[key] = jax.jit(smapped)
        return jitted[key]

    def f(stacked_params, x, rng=None):
        param_dtypes = jax.tree_util.tree_map(
            lambda a: a.dtype, stacked_params)
        flat, treedef = jax.tree_util.tree_flatten(param_dtypes)
        dtypes_key = (treedef, tuple(jnp.dtype(d).name for d in flat))
        if f32_boundary:
            stacked_params = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), stacked_params)
        b = x.shape[0]
        # a short batch (e.g. the last validation batch) may not carry
        # m microbatches over the data shards; clamp to the largest
        # feasible count for this call (retraces per batch shape only)
        dd = mesh.shape[data_axis] if data_axis else 1
        m_eff = next((d for d in range(min(m, b), 0, -1)
                      if b % d == 0 and (b // d) % dd == 0), None)
        if m_eff is None:
            raise ValueError(
                f"pipeline batch {b} does not divide over the "
                f"data-parallel degree {dd}; drop or pad ragged batches")
        if m_eff != m:
            logger.warning(
                "pipeline: clamping microbatches %d -> %d for batch %d "
                "over %d data shards (bigger bubble this call)",
                m, m_eff, b, dd)
        xm = x.reshape(b // m_eff, m_eff, *x.shape[1:])
        if f32_boundary:
            xm = xm.astype(jnp.float32)
        key = rng if rng is not None else jax.random.PRNGKey(0)
        smapped = get_jitted(rng is not None, x.dtype, param_dtypes,
                             dtypes_key)
        y, aux = smapped(stacked_params, xm, key)
        y = y.reshape(b, *x.shape[1:]).astype(x.dtype)
        return (y, aux) if collect_aux else y

    return f


class PipelinedLM(Module):
    """Heterogeneous pipeline model: head -> pipelined trunk -> tail.

    ``head`` / ``tail`` run replicated (embedding and unembedding — the
    stages the reference-style homogeneous trunk can't absorb); the
    ``stage`` module is instantiated ``num_stages`` times with stacked
    params over the pipe axis.  The tail may be ``None``; pass
    ``tied_embed_path=("embed", "weight")`` for a weight-tied LM head
    (``logits = h @ embed.weight.T``, matching nn.Transformer).

    Engine integration: a regular Module — ``make_train_step``,
    ``Optimizer.set_optim_method``, checkpointing, and validation all
    see ``{"head", "trunk", "tail"}`` params.  Use
    :meth:`param_shardings` for the DistriOptimizer ``param_shardings``
    argument.
    """

    def __init__(self, head: Module, stage: Module, tail: Optional[Module],
                 mesh: Mesh, num_microbatches: int,
                 axis: str = PIPE_AXIS,
                 data_axis: Optional[str] = None,
                 tied_embed_path: Optional[tuple] = None,
                 embed_scale: Optional[float] = None,
                 remat: bool = True,
                 collect_aux: bool = False,
                 name: Optional[str] = None):
        super().__init__(name)
        self.head = head
        self.stage = stage
        self.tail = tail
        self.mesh = mesh
        self.num_stages = mesh.shape[axis]
        self.num_microbatches = num_microbatches
        self.axis = axis
        self.data_axis = data_axis
        # e.g. ("embed", "weight"): path into params["head"] of the
        # embedding matrix for a weight-tied LM head
        self.tied_embed_path = tied_embed_path
        self.embed_scale = embed_scale
        self.remat = remat
        # surface the stages' MoE aux_loss through this module's state
        # (make_train_step folds state aux_losses into the loss)
        self.collect_aux = collect_aux
        # one pipeline_apply per training mode, so its jitted shard_map
        # cache survives across apply calls (eager eval loops)
        self._fwd_cache: dict = {}

    def _fwd(self, training: bool):
        if training not in self._fwd_cache:
            self._fwd_cache[training] = pipeline_apply(
                self.stage, self.mesh, self.num_microbatches,
                self.axis, self.data_axis, training=training,
                remat=self.remat, collect_aux=True)
        return self._fwd_cache[training]

    def init_params(self, rng, dtype=jnp.float32):
        k1, k2, k3 = jax.random.split(rng, 3)
        p = {
            "head": self.head.init_params(k1, dtype),
            "trunk": init_stacked_params(self.stage, self.num_stages, k2,
                                         dtype),
        }
        if self.tail is not None:
            p["tail"] = self.tail.init_params(k3, dtype)
        return p

    def init_state(self, dtype=jnp.float32):
        s = {"head": self.head.init_state(dtype)}
        if self.collect_aux:
            s["trunk"] = {"aux_loss": jnp.zeros((), jnp.float32)}
        if self.tail is not None:
            s["tail"] = self.tail.init_state(dtype)
        return s

    def param_shardings(self, mesh: Optional[Mesh] = None,
                        tp_rules=None, expert_axis: Optional[str] = None):
        """{"head": replicated, "trunk": P(pipe), "tail": replicated}.

        ``tp_rules`` (tensor_parallel.Rules): tensor-parallel specs for
        the stage params, shifted one dim right under the stacked pipe
        dim — e.g. a ``wq -> P(None, "model")`` rule places the trunk
        leaf at ``P("pipe", None, "model")``; head/tail get the rules
        unshifted.  ``expert_axis``: shard stacked MoE expert banks
        (leaves named w_in/w_out with a leading (S, E, ...) shape) as
        ``P("pipe", expert_axis)`` — the pp x ep composition.
        """
        import re

        from bigdl_tpu.parallel.tensor_parallel import (map_with_paths,
                                                        match_rule_spec)

        mesh = mesh or self.mesh
        tpl = jax.eval_shape(
            lambda: self.init_params(jax.random.PRNGKey(0)))
        rep = NamedSharding(mesh, P())
        out = {k: jax.tree_util.tree_map(lambda _: rep, v)
               for k, v in tpl.items()}
        compiled = [(re.compile(pat), spec) for pat, spec in
                    (tp_rules or ())]

        def trunk_spec(path: str, leaf) -> NamedSharding:
            name = path.rsplit("/", 1)[-1]
            if expert_axis and name in ("w_in", "w_out") \
                    and getattr(leaf, "ndim", 0) == 4:
                if leaf.shape[1] % mesh.shape[expert_axis]:
                    # silent replication would still spend mesh devices
                    # on the expert axis — refuse instead
                    raise ValueError(
                        f"{path}: {leaf.shape[1]} experts do not divide "
                        f"over the {mesh.shape[expert_axis]}-way "
                        f"'{expert_axis}' mesh axis")
                return NamedSharding(mesh, P(self.axis, expert_axis))
            spec = match_rule_spec(mesh, path, leaf, compiled, shift=1)
            if spec is not None:
                return NamedSharding(mesh, P(self.axis, *spec))
            return NamedSharding(mesh, P(self.axis))

        out["trunk"] = map_with_paths(tpl["trunk"], trunk_spec)
        if tp_rules:
            def edge_spec(path, leaf):
                spec = match_rule_spec(mesh, path, leaf, compiled)
                return NamedSharding(mesh, spec) if spec is not None \
                    else rep

            out["head"] = map_with_paths(tpl["head"], edge_spec)
            if "tail" in out:
                out["tail"] = map_with_paths(tpl["tail"], edge_spec)
        return out

    def apply(self, params, state, x, training=False, rng=None):
        h, head_state = self.head.apply(
            params["head"], state["head"], x, training=training, rng=rng)
        if self.embed_scale is not None:
            h = h * self.embed_scale
        fwd = self._fwd(training)
        h, aux = fwd(params["trunk"], h,
                     jax.random.fold_in(rng, 1) if rng is not None else None)
        new_state = dict(state)
        new_state["head"] = head_state
        if self.collect_aux:
            new_state["trunk"] = {"aux_loss": aux}
        if self.tail is not None:
            h, tail_state = self.tail.apply(
                params["tail"], state["tail"], h, training=training,
                rng=jax.random.fold_in(rng, 2) if rng is not None else None)
            new_state["tail"] = tail_state
        if self.tied_embed_path is not None:
            w = params["head"]
            for k in self.tied_embed_path:
                w = w[k]
            h = h @ w.astype(h.dtype).T
        return h, new_state


def pipelined_transformer_lm(
    vocab_size: int, hidden_size: int, num_heads: int, filter_size: int,
    num_layers: int, mesh: Mesh, num_microbatches: int,
    dropout: float = 0.0, causal: bool = True,
    use_flash: Optional[bool] = None,
    axis: str = PIPE_AXIS, data_axis: Optional[str] = None,
    moe_experts: int = 0,
) -> PipelinedLM:
    """The pipelined equivalent of ``nn.Transformer`` (same math when
    layer params match): embed+pos+dropout head, ``num_layers/S``
    transformer blocks per pipe stage, final-LN tail, weight-tied
    logits.  This is what ``transformer_train --pp N`` builds.

    ``moe_experts``: swap each block's dense FFN for a Switch-MoE bank
    (nn.attention.TransformerLayer moe path) — pp x ep composition; the
    expert all-to-alls stay on the auto ``expert`` axis inside each
    pipeline tick (no moe_mesh constraint needed: the expert banks'
    ``P("pipe", "expert")`` sharding propagates through GSPMD)."""
    import math

    from bigdl_tpu.nn.attention import PositionEncode, TransformerLayer
    from bigdl_tpu.nn.dropout import Dropout
    from bigdl_tpu.nn.embedding import LookupTable
    from bigdl_tpu.nn.init import RandomNormal
    from bigdl_tpu.nn.module import Sequential
    from bigdl_tpu.nn.norm import LayerNormalization
    from bigdl_tpu.nn.reshape import MulConstant

    num_stages = mesh.shape[axis]
    assert num_layers % num_stages == 0, (
        f"num_layers={num_layers} must divide over {num_stages} pipe "
        "stages")
    per_stage = num_layers // num_stages
    head = Sequential(
        LookupTable(vocab_size, hidden_size,
                    weight_init=RandomNormal(0.0, hidden_size ** -0.5)
                    ).set_name("embed"),
        MulConstant(math.sqrt(hidden_size)).set_name("scale"),
        PositionEncode().set_name("pos"),
        Dropout(dropout).set_name("drop"),
    )
    stage = Sequential(*[
        TransformerLayer(hidden_size, num_heads, filter_size,
                         attn_dropout=dropout, ffn_dropout=dropout,
                         causal=causal, use_flash=use_flash,
                         moe_experts=moe_experts,
                         ).set_name(f"block{i}")
        for i in range(per_stage)
    ])
    tail = LayerNormalization(hidden_size).set_name("ln_f")
    return PipelinedLM(head, stage, tail, mesh, num_microbatches,
                       axis=axis, data_axis=data_axis,
                       tied_embed_path=("embed", "weight"),
                       collect_aux=moe_experts > 0)


def build_pipeline_train_step(stage: Module, mesh: Mesh,
                              num_microbatches: int,
                              loss_fn: Callable,
                              axis: str = PIPE_AXIS,
                              optim_method=None,
                              lr: float = 1e-2):
    """Homogeneous-trunk pp train step with a pluggable OptimMethod.

    ``loss_fn(y, targets) -> scalar``.  ``optim_method``: any
    bigdl_tpu.optim.OptimMethod (default SGD(lr)); its state is built on
    the stacked params so it shards with them.  Returns
    ``step(stacked_params, opt_state, x, targets, step_idx=0, lr=None)
    -> (params, opt_state, loss)`` plus ``init(params)``.  ``step_idx``
    and ``lr`` are traced arguments (like the engine's train step,
    optim/optimizer.py) so Adam-style bias correction advances and LR
    schedules are not baked in at trace time; ``lr=None`` falls back to
    the method's base rate as a trace-time constant.
    """
    from bigdl_tpu.optim.optim_method import SGD

    method = optim_method if optim_method is not None else SGD(lr)
    fwd = pipeline_apply(stage, mesh, num_microbatches, axis,
                         training=True)

    def init(params):
        return method.init_state(params)

    def step(params, opt_state, x, targets, step_idx=1, lr=None):
        # step_idx is 1-based like the engine's neval+1 (t=0 would zero
        # Adam's bias-correction denominators)
        def objective(p):
            y = fwd(p, x)
            return loss_fn(y, targets)

        loss, grads = jax.value_and_grad(objective)(params)
        lr_now = (jnp.asarray(method.current_rate(), jnp.float32)
                  if lr is None else lr)
        new_params, new_opt = method.update(
            grads, opt_state, params, lr_now,
            jnp.asarray(step_idx, jnp.int32))
        return new_params, new_opt, loss

    return step, init
