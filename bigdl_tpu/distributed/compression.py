"""Compressed gradient exchange: reduced-precision allreduce with fp32
master accumulation.

The reference moved every gradient through the BlockManager as an
``FP16CompressedTensor`` — fp32 values truncated to their upper 16 bits
on the wire, decompressed and accumulated in fp32 on the parameter
partitions (AllReduceParameter.scala:155-328).  "RPC Considered
Harmful" (PAPERS.md) is the scaling argument: past a few hosts the
gradient exchange dominates the step, so recovery and steady state
alike must not serialize full-precision state.

Here the same schedule is explicit in the step: a fully-manual
``shard_map`` over the mesh computes local grads, casts them to the
*wire dtype* (bf16 by default — same 8-bit exponent + 7-bit mantissa
payload the reference's truncation kept, but round-to-nearest; fp8
optional), runs ``lax.psum`` at that width, then upcasts to fp32 for
the mean + clip + optimizer update (master accumulation).  Only the
collective runs narrow; params and optimizer state stay fp32.

graft-lint audits the jaxpr (target ``compressed_allreduce_step``): any
array-valued reduction over the mesh wider than the declared wire dtype
is flagged by the dtype-hygiene rule's wire check — the seeded fixture
``compressed_fp32_allreduce`` is the defect it must catch.

Trade against the GSPMD dp path (parallel/data_parallel.py): the manual
step keeps optimizer state replicated (no ZeRO-1 leading-dim shard) and
supports no gradient accumulation — it exists for the elastic/compressed
leg, not as a drop-in replacement.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from bigdl_tpu.nn.criterion import Criterion
from bigdl_tpu.nn.module import Module
from bigdl_tpu.optim.optim_method import OptimMethod
from bigdl_tpu.optim.optimizer import _aux_losses, _clip_grads
from bigdl_tpu.parallel.mesh import (
    DATA_AXIS,
    batch_sharding,
    plan_info,
    replicated,
)

# wire dtypes the collective may run at
WIRE_DTYPES: Dict[str, Any] = {
    "bf16": jnp.bfloat16,
    "bfloat16": jnp.bfloat16,
    "fp8": jnp.float8_e4m3fn,
    "float8_e4m3fn": jnp.float8_e4m3fn,
    "float8_e5m2": jnp.float8_e5m2,
}


def fp16_compress(arr: np.ndarray) -> np.ndarray:
    """Reference-parity host codec: FP16CompressedTensor's truncation
    (keep the upper 16 bits of the fp32 word — sign + 8-bit exponent +
    7-bit mantissa, i.e. the bf16 payload) as a pure numpy round trip.
    The on-device wire cast uses round-to-nearest-even instead, which
    strictly tightens the same 2^-8 relative error bound; this function
    exists so tests can pin that relationship down.
    """
    a = np.ascontiguousarray(arr, dtype=np.float32)
    u = a.view(np.uint32) & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def _resolve_wire(wire_dtype):
    if isinstance(wire_dtype, str):
        if wire_dtype not in WIRE_DTYPES:
            raise ValueError(
                f"unknown wire dtype {wire_dtype!r} "
                f"(have {sorted(set(WIRE_DTYPES))})")
        return WIRE_DTYPES[wire_dtype]
    return jnp.dtype(wire_dtype).type


def build_compressed_dp_train_step(
    model: Module,
    criterion: Criterion,
    optim_methods: Dict[str, OptimMethod],
    mesh,
    wire_dtype="bf16",
    grad_clip_const=None,
    grad_clip_norm=None,
    aux_loss_weight: float = 0.01,
    donate: bool = True,
    template_variables: Optional[Dict[str, Any]] = None,
    numerics=None,
):
    """Compile the compressed-allreduce train step.

    Same signature contract as ``build_dp_train_step``: returns
    ``(jitted_step, placement)``; the step takes the canonical
    ``(params, model_state, opt_states, step, rng, features, targets,
    lrs)`` tuple.  ``placement`` additionally carries ``wire_dtype``
    (the dtype's name) for the lint target's metadata.

    ``numerics``: optional NumericsSpec — a fifth (replicated) stats
    output, computed inside the shard_map body from the post-allreduce,
    post-clip gradients (replica-identical by construction, so the
    ``P()`` out_spec is exact, not an average).
    """
    wire = _resolve_wire(wire_dtype)
    wire_name = np.dtype(wire).name
    info = plan_info(mesh)
    for axis, deg in info.degrees:
        if axis != DATA_AXIS and deg > 1:
            raise ValueError(
                "compressed allreduce step is data-parallel only; "
                f"mesh declares {axis}={deg}")
    ndata = info.degree(DATA_AXIS)
    method_items = sorted(optim_methods.items())
    tm = jax.tree_util.tree_map

    def select(tree, key):
        return tree if key == "__all__" else {key: tree[key]}

    def _wire_mean(tree):
        """psum at wire width, then fp32 master accumulation."""
        narrow = tm(lambda g: g.astype(wire), tree)
        summed = tm(lambda g: jax.lax.psum(g, (DATA_AXIS,)), narrow)
        return tm(lambda g: g.astype(jnp.float32) / ndata, summed)

    def body(params, model_state, opt_states, step, rng, features,
             targets, lrs):
        def loss_fn(p):
            out, new_state = model.apply(
                p, model_state, features, training=True, rng=rng)
            loss = criterion.forward(out, targets).astype(jnp.float32)
            for aux in _aux_losses(new_state):
                loss = loss + aux_loss_weight * aux.astype(jnp.float32)
            return loss, new_state

        (loss, new_model_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        grads32 = _wire_mean(grads)
        grads = tm(lambda p, g: g.astype(p.dtype), params, grads32)
        grads = _clip_grads(grads, grad_clip_const, grad_clip_norm)
        new_params = dict(params) if isinstance(params, dict) else params
        new_opt_states = {}
        for (name, method), lr in zip(method_items, lrs):
            upd, new_opt_states[name] = method.update(
                select(grads, name), opt_states[name],
                select(params, name), lr, step)
            if name == "__all__":
                new_params = upd
            else:
                new_params[name] = upd[name]
        # batch statistics in the model state (BN running stats) were
        # computed per shard: average them over the same narrow wire so
        # every replica leaves the step identical
        new_model_state = tm(
            lambda s: (jax.lax.psum(s.astype(wire), (DATA_AXIS,))
                       .astype(s.dtype) / ndata
                       if jnp.issubdtype(s.dtype, jnp.floating) else s),
            new_model_state)
        # scalar loss: full precision (ndim-0, not a bandwidth concern)
        loss = jax.lax.psum(loss, (DATA_AXIS,)) / ndata
        if numerics is not None:
            from bigdl_tpu.telemetry import numerics as numerics_mod

            stats = numerics_mod.collect(params, grads, new_params,
                                         numerics)
            return new_params, new_model_state, new_opt_states, loss, stats
        return new_params, new_model_state, new_opt_states, loss

    b_spec = P(DATA_AXIS)
    out_specs = (P(), P(), P(), P())
    if numerics is not None:
        out_specs = out_specs + (P(),)
    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), b_spec, b_spec, P()),
        out_specs=out_specs,
        check_vma=False)

    rep = replicated(mesh)
    b_shard = batch_sharding(mesh, None)
    out_shardings = (rep, rep, rep, rep)
    if numerics is not None:
        out_shardings = out_shardings + (rep,)
    jitted = jax.jit(
        mapped,
        in_shardings=(rep, rep, rep, rep, rep, b_shard, b_shard, rep),
        out_shardings=out_shardings,
        donate_argnums=(0, 1, 2) if donate else (),
    )
    placement = {
        "params": rep,
        "model_state": rep,
        "opt_states": rep,
        "batch": b_shard,
        "target": b_shard,
        "plan": info,
        "wire_dtype": wire_name,
    }
    # static build config on the X-ray record: a recompile forensic on
    # this program can then name a wire-dtype flip, not just shapes
    from bigdl_tpu.telemetry import programs

    programs.get_program_registry().annotate(
        "compressed_dp_train_step", wire_dtype=wire_name,
        ndata=mesh.shape.get(DATA_AXIS, 1), donate=donate)
    return jitted, placement
