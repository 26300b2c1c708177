"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Beyond-reference capability (SURVEY.md §5: the reference keeps whole
sequences on one replica).  Two schemes over the ``seq`` mesh axis:

* :func:`ring_attention` — K/V blocks rotate around the ICI ring via
  ``ppermute`` while each device keeps its Q block; softmax is
  accumulated blockwise with the running-max/denominator trick (flash
  attention's streaming update), so the full (T, T) score matrix never
  exists and sequence length scales linearly with ring size.
* :func:`ulysses_attention` — all-to-all reshards from sequence-sharded
  to head-sharded, runs ordinary attention locally over full sequences,
  and reshards back.  Cheaper for moderate T with enough heads.

Both are pure functions usable inside any jitted train step; causal
masking accounts for each block's global position offset.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from bigdl_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS


def _blockwise_update(o, m, l, scores, v_blk):
    """One streaming-softmax accumulation step.

    o: (B,H,Tq,D) running un-normalized output; m: (B,H,Tq,1) running max;
    l: (B,H,Tq,1) running denominator; scores: (B,H,Tq,Tk_blk).
    """
    m_blk = jnp.max(scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_blk)
    # guard against all -inf rows (fully masked block)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(scores - m_safe)
    p = jnp.where(jnp.isfinite(scores), p, 0.0)
    correction = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
    correction = jnp.where(jnp.isfinite(m), correction, 0.0)
    l_new = l * correction + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o * correction + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v_blk.astype(p.dtype)
    )
    return o_new, m_new, l_new


def ring_attention(
    q: jnp.ndarray,  # (B, H, T, D) with T sharded over 'seq'
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    causal: bool = False,
    scale: Optional[float] = None,
    axis_name: str = SEQ_AXIS,
) -> jnp.ndarray:
    """Exact attention with T sharded over the ring; O(T_local * T) time,
    O(T_local^2) memory per device."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    # heads stay sharded over 'model' when the mesh has one (attention
    # is head-independent, so tp composes with the ring for free)
    head_axis = MODEL_AXIS if MODEL_AXIS in mesh.shape else None
    spec = P(DATA_AXIS, head_axis, axis_name, None)
    n_ring = mesh.shape[axis_name]

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    def inner(qb, kb, vb):
        b, h, t_loc, d = qb.shape
        dv = vb.shape[-1]
        my_idx = lax.axis_index(axis_name)
        q_pos = my_idx * t_loc + jnp.arange(t_loc)  # global q positions

        o = jnp.zeros((b, h, t_loc, dv), jnp.float32)
        m = jnp.full((b, h, t_loc, 1), -jnp.inf, jnp.float32)
        l = jnp.zeros((b, h, t_loc, 1), jnp.float32)

        def body(step, carry):
            o, m, l, k_cur, v_cur = carry
            # after `step` rotations (shift +1), we hold block (my_idx - step)
            src = (my_idx - step) % n_ring
            scores = (
                jnp.einsum(
                    "bhqd,bhkd->bhqk", qb, k_cur,
                    preferred_element_type=jnp.float32,
                )
                * scale
            )
            if causal:
                k_pos = src * t_loc + jnp.arange(t_loc)
                mask = q_pos[:, None] >= k_pos[None, :]
                scores = jnp.where(mask[None, None], scores, -jnp.inf)
            o, m, l = _blockwise_update(o, m, l, scores, v_cur)
            perm = [(i, (i + 1) % n_ring) for i in range(n_ring)]
            k_nxt = lax.ppermute(k_cur, axis_name, perm)
            v_nxt = lax.ppermute(v_cur, axis_name, perm)
            return o, m, l, k_nxt, v_nxt

        o, m, l, _, _ = lax.fori_loop(0, n_ring, body, (o, m, l, kb, vb))
        return (o / jnp.maximum(l, 1e-30)).astype(qb.dtype)

    return inner(q, k, v)


def ulysses_attention(
    q: jnp.ndarray,  # (B, H, T, D), T sharded over 'seq'
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    causal: bool = False,
    scale: Optional[float] = None,
    axis_name: str = SEQ_AXIS,
) -> jnp.ndarray:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style): reshard
    T-sharded -> H-sharded, local full-sequence attention, reshard back.
    Requires the per-device head count to divide by seq_axis_size."""
    n = mesh.shape[axis_name]
    head_axis = MODEL_AXIS if MODEL_AXIS in mesh.shape else None
    n_model = mesh.shape.get(MODEL_AXIS, 1) if head_axis else 1
    assert (q.shape[1] // n_model) % n == 0, (
        f"per-device heads ({q.shape[1]}/{n_model}) must divide the seq "
        f"axis size ({n})")
    spec = P(DATA_AXIS, head_axis, axis_name, None)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    def inner(qb, kb, vb):
        # (B, H, T_loc, D) -> all_to_all over heads: (B, H/n, T, D)
        def a2a_fwd(x):
            return lax.all_to_all(
                x, axis_name, split_axis=1, concat_axis=2, tiled=True
            )

        def a2a_bwd(x):
            return lax.all_to_all(
                x, axis_name, split_axis=2, concat_axis=1, tiled=True
            )

        qf, kf, vf = a2a_fwd(qb), a2a_fwd(kb), a2a_fwd(vb)
        from bigdl_tpu.ops.attention import dot_product_attention

        of = dot_product_attention(qf, kf, vf, causal=causal, scale=scale)
        return a2a_bwd(of)

    return inner(q, k, v)


class RingSelfAttention:
    """Callable wrapper binding mesh/config, drop-in for the attention
    core of MultiHeadAttention when sequences are context-sharded."""

    MODES = ("ring", "ulysses")

    def __init__(self, mesh: Mesh, causal: bool = False, mode: str = "ring"):
        if mode not in self.MODES:
            raise ValueError(f"unknown sequence-parallel mode {mode!r}; "
                             f"expected one of {self.MODES}")
        self.mesh = mesh
        self.causal = causal
        self.mode = mode

    def __call__(self, q, k, v, **kw):
        fn = ring_attention if self.mode == "ring" else ulysses_attention
        return fn(q, k, v, self.mesh, causal=self.causal)
