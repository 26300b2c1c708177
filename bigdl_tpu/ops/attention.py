"""Scaled dot-product attention.

The XLA path below is the reference semantics; ``use_flash`` dispatches to
the Pallas fused kernel (bigdl_tpu.ops.pallas.flash_attention) which tiles
QK^T and the softmax-weighted sum through VMEM without materialising the
(T, T) score matrix in HBM.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def dot_product_attention(
    q: jnp.ndarray,  # (B, H, Tq, D)
    k: jnp.ndarray,  # (B, H, Tk, D)
    v: jnp.ndarray,  # (B, H, Tk, Dv)
    mask: Optional[jnp.ndarray] = None,  # broadcastable to (B, H, Tq, Tk); True=keep
    bias: Optional[jnp.ndarray] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
) -> jnp.ndarray:
    if use_flash is None:
        # auto: the fused kernel handles exactly the mask-free/bias-free
        # cases, and flash_attention itself routes to the XLA path
        # off-TPU or on non-tileable shapes — so auto-enable is safe
        use_flash = mask is None and bias is None
    # the kernel's contract: no mask/bias, V shaped like K, and causal
    # only over matching q/kv lengths (a cached decode step, Tq < Tk,
    # takes the XLA path below).  Inside that contract a kernel error
    # is a real error and propagates.
    if (use_flash and mask is None and bias is None
            and v.shape == k.shape
            and not (causal and q.shape[2] != k.shape[2])):
        from bigdl_tpu.ops.pallas.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal, sm_scale=scale)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(jnp.float32)
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if bias is not None:
        scores = scores + bias
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        causal_mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        scores = jnp.where(causal_mask, scores, -1e30)
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkv->bhqv", weights, v)
