"""Decoder of Mamba-2 and grouped-query attention layers, each followed
by a dense gated MLP, with muP scalars and a tied head: the Granite 4.0-H
family (``granitemoehybrid`` without experts), served by the same
``serving.DecodeEngine`` contract as
:class:`~bigdl_tpu.nn.hybrid_ssm.HybridSSMTransformer`, whose Mamba-2
mixer, attention layer, fixed state block a slot and engine-facing entry
points it inherits.

Layer ``l`` (``N`` = RMSNorm with a weight, ``r`` =
``residual_multiplier``) is two pre-norm residual sublayers::

    h <- h + r * Mixer_l(N_1(h))          Mamba-2 or attention, layer_types[l]
    h <- h + r * W_d(silu(W_g N_2(h)) * W_u N_2(h))

The published ``input_linear`` (hidden -> 2 x width, the first half the
gate) is held as ``W_g`` and ``W_u``.  The embedding is scaled by
``embedding_multiplier``; the head is the embedding transposed, after a
final RMSNorm, and the logits are divided by ``logits_scaling`` (in f32).

Attention: ``H`` query heads over ``G`` K/V heads of ``hidden / H``,
scores scaled by ``attention_multiplier`` (not ``head_dim ** -0.5``),
causal softmax over every cached row; no bias, no rotary
(``position_embedding_type`` "nope"), no norm on q or k.  Mamba-2: the
recurrence of nn/hybrid_ssm.py with a convolution bias, no projection
bias and the gated norm after the gate over ``mamba_n_groups`` groups.

Precision as the hybrid family's: products in the weights' dtype with
f32 accumulation; norms, the MLP's gate, ``delta``, the convolution, the
state (``ssm_state_dtype``) and every product that touches it, softmax
and logits in f32.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.hybrid_ssm import (ATTENTION, MAMBA, GroupedAttention,
                                     HybridBlock, HybridSSMTransformer,
                                     Mamba2Mixer)
from bigdl_tpu.nn.init import RandomNormal
from bigdl_tpu.nn.latent import rms_norm
from bigdl_tpu.nn.module import Module
from bigdl_tpu.nn.routed import GatedFeedForward

KINDS = {"mamba": MAMBA, "attention": ATTENTION}


class GraniteBlock(HybridBlock):
    """``h + r Mixer(N_1(h))``, then ``h + r MLP(N_2(h))``."""

    def __init__(self, kind: str, mixer: Module, hidden_size: int,
                 width: int, residual_multiplier: float, eps: float = 1e-5,
                 name: Optional[str] = None):
        super().__init__(kind, mixer, hidden_size, eps, name)
        self.mlp = GatedFeedForward(hidden_size, width)
        self.residual_multiplier = float(residual_multiplier)

    def init_params(self, rng, dtype=jnp.float32):
        km, kf = jax.random.split(rng)
        return dict(super().init_params(km, dtype),
                    post_norm={"weight": jnp.ones((self.hidden_size,),
                                                  dtype)},
                    mlp=self.mlp.init_params(kf, dtype))

    def run(self, params, x, attend, rows=None):
        r = self.residual_multiplier
        a, aux = self.mix(params, x, attend, rows)
        x = x + r * a
        with jax.named_scope("mlp"):
            v = rms_norm(x, params["post_norm"]["weight"], self.eps)
            return x + r * self.mlp.apply(params["mlp"], {}, v)[0], aux, \
                None


class GraniteHybridTransformer(HybridSSMTransformer):
    """The decoder: scaled embedding, one block a ``layer_types`` entry,
    final RMSNorm, tied and scaled head.  Keyword names follow the
    published ``config.json``; ``shared_intermediate_size`` is the dense
    MLP's width (the family's MLP beside its experts, of which this one
    has none)."""

    def __init__(self, vocab_size: int, hidden_size: int,
                 shared_intermediate_size: int, layer_types: Sequence[str],
                 num_attention_heads: int, num_key_value_heads: int,
                 mamba_n_heads: int, mamba_d_head: int, mamba_n_groups: int,
                 mamba_d_state: int, mamba_d_conv: int, mamba_expand: int,
                 mamba_chunk_size: int, embedding_multiplier: float,
                 attention_multiplier: float, residual_multiplier: float,
                 logits_scaling: float, rms_norm_eps: float = 1e-5,
                 ssm_state_dtype: str = "float32",
                 time_step_min: float = 0.001, time_step_max: float = 0.1,
                 time_step_floor: float = 1e-4,
                 name: Optional[str] = None):
        Module.__init__(self, name)
        if not set(layer_types) <= set(KINDS):
            raise ValueError(f"layer_types {list(layer_types)} name a "
                             f"layer kind other than {sorted(KINDS)}")
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.eps, self.embed_scale = rms_norm_eps, float(embedding_multiplier)
        self.logits_scaling = float(logits_scaling)
        d = hidden_size

        def mixer(kind):
            if kind == MAMBA:
                return Mamba2Mixer(d, mamba_n_heads, mamba_d_head,
                                   mamba_n_groups, mamba_d_state,
                                   mamba_d_conv, mamba_expand,
                                   mamba_chunk_size, rms_norm_eps,
                                   ssm_state_dtype, time_step_min,
                                   time_step_max, time_step_floor)
            return GroupedAttention(d, num_attention_heads,
                                    num_key_value_heads,
                                    d // num_attention_heads,
                                    scale=attention_multiplier)

        self.layers = [
            GraniteBlock(KINDS[t], mixer(KINDS[t]), d,
                         shared_intermediate_size, residual_multiplier,
                         rms_norm_eps)
            for t in layer_types]

    def init_params(self, rng, dtype=jnp.float32):
        """Embedding, final norm and the blocks: no head of its own."""
        ks = jax.random.split(rng, len(self.layers) + 1)
        d = self.hidden_size
        p = {"embed": {"weight": RandomNormal(0.0, 0.02)(
                 ks[0], (self.vocab_size, d), dtype)},
             "ln_f": {"weight": jnp.ones((d,), dtype)}}
        for lk, layer, k in zip(self._layer_keys(), self.layers, ks[1:]):
            p[lk] = layer.init_params(k, dtype)
        return p

    def _head(self, params, h):
        """Final norm, the embedding's transpose, over ``logits_scaling``;
        logits in f32."""
        with jax.named_scope("head"):
            h = rms_norm(h, params["ln_f"]["weight"], self.eps)
            e = params["embed"]["weight"].astype(h.dtype)
            logits = jax.lax.dot_general(
                h, e, (((h.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            return logits / self.logits_scaling
