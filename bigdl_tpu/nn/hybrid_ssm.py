"""Decoder of Mamba-2 layers, grouped-query attention layers and latent
routed experts, one mixer a block: the Nemotron-H family (NVIDIA
Nemotron 3), served by the same ``serving.DecodeEngine`` contract as
:class:`~bigdl_tpu.nn.window_moe.WindowMoETransformer`, whose embedding,
head, paged GQA layer and engine-facing entry points (``prefill``,
``extend``, ``extend_paged``, ``decode_step_paged``) it inherits.

What is new is a layer whose decode state is not rows of tokens: a
Mamba-2 layer keeps, a slot, one f32 block of state and the last
``conv_kernel - 1`` inputs of its convolution, declared as
:class:`~bigdl_tpu.ops.paged_kv.Block` leaves beside the attention
layer's paged K and V (serving/paging.py keeps them a slot, never a
page).

Block ``l`` is ``h <- h + Mixer_l(RMSNorm(h))`` with exactly one mixer,
``hybrid_override_pattern[l]``: ``M`` Mamba-2, ``*`` attention, ``E``
routed experts.  The embedding is not scaled; a final RMSNorm and an
untied head.

**Mamba-2** (``d_inner = expand * d``, ``H`` heads of ``P``, ``G``
groups, ``N`` states; no projection bias)::

    [z | xBC | dt] = W_in u                  widths d_inner, d_inner + 2GN, H
    xBC = silu(conv1d_causal,depthwise(xBC) + b_conv)   kernel conv_kernel
    x (H x P), B (G x N), C (G x N) = split(xBC); head h reads group h // (H/G)
    delta = softplus(dt + dt_bias);  a_h = -exp(A_log_h)
    S_t = exp(delta_t a_h) S_{t-1} + delta_t x_t B_t^T     S (P x N), S_0 = 0
    y_t = S_t C_t + D_h x_t
    out = W_out(RMSNorm_{G groups}(y * silu(z)) * w)

A prompt or a chunk runs the recurrence in the chunked form of SSD
(``chunk_size`` rows a chunk: the decay inside a chunk as a masked
matrix, the state carried from chunk to chunk), which is the same sum;
a tick runs one step of it.  A token that is padding, or a slot that is
not active, gets ``delta = 0``: its state and its convolution history
do not move.

**Attention**: ``H`` query heads over ``G`` K/V heads of ``D``, causal
softmax over every cached row; no bias, no rotary, no norm on q or k,
no gate.  **Routed experts** (nn/routed.py): sigmoid router over the
full hidden, top ``k``, weights renormalised and scaled, experts
``W2 relu(W1 l)**2`` in a latent space ``l = W_down u`` shared by all of
them, ``W_up`` back, and one shared expert of the same form at full
width.

Precision: products run in the weights' dtype with f32 accumulation;
the router, the norms, ``delta`` and its exponent, the convolution, the
state and every product that touches it (at ``highest``), softmax and
logits in f32.  The state block's dtype is ``ssm_state_dtype``.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.init import RandomNormal
from bigdl_tpu.nn.latent import rms_norm
from bigdl_tpu.nn.module import Module
from bigdl_tpu.nn.routed import RoutedExperts
from bigdl_tpu.nn.window_moe import GatedWindowAttention, \
    WindowMoETransformer
from bigdl_tpu.ops import paged_kv

MAMBA, ATTENTION, MOE = "M", "*", "E"
HIGHEST = jax.lax.Precision.HIGHEST


def _dot(spec, *xs):
    return jnp.einsum(spec, *xs, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def ssd_scan(x, dt, a, b, c, state, chunk: int):
    """The recurrence ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``,
    ``y_t = S_t C_t`` over ``T`` steps in chunks of ``chunk``.

    ``x`` (N, T, G, R, P) (R heads a group), ``dt`` (N, T, G, R) (0 at
    a step that must not move the state), ``a`` (G, R), ``b`` and ``c``
    (N, T, G, S), ``state`` (N, G, R, P, S); all f32.  -> ``(y (N, T,
    G, R, P), the state after the last step)``."""
    n, t = x.shape[:2]
    pad = -t % chunk
    if pad:
        widen = lambda v: jnp.pad(v, [(0, 0), (0, pad)]
                                  + [(0, 0)] * (v.ndim - 2))
        x, dt, b, c = map(widen, (x, dt, b, c))
    k = (t + pad) // chunk
    split = lambda v: v.reshape((n, k, chunk) + v.shape[2:])
    x, dt, b, c = map(split, (x, dt, b, c))
    cum = jnp.cumsum(dt * a, axis=2)                   # (N, K, Q, G, R)
    # inside a chunk: y_i = sum_{j <= i} (C_i . B_j) e^{cum_i - cum_j}
    # dt_j x_j
    cb = _dot("nkigs,nkjgs->nkgij", c, b)
    diff = cum[:, :, :, None] - cum[:, :, None, :]     # (N, K, Qi, Qj, ..)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    weights = cb[:, :, :, None] * jnp.moveaxis(
        decay * dt[:, :, None], (2, 3), (4, 5))      # (N, K, G, R, Qi, Qj)
    y = _dot("nkgrij,nkjgrp->nkigrp", weights, x)
    # each chunk's own contribution to the state at its end
    tail = jnp.exp(cum[:, :, -1:] - cum) * dt           # (N, K, Q, G, R)
    own = _dot("nkjgs,nkjgr,nkjgrp->nkgrps", b, tail, x)
    whole = jnp.exp(cum[:, :, -1])                      # (N, K, G, R)

    def carry(s, inputs):
        w, o = inputs
        return w[..., None, None] * s + o, s

    last, before = jax.lax.scan(
        carry, state, (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(own, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                 # (N, K, G, R, P, S)
    y = y + jnp.exp(cum)[..., None] * _dot("nkigs,nkgrps->nkigrp", c,
                                           before)
    return y.reshape((n, k * chunk) + y.shape[3:])[:, :t], last


def ssm_step(x, dt, a, b, c, state):
    """One step of the recurrence for each slot: ``x`` (S, G, R, P),
    ``dt`` (S, G, R) (0: the slot's state stays as it is), ``b``, ``c``
    (S, G, N), ``state`` (S, G, R, P, N).  -> ``(y (S, G, R, P), the
    new state)``: the state is read once and written once."""
    decay = jnp.exp(dt * a)[..., None, None]
    new = decay * state + (dt[..., None] * x)[..., None] \
        * b[:, :, None, None, :]
    return (new * c[:, :, None, None, :]).sum(-1), new


# ------------------------------------------------------------------ mamba
class Mamba2Mixer(Module):
    """The Mamba-2 mixer of one layer (see the module's docstring)."""

    window = None     # the paged entry point asks every mixer its band

    def __init__(self, hidden_size: int, num_heads: int, head_dim: int,
                 n_groups: int, state_size: int, conv_kernel: int = 4,
                 expand: int = 2, chunk_size: int = 128,
                 eps: float = 1e-5, state_dtype: str = "float32",
                 time_step_min: float = 0.001, time_step_max: float = 0.1,
                 time_step_floor: float = 1e-4,
                 name: Optional[str] = None):
        super().__init__(name)
        self.hidden_size, self.heads, self.head_dim = (hidden_size,
                                                       num_heads, head_dim)
        self.d_inner = expand * hidden_size
        if num_heads * head_dim != self.d_inner or num_heads % n_groups:
            raise ValueError(f"{num_heads} heads of {head_dim} are not "
                             f"d_inner {self.d_inner} over {n_groups} groups")
        self.groups, self.state_size = n_groups, state_size
        self.conv_kernel, self.chunk = conv_kernel, chunk_size
        self.conv_dim = self.d_inner + 2 * n_groups * state_size
        self.eps, self.state_dtype = eps, state_dtype
        self.time_step = (time_step_min, time_step_max, time_step_floor)

    def draw_decay(self, rng, dtype=jnp.float32) -> dict:
        """``A_log = log U(1, 16)`` and ``dt_bias`` the inverse softplus
        of a draw log-uniform between the time step's bounds, floored at
        its floor, as Mamba-2 initialises them."""
        ka, kd = jax.random.split(rng)
        lo, hi, floor = self.time_step
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            kd, (self.heads,), minval=math.log(lo), maxval=math.log(hi))),
            floor)
        return {"A_log": jnp.log(jax.random.uniform(
                    ka, (self.heads,), minval=1.0, maxval=16.0)).astype(dtype),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)}

    def init_params(self, rng, dtype=jnp.float32):
        ks = jax.random.split(rng, 4)
        init = RandomNormal(0.0, 0.02)
        d, h = self.hidden_size, self.heads
        return {"w_in": init(ks[0], (d, self.d_inner + self.conv_dim + h),
                             dtype),
                "conv_w": init(ks[1], (self.conv_kernel, self.conv_dim),
                               dtype),
                "conv_b": jnp.zeros((self.conv_dim,), dtype),
                **self.draw_decay(ks[3], dtype),
                "D": jnp.ones((h,), dtype),
                "norm": jnp.ones((self.d_inner,), dtype),
                "w_out": init(ks[2], (self.d_inner, d), dtype)}

    def decode_state(self) -> dict:
        block = paged_kv.Block
        return {"ssm": block((self.heads, self.head_dim, self.state_size),
                             self.state_dtype),
                "conv": block((self.conv_kernel - 1, self.conv_dim))}

    # ---------------------------------------------------------- pieces
    def _conv(self, params, xbc, hist, advance):
        """Causal depthwise convolution of ``xbc`` (N, T, C) behind
        ``hist`` (N, K-1, C), the last inputs before it; ->
        ``(silu(conv + b) in f32, the last K-1 inputs up to each row's
        ``advance`` (N,)``)."""
        k, t = self.conv_kernel, xbc.shape[1]
        seq = jnp.concatenate([hist.astype(xbc.dtype), xbc], axis=1)
        w = params["conv_w"].astype(jnp.float32)
        out = sum(seq[:, j:j + t].astype(jnp.float32) * w[j]
                  for j in range(k))
        out = jax.nn.silu(out + params["conv_b"].astype(jnp.float32))
        kept = jax.vmap(lambda s, at: jax.lax.dynamic_slice_in_dim(
            s, at, k - 1))(seq, advance)
        return out, kept.astype(hist.dtype)

    def _split(self, params, u, rows):
        """``u`` (N, T, d) -> z, xBC, and ``delta`` (N, T, H) in f32,
        0 where ``rows`` is false."""
        zxd = u @ params["w_in"].astype(u.dtype)
        z, xbc, dt = jnp.split(zxd, [self.d_inner,
                                     self.d_inner + self.conv_dim], -1)
        delta = jax.nn.softplus(dt.astype(jnp.float32)
                                + params["dt_bias"].astype(jnp.float32))
        return z, xbc, jnp.where(rows[..., None], delta, 0.0)

    def _finish(self, params, y, x, z):
        """``y + D x``, gated by ``silu(z)``, normed by group, out."""
        n, t = y.shape[:2]
        d = params["D"].astype(jnp.float32).reshape(
            self.groups, self.heads // self.groups, 1)
        y = (y + d * x).reshape(n, t, self.d_inner)
        y = y * jax.nn.silu(z.astype(jnp.float32))
        g = y.reshape(n, t, self.groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                              + self.eps)
        y = g.reshape(n, t, -1) * params["norm"].astype(jnp.float32)
        return y.astype(z.dtype) @ params["w_out"].astype(z.dtype)

    def forward(self, params, u, cache, rows):
        """``u`` (N, T, d) after the block's norm, from the state and
        history in ``cache``; ``rows`` (N, T) marks the tokens that are
        no padding (consecutive from the first).  -> ``(out, cache)``
        with the state after each row's last real token."""
        n, t, _ = u.shape
        g, r = self.groups, self.heads // self.groups
        z, xbc, delta = self._split(params, u, rows)
        with jax.named_scope("conv"):
            xbc, hist = self._conv(params, xbc, cache["conv"],
                                   rows.sum(1).astype(jnp.int32))
        x, b, c = jnp.split(xbc, [self.d_inner,
                                  self.d_inner + g * self.state_size], -1)
        x = x.reshape(n, t, g, r, self.head_dim)
        b = b.reshape(n, t, g, self.state_size)
        c = c.reshape(n, t, g, self.state_size)
        delta = delta.reshape(n, t, g, r)
        a = -jnp.exp(params["A_log"].astype(jnp.float32)).reshape(g, r)
        s0 = cache["ssm"].astype(jnp.float32).reshape(
            n, g, r, self.head_dim, self.state_size)
        if t == 1:
            with jax.named_scope("ssm"):
                y, s = ssm_step(x[:, 0], delta[:, 0], a, b[:, 0], c[:, 0],
                                s0)
                y = y[:, None]
        else:
            with jax.named_scope("ssd"):
                y, s = ssd_scan(x, delta, a, b, c, s0, self.chunk)
        out = self._finish(params, y, x, z)
        kept = dict(cache, ssm=s.reshape(cache["ssm"].shape).astype(
            cache["ssm"].dtype), conv=hist, length=cache["length"] + t)
        return out, kept

    # ------------------------------------------------------ entry points
    def apply(self, params, state, x, training=False, rng=None):
        fresh = paged_kv.init_cache(self.decode_state(), x.shape[0], 0,
                                    x.dtype)
        return self.forward(params, x, fresh,
                            jnp.ones(x.shape[:2], bool))[0], state

    def apply_cached(self, params, x, cache, rows=None):
        rows = jnp.ones(x.shape[:2], bool) if rows is None else rows
        return self.forward(params, x, cache, rows)

    apply_prefill = apply_cached   # a fresh row's cache holds zeros

    def apply_paged(self, params, x, cache, table, active, rows=None):
        """The state is a block a slot (no pages, no table): a row that
        is not active keeps it as it is."""
        rows = jnp.broadcast_to(active[:, None], x.shape[:2]) \
            if rows is None else rows
        return self.forward(params, x, cache, rows)


# -------------------------------------------------------------- attention
class GroupedAttention(GatedWindowAttention):
    """Grouped-query attention with no norm on q or k, no gate, no
    rotary and no band: the paged layer of nn/window_moe.py without the
    parts that family adds.  ``scale`` multiplies the scores (default
    ``head_dim ** -0.5``)."""

    def __init__(self, hidden_size: int, num_heads: int, kv_heads: int,
                 head_dim: int, scale: Optional[float] = None,
                 name: Optional[str] = None):
        super().__init__(hidden_size, num_heads, kv_heads, head_dim,
                         window=None, name=name)
        if scale is not None:
            self.scale = float(scale)

    def init_params(self, rng, dtype=jnp.float32):
        ks = jax.random.split(rng, 4)
        init = RandomNormal(0.0, 0.02)
        d, hd = self.hidden_size, self.num_heads * self.head_dim
        gd = self.kv_heads * self.head_dim
        return {"wq": init(ks[0], (d, hd), dtype),
                "wk": init(ks[1], (d, gd), dtype),
                "wv": init(ks[2], (d, gd), dtype),
                "wo": init(ks[3], (hd, d), dtype)}

    def project(self, params, x, positions):
        n, t, _ = x.shape

        def heads(w, count):
            return (x @ w.astype(x.dtype)).reshape(
                n, t, count, self.head_dim).transpose(0, 2, 1, 3)

        return (heads(params["wq"], self.num_heads),
                heads(params["wk"], self.kv_heads),
                heads(params["wv"], self.kv_heads), None)

    def finish(self, params, out, gate):
        n, h, t, d = out.shape
        out = out.transpose(0, 2, 1, 3).reshape(n, t, h * d)
        return out @ params["wo"].astype(out.dtype)


# ------------------------------------------------------------------- block
class HybridBlock(Module):
    """``h + mixer(RMSNorm(h))``: Mamba-2, attention or routed experts."""

    def __init__(self, kind: str, mixer: Module, hidden_size: int,
                 eps: float = 1e-5, name: Optional[str] = None):
        super().__init__(name)
        self.kind, self.hidden_size, self.eps = kind, hidden_size, eps
        self.mixer = {MAMBA: "mamba", ATTENTION: "attn", MOE: "ffn"}[kind]
        # the engine's entry points call a stateful mixer ``attn``
        self.attn = None if kind == MOE else mixer
        self.ffn = mixer if kind == MOE else None

    def init_params(self, rng, dtype=jnp.float32):
        return {"norm": {"weight": jnp.ones((self.hidden_size,), dtype)},
                self.mixer: (self.attn or self.ffn).init_params(rng, dtype)}

    def mix(self, params, x, attend, rows=None):
        """A stateful mixer over ``RMSNorm(x)`` -> ``(a, aux)``."""
        h = rms_norm(x, params["norm"]["weight"], self.eps)
        if self.kind == MAMBA:
            with jax.named_scope("mixer"):
                return attend(h, rows)
        with jax.named_scope("attention"), jax.named_scope("full"):
            return attend(h, rows)

    def run(self, params, x, attend, rows=None):
        """``attend(h, rows) -> (a, aux)`` is a stateful mixer's path;
        ``rows`` (N, T) bool marks the tokens that are no padding.  ->
        ``(x, aux or None, expert counts or None)``."""
        if self.kind == MOE:
            h = rms_norm(x, params["norm"]["weight"], self.eps)
            with jax.named_scope("ffn"):
                f, counts = self.ffn.apply_counted(params["ffn"], h,
                                                   rows=rows)
            return x + f, None, counts
        a, aux = self.mix(params, x, attend, rows)
        return x + a, aux, None

    def apply(self, params, state, x, training=False, rng=None):
        out, _, _ = self.run(params, x, lambda h, rows: (
            self.attn.apply(params[self.mixer], {}, h)))
        return out, state


# ------------------------------------------------------------------- model
class HybridSSMTransformer(WindowMoETransformer):
    """The decoder: embedding, one block a ``hybrid_override_pattern``
    letter, final RMSNorm, untied head.  Keyword names follow the
    published ``config.json``; ``n_routed_experts`` is the router's
    width and ``experts_held`` the experts whose weights live here."""

    def __init__(self, vocab_size: int, hidden_size: int,
                 hybrid_override_pattern: str,
                 num_attention_heads: int, num_key_value_heads: int,
                 head_dim: int, mamba_num_heads: int, mamba_head_dim: int,
                 n_groups: int, ssm_state_size: int, conv_kernel: int,
                 expand: int, chunk_size: int, n_routed_experts: int,
                 num_experts_per_tok: int, moe_intermediate_size: int,
                 moe_latent_size: Optional[int],
                 moe_shared_expert_intermediate_size: int,
                 n_shared_experts: int = 1,
                 routed_scaling_factor: float = 1.0,
                 norm_topk_prob: bool = True, n_group: int = 1,
                 topk_group: int = 1, norm_eps: float = 1e-5,
                 ssm_state_dtype: str = "float32",
                 time_step_min: float = 0.001, time_step_max: float = 0.1,
                 time_step_floor: float = 1e-4,
                 experts_held: Optional[Sequence[int]] = None,
                 name: Optional[str] = None):
        Module.__init__(self, name)
        if not set(hybrid_override_pattern) <= {MAMBA, ATTENTION, MOE}:
            raise ValueError(f"hybrid_override_pattern "
                             f"{hybrid_override_pattern!r} names a layer "
                             f"kind other than M, * and E")
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.eps, self.embed_scale = norm_eps, 1.0
        d = hidden_size

        def mixer(kind):
            if kind == MAMBA:
                return Mamba2Mixer(d, mamba_num_heads, mamba_head_dim,
                                   n_groups, ssm_state_size, conv_kernel,
                                   expand, chunk_size, norm_eps,
                                   ssm_state_dtype, time_step_min,
                                   time_step_max, time_step_floor)
            if kind == ATTENTION:
                return GroupedAttention(d, num_attention_heads,
                                        num_key_value_heads, head_dim)
            return RoutedExperts(
                d, moe_intermediate_size, n_routed_experts,
                num_experts_per_tok, n_group, topk_group,
                routed_scaling_factor, norm_topk_prob, n_shared_experts,
                experts_held, activation="relu2",
                latent_size=moe_latent_size,
                shared_width=moe_shared_expert_intermediate_size)

        self.layers = [HybridBlock(kind, mixer(kind), d, norm_eps)
                       for kind in hybrid_override_pattern]

    def _stateful(self):
        return [(lk, layer) for lk, layer in zip(self._layer_keys(),
                                                 self.layers)
                if layer.attn is not None]

    def decode_state(self) -> dict:
        """K and V rows of an attention layer; a Mamba-2 layer's state
        and convolution history, one block a slot."""
        return {lk: layer.attn.decode_state()
                for lk, layer in self._stateful()}

    def decode_extents(self) -> dict:
        return {lk: None for lk, _ in self._stateful()}

