"""Decoder with window and full attention layers mixed, gated
grouped-query attention and routed experts: the block of the AFMoE
family (Arcee Trinity), served by the same ``serving.DecodeEngine``
contract as :class:`~bigdl_tpu.nn.latent.LatentMoETransformer`
(``decode_state``, ``init_cache``, ``init_paged_cache``, ``prefill``,
``extend``, ``extend_paged``, ``decode_step_paged``).

A file of its own, not ``nn/latent.py``'s block with options: the block
has four norms where that one has two, the mixer keeps K and V heads
where that one keeps a latent row, and a layer declares an *extent*
(:meth:`WindowMoETransformer.decode_extents`) that the latent family
has no notion of; what the two share is imported (``rms_norm``, the
routed layer, the gated feed-forward, the length bookkeeping).

Block ``l`` (``N`` = RMSNorm with a weight)::

    h <- h + N_post(Mixer_l(N_in(h)))
    h <- h + N_post_ff(FFN_l(N_pre_ff(h)))

with a gated (SiLU) feed-forward, dense for the first
``num_dense_layers`` layers and routed (nn/routed.py, one group) after;
the embedding is scaled by ``sqrt(hidden)``; a final RMSNorm and an
untied head.

The mixer: ``H`` query heads over ``G`` K/V heads of ``D`` (query head
``i`` reads K/V head ``i // (H/G)``), RMSNorm over each query and key
head's ``D``, a sigmoid gate ``sigmoid(Wg a)`` on the attention output
before the output projection.  A **window** layer turns queries and keys
by rotary positions (rotate-half pairing, all ``D`` lanes) and attends
the causal keys less than ``window`` behind; a **full** layer has no
position signal and attends every causal key.

What a layer keeps per token is ``{"k": (G, D), "v": (G, D)}``; how many
rows of a slot it keeps is its extent: all, or the last ``window``.  The
engine's cache manager (serving/paging.py) gives the two extents a pool
and a block table each, stacked ``(2, S, M)`` in the one table argument
the paged entry points take: ``[0]`` the full layers', ``[1]`` the
window layers', in which pages behind the band are unmapped.  Products
run in the weights' dtype with f32 accumulation; norms, rotary, the gate,
softmax and router scores in f32.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.init import RandomNormal
from bigdl_tpu.nn.latent import _advanced, rms_norm
from bigdl_tpu.nn.module import Module
from bigdl_tpu.nn.routed import GatedFeedForward, RoutedExperts
from bigdl_tpu.ops import paged_kv

WINDOW, FULL = "sliding_attention", "full_attention"
# a chunk of at least this many queries goes through the banded kernel
KERNEL_MIN_QUERY = 128


def rotate_half(x, positions, inv_freq):
    """Rotary embedding of ``x`` (N, H, T, D) at integer ``positions``
    (N, T): lane ``i`` pairs with lane ``i + D/2``, both turned by
    ``pos * inv_freq[i]``, in f32."""
    angle = positions.astype(jnp.float32)[:, None, :, None] * jnp.asarray(
        inv_freq, jnp.float32)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


# --------------------------------------------------------------- attention
class GatedWindowAttention(Module):
    """Grouped-query attention with QK-norm and an output gate (no
    bias); ``window`` rows of band and rotary, or neither."""

    def __init__(self, hidden_size: int, num_heads: int, kv_heads: int,
                 head_dim: int, window: Optional[int] = None,
                 rope_theta: float = 10000.0, rms_norm_eps: float = 1e-5,
                 name: Optional[str] = None):
        super().__init__(name)
        if num_heads % kv_heads:
            raise ValueError(f"{num_heads} query heads do not share "
                             f"{kv_heads} K/V heads evenly")
        self.hidden_size = hidden_size
        self.num_heads, self.kv_heads = num_heads, kv_heads
        self.head_dim, self.window = head_dim, window
        self.eps = rms_norm_eps
        self.scale = head_dim ** -0.5
        self.kind = "window" if window else "full"
        self.inv_freq = 1.0 / float(rope_theta) ** (
            np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)

    def init_params(self, rng, dtype=jnp.float32):
        ks = jax.random.split(rng, 5)
        init = RandomNormal(0.0, 0.02)
        d, hd = self.hidden_size, self.num_heads * self.head_dim
        gd = self.kv_heads * self.head_dim
        return {"wq": init(ks[0], (d, hd), dtype),
                "wk": init(ks[1], (d, gd), dtype),
                "wv": init(ks[2], (d, gd), dtype),
                "wg": init(ks[3], (d, hd), dtype),
                "wo": init(ks[4], (hd, d), dtype),
                "q_norm": jnp.ones((self.head_dim,), dtype),
                "k_norm": jnp.ones((self.head_dim,), dtype)}

    # ---------------------------------------------------------- pieces
    def project(self, params, x, positions):
        """``x`` (N, T, d) at ``positions`` (N, T) -> normed (and, on a
        window layer, rotated) ``q`` (N, H, T, D), ``k`` and ``v``
        (N, G, T, D), and the gate's pre-activation (N, T, H*D)."""
        n, t, _ = x.shape

        def heads(w, count):
            return (x @ w.astype(x.dtype)).reshape(
                n, t, count, self.head_dim).transpose(0, 2, 1, 3)

        q, k = heads(params["wq"], self.num_heads), heads(params["wk"],
                                                          self.kv_heads)
        v = heads(params["wv"], self.kv_heads)
        with jax.named_scope("qk_norm"):
            q = rms_norm(q, params["q_norm"], self.eps)
            k = rms_norm(k, params["k_norm"], self.eps)
        if self.window:
            q = rotate_half(q, positions, self.inv_freq)
            k = rotate_half(k, positions, self.inv_freq)
        return q, k, v, x @ params["wg"].astype(x.dtype)

    def finish(self, params, out, gate):
        """Attention output (N, H, T, D) -> the mixer's (N, T, d)."""
        n, h, t, d = out.shape
        out = out.transpose(0, 2, 1, 3).reshape(n, t, h * d)
        with jax.named_scope("gate"):
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(out.dtype)
        return out @ params["wo"].astype(out.dtype)

    def _band(self, keys: int) -> Optional[int]:
        """The window, where it can hide any of ``keys`` causal keys."""
        return self.window if self.window and self.window < keys else None

    def attend_fresh(self, q, k, v):
        """Causal (and banded) over the same T tokens."""
        from bigdl_tpu.ops.pallas.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=True, sm_scale=self.scale,
                               window=self._band(q.shape[2]))

    def attend_extent(self, q, k, v, q_pos):
        """Queries at absolute ``q_pos`` (N, T), consecutive from
        ``q_pos[:, 0]``, over a cached extent ``k``, ``v`` (N, G, L, D)
        in absolute rows.  On the TPU a chunk goes through the banded
        kernel, which fetches only the key blocks its band touches;
        short queries and other backends mask the whole extent."""
        from bigdl_tpu.ops.pallas import report
        from bigdl_tpu.ops.pallas.flash_attention import (
            band_attention_reference, band_blocks, banded_flash_attention)

        t, extent = q.shape[2], k.shape[2]
        window = self._band(extent)
        on_tpu = report.force_pallas() or jax.default_backend() == "tpu"
        blocks = band_blocks(t, extent, self.num_heads // self.kv_heads) \
            if on_tpu and t >= KERNEL_MIN_QUERY else None
        if blocks:
            report.record("prefix_flash_attention", "pallas")
            return banded_flash_attention(
                q, k, v, q_pos[:, 0], sm_scale=self.scale, window=window,
                blocks=blocks, name="flash_prefix")
        if on_tpu and t >= KERNEL_MIN_QUERY:
            report.record("prefix_flash_attention", "xla",
                          (q.shape[0], self.num_heads, t, extent,
                           self.head_dim))
        return band_attention_reference(q, k, v, q_pos, window, self.scale)

    # ------------------------------------------------------ entry points
    def apply(self, params, state, x, training=False, rng=None):
        pos = jnp.arange(x.shape[1])[None, :]
        q, k, v, gate = self.project(params, x, pos)
        return self.finish(params, self.attend_fresh(q, k, v), gate), state

    def decode_state(self) -> dict:
        return {"k": (self.kv_heads, self.head_dim),
                "v": (self.kv_heads, self.head_dim)}

    def apply_prefill(self, params, x, cache, rows=None):
        """A fresh row's prompt: keep its K and V at ``[0, T)`` and
        attend over the prompt itself.  ``rows`` (the tokens that are no
        padding) is not needed: a row past a slot's length is never
        read."""
        pos = jnp.arange(x.shape[1])[None, :]
        q, k, v, gate = self.project(params, x, pos)
        kept = {name: jax.lax.dynamic_update_slice_in_dim(
            cache[name], new.astype(cache[name].dtype), 0, axis=2)
            for name, new in (("k", k), ("v", v))}
        out = self.finish(params, self.attend_fresh(q, k, v), gate)
        return out, dict(cache, **kept,
                         length=cache["length"] + x.shape[1])

    def apply_cached(self, params, x, cache, rows=None):
        """Append ``x`` (N, T, d) at each row's ``length`` of the dense
        cache (absolute rows: a window layer's staging row is as long as
        a full layer's) and attend under the layer's mask."""
        t = x.shape[1]
        pos = cache["length"][:, None] + jnp.arange(t)[None]
        q, k, v, gate = self.project(params, x, pos)
        kept = {name: jax.vmap(
            lambda c, r, at: jax.lax.dynamic_update_slice(
                c, r, (0, at, 0)))(cache[name],
                                   new.astype(cache[name].dtype),
                                   cache["length"])
            for name, new in (("k", k), ("v", v))}
        out = self.attend_extent(q, kept["k"].astype(x.dtype),
                                 kept["v"].astype(x.dtype), pos)
        return self.finish(params, out, gate), dict(
            cache, **kept, length=cache["length"] + t)

    def apply_paged(self, params, x, cache, table, active, rows=None):
        """``apply_cached`` over this layer's pool and block ``table``
        (S, M): one token a slot on the TPU reads only the pages held,
        a window layer none before its band
        (ops/pallas/paged_attention.py); everything else gathers the
        slot's extent (unmapped pages read the trash page, which the
        mask hides)."""
        from bigdl_tpu.ops.pallas import paged_attention

        n, t, _ = x.shape
        page = cache["k"].shape[1]
        extent = table.shape[1] * page
        length = cache["length"]
        pos = length[:, None] + jnp.arange(t)[None]
        q, k, v, gate = self.project(params, x, pos)
        cache = paged_kv.paged_append(cache, table, active,
                                      {"k": k, "v": v}, page, extent)
        new_cache = dict(cache, length=length + t)
        if paged_attention.routes(
                (n, t, self.kv_heads * self.head_dim), cache["k"], table,
                self.kv_heads):
            kv_len = jnp.where(active, length + 1, 0)
            first = jnp.maximum(kv_len - self.window, 0) \
                if self.window else None
            out = paged_attention.paged_attn(
                q[:, :, 0], cache["k"], cache["v"], table, kv_len, first,
                num_heads=self.num_heads, kv_heads=self.kv_heads,
                sm_scale=self.scale)
            return self.finish(params, out[:, :, None], gate), new_cache
        with jax.named_scope("paged_gather"):
            held = [paged_kv._gather_heads(cache, name, table,
                                           self.kv_heads)[0].astype(x.dtype)
                    for name in ("k", "v")]
        out = self.attend_extent(q, held[0], held[1], pos)
        return self.finish(params, out, gate), new_cache


# ------------------------------------------------------------------- block
class SandwichBlock(Module):
    """A norm before and after the mixer, a norm before and after the
    feed-forward (dense or routed)."""

    mixer = "attn"  # the mixer's key in the block's parameters

    def __init__(self, attention: GatedWindowAttention, ffn: Module,
                 rms_norm_eps: float = 1e-5, name: Optional[str] = None):
        super().__init__(name)
        self.attn, self.ffn, self.eps = attention, ffn, rms_norm_eps

    def init_params(self, rng, dtype=jnp.float32):
        ka, kf = jax.random.split(rng)
        norm = lambda: {"weight": jnp.ones((self.attn.hidden_size,), dtype)}
        return {"ln1": norm(), "attn": self.attn.init_params(ka, dtype),
                "ln1_post": norm(), "ln2": norm(),
                "ffn": self.ffn.init_params(kf, dtype), "ln2_post": norm()}

    def run(self, params, x, attend, rows=None):
        """``attend(h, rows) -> (a, aux)`` is the attention path;
        ``rows`` (N, T) bool marks the tokens that are no padding.  ->
        ``(x, aux, expert counts or None)``."""
        norm = lambda v, name: rms_norm(v, params[name]["weight"], self.eps)
        with jax.named_scope("attention"), \
                jax.named_scope(self.attn.kind):
            a, aux = attend(norm(x, "ln1"), rows)
            x = x + norm(a, "ln1_post")
        with jax.named_scope("ffn"):
            h = norm(x, "ln2")
            if isinstance(self.ffn, RoutedExperts):
                f, counts = self.ffn.apply_counted(params["ffn"], h,
                                                   rows=rows)
            else:
                f, counts = self.ffn.apply(params["ffn"], {}, h)[0], None
            return x + norm(f, "ln2_post"), aux, counts

    def apply(self, params, state, x, training=False, rng=None):
        out, _, _ = self.run(
            params, x, lambda h, rows: self.attn.apply(params["attn"], {}, h))
        return out, state


# ------------------------------------------------------------------- model
class WindowMoETransformer(Module):
    """The decoder: scaled embedding, one block a ``layer_types`` entry
    (the first ``num_dense_layers`` dense, the rest routed over
    ``experts_held``), final RMSNorm, untied head.  Keyword names
    follow the published ``config.json``."""

    def __init__(self, vocab_size: int, hidden_size: int,
                 intermediate_size: int, moe_intermediate_size: int,
                 num_hidden_layers: int, num_dense_layers: int,
                 num_attention_heads: int, num_key_value_heads: int,
                 head_dim: int, layer_types: Sequence[str],
                 sliding_window: int, num_experts: int,
                 num_experts_per_tok: int, num_shared_experts: int = 1,
                 route_norm: bool = True, route_scale: float = 1.0,
                 rms_norm_eps: float = 1e-5, rope_theta: float = 10000.0,
                 mup_enabled: bool = True,
                 experts_held: Optional[Sequence[int]] = None,
                 name: Optional[str] = None):
        super().__init__(name)
        if len(layer_types) != num_hidden_layers or not set(
                layer_types) <= {WINDOW, FULL}:
            raise ValueError(f"layer_types {list(layer_types)} do not "
                             f"name {num_hidden_layers} layers' kinds")
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.eps = rms_norm_eps
        self.embed_scale = math.sqrt(hidden_size) if mup_enabled else 1.0
        self.layers = [
            SandwichBlock(
                GatedWindowAttention(
                    hidden_size, num_attention_heads, num_key_value_heads,
                    head_dim, sliding_window if kind == WINDOW else None,
                    rope_theta, rms_norm_eps),
                GatedFeedForward(hidden_size, intermediate_size)
                if i < num_dense_layers else RoutedExperts(
                    hidden_size, moe_intermediate_size, num_experts,
                    num_experts_per_tok, 1, 1, route_scale, route_norm,
                    num_shared_experts, experts_held),
                rms_norm_eps)
            for i, kind in enumerate(layer_types)]

    def _layer_keys(self):
        return [f"layer{i}" for i in range(len(self.layers))]

    def init_params(self, rng, dtype=jnp.float32):
        ks = jax.random.split(rng, len(self.layers) + 2)
        init = RandomNormal(0.0, 0.02)
        d = self.hidden_size
        p = {"embed": {"weight": init(ks[0], (self.vocab_size, d), dtype)},
             "ln_f": {"weight": jnp.ones((d,), dtype)},
             "head": {"weight": init(ks[1], (d, self.vocab_size), dtype)}}
        for lk, layer, k in zip(self._layer_keys(), self.layers, ks[2:]):
            p[lk] = layer.init_params(k, dtype)
        return p

    # ---------------------------------------------------------- pieces
    def _embed(self, params, ids):
        with jax.named_scope("embed"):
            e = jnp.take(params["embed"]["weight"], ids.astype(jnp.int32),
                         axis=0)
            return (e.astype(jnp.float32) * self.embed_scale).astype(
                e.dtype)

    def _head(self, params, h):
        """Final norm and the vocabulary product, logits in f32."""
        with jax.named_scope("head"):
            h = rms_norm(h, params["ln_f"]["weight"], self.eps)
            return jnp.dot(h, params["head"]["weight"].astype(h.dtype),
                           preferred_element_type=jnp.float32)

    def _run(self, params, h, attend_of, rows=None):
        """Every block over ``h``; ``attend_of(lk, layer)`` gives the
        block's mixer path, ``(x, rows) -> (a, aux)``.  -> ``(h, {lk:
        aux}, {lk: counts})``; a block that keeps no state gives no
        aux."""
        aux, counts = {}, {}
        for lk, layer in zip(self._layer_keys(), self.layers):
            h, a, c = layer.run(params[lk], h, attend_of(lk, layer), rows)
            if a is not None:
                aux[lk] = a
            if c is not None:
                counts[lk] = c
        return h, aux, counts

    def apply(self, params, state, ids, training=False, rng=None):
        h, _, _ = self._run(
            params, self._embed(params, ids),
            lambda lk, layer: lambda x, rows: layer.attn.apply(
                params[lk][layer.mixer], {}, x))
        return self._head(params, h), state

    # ---------------------------------------------- the engine's contract
    def decode_state(self) -> dict:
        return {lk: layer.attn.decode_state()
                for lk, layer in zip(self._layer_keys(), self.layers)}

    def decode_extents(self) -> dict:
        """How many rows of a slot each layer keeps: ``None`` all of
        them, a number the last so many (serving/paging.py keeps a pool
        and a block table for each)."""
        return {lk: layer.attn.window
                for lk, layer in zip(self._layer_keys(), self.layers)}

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        return {lk: paged_kv.init_cache(leaves, batch, max_len, dtype)
                for lk, leaves in self.decode_state().items()}

    def init_paged_cache(self, num_pages: int, page_size: int, batch: int,
                         dtype=jnp.float32, kv_dtype=None,
                         window_pages: Optional[int] = None):
        """A pool a layer: ``num_pages`` for a full layer,
        ``window_pages`` (default the same) for a window layer."""
        if kv_dtype is not None:
            raise ValueError("these pools have no quantized form")
        extents = self.decode_extents()
        return {lk: paged_kv.init_pool(
            window_pages if extents[lk] and window_pages else num_pages,
            page_size, leaves, batch, dtype)
            for lk, leaves in self.decode_state().items()}

    def prefill(self, params, state, ids, cache, lengths=None):
        """Causal forward over padded prompts ``ids`` (N, T) into fresh
        cache rows; ``(next-token logits (N, V), cache)`` with each
        row's length set to its true ``lengths``."""
        n, t = ids.shape
        lengths = jnp.full((n,), t, jnp.int32) if lengths is None \
            else lengths.astype(jnp.int32)
        h, new, _ = self._run(
            params, self._embed(params, ids),
            lambda lk, layer: lambda x, rows: layer.attn.apply_prefill(
                params[lk][layer.mixer], x, cache[lk], rows),
            rows=jnp.arange(t)[None, :] < lengths[:, None])
        cache = {lk: dict(c, length=lengths) for lk, c in new.items()}
        last = jnp.take_along_axis(h, (lengths - 1)[:, None, None], axis=1)
        return self._head(params, last)[:, 0], cache

    def extend(self, params, state, cache, ids, advance=None, rows=None):
        """Append ``ids`` (N, T) at each row's current length; logits of
        the appended positions ``rows`` (N, R) (default every one).
        ``advance`` (N,) is how many of the T are real."""
        h, new, _ = self._run(
            params, self._embed(params, ids),
            lambda lk, layer: lambda x, rows: layer.attn.apply_cached(
                params[lk][layer.mixer], x, cache[lk], rows),
            rows=None if advance is None
            else jnp.arange(ids.shape[1])[None, :] < advance[:, None])
        if rows is not None:
            h = jnp.take_along_axis(h, rows[:, :, None], axis=1)
        return self._head(params, h), _advanced(cache, new, advance)

    def decode_step(self, params, state, cache, ids_t):
        logits, cache = self.extend(params, state, cache, ids_t[:, None])
        return logits[:, 0], cache

    def _extend_paged(self, params, state, cache, table, ids, active,
                      advance=None):
        """``extend`` over the paged pools -> ``(logits, cache,
        counters)``; ``table`` is the two extents' tables stacked
        (2, S, M), or one (S, M) that every layer reads."""
        full, band = (table, table) if table.ndim == 2 else (table[0],
                                                             table[1])
        rows = jnp.broadcast_to(active[:, None], ids.shape)
        if advance is not None:
            rows &= jnp.arange(ids.shape[1])[None, :] < advance[:, None]
        h, new, counts = self._run(
            params, self._embed(params, ids),
            lambda lk, layer: lambda x, rows: layer.attn.apply_paged(
                params[lk][layer.mixer], x, cache[lk],
                band if layer.attn.window else full, active, rows),
            rows=rows)
        counters = {"expert_tokens": jnp.stack(list(counts.values()))} \
            if counts else {}
        return self._head(params, h), _advanced(cache, new, advance), \
            counters

    def extend_paged(self, params, state, cache, table, ids, active,
                     advance=None):
        return self._extend_paged(params, state, cache, table, ids,
                                  active, advance)[:2]

    def decode_step_paged(self, params, state, cache, table, ids_t,
                          active):
        """One paged decode step -> ``(logits (N, V), cache,
        counters)``: the counters ride out of the tick with its tokens."""
        logits, cache, counters = self._extend_paged(
            params, state, cache, table, ids_t[:, None], active)
        return logits[:, 0], cache, counters
