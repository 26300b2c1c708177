"""Latent-attention (MLA) decoder with routed experts: the block of the
DeepSeek-V3 family, built like :class:`~bigdl_tpu.nn.attention.
Transformer` and served by the same ``serving.DecodeEngine`` contract
(``init_cache``, ``prefill``, ``extend``, ``init_paged_cache``,
``extend_paged``, ``decode_step_paged``).

Block ``l``: ``x <- x + MLA(RMSNorm(x))``, ``x <- x + FFN_l(RMSNorm(x))``
with a gated (SiLU) feed-forward, dense for the first
``first_k_dense_replace`` layers and routed (nn/routed.py) after; a
final RMSNorm and an untied head.

Latent attention keeps per token and layer one row ``[ckv ; k_pe]``
(``kv_lora_rank + qk_rope_head_dim`` = 576 numbers: the normed
compressed K/V and the rotated positional key all heads share), stored
in whole 128-lane tiles - that row is the layer's declared decode
state, ``{"latent": (1, 640)}``.  Two paths
compute the same attention from it:

* **expanded** (prefill, long chunks): K and V of every head are
  expanded from the latent rows (``ckv Wkvb``) and attention runs at
  head width ``nope + rope`` (192) - the flash kernel on a fresh row,
  blocks of the cached extent with a running softmax on a chunk;
* **absorbed** (decode, short appends): ``Wkvb``'s key half is folded
  into the query (``q_lat = q_nope Wk``, 128 -> 512 a head), scores are
  ``[q_lat ; q_pe] . [ckv ; k_pe]`` straight against the stored rows,
  the values are the ``ckv`` themselves and ``Wkvb``'s value half is
  applied to the 512-wide result.  Nothing is expanded, so a tick reads
  576 numbers a token and layer whatever the head count.

The path is chosen on the query length (``ABSORB_MAX_QUERY``), the
kernels on shape, dtype and backend.  Rotary positions are YaRN-scaled,
applied to interleaved pairs ``(2i, 2i+1)`` and stored de-interleaved
(rotated pair ``i`` at ``i`` and ``i + rope/2``), as in the published
code; products run in the weights' dtype with f32 accumulation, norms,
rotary, softmax and router scores in f32.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.init import RandomNormal
from bigdl_tpu.nn.module import Module
from bigdl_tpu.nn.routed import GatedFeedForward, RoutedExperts
from bigdl_tpu.ops import paged_kv
from bigdl_tpu.ops.attention import dot_product_attention

# queries longer than this expand K/V; shorter ones absorb (the two
# cost the same near T = 512*320 / 1088 ~ 150 query tokens)
ABSORB_MAX_QUERY = 128
_NEG = -1e30


# ------------------------------------------------------------------ rotary
def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, base: float, scaling: Optional[dict]):
    """Rotary frequencies ``(dim/2,)`` float64; with ``scaling`` (the
    config's ``rope_scaling``, ``rope_type`` yarn) high frequencies are
    kept, low ones divided by ``factor``, with a linear ramp between
    the dimensions that turn ``beta_fast`` and ``beta_slow`` times over
    the original context."""
    i = np.arange(0, dim, 2, dtype=np.float64)
    plain = 1.0 / base ** (i / dim)
    if not scaling:
        return plain
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def turns_at(beta):
        return dim * math.log(orig / (beta * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(turns_at(scaling["beta_fast"])), 0)
    high = min(math.ceil(turns_at(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp
    return plain / factor * (1.0 - keep) + plain * keep


def rotate(x, positions, inv_freq, factor: float = 1.0):
    """Rotary embedding of ``x`` (..., T, [H,] R) at integer
    ``positions`` (..., T): pairs ``(2i, 2i+1)`` turned by
    ``pos * inv_freq[i]`` in f32, the rotated pair stored at ``i`` and
    ``i + R/2``."""
    angle = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv_freq, jnp.float32)
    if x.ndim == angle.ndim + 1:           # a head axis before R
        angle = angle[..., None, :]
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    xf = x.astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin,
                            even * sin + odd * cos], -1).astype(x.dtype)


def rms_norm(x, weight, eps: float):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)
            * weight.astype(jnp.float32)).astype(x.dtype)


def _block_of(extent: int, cap: int = 1024) -> int:
    """Largest divisor of ``extent`` that is at most ``cap``."""
    return next(b for b in range(min(cap, extent), 0, -1)
                if extent % b == 0)


# --------------------------------------------------------------- attention
class LatentAttention(Module):
    """Multi-head latent attention (no bias)."""

    def __init__(self, hidden_size: int, num_heads: int, q_lora_rank: int,
                 kv_lora_rank: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int,
                 rope_theta: float = 10000.0,
                 rope_scaling: Optional[dict] = None,
                 rms_norm_eps: float = 1e-6, name: Optional[str] = None):
        super().__init__(name)
        self.hidden_size, self.num_heads = hidden_size, num_heads
        self.q_rank, self.kv_rank = q_lora_rank, kv_lora_rank
        self.nope, self.rope, self.vdim = (qk_nope_head_dim,
                                           qk_rope_head_dim, v_head_dim)
        self.eps = rms_norm_eps
        self.inv_freq = yarn_inv_freq(qk_rope_head_dim, rope_theta,
                                      rope_scaling)
        self.scale = (self.nope + self.rope) ** -0.5
        self.rope_factor = 1.0
        if rope_scaling:
            f = rope_scaling["factor"]
            all_dim = rope_scaling.get("mscale_all_dim", 0)
            if all_dim:
                self.scale *= yarn_mscale(f, all_dim) ** 2
            self.rope_factor = yarn_mscale(
                f, rope_scaling.get("mscale", 1)) / yarn_mscale(f, all_dim)

    @property
    def latent_width(self) -> int:
        return self.kv_rank + self.rope

    @property
    def row_width(self) -> int:
        """Lanes a kept row takes: ``latent_width`` rounded up to whole
        128-lane tiles, the rest zero.  The TPU lays a 576-wide row out
        in five tiles whatever the shape says, and a page can only be
        fetched in whole tiles, so the padding is spelled out and costs
        no memory the layout had not already taken."""
        return -(-self.latent_width // 128) * 128

    def init_params(self, rng, dtype=jnp.float32):
        ks = jax.random.split(rng, 5)
        init = RandomNormal(0.0, 0.02)
        d, h = self.hidden_size, self.num_heads
        return {
            "wq_a": init(ks[0], (d, self.q_rank), dtype),
            "q_norm": jnp.ones((self.q_rank,), dtype),
            "wq_b": init(ks[1], (self.q_rank,
                                 h * (self.nope + self.rope)), dtype),
            "wkv_a": init(ks[2], (d, self.latent_width), dtype),
            "kv_norm": jnp.ones((self.kv_rank,), dtype),
            "wkv_b": init(ks[3], (self.kv_rank,
                                  h * (self.nope + self.vdim)), dtype),
            "wo": init(ks[4], (h * self.vdim, d), dtype),
        }

    # --------------------------------------------------- the two halves
    def queries(self, params, x, positions):
        """``x`` (N, T, d) -> ``q_nope`` (N, T, H, nope), rotated
        ``q_pe`` (N, T, H, rope)."""
        with jax.named_scope("mla/q"):
            n, t, _ = x.shape
            cq = rms_norm(x @ params["wq_a"].astype(x.dtype),
                          params["q_norm"], self.eps)
            q = (cq @ params["wq_b"].astype(x.dtype)).reshape(
                n, t, self.num_heads, self.nope + self.rope)
            q_pe = rotate(q[..., self.nope:], positions, self.inv_freq,
                          self.rope_factor)
            return q[..., :self.nope], q_pe

    def latent(self, params, x, positions):
        """``x`` (N, T, d) -> the rows to keep, (N, T, row_width):
        normed ``ckv``, the rotated shared ``k_pe``, zeros to whole
        lanes."""
        with jax.named_scope("mla/latent"):
            kv = x @ params["wkv_a"].astype(x.dtype)
            ckv = rms_norm(kv[..., :self.kv_rank], params["kv_norm"],
                           self.eps)
            k_pe = rotate(kv[..., self.kv_rank:], positions,
                          self.inv_freq, self.rope_factor)
            pad = jnp.zeros(kv.shape[:-1] + (self.row_width
                                             - self.latent_width,),
                            kv.dtype)
            return jnp.concatenate([ckv, k_pe, pad], -1)

    def _k_pe(self, rows):
        return rows[..., self.kv_rank:self.latent_width]

    def _wkv_b(self, params, dtype):
        w = params["wkv_b"].astype(dtype).reshape(
            self.kv_rank, self.num_heads, self.nope + self.vdim)
        return w[..., :self.nope], w[..., self.nope:]

    def expand(self, params, rows):
        """Latent rows (N, S, C) -> ``k_nope`` (N, S, H, nope), ``v``
        (N, S, H, vdim)."""
        n, s, _ = rows.shape
        kv = (rows[..., :self.kv_rank]
              @ params["wkv_b"].astype(rows.dtype)).reshape(
            n, s, self.num_heads, self.nope + self.vdim)
        return kv[..., :self.nope], kv[..., self.nope:]

    def _project(self, params, out):
        n, t = out.shape[:2]
        out = out.reshape(n, t, self.num_heads * self.vdim)
        return out @ params["wo"].astype(out.dtype)

    # ------------------------------------------------------------ paths
    def _expanded_qkv(self, params, q_nope, q_pe, rows):
        """Whole heads for the attention core, each (N, H, T|S, width):
        queries ``[q_nope ; q_pe]``, keys ``[k_nope ; k_pe]`` with the
        shared ``k_pe`` repeated per head, values."""
        k_nope, v = self.expand(params, rows)
        k_pe = jnp.broadcast_to(self._k_pe(rows)[:, :, None],
                                k_nope.shape[:3] + (self.rope,))
        heads_first = lambda *parts: jnp.concatenate(parts, -1).transpose(
            0, 2, 1, 3)
        return (heads_first(q_nope, q_pe), heads_first(k_nope, k_pe),
                heads_first(v))

    def attend_fresh(self, params, q_nope, q_pe, rows):
        """Expanded, causal over the same T tokens (a fresh row)."""
        with jax.named_scope("mla_prefill_attention"):
            q, k, v = self._expanded_qkv(params, q_nope, q_pe, rows)
            out = dot_product_attention(q, k, v, causal=True,
                                        scale=self.scale)
            return self._project(params, out.transpose(0, 2, 1, 3))

    def attend_expanded(self, params, q_nope, q_pe, rows, q_pos):
        """Expanded over the cached extent ``rows`` (N, L, C) for
        queries at absolute ``q_pos`` (N, T), consecutive from
        ``q_pos[:, 0]``.  On the TPU, where the shapes tile, the whole
        extent is expanded and the ``flash_prefix`` kernel skips what no
        query sees; elsewhere blocks of the extent are expanded and
        attended under a running softmax, and only the blocks some
        query can see are visited."""
        from bigdl_tpu.ops.pallas import report
        from bigdl_tpu.ops.pallas.flash_attention import (
            prefix_blocks, prefix_flash_attention)

        with jax.named_scope("mla_prefill_attention"):
            n, t, h, _ = q_nope.shape
            extent = rows.shape[1]
            blocks = prefix_blocks(t, extent)
            on_tpu = report.force_pallas() or jax.default_backend() == "tpu"
            if blocks and on_tpu and self.vdim == self.nope + self.rope:
                report.record("prefix_flash_attention", "pallas")
                out = prefix_flash_attention(
                    *self._expanded_qkv(params, q_nope, q_pe, rows),
                    q_pos[:, 0], sm_scale=self.scale, blocks=blocks)
                return self._project(params, out.transpose(0, 2, 1, 3))
            if on_tpu:
                report.record("prefix_flash_attention", "xla",
                              (n, h, t, extent, self.nope + self.rope))
            block = _block_of(extent)
            needed = jnp.minimum((jnp.max(q_pos) + block) // block,
                                 extent // block)

            def body(j, carry):
                m, l, acc = carry
                blk = jax.lax.dynamic_slice_in_dim(rows, j * block, block,
                                                   axis=1)
                k_nope, v = self.expand(params, blk)
                s = jnp.einsum("nthd,nbhd->nhtb", q_nope, k_nope,
                               preferred_element_type=jnp.float32)
                s += jnp.einsum("nthr,nbr->nhtb", q_pe, self._k_pe(blk),
                                preferred_element_type=jnp.float32)
                k_pos = j * block + jnp.arange(block)
                seen = k_pos[None, None, None, :] <= q_pos[:, None, :, None]
                s = jnp.where(seen, s * self.scale, _NEG)
                m_new = jnp.maximum(m, s.max(-1))
                p = jnp.exp(s - m_new[..., None])
                alpha = jnp.exp(m - m_new)
                acc = acc * alpha[..., None] + jnp.einsum(
                    "nhtb,nbhv->nhtv", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
                return m_new, l * alpha + p.sum(-1), acc

            m, l, acc = jax.lax.fori_loop(0, needed, body, (
                jnp.full((n, h, t), _NEG, jnp.float32),
                jnp.zeros((n, h, t), jnp.float32),
                jnp.zeros((n, h, t, self.vdim), jnp.float32)))
            out = (acc / l[..., None]).astype(q_nope.dtype)
            return self._project(params, out.transpose(0, 2, 1, 3))

    def absorb_queries(self, params, q_nope, q_pe):
        """``[q_nope Wk ; q_pe ; 0]`` (N, T, H, row_width): the query in
        the stored rows' own space."""
        wk, _ = self._wkv_b(params, q_nope.dtype)
        q_lat = jnp.einsum("nthd,chd->nthc", q_nope, wk)
        pad = jnp.zeros(q_pe.shape[:-1] + (self.row_width
                                           - self.latent_width,),
                        q_pe.dtype)
        return jnp.concatenate([q_lat, q_pe, pad], -1)

    def unabsorb(self, params, o_lat):
        """(N, T, H, kv_rank) -> the block's output (N, T, d)."""
        _, wv = self._wkv_b(params, o_lat.dtype)
        return self._project(params, jnp.einsum("nthc,chv->nthv", o_lat,
                                                wv))

    def attend_absorbed(self, params, q_nope, q_pe, rows, q_pos):
        """Absorbed over the stored rows (N, L, C) themselves."""
        q = self.absorb_queries(params, q_nope, q_pe)
        with jax.named_scope("mla_attention"):
            s = jnp.einsum("nthc,nlc->nhtl", q, rows,
                           preferred_element_type=jnp.float32)
            seen = (jnp.arange(rows.shape[1])[None, None, None, :]
                    <= q_pos[:, None, :, None])
            p = jax.nn.softmax(jnp.where(seen, s * self.scale, _NEG), -1)
            o_lat = jnp.einsum("nhtl,nlc->nthc", p.astype(rows.dtype),
                               rows[..., :self.kv_rank])
        return self.unabsorb(params, o_lat)

    def attend_cached(self, params, q_nope, q_pe, rows, q_pos):
        if q_nope.shape[1] <= ABSORB_MAX_QUERY:
            return self.attend_absorbed(params, q_nope, q_pe, rows, q_pos)
        return self.attend_expanded(params, q_nope, q_pe, rows, q_pos)

    # ------------------------------------------------------ entry points
    def apply(self, params, state, x, training=False, rng=None):
        pos = jnp.arange(x.shape[1])[None, :]
        q_nope, q_pe = self.queries(params, x, pos)
        rows = self.latent(params, x, pos)
        return self.attend_fresh(params, q_nope, q_pe, rows), state

    def decode_state(self) -> dict:
        """One row of ``kv_rank + rope`` numbers a token (in whole
        lanes), shared by all heads: ops/paged_kv.py allocates and
        writes by this."""
        return {"latent": (1, self.row_width)}

    def apply_prefill(self, params, x, cache):
        """A fresh row's prompt: keep its latent rows at ``[0, T)`` and
        attend over the prompt itself (expanded, causal)."""
        pos = jnp.arange(x.shape[1])[None, :]
        q_nope, q_pe = self.queries(params, x, pos)
        rows = self.latent(params, x, pos)
        kept = jax.lax.dynamic_update_slice_in_dim(
            cache["latent"], rows[:, None].astype(cache["latent"].dtype),
            0, axis=2)
        out = self.attend_fresh(params, q_nope, q_pe, rows)
        return out, dict(cache, latent=kept,
                         length=cache["length"] + x.shape[1])

    def apply_cached(self, params, x, cache):
        """Append ``x`` (N, T, d) at each row's ``length`` of the dense
        cache and attend under the causal-by-length mask.  The caller
        keeps ``length + T`` within the extent."""
        t = x.shape[1]
        pos = cache["length"][:, None] + jnp.arange(t)[None]
        q_nope, q_pe = self.queries(params, x, pos)
        rows = self.latent(params, x, pos)
        kept = jax.vmap(lambda c, r, at: jax.lax.dynamic_update_slice(
            c, r[None], (0, at, 0)))(
            cache["latent"], rows.astype(cache["latent"].dtype),
            cache["length"])
        out = self.attend_cached(params, q_nope, q_pe,
                                 kept[:, 0].astype(x.dtype), pos)
        return out, dict(cache, latent=kept, length=cache["length"] + t)

    def apply_paged(self, params, x, cache, table, active):
        """``apply_cached`` over the paged pool: one token a slot on a
        bf16/f32 pool on the TPU reads only the pages held, in place
        (ops/pallas/latent_attention.py); everything else gathers the
        slot's extent and takes the dense paths."""
        from bigdl_tpu.ops.pallas import latent_attention

        t = x.shape[1]
        page = cache["latent"].shape[1]
        extent = table.shape[1] * page
        length = cache["length"]
        pos = length[:, None] + jnp.arange(t)[None]
        q_nope, q_pe = self.queries(params, x, pos)
        rows = self.latent(params, x, pos)
        cache = paged_kv.paged_append(cache, table, active,
                                      {"latent": rows[:, None]}, page,
                                      extent)
        new_cache = dict(cache, length=length + t)
        if latent_attention.routes(x.shape, cache["latent"], table):
            q = self.absorb_queries(params, q_nope, q_pe)
            with jax.named_scope("mla_attention"):
                o_lat = latent_attention.latent_paged_attn(
                    q[:, 0], cache["latent"], table,
                    jnp.where(active, length + 1, 0),
                    value_width=self.kv_rank, sm_scale=self.scale)
            return self.unabsorb(params, o_lat[:, None]), new_cache
        with jax.named_scope("paged_gather"):
            held = paged_kv.gather_pages(cache["latent"], table, page)
        out = self.attend_cached(params, q_nope, q_pe,
                                 held.astype(x.dtype), pos)
        return out, new_cache


# ------------------------------------------------------------------- block
class LatentBlock(Module):
    """Pre-RMSNorm block: latent attention, then a dense or a routed
    gated feed-forward."""

    def __init__(self, attention: LatentAttention, ffn: Module,
                 rms_norm_eps: float = 1e-6, name: Optional[str] = None):
        super().__init__(name)
        self.mla, self.ffn, self.eps = attention, ffn, rms_norm_eps

    def init_params(self, rng, dtype=jnp.float32):
        ka, kf = jax.random.split(rng)
        d = self.mla.hidden_size
        return {"ln1": {"weight": jnp.ones((d,), dtype)},
                "mla": self.mla.init_params(ka, dtype),
                "ln2": {"weight": jnp.ones((d,), dtype)},
                "ffn": self.ffn.init_params(kf, dtype)}

    def run(self, params, x, attend, rows=None):
        """``attend(h) -> (a, aux)`` is the attention path; ``rows``
        (N, T) bool marks the tokens that are no padding (the routed
        experts skip the rest).  -> ``(x, aux, expert counts or None)``."""
        with jax.named_scope("attention"):
            a, aux = attend(rms_norm(x, params["ln1"]["weight"], self.eps))
            x = x + a
        with jax.named_scope("ffn"):
            h = rms_norm(x, params["ln2"]["weight"], self.eps)
            if isinstance(self.ffn, RoutedExperts):
                f, counts = self.ffn.apply_counted(params["ffn"], h,
                                                   rows=rows)
            else:
                f, counts = self.ffn.apply(params["ffn"], {}, h)[0], None
            return x + f, aux, counts

    def apply(self, params, state, x, training=False, rng=None):
        out, _, _ = self.run(
            params, x, lambda h: self.mla.apply(params["mla"], {}, h))
        return out, state


def _advanced(old, new, advance):
    """``new`` with each row's length moved on from ``old``'s by
    ``advance`` (N,) and not by the padded T (no ``advance``: as is)."""
    if advance is None:
        return new
    return {lk: dict(c, length=old[lk]["length"] + advance.astype(jnp.int32))
            for lk, c in new.items()}


# ------------------------------------------------------------------- model
class LatentMoETransformer(Module):
    """The decoder: embedding, ``num_hidden_layers`` blocks (the first
    ``first_k_dense_replace`` dense, the rest routed over
    ``experts_held``), final RMSNorm, untied head; with
    ``num_nextn_predict_layers`` one multi-token-prediction module
    (:meth:`apply_with_mtp`), which the cached paths neither hold nor
    run.  Keyword names follow the published ``config.json``."""

    def __init__(self, vocab_size: int, hidden_size: int,
                 intermediate_size: int, moe_intermediate_size: int,
                 num_hidden_layers: int, first_k_dense_replace: int,
                 num_attention_heads: int, q_lora_rank: int,
                 kv_lora_rank: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int,
                 n_routed_experts: int, num_experts_per_tok: int,
                 n_group: int = 1, topk_group: int = 1,
                 routed_scaling_factor: float = 1.0,
                 norm_topk_prob: bool = True, n_shared_experts: int = 1,
                 rms_norm_eps: float = 1e-6, rope_theta: float = 10000.0,
                 rope_scaling: Optional[dict] = None,
                 num_nextn_predict_layers: int = 0,
                 experts_held: Optional[Sequence[int]] = None,
                 name: Optional[str] = None):
        super().__init__(name)
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        self.eps = rms_norm_eps

        def attention():
            return LatentAttention(
                hidden_size, num_attention_heads, q_lora_rank,
                kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                v_head_dim, rope_theta, rope_scaling, rms_norm_eps)

        def routed():
            return RoutedExperts(
                hidden_size, moe_intermediate_size, n_routed_experts,
                num_experts_per_tok, n_group, topk_group,
                routed_scaling_factor, norm_topk_prob, n_shared_experts,
                experts_held)

        self.layers = [
            LatentBlock(attention(),
                        GatedFeedForward(hidden_size, intermediate_size)
                        if i < first_k_dense_replace else routed(),
                        rms_norm_eps)
            for i in range(num_hidden_layers)]
        self.mtp_block = LatentBlock(attention(), routed(), rms_norm_eps) \
            if num_nextn_predict_layers else None

    def _layer_keys(self):
        return [f"layer{i}" for i in range(len(self.layers))]

    def init_params(self, rng, dtype=jnp.float32):
        ks = jax.random.split(rng, len(self.layers) + 4)
        init = RandomNormal(0.0, 0.02)
        d = self.hidden_size
        p = {"embed": {"weight": init(ks[0], (self.vocab_size, d), dtype)},
             "ln_f": {"weight": jnp.ones((d,), dtype)},
             "head": {"weight": init(ks[1], (d, self.vocab_size), dtype)}}
        for lk, layer, k in zip(self._layer_keys(), self.layers, ks[4:]):
            p[lk] = layer.init_params(k, dtype)
        if self.mtp_block is not None:
            p["mtp"] = {"hnorm": {"weight": jnp.ones((d,), dtype)},
                        "enorm": {"weight": jnp.ones((d,), dtype)},
                        "proj": init(ks[2], (2 * d, d), dtype),
                        "block": self.mtp_block.init_params(ks[3], dtype),
                        "ln_f": {"weight": jnp.ones((d,), dtype)}}
        return p

    # ---------------------------------------------------------- pieces
    def _embed(self, params, ids):
        with jax.named_scope("embed"):
            return jnp.take(params["embed"]["weight"],
                            ids.astype(jnp.int32), axis=0)

    def _head(self, params, h, norm=None):
        """Final norm (the model's own unless ``norm`` gives another
        weight) and the vocabulary product, logits in f32 (bf16 logits
        tie at the top and the arg-max would pick by index)."""
        with jax.named_scope("head"):
            h = rms_norm(h, params["ln_f"]["weight"] if norm is None
                         else norm, self.eps)
            return jnp.dot(h, params["head"]["weight"].astype(h.dtype),
                           preferred_element_type=jnp.float32)

    def _run(self, params, h, attend_of, rows=None):
        """Every block over ``h``; ``attend_of(lk, layer)`` gives the
        block's attention path.  -> ``(h, {lk: aux}, {lk: counts})``."""
        aux, counts = {}, {}
        for lk, layer in zip(self._layer_keys(), self.layers):
            h, aux[lk], c = layer.run(params[lk], h,
                                          attend_of(lk, layer), rows)
            if c is not None:
                counts[lk] = c
        return h, aux, counts

    def hidden(self, params, ids):
        """Uncached causal forward: the last block's output (N, T, d)."""
        h, _, _ = self._run(
            params, self._embed(params, ids),
            lambda lk, layer: lambda x: layer.mla.apply(
                params[lk]["mla"], {}, x))
        return h

    def apply(self, params, state, ids, training=False, rng=None):
        return self._head(params, self.hidden(params, ids)), state

    def apply_with_mtp(self, params, state, ids):
        """-> ``(logits (N, T, V), next-next-token logits (N, T-1, V))``:
        position ``i`` of the second predicts token ``i + 2`` from
        ``[RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))] Wm`` through one routed
        block, its own final norm and the shared embedding and head."""
        if self.mtp_block is None:
            raise ValueError("built without num_nextn_predict_layers")
        h = self.hidden(params, ids)
        m = params["mtp"]
        with jax.named_scope("mtp"):
            both = jnp.concatenate(
                [rms_norm(h[:, :-1], m["hnorm"]["weight"], self.eps),
                 rms_norm(self._embed(params, ids[:, 1:]),
                          m["enorm"]["weight"], self.eps)], -1)
            x, _ = self.mtp_block.apply(m["block"], {},
                                        both @ m["proj"].astype(h.dtype))
            return self._head(params, h), \
                self._head(params, x, m["ln_f"]["weight"])

    # ---------------------------------------------- the engine's contract
    def decode_state(self) -> dict:
        return {lk: layer.mla.decode_state()
                for lk, layer in zip(self._layer_keys(), self.layers)}

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        return {lk: paged_kv.init_cache(leaves, batch, max_len, dtype)
                for lk, leaves in self.decode_state().items()}

    def init_paged_cache(self, num_pages: int, page_size: int, batch: int,
                         dtype=jnp.float32, kv_dtype=None):
        if kv_dtype is not None:
            raise ValueError("the latent pool has no quantized form")
        return {lk: paged_kv.init_pool(num_pages, page_size, leaves,
                                       batch, dtype)
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
            lambda lk, layer: lambda x: layer.mla.apply_prefill(
                params[lk]["mla"], x, cache[lk]),
            rows=jnp.arange(t)[None, :] < lengths[:, None])
        cache = {lk: dict(c, length=lengths) for lk, c in new.items()}
        last = jnp.take_along_axis(h, (lengths - 1)[:, None, None], axis=1)
        return self._head(params, last)[:, 0], cache

    def extend(self, params, state, cache, ids, advance=None, rows=None):
        """Append ``ids`` (N, T) at each row's current length; logits
        of the appended positions ``rows`` (N, R) (default every one:
        the head runs only over what is asked for).  ``advance`` (N,) is
        how many of the T are real (the rest pad the last chunk)."""
        h, new, _ = self._run(
            params, self._embed(params, ids),
            lambda lk, layer: lambda x: layer.mla.apply_cached(
                params[lk]["mla"], x, cache[lk]),
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
        counters)``; inactive rows write to the trash page and send no
        token to an expert.  ``counters["expert_tokens"]`` is the
        tokens each held expert got, (routed layers, E)."""
        rows = jnp.broadcast_to(active[:, None], ids.shape)
        if advance is not None:
            rows &= jnp.arange(ids.shape[1])[None, :] < advance[:, None]
        h, new, counts = self._run(
            params, self._embed(params, ids),
            lambda lk, layer: lambda x: layer.mla.apply_paged(
                params[lk]["mla"], x, cache[lk], table, active),
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
        counters)``: the counters ride out of the tick with its tokens
        (serving/decode_programs.build_paged_tick)."""
        logits, cache, counters = self._extend_paged(
            params, state, cache, table, ids_t[:, None], active)
        return logits[:, 0], cache, counters
