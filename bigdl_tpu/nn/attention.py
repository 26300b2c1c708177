"""Attention and Transformer layers.

Reference nn/Attention.scala (multi-head attention), nn/FeedForwardNetwork.scala,
nn/Transformer.scala (pre-LN encoder/decoder blocks used by the reference's
Transformer model).  TPU design: one packed QKV projection per block, f32
softmax accumulation, optional Pallas flash kernel, and head-dim layouts
chosen so tensor parallelism can shard heads (see bigdl_tpu.parallel).
"""
from __future__ import annotations

import contextlib
import math
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import Container, Module, Sequential
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.nn.norm import LayerNormalization
from bigdl_tpu.nn.dropout import Dropout
from bigdl_tpu.nn.init import Xavier
from bigdl_tpu.ops.attention import dot_product_attention


class MultiHeadAttention(Module):
    """Multi-head attention (reference nn/Attention.scala).

    Input: query (N, Tq, D) and key/value (N, Tk, D) — pass the same
    array for self-attention.  ``use_flash`` selects the Pallas kernel
    (default None = auto: fused when mask-free, XLA fallback elsewhere).
    """

    def __init__(
        self,
        hidden_size: int,
        num_heads: int,
        attn_dropout: float = 0.0,
        causal: bool = False,
        use_flash: Optional[bool] = None,
        seq_mesh=None,
        seq_mode: str = "ring",
        name: Optional[str] = None,
    ):
        super().__init__(name)
        assert hidden_size % num_heads == 0
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.head_dim = hidden_size // num_heads
        self.attn_dropout = attn_dropout
        self.causal = causal
        self.use_flash = use_flash
        # context parallelism: with a mesh whose 'seq' axis is >1, the
        # attention core runs ring (or Ulysses) attention from
        # parallel/sequence.py — K/V rotate over ICI, the (T, T) score
        # matrix never exists, sequence length scales with ring size
        if seq_mesh is not None:
            from bigdl_tpu.parallel.sequence import RingSelfAttention

            if seq_mode not in RingSelfAttention.MODES:
                raise ValueError(
                    f"unknown seq_mode {seq_mode!r}; expected one of "
                    f"{RingSelfAttention.MODES}")
        self.seq_mesh = seq_mesh
        self.seq_mode = seq_mode

    def init_params(self, rng, dtype=jnp.float32):
        ks = jax.random.split(rng, 4)
        init = Xavier()
        d = self.hidden_size
        return {
            "wq": init(ks[0], (d, d), dtype, fan_in=d, fan_out=d),
            "wk": init(ks[1], (d, d), dtype, fan_in=d, fan_out=d),
            "wv": init(ks[2], (d, d), dtype, fan_in=d, fan_out=d),
            "wo": init(ks[3], (d, d), dtype, fan_in=d, fan_out=d),
        }

    def _heads(self, x, w):
        n, t, _ = x.shape
        y = x @ w.astype(x.dtype)
        return y.reshape(n, t, self.num_heads, self.head_dim).transpose(0, 2, 1, 3)

    def apply(self, params, state, inputs, training=False, rng=None):
        if isinstance(inputs, (tuple, list)):
            query, kv = inputs[0], inputs[1]
            mask = inputs[2] if len(inputs) > 2 else None
        else:
            query = kv = inputs
            mask = None
        q = self._heads(query, params["wq"])
        k = self._heads(kv, params["wk"])
        v = self._heads(kv, params["wv"])
        seq_par = False
        if self.seq_mesh is not None:
            from bigdl_tpu.parallel.mesh import SEQ_AXIS

            if SEQ_AXIS in self.seq_mesh.shape \
                    and self.seq_mesh.shape[SEQ_AXIS] > 1:
                # ring geometry is self-attention only, and an explicit
                # mask has no blockwise decomposition here — falling
                # back silently would materialize the (T, T) scores the
                # seq mesh exists to avoid, so refuse loudly
                if query is not kv:
                    raise ValueError(
                        "seq_mesh attention supports self-attention "
                        "only (query is not the key/value input)")
                if mask is not None:
                    raise ValueError(
                        "seq_mesh attention does not take an explicit "
                        "mask (use causal=; a dense mask would defeat "
                        "the sequence sharding)")
                seq_par = True
        if seq_par:
            from bigdl_tpu.parallel.sequence import RingSelfAttention

            out = RingSelfAttention(self.seq_mesh, causal=self.causal,
                                    mode=self.seq_mode)(q, k, v)
        else:
            out = dot_product_attention(
                q, k, v, mask=mask, causal=self.causal,
                use_flash=self.use_flash
            )
        n, h, t, d = out.shape
        out = out.transpose(0, 2, 1, 3).reshape(n, t, h * d)
        out = out @ params["wo"].astype(out.dtype)
        if training and self.attn_dropout > 0.0 and rng is not None:
            keep = 1.0 - self.attn_dropout
            mask_d = jax.random.bernoulli(rng, keep, out.shape)
            out = jnp.where(mask_d, out / keep, 0.0)
        return out, state

    # ------------------------------------------------------------------
    # cached incremental decoding (docs/decoding.md)
    # ------------------------------------------------------------------
    def decode_state(self) -> dict:
        """What this layer keeps per token while decoding, as
        ``{leaf: (heads, width)}``: the cache manager (ops/paged_kv.py,
        serving/paging.py) allocates, writes and frees by it."""
        if self.seq_mesh is not None:
            raise ValueError(
                "cached decode does not compose with seq_mesh ring "
                "attention (single-token queries have no ring "
                "decomposition)")
        return {"k": (self.num_heads, self.head_dim),
                "v": (self.num_heads, self.head_dim)}

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        """Static-shape KV cache pytree for ``batch`` independent rows.

        Every leaf leads with the batch dim so the cache tiles across
        beams (SequenceBeamSearch) and packs into the serving engine's
        slot grid.  ``length`` is per-row: rows at different decode
        depths coexist in one compiled program (continuous batching).
        """
        from bigdl_tpu.ops import paged_kv

        return paged_kv.init_cache(self.decode_state(), batch, max_len,
                                   dtype)

    def apply_cached(self, params, x, cache):
        """Self-attention over the KV cache: append ``x``'s K/V at each
        row's current ``length`` and attend the query under a length
        mask.  ``x`` is (N, Tq, D) — Tq > 1 is a prefill chunk, Tq == 1
        one decode step.  All shapes static: the same compiled program
        serves every position, so steady-state decode never recompiles.
        """
        n, tq, _ = x.shape
        q = self._heads(x, params["wq"])
        k = self._heads(x, params["wk"])
        v = self._heads(x, params["wv"])
        length = cache["length"]                       # (N,)
        t_max = cache["k"].shape[2]
        # scatter-by-one-hot: dynamic_update_slice cannot take a per-row
        # start index, and a vmap'd slice would re-layout the cache; the
        # (Tq, Tmax) one-hot contraction keeps the write a single fused
        # einsum with fully static shapes.  Positions >= Tmax drop the
        # write (cache overflow is the caller's retirement condition).
        pos = length[:, None] + jnp.arange(tq)[None]   # (N, Tq)
        onehot = (pos[:, :, None] == jnp.arange(t_max)[None, None]
                  ).astype(cache["k"].dtype)           # (N, Tq, Tmax)
        keep = (1.0 - onehot.sum(axis=1))[:, None, :, None]
        new_k = cache["k"] * keep + jnp.einsum(
            "ntm,nhtd->nhmd", onehot, k.astype(cache["k"].dtype))
        new_v = cache["v"] * keep + jnp.einsum(
            "ntm,nhtd->nhmd", onehot, v.astype(cache["v"].dtype))
        # causal-by-length mask: query at absolute position p sees cache
        # slots 0..p (its own K/V included) — identical semantics to the
        # uncached causal forward restricted to the live prefix
        mask = (jnp.arange(t_max)[None, None, None, :]
                <= pos[:, None, :, None])              # (N, 1, Tq, Tmax)
        out = dot_product_attention(
            q, new_k.astype(q.dtype), new_v.astype(q.dtype), mask=mask,
            use_flash=False)
        out = out.transpose(0, 2, 1, 3).reshape(n, tq, self.hidden_size)
        out = out @ params["wo"].astype(out.dtype)
        new_cache = {"k": new_k, "v": new_v, "length": length + tq}
        return out, new_cache

    # ------------------------------------------------------------------
    # paged KV cache (docs/decoding.md §Paged KV; ops/paged_kv.py)
    # ------------------------------------------------------------------
    def init_paged_cache(self, num_pages: int, page_size: int,
                         batch: int, dtype=jnp.float32,
                         quantized: bool = False):
        """Paged pool for this layer: fixed-size pages + a host-owned
        block table instead of ``batch`` worst-case dense rows.  Page 0
        is the reserved trash page (never allocated)."""
        from bigdl_tpu.ops import paged_kv

        return paged_kv.init_pool(num_pages, page_size,
                                  self.decode_state(), batch, dtype,
                                  quantized=quantized)

    def apply_paged(self, params, x, cache, table, active):
        """``apply_cached`` over the paged pool: scatter ``x``'s K/V
        through the block table at each row's ``length`` and attend
        under the same causal-by-length mask — the math is that of the
        dense path, so dense-vs-paged is a byte-near parity oracle.
        Writes for inactive rows are redirected to the trash page;
        stray entries past ``length`` are masked (stale-above-length).

        The read side is chosen on what is seen here: one query token
        on a float pool reads only the pages held, in place, through
        the ``paged_attn`` kernel (TPU; inactive rows read zeros);
        longer queries (speculative verify, prefill chunks), the int8
        pool and every other backend gather the full logical extent
        and run the stock attention core."""
        from bigdl_tpu.ops import paged_kv
        from bigdl_tpu.ops.pallas import paged_attention

        n, tq, _ = x.shape
        k = self._heads(x, params["wk"])
        v = self._heads(x, params["wv"])
        page = cache["k"].shape[1]
        l_max = table.shape[1] * page                  # logical extent
        length = cache["length"]                       # (N,)
        cache = paged_kv.paged_append(cache, table, active,
                                      {"k": k, "v": v}, page, l_max)
        new_cache = dict(cache, length=length + tq)
        if paged_attention.routes(x.shape, cache["k"], table,
                                  self.num_heads):
            with jax.named_scope("paged_attention"):
                out = paged_attention.paged_attn(
                    x @ params["wq"].astype(x.dtype), cache["k"],
                    cache["v"], table, jnp.where(active, length + 1, 0),
                    num_heads=self.num_heads)
            return out @ params["wo"].astype(out.dtype), new_cache
        q = self._heads(x, params["wq"])
        pos = length[:, None] + jnp.arange(tq)[None]   # (N, Tq)
        mask = (jnp.arange(l_max)[None, None, None, :]
                <= pos[:, None, :, None])              # (N, 1, Tq, L)
        if paged_kv.is_quantized(cache) and paged_kv._int8_eligible(
                tq, l_max, self.head_dim):
            # TPU + 128-aligned: QK^T routes through the Pallas int8
            # dequant matmul (per-cache-position scale column); PV and
            # the f32 softmax stay XLA (per-row V scale has no
            # scale-epilogue analogue).  Everywhere else the gather
            # dequantizes and the stock attention core runs.
            k_q, k_s, v_all = paged_kv.paged_gather_q(
                cache, table, self.num_heads)
            scores = paged_kv.int8_scores(q, k_q, k_s, jnp.float32)
            scores = scores / math.sqrt(self.head_dim)
            scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("nhql,nhld->nhqd", probs,
                             v_all).astype(q.dtype)
        else:
            k_all, v_all = paged_kv.paged_gather(
                cache, table, self.num_heads, q.dtype)
            out = dot_product_attention(q, k_all, v_all, mask=mask,
                                        use_flash=False)
        out = out.transpose(0, 2, 1, 3).reshape(n, tq, self.hidden_size)
        return out @ params["wo"].astype(out.dtype), new_cache


# Reference exposes this as `Attention`
Attention = MultiHeadAttention


class FeedForwardNetwork(Module):
    """Position-wise FFN (reference nn/FeedForwardNetwork.scala):
    Linear -> activation -> dropout -> Linear."""

    def __init__(
        self,
        hidden_size: int,
        filter_size: int,
        relu_dropout: float = 0.0,
        activation=jax.nn.relu,
        name: Optional[str] = None,
    ):
        super().__init__(name)
        self.hidden_size = hidden_size
        self.filter_size = filter_size
        self.relu_dropout = relu_dropout
        self.activation = activation

    def init_params(self, rng, dtype=jnp.float32):
        k1, k2 = jax.random.split(rng)
        init = Xavier()
        return {
            "w1": init(k1, (self.hidden_size, self.filter_size), dtype,
                       fan_in=self.hidden_size, fan_out=self.filter_size),
            "b1": jnp.zeros((self.filter_size,), dtype),
            "w2": init(k2, (self.filter_size, self.hidden_size), dtype,
                       fan_in=self.filter_size, fan_out=self.hidden_size),
            "b2": jnp.zeros((self.hidden_size,), dtype),
        }

    def apply(self, params, state, x, training=False, rng=None):
        y = self.activation(x @ params["w1"].astype(x.dtype) + params["b1"].astype(x.dtype))
        if training and self.relu_dropout > 0.0 and rng is not None:
            keep = 1.0 - self.relu_dropout
            mask = jax.random.bernoulli(rng, keep, y.shape)
            y = jnp.where(mask, y / keep, 0.0)
        return y @ params["w2"].astype(x.dtype) + params["b2"].astype(x.dtype), state


class TransformerLayer(Container):
    """Pre-LN transformer encoder block (reference nn/Transformer.scala
    block assembly): x + MHA(LN(x)), then x + FFN(LN(x))."""

    def __init__(
        self,
        hidden_size: int,
        num_heads: int,
        filter_size: Optional[int] = None,
        attn_dropout: float = 0.0,
        ffn_dropout: float = 0.0,
        causal: bool = False,
        use_flash: Optional[bool] = None,
        moe_experts: int = 0,
        moe_mesh=None,
        seq_mesh=None,
        seq_mode: str = "ring",
        name: Optional[str] = None,
    ):
        super().__init__(name=name)
        filter_size = filter_size or 4 * hidden_size
        self.add(LayerNormalization(hidden_size).set_name("ln1"))
        self.add(
            MultiHeadAttention(
                hidden_size, num_heads, attn_dropout, causal, use_flash,
                seq_mesh=seq_mesh, seq_mode=seq_mode,
            ).set_name("mha")
        )
        self.add(LayerNormalization(hidden_size).set_name("ln2"))
        if moe_experts:
            # Switch-style MoE FFN: experts shard over the mesh's expert
            # axis; the router aux loss surfaces through layer state and
            # is folded into training loss by make_train_step
            from bigdl_tpu.parallel.expert import MoE

            self.add(MoE(hidden_size, filter_size, moe_experts,
                         mesh=moe_mesh).set_name("ffn"))
        else:
            self.add(
                FeedForwardNetwork(
                    hidden_size, filter_size, ffn_dropout).set_name("ffn")
            )

    def apply(self, params, state, x, training=False, rng=None):
        with jax.named_scope("attention"):
            h, s0 = self._child_apply(0, params, state, x, training=training, rng=rng)
            a, s1 = self._child_apply(1, params, state, h, training=training, rng=rng)
            x = x + a
        with jax.named_scope("ffn"):
            h, s2 = self._child_apply(2, params, state, x, training=training, rng=rng)
            f, s3 = self._child_apply(3, params, state, h, training=training, rng=rng)
            x = x + f
        return x, self._merge_state(
            state,
            {self._keys[0]: s0, self._keys[1]: s1, self._keys[2]: s2, self._keys[3]: s3},
        )

    @property
    def mha(self) -> MultiHeadAttention:
        return self._children[1]

    def apply_cached(self, params, state, x, cache):
        """Eval-mode block forward with the attention core routed
        through the KV cache.  LN and the FFN are per-position, so the
        same code serves prefill chunks and single-token decode steps."""
        lnk, mhak, ln2k, ffnk = self._keys
        with jax.named_scope("attention"):
            h, _ = self._children[0].apply(params[lnk], state[lnk], x)
            a, cache = self.mha.apply_cached(params[mhak], h, cache)
            x = x + a
        with jax.named_scope("ffn"):
            h, _ = self._children[2].apply(params[ln2k], state[ln2k], x)
            f, _ = self._children[3].apply(params[ffnk], state[ffnk], h)
            return x + f, cache

    def apply_paged(self, params, state, x, cache, table, active):
        """``apply_cached`` with the attention core routed through the
        paged pool (LN/FFN are per-position either way)."""
        lnk, mhak, ln2k, ffnk = self._keys
        with jax.named_scope("attention"):
            h, _ = self._children[0].apply(params[lnk], state[lnk], x)
            a, cache = self.mha.apply_paged(params[mhak], h, cache,
                                            table, active)
            x = x + a
        with jax.named_scope("ffn"):
            h, _ = self._children[2].apply(params[ln2k], state[ln2k], x)
            f, _ = self._children[3].apply(params[ffnk], state[ffnk], h)
            return x + f, cache


class PositionEncode(Module):
    """Sinusoidal position encoding added to (N, T, D) embeddings
    (reference nn/PositionEncode in Transformer.scala)."""

    def __init__(self, max_len: int = 4096, name: Optional[str] = None):
        super().__init__(name)
        self.max_len = max_len

    def apply(self, params, state, x, training=False, rng=None):
        t, d = x.shape[1], x.shape[2]
        pe = self.encode_at(jnp.arange(t), d, x.dtype)
        return x + pe[None], state

    @staticmethod
    def encode_at(positions, d: int, dtype):
        """PE rows for integer ``positions`` (any shape) ->
        ``positions.shape + (d,)`` — the decode path needs the encoding
        at each row's own cache length, not a [0, t) prefix."""
        pos = positions.astype(jnp.float32)[..., None]
        i = jnp.arange(d // 2)[None, :].astype(jnp.float32)
        angle = pos / jnp.power(10000.0, 2.0 * i / d)
        return jnp.concatenate(
            [jnp.sin(angle), jnp.cos(angle)], axis=-1).astype(dtype)


# device scopes of the Transformer's own children: embed (+ positions)
# and head (final LN; the tied matmul joins it); the layers scope their
# attention / ffn themselves
_TOP_SCOPES = {"embed": "embed", "pos": "embed", "drop": "embed",
               "ln_f": "head"}


class Transformer(Container):
    """Stack of transformer blocks with embedding + position encoding
    (reference nn/Transformer.scala — the encoder-only/LM configuration)."""

    def __init__(
        self,
        vocab_size: int,
        hidden_size: int,
        num_heads: int,
        filter_size: int,
        num_layers: int,
        dropout: float = 0.1,
        causal: bool = True,
        use_flash: Optional[bool] = None,
        moe_experts: int = 0,
        moe_mesh=None,
        seq_mesh=None,
        seq_mode: str = "ring",
        name: Optional[str] = None,
    ):
        super().__init__(name=name)
        from bigdl_tpu.nn.embedding import LookupTable

        self.hidden_size = hidden_size
        self.vocab_size = vocab_size
        self.causal = causal
        # N(0, 1/sqrt(d)) embeddings: with the sqrt(d) input scaling and
        # the weight-tied LM head, unit-variance init (LookupTable's
        # Torch default) makes initial logits ~sqrt(d) too large —
        # initial loss sits far above ln(vocab) and training wastes
        # epochs recovering
        from bigdl_tpu.nn.init import RandomNormal

        self.add(LookupTable(
            vocab_size, hidden_size,
            weight_init=RandomNormal(0.0, hidden_size ** -0.5),
        ).set_name("embed"))
        self.add(PositionEncode().set_name("pos"))
        self.add(Dropout(dropout).set_name("drop"))
        for i in range(num_layers):
            self.add(
                TransformerLayer(
                    hidden_size, num_heads, filter_size,
                    attn_dropout=dropout, ffn_dropout=dropout,
                    causal=causal, use_flash=use_flash,
                    moe_experts=moe_experts, moe_mesh=moe_mesh,
                    seq_mesh=seq_mesh, seq_mode=seq_mode,
                ).set_name(f"layer{i}")
            )
        self.add(LayerNormalization(hidden_size).set_name("ln_f"))

    def apply(self, params, state, x, training=False, rng=None):
        h = x
        updates = {}
        for i, k in enumerate(self._keys):
            scope = _TOP_SCOPES.get(k)
            with jax.named_scope(scope) if scope \
                    else contextlib.nullcontext():
                h, s = self._child_apply(i, params, state, h, training=training, rng=rng)
                if k == "embed":
                    h = h * math.sqrt(self.hidden_size)
            updates[k] = s
        with jax.named_scope("head"):  # weight-tied LM head
            logits = h @ params["embed"]["weight"].astype(h.dtype).T
        return logits, self._merge_state(state, updates)

    # ------------------------------------------------------------------
    # cached incremental decoding (docs/decoding.md): prefill once over
    # the prompt, then O(1) work per generated token instead of a full
    # re-forward over the growing prefix
    # ------------------------------------------------------------------
    def _layer_keys(self):
        return [k for k in self._keys if k.startswith("layer")]

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        """Per-layer ``{k, v, length}`` KV cache (leaves lead with the
        batch dim — beam-tilable and slot-packable)."""
        return {k: self._children[self._keys.index(k)].mha.init_cache(
                    batch, max_len, dtype)
                for k in self._layer_keys()}

    def _embed_positions(self, params, ids, positions):
        """Embedding + sqrt(d) scaling + positional encoding at explicit
        absolute ``positions`` — the cached twin of the apply() head."""
        with jax.named_scope("embed"):
            emb = jnp.take(params["embed"]["weight"],
                           ids.astype(jnp.int32), axis=0)
            emb = emb * math.sqrt(self.hidden_size)
            return emb + PositionEncode.encode_at(
                positions, self.hidden_size, emb.dtype)

    def _head(self, params, state, h):
        """Final LayerNorm + the weight-tied vocabulary matmul of the
        cached paths."""
        with jax.named_scope("head"):
            h, _ = self._children[self._keys.index("ln_f")].apply(
                params["ln_f"], state["ln_f"], h)
            return h @ params["embed"]["weight"].astype(h.dtype).T

    def prefill(self, params, state, ids, cache, lengths=None):
        """Run the causal forward over (padded) prompts ``ids`` (N, T),
        writing every position's K/V into ``cache`` (fresh rows assumed:
        row lengths 0).  ``lengths`` (N,) gives each row's true prompt
        length (default: the full padded T); rows may be padded past it
        — the stale cache slots beyond ``lengths`` are overwritten by
        later decode steps before a length mask can expose them.

        Returns ``(next-token logits (N, V), cache)`` with each row's
        cache length set to its true prompt length.
        """
        n, t = ids.shape
        if lengths is None:
            lengths = jnp.full((n,), t, jnp.int32)
        lengths = lengths.astype(jnp.int32)
        h = self._embed_positions(params, ids, jnp.arange(t)[None, :])
        cache = dict(cache)
        for lk in self._layer_keys():
            layer = self._children[self._keys.index(lk)]
            h, new = layer.apply_cached(params[lk], state[lk], h,
                                        cache[lk])
            cache[lk] = dict(new, length=lengths)
        logits = self._head(params, state, h)
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None].astype(jnp.int32),
            axis=1)[:, 0]
        return last, cache

    def decode_step(self, params, state, cache, ids_t):
        """One cached decode step: ``ids_t`` (N,) is the token at each
        row's current cache length.  Returns ``(logits (N, V), cache)``
        — O(cache) work per step, every shape static, so the whole
        decode is one compiled program regardless of position.
        """
        layer_keys = self._layer_keys()
        pos = cache[layer_keys[0]]["length"]           # (N,)
        h = self._embed_positions(params, ids_t[:, None], pos[:, None])
        cache = dict(cache)
        for lk in layer_keys:
            layer = self._children[self._keys.index(lk)]
            h, cache[lk] = layer.apply_cached(params[lk], state[lk], h,
                                              cache[lk])
        logits = self._head(params, state, h)
        return logits[:, 0], cache

    def extend(self, params, state, cache, ids, advance=None, rows=None):
        """Append ``ids`` (N, T) at each row's *current* cache length
        and return logits for every appended position (N, T, V), or of
        the positions ``rows`` (N, R) alone (the head then runs only
        over those) — the
        workhorse behind chunked prefill (feed a long prompt in bounded
        chunks) and the speculative verify pass (score draft tokens in
        one forward).  On a fresh cache this is exactly ``prefill``
        (positions start at 0).

        ``advance`` (N,) optionally overrides how far each row's length
        moves (default T): a padded final chunk advances only by its
        true token count, leaving the padding stale-above-length.
        """
        n, t = ids.shape
        layer_keys = self._layer_keys()
        pos0 = cache[layer_keys[0]]["length"]          # (N,)
        h = self._embed_positions(
            params, ids, pos0[:, None] + jnp.arange(t)[None, :])
        cache = dict(cache)
        for lk in layer_keys:
            layer = self._children[self._keys.index(lk)]
            h, new = layer.apply_cached(params[lk], state[lk], h,
                                        cache[lk])
            if advance is not None:
                new = dict(new, length=pos0 + advance.astype(jnp.int32))
            cache[lk] = new
        if rows is not None:
            h = jnp.take_along_axis(h, rows[:, :, None], axis=1)
        logits = self._head(params, state, h)
        return logits, cache

    # ------------------------------------------------------------------
    # paged decode (docs/decoding.md §Paged KV; serving/paging.py)
    # ------------------------------------------------------------------
    def init_paged_cache(self, num_pages: int, page_size: int,
                         batch: int, dtype=jnp.float32,
                         kv_dtype=None):
        """Per-layer paged pools sharing one block-table geometry.
        ``kv_dtype='int8'`` stores K/V quantized with per-(token, head)
        scales (~2x cache bytes; ops/paged_kv.py)."""
        quantized = kv_dtype in ("int8", jnp.int8)
        return {k: self._children[self._keys.index(k)].mha
                .init_paged_cache(num_pages, page_size, batch, dtype,
                                  quantized=quantized)
                for k in self._layer_keys()}

    def extend_paged(self, params, state, cache, table, ids, active,
                     advance=None):
        """``extend`` over the paged pools: same math, same length
        bookkeeping, with the block ``table`` (N, M) threaded to every
        layer's scatter/gather and ``active`` (N,) gating the writes."""
        n, t = ids.shape
        layer_keys = self._layer_keys()
        pos0 = cache[layer_keys[0]]["length"]
        h = self._embed_positions(
            params, ids, pos0[:, None] + jnp.arange(t)[None, :])
        cache = dict(cache)
        for lk in layer_keys:
            layer = self._children[self._keys.index(lk)]
            h, new = layer.apply_paged(params[lk], state[lk], h,
                                       cache[lk], table, active)
            if advance is not None:
                new = dict(new, length=pos0 + advance.astype(jnp.int32))
            cache[lk] = new
        logits = self._head(params, state, h)
        return logits, cache

    def decode_step_paged(self, params, state, cache, table, ids_t,
                          active):
        """One paged decode step — ``decode_step`` through the block
        table.  Returns ``(logits (N, V), cache, counters)``: what the
        model counts inside its step for a live trace (here nothing)."""
        logits, cache = self.extend_paged(params, state, cache, table,
                                          ids_t[:, None], active)
        return logits[:, 0], cache, {}

    def generate(self, params, state, initial_ids, max_decode_length,
                 beam_size: int = 4, alpha: float = 0.6,
                 eos_id: Optional[int] = None, use_cache: bool = True):
        """Beam-search decode from one start token per batch row
        (reference wires nn/SequenceBeamSearch.scala into its
        Transformer the same way).

        ``initial_ids`` (B,) int; returns ``(sequences (B, beam, T+1),
        scores (B, beam))`` best-first.  ``use_cache=True`` (default)
        threads the per-layer KV cache through the search — O(1) work
        per step per beam.  ``use_cache=False`` keeps the seed behavior
        — each step re-runs the causal forward over the decoded prefix,
        O(T^2) forwards — as the numerics parity oracle (positions
        beyond the current step cannot influence it under the causal
        mask, so both paths produce identical logits).
        """
        from bigdl_tpu.nn.beam_search import SequenceBeamSearch

        if not self.causal:
            raise ValueError(
                "generate() needs a causal Transformer: with "
                "causal=False every step would attend to the padding "
                "beyond the current position")

        if use_cache:
            initial_cache = self.init_cache(
                initial_ids.shape[0], max_decode_length,
                params["embed"]["weight"].dtype)

            def fn(ids, i, cache):
                tok = jax.lax.dynamic_index_in_dim(ids, i, axis=1,
                                                   keepdims=False)
                return self.decode_step(params, state, cache, tok)
        else:
            initial_cache = {}

            def fn(ids, i, cache):
                logits_all, _ = self.apply(params, state, ids,
                                           training=False)
                # i is a tracer under the search's scan: dynamic index
                return logits_all[:, i, :], cache

        bs = SequenceBeamSearch(
            self.vocab_size, beam_size, alpha, max_decode_length,
            eos_id=self.vocab_size - 1 if eos_id is None else eos_id,
            symbols_to_logits_fn=fn)
        return bs.search(initial_ids, initial_cache)
