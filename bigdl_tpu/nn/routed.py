"""Routed feed-forward experts, dropless, told which experts live here.

The layer of the DeepSeek-V3 family (``scoring_func`` sigmoid,
``topk_method`` noaux_tc): every token scores all ``n_routed`` experts,
a learned per-expert correction ``bias`` enters the *choice* only, the
choice is limited to the ``topk_group`` best of ``n_group`` groups, the
``k`` chosen scores are renormalised and scaled, and one shared expert
sees every token.  There is no capacity: a token is never dropped, and
a row's output depends on no other row of its batch.

Expert parallelism is in the constructor: ``experts_held`` names the
experts whose weights this rank holds.  The router keeps its published
width, and the layer computes the part of the sum that its own experts
give; what the absent experts would add is another rank's part (the
exchange that sums the parts lives with the mesh, ROADMAP R-M2).  The
shared expert and the router are replicated, so every rank computes
them alike and a caller that sums ranks counts them once
(``include_shared``).

Static shapes: the ``T*k`` token-expert assignments are sorted by the
local expert they name (assignments to absent experts last), the rows
that landed here gathered in that order, the three expert products are
``jax.lax.ragged_dot`` over the groups (on the TPU XLA's grouped
matmul kernel), and each row's result is added, weighted, into its
token in f32.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.init import RandomNormal
from bigdl_tpu.nn.module import Module


# a batch of more than SHORT_BATCH_ROWS assignments first tries a buffer
# of this many rows a token (see RoutedExperts.routed_part)
LIKELY_ROWS_PER_TOKEN = 2
SHORT_BATCH_ROWS = 512


def gated_ffn(x, p):
    """``(silu(x Wg) * (x Wu)) Wd`` with the weights' own dtype for the
    products (f32 accumulation inside each)."""
    w = p["wg"].dtype
    g = x.astype(w) @ p["wg"]
    u = x.astype(w) @ p["wu"]
    return ((jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32))
            .astype(w) @ p["wd"]).astype(x.dtype)


def _gated_init(rng, d_in: int, width: int, dtype, lead=()):
    k1, k2, k3 = jax.random.split(rng, 3)
    init = RandomNormal(0.0, 0.02)
    return {"wg": init(k1, lead + (d_in, width), dtype),
            "wu": init(k2, lead + (d_in, width), dtype),
            "wd": init(k3, lead + (width, d_in), dtype)}


class GatedFeedForward(Module):
    """Gated (SiLU) position-wise feed-forward, no bias."""

    def __init__(self, hidden_size: int, width: int,
                 name: Optional[str] = None):
        super().__init__(name)
        self.hidden_size, self.width = hidden_size, width

    def init_params(self, rng, dtype=jnp.float32):
        return _gated_init(rng, self.hidden_size, self.width, dtype)

    def apply(self, params, state, x, training=False, rng=None):
        return gated_ffn(x, params), state


class RoutedExperts(Module):
    """``y = sum_e w_e E_e(x) [+ S(x)]`` over the experts held here."""

    def __init__(self, hidden_size: int, expert_width: int,
                 n_routed: int, experts_per_token: int,
                 n_group: int = 1, topk_group: int = 1,
                 routed_scaling_factor: float = 1.0,
                 norm_topk_prob: bool = True, n_shared: int = 1,
                 experts_held: Optional[Sequence[int]] = None,
                 name: Optional[str] = None):
        super().__init__(name)
        if n_routed % n_group:
            raise ValueError(f"{n_routed} experts do not split into "
                             f"{n_group} groups")
        self.hidden_size, self.expert_width = hidden_size, expert_width
        self.n_routed, self.k = n_routed, experts_per_token
        self.n_group, self.topk_group = n_group, topk_group
        self.scaling = float(routed_scaling_factor)
        self.norm_topk_prob = norm_topk_prob
        self.n_shared = n_shared
        held = list(range(n_routed)) if experts_held is None \
            else [int(e) for e in experts_held]
        if len(set(held)) != len(held) or not all(
                0 <= e < n_routed for e in held):
            raise ValueError(f"experts_held {held} are not distinct ids "
                             f"below {n_routed}")
        self.experts_held = tuple(held)
        # global expert id -> its row in this rank's stacked weights;
        # an absent expert maps past the last row, so it sorts last
        local = np.full((n_routed,), len(held), np.int32)
        local[held] = np.arange(len(held), dtype=np.int32)
        self._local_of = local

    def init_params(self, rng, dtype=jnp.float32):
        kr, ke, ks = jax.random.split(rng, 3)
        p = {"router": {
            "weight": RandomNormal(0.0, 0.02)(
                kr, (self.hidden_size, self.n_routed), dtype),
            "bias": jnp.zeros((self.n_routed,), dtype)},
            "experts": _gated_init(ke, self.hidden_size,
                                   self.expert_width, dtype,
                                   lead=(len(self.experts_held),))}
        if self.n_shared:
            p["shared"] = _gated_init(
                ks, self.hidden_size, self.n_shared * self.expert_width,
                dtype)
        return p

    # ---------------------------------------------------------- router
    def route(self, params, x):
        """``x`` (T, d) -> ``(ids (T, k) int32, weights (T, k) f32)``:
        sigmoid scores in f32; the correction bias and the group limit
        decide *which* experts, the raw scores *how much*."""
        with jax.named_scope("moe/router"):
            r = params["router"]
            # bf16 x bf16 products are exact in f32: this is the f32
            # router of the published code on bf16 activations
            logits = jnp.dot(x, r["weight"],
                             preferred_element_type=jnp.float32)
            s = jax.nn.sigmoid(logits)
            c = s + r["bias"].astype(jnp.float32)
            t = x.shape[0]
            per = self.n_routed // self.n_group
            if self.n_group > 1:
                grouped = c.reshape(t, self.n_group, per)
                top2 = jax.lax.top_k(grouped, min(2, per))[0].sum(-1)
                kept = jax.lax.top_k(top2, self.topk_group)[1]
                keep = jnp.zeros((t, self.n_group), bool).at[
                    jnp.arange(t)[:, None], kept].set(True)
                c = jnp.where(jnp.repeat(keep, per, axis=1), c,
                              -jnp.inf)
            ids = jax.lax.top_k(c, self.k)[1].astype(jnp.int32)
            w = jnp.take_along_axis(s, ids, axis=1)
            if self.norm_topk_prob:
                w = w / (w.sum(-1, keepdims=True) + 1e-20)
            return ids, w * self.scaling

    # --------------------------------------------------------- experts
    def _grouped(self, params, x, order, w_sorted, counts, cap: int):
        """The first ``cap`` sorted assignments through their experts,
        summed into their tokens in f32: ``(T, d)``.  The caller knows
        that at most ``cap`` assignments landed here."""
        t, d = x.shape
        with jax.named_scope("moe/dispatch"):
            token = order[:cap] // self.k
            sent = jnp.take(x, token, axis=0)
        with jax.named_scope("moe/experts"):
            p = params["experts"]
            wd = p["wg"].dtype
            sent = sent.astype(wd)
            g = jax.lax.ragged_dot(sent, p["wg"], counts)
            u = jax.lax.ragged_dot(sent, p["wu"], counts)
            h = (jax.nn.silu(g.astype(jnp.float32))
                 * u.astype(jnp.float32)).astype(wd)
            out = jax.lax.ragged_dot(h, p["wd"], counts)
        with jax.named_scope("moe/combine"):
            # rows of no group hold nothing defined: their weight is 0
            weight = jnp.where(jnp.arange(cap) < counts.sum(),
                               w_sorted[:cap], 0.0)
            part = jnp.where(weight[:, None] != 0,
                             out.astype(jnp.float32) * weight[:, None], 0.0)
            # a token's parts arrive in the order of its experts' ids,
            # whatever else the batch holds
            return jnp.zeros((t, d), jnp.float32).at[token].add(part)

    def routed_part(self, params, x, ids, w, rows=None):
        """The held experts' part of the sum for ``x`` (T, d), and how
        many assignments each held expert got, ``(E,)`` int32.  Tokens
        outside ``rows`` (T,) bool (padding, idle slots) are sent to no
        expert and get zero.

        Nothing is dropped: ``T*k`` assignments can land here.  A rank
        that holds a sixteenth of the experts sees ``T*k/16``, so a
        long batch first tries a buffer of ``LIKELY_ROWS_PER_TOKEN * T``
        rows and takes the full one only when more landed (both are
        compiled; which runs is decided on the device)."""
        t, _ = x.shape
        e = len(self.experts_held)
        with jax.named_scope("moe/dispatch"):
            local = jnp.asarray(self._local_of)[ids]            # (T, k)
            if rows is not None:
                local = jnp.where(rows[:, None], local, e)
            local = local.reshape(-1)
            order = jnp.argsort(local, stable=True)
            counts = jnp.zeros((e + 1,), jnp.int32).at[local].add(1)[:e]
            w_sorted = jnp.take(w.reshape(-1), order)
        full, likely = t * self.k, LIKELY_ROWS_PER_TOKEN * t
        run = lambda cap: lambda: self._grouped(params, x, order, w_sorted,
                                                counts, cap)
        if full <= SHORT_BATCH_ROWS or likely >= full:
            y = run(full)()
        else:
            y = jax.lax.cond(counts.sum() <= likely, run(likely), run(full))
        return y.astype(x.dtype), counts

    def apply_counted(self, params, x, rows=None,
                      include_shared: bool = True):
        """``x`` (..., d) -> ``(y, tokens per held expert (E,))``;
        ``rows`` (...) bool marks the tokens that count."""
        lead = x.shape[:-1]
        flat = x.reshape(-1, x.shape[-1])
        ids, w = self.route(params, flat)
        y, counts = self.routed_part(
            params, flat, ids, w,
            None if rows is None else rows.reshape(-1))
        if include_shared and self.n_shared:
            with jax.named_scope("moe/shared"):
                y = y + gated_ffn(flat, params["shared"])
        return y.reshape(lead + (x.shape[-1],)), counts

    def apply(self, params, state, x, training=False, rng=None):
        return self.apply_counted(params, x)[0], state
