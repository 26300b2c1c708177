"""Routed feed-forward experts, dropless, told which experts live here.

The layer of the DeepSeek-V3 family (``scoring_func`` sigmoid,
``topk_method`` noaux_tc): every token scores all ``n_routed`` experts,
a learned per-expert correction ``bias`` enters the *choice* only, the
choice is limited to the ``topk_group`` best of ``n_group`` groups, the
``k`` chosen scores are renormalised and scaled, and one shared expert
sees every token.  There is no capacity: a token is never dropped, and
a row's output depends on no other row of its batch.

Two forms of expert are arguments of the layer: ``activation="silu"``,
the gated unit ``Wd (silu(Wg x) * (Wu x))`` of three matrices, and
``"relu2"``, ``Wd relu(Wu x)**2`` of two (Nemotron-H).  ``latent_size``
runs the routed experts in a space of that width shared by all of them
(LatentMoE): ``W_up sum_e w_e E_e(W_down x)``, the router and the shared
expert reading ``x`` at full width.

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
that landed here gathered in that order, and the three expert products
run over the groups.  Every buffer, a tick's as a prefill chunk's,
takes the repo's grouped-matmul kernel (ops/pallas/grouped_matmul.py)
where its shape routes there: the kernel visits only the row tiles that
landed, at a row tile of the rows an expert expects,
``T * k / n_routed``, and never reads the weights of an expert that got
no row.  A shape the kernel refuses, and every buffer off the TPU,
takes ``jax.lax.ragged_dot``.  Each token then gathers its own rows
where they landed and sums them, weighted, in f32 in the order of its
experts' local ids.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.init import RandomNormal
from bigdl_tpu.nn.module import Module
from bigdl_tpu.ops.pallas import grouped_matmul


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


def relu2_ffn(x, p):
    """``relu(x Wu)**2 Wd``, the products in the weights' dtype."""
    w = p["wu"].dtype
    u = (x.astype(w) @ p["wu"]).astype(jnp.float32)
    return (jnp.square(jax.nn.relu(u)).astype(w) @ p["wd"]).astype(x.dtype)


def _gated_init(rng, d_in: int, width: int, dtype, lead=()):
    k1, k2, k3 = jax.random.split(rng, 3)
    init = RandomNormal(0.0, 0.02)
    return {"wg": init(k1, lead + (d_in, width), dtype),
            "wu": init(k2, lead + (d_in, width), dtype),
            "wd": init(k3, lead + (width, d_in), dtype)}


def _relu2_init(rng, d_in: int, width: int, dtype, lead=()):
    k1, k2 = jax.random.split(rng)
    init = RandomNormal(0.0, 0.02)
    return {"wu": init(k1, lead + (d_in, width), dtype),
            "wd": init(k2, lead + (width, d_in), dtype)}


# an expert's form: (its weights' init, the unit itself)
_FORMS = {"silu": (_gated_init, gated_ffn),
          "relu2": (_relu2_init, relu2_ffn)}


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
                 activation: str = "silu",
                 latent_size: Optional[int] = None,
                 shared_width: Optional[int] = None,
                 name: Optional[str] = None):
        super().__init__(name)
        if activation not in _FORMS:
            raise ValueError(f"activation {activation!r} is none of "
                             f"{sorted(_FORMS)}")
        if n_routed % n_group:
            raise ValueError(f"{n_routed} experts do not split into "
                             f"{n_group} groups")
        self.hidden_size, self.expert_width = hidden_size, expert_width
        self.n_routed, self.k = n_routed, experts_per_token
        self.n_group, self.topk_group = n_group, topk_group
        self.scaling = float(routed_scaling_factor)
        self.norm_topk_prob = norm_topk_prob
        self.n_shared = n_shared
        self.activation, self.latent_size = activation, latent_size
        self.shared_width = shared_width or n_shared * expert_width
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
        init = _FORMS[self.activation][0]
        d = self.hidden_size
        p = {"router": {
            "weight": RandomNormal(0.0, 0.02)(kr, (d, self.n_routed),
                                              dtype),
            "bias": jnp.zeros((self.n_routed,), dtype)},
            "experts": init(ke, self.latent_size or d, self.expert_width,
                            dtype, lead=(len(self.experts_held),))}
        if self.latent_size:
            kd, ku = jax.random.split(jax.random.fold_in(rng, 3))
            p["latent"] = {
                "down": RandomNormal(0.0, 0.02)(kd, (d, self.latent_size),
                                                dtype),
                "up": RandomNormal(0.0, 0.02)(ku, (self.latent_size, d),
                                              dtype)}
        if self.n_shared:
            p["shared"] = init(ks, d, self.shared_width, dtype)
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
    def _product(self, x, w, counts, rows: int):
        """One grouped product ``(cap, K) x (E, K, N)``: the kernel
        wherever the buffer's shape routes, at a row tile of the ``rows``
        an expert expects, ``ragged_dot`` otherwise."""
        tiling = grouped_matmul.routes(x.shape, w.shape, x.dtype, rows)
        if tiling:
            return grouped_matmul.grouped_matmul(x, w, counts, tiling=tiling)
        return jax.lax.ragged_dot(x, w, counts)

    def _grouped(self, params, x, order, slot, w_sorted, counts, cap: int):
        """The first ``cap`` sorted assignments through their experts,
        summed into their tokens in f32: ``(T, d)``.  The caller knows
        that at most ``cap`` assignments landed here; ``slot`` (T, k)
        is where each token's assignments sit in ``order``, ascending."""
        with jax.named_scope("moe/dispatch"):
            sent = jnp.take(x, order[:cap] // self.k, axis=0)
        with jax.named_scope("moe/experts"):
            p = params["experts"]
            wd = p["wd"].dtype
            sent = sent.astype(wd)
            expect = max(1, x.shape[0] * self.k // self.n_routed)
            u = self._product(sent, p["wu"], counts, expect)
            if self.activation == "silu":
                g = self._product(sent, p["wg"], counts, expect)
                h = jax.nn.silu(g.astype(jnp.float32)) \
                    * u.astype(jnp.float32)
            else:
                h = jnp.square(jax.nn.relu(u.astype(jnp.float32)))
            out = self._product(h.astype(wd), p["wd"], counts, expect)
        with jax.named_scope("moe/combine"):
            # a token reads its own rows, in the order of its experts'
            # ids, whatever else the batch holds; a row that did not land
            # is left out, never weighed by 0: the kernel leaves the rows
            # past the landed ones undefined
            mine = slot < counts.sum()
            rows = jnp.take(out, jnp.minimum(slot, cap - 1), axis=0)
            part = rows.astype(jnp.float32) \
                * jnp.take(w_sorted, slot)[..., None]
            return jnp.where(mine[..., None], part, 0.0).sum(axis=1)

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
            # the inverse of ``order``: a token's k places, ascending
            n = t * self.k
            inv = jnp.zeros((n,), jnp.int32).at[order].set(
                jnp.arange(n, dtype=jnp.int32))
            slot = jnp.sort(inv.reshape(t, self.k), axis=1)
        full, likely = t * self.k, LIKELY_ROWS_PER_TOKEN * t
        run = lambda cap: lambda: self._grouped(
            params, x, order, slot, w_sorted, counts, cap)
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
        sent = flat
        if self.latent_size:
            with jax.named_scope("moe/latent"):
                sent = flat @ params["latent"]["down"].astype(flat.dtype)
        y, counts = self.routed_part(
            params, sent, ids, w,
            None if rows is None else rows.reshape(-1))
        if self.latent_size:
            with jax.named_scope("moe/latent"):
                y = y @ params["latent"]["up"].astype(y.dtype)
        if include_shared and self.n_shared:
            with jax.named_scope("moe/shared"):
                y = y + _FORMS[self.activation][1](flat, params["shared"])
        return y.reshape(lead + (x.shape[-1],)), counts

    def apply(self, params, state, x, training=False, rng=None):
        return self.apply_counted(params, x)[0], state
