"""Plain reference of the latent-attention decoder with routed experts
that the ``gigachat3.1-702b-ep16share`` configuration runs (the
DeepSeek-V3 family's equations, written out in ``jax.numpy``).  No
kernel, no cache, no absorbed form, no sorting of tokens: K and V of
every head are expanded from the latent and every held expert is
computed for every token and weighted by what the router gave it.  It
imports nothing of the program; it is handed the benchmark's own
weights (the same bf16 tree, the same held experts) and upcasts one
layer, and inside a routed layer one expert, at a time.

Block ``l``: ``x <- x + MLA(RMSNorm(x))``; ``x <- x + FFN_l(RMSNorm(x))``
(dense for ``l < first_k_dense_replace``, routed after); final RMSNorm;
untied head.  RMSNorm: weight only, eps ``rms_norm_eps``.

MLA: ``cq = RMSNorm(x Wqa)``; ``q = cq Wqb`` -> per head ``[q_nope ;
q_pe]``; ``[ckv ; k_pe] = x Wkva``; ``ckv <- RMSNorm(ckv)``; ``k_pe`` is
shared by all heads; ``[k_nope ; v]`` per head ``= ckv Wkvb``; rotary
(YaRN frequencies) on ``q_pe`` and ``k_pe``: pairs ``(2i, 2i+1)`` turned
by ``pos * inv_freq[i]``, the rotated pair stored at ``i`` and
``i + 32`` (any fixed arrangement gives the same scores as long as
queries and keys share it); ``score = (q_nope . k_nope + q_pe . k_pe) *
scale``, causal, softmax in f32; ``out = (P v) Wo``.

Routed layer: ``s = sigmoid(x Wr)``; ``c = s + b`` (for choosing only);
a group's score is the sum of its two largest ``c``; the ``topk_group``
best groups stay; of their experts the ``num_experts_per_tok`` largest
``c`` are chosen; ``w_e = s_e / (sum of the chosen s + 1e-20) *
routed_scaling_factor``; ``y = sum over the held e of w_e E_e(x) +
S(x)``.  What the absent experts would add is left out, as in the
program.

``precision`` picks how every matrix product is computed:
``"reference"`` float32 at ``highest``; ``"bf16"`` and ``"fp8"`` round
both operands first and keep their activations in that type too - the
controls that have to come out as not correct.  The router's product is
a matrix product like the others.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.rounding import ROUND as _ROUND

Q_BLOCK = 256      # query rows attended at a time
ROW_BLOCK = 1024   # rows through a feed-forward at a time
F32 = jnp.float32


def matmul(a, b, precision: str):
    r = _ROUND[precision]
    out = jnp.matmul(r(a.astype(F32)), r(b.astype(F32)),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=F32)
    return out if precision == "reference" else r(out)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w.astype(F32)


# ------------------------------------------------------------------ rotary
def mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(cfg: dict):
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    plain = [1.0 / base ** (2 * i / dim) for i in range(dim // 2)]
    sc = cfg.get("rope_scaling")
    if not sc:
        return np.asarray(plain)
    orig = sc["original_max_position_embeddings"]

    def dim_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(dim_of(sc["beta_fast"])), 0)
    high = min(math.ceil(dim_of(sc["beta_slow"])), dim - 1)
    out = []
    for i, f in enumerate(plain):
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        mask = 1.0 - ramp
        out.append(f / sc["factor"] * (1.0 - mask) + f * mask)
    return np.asarray(out)


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    sc = cfg.get("rope_scaling")
    if sc and sc.get("mscale_all_dim"):
        scale *= mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return scale


def rotate(x, pos, cfg: dict):
    """``x`` (T, ..., R) at positions ``pos`` (T,)."""
    sc = cfg.get("rope_scaling")
    factor = mscale(sc["factor"], sc.get("mscale", 1)) / mscale(
        sc["factor"], sc.get("mscale_all_dim", 0)) if sc else 1.0
    angle = pos.astype(F32)[:, None] * jnp.asarray(inv_freq(cfg), F32)
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin,
                            even * sin + odd * cos], -1)


# --------------------------------------------------------------- attention
def attention(x, p, cfg: dict, precision: str):
    """``x`` (T, d) of one sequence -> (T, d)."""
    t = x.shape[0]
    h = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    pos = jnp.arange(t)
    cq = rms_norm(matmul(x, p["wq_a"], precision), p["q_norm"], eps)
    kv = matmul(x, p["wkv_a"], precision)
    ckv = rms_norm(kv[:, :rank], p["kv_norm"], eps)
    k_pe = rotate(kv[:, rank:], pos, cfg)
    kvb = matmul(ckv, p["wkv_b"], precision).reshape(t, h, nope + vd)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_pe[:, None], (t, h, rope))], -1)
    v = kvb[..., nope:]
    kt = k.transpose(1, 2, 0)                      # (H, D, T)
    vt = v.transpose(1, 0, 2)                      # (H, T, V)
    scale = softmax_scale(cfg)
    rows = Q_BLOCK if t % Q_BLOCK == 0 else t      # blocks, so it fits

    def attend(args):
        lo, cqb = args                              # cqb (B, q_lora_rank)
        at = lo + jnp.arange(rows)
        q = matmul(cqb, p["wq_b"], precision).reshape(rows, h, nope + rope)
        q = jnp.concatenate([q[..., :nope],
                             rotate(q[..., nope:], at, cfg)], -1)
        s = matmul(q.transpose(1, 0, 2), kt, precision) * scale
        seen = jnp.arange(t)[None, :] <= at[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
        return matmul(prob, vt, precision).transpose(1, 0, 2)

    out = jax.lax.map(attend, (jnp.arange(0, t, rows),
                               cq.reshape(t // rows, rows, -1)))
    a = out.reshape(t, h * vd)
    return matmul(a, p["wo"], precision)


# ------------------------------------------------------------ feed-forward
def gated(x, wg, wu, wd, precision: str):
    def rows(xb):
        return matmul(jax.nn.silu(matmul(xb, wg, precision))
                      * matmul(xb, wu, precision), wd, precision)

    t = x.shape[0]
    if precision == "reference" and t % ROW_BLOCK == 0 and t > ROW_BLOCK:
        # blocks of rows, so that the widest layer's hidden fits (the
        # controls round by the whole tensor's scale: not in blocks)
        return jax.lax.map(rows, x.reshape(t // ROW_BLOCK, ROW_BLOCK, -1)
                           ).reshape(x.shape)
    return rows(x)


def route(x, router, cfg: dict, precision: str):
    """-> ``(chosen (T, k) expert ids, weights (T, k))``."""
    n, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    groups, kept = cfg.get("n_group", 1), cfg.get("topk_group", 1)
    s = jax.nn.sigmoid(matmul(x, router["weight"], precision))
    c = s + router["bias"].astype(F32)
    if groups > 1:
        by_group = c.reshape(-1, groups, n // groups)
        two = -jnp.sort(-by_group, axis=-1)[..., :2].sum(-1)
        best = jnp.argsort(-two, axis=-1, stable=True)[:, :kept]
        stays = (jnp.arange(groups)[None, :, None]
                 == best[:, None, :]).any(-1)       # (T, groups)
        c = jnp.where(jnp.repeat(stays, n // groups, axis=1), c, -jnp.inf)
    chosen = jnp.argsort(-c, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(s, chosen, axis=1)
    if cfg.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return chosen, w * cfg.get("routed_scaling_factor", 1.0)


def routed(x, p, cfg: dict, precision: str, shared: bool = True):
    """The held experts' part of the layer's sum (+ the shared expert)."""
    held = cfg.get("experts_held")
    held = list(range(cfg["n_routed_experts"])) if held is None else held
    chosen, w = route(x, p["router"], cfg, precision)

    def one(y, xs):
        e, wg, wu, wd = xs
        w_e = jnp.where(chosen == e, w, 0.0).sum(-1)      # (T,)
        return y + w_e[:, None] * gated(x, wg, wu, wd, precision), None

    ex = p["experts"]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.asarray(held, jnp.int32), ex["wg"], ex["wu"], ex["wd"]))
    if shared and "shared" in p:
        y = y + gated(x, p["shared"]["wg"], p["shared"]["wu"],
                      p["shared"]["wd"], precision)
    return y


def block(x, p, cfg: dict, precision: str):
    eps = cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, p["ln1"]["weight"], eps), p["mla"], cfg,
                      precision)
    h = rms_norm(x, p["ln2"]["weight"], eps)
    f = routed(h, p["ffn"], cfg, precision) if "router" in p["ffn"] \
        else gated(h, p["ffn"]["wg"], p["ffn"]["wu"], p["ffn"]["wd"],
                   precision)
    return x + f


# ------------------------------------------------------------------- model
def _key(cfg: dict):
    def freeze(v):
        if isinstance(v, dict):
            return tuple(sorted((k, freeze(x)) for k, x in v.items()))
        return tuple(v) if isinstance(v, list) else v

    return freeze(cfg)


def _thaw(key):
    out = {}
    for k, v in key:
        if k == "rope_scaling" and v is not None:
            v = dict(v)
        elif isinstance(v, tuple):
            v = list(v)
        out[k] = v
    return out


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _block(x, p, cfg_key, precision):
    return block(x, p, _thaw(cfg_key), precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, norm_w, head_w, eps, precision):
    return matmul(rms_norm(x, norm_w, eps), head_w, precision)


def hidden(params, ids, cfg: dict, precision: str = "reference"):
    """(T,) ids of one sequence -> the last block's output (T, d), one
    layer's program at a time."""
    x = jnp.take(params["embed"]["weight"], jnp.asarray(ids), axis=0
                 ).astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        x = _block(x, params[f"layer{i}"], _key(cfg), precision)
    return x


def logits_fn(params, ids, cfg: dict, precision: str = "reference",
              rows=slice(None)):
    """(T,) ids -> (T, V) float32 logits (of ``rows`` only)."""
    x = hidden(params, ids, cfg, precision)[rows]
    return _head(x, params["ln_f"]["weight"], params["head"]["weight"],
                 cfg["rms_norm_eps"], precision)


def mtp_logits(params, ids, cfg: dict, precision: str = "reference"):
    """The multi-token-prediction module: position ``i`` predicts token
    ``i + 2`` from ``[RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))] Wm``, one
    routed block, its own final norm, the shared embedding and head.
    -> (T - 1, V)."""
    m, eps = params["mtp"], cfg["rms_norm_eps"]
    h = hidden(params, ids, cfg, precision)[:-1]
    emb = jnp.take(params["embed"]["weight"], jnp.asarray(ids)[1:],
                   axis=0).astype(F32)
    both = jnp.concatenate([rms_norm(h, m["hnorm"]["weight"], eps),
                            rms_norm(emb, m["enorm"]["weight"], eps)], -1)
    x = _block(matmul(both, m["proj"], precision), m["block"], _key(cfg),
               precision)
    return _head(x, m["ln_f"]["weight"], params["head"]["weight"], eps,
                 precision)


def served_gaps(params, prompt, served, cfg: dict, pad_to: int = 2048,
                control: str = "") -> dict:
    """Teacher-forced over ``prompt + served``: at each served position
    the gap by which the served token's reference logit lies below the
    reference's best; with ``control`` also the gap of the token that
    the lower precision puts first."""
    ids = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    t = -(-ids.size // pad_to) * pad_to
    padded = np.zeros((t,), np.int32)
    padded[:ids.size] = ids
    at = slice(prompt.size - 1, ids.size)
    rows = logits_fn(params, padded, cfg, "reference", at)
    best = jnp.max(rows, axis=-1)
    idx = jnp.arange(served.size)
    out = {"gaps": np.asarray(best - rows[idx, jnp.asarray(served)])}
    if control:
        low = logits_fn(params, padded, cfg, control, at)
        out["control_gaps"] = np.asarray(
            best - rows[idx, jnp.argmax(low, axis=-1)])
    return out
