"""Plain reference of the decoder with window and full attention layers
mixed, gated grouped-query attention and routed experts that the
``trinity-mini-5of32`` configuration runs (the AFMoE family's equations,
written out in ``jax.numpy``).  No kernel, no cache, no sorting of
tokens, no block skipped: a window layer masks the keys behind its band,
and the experts are visited one after the other, each computing the
tokens that chose it (gathered into a buffer of ``ROOM`` times the mean
load; a layer in which an expert got more is computed again with every
expert over every token) and adding its weighted result to them.  It
imports nothing of the program; it is handed the benchmark's own weights
(the same bf16 tree) and upcasts one layer, and inside a routed layer
one expert, at a time.

``d`` hidden, ``H`` query heads over ``G`` K/V heads of ``D``; ``N`` is
RMSNorm (weight only, eps ``rms_norm_eps``).

Embedding: ``h = Emb[id] * sqrt(d)`` (``mup_enabled``).

Mixer of layer ``l``: ``a = N_in(h)``; ``q = a Wq`` (H x D), ``k = a
Wk``, ``v = a Wv`` (G x D), ``g = a Wg`` (H x D); ``q = N_q(q)``, ``k =
N_k(k)`` over each head's ``D``.  A window layer (``layer_types[l]`` is
``sliding_attention``): rotary on ``q`` and ``k`` over all ``D`` lanes
(lane ``i`` pairs with ``i + D/2``, turned by ``pos / theta**(2i/D)``),
scores over ``j <= i`` with ``i - j < sliding_window``.  A full layer:
no position signal, scores over all ``j <= i``.  Scale ``1/sqrt(D)``,
softmax in f32, query head ``i`` reads K/V head ``i // (H/G)``; ``o =
(attn * sigmoid(g)) Wo``; ``h = h + N_post(o)``.

Feed-forward: ``m = N_pre(h)``; dense layers (``l < num_dense_layers``)
``f = (silu(m Wg) * (m Wu)) Wd``; routed layers ``s = sigmoid(m Wr)``,
the ``num_experts_per_tok`` largest of ``s + b`` chosen, ``w = s[ids]``,
``w = w / sum(w)`` (``route_norm``), ``w = route_scale * w``, ``f =
sum_e w_e E_e(m) + Shared(m)``, each a gated unit; ``h = h +
N_post_ff(f)``.  Head: ``logits = N_f(h) Whead``, untied.

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

Q_BLOCK = 128      # query rows attended at a time
ROW_BLOCK = 2048   # rows through a feed-forward at a time
# a request is padded to one of these lengths and its served rows to a
# multiple of HEAD_ROWS: on the chip a layer's program takes 7-14 s to
# compile and under a second to run at 8192 rows (0.9-1.2 s at 24576),
# so a run compiles for two lengths and not for a length a request
PAD_TO = (8192, 32768)
HEAD_ROWS = 512
ROOM = 3           # an expert's buffer, in mean loads (T * k / experts)
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


def rotate(x, pos, theta: float):
    """``x`` (T, heads, D) at positions ``pos`` (T,): rotate-half."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    angle = pos.astype(F32)[:, None, None] * jnp.asarray(inv, F32)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# --------------------------------------------------------------- attention
def attention(x, p, cfg: dict, window, precision: str):
    """``x`` (T, d) of one sequence -> (T, d); ``window`` rows of band
    (and rotary), or None: every causal key and no position signal."""
    t = x.shape[0]
    h, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(t)
    k = rms_norm(matmul(x, p["wk"], precision).reshape(t, g, d),
                 p["k_norm"], eps)
    v = matmul(x, p["wv"], precision).reshape(t, g, d)
    if window:
        k = rotate(k, pos, float(cfg["rope_theta"]))
    kt = k.transpose(1, 2, 0)                      # (G, D, T)
    vt = v.transpose(1, 0, 2)                      # (G, T, D)
    rows = Q_BLOCK if t % Q_BLOCK == 0 else t      # blocks, so it fits

    def attend(args):
        lo, xb = args                              # xb (rows, d_model)
        at = lo + jnp.arange(rows)
        q = rms_norm(matmul(xb, p["wq"], precision).reshape(rows, h, d),
                     p["q_norm"], eps)
        if window:
            q = rotate(q, at, float(cfg["rope_theta"]))
        qg = q.reshape(rows, g, h // g, d).transpose(1, 2, 0, 3)
        s = matmul(qg.reshape(g, -1, d), kt, precision) / math.sqrt(d)
        s = s.reshape(g, h // g, rows, t)
        behind = at[:, None] - jnp.arange(t)[None, :]
        seen = behind >= 0
        if window:
            seen &= behind < window
        prob = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), -1)
        out = matmul(prob.reshape(g, -1, t), vt, precision)
        out = out.reshape(g, h // g, rows, d).transpose(2, 0, 1, 3)
        gate = jax.nn.sigmoid(matmul(xb, p["wg"], precision))
        return out.reshape(rows, h * d) * gate

    a = jax.lax.map(attend, (jnp.arange(0, t, rows),
                             x.reshape(t // rows, rows, -1)))
    return matmul(a.reshape(t, h * d), p["wo"], precision)


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
    s = jax.nn.sigmoid(matmul(x, router["weight"], precision))
    c = s + router["bias"].astype(F32)
    chosen = jnp.argsort(-c, axis=-1, stable=True)[
        :, :cfg["num_experts_per_tok"]]
    w = jnp.take_along_axis(s, chosen, axis=1)
    if cfg.get("route_norm", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return chosen, w * cfg.get("route_scale", 1.0)


def room_for(t: int, cfg: dict) -> int:
    """Rows of an expert's buffer for ``t`` tokens."""
    mean = t * cfg["num_experts_per_tok"] / cfg["num_experts"]
    return min(t, int(math.ceil(ROOM * mean)))


def routed(x, p, cfg: dict, precision: str, room=None):
    """-> ``(sum over the experts of w_e E_e(x) + Shared(x), the most
    tokens any expert got)``.  Expert ``e`` computes the first ``room``
    of the tokens that chose it (default :func:`room_for`): the result
    is the layer's only if the second number is at most ``room``, and
    ``room = T`` is every expert over every token."""
    t = x.shape[0]
    room = room_for(t, cfg) if room is None else room
    held = cfg.get("experts_held")
    held = list(range(cfg["num_experts"])) if held is None else held
    chosen, w = route(x, p["router"], cfg, precision)

    def one(y, xs):
        e, wg, wu, wd = xs
        mine = (chosen == e).any(-1)                      # (T,)
        w_e = jnp.where(chosen == e, w, 0.0).sum(-1)
        rows, = jnp.nonzero(mine, size=room, fill_value=t)
        sent = jnp.take(x, rows, axis=0, mode="fill", fill_value=0.0)
        share = jnp.take(w_e, rows, mode="fill", fill_value=0.0)
        out = share[:, None] * gated(sent, wg, wu, wd, precision)
        return y.at[rows].add(out, mode="drop"), mine.sum()

    ex = p["experts"]
    y, got = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.asarray(held, jnp.int32), ex["wg"], ex["wu"], ex["wd"]))
    if "shared" in p:
        y = y + gated(x, p["shared"]["wg"], p["shared"]["wu"],
                      p["shared"]["wd"], precision)
    return y, got.max()


def block(x, p, cfg: dict, window, precision: str, room=None):
    """-> ``(x, the most tokens an expert of this layer got)``."""
    eps = cfg["rms_norm_eps"]
    norm = lambda v, name: rms_norm(v, p[name]["weight"], eps)
    x = x + norm(attention(norm(x, "ln1"), p["attn"], cfg, window,
                           precision), "ln1_post")
    m = norm(x, "ln2")
    if "router" in p["ffn"]:
        f, most = routed(m, p["ffn"], cfg, precision, room)
    else:
        f, most = gated(m, p["ffn"]["wg"], p["ffn"]["wu"], p["ffn"]["wd"],
                        precision), 0
    return x + norm(f, "ln2_post"), most


# ------------------------------------------------------------------- model
def _key(cfg: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()))


@functools.partial(jax.jit, static_argnames=("cfg_key", "window",
                                             "precision", "room"))
def _block(x, p, cfg_key, window, precision, room=None):
    return block(x, p, dict(cfg_key), window, precision, room)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, norm_w, head_w, eps, precision):
    return matmul(rms_norm(x, norm_w, eps), head_w, precision)


def hidden(params, ids, cfg: dict, precision: str = "reference"):
    """(T,) ids of one sequence -> the last block's output (T, d), one
    layer's program at a time."""
    x = jnp.take(params["embed"]["weight"], jnp.asarray(ids), axis=0
                 ).astype(F32)
    if cfg.get("mup_enabled", True):
        x = x * math.sqrt(cfg["hidden_size"])
    for i, kind in enumerate(cfg["layer_types"]):
        window = cfg["sliding_window"] if kind == "sliding_attention" \
            else None
        args = (x, params[f"layer{i}"], _key(cfg), window, precision)
        x, most = _block(*args)
        if int(most) > room_for(x.shape[0], cfg):
            # an expert got more than its buffer holds: every expert
            # over every token
            x, _ = _block(*args, room=x.shape[0])
    return x


def logits_fn(params, ids, cfg: dict, precision: str = "reference",
              rows=slice(None)):
    """(T,) ids -> (T, V) float32 logits (of ``rows`` only)."""
    x = hidden(params, ids, cfg, precision)[rows]
    return _head(x, params["ln_f"]["weight"], params["head"]["weight"],
                 cfg["rms_norm_eps"], precision)


def padded_length(n: int, pad_to=None) -> int:
    """The least of the few lengths the reference compiles for
    (``PAD_TO``) that holds ``n`` rows (a multiple of the largest
    beyond it)."""
    pad_to = pad_to or PAD_TO
    if isinstance(pad_to, int):
        return -(-n // pad_to) * pad_to
    return next((p for p in pad_to if p >= n),
                -(-n // pad_to[-1]) * pad_to[-1])


def served_gaps(params, prompt, served, cfg: dict, pad_to=None,
                control: str = "") -> dict:
    """Teacher-forced over ``prompt + served``: at each served position
    the gap by which the served token's reference logit lies below the
    reference's best; with ``control`` also the gap of the token that
    the lower precision puts first."""
    ids = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    padded = np.zeros((padded_length(ids.size, pad_to),), np.int32)
    padded[:ids.size] = ids
    # the served rows, padded to a few counts (the last row repeated)
    count = -(-served.size // HEAD_ROWS) * HEAD_ROWS
    at = np.minimum(prompt.size - 1 + np.arange(count), ids.size - 1)
    rows = logits_fn(params, padded, cfg, "reference", at)[:served.size]
    best = jnp.max(rows, axis=-1)
    idx = jnp.arange(served.size)
    out = {"gaps": np.asarray(best - rows[idx, jnp.asarray(served)])}
    if control:
        low = logits_fn(params, padded, cfg, control, at)[:served.size]
        out["control_gaps"] = np.asarray(
            best - rows[idx, jnp.argmax(low, axis=-1)])
    return out
