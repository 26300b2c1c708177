"""Plain reference of the transformer LM the ``opt-125m`` configuration
runs (``nn.Transformer``'s equations, written out in ``jax.numpy``):
token embedding scaled by sqrt(d), sinusoidal positions (sin half, cos
half), pre-LayerNorm blocks of causal multi-head attention (no
projection bias) and a ReLU feed-forward, a final LayerNorm, and the
embedding as the output head.  No kernel, no cache, no batching tricks.
It imports nothing of the program; it is handed the benchmark's own
weights.

``precision`` picks how every matrix product is computed:
``"reference"`` float32 at ``highest``; ``"bf16"`` and ``"fp8"`` round
both operands first (fp8: e4m3 with a per-tensor scale, straight-through
gradient) - the controls that have to come out as not correct.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.references.rounding import ROUND as _ROUND
from benchmark.references.rounding import leaf_norms, tree_map as _tm

LN_EPS = 1e-6


def matmul(a, b, precision: str):
    r = _ROUND[precision]
    out = jnp.matmul(r(a), r(b), precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    # the lower precisions keep their activations in that type too
    return out if precision == "reference" else _ROUND[precision](out)


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["weight"] + p["bias"]


def positions(t: int, d: int):
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2.0 * i / d)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)


def block(x, p, heads: int, precision: str):
    n, t, d = x.shape
    h = layer_norm(x, p["ln1"])

    def split(w):
        return matmul(h, w, precision).reshape(
            n, t, heads, d // heads).transpose(0, 2, 1, 3)

    q, k, v = split(p["mha"]["wq"]), split(p["mha"]["wk"]), \
        split(p["mha"]["wv"])
    scores = matmul(q, k.transpose(0, 1, 3, 2), precision) \
        / math.sqrt(d // heads)
    keep = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
    a = matmul(probs, v, precision).transpose(0, 2, 1, 3).reshape(n, t, d)
    x = x + matmul(a, p["mha"]["wo"], precision)
    h = layer_norm(x, p["ln2"])
    f = jax.nn.relu(matmul(h, p["ffn"]["w1"], precision) + p["ffn"]["b1"])
    return x + matmul(f, p["ffn"]["w2"], precision) + p["ffn"]["b2"]


def _stack_layers(params, layers: int):
    rows = [params[f"layer{i}"] for i in range(layers)]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *rows)


def logits_fn(params, ids, cfg: dict, precision: str = "reference"):
    """(N, T) ids -> (N, T, V) float32 logits."""
    d, heads = cfg["hidden_size"], cfg["num_heads"]
    emb = params["embed"]["weight"]
    x = jnp.take(emb, ids, axis=0) * math.sqrt(d) + positions(
        ids.shape[1], d)[None]

    @jax.checkpoint
    def body(x, p):
        return block(x, p, heads, precision), None

    x, _ = jax.lax.scan(body, x, _stack_layers(params, cfg["num_layers"]))
    x = layer_norm(x, params["ln_f"])
    return matmul(x, emb.T, precision)


def loss_fn(params, ids, targets, cfg, precision="reference"):
    """Mean over every token of the cross-entropy (the sum over this
    block of rows; the caller divides by the whole batch's tokens)."""
    logits = logits_fn(params, ids, cfg, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision",
                                             "rows"))
def _loss_and_grad(params, ids, targets, cfg_key, precision, rows):
    cfg = dict(cfg_key)
    n = ids.shape[0]
    grad = jax.value_and_grad(loss_fn)
    loss, g = 0.0, _tm(jnp.zeros_like, params)
    for lo in range(0, n, rows):  # blocks of rows, so that it fits
        l, gi = grad(params, ids[lo:lo + rows], targets[lo:lo + rows],
                     cfg, precision)
        loss, g = loss + l, _tm(jnp.add, g, gi)
    tokens = ids.size
    return loss / tokens, _tm(lambda x: x / tokens, g)


@functools.partial(jax.jit, static_argnames=("opt_key",))
def _adam_step(params, m, v, g, t, opt_key):
    o = dict(opt_key)
    if o.get("clip_norm"):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                            for x in jax.tree_util.tree_leaves(g)))
        g = _tm(lambda x: x * jnp.minimum(
            1.0, o["clip_norm"] / jnp.maximum(norm, 1e-12)), g)
    b1, b2 = o["beta1"], o["beta2"]
    m = _tm(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = _tm(lambda v, g: b2 * v + (1 - b2) * jnp.square(g), v, g)
    params = _tm(lambda p, m, v: p - o["lr"] * (m / (1 - b1 ** t)) / (
        jnp.sqrt(v / (1 - b2 ** t)) + o["eps"]), params, m, v)
    return params, m, v, g


def train_steps(params, batches, cfg: dict, opt: dict,
                precision: str = "reference", rows: int = 2,
                drop_half: bool = False) -> dict:
    """Follow the optimizer through ``batches`` (a list of (ids,
    targets)): each step's loss, the norm of every leaf of the first
    gradient as the optimizer gets it (after clipping), and the norm of
    every leaf's change after the last step.  ``drop_half`` is a fault
    for the tests: the mean over the first half of each batch only."""
    cfg_key = tuple(sorted(cfg.items()))
    opt_key = tuple(sorted((k, v) for k, v in opt.items() if k != "kind"))
    p0 = params
    m = _tm(jnp.zeros_like, params)
    v = _tm(jnp.zeros_like, params)
    losses, first = [], None
    for t, (ids, targets) in enumerate(batches, start=1):
        ids, targets = jnp.asarray(ids, jnp.int32), \
            jnp.asarray(targets, jnp.int32)
        if drop_half:
            ids, targets = ids[:ids.shape[0] // 2], \
                targets[:targets.shape[0] // 2]
        loss, g = _loss_and_grad(params, ids, targets, cfg_key, precision,
                                 min(rows, ids.shape[0]))
        params, m, v, g = _adam_step(params, m, v, g, float(t), opt_key)
        losses.append(float(loss))
        if first is None:
            first = jax.device_get(leaf_norms(g))
    change = jax.device_get(leaf_norms(_tm(jnp.subtract, params, p0)))
    return {"losses": losses, "grad1_norms": first, "change_norms": change}


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _logits(params, ids, cfg_key, precision):
    return logits_fn(params, ids, dict(cfg_key), precision)


def served_gaps(params, prompt, served, cfg: dict, pad_to: int = 512,
                control: str = "") -> dict:
    """Teacher-forced over ``prompt + served``: at each served position
    the gap by which the served token's reference logit lies below the
    reference's best; with ``control`` also the gap of the token that
    the lower precision puts first."""
    import numpy as np

    cfg_key = tuple(sorted(cfg.items()))
    ids = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    t = -(-ids.size // pad_to) * pad_to
    padded = np.zeros((1, t), np.int32)
    padded[0, :ids.size] = ids
    rows = _logits(params, jnp.asarray(padded), cfg_key, "reference")[
        0, prompt.size - 1:ids.size]
    best = jnp.max(rows, axis=-1)
    idx = jnp.arange(served.size)
    out = {"gaps": np.asarray(best - rows[idx, jnp.asarray(served)])}
    if control:
        low = _logits(params, jnp.asarray(padded), cfg_key, control)[
            0, prompt.size - 1:ids.size]
        out["control_gaps"] = np.asarray(
            best - rows[idx, jnp.argmax(low, axis=-1)])
    return out
