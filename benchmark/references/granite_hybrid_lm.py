"""Plain reference of the decoder of Mamba-2 and grouped-query attention
layers, each followed by a dense gated MLP, that the
``granite-4.0-h-micro`` configuration runs (the Granite 4.0-H family's
equations, written out in ``jax.numpy``).  No kernel, no cache, no
chunked scan: a Mamba-2 layer runs its recurrence one step after the
other (``references/hybrid_ssm_lm.mamba``, ``lax.scan`` over the
tokens, handed this family's keys), so the program's chunked form is
checked against the definition.  It imports nothing of the program; it
is handed the benchmark's own weights (the same bf16 tree) and upcasts
one layer at a time, so that the whole model's bf16 weights and one
layer in f32 fit on the chip side by side.

``d`` hidden; ``N`` is RMSNorm (weight only, eps ``rms_norm_eps``);
``r`` is ``residual_multiplier``.  Embedding ``h = embedding_multiplier
* E[id]``.  Layer ``l``: ``h = h + r Mixer_l(N_1(h))`` with the mixer
that ``layer_types[l]`` names, then ``h = h + r ((silu(v Wg) * (v Wu))
Wd)`` with ``v = N_2(h)``.  Head ``N_f(h) E^T / logits_scaling``: the
embedding's own matrix, transposed.

``mamba`` (``d_inner = mamba_expand d``, ``H`` heads of ``P``, ``G``
groups of ``S`` states, kernel ``K``, conv bias, no projection bias):
``[z | xBC | dt] = u W_in``; ``xBC = silu(sum_j w_j xBC_{t-K+1+j} +
b)`` (zeros before the first token); ``delta = softplus(dt +
dt_bias)``; ``S_t = exp(delta_t a) S_{t-1} + delta_t x_t B_t^T``; ``y_t =
S_t C_t + D x_t``; ``out = (N_{G groups}(y * silu(z)) * w) W_out``.

``attention``: ``q = u Wq`` (``H`` heads of ``d / H``), ``k = u Wk``,
``v = u Wv`` (``G`` heads), query head ``i`` reading K/V head ``i //
(H/G)``, causal softmax of ``q k^T * attention_multiplier`` in f32,
``out = attn Wo``; no rotary, no bias.

``precision`` picks how every matrix product is computed:
``"reference"`` float32 at ``highest``; ``"bf16"`` and ``"fp8"`` round
both operands first and keep their activations in that type too - the
controls that have to come out as not correct.  The recurrence itself
is elementwise and stays in float32 in every precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references import hybrid_ssm_lm as base
from benchmark.references.hybrid_ssm_lm import F32, matmul, rms_norm

Q_BLOCK = 128      # query rows attended at a time
# a request is padded to one of these lengths (the cell's prompts and
# answers make at most 4096 tokens) and its served rows to a multiple
# of HEAD_ROWS, so that a run compiles for two lengths and not for a
# length a request
PAD_TO = (1024, 4096)
HEAD_ROWS = 512


def mamba_keys(cfg: dict) -> dict:
    """This family's keys under the names ``hybrid_ssm_lm.mamba``
    reads."""
    return {"expand": cfg["mamba_expand"],
            "mamba_num_heads": cfg["mamba_n_heads"],
            "mamba_head_dim": cfg["mamba_d_head"],
            "n_groups": cfg["mamba_n_groups"],
            "ssm_state_size": cfg["mamba_d_state"],
            "conv_kernel": cfg["mamba_d_conv"],
            "norm_eps": cfg["rms_norm_eps"]}


def attention(x, p, cfg: dict, precision: str):
    """``x`` (T, d) of one sequence -> (T, d): every causal key."""
    t, dm = x.shape
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = dm // h
    kt = matmul(x, p["wk"], precision).reshape(t, g, d).transpose(1, 2, 0)
    vt = matmul(x, p["wv"], precision).reshape(t, g, d).transpose(1, 0, 2)
    rows = Q_BLOCK if t % Q_BLOCK == 0 else t

    def attend(args):
        lo, xb = args
        at = lo + jnp.arange(rows)
        q = matmul(xb, p["wq"], precision).reshape(rows, g, h // g, d)
        q = q.transpose(1, 2, 0, 3).reshape(g, -1, d)
        sc = matmul(q, kt, precision).reshape(g, h // g, rows, t) \
            * cfg["attention_multiplier"]
        seen = at[:, None] >= jnp.arange(t)[None, :]
        prob = jax.nn.softmax(jnp.where(seen[None, None], sc, -1e30), -1)
        out = matmul(prob.reshape(g, -1, t), vt, precision)
        return out.reshape(g, h // g, rows, d).transpose(2, 0, 1, 3) \
            .reshape(rows, h * d)

    a = jax.lax.map(attend, (jnp.arange(0, t, rows),
                             x.reshape(t // rows, rows, -1)))
    return matmul(a.reshape(t, h * d), p["wo"], precision)


def mlp(v, p, precision: str):
    gate = matmul(v, p["wg"], precision)
    return matmul(jax.nn.silu(gate) * matmul(v, p["wu"], precision),
                  p["wd"], precision)


def layer(x, p, cfg: dict, kind: str, precision: str, length=None,
          state_dtype: str = "float32"):
    """-> ``(x, the Mamba-2 state after the last real row or None)``."""
    r, eps = cfg["residual_multiplier"], cfg["rms_norm_eps"]
    u = rms_norm(x, p["norm"]["weight"], eps)
    state = None
    if kind == "mamba":
        a, state = base.mamba(u, p["mamba"], mamba_keys(cfg), precision,
                              length, state_dtype)
    else:
        a = attention(u, p["attn"], cfg, precision)
    x = x + r * a
    v = rms_norm(x, p["post_norm"]["weight"], eps)
    return x + r * mlp(v, p["mlp"], precision), state


@functools.partial(jax.jit, static_argnames=("cfg_key", "kind",
                                             "precision", "state_dtype"))
def _layer(x, p, length, cfg_key, kind, precision, state_dtype="float32"):
    return layer(x, p, dict(cfg_key), kind, precision, length, state_dtype)


@functools.partial(jax.jit, static_argnames=("eps", "scaling",
                                             "precision"))
def _head(x, norm_w, embed, eps, scaling, precision):
    return matmul(rms_norm(x, norm_w, eps), embed.T, precision) / scaling


def _embed(params, ids, cfg: dict):
    return cfg["embedding_multiplier"] * jnp.take(
        params["embed"]["weight"], jnp.asarray(ids), axis=0).astype(F32)


def hidden(params, ids, cfg: dict, precision: str = "reference"):
    """(T,) ids of one sequence -> the last block's output (T, d), one
    layer's program at a time."""
    x = _embed(params, ids, cfg)
    for i, kind in enumerate(cfg["layer_types"]):
        x, _ = _layer(x, params[f"layer{i}"], None, base._key(cfg), kind,
                      precision)
    return x


def final_states(params, ids, cfg: dict, precision: str = "reference",
                 pad_to=None, state_dtype: str = "float32") -> dict:
    """Teacher-forced over ``ids``: each Mamba-2 layer's state after the
    last of them, ``{"layer<i>": (H, P, S) float32}``, held in
    ``state_dtype`` between steps.  The sequence is padded as
    :func:`served_gaps` pads it; the pads do not move the state."""
    ids = np.asarray(ids, np.int32)
    padded = np.zeros((padded_length(ids.size, pad_to),), np.int32)
    padded[:ids.size] = ids
    kinds = list(cfg["layer_types"])
    last = len(kinds) - 1 - kinds[::-1].index("mamba")
    states = {}
    with jax.default_matmul_precision("highest"):
        x = _embed(params, padded, cfg)
        for i, kind in enumerate(kinds[:last + 1]):
            x, s = _layer(x, params[f"layer{i}"], ids.size, base._key(cfg),
                          kind, precision, state_dtype)
            if s is not None:
                states[f"layer{i}"] = np.asarray(s)
    return states


def logits_fn(params, ids, cfg: dict, precision: str = "reference",
              rows=slice(None)):
    """(T,) ids -> (T, V) float32 logits (of ``rows`` only)."""
    x = hidden(params, ids, cfg, precision)[rows]
    return _head(x, params["ln_f"]["weight"], params["embed"]["weight"],
                 cfg["rms_norm_eps"], float(cfg["logits_scaling"]),
                 precision)


def padded_length(n: int, pad_to=None) -> int:
    """The least of the few lengths the reference compiles for
    (``PAD_TO``) that holds ``n`` rows (a multiple of the largest
    beyond it)."""
    return base.padded_length(n, pad_to or PAD_TO)


def served_gaps(params, prompt, served, cfg: dict, pad_to=None,
                control: str = "") -> dict:
    """Teacher-forced over ``prompt + served``: at each served position
    the gap by which the served token's reference logit lies below the
    reference's best; with ``control`` also the gap of the token that
    the lower precision puts first.  Padding after the last token moves
    no earlier position: every mixer is causal."""
    ids = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    padded = np.zeros((padded_length(ids.size, pad_to),), np.int32)
    padded[:ids.size] = ids
    count = -(-served.size // HEAD_ROWS) * HEAD_ROWS
    at = np.minimum(prompt.size - 1 + np.arange(count), ids.size - 1)
    with jax.default_matmul_precision("highest"):
        rows = logits_fn(params, padded, cfg, "reference", at)[:served.size]
        best = jnp.max(rows, axis=-1)
        idx = jnp.arange(served.size)
        out = {"gaps": np.asarray(best - rows[idx, jnp.asarray(served)])}
        if control:
            low = logits_fn(params, padded, cfg, control, at)[:served.size]
            out["control_gaps"] = np.asarray(
                best - rows[idx, jnp.argmax(low, axis=-1)])
    return out
