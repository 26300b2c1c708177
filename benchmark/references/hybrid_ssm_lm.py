"""Plain reference of the decoder of Mamba-2 layers, grouped-query
attention layers and latent routed experts that the
``nemotron3-super-11of88-ep4share`` configuration runs (the Nemotron-H
family's equations, written out in ``jax.numpy``).  No kernel, no cache,
no chunked scan: a Mamba-2 layer runs its recurrence one step after the
other (``lax.scan`` over the tokens), so that the program's chunked form
is checked against the definition; the experts are visited one after
the other, each computing the tokens that chose it (gathered into a
buffer of ``ROOM`` times the mean load; a layer in which an expert got
more is computed again with every expert over every token).  It imports
nothing of the program; it is handed the benchmark's own weights (the
same bf16 tree) and upcasts one layer, and inside a routed layer one
expert, at a time.

``d`` hidden; ``N`` is RMSNorm (weight only, eps ``norm_eps``).  Block
``l``: ``h = h + Mixer_l(N_l(h))``, the mixer named by
``hybrid_override_pattern[l]``.  Embedding unscaled; head ``N_f(h)
Whead``, untied.

``M`` (``d_inner = expand d``, ``H`` heads of ``P``, ``G`` groups of
``S`` states, kernel ``K``): ``[z | xBC | dt] = u W_in``; ``xBC =
silu(sum_j w_j xBC_{t-K+1+j} + b)`` (zeros before the first token);
``x, B, C`` split from it, head ``h`` reading group ``h // (H/G)``;
``delta = softplus(dt + dt_bias)``, ``a = -exp(A_log)``; ``S_t =
exp(delta_t a) S_{t-1} + delta_t x_t B_t^T`` from ``S_0 = 0``; ``y_t =
S_t C_t + D x_t``; ``out = (N_{G groups}(y * silu(z)) * w) W_out``.

``*``: ``q = u Wq`` (32 heads of 128), ``k = u Wk``, ``v = u Wv`` (2
heads), query head ``i`` reading K/V head ``i // 16``, causal softmax of
``q k^T / sqrt(128)`` in f32, ``out = attn Wo``; no rotary.

``E``: ``s = sigmoid(u Wr)`` over all ``n_routed_experts``, the
``num_experts_per_tok`` largest of ``s + b`` chosen, ``w = s[chosen] /
sum s[chosen] * routed_scaling_factor``; ``l = u W_down``; ``routed =
(sum over the chosen experts held here of w_e W2_e relu(W1_e l)**2)
W_up``; ``shared = W2_s relu(W1_s u)**2``; ``out = routed + shared``.

``precision`` picks how every matrix product is computed:
``"reference"`` float32 at ``highest``; ``"bf16"`` and ``"fp8"`` round
both operands first and keep their activations in that type too - the
controls that have to come out as not correct.  The recurrence itself
is elementwise and stays in float32 in every precision.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.rounding import ROUND as _ROUND

Q_BLOCK = 128      # query rows attended at a time
ROW_BLOCK = 2048   # rows through the shared expert at a time
# a request is padded to one of these lengths (the cell's prompts and
# answers make at most 7680 tokens) and its served rows to a multiple of
# HEAD_ROWS, so that a run compiles for two lengths and not for a
# length a request
PAD_TO = (2048, 8192)
HEAD_ROWS = 512
ROOM = 3           # an expert's buffer, in mean loads (T * k / experts)
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def matmul(a, b, precision: str):
    r = _ROUND[precision]
    out = jnp.matmul(r(a.astype(F32)), r(b.astype(F32)),
                     precision=HIGHEST, preferred_element_type=F32)
    return out if precision == "reference" else r(out)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w.astype(F32)


# ------------------------------------------------------------------ mamba
def mamba(x, p, cfg: dict, precision: str, length=None,
          state_dtype: str = "float32"):
    """``x`` (T, d) of one sequence -> ``((T, d), the state (H, P, S)
    after the last step)``, the recurrence stepped; rows from
    ``length`` on are padding and do not move the state.  The state is
    rounded to ``state_dtype`` after every step (a control: the
    configuration's is float32)."""
    t, d = x.shape
    di = cfg["expand"] * d
    h, hp = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, s, k = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    zxd = matmul(x, p["w_in"], precision)
    z, xbc, dt = zxd[:, :di], zxd[:, di:-h], zxd[:, -h:]
    w = p["conv_w"].astype(F32)
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), xbc])
    conv = sum(w[j] * padded[j:j + t] for j in range(k))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(F32))
    xs = xbc[:, :di].reshape(t, h, hp)
    group = np.arange(h) // (h // g)
    bs = xbc[:, di:di + g * s].reshape(t, g, s)[:, group]    # (T, H, S)
    cs = xbc[:, di + g * s:].reshape(t, g, s)[:, group]
    delta = jax.nn.softplus(dt + p["dt_bias"].astype(F32))   # (T, H)
    if length is not None:
        delta = jnp.where((jnp.arange(t) < length)[:, None], delta, 0.0)
    a = -jnp.exp(p["A_log"].astype(F32))

    def step(state, inputs):
        x_t, b_t, c_t, dl = inputs
        state = jnp.exp(dl * a)[:, None, None] * state \
            + (dl[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        if state_dtype != "float32":     # a rounding XLA cannot drop
            info = jnp.finfo(state_dtype)
            state = jax.lax.reduce_precision(state, info.nexp, info.nmant)
        return state, jnp.einsum("hps,hs->hp", state, c_t,
                                 precision=HIGHEST)

    last, y = jax.lax.scan(step, jnp.zeros((h, hp, s), F32),
                           (xs, bs, cs, delta))
    y = (y + p["D"].astype(F32)[:, None] * xs).reshape(t, di)
    y = (y * jax.nn.silu(z)).reshape(t, g, di // g)
    y = rms_norm(y, jnp.ones(()), cfg["norm_eps"]).reshape(t, di) \
        * p["norm"].astype(F32)
    return matmul(y, p["w_out"], precision), last


# --------------------------------------------------------------- attention
def attention(x, p, cfg: dict, precision: str):
    """``x`` (T, d) of one sequence -> (T, d): every causal key."""
    t = x.shape[0]
    h, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    kt = matmul(x, p["wk"], precision).reshape(t, g, d).transpose(1, 2, 0)
    vt = matmul(x, p["wv"], precision).reshape(t, g, d).transpose(1, 0, 2)
    rows = Q_BLOCK if t % Q_BLOCK == 0 else t

    def attend(args):
        lo, xb = args
        at = lo + jnp.arange(rows)
        q = matmul(xb, p["wq"], precision).reshape(rows, g, h // g, d)
        q = q.transpose(1, 2, 0, 3).reshape(g, -1, d)
        sc = matmul(q, kt, precision).reshape(g, h // g, rows, t) \
            / math.sqrt(d)
        seen = at[:, None] >= jnp.arange(t)[None, :]
        prob = jax.nn.softmax(jnp.where(seen[None, None], sc, -1e30), -1)
        out = matmul(prob.reshape(g, -1, t), vt, precision)
        return out.reshape(g, h // g, rows, d).transpose(2, 0, 1, 3) \
            .reshape(rows, h * d)

    a = jax.lax.map(attend, (jnp.arange(0, t, rows),
                             x.reshape(t // rows, rows, -1)))
    return matmul(a.reshape(t, h * d), p["wo"], precision)


# ------------------------------------------------------------------ experts
def relu2(x, w1, w2, precision: str):
    def rows(xb):
        return matmul(jnp.square(jax.nn.relu(matmul(xb, w1, precision))),
                      w2, precision)

    t = x.shape[0]
    if precision == "reference" and t % ROW_BLOCK == 0 and t > ROW_BLOCK:
        # blocks of rows, so that the widest hidden fits (the controls
        # round by the whole tensor's scale: not in blocks)
        return jax.lax.map(rows, x.reshape(t // ROW_BLOCK, ROW_BLOCK, -1)
                           ).reshape(t, -1)
    return rows(x)


def route(x, router, cfg: dict, precision: str):
    """-> ``(chosen (T, k) expert ids, weights (T, k))``."""
    s = jax.nn.sigmoid(matmul(x, router["weight"], precision))
    c = s + router["bias"].astype(F32)
    chosen = jnp.argsort(-c, axis=-1, stable=True)[
        :, :cfg["num_experts_per_tok"]]
    w = jnp.take_along_axis(s, chosen, axis=1)
    if cfg.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return chosen, w * cfg.get("routed_scaling_factor", 1.0)


def room_for(t: int, cfg: dict) -> int:
    """Rows of an expert's buffer for ``t`` tokens."""
    mean = t * cfg["num_experts_per_tok"] / cfg["n_routed_experts"]
    return min(t, int(math.ceil(ROOM * mean)))


def routed(x, p, cfg: dict, precision: str, room=None):
    """-> ``(routed + shared, the most tokens any held expert got)``;
    expert ``e`` computes the first ``room`` of the tokens that chose it
    (default :func:`room_for`): the result is the layer's only if the
    second number is at most ``room``."""
    t = x.shape[0]
    room = room_for(t, cfg) if room is None else room
    held = cfg.get("experts_held")
    held = list(range(cfg["n_routed_experts"])) if held is None else held
    chosen, w = route(x, p["router"], cfg, precision)
    lat = matmul(x, p["latent"]["down"], precision)

    def one(y, xs):
        e, w1, w2 = xs
        mine = (chosen == e).any(-1)
        w_e = jnp.where(chosen == e, w, 0.0).sum(-1)
        rows, = jnp.nonzero(mine, size=room, fill_value=t)
        sent = jnp.take(lat, rows, axis=0, mode="fill", fill_value=0.0)
        share = jnp.take(w_e, rows, mode="fill", fill_value=0.0)
        out = share[:, None] * relu2(sent, w1, w2, precision)
        return y.at[rows].add(out, mode="drop"), mine.sum()

    ex = p["experts"]
    y, got = jax.lax.scan(one, jnp.zeros_like(lat), (
        jnp.asarray(held, jnp.int32), ex["wu"], ex["wd"]))
    y = matmul(y, p["latent"]["up"], precision)
    if "shared" in p:
        y = y + relu2(x, p["shared"]["wu"], p["shared"]["wd"], precision)
    return y, got.max()


# ------------------------------------------------------------------- model
def block(x, p, cfg: dict, kind: str, precision: str, room=None):
    """-> ``(x, the most tokens an expert of this layer got, or 0)``."""
    u = rms_norm(x, p["norm"]["weight"], cfg["norm_eps"])
    if kind == "M":
        return x + mamba(u, p["mamba"], cfg, precision)[0], 0
    if kind == "*":
        return x + attention(u, p["attn"], cfg, precision), 0
    f, most = routed(u, p["ffn"], cfg, precision, room)
    return x + f, most


def _key(cfg: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()))


@functools.partial(jax.jit, static_argnames=("cfg_key", "kind",
                                             "precision", "room"))
def _block(x, p, cfg_key, kind, precision, room=None):
    return block(x, p, dict(cfg_key), kind, precision, room)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, norm_w, head_w, eps, precision):
    return matmul(rms_norm(x, norm_w, eps), head_w, precision)


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision",
                                             "state_dtype"))
def _mamba_block(x, p, length, cfg_key, precision, state_dtype):
    cfg = dict(cfg_key)
    out, state = mamba(rms_norm(x, p["norm"]["weight"], cfg["norm_eps"]),
                       p["mamba"], cfg, precision, length, state_dtype)
    return x + out, state


def _layer(x, p, cfg: dict, kind: str, precision: str):
    args = (x, p, _key(cfg), kind, precision)
    x, most = _block(*args)
    if kind == "E" and int(most) > room_for(x.shape[0], cfg):
        # an expert got more than its buffer holds: every expert over
        # every token
        x, _ = _block(*args, room=x.shape[0])
    return x


def _embed(params, ids):
    return jnp.take(params["embed"]["weight"], jnp.asarray(ids), axis=0
                    ).astype(F32)


def hidden(params, ids, cfg: dict, precision: str = "reference"):
    """(T,) ids of one sequence -> the last block's output (T, d), one
    layer's program at a time."""
    x = _embed(params, ids)
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        x = _layer(x, params[f"layer{i}"], cfg, kind, precision)
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
    pattern = cfg["hybrid_override_pattern"]
    states = {}
    with jax.default_matmul_precision("highest"):
        x = _embed(params, padded)
        for i, kind in enumerate(pattern[:pattern.rindex("M") + 1]):
            p = params[f"layer{i}"]
            if kind == "M":
                x, s = _mamba_block(x, p, ids.size, _key(cfg), precision,
                                    state_dtype)
                states[f"layer{i}"] = np.asarray(s)
            else:
                x = _layer(x, p, cfg, kind, precision)
    return states


def logits_fn(params, ids, cfg: dict, precision: str = "reference",
              rows=slice(None)):
    """(T,) ids -> (T, V) float32 logits (of ``rows`` only)."""
    x = hidden(params, ids, cfg, precision)[rows]
    return _head(x, params["ln_f"]["weight"], params["head"]["weight"],
                 cfg["norm_eps"], precision)


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
