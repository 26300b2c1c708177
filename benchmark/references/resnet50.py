"""Plain reference of ResNet-50 v1 as the ``resnet50`` configuration
trains it (He et al. 2015, Table 1; stride on the 3x3 of each
bottleneck's first block, projection shortcuts; BatchNorm on batch
statistics, eps 1e-5; 3x3/2 max pool; global average pool; linear
classifier; mean cross-entropy) with LARS as the recipe sets it:
``v = momentum v + lr * trust * |w| / (|g| + wd |w|) * (g + wd w)``,
ratio 1 where ``|w|`` is 0.  Plain ``jax.numpy``/``lax`` in float32 at
``highest``; each bottleneck is rematerialised so that it fits.

It is handed the benchmark's weights in the program's tree, whose
modules are numbered in the order they were made: ``conv1``, then
``SpatialConvolution[_k]`` and ``SpatialBatchNormalization[_k]``; the
reference walks the published architecture in that same order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.references.rounding import ROUND as _ROUND
from benchmark.references.rounding import tree_map as _tm

BN_EPS = 1e-5
STAGES = ((64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2))


def conv(x, w, stride: int, precision: str):
    r = _ROUND[precision]
    return jax.lax.conv_general_dilated(
        r(x), r(w), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def batch_norm(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["weight"] + p["bias"]


def _name(kind: str, k: int) -> str:
    return kind if k == 0 else f"{kind}_{k}"


def plan():
    """The architecture as a list of blocks, each naming its modules in
    the order the program made them."""
    ci, bi = 0, 1  # conv1 / the stem's BN come first
    blocks, n_in = [], 64
    for planes, count, stride in STAGES:
        for b in range(count):
            names = {"stride": stride if b == 0 else 1}
            for tag in ("a", "b", "c"):
                names["conv_" + tag] = _name("SpatialConvolution", ci)
                names["bn_" + tag] = _name("SpatialBatchNormalization", bi)
                ci, bi = ci + 1, bi + 1
            if b == 0:
                names["conv_s"] = _name("SpatialConvolution", ci)
                names["bn_s"] = _name("SpatialBatchNormalization", bi)
                ci, bi = ci + 1, bi + 1
            blocks.append(names)
            n_in = planes * 4
    return blocks


def zero_gamma_rules():
    """Init rules that zero the closing BatchNorm gamma of every block
    (the recipe's zero-gamma trick), for the configuration file."""
    return [[f"params/{b['bn_c']}/weight", "const", 0.0] for b in plan()]


def bottleneck(x, p, stride, precision):
    """``p``: the block's modules by role (conv_a/bn_a 1x1, conv_b/bn_b
    3x3 with the stride, conv_c/bn_c 1x1, conv_s/bn_s the projection)."""
    y = jax.nn.relu(batch_norm(conv(x, p["conv_a"]["weight"], 1, precision),
                               p["bn_a"]))
    y = jax.nn.relu(batch_norm(conv(y, p["conv_b"]["weight"], stride,
                                    precision), p["bn_b"]))
    y = batch_norm(conv(y, p["conv_c"]["weight"], 1, precision), p["bn_c"])
    if "conv_s" in p:
        x = batch_norm(conv(x, p["conv_s"]["weight"], stride, precision),
                       p["bn_s"])
    return jax.nn.relu(x + y)


def logits_fn(params, images, precision: str = "reference"):
    x = conv(images, params["conv1"]["weight"], 2, precision)
    x = jax.nn.relu(batch_norm(x, params["SpatialBatchNormalization"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for names in plan():
        roles = {k: params[v] for k, v in names.items() if k != "stride"}
        x = jax.checkpoint(functools.partial(
            bottleneck, stride=names["stride"], precision=precision))(
                x, roles)
    x = jnp.mean(x, axis=(1, 2))
    fc = params["fc1000"]
    r = _ROUND[precision]
    return jnp.matmul(r(x), r(fc["weight"]),
                      precision=jax.lax.Precision.HIGHEST) + fc["bias"]


def loss_fn(params, images, labels, precision="reference"):
    logits = logits_fn(params, images, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x)))


@functools.partial(jax.jit, static_argnames=("precision", "opt_key"))
def _step(params, velocity, images, labels, precision, opt_key):
    o = dict(opt_key)
    loss, g = jax.value_and_grad(loss_fn)(params, images, labels, precision)

    def update(p, g, v):
        wn, gn = _norm(p), _norm(g)
        denom = gn + o["weight_decay"] * wn
        ratio = jnp.where((wn > 0) & (denom > 0),
                          o["trust"] * wn / (denom + 1e-12), 1.0)
        v = o["momentum"] * v + o["lr"] * ratio * (
            g + o["weight_decay"] * p)
        return p - v, v

    pairs = _tm(update, params, g, velocity)
    is_pair = lambda x: isinstance(x, tuple)
    new_p = jax.tree_util.tree_map(lambda t: t[0], pairs, is_leaf=is_pair)
    new_v = jax.tree_util.tree_map(lambda t: t[1], pairs, is_leaf=is_pair)
    return new_p, new_v, loss


def leaf_norms(tree):
    return _tm(_norm, tree)


def train_steps(params, batches, cfg: dict, opt: dict,
                precision: str = "reference",
                drop_half: bool = False) -> dict:
    """Losses of each step, the norm of every leaf of the optimizer's
    state after one step (LARS keeps no gradient: its first velocity is
    the first gradient as the update used it), and the norm of every
    leaf's change after the last step."""
    opt_key = tuple(sorted((k, v) for k, v in opt.items() if k != "kind"))
    p0 = params
    v = _tm(jnp.zeros_like, params)
    losses, first, update1 = [], None, None
    for images, labels in batches:
        images = jnp.asarray(images, jnp.float32)
        labels = jnp.asarray(labels, jnp.int32)
        if drop_half:
            images, labels = images[:images.shape[0] // 2], \
                labels[:labels.shape[0] // 2]
        params, v, loss = _step(params, v, images, labels, precision,
                                opt_key)
        losses.append(float(loss))
        if first is None:
            first = jax.device_get(leaf_norms(v))
            update1 = jax.device_get(v)
    change = jax.device_get(leaf_norms(_tm(jnp.subtract, params, p0)))
    return {"losses": losses, "grad1_norms": first, "change_norms": change,
            "update1": update1}
