"""Weights from ``--seed``, made on the device in one jitted call.

The tree's structure (paths, shapes, dtypes) comes from
``jax.eval_shape`` of the model's own ``init``; the values come from the
configuration file's ``init`` rules: ``[glob on the leaf's path, kind,
number]``, first match wins, kind ``normal`` (std), ``he_normal`` or ``const``.  The
plain reference is given the same tree, so it takes nothing the program
has made.
"""
from __future__ import annotations

import fnmatch

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed // (2 ** 31))


def leaf_paths(tree) -> list:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path) for path, _ in flat]


def _rule_for(path: str, rules):
    for pattern, kind, number in rules:
        if fnmatch.fnmatchcase(path, pattern):
            return kind, float(number)
    raise KeyError(f"no init rule matches leaf {path!r}")


def make_variables(model, rules, seed: int, dtype=jnp.float32):
    """``{"params", "state"}`` for ``model`` from ``seed`` by ``rules``."""
    template = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    paths = leaf_paths(template)
    leaves, treedef = jax.tree_util.tree_flatten(template)
    plan = [_rule_for(p, rules) for p in paths]

    def build(key):
        out = []
        for i, (leaf, (kind, number)) in enumerate(zip(leaves, plan)):
            dt = dtype if jnp.issubdtype(leaf.dtype, jnp.floating) \
                else leaf.dtype
            if kind == "normal":
                out.append(number * jax.random.normal(
                    jax.random.fold_in(key, i), leaf.shape, dt))
            elif kind == "he_normal":  # std sqrt(2 / fan_in), HWIO
                fan_in = 1
                for n in leaf.shape[:-1]:
                    fan_in *= n
                out.append((2.0 / fan_in) ** 0.5 * jax.random.normal(
                    jax.random.fold_in(key, i), leaf.shape, dt))
            elif kind == "const":
                out.append(jnp.full(leaf.shape, number, dt))
            else:
                raise ValueError(f"unknown init kind {kind!r}")
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed))
