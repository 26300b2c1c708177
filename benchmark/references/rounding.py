"""How a reference rounds the operands of its matrix products and
convolutions: ``"reference"`` not at all (float32 at ``highest``);
``"bf16"`` and ``"fp8"`` (e4m3 with a per-tensor scale, straight-through
gradient) are the controls that have to come out as not correct."""
import jax
import jax.numpy as jnp


def _fp8(x):
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


ROUND = {"reference": lambda x: x, "bf16": _bf16, "fp8": _fp8}


def tree_map(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def leaf_norms(tree):
    return tree_map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))), tree)
