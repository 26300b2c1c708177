"""Pallas kernels under sharded meshes (ops/pallas/partition.py).

Mosaic custom calls cannot be auto-partitioned by GSPMD; each kernel
call site wraps itself in a shard_map over the mesh axes that shard its
batch dims, discovered at trace time (engine scope or ambient manual
region).  These tests run the INTERPRET kernels on the 8-device CPU
mesh and assert the sharded result — outputs, psum'd statistics, and
grads through shard_map's transpose — matches the unsharded call
bit-for-bit in structure and numerically in value.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops.pallas.flash_attention import flash_attention
from bigdl_tpu.ops.pallas.fused_matmul import fused_matmul_bn
from bigdl_tpu.ops.pallas.int8_matmul import int8_matmul_dequant
from bigdl_tpu.ops.pallas.partition import (
    current_kernel_mesh,
    kernel_mesh_scope,
)
from bigdl_tpu.parallel.mesh import MeshConfig, make_mesh


def _mesh(**kw):
    n = int(np.prod(list(kw.values())))
    return make_mesh(MeshConfig(**kw), jax.devices()[:n])


def test_current_kernel_mesh_scope():
    assert current_kernel_mesh() is None
    mesh = _mesh(data=4, model=2)
    with kernel_mesh_scope(mesh):
        m, avail, remaining = current_kernel_mesh()
        assert m is mesh
        assert avail == frozenset({"data", "model"})
        # nothing manual yet: every mesh axis remains to be taken
        assert avail <= remaining
        assert remaining == frozenset(mesh.axis_names)
    assert current_kernel_mesh() is None


def test_fused_matmul_sharded_matches_unsharded():
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(64, 32), jnp.float32)
    w = jnp.asarray(rs.randn(32, 16), jnp.float32)
    ps = jnp.asarray(rs.rand(32) + 0.5, jnp.float32)
    pb = jnp.asarray(rs.randn(32), jnp.float32)

    ref = fused_matmul_bn(x, w, ps, pb, interpret=True)
    mesh = _mesh(data=4)

    def call(x_, w_):
        return fused_matmul_bn(x_, w_, ps, pb, interpret=True)

    with kernel_mesh_scope(mesh):
        got = jax.jit(call)(x, w)
    for r, g in zip(ref, got):
        np.testing.assert_allclose(np.asarray(r), np.asarray(g),
                                   rtol=1e-5, atol=1e-5)

    # grads through shard_map's transpose (dw/dps/dpb psums)
    def loss(x_, w_, ps_, pb_):
        y, ssum, ssq = fused_matmul_bn(x_, w_, ps_, pb_, interpret=True)
        return (jnp.sum(y * y) + jnp.sum(ssum) + 0.1 * jnp.sum(ssq))

    gref = jax.grad(loss, argnums=(0, 1, 2, 3))(x, w, ps, pb)
    with kernel_mesh_scope(mesh):
        ggot = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(x, w, ps, pb)
    for r, g in zip(gref, ggot):
        np.testing.assert_allclose(np.asarray(r), np.asarray(g),
                                   rtol=1e-4, atol=1e-4)


def test_flash_attention_sharded_matches_unsharded():
    rs = np.random.RandomState(1)
    q = jnp.asarray(rs.randn(4, 4, 32, 8), jnp.float32)
    k = jnp.asarray(rs.randn(4, 4, 32, 8), jnp.float32)
    v = jnp.asarray(rs.randn(4, 4, 32, 8), jnp.float32)

    ref = flash_attention(q, k, v, causal=True, interpret=True)
    mesh = _mesh(data=2, model=2)

    def call(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal=True, interpret=True)

    with kernel_mesh_scope(mesh):
        got = jax.jit(call)(q, k, v)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-5, atol=1e-5)

    def loss(q_, k_, v_):
        return jnp.sum(
            flash_attention(q_, k_, v_, causal=True, interpret=True) ** 2)

    gref = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    with kernel_mesh_scope(mesh):
        ggot = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    for r, g in zip(gref, ggot):
        np.testing.assert_allclose(np.asarray(r), np.asarray(g),
                                   rtol=1e-4, atol=1e-4)


def test_flash_nested_inside_manual_region():
    """Flash inside a shard_map already manual over 'data' (the
    pipeline-stage case) nests over the remaining 'model' axis only."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    rs = np.random.RandomState(2)
    q = jnp.asarray(rs.randn(4, 4, 32, 8), jnp.float32)
    ref = flash_attention(q, q, q, causal=True, interpret=True)
    mesh = _mesh(data=2, model=2)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=P("data", None, None, None),
             out_specs=P("data", None, None, None),
             axis_names=frozenset({"data"}), check_vma=False)
    def body(qb):
        # ambient manual region: 'data' taken, 'model' still auto
        m, avail, remaining = current_kernel_mesh()
        assert "data" not in avail and "model" in avail
        assert "data" not in remaining
        assert avail == frozenset({"model"})
        return flash_attention(qb, qb, qb, causal=True, interpret=True)

    got = jax.jit(body)(q)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-5, atol=1e-5)


def test_int8_matmul_sharded_matches_unsharded():
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randint(-127, 127, (64, 128)), jnp.int8)
    w = jnp.asarray(rs.randint(-127, 127, (128, 128)), jnp.int8)
    s = jnp.asarray(rs.rand(128), jnp.float32)

    ref = int8_matmul_dequant(x, w, s, out_dtype=jnp.float32,
                              interpret=True)
    mesh = _mesh(data=4)
    with kernel_mesh_scope(mesh):
        got = jax.jit(lambda x_: int8_matmul_dequant(
            x_, w, s, out_dtype=jnp.float32, interpret=True))(x)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=1e-5, atol=1e-5)


def test_indivisible_dims_fall_back_to_plain_call():
    """Batch 6 over data=4 does not divide — the kernel must run
    unwrapped (replicated), not fail."""
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(6, 32), jnp.float32)
    w = jnp.asarray(rs.randn(32, 16), jnp.float32)
    ref = fused_matmul_bn(x, w, interpret=True)
    mesh = _mesh(data=4)
    with kernel_mesh_scope(mesh):
        got = jax.jit(lambda x_: fused_matmul_bn(
            x_, w, interpret=True))(x)
    for r, g in zip(ref, got):
        np.testing.assert_allclose(np.asarray(r), np.asarray(g),
                                   rtol=1e-5, atol=1e-5)
