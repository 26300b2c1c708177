"""Pallas kernel tests (interpret mode on the CPU mesh): flash attention
forward/backward parity against the XLA reference path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops.attention import dot_product_attention
from bigdl_tpu.ops.pallas.flash_attention import flash_attention


def _rand_qkv(rs, b=2, h=2, t=64, d=16):
    q = jnp.asarray(rs.randn(b, h, t, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, h, t, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, h, t, d).astype(np.float32))
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_xla(causal):
    rs = np.random.RandomState(0)
    q, k, v = _rand_qkv(rs)
    ref = dot_product_attention(q, k, v, causal=causal, use_flash=False)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_xla(causal):
    rs = np.random.RandomState(1)
    q, k, v = _rand_qkv(rs, b=1, h=2, t=32, d=8)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=16,
                                       block_k=16, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=causal, use_flash=False) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_flash_uneven_falls_back():
    rs = np.random.RandomState(2)
    q, k, v = _rand_qkv(rs, t=48)  # 48 % 32 != 0 with default blocks
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    # use_flash=False: keep the reference on the independent einsum path
    # (the auto default would route it through flash's own fallback)
    ref = dot_product_attention(q, k, v, use_flash=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_kv_longer_than_q():
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(1, 2, 16, 8).astype(np.float32))
    k = jnp.asarray(rs.randn(1, 2, 64, 8).astype(np.float32))
    v = jnp.asarray(rs.randn(1, 2, 64, 8).astype(np.float32))
    out = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    ref = dot_product_attention(q, k, v, use_flash=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_under_jit_and_bf16():
    rs = np.random.RandomState(4)
    q, k, v = _rand_qkv(rs, t=32, d=8)
    q = q.astype(jnp.bfloat16)
    k = k.astype(jnp.bfloat16)
    v = v.astype(jnp.bfloat16)

    @jax.jit
    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=16,
                               block_k=16, interpret=True)

    out = f(q, k, v)
    ref = dot_product_attention(q, k, v, causal=True, use_flash=False)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=0.05, atol=0.05)


# ------------------------------------------------------ int8 matmul
@pytest.mark.parametrize("m,k,n", [(64, 128, 256), (48, 128, 128)])
def test_int8_matmul_dequant_interpret_matches_xla(m, k, n):
    """Pallas int8 kernel (interpret mode) vs the plain XLA integer dot
    + dequant — exact int32 accumulation, identical scaled output."""
    from bigdl_tpu.ops.pallas.int8_matmul import int8_matmul_dequant

    rs = np.random.RandomState(0)
    xq = jnp.asarray(rs.randint(-127, 128, (m, k)), jnp.int8)
    wq = jnp.asarray(rs.randint(-127, 128, (k, n)), jnp.int8)
    scale = jnp.asarray(rs.rand(n).astype(np.float32) * 0.01)

    got = int8_matmul_dequant(xq, wq, scale, out_dtype=jnp.float32,
                              interpret=True)
    acc = np.asarray(xq, np.int64) @ np.asarray(wq, np.int64)
    ref = acc.astype(np.float32) * np.asarray(scale)[None, :]
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-6)


def test_int8_matmul_fallback_non_128_shapes():
    from bigdl_tpu.ops.pallas.int8_matmul import int8_matmul_dequant

    rs = np.random.RandomState(1)
    xq = jnp.asarray(rs.randint(-10, 10, (8, 20)), jnp.int8)
    wq = jnp.asarray(rs.randint(-10, 10, (20, 12)), jnp.int8)
    scale = jnp.ones((12,), jnp.float32)
    got = int8_matmul_dequant(xq, wq, scale, out_dtype=jnp.float32)
    ref = (np.asarray(xq, np.int64) @ np.asarray(wq, np.int64)).astype(
        np.float32)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-6)


def test_attention_routes_by_contract_and_never_swallows_kernel_errors(
        monkeypatch):
    """ops/attention picks the kernel by an explicit contract (no
    mask/bias, V shaped like K, causal only over equal lengths); inside
    it a kernel error is a real error and must propagate — it used to
    be swallowed into the XLA path."""
    import sys

    # (the package re-exports the function under the module's name)
    fa = sys.modules["bigdl_tpu.ops.pallas.flash_attention"]
    rs = np.random.RandomState(5)
    q = jnp.asarray(rs.randn(1, 2, 4, 8), jnp.float32)
    kv = jnp.asarray(rs.randn(1, 2, 16, 8), jnp.float32)

    def boom(*a, **kw):
        raise RuntimeError("lowering failed")

    monkeypatch.setattr(fa, "flash_attention", boom)
    # a cached decode step (causal, Tq < Tk) is outside the contract:
    # XLA path, kernel never called
    out = dot_product_attention(q, kv, kv, causal=True)
    assert out.shape == q.shape
    # inside the contract the kernel's error surfaces
    with pytest.raises(RuntimeError, match="lowering failed"):
        dot_product_attention(q, q, q, causal=True)
