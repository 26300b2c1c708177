"""Pallas kernel tests (interpret mode on the CPU mesh): flash attention
forward/backward parity against the XLA reference path."""
import importlib

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
    """``jax.grad`` through the kernel (its Pallas backward, recorded as
    such) against the reference attention's."""
    from bigdl_tpu.ops.pallas import report

    rs = np.random.RandomState(1)
    q, k, v = _rand_qkv(rs, b=1, h=2, t=32, d=8)
    before = report.report().get("flash_attention_bwd", {}).get("pallas", 0)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=16,
                                       block_k=16, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=causal, use_flash=False) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    assert report.report()["flash_attention_bwd"]["pallas"] == before + 1
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


# ------------------------------------------------ flash backward kernel
# (the package re-exports the function under the module's name)
fa = importlib.import_module("bigdl_tpu.ops.pallas.flash_attention")

# name -> (t, s, causal, dtype, bq, bk, sm_scale): blocks of unequal
# sizes put pairs that straddle the diagonal at every offset
BWD_CASES = {
    "causal": (64, 64, True, jnp.float32, 16, 16, None),
    "causal_bq_under_bk": (64, 64, True, jnp.float32, 16, 32, None),
    "causal_bq_over_bk": (64, 64, True, jnp.float32, 32, 16, None),
    "full": (32, 32, False, jnp.float32, 16, 16, None),
    "kv_longer_than_q": (16, 64, False, jnp.float32, 16, 16, None),
    "causal_bf16": (64, 64, True, jnp.bfloat16, 32, 16, None),
    "causal_scale": (32, 32, True, jnp.float32, 16, 16, 0.3),
}


@pytest.mark.parametrize("case", sorted(BWD_CASES))
def test_flash_bwd_kernel_matches_blockwise_and_xla(case):
    """The Pallas backward (interpret mode) against the blockwise XLA
    backward at the same residuals and against ``jax.grad`` of the
    reference attention in f32."""
    t, s, causal, dtype, bq, bk, scale = BWD_CASES[case]
    rs = np.random.RandomState(6)
    q, k, v, g = (jnp.asarray(rs.randn(1, 2, n, 8), dtype)
                  for n in (t, s, s, t))
    scale = scale or 1.0 / np.sqrt(8)
    o, lse = fa._flash_fwd_pallas(q, k, v, causal, scale, bq, bk, True)
    got = fa._flash_bwd_pallas(q, k, v, o, lse, g, causal, scale, bq, bk,
                               True)
    oracle = fa._bwd_blockwise(q, k, v, o, lse, g, causal, scale, bq)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, g)]
    _, vjp = jax.vjp(lambda q, k, v: dot_product_attention(
        q, k, v, causal=causal, scale=scale, use_flash=False), *f32[:3])
    tol = 5e-4 if dtype == jnp.float32 else 0.05
    for a, b, c in zip(got, oracle, vjp(f32[3])):
        assert a.dtype == dtype
        for ref in (b, c):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(ref, np.float32),
                                       rtol=tol, atol=tol)


def test_flash_bwd_untileable_shape_takes_blockwise():
    """On the chip the backward tiles by :func:`bwd_blocks`: a length
    over the block cap with no 128-multiple divisor, or a sequence whose
    dQ outgrows the kernel's VMEM, takes the blockwise XLA backward and
    records the ``xla`` route at its shape."""
    from bigdl_tpu.ops.pallas import report

    assert fa.bwd_blocks(2048, 2048, 64, 2) == (512, 512)
    assert fa.bwd_blocks(384, 384, 64, 2) == (384, 384)   # whole axis
    assert fa.bwd_blocks(520, 520, 64, 2) is None
    assert fa.bwd_blocks(32768, 32768, 64, 2) == (512, 512)
    assert fa.bwd_blocks(32768, 32768, 128, 2) is None
    rs = np.random.RandomState(8)
    q, k, v = _rand_qkv(rs, b=1, h=1, t=520, d=8)
    g = jnp.asarray(rs.randn(*q.shape), jnp.float32)
    scale = 1.0 / np.sqrt(8)
    o, lse = fa._xla_attention_lse(q, k, v, True, scale)
    n = len(report.fallbacks())
    got = fa._flash_bwd(True, scale, 520, 520, False, (q, k, v, o, lse), g)
    assert report.fallbacks()[n:] == [
        ("flash_attention_bwd", "xla", (1, 1, 520, 520, 8))]
    want = fa._bwd_blockwise(q, k, v, o, lse, g, True, scale, 520)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_flash_bwd_roofline_reader():
    """``flash_bwd_roofline.train`` puts twice the forward's cost over
    the backward kernels' time a forward call, and reads nothing from a
    trace whose backward is the blockwise scan."""
    from benchmark import flops
    from benchmark.run import read_metric

    by_name = {"flash_fwd.3 tpu_custom_call": [0.0157, 12],
               "flash_bwd.4 tpu_custom_call": [0.0180, 6],
               "flash_bwd.5 tpu_custom_call": [0.0120, 6],
               "fusion.7": [0.5, 40]}
    run = {"kind": "train", "trace": {"by_name": by_name}, "chips": 1,
           "config": {"model": {"num_heads": 12, "hidden_size": 768}},
           "traffic": {"batch": 8, "seq_len": 2048},
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    bound = 2 * flops.flash_fwd_cost(8, 12, 2048, 64)["flops"] / 197e12
    assert read_metric("flash_bwd_roofline.train", run) == pytest.approx(
        100 * bound / (0.030 / 12))
    for name in ("flash_bwd.4 tpu_custom_call", "flash_bwd.5 tpu_custom_call"):
        del by_name[name]
    by_name["while.36"] = [0.108, 12]
    assert read_metric("flash_bwd_roofline.train", run) is None


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
