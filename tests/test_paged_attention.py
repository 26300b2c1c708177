"""``paged_attn`` (ops/pallas/paged_attention.py) in interpret mode
against the gather path it replaces for single-token queries: gather
the full extent (ops/paged_kv.paged_gather) + the stock attention core
under a length mask."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops import paged_kv
from bigdl_tpu.ops.attention import dot_product_attention
from bigdl_tpu.ops.pallas import paged_attention

S, H, D, Q, M = 6, 4, 32, 8, 8          # slots, heads, head dim, page, pages
# nothing held, one token, a page edge, the full extent, mid-page twice
KV_LEN = np.array([0, 1, Q, M * Q, 3 * Q + 5, 2 * Q + 1], np.int32)


def _bf16_exact(rng, shape):
    """Normal draws that bf16 holds exactly, so the kernel's rounding of
    its matmul operands loses nothing the f32 reference keeps."""
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@pytest.fixture(scope="module")
def case():
    rng = np.random.default_rng(0)
    pool = paged_kv.init_pool(S * M + 1, Q, {"k": (H, D), "v": (H, D)}, S)
    pool["k"] = _bf16_exact(rng, pool["k"].shape)
    pool["v"] = _bf16_exact(rng, pool["v"].shape)
    table = rng.permutation(np.arange(1, S * M + 1)).reshape(S, M)
    for row, held in enumerate(-(-KV_LEN // Q)):
        table[row, held:] = 0            # unmapped: the trash page
    return pool, jnp.asarray(table, jnp.int32), _bf16_exact(
        rng, (S, 1, H * D))


def _gathered(pool, table, q, kv_len):
    k_all, v_all = paged_kv.paged_gather(pool, table, H, jnp.float32)
    mask = jnp.arange(M * Q)[None, None, None, :] < kv_len[
        :, None, None, None]
    out = dot_product_attention(
        q.reshape(S, 1, H, D).transpose(0, 2, 1, 3), k_all, v_all,
        mask=mask, use_flash=False)
    return np.asarray(out.transpose(0, 2, 1, 3).reshape(S, 1, H * D))


@pytest.mark.parametrize("pages_per_step", [1, 3, 8])
def test_paged_attn_matches_gather_on_ragged_lengths(case, pages_per_step):
    pool, table, q = case
    got = np.asarray(paged_attention.paged_attn(
        q, pool["k"], pool["v"], table, jnp.asarray(KV_LEN), num_heads=H,
        pages_per_step=pages_per_step, interpret=True))
    want = _gathered(pool, table, q, KV_LEN)
    held = KV_LEN > 0
    # the softmax weights are rounded to bf16 before P @ V: 2^-9 relative
    np.testing.assert_allclose(got[held], want[held], atol=1e-2)
    assert not got[~held].any()          # a row with nothing held: zeros


def test_paged_attn_ignores_what_lies_past_the_length(case):
    """Pages past a row's length are neither fetched nor weighed: the
    answer does not move when they, and the trash page, change."""
    pool, table, q = case
    run = functools.partial(
        paged_attention.paged_attn, q, table=table,
        kv_len=jnp.asarray(KV_LEN), num_heads=H, pages_per_step=2,
        interpret=True)
    base = np.asarray(run(k_pool=pool["k"], v_pool=pool["v"]))
    k, v = np.array(pool["k"]), np.array(pool["v"])
    tab = np.asarray(table)
    for row, n in enumerate(KV_LEN):      # spoil every row past length
        flat_k = k[tab[row]].reshape(M * Q, -1)
        flat_v = v[tab[row]].reshape(M * Q, -1)
        flat_k[n:], flat_v[n:] = 1e4, -1e4
        k[tab[row]] = flat_k.reshape(M, Q, -1)
        v[tab[row]] = flat_v.reshape(M, Q, -1)
    k[0], v[0] = 1e4, -1e4
    spoiled = np.asarray(run(k_pool=jnp.asarray(k), v_pool=jnp.asarray(v)))
    np.testing.assert_array_equal(spoiled, base)


def test_apply_paged_kernel_route_matches_gather_route(
        interpreted_paged_attn, monkeypatch):
    """One decode step of an attention layer over the paged pool, by
    the kernel (routed as on the TPU, interpreted) and by the gather:
    same output on the active rows, same pool."""
    import bigdl_tpu.nn as nn

    mha = nn.MultiHeadAttention(H * D, H)
    rng = np.random.default_rng(1)
    params = mha.init_params(jax.random.PRNGKey(0))
    cache = mha.init_paged_cache(S * M + 1, Q, S)
    cache["k"] = _bf16_exact(rng, cache["k"].shape)
    cache["v"] = _bf16_exact(rng, cache["v"].shape)
    length = np.array([0, 1, Q - 1, M * Q - 1, 3 * Q + 5, 7], np.int32)
    cache["length"] = jnp.asarray(length)
    active = jnp.asarray([True, True, True, True, True, False])
    table = jnp.asarray(np.arange(1, S * M + 1).reshape(S, M), jnp.int32)
    x = _bf16_exact(rng, (S, 1, H * D))

    got, pool_got = mha.apply_paged(params, x, cache, table, active)
    monkeypatch.setenv("BIGDL_TPU_FORCE_PALLAS", "0")   # off the TPU
    want, pool_want = mha.apply_paged(params, x, cache, table, active)
    on = np.asarray(active)
    np.testing.assert_allclose(np.asarray(got)[on], np.asarray(want)[on],
                               atol=2e-2)
    for name in ("k", "v", "length"):
        np.testing.assert_array_equal(pool_got[name], pool_want[name])
