"""The decoder with window and full attention layers mixed
(nn/window_moe.py) against its plain reference
(benchmark/references/window_moe_lm.py) at a small size on the CPU, the
two extents of its paged cache (serving/paging.py), the three kernels
its mixers use in interpret mode, and the new cell's files."""
import copy
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from benchmark import flops_window_moe as counts
from benchmark.references import window_moe_lm as ref
from bigdl_tpu.nn import latent, window_moe
from bigdl_tpu.ops import paged_kv
from bigdl_tpu.serving import DecodeEngine, paging

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S, F = "sliding_attention", "full_attention"
TINY = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=5,
            num_dense_layers=1, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16,
            layer_types=[S, S, S, S, F], sliding_window=8, num_experts=8,
            num_experts_per_tok=2, num_shared_experts=1, route_norm=True,
            route_scale=2.826, rms_norm_eps=1e-5, rope_theta=10000,
            mup_enabled=True)
# float32 program against the float32 reference: what differs is the
# order of the sums (logits of magnitude 3-4 agree to 1e-5); a product
# with operands rounded to bf16 moves them by 1e-2, so this tolerance
# fails any lower precision (test_bf16_products_fail_the_tolerance)
ATOL = 1e-4


def build(seed=0, **over):
    """Weights at the benchmark's scale (every matrix N(0, 1/fan_in),
    the embedding N(0, 1)), norm weights and the router's bias off
    their neutral values so that each of them matters."""
    cfg = dict(TINY, **over)
    model = window_moe.WindowMoETransformer(**cfg)
    var = model.init(jax.random.PRNGKey(seed))
    flat, tree = jax.tree_util.tree_flatten_with_path(var["params"])
    out = []
    for i, (path, leaf) in enumerate(flat):
        key = jax.random.fold_in(jax.random.PRNGKey(seed + 1), i)
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        noise = jax.random.normal(key, leaf.shape)
        if name == "embed/weight":
            out.append(noise)
        elif leaf.ndim >= 2:
            out.append(noise / np.sqrt(leaf.shape[-2]))
        elif name.endswith("router/bias"):
            out.append(0.05 * noise)
        else:
            out.append(1.0 + 0.1 * noise)
    var["params"] = jax.tree_util.tree_unflatten(tree, out)
    return model, var, cfg


@pytest.fixture(scope="module")
def tiny():
    return build()


def ids_of(seed, *shape, vocab=TINY["vocab_size"]):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape,
                                         0, vocab))


# ------------------------------------------------------------ the reference
def test_full_forward_logits_match_the_reference(tiny):
    model, var, cfg = tiny
    ids = ids_of(1, 2, 40)                  # five windows long
    got, _ = model.apply(var["params"], var["state"], ids)
    for row in range(2):
        want = ref.logits_fn(var["params"], ids[row], cfg)
        assert float(jnp.abs(want).max()) > 1.0
        np.testing.assert_allclose(got[row], want, atol=ATOL)


def test_bf16_products_fail_the_tolerance(tiny):
    """The nearest precision below the test's: the reference with its
    products' operands rounded to bf16 is outside ``ATOL``."""
    _, var, cfg = tiny
    ids = ids_of(1, 40)
    want = ref.logits_fn(var["params"], ids, cfg)
    low = ref.logits_fn(var["params"], ids, cfg, "bf16")
    assert float(jnp.abs(low - want).max()) > 10 * ATOL


def test_the_references_buffers_and_its_every_expert_form_agree(tiny):
    """An expert's buffer of ``ROOM`` mean loads gives what every expert
    over every token gives, where no expert got more; where one did,
    the layer says so and ``hidden`` computes it in full."""
    _, var, cfg = tiny
    x = jax.random.normal(jax.random.PRNGKey(3), (48, cfg["hidden_size"]))
    p = var["params"]["layer2"]["ffn"]
    whole, most = ref.routed(x, p, cfg, "reference", room=48)
    assert ref.room_for(48, cfg) == 36 >= int(most) > 12
    part, _ = ref.routed(x, p, cfg, "reference")
    np.testing.assert_allclose(part, whole, atol=1e-5)
    short, said = ref.routed(x, p, cfg, "reference", room=12)
    assert int(said) == int(most)
    assert float(jnp.abs(short - whole).max()) > 1e-2
    # a router bias that sends every token to experts 0 and 1: more
    # than the buffers hold, and the logits are still the model's
    model, var2, _ = build(seed=3)
    for i in range(1, 5):
        bias = var2["params"][f"layer{i}"]["ffn"]["router"]["bias"]
        var2["params"][f"layer{i}"]["ffn"]["router"]["bias"] = \
            bias.at[:2].add(5.0)
    ids = ids_of(9, 1, 64)
    got, _ = model.apply(var2["params"], var2["state"], ids)
    want = ref.logits_fn(var2["params"], ids[0], cfg)
    assert int(ref.routed(x, var2["params"]["layer2"]["ffn"], cfg,
                          "reference")[1]) == 48 > ref.room_for(48, cfg)
    np.testing.assert_allclose(got[0], want, atol=ATOL)


def test_the_band_and_the_missing_rotary_matter(tiny):
    """A full layer in a window layer's place (or the reverse) gives
    other logits: the reference tells the kinds apart."""
    model, var, cfg = tiny
    ids = ids_of(2, 40)
    want = ref.logits_fn(var["params"], ids, cfg)
    for kinds in ([S, S, S, S, S], [S, F, S, S, F]):
        other = ref.logits_fn(var["params"], ids,
                              dict(cfg, layer_types=kinds))
        assert float(jnp.abs(other - want).max()) > 100 * ATOL


@pytest.mark.parametrize("prompt,steps", [(9, 6), (16, 12)])
def test_prefill_then_decode_through_the_dense_cache(tiny, prompt, steps):
    model, var, cfg = tiny
    ids = ids_of(3, 2, prompt + steps)
    lengths = jnp.array([prompt, prompt - 2])
    cache = model.init_cache(2, 32)
    last, cache = model.prefill(var["params"], var["state"],
                                ids[:, :prompt], cache, lengths=lengths)
    full = [ref.logits_fn(var["params"], ids[0], cfg),
            ref.logits_fn(var["params"],
                          np.concatenate([ids[1, :prompt - 2],
                                          ids[1, prompt:]]), cfg)]
    np.testing.assert_allclose(last[0], full[0][prompt - 1], atol=ATOL)
    np.testing.assert_allclose(last[1], full[1][prompt - 3], atol=ATOL)
    for j in range(steps):
        logits, cache = model.decode_step(var["params"], var["state"],
                                          cache, ids[:, prompt + j])
        np.testing.assert_allclose(logits[0], full[0][prompt + j],
                                   atol=ATOL)
        np.testing.assert_allclose(logits[1], full[1][prompt - 2 + j],
                                   atol=ATOL)


@pytest.mark.parametrize("chunk", [5, 8, 12])
def test_chunks_that_straddle_the_window_equal_the_reference(tiny, chunk):
    """Chunked prefill on the staging cache, chunks shorter than,
    equal to and longer than the window of 8; the last chunk padded,
    and the head applied to the rows asked for only."""
    model, var, cfg = tiny
    total = 27
    ids = ids_of(4, 1, total)
    want = ref.logits_fn(var["params"], ids[0], cfg)
    cache = model.init_cache(1, 48)
    for lo in range(0, total, chunk):
        real = min(chunk, total - lo)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :real] = ids[0, lo:lo + real]
        logits, cache = model.extend(
            var["params"], var["state"], cache, padded,
            advance=jnp.array([real]), rows=jnp.array([[real - 1]]))
        assert logits.shape == (1, 1, cfg["vocab_size"])
        np.testing.assert_allclose(logits[0, 0], want[lo + real - 1],
                                   atol=ATOL)
    assert int(cache["layer4"]["length"][0]) == total


# ------------------------------------------------------- the two extents
def paged(model, var, slots=3, max_len=64, page=4, step=1):
    kv = paging.PagedCache(slots, max_len, page,
                           paging.default_num_pages(slots, max_len, page),
                           step=step)
    cache = kv.init_cache(model, jnp.float32)
    return kv, cache, kv.build_write()


def test_paged_decode_logits_and_what_a_window_layer_holds(tiny):
    """Prefill, the slot write, then the paged tick one token at a time
    to more than three windows past the prompt: the reference's logits
    at every position; meanwhile a window layer holds no more than
    ``window + step`` rows a slot (and a page of misalignment), the
    pages behind the band are unmapped and back on the free list."""
    model, var, cfg = tiny
    window, page, slot, prompt, steps = cfg["sliding_window"], 4, 1, 13, 30
    ids = ids_of(5, prompt + steps)
    want = ref.logits_fn(var["params"], ids, cfg)
    kv, cache, write = paged(model, var, page=page)
    band = kv.band
    assert band.pages_per_band == -(-window // page) + 1
    # the window layers' pools are the band's size, the full layer's the
    # worst case
    assert cache["layer0"]["k"].shape[0] == band.num_pages == 3 * 3 + 1
    assert cache["layer4"]["k"].shape[0] == kv.num_pages == 3 * 16 + 1
    dense = model.init_cache(1, 64)
    _, dense = model.prefill(var["params"], var["state"],
                             ids[None, :prompt], dense)
    assert kv.reserve(slot, prompt + 1)
    cache = write(cache, *kv.write_extra(slot), dense, 0, slot)
    active = np.arange(3) == slot
    tokens = np.zeros((3,), np.int32)
    for j in range(steps):
        length = prompt + j
        assert kv.reserve(slot, length + 1)
        # the band's pages and no other: rows [length - window + 1, ..]
        first = max(length + 1 - 1 - window + 1, 0) // page
        row = band.table[slot]
        assert not row[:first].any() and row[first:length // page + 1].all()
        assert band.owned(slot) == length // page + 1 - first \
            <= band.pages_per_band
        assert band.owned(slot) * page <= window + 1 + 2 * page
        assert band.pages_free + band.owned(slot) == band.num_pages - 1
        assert kv.owned(slot) == -(-(length + 1) // page)
        tokens[slot] = ids[length]
        logits, cache, counters = model.decode_step_paged(
            var["params"], var["state"], cache, kv.tick_extra()[0],
            jnp.asarray(tokens), jnp.asarray(active))
        np.testing.assert_allclose(logits[slot], want[length], atol=ATOL)
        assert counters["expert_tokens"].shape == (4, 8)
    assert kv.span_args() == {"pages_held": kv.owned(slot),
                              "window_pages_held": band.owned(slot)}
    kv.release(slot)
    assert band.pages_free == band.num_pages - 1 and not band.table.any()
    assert kv.resident_bytes() == 0


def test_a_reused_slot_reads_nothing_of_its_predecessor(tiny):
    """Fill a slot's pages, release, and serve another request from the
    same slot and physical pages: its logits are the reference's."""
    model, var, cfg = tiny
    kv, cache, write = paged(model, var, slots=1, max_len=32, page=4)
    active = jnp.ones((1,), bool)
    for seed, prompt, steps in ((6, 21, 9), (7, 3, 14)):
        ids = ids_of(seed, prompt + steps)
        want = ref.logits_fn(var["params"], ids, cfg)
        dense = model.init_cache(1, 32)
        _, dense = model.prefill(var["params"], var["state"],
                                 ids[None, :prompt], dense)
        assert kv.reserve(0, prompt + 1)
        cache = write(cache, *kv.write_extra(0), dense, 0, 0)
        for j in range(steps):
            assert kv.reserve(0, prompt + j + 1)
            logits, cache, _ = model.decode_step_paged(
                var["params"], var["state"], cache, kv.tick_extra()[0],
                jnp.asarray(ids[prompt + j:prompt + j + 1]), active)
            np.testing.assert_allclose(logits[0], want[prompt + j],
                                       atol=ATOL)
        kv.release(0)


def test_a_speculative_step_widens_the_band():
    """``step`` tokens a round (a draft's write-ahead) keep ``step - 1``
    rows more behind the band."""
    band = paging.BandAllocator(page_size=4, slots=2, max_len=64,
                                window=8, step=4)
    assert band.first_row(40) == 40 - 4 - 8 + 1
    assert band.ensure(1, 40) and band.owned(1) == 10 - 29 // 4
    assert band.ensure(1, 44) and not band.table[1, :33 // 4].any()
    assert band.owned(1) <= band.pages_per_band


# ------------------------------------------------------------------ engine
def greedy_by_reference(var, cfg, prompt, steps):
    ids = list(prompt)
    for _ in range(steps):
        ids.append(int(np.argmax(ref.logits_fn(
            var["params"], np.asarray(ids, np.int32), cfg)[-1])))
    return np.asarray(ids[len(prompt):], np.int32)


@pytest.fixture(scope="module")
def engine(tiny):
    model, var, _ = tiny
    eng = DecodeEngine(model, var, slots=3, max_len=64,
                       prompt_buckets=[8], prefill_batch_sizes=[1, 2],
                       kv_layout="paged", page_size=4, prefill_chunk=8)
    yield eng
    eng.close()


def test_engine_declares_and_compiles_its_programs(engine):
    # tick, prefill 1x8 and 2x8, write 1 and 2, the chunk
    assert engine.declared_programs() == 6
    assert engine.recompiles == 6
    assert engine._kv.tick_extra()[0].shape == (2, 3, 16)
    assert engine._kv.page_bytes == 1 * 2 * 4 * 32 * 4       # one full layer
    assert engine._kv.band_page_bytes == 4 * 2 * 4 * 32 * 4  # four window


@pytest.mark.parametrize("prompt_len,steps", [(5, 30), (8, 4), (19, 28),
                                              (30, 30)])
def test_engine_paged_tokens_are_the_references(tiny, engine, prompt_len,
                                                steps):
    """Bucketed prefill (<= 8) and chunked prefill (> 8: chunks of one
    window) into the two extents' pages, then the tick to more than
    three windows past the prompt: every served token is the
    reference's best at its position."""
    _, var, cfg = tiny
    prompt = ids_of(10 + prompt_len, prompt_len)
    got = engine.generate(prompt, steps, timeout=300)
    gaps = ref.served_gaps(var["params"], prompt, got, cfg, pad_to=8)
    assert gaps["gaps"].max() == 0.0
    assert engine.recompiles == 6
    assert engine._kv.pages_in_use == engine._kv.band.pages_in_use == 0


def test_engine_rows_in_one_tick_and_the_tick_spans_counters(tiny, engine):
    from bigdl_tpu.telemetry import get_tracer

    _, var, cfg = tiny
    tracer = get_tracer()
    prompts = [ids_of(40 + i, n) for i, n in enumerate((6, 13, 7))]
    tracer.clear()
    tracer.enable()
    try:
        futs = [engine.submit(p, 12) for p in prompts]
        got = [f.result(300) for f in futs]
    finally:
        tracer.disable()
    for p, g in zip(prompts, got):
        np.testing.assert_array_equal(
            g, greedy_by_reference(var, cfg, p, 12))
    ticks = [s.args for s in tracer.spans()
             if s.name == "loop/tick_dispatch"]
    assert ticks and all(
        0 < a["window_pages_held"] <= 3 * engine._kv.band.pages_per_band
        and a["pages_held"] >= a["window_pages_held"] - 3 for a in ticks)
    assert max(a["pages_held"] for a in ticks) > 9   # past the band
    traced = counts.traced_ticks({"traffic": {"page_size": 4}})
    assert traced and all(np.shape(t["expert_tokens"]) == (4, 8)
                          for t in traced)
    assert engine.metrics.snapshot()["window_pages_in_use"] == 0
    tracer.clear()


def test_one_extent_models_keep_their_cache_table_and_programs():
    """A model whose layers all keep every row gets what it had: one
    pool size, the (S, M) table, the same programs."""
    opt = nn.Transformer(vocab_size=50, hidden_size=32, num_heads=4,
                         filter_size=64, num_layers=2, dropout=0.0,
                         causal=True)
    routed = latent.LatentMoETransformer(
        vocab_size=96, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=2,
        first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=12, n_routed_experts=8, num_experts_per_tok=2)
    for model, leaves, declared in ((opt, ["k", "length", "v"], 3),
                                    (routed, ["latent", "length"], 4)):
        var = model.init(jax.random.PRNGKey(0))
        with DecodeEngine(model, var, slots=2, max_len=32,
                          prompt_buckets=[8], prefill_batch_sizes=[1],
                          kv_layout="paged", page_size=4,
                          prefill_chunk=8 if model is routed else None
                          ) as eng:
            kv = eng._kv
            assert eng.declared_programs() == eng.recompiles == declared
            assert kv.band is None and kv.band_page_bytes == 0
            assert kv.tick_extra()[0].shape == (2, 8)
            assert kv.write_extra(1)[0].shape == (8,)
            assert kv.span_args() == {"pages_held": 0}
            for pool in eng._target.cache.values():
                assert sorted(pool) == leaves
                assert pool[leaves[0]].shape[0] == kv.num_pages == 17
            got = eng.generate(ids_of(60, 7, vocab=50), 5, timeout=120)
            assert got.shape == (5,)


# ------------------------------------------------------------------ kernels
def _r16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


@pytest.mark.parametrize("lengths,firsts", [
    ((0, 5, 33, 64), None), ((64, 1, 17, 40), (50, 0, 9, 33)),
    ((64, 64, 20, 3), (63, 31, 16, 0))])
def test_paged_attn_grouped_heads_and_a_first_row(lengths, firsts):
    from bigdl_tpu.ops.pallas.paged_attention import paged_attn

    s, h, g, d, page, m = 4, 4, 2, 16, 16, 4
    rng = np.random.default_rng(0)
    pools = [jnp.asarray(rng.normal(size=(s * m + 1, page, g * d)),
                         jnp.float32) for _ in range(2)]
    table = jnp.asarray(
        rng.permutation(np.arange(1, s * m + 1)).reshape(s, m), jnp.int32)
    q = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
    kv_len = jnp.asarray(lengths, jnp.int32)
    first = None if firsts is None else jnp.asarray(firsts, jnp.int32)
    got = paged_attn(q, *pools, table, kv_len, first, num_heads=h,
                     kv_heads=g, sm_scale=0.2, pages_per_step=2,
                     interpret=True)
    k, v = (paged_kv.gather_pages(p, table, page).reshape(s, m * page, g, d)
            for p in pools)
    sc = jnp.einsum("sgrd,slgd->sgrl", _r16(q.reshape(s, g, h // g, d)),
                    _r16(k)) * 0.2
    pos = jnp.arange(m * page)[None, None, None, :]
    seen = pos < kv_len[:, None, None, None]
    if first is not None:
        seen &= pos >= first[:, None, None, None]
    p = jax.nn.softmax(jnp.where(seen, sc, -1e30), -1)
    want = jnp.einsum("sgrl,slgd->sgrd", _r16(p), _r16(v)).reshape(s, h, d)
    want = jnp.where((kv_len > 0)[:, None, None], want, 0.0)
    np.testing.assert_allclose(got, want, atol=3e-2, rtol=3e-2)
    assert not np.asarray(got[np.asarray(lengths) == 0]).any()


def test_paged_attn_with_a_head_each_is_what_it_was():
    """``kv_heads`` = ``num_heads``, no first row: the (S, 1, H*D)
    form the multi-head model calls, against the gathered path."""
    from bigdl_tpu.ops.pallas.paged_attention import paged_attn

    s, h, d, page, m = 3, 4, 32, 8, 4
    rng = np.random.default_rng(1)
    pools = [jnp.asarray(rng.normal(size=(s * m + 1, page, h * d)),
                         jnp.float32) for _ in range(2)]
    table = jnp.asarray(
        rng.permutation(np.arange(1, s * m + 1)).reshape(s, m), jnp.int32)
    q = jnp.asarray(rng.normal(size=(s, 1, h * d)), jnp.float32)
    kv_len = jnp.asarray((7, 32, 0), jnp.int32)
    got = paged_attn(q, *pools, table, kv_len, num_heads=h,
                     pages_per_step=2, interpret=True)
    k, v = (paged_kv.gather_pages(p, table, page).reshape(s, m * page, h, d)
            for p in pools)
    sc = jnp.einsum("shd,slhd->shl", _r16(q.reshape(s, h, d)), _r16(k)) \
        / np.sqrt(d)
    seen = jnp.arange(m * page)[None, None, :] < kv_len[:, None, None]
    p = jax.nn.softmax(jnp.where(seen, sc, -1e30), -1)
    want = jnp.einsum("shl,slhd->shd", _r16(p), _r16(v)).reshape(s, 1, -1)
    want = jnp.where((kv_len > 0)[:, None, None], want, 0.0)
    np.testing.assert_allclose(got, want, atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("window", [None, 5, 16])
@pytest.mark.parametrize("groups", [4, 2])
def test_flash_forward_with_a_band_and_grouped_heads(window, groups):
    from bigdl_tpu.ops.pallas.flash_attention import (
        band_attention_reference, flash_attention)

    b, h, t, d = 2, 4, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (b, h, t, d))
    k = jax.random.normal(ks[1], (b, groups, t, d))
    v = jax.random.normal(ks[2], (b, groups, t, d))
    if window is None and groups == h:
        pytest.skip("the plain causal kernel: tests/test_pallas_kernels")
    got = flash_attention(q, k, v, causal=True, sm_scale=0.3, block_q=8,
                          block_k=8, interpret=True, window=window)
    want = band_attention_reference(q, k, v, jnp.arange(t)[None], window,
                                    0.3)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the reference itself, by hand: a key is seen inside the band only
    sc = jnp.einsum("bhtd,bhsd->bhts", q * 0.3,
                    jnp.repeat(k, h // groups, axis=1))
    behind = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = (behind >= 0) & (behind < (window or t))
    hand = jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(
        jnp.where(seen, sc, -1e30), -1), jnp.repeat(v, h // groups, axis=1))
    np.testing.assert_allclose(want, hand, atol=2e-5)


@pytest.mark.parametrize("offset", [(0, 0), (24, 8), (7, 40)])
@pytest.mark.parametrize("window", [None, 8, 20])
def test_prefix_flash_with_a_band_an_offset_and_grouped_heads(offset,
                                                              window):
    from bigdl_tpu.ops.pallas.flash_attention import (
        band_attention_reference, prefix_flash_attention)

    b, h, g, t, s, d = 2, 4, 2, 16, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (b, h, t, d))
    k = jax.random.normal(ks[1], (b, g, s, d))
    v = jax.random.normal(ks[2], (b, g, s, d))
    off = jnp.asarray(offset, jnp.int32)
    got = prefix_flash_attention(q, k, v, off, sm_scale=0.3,
                                 blocks=(8, 16), window=window,
                                 interpret=True)
    want = band_attention_reference(
        q, k, v, off[:, None] + jnp.arange(t)[None], window, 0.3)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_band_leaves_blocks_out_of_the_grid():
    """Key blocks wholly outside the band are not steps of the grid:
    the banded kernel's innermost axis covers the band, not the
    extent."""
    import importlib

    fa = importlib.import_module("bigdl_tpu.ops.pallas.flash_attention")
    b, h, g, t, s, d = 1, 2, 1, 16, 256, 8
    q = jnp.ones((b, h, t, d))
    k = v = jnp.ones((b, g, s, d))
    off = jnp.asarray([200], jnp.int32)
    text = str(jax.make_jaxpr(lambda *a: fa.banded_flash_attention(
        *a, sm_scale=1.0, window=16, blocks=(8, 8), interpret=True))(
            q, k, v, off))
    # (16 + 8 - 2) // 8 + 2 = 4 key blocks a q block, of the extent's 32
    assert "grid=(1, 2, 4)" in text.replace("\n", "")
    full = str(jax.make_jaxpr(lambda *a: fa.banded_flash_attention(
        *a, sm_scale=1.0, window=None, blocks=(8, 8), interpret=True))(
            q, k, v, off))
    assert "grid=(1, 2, 32)" in full.replace("\n", "")


# ------------------------------------------------------------------- counts
def test_operations_and_bytes_against_a_hand_count():
    cfg = TINY
    d, hd, gd, w, ff, v = 64, 4 * 16, 2 * 16, 32, 96, 96
    attn = 3 * d * hd + 2 * d * gd
    expert = 3 * d * w
    once = 5 * attn + 3 * d * ff + 4 * (expert + d * 8) + d * v
    assert counts.resident_params(cfg) == {"read_every_tick": once,
                                           "one_expert": expert}
    model = window_moe.WindowMoETransformer(**cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    assert counts.parameter_count(cfg) == sum(
        int(np.prod(x.shape))
        for x in jax.tree_util.tree_leaves(shapes["params"]))
    # 3 rows, 100 rows held at the full layer, 20 inside the bands, 6
    # assignments a routed layer on 5 experts each
    got = counts.tick_cost(cfg, 3, 100, 20, 24, 20)
    kv = 2 * gd
    assert got["flops"] == 3 * 2 * once + (100 + 4 * 20) * 4 * 2 * 2 * 16 \
        + 24 * 2 * expert
    assert got["bytes"] == 2 * (once + 20 * expert + 3 * d) \
        + 2 * ((100 + 4 * 20) * kv + 5 * 3 * 2 * hd)
    one = counts.experts_cost(cfg, 6, 5)
    assert one == {"flops": 6 * 2 * expert,
                   "bytes": 2 * (5 * expert + 6 * 2 * d)}
    # a chunk of 4 rows behind 10: a window layer sees min(11.., 8) keys
    chunk = counts.chunk_cost(cfg, 4, 10)
    per_row = 5 * attn + 3 * d * ff + 4 * (2 * expert + expert + d * 8)
    keys = 1 * (11 + 12 + 13 + 14) + 4 * (4 * 8)
    assert chunk["flops"] == 4 * 2 * per_row + 2 * d * v \
        + 4 * 2 * 2 * 16 * keys


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def test_published_numbers_are_all_in_the_configuration_file():
    cell = load("configs", "trinity-mini-5of32.json")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Mini")
    assert cell["source"] == row["source_url"]
    assert cell["published"] == row["config"]
    assert cell["reduced"] == ["num_hidden_layers", "num_dense_layers",
                               "layer_types"]
    for key, value in row["config"].items():
        assert cell[key] == value or key in cell["reduced"], key
    for key in ("deployment", "reduced_how", "assumed", "precision",
                "model", "serve", "reference"):
        assert cell[key], key
    assert set(cell["reduced_how"]) == set(cell["reduced"])
    assert {"output_gate", "qk_norm", "rotary_on_window_layers_only",
            "four_norms", "embedding_scale", "weights", "max_len"} \
        <= set(cell["assumed"])
    # the model section: the published widths, all experts, the whole
    # vocabulary, one period of the pattern behind one dense layer
    for key, value in cell["model"].items():
        assert value == cell[key], key
    assert cell["model"]["layer_types"] == [S, S, S, S, F]
    assert cell["model"]["num_experts"] == 128
    assert cell["model"]["vocab_size"] == 200192


def test_the_configuration_builds_the_class_at_4_24_billion():
    from benchmark.drivers import decode_model

    cell = load("configs", "trinity-mini-5of32.json")
    model = decode_model.build_model(cell)
    assert isinstance(model, window_moe.WindowMoETransformer)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(shapes["params"]))
    assert n == counts.parameter_count(cell["model"])
    assert round(n / 1e9, 2) == 4.24 and round(2 * n / 2 ** 30, 2) == 7.9
    assert model.decode_extents() == {
        "layer0": 2048, "layer1": 2048, "layer2": 2048, "layer3": 2048,
        "layer4": None}
    # every leaf has an init rule, and each matrix gets 1/sqrt(fan_in)
    from benchmark import weights

    for path, leaf in zip(weights.leaf_paths(shapes), jax.tree_util.
                          tree_leaves(shapes)):
        kind, number = weights._rule_for(path, cell["serve"]["init"])
        if leaf.ndim >= 2 and "embed" not in path:
            assert kind == "normal" and number == pytest.approx(
                leaf.shape[-2] ** -0.5, rel=1e-4), path
        elif "embed" not in path:
            assert (kind, number) == ("const", 0.0 if path.endswith("bias")
                                      else 1.0), path


def test_the_cells_files_say_what_the_issue_asked_for():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"]
                if w["name"] == "trinity-decode-closed32")
    assert cell["chips"] == 1 and cell["config"] == "trinity-mini-5of32"
    mix = load("traffic", cell["traffic"] + ".json")
    assert mix["driver"] == "decode_model" and mix["clients"] \
        == mix["slots"] == mix["strata"] == 32
    assert (mix["max_len"], mix["prefill_chunk"], mix["prompt_buckets"],
            mix["prefill_batch_sizes"]) == (32768, 2048, [512, 2048], [1])
    assert mix["prompt_tokens"] == {"median": 4096, "sigma": 0.8,
                                    "min": 512, "max": 30720}
    assert mix["output_tokens"] == {"median": 384, "sigma": 0.7,
                                    "min": 64, "max": 1536}
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= mix["max_len"]
    assert (mix["supply_requests_per_s"], mix["lead_in_s"],
            mix["trace_seconds"], mix["check_requests"]) == (12.0, 16.0, 3,
                                                             4)
    assert mix["page_size"] % 16 == 0 and mix["page_size_why"]
    limits = load("cells", "trinity-decode-closed32.json")["limits"]
    assert set(limits) <= {"served_logit_gap", "served_mismatch_share",
                           "served_mean_gap"}
    listed = [m["name"] for m in bench["per_layer"]
              if "trinity-decode-closed32" in m.get("workloads", [])]
    assert sorted(listed) == sorted(
        [n + ".moe_serve" for n in (
            "tick_ms_p50", "device_idle_share", "prefill_device_share",
            "expert_load_max_over_mean")]
        + [n + ".swa_moe_serve" for n in (
            "tick_mfu", "tick_hbm_roofline", "paged_attn_roofline",
            "moe_experts_roofline", "window_rows_read_share")])
    for name in listed:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           name + ".py")), name
    # the K/V cache of both extents at the cell's size: at most 3.0 GiB,
    # where one extent for five layers would be 10.0
    row = 2 * 4 * 128 * 2
    full = paging.default_num_pages(32, 32768, mix["page_size"])
    band = paging.BandAllocator(mix["page_size"], 32, 32768, 2048, 1)
    held = (full + 4 * band.num_pages) * mix["page_size"] * row
    assert 2.4 < held / 2 ** 30 < 3.0
    assert 5 * full * mix["page_size"] * row / 2 ** 30 > 10.0


# --------------------------------------------------------------- benchmark
def tiny_cell():
    """The cell's own files at tiny widths (the published widths stay
    in the files): the driver runs end to end on the CPU in seconds."""
    config = load("configs", "trinity-mini-5of32.json")
    config["model"] = dict(TINY)
    config["serve"]["dtype"] = "float32"
    mix = load("traffic", "decode-mixedlen-closed32.json")
    mix.update(slots=4, clients=4, strata=4, supply_requests_per_s=800.0,
               max_len=96, page_size=8, prompt_buckets=[8, 16],
               prefill_chunk=16, lead_in_s=0.5,
               prompt_tokens={"median": 16, "sigma": 0.8, "min": 2,
                              "max": 48},
               output_tokens={"median": 12, "sigma": 0.7, "min": 2,
                              "max": 40})
    return copy.deepcopy({
        "name": "tiny-trinity", "chips": 1, "config": config,
        "traffic": mix, "limits": {"served_logit_gap": 1e-3,
                                   "served_mismatch_share": 0.02}})


def test_benchmark_driver_end_to_end_and_its_readers(monkeypatch):
    """``drivers/decode_model`` on the tiny cell: correct against the
    plain reference, chunked prompts and answers past the window among
    them; the fp8 control is not; the new readers read the traced
    ticks' counters and nothing without them."""
    from benchmark import check
    from benchmark.device import CompileCount
    from benchmark.drivers import decode_model
    from benchmark.run import read_metric
    from bigdl_tpu.telemetry import get_tracer

    cell = tiny_cell()
    device = {"platform": "cpu", "kind": "cpu", "count": 1,
              "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
    monkeypatch.setattr(ref, "PAD_TO", (32, 96))     # the cell's: 8192..
    monkeypatch.setattr(ref, "HEAD_ROWS", 8)
    tracer = get_tracer()
    tracer.clear()
    tracer.enable()
    try:
        run = decode_model.run(
            cell=cell, device=device, seed=2 ** 31 + 5, seconds=2.0,
            trace=False, t_start=time.perf_counter(),
            compiles=CompileCount(), control="fp8")
    finally:
        tracer.disable()
    verdict = check.judge(run["numbers"], cell["limits"], run["flags"])
    assert verdict["correct"], verdict
    assert run["attempted"] > 5 and run["failed"] == 0
    assert any(s.name == "loop/chunk_step" and s.args.get("tokens")
               for s in tracer.spans())
    assert not check.judge(run["control_numbers"], cell["limits"],
                           {})["correct"]
    run["trace"] = {"by_module": {"jit_tick": [2e-3, 2],
                                  "jit_chunk": [1e-3, 1]},
                    "busy_s": 4e-3, "window_s": 1.0}
    ops = [["paged_attn.1 tpu_custom_call",
            "attention/window/paged_attn", 4e-4],
           ["paged_attn.2 tpu_custom_call",
            "attention/full/paged_attn", 2e-4],
           ["fusion.7", "ffn/moe/experts", 1e-4],
           ["ragged-dot-none.1 tpu_custom_call", "-", 3e-4],
           ["fusion.1", "-", 6e-4]]
    run["program_ops"] = {"jit_tick": {"runs": 2, "ops": ops}}
    names = ("tick_mfu", "tick_hbm_roofline", "paged_attn_roofline",
             "moe_experts_roofline", "window_rows_read_share")
    got = {n: read_metric(n + ".swa_moe_serve", run) for n in names}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["window_rows_read_share"] <= 100.0
    for n in ("prefill_device_share", "expert_load_max_over_mean",
              "tick_ms_p50", "device_idle_share"):
        assert read_metric(n + ".moe_serve", run) is not None, n
    tick = counts.mean_tick(run)
    assert tick["window_rows"] <= tick["window_rows_held"] \
        <= tick["full_rows"] + 4 * 8
    # both kernels' calls are counted: without the full layer's the
    # same bytes stand against two thirds of the time
    run["program_ops"] = {"jit_tick": {"runs": 2, "ops": ops[:1] + ops[2:]}}
    assert read_metric("paged_attn_roofline.swa_moe_serve", run) \
        == pytest.approx(1.5 * got["paged_attn_roofline"])
    # a program that keeps one extent (the parent's): nothing to read
    for s in tracer.spans():
        if s.name == "loop/tick_dispatch" and s.args:
            s.args.pop("window_pages_held", None)
    for n in names:
        assert read_metric(n + ".swa_moe_serve", run) is None, n
    tracer.clear()
