"""The latent-attention decoder with routed experts (nn/latent.py,
nn/routed.py) against its plain reference
(benchmark/references/latent_moe_lm.py) at a small size on the CPU, and
the invariants the serving cut rests on: the two attention paths agree,
a row's output does not depend on its tick, and the ranks' shares of a
routed layer add up to the uncut layer."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from benchmark.references import latent_moe_lm as ref
from bigdl_tpu.nn import latent, routed
from bigdl_tpu.serving import DecodeEngine

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "rope_type": "yarn"}
TINY = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
            moe_intermediate_size=16, num_hidden_layers=3,
            first_k_dense_replace=1, num_attention_heads=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=12, n_routed_experts=16,
            num_experts_per_tok=4, n_group=4, topk_group=2,
            routed_scaling_factor=2.5, norm_topk_prob=True,
            n_shared_experts=1, rms_norm_eps=1e-6, rope_theta=100000,
            rope_scaling=YARN, num_nextn_predict_layers=1,
            experts_held=[0, 1, 2, 3, 8, 9])


def build(seed=0, **over):
    cfg = dict(TINY, **over)
    model = latent.LatentMoETransformer(**cfg)
    var = model.init(jax.random.PRNGKey(seed))
    # a router bias that matters, so choosing and weighing differ
    p = var["params"]
    for lk, layer in p.items():
        if isinstance(layer, dict) and "router" in layer.get("ffn", {}):
            layer["ffn"]["router"]["bias"] = 0.05 * jax.random.normal(
                jax.random.PRNGKey(seed + 7), (cfg["n_routed_experts"],))
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
    ids = ids_of(1, 2, 24)
    got, _ = model.apply(var["params"], var["state"], ids)
    for row in range(2):
        want = ref.logits_fn(var["params"], ids[row], cfg)
        np.testing.assert_allclose(got[row], want, atol=2e-5)


def test_multi_token_head_matches_the_reference(tiny):
    model, var, cfg = tiny
    ids = ids_of(2, 1, 16)
    _, got = model.apply_with_mtp(var["params"], var["state"], ids)
    want = ref.mtp_logits(var["params"], ids[0], cfg)
    assert got.shape == (1, 15, cfg["vocab_size"])
    np.testing.assert_allclose(got[0], want, atol=2e-5)


@pytest.mark.parametrize("prompt,steps", [(9, 6), (16, 3)])
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
    np.testing.assert_allclose(last[0], full[0][prompt - 1], atol=2e-5)
    np.testing.assert_allclose(last[1], full[1][prompt - 3], atol=2e-5)
    for j in range(steps):
        logits, cache = model.decode_step(var["params"], var["state"],
                                          cache, ids[:, prompt + j])
        np.testing.assert_allclose(logits[0], full[0][prompt + j],
                                   atol=2e-5)
        np.testing.assert_allclose(logits[1], full[1][prompt - 2 + j],
                                   atol=2e-5)


@pytest.mark.parametrize("chunk", [4, 8])
def test_chunked_prefill_equals_one_shot(tiny, chunk, monkeypatch):
    """Chunks go through the expanded path over blocks of the cache
    (forced here: a chunk of 4 would otherwise absorb)."""
    model, var, cfg = tiny
    monkeypatch.setattr(latent, "ABSORB_MAX_QUERY", 0)
    ids = ids_of(4, 1, 16)
    one, whole = model.prefill(var["params"], var["state"], ids,
                               model.init_cache(1, 32))
    cache = model.init_cache(1, 32)
    for lo in range(0, 16, chunk):
        logits, cache = model.extend(var["params"], var["state"], cache,
                                     ids[:, lo:lo + chunk])
    np.testing.assert_allclose(logits[0, -1], one[0], atol=2e-5)
    for lk in whole:
        np.testing.assert_allclose(cache[lk]["latent"][:, :, :16],
                                   whole[lk]["latent"][:, :, :16],
                                   atol=2e-5)
        assert int(cache[lk]["length"][0]) == 16


def test_padded_last_chunk_advances_by_its_true_count(tiny):
    model, var, cfg = tiny
    ids = ids_of(5, 1, 11)
    want = ref.logits_fn(var["params"], ids[0], cfg)
    cache = model.init_cache(1, 32)
    _, cache = model.extend(var["params"], var["state"], cache, ids[:, :8])
    padded = np.zeros((1, 8), np.int32)
    padded[0, :3] = ids[0, 8:]
    logits, cache = model.extend(var["params"], var["state"], cache, padded,
                                 advance=jnp.array([3]))
    np.testing.assert_allclose(logits[0, 2], want[10], atol=2e-5)
    assert int(cache["layer0"]["length"][0]) == 11


def test_absorbed_equals_expanded_attention(tiny):
    model, var, cfg = tiny
    mla, p = model.layers[0].mla, var["params"]["layer0"]["mla"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 5, cfg["hidden_size"]))
    rows = jax.random.normal(jax.random.PRNGKey(7),
                             (2, 32, mla.row_width))
    pos = jnp.array([[20, 21, 22, 23, 24], [3, 4, 5, 6, 7]])
    q_nope, q_pe = mla.queries(p, x, pos)
    a = mla.attend_absorbed(p, q_nope, q_pe, rows, pos)
    b = mla.attend_expanded(p, q_nope, q_pe, rows, pos)
    np.testing.assert_allclose(a, b, atol=2e-5)


def test_yarn_frequencies_and_scale_by_hand():
    """The published rope_scaling at rope width 64: dimensions 0-8 keep
    their frequency, 19-31 are divided by 64, a ramp between."""
    f = latent.yarn_inv_freq(64, 100000.0, YARN)
    base = lambda i: 100000.0 ** (-2 * i / 64)
    # 64 ln(4096 / (32 * 2 pi)) / (2 ln 1e5) = 8.38 -> 8; for 1: 18.01 -> 19
    assert math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                      / (2 * math.log(1e5))) == 8
    assert math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(1e5))) == 19
    np.testing.assert_allclose(f[:9], [base(i) for i in range(9)],
                               rtol=1e-12)
    np.testing.assert_allclose(f[19:], [base(i) / 64 for i in range(19, 32)],
                               rtol=1e-12)
    # dimension 12: ramp (12 - 8) / 11, so 7/11 of it keeps its frequency
    np.testing.assert_allclose(
        f[12], base(12) / 64 * (4 / 11) + base(12) * (7 / 11), rtol=1e-12)
    np.testing.assert_allclose(f, ref.inv_freq(
        dict(qk_rope_head_dim=64, rope_theta=100000, rope_scaling=YARN)),
        rtol=1e-12)
    mla = latent.LatentAttention(64, 2, 8, 8, 128, 64, 192, 100000.0, YARN)
    m = 0.1 * math.log(64) + 1.0
    assert abs(m - 1.4159) < 1e-4
    assert abs(mla.scale - 192 ** -0.5 * m * m) < 1e-12
    assert mla.rope_factor == 1.0


# ------------------------------------------------------------------ router
def crafted_router(scores, bias, **kw):
    """A layer whose router gives ``sigmoid^-1(scores)`` for the input
    ``e_0`` (first unit vector): the scores come out as crafted."""
    scores = np.asarray(scores, np.float64)
    n = scores.size
    layer = routed.RoutedExperts(4, 2, n, **kw)
    params = layer.init_params(jax.random.PRNGKey(0))
    w = np.zeros((4, n), np.float32)
    w[0] = np.log(scores / (1 - scores))
    params["router"] = {"weight": jnp.asarray(w),
                        "bias": jnp.asarray(bias, jnp.float32)}
    x = jnp.zeros((1, 4)).at[0, 0].set(1.0)
    ids, weights = layer.route(params, x)
    return np.asarray(ids[0]), np.asarray(weights[0]), params, layer


def test_bias_changes_the_choice_and_not_the_weight():
    scores = [0.9, 0.8, 0.5, 0.4]
    ids, w, _, _ = crafted_router(scores, [0, 0, 0, 0],
                                  experts_per_token=2,
                                  routed_scaling_factor=2.5)
    assert sorted(ids) == [0, 1]
    np.testing.assert_allclose(sorted(w), [2.5 * 0.8 / 1.7, 2.5 * 0.9 / 1.7],
                               rtol=1e-5)
    ids, w, params, _ = crafted_router(scores, [0, 0, 0.45, 0],
                                       experts_per_token=2,
                                       routed_scaling_factor=2.5)
    assert sorted(ids) == [0, 2]          # 0.5 + 0.45 beats 0.8
    got = dict(zip(ids.tolist(), w.tolist()))
    # ... and weighs by its raw score 0.5, not by 0.95
    np.testing.assert_allclose(got[2], 2.5 * 0.5 / 1.4, rtol=1e-5)
    np.testing.assert_allclose(got[0], 2.5 * 0.9 / 1.4, rtol=1e-5)
    want_ids, want_w = ref.route(
        jnp.zeros((1, 4)).at[0, 0].set(1.0), params["router"],
        dict(n_routed_experts=4, num_experts_per_tok=2,
             routed_scaling_factor=2.5), "reference")
    assert sorted(want_ids[0].tolist()) == [0, 2]
    np.testing.assert_allclose(sorted(want_w[0]), sorted(w), rtol=1e-5)


def test_group_limit_excludes_a_high_scorer_in_a_dropped_group():
    # groups of 2 by the sum of their two best: (0.6, 0.55) 1.15;
    # (0.95, 0.1) 1.05; (0.5, 0.45) 0.95; (0.3, 0.2) 0.5.  The highest
    # single score, 0.95, sits in the second-best group.
    scores = [0.6, 0.55, 0.95, 0.1, 0.5, 0.45, 0.3, 0.2]
    kw = dict(experts_per_token=2, n_group=4, routed_scaling_factor=1.0)
    ids, w, params, _ = crafted_router(scores, [0] * 8, topk_group=1, **kw)
    assert sorted(ids) == [0, 1]          # 0.95 is in a dropped group
    np.testing.assert_allclose(sorted(w), [0.55 / 1.15, 0.6 / 1.15],
                               rtol=1e-5)
    ids, _, _, _ = crafted_router(scores, [0] * 8, topk_group=2, **kw)
    assert sorted(ids) == [0, 2]          # with group 1 kept it wins
    want_ids, _ = ref.route(
        jnp.zeros((1, 4)).at[0, 0].set(1.0), params["router"],
        dict(n_routed_experts=8, num_experts_per_tok=2, n_group=4,
             topk_group=1), "reference")
    assert sorted(want_ids[0].tolist()) == [0, 1]


def moe_layer(held=None, n=8, seed=0):
    cfg = dict(hidden_size=16, moe_intermediate_size=8, n_routed_experts=n,
               num_experts_per_tok=3, n_group=4, topk_group=2,
               routed_scaling_factor=2.5, norm_topk_prob=True,
               experts_held=held)
    layer = routed.RoutedExperts(16, 8, n, 3, 4, 2, 2.5, True, 1, held)
    whole = routed.RoutedExperts(16, 8, n, 3, 4, 2, 2.5, True, 1)
    params = whole.init_params(jax.random.PRNGKey(seed))
    params["router"]["bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), (n,))
    if held is not None:
        params = dict(params, experts={
            k: v[np.asarray(held)] for k, v in params["experts"].items()})
    return layer, params, cfg


def test_the_shares_add_up_to_the_uncut_layer():
    """4 ranks x 2 experts: the routed parts of all ranks plus the
    shared expert once equal the uncut reference layer."""
    x = jax.random.normal(jax.random.PRNGKey(3), (12, 16))
    _, whole_params, cfg = moe_layer()
    want = ref.routed(x, whole_params, cfg, "reference")
    total = 0.0
    for rank in range(4):
        held = [2 * rank, 2 * rank + 1]
        layer, params, rank_cfg = moe_layer(held)
        part, counts = layer.apply_counted(params, x, include_shared=False)
        np.testing.assert_allclose(
            part, ref.routed(x, params, rank_cfg, "reference",
                             shared=False), atol=2e-5)
        total = total + part
    shared = routed.gated_ffn(x, whole_params["shared"])
    np.testing.assert_allclose(total + shared, want, atol=2e-5)
    # a rank with the shared expert is the program's serving cut
    layer, params, rank_cfg = moe_layer([0, 1])
    np.testing.assert_allclose(
        layer.apply(params, {}, x)[0],
        ref.routed(x, params, rank_cfg, "reference"), atol=2e-5)


@pytest.mark.parametrize("held", [[0, 1], None], ids=["likely", "full"])
def test_a_long_batch_is_dropless_through_either_buffer(held):
    """768 assignments: with 2 of 8 experts held about 190 land here
    and the short buffer (2 rows a token) runs; with all held all 768
    do and the full buffer runs.  Both are the reference's sum."""
    layer, params, cfg = moe_layer(held)
    x = jax.random.normal(jax.random.PRNGKey(8), (256, 16))
    assert 256 * layer.k > routed.SHORT_BATCH_ROWS
    got, counts = jax.jit(lambda p, x: layer.apply_counted(p, x))(params, x)
    landed = int(counts.sum())
    assert (landed <= routed.LIKELY_ROWS_PER_TOKEN * 256) == (held is not None)
    np.testing.assert_allclose(got, ref.routed(x, params, cfg, "reference"),
                               atol=2e-5)


def test_a_rows_output_is_the_same_alone_and_in_a_full_tick():
    layer, params, _ = moe_layer([0, 1, 4, 5])
    x = jax.random.normal(jax.random.PRNGKey(4), (16, 16))
    full, counts = layer.apply_counted(params, x)
    ids, _ = layer.route(params, x)
    for row in (0, 7, 15):
        # the same experts with the same weights; the products of a
        # 1-row and a 16-row matmul differ in the last bit on the CPU
        alone, _ = layer.apply_counted(params, x[row:row + 1])
        np.testing.assert_allclose(alone[0], full[row], rtol=1e-5,
                                   atol=1e-9)
        np.testing.assert_array_equal(
            layer.route(params, x[row:row + 1])[0][0], ids[row])
    for local, e in enumerate(layer.experts_held):
        assert int(counts[local]) == int((np.asarray(ids) == e).sum())


def test_rows_that_do_not_count_reach_no_expert():
    layer, params, _ = moe_layer([0, 1, 4, 5])
    x = jax.random.normal(jax.random.PRNGKey(5), (8, 16))
    rows = jnp.arange(8) < 3
    y, counts = layer.apply_counted(params, x, rows=rows,
                                    include_shared=False)
    ids, _ = layer.route(params, x[:3])
    assert int(counts.sum()) == int(np.isin(np.asarray(ids),
                                            layer.experts_held).sum())
    assert not np.asarray(y[3:]).any()
    np.testing.assert_allclose(
        y[:3], layer.apply_counted(params, x[:3],
                                   include_shared=False)[0],
        rtol=1e-5, atol=1e-9)


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
    eng = DecodeEngine(model, var, slots=3, max_len=48,
                       prompt_buckets=[8], prefill_batch_sizes=[1, 2],
                       kv_layout="paged", page_size=4, prefill_chunk=8)
    yield eng
    eng.close()


def test_engine_declares_and_compiles_its_programs(engine):
    # tick, prefill 1x8 and 2x8, write 1 and 2, the chunk
    assert engine.declared_programs() == 6
    assert engine.recompiles == 6
    pool = engine._target.cache["layer0"]
    assert sorted(pool) == ["latent", "length"]
    assert pool["latent"].shape[1:] == (4, 128)    # 16 + 4 in whole lanes
    assert engine._kv.page_bytes == 3 * 4 * 128 * 4


@pytest.mark.parametrize("prompt_len,steps", [(5, 6), (8, 4), (19, 5),
                                              (30, 3)])
def test_engine_paged_tokens_are_the_references(tiny, engine, prompt_len,
                                                steps):
    """Bucketed prefill (<= 8) and chunked prefill (> 8) into latent
    pages, then the absorbed tick: every served token is the
    reference's best at its position."""
    _, var, cfg = tiny
    prompt = ids_of(10 + prompt_len, prompt_len)
    got = engine.generate(prompt, steps, timeout=120)
    np.testing.assert_array_equal(
        got, greedy_by_reference(var, cfg, prompt, steps))
    gaps = ref.served_gaps(var["params"], prompt, got, cfg, pad_to=8)
    assert gaps["gaps"].max() == 0.0
    assert engine.recompiles == 6


def test_engine_rows_in_one_tick_do_not_depend_on_each_other(tiny, engine):
    _, var, cfg = tiny
    prompts = [ids_of(40 + i, n) for i, n in enumerate((6, 13, 7))]
    futs = [engine.submit(p, 5) for p in prompts]
    for p, f in zip(prompts, futs):
        np.testing.assert_array_equal(
            f.result(120), greedy_by_reference(var, cfg, p, 5))


def test_tick_counters_reach_the_dispatch_span_only_while_tracing(tiny):
    from bigdl_tpu.telemetry import get_tracer

    model, var, cfg = tiny
    tracer = get_tracer()
    with DecodeEngine(model, var, slots=2, max_len=32, prompt_buckets=[8],
                      prefill_batch_sizes=[1], kv_layout="paged",
                      page_size=4, prefill_chunk=8) as eng:
        tracer.clear()
        tracer.enable()
        try:
            eng.generate(ids_of(50, 12), 4, timeout=120)
        finally:
            tracer.disable()
        spans = tracer.spans()
    ticks = [s for s in spans if s.name == "loop/tick_dispatch"]
    assert ticks and all("pages_held" in s.args for s in ticks)
    held = len(cfg["experts_held"])
    for s in ticks:
        counts = np.asarray(s.args["expert_tokens"])
        assert counts.shape == (2, held)      # two routed layers
        assert counts.sum() <= 2 * cfg["num_experts_per_tok"]
    chunks = [s.args["tokens"] for s in spans
              if s.name == "loop/chunk_step"]
    assert sorted(c for c in chunks if c) == [4, 8]
    tracer.clear()


@pytest.mark.parametrize("session", ["whole", "under_way"])
def test_a_ticks_counters_sit_on_its_own_dispatch_span(
        tiny, read_each_tick_first, until, session):
    """With one tick in flight a tick is read a turn after it was
    enqueued.  ``whole``: traced from the first tick, its counters are
    those of the same tick in a loop that reads each tick before the
    next is enqueued.  ``under_way``: the tracer turns on and off under
    decoding requests (a tick enqueued dark is read lit, the last lit
    one is read dark): the tokens are the untraced engine's, every lit
    tick carries its counters and ``traced_ticks`` reads them."""
    from benchmark.flops_latent_moe import traced_ticks
    from bigdl_tpu.telemetry import get_tracer

    model, var, _ = tiny
    tracer = get_tracer()
    prompts, budget = [ids_of(60, 6), ids_of(61, 5)], [40, 25]

    def engine():
        # started by hand: both requests are admitted in the first turn
        return DecodeEngine(model, var, slots=2, max_len=48,
                            prompt_buckets=[8], prefill_batch_sizes=[1],
                            kv_layout="paged", page_size=4, start=False)

    def decode(eng, between=lambda futs: None):
        futs = [eng.submit(p, n) for p, n in zip(prompts, budget)]
        eng.start()
        between(futs)
        return [list(f.result(120)) for f in futs]

    def lit():
        return [s for s in tracer.spans() if s.name == "loop/tick_dispatch"]

    tracer.disable()
    tracer.clear()
    try:
        if session == "whole":
            with engine() as eng:
                tracer.enable()
                got = decode(eng)
                tracer.disable()
            ours = [s.args for s in lit()]
            tracer.clear()
            read_each_tick_first()
            with engine() as eng:
                tracer.enable()
                want = decode(eng)
                tracer.disable()
            theirs = [s.args for s in lit()]
            assert got == want and len(ours) == budget[0] - 1
            assert sum(a["in_flight"] for a in ours) >= len(ours) - 2
            assert not any(a["in_flight"] for a in theirs)
            # the pages are the pool's at dispatch: a row whose last
            # token is in flight still holds its own for that one tick
            assert all(a.pop("pages_held") >= b.pop("pages_held")
                       for a, b in zip(ours, theirs))
            for a in ours + theirs:
                a.pop("in_flight")
            assert ours == theirs
            return
        with engine() as eng:
            want = decode(eng)
        with engine() as eng:
            def flip(futs):
                until(lambda: eng.metrics.decoded_tokens >= 6)
                tracer.enable()
                until(lambda: len(lit()) >= 8)
                tracer.disable()
                assert not all(f.done() for f in futs)

            assert decode(eng, flip) == want
        ticks = lit()
        assert ticks[0].args["in_flight"] == 1
        assert all(np.shape(s.args["expert_tokens"]) == (2, 6)
                   and "pages_held" in s.args for s in ticks)
        traced = traced_ticks({"traffic": {"page_size": 4}})
        assert len(traced) >= len(ticks) - 1
        assert [t["expert_tokens"] for t in traced] == \
            [s.args["expert_tokens"] for s in ticks[:len(traced)]]
    finally:
        tracer.disable()
        tracer.clear()


def test_opt_tokens_unchanged_through_the_generalised_page_code():
    """The multi-head model's paged engine (K and V leaves, allocated
    and written by its declaration) still serves what its uncached
    forward puts first."""
    model = nn.Transformer(vocab_size=50, hidden_size=32, num_heads=4,
                           filter_size=64, num_layers=2, dropout=0.0,
                           causal=True)
    var = model.init(jax.random.PRNGKey(0))
    assert model._children[3].mha.decode_state() == {"k": (4, 8),
                                                      "v": (4, 8)}
    prompt = ids_of(60, 7, vocab=50)
    with DecodeEngine(model, var, slots=2, max_len=32, prompt_buckets=[8],
                      prefill_batch_sizes=[1], kv_layout="paged",
                      page_size=4) as eng:
        assert sorted(eng._target.cache["layer0"]) == ["k", "length", "v"]
        got = eng.generate(prompt, 6, timeout=120)
    ids = list(prompt)
    for _ in range(6):
        logits, _ = model.apply(var["params"], var["state"],
                                np.asarray([ids]))
        ids.append(int(np.argmax(logits[0, -1])))
    np.testing.assert_array_equal(got, ids[7:])


# ------------------------------------------------------------------ kernel
@pytest.mark.parametrize("lengths", [(0, 5, 33, 64), (64, 1, 17, 16)])
def test_latent_page_kernel_matches_the_gathered_path(lengths):
    from bigdl_tpu.ops import paged_kv
    from bigdl_tpu.ops.pallas.latent_attention import latent_paged_attn

    s, h, c, vw, page, m = 4, 8, 128 + 64, 128, 16, 4
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(s * m + 1, page, c)), jnp.float32)
    table = jnp.asarray(
        rng.permutation(np.arange(1, s * m + 1)).reshape(s, m), jnp.int32)
    q = jnp.asarray(rng.normal(size=(s, h, c)), jnp.float32)
    kv_len = jnp.asarray(lengths, jnp.int32)
    got = latent_paged_attn(q, pool, table, kv_len, value_width=vw,
                            sm_scale=0.1, pages_per_step=2, interpret=True)
    rows = paged_kv.gather_pages(pool, table, page)
    r16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    sc = jnp.einsum("shc,slc->shl", r16(q), r16(rows)) * 0.1
    seen = jnp.arange(m * page)[None, None, :] < kv_len[:, None, None]
    p = jax.nn.softmax(jnp.where(seen, sc, -1e30), -1)
    want = jnp.einsum("shl,slv->shv", r16(p), r16(rows[..., :vw]))
    want = jnp.where((kv_len > 0)[:, None, None], want, 0.0)
    np.testing.assert_allclose(got, want, atol=3e-2, rtol=3e-2)
    assert not np.asarray(got[np.asarray(lengths) == 0]).any()


# --------------------------------------------------------------- benchmark
def tiny_cell():
    """The cell's own files at tiny widths (the published widths stay
    in the files): the driver runs end to end on the CPU in seconds."""
    import copy
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def load(*parts):
        with open(os.path.join(root, "benchmark", *parts)) as f:
            return json.load(f)

    config = load("configs", "gigachat3.1-702b-ep16share.json")
    config["model"] = dict(TINY, num_nextn_predict_layers=0)
    config["serve"]["dtype"] = "float32"
    mix = load("traffic", "decode-longprompt.json")
    mix.update(slots=4, max_len=64, page_size=8, prompt_buckets=[8, 16],
               prefill_chunk=16, rate=12.0, lead_in_s=0.5,
               prompt_tokens={"median": 16, "sigma": 0.8, "min": 2,
                              "max": 40},
               output_tokens={"median": 6, "sigma": 0.7, "min": 2,
                              "max": 16})
    return copy.deepcopy({
        "name": "tiny-gigachat", "chips": 1, "config": config,
        "traffic": mix, "limits": {"served_logit_gap": 1e-4,
                                   "served_mismatch_share": 0.02}})


def test_published_numbers_are_all_in_the_configuration_file():
    import json

    cell = tiny_cell()["config"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = json.loads(f.readline())
    assert row["name"] == "GigaChat3.1-702B-A36B"
    assert cell["source"] == row["source_url"]
    assert cell["published"] == row["config"]
    for key, value in row["config"].items():
        assert cell[key] == value or key in cell["reduced"], key
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
              "num_attention_heads")
    assert not set(widths) & set(cell["reduced"])


def test_benchmark_driver_end_to_end_and_its_readers(monkeypatch):
    """``drivers/decode_model`` on the tiny cell: correct against the
    plain reference, chunked prompts among them; the fp8 control is not;
    the new readers read the traced ticks' counters."""
    import time

    from benchmark import check
    from benchmark.device import CompileCount
    from benchmark.drivers import decode_model
    from benchmark.run import read_metric
    from bigdl_tpu.telemetry import get_tracer

    cell = tiny_cell()
    device = {"platform": "cpu", "kind": "cpu", "count": 1,
              "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
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
    assert run["numbers"]["served_mismatch_share"] == 0.0
    assert run["control_numbers"]["served_mismatch_share"] > 0.02
    # what a traced run's readers see: the ticks' spans, and a device
    # trace stood in by hand (no device here)
    run["trace"] = {"by_module": {"jit_tick": [2e-3, 2],
                                  "jit_chunk": [1e-3, 1]},
                    "busy_s": 4e-3, "window_s": 1.0}
    ops = [["latent_paged_attn.1 tpu_custom_call",
            "attention/mla_attention/latent_paged_attn", 6e-4],
           ["fusion.7", "ffn/moe/experts", 1e-4],
           ["ragged-dot-none.1 tpu_custom_call", "-", 3e-4],
           ["fusion.1", "-", 6e-4]]
    run["program_ops"] = {"jit_tick": {"runs": 2, "ops": ops}}
    got = {name: read_metric(name + ".moe_serve", run) for name in (
        "tick_mfu", "tick_hbm_roofline", "mla_decode_roofline",
        "moe_experts_roofline", "prefill_device_share",
        "expert_load_max_over_mean")}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["prefill_device_share"] == pytest.approx(25.0)
    with_kernel = got["moe_experts_roofline"]
    # a grouped kernel that kept its scope is still counted once
    ops[2][1] = "ffn/moe/experts"
    assert read_metric("moe_experts_roofline.moe_serve", run) \
        == pytest.approx(with_kernel)
    run["program_ops"] = {"jit_tick": {"runs": 2, "ops": ops[:2]}}
    assert read_metric("moe_experts_roofline.moe_serve", run) \
        == pytest.approx(4 * with_kernel)     # 1e-4 against 1e-4 + 3e-4
    assert got["expert_load_max_over_mean"] >= 1.0
    # a program without the counters or the scopes reads nothing
    tracer.clear()
    run["program_ops"] = {}
    for name in ("tick_mfu", "tick_hbm_roofline", "mla_decode_roofline",
                 "moe_experts_roofline", "expert_load_max_over_mean"):
        assert read_metric(name + ".moe_serve", run) is None


@pytest.mark.parametrize("offset", [(0, 0), (24, 8), (7, 40)])
def test_prefix_flash_kernel_matches_the_masked_softmax(offset):
    from bigdl_tpu.ops.pallas.flash_attention import prefix_flash_attention

    b, h, t, s, d = 2, 3, 16, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (b, h, t, d))
    k = jax.random.normal(ks[1], (b, h, s, d))
    v = jax.random.normal(ks[2], (b, h, s, d))
    off = jnp.asarray(offset, jnp.int32)
    got = prefix_flash_attention(q, k, v, off, sm_scale=0.3, blocks=(8, 16),
                                 interpret=True)
    sc = jnp.einsum("bhtd,bhsd->bhts", q * 0.3, k)
    seen = (jnp.arange(s)[None, None, None, :]
            <= (off[:, None] + jnp.arange(t)[None])[:, None, :, None])
    want = jnp.einsum("bhts,bhsd->bhtd",
                      jax.nn.softmax(jnp.where(seen, sc, -1e30), -1), v)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_operations_are_attributed_to_the_program_run_they_fall_in():
    """On the benchmark's recorded trace: every device operation lies in
    the one run of ``jit_train_step``, once."""
    import os

    from benchmark import trace_reduce, trace_scopes

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "benchmark", "fixtures", "tiny.xplane.txt")
    got = trace_scopes.read(path)
    assert list(got) == ["jit_train_step"]
    assert got["jit_train_step"]["runs"] == 1
    ops = got["jit_train_step"]["ops"]
    events = trace_reduce.device_events(trace_reduce.load(path))[0]
    assert {name for name, _, _ in ops} <= {name for _, _, name in events}
    assert all(scope == "-" for _, scope, _ in ops)  # a text fixture
    t0, t1 = trace_reduce.window_of({0: events})
    assert 0 < sum(sec for _, _, sec in ops) \
        <= trace_reduce.busy_seconds(events, t0, t1) * (1 + 1e-9)
    assert trace_scopes.seconds_per_run(got, "train_step", kernel="fusion")
    assert trace_scopes.seconds_per_run(got, "tick", kernel="fusion") is None


def test_scopes_are_read_from_the_xplane_wire_format(tmp_path):
    """``tf_op`` of an event's metadata, by string value and by
    reference, on a hand-made XSpace; the scope path below the jit
    frames."""
    from benchmark import trace_scopes
    from bigdl_tpu.interop import protowire as pw

    def entry(key, message):  # one map entry: key = 1, value = 2
        return pw.enc_int(1, key) + pw.enc_bytes(2, message)

    stat_meta = [entry(1, pw.enc_int(1, 1) + pw.enc_str(2, "tf_op")),
                 entry(2, pw.enc_int(1, 2) + pw.enc_str(
                     2, "jit(tick)/ffn/moe/experts/dot_general"))]
    event_meta = [
        entry(1, pw.enc_int(1, 1) + pw.enc_str(2, "%fusion.1 = f32[] x")
              + pw.enc_bytes(5, pw.enc_int(1, 1) + pw.enc_str(
                  5, "jit(tick)/jit(main)/attention/mla_attention/mul"))),
        entry(2, pw.enc_int(1, 2) + pw.enc_str(2, "%dot.2 = f32[] y")
              + pw.enc_bytes(5, pw.enc_int(1, 1) + pw.enc_int(7, 2))),
        entry(3, pw.enc_int(1, 3) + pw.enc_str(2, "%copy.3 = f32[] z"))]
    plane = pw.enc_str(2, "/device:TPU:0") \
        + b"".join(pw.enc_bytes(4, e) for e in event_meta) \
        + b"".join(pw.enc_bytes(5, e) for e in stat_meta)
    host = pw.enc_str(2, "/host:CPU")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(pw.enc_bytes(1, host) + pw.enc_bytes(1, plane))
    got = trace_scopes.op_scopes(str(path))
    assert got == {
        "%fusion.1 = f32[] x":
        "jit(tick)/jit(main)/attention/mla_attention/mul",
        "%dot.2 = f32[] y": "jit(tick)/ffn/moe/experts/dot_general"}
    assert trace_scopes.scope_of(got["%fusion.1 = f32[] x"]) \
        == "attention/mla_attention"
    assert trace_scopes.scope_of(got["%dot.2 = f32[] y"]) \
        == "ffn/moe/experts"
    assert trace_scopes.scope_of("jit(tick)/while/body/add") == "-"
