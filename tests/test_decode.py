"""Cached incremental decoding + continuous batching (ISSUE 4
tentpole; docs/decoding.md):

* numerics: prefill / per-step decode logits allclose to the uncached
  causal forward (greedy and beam), for the Transformer LM and the
  Seq2Seq LSTM decoder — the cached path must be a pure perf change;
* SequenceBeamSearch threads dict-valued caches (beam tiling +
  ``_gather_beams`` on leaves with extra trailing dims) correctly;
* the ``DecodeEngine`` slot grid: greedy outputs match the direct
  rollout, retirement on EOS / token budget / deadline, slot reuse at
  token granularity, recompile counter flat across occupancy churn;
* the CPU A/B acceptance gate — ``bench.decode_ab``: cached decode
  >= 3x the re-forward ``generate`` at T >= 128, continuous batching
  beats static run-to-completion batching, zero steady-state
  recompiles.
"""
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu import models
from bigdl_tpu.serving import DecodeEngine
from bigdl_tpu.serving.engine import (
    DeadlineExceededError,
    EngineClosedError,
    QueueFullError,
)

VOCAB = 24


def _lm(vocab=VOCAB, hidden=32, heads=2, filt=64, layers=2):
    return nn.Transformer(vocab_size=vocab, hidden_size=hidden,
                          num_heads=heads, filter_size=filt,
                          num_layers=layers, dropout=0.0, causal=True)


@pytest.fixture(scope="module")
def lm():
    model = _lm()
    var = model.init(jax.random.PRNGKey(0))
    return model, var


def _direct_greedy(model, var, prompt, n_new):
    """Greedy rollout via the uncached full forward — the oracle."""
    p, s = var["params"], var["state"]
    ids = list(int(t) for t in prompt)
    out = []
    for _ in range(n_new):
        logits, _ = model.apply(p, s, jnp.asarray([ids]), training=False)
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
        ids.append(tok)
    return out


# ------------------------------------------------------- numerics parity
def test_prefill_logits_match_uncached_forward(lm):
    model, var = lm
    p, s = var["params"], var["state"]
    ids = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, (2, 9)))
    full, _ = model.apply(p, s, ids, training=False)
    cache = model.init_cache(2, 16)
    last, cache = model.prefill(p, s, ids, cache)
    np.testing.assert_allclose(np.asarray(last), np.asarray(full[:, -1]),
                               rtol=1e-5, atol=1e-5)
    for lk in ("layer0", "layer1"):
        np.testing.assert_array_equal(np.asarray(cache[lk]["length"]),
                                      [9, 9])


def test_prefill_ragged_lengths_match_per_row_forward(lm):
    """Padded prompt rows with per-row true lengths: each row's
    next-token logits equal the forward over just its own prefix."""
    model, var = lm
    p, s = var["params"], var["state"]
    ids = jnp.asarray(np.random.RandomState(1).randint(0, VOCAB, (2, 8)))
    cache = model.init_cache(2, 16)
    last, cache = model.prefill(p, s, ids, cache,
                                lengths=jnp.asarray([3, 7]))
    for row, t in ((0, 3), (1, 7)):
        full, _ = model.apply(p, s, ids[row:row + 1, :t], training=False)
        np.testing.assert_allclose(np.asarray(last[row]),
                                   np.asarray(full[0, -1]),
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(cache["layer0"]["length"]), [3, 7])


def test_decode_step_logits_match_uncached_forward_per_step(lm):
    """The acceptance criterion: per-step cached logits allclose to the
    uncached causal forward over the growing prefix (greedy chain)."""
    model, var = lm
    p, s = var["params"], var["state"]
    rs = np.random.RandomState(2)
    ids = jnp.asarray(rs.randint(0, VOCAB, (2, 5)))
    cache = model.init_cache(2, 16)
    logits, cache = model.prefill(p, s, ids, cache)
    cur = ids
    for _ in range(6):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits, cache = model.decode_step(p, s, cache, tok)
        cur = jnp.concatenate([cur, tok[:, None]], axis=1)
        full, _ = model.apply(p, s, cur, training=False)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, -1]),
                                   rtol=1e-4, atol=1e-5)


def test_transformer_generate_cached_matches_uncached_beam(lm):
    """Cached beam search returns the identical sequences and scores to
    the seed re-forward path (the beam acceptance criterion)."""
    model, var = lm
    p, s = var["params"], var["state"]
    start = jnp.zeros((2,), jnp.int32)
    sc, vc = model.generate(p, s, start, 10, beam_size=3, use_cache=True)
    su, vu = model.generate(p, s, start, 10, beam_size=3,
                            use_cache=False)
    np.testing.assert_array_equal(np.asarray(sc), np.asarray(su))
    np.testing.assert_allclose(np.asarray(vc), np.asarray(vu),
                               rtol=1e-4, atol=1e-5)


def test_transformer_generate_cached_greedy_matches_manual_rollout(lm):
    model, var = lm
    p, s = var["params"], var["state"]
    t_max = 8
    seqs, _ = model.generate(p, s, jnp.asarray([1], jnp.int32), t_max,
                             beam_size=1, eos_id=VOCAB - 1,
                             use_cache=True)
    want = _direct_greedy(model, var, [1], t_max)
    got = list(np.asarray(seqs[0, 0, 1:]))
    for w, g in zip(want, got):
        assert w == g
        if w == VOCAB - 1:
            break


def test_seq2seq_generate_cached_matches_uncached():
    m = models.Seq2Seq(src_vocab=8, tgt_vocab=10, embedding_size=8,
                       hidden_size=12)
    v = m.init(jax.random.PRNGKey(0))
    src = jnp.asarray(np.random.RandomState(0).randint(0, 8, (2, 5)))
    sc, vc = m.generate(v["params"], v["state"], src, 5, beam_size=3,
                        alpha=0.0, use_cache=True)
    su, vu = m.generate(v["params"], v["state"], src, 5, beam_size=3,
                        alpha=0.0, use_cache=False)
    np.testing.assert_array_equal(np.asarray(sc), np.asarray(su))
    np.testing.assert_allclose(np.asarray(vc), np.asarray(vu),
                               rtol=1e-4, atol=1e-5)


def test_seq2seq_decode_step_matches_teacher_forcing():
    """Stepping the decoder LSTM through the cache reproduces the
    teacher-forcing decoder's per-position logits exactly."""
    m = models.Seq2Seq(src_vocab=8, tgt_vocab=10, embedding_size=8,
                       hidden_size=12)
    v = m.init(jax.random.PRNGKey(1))
    p, s = v["params"], v["state"]
    rs = np.random.RandomState(3)
    src = jnp.asarray(rs.randint(0, 8, (2, 5)))
    tgt = jnp.asarray(rs.randint(0, 10, (2, 6)))
    full, _ = m.apply(p, s, (src, tgt), training=False)  # (2, 6, 10)

    updates: dict = {}
    enc_in = m._run("src_embed", src, p, s, updates, False, None)
    enc = m._run("encoder", enc_in, p, s, updates, False, None)
    cache = m.init_decode_cache(enc)
    for t in range(6):
        logits, cache = m.decode_step(p, s, cache, tgt[:, t])
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, t]),
                                   rtol=1e-5, atol=1e-6)


# ------------------------------------------- beam search cache handling
def test_gather_beams_leaves_with_extra_trailing_dims():
    from bigdl_tpu.nn.beam_search import _gather_beams

    rs = np.random.RandomState(4)
    tree = {
        "len": jnp.asarray(rs.randint(0, 9, (2, 3))),           # (B, k)
        "kv": jnp.asarray(rs.rand(2, 3, 4, 5, 6)),  # extra trailing dims
        "enc": jnp.asarray(rs.rand(2, 3, 7)),
    }
    idx = jnp.asarray([[2, 0, 0], [1, 1, 2]])
    out = _gather_beams(tree, idx)
    for key in tree:
        want = np.stack([np.asarray(tree[key])[b, np.asarray(idx)[b]]
                         for b in range(2)])
        np.testing.assert_array_equal(np.asarray(out[key]), want)


def test_beam_search_threads_dict_cache_consistently():
    """A cache that accumulates the tokens each beam actually decoded
    must stay synchronized with the ids the search itself reports —
    any beam-gather mismap on a dict-valued cache (the KV-cache carrier
    shape: extra trailing dims + an int leaf) would desynchronize the
    accumulator from its beam's own prefix and change the outputs."""
    vocab, k, t_max = 6, 3, 5
    w = jnp.asarray(np.random.RandomState(5).rand(vocab, vocab))

    def fn_cached(ids, i, cache):
        # history carried in the CACHE: per-beam one-hot token counts
        # (trailing singleton dim exercises >2-d gathers)
        tok = jax.lax.dynamic_index_in_dim(ids, i, axis=1,
                                           keepdims=False)
        acc = cache["acc"][:, :, 0] + jax.nn.one_hot(tok, vocab)
        return acc @ w, {"acc": acc[:, :, None],
                         "step": cache["step"] + 1}

    def fn_ids(ids, i, cache):
        # the same history recomputed from the search-reported ids
        seen = (jnp.arange(ids.shape[1]) <= i)[None, :, None]
        acc = (jax.nn.one_hot(ids, vocab) * seen).sum(axis=1)
        return acc @ w, cache

    bs = nn.SequenceBeamSearch(vocab, k, alpha=0.0,
                               max_decode_length=t_max, eos_id=vocab - 1)
    init = jnp.asarray([2, 4], jnp.int32)
    cache0 = {"acc": jnp.zeros((2, vocab, 1)),
              "step": jnp.zeros((2,), jnp.int32)}
    seq_c, sc_c = bs.search(init, cache0, fn=fn_cached)
    seq_i, sc_i = bs.search(init, {}, fn=fn_ids)
    np.testing.assert_array_equal(np.asarray(seq_c), np.asarray(seq_i))
    np.testing.assert_allclose(np.asarray(sc_c), np.asarray(sc_i),
                               rtol=1e-6)


# --------------------------------------------------------- DecodeEngine
@pytest.fixture(scope="module")
def engine_lm():
    model = _lm()
    var = model.init(jax.random.PRNGKey(0))
    return model, var


def _engine(model, var, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("prompt_buckets", (4, 8))
    kw.setdefault("prefill_batch_sizes", (1, 2))
    kw.setdefault("eos_id", None)
    return DecodeEngine(model, var, **kw)


def test_engine_greedy_matches_direct_rollout(engine_lm):
    model, var = engine_lm
    rs = np.random.RandomState(0)
    with _engine(model, var) as eng:
        declared = eng.declared_programs()
        assert eng.metrics.recompiles == declared  # warmup == programs
        assert eng.warmup() == 0                   # re-warm is free
        prompts = [rs.randint(0, VOCAB, (t,)) for t in (3, 4, 7, 5, 8)]
        n_news = [6, 9, 4, 8, 5]
        futs = [eng.submit(pr, n) for pr, n in zip(prompts, n_news)]
        outs = [f.result(120) for f in futs]
        for pr, n, got in zip(prompts, n_news, outs):
            assert list(got) == _direct_greedy(model, var, pr, n)
        # occupancy churned (5 requests over 2 slots, mixed lengths)
        # yet the compiled-program set never grew: zero steady-state
        # recompiles — the tick is occupancy-independent
        assert eng.metrics.recompiles == declared
        assert eng.metrics.completed == 5
        assert eng.metrics.decoded_tokens > 0
        assert 0.0 < eng.metrics.slot_occupancy() <= 1.0


def test_engine_eos_retires_slot_immediately(engine_lm):
    model, var = engine_lm
    prompt = [1, 2, 3]
    roll = _direct_greedy(model, var, prompt, 8)
    eos = roll[3]
    want = roll[:roll.index(eos) + 1]
    with _engine(model, var, eos_id=eos) as eng:
        got = eng.generate(prompt, 8, timeout=120)
        assert list(got) == want
        assert eng.metrics.finished("eos") == 1


def test_engine_deadline_semantics(engine_lm):
    model, var = engine_lm
    # expired before prefill: fail fast, same as the stateless engine
    with _engine(model, var) as eng:
        fut = eng.submit([1, 2], 4, deadline_ms=0.0)
        with pytest.raises(DeadlineExceededError):
            fut.result(60)
        assert eng.metrics.expired >= 1
        # the engine keeps serving after an expiry
        assert len(eng.generate([1, 2], 3, timeout=120)) == 3
    # expiring mid-decode: truncate, deliver what was generated
    with _engine(model, var, max_len=2048, prompt_buckets=(8,),
                 prefill_batch_sizes=(1,)) as eng:
        got = eng.generate([1, 2, 3], 2000, deadline_ms=100,
                           timeout=120)
        assert 1 <= len(got) < 2000
        assert eng.metrics.finished("deadline") == 1


def test_engine_admission_and_validation(engine_lm):
    model, var = engine_lm
    eng = _engine(model, var, max_queue=2, start=False, warmup=False)
    with pytest.raises(ValueError):
        eng.submit([], 4)               # empty prompt
    with pytest.raises(ValueError):
        eng.submit([1, 2], 0)           # no token budget
    with pytest.raises(ValueError):
        eng.submit([1] * 8, 100)        # cannot fit max_len=32
    f1 = eng.submit([1, 2], 2)
    f2 = eng.submit([1, 2], 2)
    with pytest.raises(QueueFullError):
        eng.submit([1, 2], 2)
    assert eng.metrics.rejected == 1
    eng.close()  # closed before start: queued requests fail cleanly
    for f in (f1, f2):
        assert isinstance(f.exception(10), EngineClosedError)
    with pytest.raises(EngineClosedError):
        eng.submit([1, 2], 2)


def test_engine_oversized_prompt_becomes_learned_bucket(engine_lm):
    """A prompt longer than the largest declared bucket prefills
    through a visible learned bucket (exactly one recompile), and the
    decode itself still adds none."""
    model, var = engine_lm
    rs = np.random.RandomState(7)
    with _engine(model, var) as eng:
        declared = eng.declared_programs()
        assert eng.metrics.recompiles == declared
        prompt = rs.randint(0, VOCAB, (11,))  # > largest bucket (8,)
        got = eng.generate(prompt, 4, timeout=120)
        assert list(got) == _direct_greedy(model, var, prompt, 4)
        assert eng.metrics.recompiles == declared + 1
        # the learned bucket is reused: same length again is free
        eng.generate(rs.randint(0, VOCAB, (11,)), 4, timeout=120)
        assert eng.metrics.recompiles == declared + 1


def test_engine_close_drains_in_flight(engine_lm):
    model, var = engine_lm
    eng = _engine(model, var)
    futs = [eng.submit([1, 2, 3], 6) for _ in range(4)]
    eng.close()  # drain=True: everything queued must still decode
    want = _direct_greedy(model, var, [1, 2, 3], 6)
    for f in futs:
        assert list(f.result(1)) == want
    assert not eng._loop_thread.is_alive()
    eng.close()  # idempotent


# ----------------------------------------------------- metrics exports
def test_serving_metrics_tensorboard_export(tmp_path, engine_lm):
    from bigdl_tpu.visualization import ServingSummary

    model, var = engine_lm
    with _engine(model, var) as eng:
        eng.generate([1, 2, 3], 5, timeout=120)
        summary = ServingSummary(str(tmp_path), "decode_test")
        snap = eng.metrics.write_summary(summary, step=1)
        eng.metrics.write_summary(summary, step=2)
        summary.close()
    assert snap["decoded_tokens"] > 0
    for tag in ("Serving/TokensPerSec", "Serving/SlotOccupancy",
                "Serving/LatencyP95Ms", "Serving/Recompiles",
                "Serving/TickP50Ms"):
        rows = summary.read_scalar(tag)
        assert [step for step, _ in rows] == [1, 2], tag
    rows = summary.read_scalar("Serving/Completed")
    assert rows[0][1] == 1.0


def test_decode_log_line_carries_token_metrics(engine_lm):
    model, var = engine_lm
    with _engine(model, var) as eng:
        eng.generate([1, 2], 4, timeout=120)
        line = eng.log_line()
    assert "tok/s" in line and "slots=" in line and "tick p50=" in line
    assert "ttft p50=" in line and "gap p50=" in line and "p95=" in line


# ------------------------------------------------------- acceptance A/B
def test_decode_ab_gates():
    """ISSUE 4 acceptance: cached decode >= 3x the re-forward generate
    at T >= 128, continuous batching beats static run-to-completion
    batching on mixed-length traffic, and the recompile counter stays
    flat across occupancy churn (zero steady-state recompiles).  The
    ISSUE-14 production arms are gated separately in
    test_decode_production_arms_gates."""
    bench = pytest.importorskip("bench")

    rec = bench.decode_ab(n_requests=8, production_arms=False)
    d = rec["detail"]
    if rec["value"] < 3.0 or d["continuous_vs_static"] <= 1.0:
        # one retry on a noisy box
        rec = bench.decode_ab(n_requests=8, production_arms=False)
        d = rec["detail"]
    assert rec["value"] >= 3.0, rec
    assert d["t_decode"] >= 128
    assert d["continuous_vs_static"] > 1.0, rec
    # continuous refill must also strictly reduce grid ticks
    assert d["continuous"]["ticks"] < d["static"]["ticks"], rec
    assert d["continuous"]["steady_state_recompiles"] == 0, rec
    assert d["static"]["steady_state_recompiles"] == 0, rec
    assert d["continuous"]["slot_occupancy"] \
        > d["static"]["slot_occupancy"], rec


# -------------------------------------------- production decode (ISSUE 14)
def _ledger_resident(name: str) -> int:
    from bigdl_tpu.telemetry import programs as _programs

    rec = _programs.get_hbm_ledger().sample()
    return rec["resident"].get(name, 0) if rec else 0


def test_paged_engine_matches_dense_greedy(engine_lm):
    """Dense-vs-paged parity oracle: the paged tick gathers the same
    tokens through its block table as the dense per-slot cache."""
    model, var = engine_lm
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, VOCAB, (t,)) for t in (3, 7, 5, 8, 4)]
    n_news = [6, 4, 9, 5, 7]
    with _engine(model, var, kv_layout="paged", page_size=4) as eng:
        declared = eng.declared_programs()
        assert eng.metrics.recompiles == declared
        futs = [eng.submit(p, n) for p, n in zip(prompts, n_news)]
        outs = [f.result(120) for f in futs]
        for p, n, got in zip(prompts, n_news, outs):
            assert list(got) == _direct_greedy(model, var, p, n)
        # occupancy churn added no programs, and retirement returned
        # every page to the free list
        assert eng.metrics.recompiles == declared
        assert eng._kv.pages_in_use == 0


def test_paged_retirement_frees_pages_in_hbm_ledger(engine_lm):
    """The HbmLedger resident lane is the readout that paging frees
    memory: bytes rise while a request holds pages and return to zero
    at retirement (token-granularity page recycling)."""
    model, var = engine_lm
    with _engine(model, var, kv_layout="paged", page_size=4,
                 slots=1) as eng:
        fut = eng.submit([1, 2, 3, 4, 5, 6], 18)
        peak = 0
        while not fut.done():
            peak = max(peak, _ledger_resident("decode_kv_pages"))
            time.sleep(0.001)
        fut.result(120)
        per_page = eng._kv.page_bytes
        # 6 prompt + 18 generated tokens at page_size=4 grows through
        # 6 pages; the poll must observe at least the mid-flight hold
        assert peak >= 3 * per_page
        assert _ledger_resident("decode_kv_pages") == 0
        assert eng.metrics.pages_in_use == 0


def test_paged_admission_rejects_unservable_and_evicts_younger(engine_lm):
    """Page-pool admission control: a request that cannot fit an EMPTY
    pool is rejected at submit; under contention the oldest request is
    always funded (younger slots are evicted and re-queued or paused),
    so traffic completes with exact greedy parity and no livelock."""
    from bigdl_tpu.serving import OutOfPagesError

    model, var = engine_lm
    # pool of 6 usable pages of 4 tokens => max 24 cached tokens/request
    with _engine(model, var, kv_layout="paged", page_size=4,
                 num_pages=7) as eng:
        with pytest.raises(OutOfPagesError):
            eng.submit([1] * 8, 24)  # needs 8 pages solo: unservable
        prompts = [[1, 2, 3], [2, 3, 4], [3, 4, 5], [4, 5, 6]]
        futs = [eng.submit(p, 12) for p in prompts]
        outs = [f.result(180) for f in futs]
        for p, got in zip(prompts, outs):
            assert list(got) == _direct_greedy(model, var, p, 12)
        assert eng._kv.pages_in_use == 0


def test_int8_kv_halves_cache_bytes_with_parity(engine_lm):
    """fp-vs-int8-KV oracle: the quantized pool costs < half the bytes
    per page and greedy tokens agree within tolerance (near-tie argmax
    flips are the only allowed difference)."""
    model, var = engine_lm
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, VOCAB, (t,)) for t in (4, 7, 3, 6)]
    kw = dict(kv_layout="paged", page_size=4)
    with _engine(model, var, **kw) as fp_eng:
        fp_bytes = fp_eng._kv.page_bytes
        fp_outs = [fp_eng.generate(p, 8, timeout=120) for p in prompts]
    with _engine(model, var, kv_dtype="int8", **kw) as q_eng:
        q_bytes = q_eng._kv.page_bytes
        q_outs = [q_eng.generate(p, 8, timeout=120) for p in prompts]
    assert 2 * q_bytes <= fp_bytes
    agree = sum(int(np.sum(np.asarray(a) == np.asarray(b)))
                for a, b in zip(fp_outs, q_outs))
    total = sum(len(a) for a in fp_outs)
    assert agree / total >= 0.9, (agree, total)


def test_sampling_reproducible_per_seed(engine_lm):
    """In-tick sampling: identical seeds replay the identical stream,
    different seeds diverge, and temperature=0 rows stay exactly
    greedy even while sampled rows share the grid."""
    model, var = engine_lm
    prompt = [1, 2, 3, 4]
    # high temperature flattens the distribution so distinct seeds
    # diverge with overwhelming probability over 12 draws
    kw = dict(temperature=1.5, top_k=0, top_p=0.95)
    with _engine(model, var) as eng:
        a = eng.generate(prompt, 12, seed=11, timeout=120, **kw)
        b = eng.generate(prompt, 12, seed=11, timeout=120, **kw)
        c = eng.generate(prompt, 12, seed=12, timeout=120, **kw)
        greedy = eng.generate(prompt, 12, timeout=120)
        assert list(a) == list(b)           # same seed, same stream
        assert list(a) != list(c)           # fresh seed diverges
        assert list(greedy) == _direct_greedy(model, var, prompt, 12)
        # the sampled stream is a real distribution change, and every
        # request ran through the SAME compiled tick: sampling params
        # are data, not shapes
        assert eng.metrics.recompiles == eng.declared_programs()


def test_sampling_mixed_traffic_keeps_greedy_parity(engine_lm):
    """Greedy requests interleaved with sampled ones on the same grid
    keep the exact greedy oracle (per-slot temperature gating)."""
    model, var = engine_lm
    rs = np.random.RandomState(9)
    prompts = [rs.randint(0, VOCAB, (t,)) for t in (3, 5, 7, 4)]
    with _engine(model, var) as eng:
        futs = []
        for i, p in enumerate(prompts):
            if i % 2:
                futs.append(eng.submit(p, 6, temperature=0.8, seed=i))
            else:
                futs.append(eng.submit(p, 6))
        outs = [f.result(120) for f in futs]
        for i, (p, got) in enumerate(zip(prompts, outs)):
            if i % 2 == 0:
                assert list(got) == _direct_greedy(model, var, p, 6)


# ---------------------- the sampling epilogue runs only when asked for
_GRIDS = {
    #               temperature            active
    "all_greedy": ((0.0, 0.0, 0.0, 0.0), (True, True, True, True)),
    "all_sampled": ((0.7, 1.0, 1.5, 0.3), (True, True, True, True)),
    "mixed": ((0.0, 0.9, 0.0, 1.2), (True, True, False, True)),
    "sampled_row_inactive": ((0.0, 0.8, 0.0, 0.0),
                             (True, False, True, True)),
    "nothing_active": ((0.0, 0.8, 0.0, 1.1), (False,) * 4),
}


def _ungated_next_tokens(logits, tokens, active, keys, temp, top_k,
                         top_p):
    """The epilogue as it was before the branch: every row sampled,
    then thrown away row by row.  The oracle of the gated one."""
    from bigdl_tpu.serving.decode_programs import sample_logits

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampled = sample_logits(logits, keys, temp, top_k, top_p)
    nxt = jnp.where(temp > 0.0, sampled, greedy)
    nxt = jnp.where(active, nxt, tokens)
    split = jax.vmap(lambda k: jax.random.split(k, 2)[0])(keys)
    return nxt, jnp.where(active[:, None], split, keys)


@pytest.mark.parametrize("grid", sorted(_GRIDS))
def test_next_tokens_equal_the_ungated_epilogue(grid):
    """Tokens and keys of every row, bit for bit, whichever branch the
    tick takes: the branch only decides whether discarded work is
    done."""
    from bigdl_tpu.serving.decode_programs import _next_tokens

    temp, active = _GRIDS[grid]
    rs = np.random.RandomState(sorted(_GRIDS).index(grid))
    args = (rs.randn(4, 97).astype(np.float32),
            rs.randint(0, 97, (4,)).astype(np.int32),
            np.asarray(active),
            rs.randint(0, 2 ** 32, (4, 2), dtype=np.uint64).astype(
                np.uint32),
            np.asarray(temp, np.float32),
            np.asarray([0, 5, 0, 40], np.int32),
            np.asarray([1.0, 0.9, 0.5, 1.0], np.float32))
    gated, ungated = jax.jit(_next_tokens), jax.jit(_ungated_next_tokens)
    for _ in range(3):  # a key chain: the keys feed the next tick
        tok, keys = gated(*args)
        want_tok, want_keys = ungated(*args)
        np.testing.assert_array_equal(np.asarray(tok),
                                      np.asarray(want_tok))
        np.testing.assert_array_equal(np.asarray(keys),
                                      np.asarray(want_keys))
        args = args[:1] + (np.asarray(tok),) + args[2:3] + (
            np.asarray(keys),) + args[4:]


def _primitives(jaxpr, inside=False):
    """``[(primitive, under a cond)]`` of a jaxpr and all it nests."""
    out = []
    for eqn in jaxpr.eqns:
        out.append((eqn.primitive.name, inside))
        under = inside or eqn.primitive.name == "cond"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) \
                    else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _primitives(sub, under)
    return out


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_tick_sorts_and_draws_only_inside_its_branch(engine_lm, layout):
    """The tick program holds one conditional, and every sort and every
    random-bits draw of it lies inside: a greedy grid reaches neither.
    One compiled tick as before: warm-up compiles what is declared."""
    from bigdl_tpu.serving import decode_programs as decode

    model, var = engine_lm
    slots, page, pages = 2, 4, 8
    samp = (np.zeros((slots,), np.int32), np.ones((slots,), bool),
            np.zeros((slots, 2), np.uint32), np.zeros((slots,), np.float32),
            np.zeros((slots,), np.int32), np.ones((slots,), np.float32))
    if layout == "paged":
        tick = decode.build_paged_tick(model)
        args = (var["params"], var["state"],
                model.init_paged_cache(slots * pages + 1, page, slots),
                np.zeros((slots, pages), np.int32)) + samp
    else:
        tick = decode.build_sampling_tick(model)
        args = (var["params"], var["state"],
                model.init_cache(slots, page * pages)) + samp
    prims = _primitives(jax.make_jaxpr(tick)(*args).jaxpr)
    assert [p for p in prims if p[0] == "cond"] == [("cond", False)]
    drawn = [p for p in prims if p[0] in ("sort", "random_bits")]
    assert {name for name, _ in drawn} == {"sort", "random_bits"}
    assert all(under for _, under in drawn), drawn
    # the greedy path stays outside: the argmax and the key split
    assert ("argmax", False) in prims and ("random_split", False) in prims
    text = tick.lower(*args).as_text()
    assert text.count("stablehlo.case") + text.count("stablehlo.if") == 1
    kw = dict(kv_layout="paged", page_size=page) if layout == "paged" \
        else {}
    with _engine(model, var, **kw) as eng:
        assert eng.metrics.recompiles == eng.declared_programs()
        assert eng.warmup() == 0


def test_speculative_decode_exact_match(engine_lm):
    """Speculative correctness property: whatever the draft proposes,
    the verify pass emits exactly the big model's greedy tokens — the
    draft only changes WHEN tokens appear, never WHICH."""
    model, var = engine_lm
    draft = _lm(layers=1)
    dvar = draft.init(jax.random.PRNGKey(1))
    rs = np.random.RandomState(13)
    prompts = [rs.randint(0, VOCAB, (t,)) for t in (3, 6, 8, 5)]
    n_news = [9, 5, 7, 11]
    with _engine(model, var, draft=(draft, dvar), draft_k=3,
                 max_len=48) as eng:
        declared = eng.declared_programs()
        assert eng.metrics.recompiles == declared
        futs = [eng.submit(p, n) for p, n in zip(prompts, n_news)]
        outs = [f.result(180) for f in futs]
        for p, n, got in zip(prompts, n_news, outs):
            assert list(got) == _direct_greedy(model, var, p, n)
        assert eng.metrics.recompiles == declared
        assert 0.0 <= eng.metrics.spec_acceptance_rate() <= 1.0
        # sampling + speculation is rejected up front (verify pass is
        # a greedy argmax oracle)
        with pytest.raises(ValueError):
            eng.submit([1, 2], 4, temperature=0.5)


def test_speculative_paged_chunked_combined(engine_lm):
    """The full production stack at once — paged int8-less KV, chunked
    prefill past the largest bucket, speculative ticks — still equals
    the direct greedy rollout with zero steady-state recompiles."""
    model, var = engine_lm
    draft = _lm(layers=1)
    dvar = draft.init(jax.random.PRNGKey(1))
    rs = np.random.RandomState(17)
    long_prompt = rs.randint(0, VOCAB, (19,))  # > largest bucket (8)
    short = rs.randint(0, VOCAB, (5,))
    with _engine(model, var, kv_layout="paged", page_size=4,
                 max_len=48, draft=(draft, dvar), draft_k=2,
                 prefill_chunk=8) as eng:
        declared = eng.declared_programs()
        futs = [eng.submit(long_prompt, 8), eng.submit(short, 10)]
        outs = [f.result(180) for f in futs]
        assert list(outs[0]) == _direct_greedy(model, var, long_prompt, 8)
        assert list(outs[1]) == _direct_greedy(model, var, short, 10)
        assert eng.metrics.recompiles == declared
        assert eng.metrics.prefill_chunks >= 3
        assert eng._kv.pages_in_use == 0


def test_chunked_prefill_matches_bucketed(engine_lm):
    """Chunked prefill is a pure admission-path change: a long prompt
    fed in bounded chunks produces the same tokens as the learned
    jumbo-bucket path, without compiling any prompt-length program."""
    model, var = engine_lm
    rs = np.random.RandomState(21)
    prompt = rs.randint(0, VOCAB, (21,))
    with _engine(model, var, max_len=48, prefill_chunk=8) as eng:
        declared = eng.declared_programs()
        got = eng.generate(prompt, 6, timeout=120)
        assert list(got) == _direct_greedy(model, var, prompt, 6)
        # no learned bucket: the chunk program covered the long prompt
        assert eng.metrics.recompiles == declared
        assert eng.metrics.prefill_chunks >= 3


# ----------------------------- the seams of the scheduler (ISSUE 31)
def _still(model, var, **kw):
    """An engine that runs nothing: no warm-up, no loop thread."""
    return _engine(model, var, warmup=False, start=False, **kw)


def _bind(eng, slot, rid, *, prompt=3, max_new=64, deadline=None,
          tok0=1, t0=100.0):
    """Put a hand-made request into ``slot`` as admission would."""
    from bigdl_tpu.serving.decode import _DecodeRequest
    from bigdl_tpu.serving.engine import ServingFuture

    req = _DecodeRequest(np.arange(1, prompt + 1, dtype=np.int32),
                         max_new, ServingFuture(), t0, deadline, rid=rid)
    eng._activate(slot, req, tok0, t0)
    return req


@pytest.mark.parametrize("k", [1, 4], ids=["k1", "k4"])
def test_retire_takes_a_round(engine_lm, monkeypatch, k):
    """The one ``_retire`` on a hand-made ``(emitted, n_emit)``: a
    deadline, an end of sequence inside the round, the budget ending
    inside the round, a row that emitted nothing, a row that goes on.
    For K = 1 these are the gaps, reasons and the order of ``_finish``
    and ``_free`` the tick's retirement always had."""
    model, var = engine_lm
    eos, now = 7, 103.0
    eng = _still(model, var, slots=5, eos_id=eos)
    # rows 1 and 2 end on their last emitted token but one when K > 1
    reqs = [_bind(eng, 0, 10, deadline=102.0),
            _bind(eng, 1, 11),
            _bind(eng, 2, 12, max_new=max(k, 2)),
            _bind(eng, 3, 13),
            _bind(eng, 4, 14, t0=101.0)]
    eng._active[3] = False  # paused: holds its state, emits nothing
    n_emit = np.array([k, k, k, 0, min(k, 2)], np.int32)
    emitted = np.array([[2, 3, 4, 5], [2, eos, 4, 5], [2, 3, 4, 5],
                        [9, 9, 9, 9], [6, 8, 4, 5]], np.int32)[:, :k]
    if k == 1:
        emitted[1, 0] = eos
    calls = []
    finish, free = eng._finish, eng._free
    monkeypatch.setattr(eng, "_finish", lambda req, toks, times, why: (
        calls.append(("finish", req.rid, why)),
        finish(req, toks, times, why)))
    monkeypatch.setattr(eng, "_free", lambda s: (
        calls.append(("free", s)), free(s)))

    gaps = eng._retire(emitted, n_emit, now)

    assert calls == [("finish", 10, "deadline"), ("free", 0),
                     ("finish", 11, "eos"), ("free", 1),
                     ("finish", 12, "length"), ("free", 2)]
    want = {1: ([1, 2], [1, eos], [1, 2], [1, 6]),
            4: ([1, 2, 3, 4, 5], [1, 2, eos], [1, 2, 3, 4],
                [1, 6, 8])}[k]
    for req, toks in zip(reqs[:3], want):
        assert list(req.fut.result(0)) == toks
        np.testing.assert_array_equal(
            req.fut.token_times, [100.0] + [now] * (len(toks) - 1))
    # a row's first token of the round waited since its last one; the
    # rest of a round arrive with it.  Slot order.
    first = [3.0, 3.0, 3.0, 2.0]
    assert gaps == [g for f, toks in zip(first, want)
                    for g in [f] + [0.0] * (len(toks) - 2)]
    assert [eng.metrics.finished(r) for r in ("deadline", "eos",
                                              "length")] == [1, 1, 1]
    # the row that emitted nothing is as it was, garbage and all
    assert not eng._active[3] and eng._slot_state[3].req is reqs[3]
    assert eng._slot_state[3].generated == [1] and eng._tokens[3] == 1
    # the row that goes on: fed its last token.  Its extent is the
    # round's to advance (the tick as it is enqueued, a verify by what
    # it accepted: test_a_token_belongs_to_the_slot_it_was_dispatched_for,
    # test_speculative_decode_exact_match), so a retirement leaves it
    # and zeroes a freed slot's
    assert eng._active[4] and eng._slot_state[4].generated == want[3]
    assert eng._tokens[4] == want[3][-1]
    assert list(eng._host_len) == [0, 0, 0, 3, 3]
    assert list(eng._limit) == [0, 0, 0, 66, 66]
    # K - 1 tokens a row were a draft's, n_emit - 1 of them accepted
    assert eng.metrics.spec_acceptance_rate() == pytest.approx(
        (3 + 3 + 3 + 1) / (4 * 3) if k > 1 else 0.0)
    eng.close()


class _Room:
    """A cache manager with ``room`` tokens to give and nothing else: no
    pool, no program.  What the scheduler's page policy is written
    against."""

    def __init__(self, room, held):
        self.room, self.held, self.released = room, dict(held), []

    def reserve(self, slot, tokens):
        grow = max(0, tokens - self.held.get(slot, 0))
        if grow > self.room:
            return False
        self.room -= grow
        self.held[slot] = self.held.get(slot, 0) + grow
        return True

    def release(self, slot):
        self.room += self.held.pop(slot, 0)
        self.released.append(slot)

    def owned(self, slot):
        return self.held.get(slot, 0)


@pytest.mark.parametrize("case", ["pause", "evict_younger",
                                  "oldest_always_funded",
                                  "spares_a_finishing_row"])
def test_page_policy_on_a_fake_cache_manager(engine_lm, case):
    """Oldest first, evict strictly younger, pause when there is none,
    never starve the oldest: the policy alone, against a manager whose
    room the test sets."""
    model, var = engine_lm
    eng = _still(model, var, slots=3)
    # every slot holds its 3 prompt tokens and needs a 4th this round
    rids = {"pause": [0, 1], "evict_younger": [5, 2],
            "oldest_always_funded": [1, 0, 2],
            "spares_a_finishing_row": [5, 2]}[case]
    reqs = [_bind(eng, s, rid) for s, rid in enumerate(rids)]
    if case == "spares_a_finishing_row":
        # the younger row's whole budget is dispatched (its last token
        # is in flight): it is neither funded nor evicted, the older
        # waits the one turn until the read frees the younger's pages
        eng._kv = room = _Room(0, {0: 3, 1: 3})
        eng._limit[0] = eng._host_len[0]
        eng._budget_pages()
        assert not room.released and eng._slot_state[0].req is reqs[0]
        assert list(eng._active[:2]) == [True, False]
        assert room.held == {0: 3, 1: 3} and not eng._pending
        eng._free(0)         # the read retired it
        eng._budget_pages()
        assert eng._active[1] and room.held == {1: 4}
        assert eng.metrics.page_evictions == 0
    elif case == "pause":
        eng._kv = room = _Room(1, {0: 3, 1: 3})
        eng._budget_pages()
        # the older is funded; the younger has no one younger to evict
        assert list(eng._active[:2]) == [True, False]
        assert eng._slot_state[1].req is reqs[1] and room.held[1] == 3
        assert not room.released and not eng._pending
        eng._budget_pages()  # still no room: stays paused, nothing lost
        assert list(eng._active[:2]) == [True, False]
        room.room += 1       # a retirement elsewhere freed some
        eng._budget_pages()
        assert list(eng._active[:2]) == [True, True]
        assert eng.metrics.page_evictions == 0
    elif case == "evict_younger":
        eng._kv = room = _Room(0, {0: 3, 1: 3})
        eng._budget_pages()
        # slot 1 (request 2) is the older: slot 0 (request 5) gives way
        assert room.released == [0] and eng._slot_state[0] is None
        assert list(eng._pending) == [reqs[0]]   # back at the front
        assert eng._active[1] and not eng._active[0]
        assert room.held == {1: 4}
        assert eng.metrics.page_evictions == 1
        assert not reqs[0].fut.done()            # re-decoded, not failed
    else:
        eng._kv = room = _Room(0, {0: 2, 1: 1, 2: 1})
        eng._budget_pages()
        # slot 1 holds the oldest request: it takes the youngest's room
        # first and the next youngest's only if that was not enough
        assert room.released == [2, 0]
        assert eng._active[1] and room.held == {1: 4}
        assert [r.rid for r in eng._pending] == [1, 2]
        assert eng.metrics.page_evictions == 2
    eng.close()


# ------------------------------------- one tick in flight (ISSUE 32)
_LAYOUTS = {"dense": {}, "paged": dict(kv_layout="paged", page_size=4)}


@contextlib.contextmanager
def _compiles():
    """The backend compile requests made inside the block: the event
    ``benchmark/device.CompileCount`` counts (a cache hit fires it
    too).  Around an engine's traffic only: an oracle compiles its own
    programs."""
    seen = []

    def on(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on)


def _drive(eng, requests):
    futs = [eng.submit(p, n, **opts) for p, n, opts in requests]
    return [list(f.result(120)) for f in futs]


def _counts(eng):
    """Ticks timed, ticks overlapped, tokens decoded, ended by budget:
    counted since the engine started (take a difference)."""
    m = eng.metrics
    return np.array([m.base.count("decode_tick"), m.overlapped_ticks,
                     m.decoded_tokens, m.finished("length")])


def _row_ticks(eng, monkeypatch):
    """Count the rows of every tick ``eng`` enqueues from here on."""
    rows = []
    real = eng._dispatch_tick
    monkeypatch.setattr(eng, "_dispatch_tick", lambda mask, prev: (
        rows.append(int(mask.sum())), real(mask, prev))[1])
    return rows


@pytest.fixture(params=sorted(_LAYOUTS))
def piped(request, engine_lm):
    """A running engine, of either layout."""
    model, var = engine_lm
    with _engine(model, var, slots=3, max_len=48,
                 **_LAYOUTS[request.param]) as eng:
        yield eng


def _grids():
    rs = np.random.RandomState(32)
    sampled = lambda i: dict(temperature=0.9, top_k=8, top_p=0.9,
                             seed=70 + i)
    prompts = [rs.randint(0, VOCAB, (t,)) for t in (3, 7, 5, 8, 4, 6)]
    n_news = [9, 14, 6, 11, 17, 8]
    return {
        "greedy": [(p, n, {}) for p, n in zip(prompts, n_news)],
        "sampled": [(p, n, sampled(i))
                    for i, (p, n) in enumerate(zip(prompts, n_news))],
        "mixed": [(p, n, sampled(i) if i % 2 else {})
                  for i, (p, n) in enumerate(zip(prompts, n_news))],
    }


def test_a_tick_in_flight_serves_the_tokens_of_the_loop_without(
        piped, engine_lm, read_each_tick_first):
    """Greedy, seeded sampled and mixed grids through six requests over
    three slots (admissions, retirements and, paged, page crossings
    while ticks are in flight): every request's tokens are those of the
    same engine with overlapping made impossible, bit for bit, and the
    greedy ones the uncached forward's.  The first traffic after
    warm-up, overlapped calls among it, compiles nothing."""
    model, var = engine_lm
    grids = _grids()
    declared = piped.declared_programs()
    before = _counts(piped)
    with _compiles() as seen:
        got = {name: _drive(piped, reqs) for name, reqs in grids.items()}
    ticks, overlapped, _, _ = _counts(piped) - before
    assert not seen and piped.recompiles == declared
    assert overlapped > 0.5 * ticks
    overlapped = piped.metrics.overlapped_ticks
    read_each_tick_first()
    want = {name: _drive(piped, reqs) for name, reqs in grids.items()}
    assert piped.metrics.overlapped_ticks == overlapped  # none since
    assert got == want
    for (p, n, opts), toks in zip(grids["greedy"] + grids["mixed"],
                                  got["greedy"] + got["mixed"]):
        if not opts:
            assert toks == _direct_greedy(model, var, p, n)
    assert got["sampled"] != got["greedy"]
    assert piped.recompiles == declared


def test_a_budget_end_computes_no_discarded_token(piped, monkeypatch):
    """A row that ends by its budget is known by count: it is left out
    of the tick after its last, so on an all-"length" workload the
    rows of the ticks enqueued are the tokens served after each
    request's first, and nothing was in flight when the last was
    read."""
    rows = _row_ticks(piped, monkeypatch)
    n_news = [5, 9, 2, 12, 7, 3, 6]
    before = _counts(piped)
    outs = _drive(piped, [([1 + i, 2, 3], n, {})
                          for i, n in enumerate(n_news)])
    _, _, tokens, ended = _counts(piped) - before
    assert [len(o) for o in outs] == n_news
    assert ended == len(n_news)
    assert sum(rows) == sum(n - 1 for n in n_news) == tokens
    assert 0 not in rows and piped._flight is None
    assert not piped._host_len.any() and not piped._limit.any()


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_overlapped_ticks_are_counted_and_carried_by_the_spans(
        engine_lm, tracer, layout):
    """A long all-greedy stream overlaps nearly every tick:
    ``overlapped_tick_share()`` >= 0.9, ``overlap=..%`` in the log
    line, and ``loop/tick_dispatch`` carries ``args.in_flight`` 1 on
    exactly the ticks the metric counted (the first after an empty
    engine and the one after each admission read their predecessor
    first)."""
    model, var = engine_lm
    tracer.enable()
    with _engine(model, var, slots=3, max_len=48,
                 **_LAYOUTS[layout]) as eng:
        _drive(eng, [([1, 2, 3 + i], 40, {}) for i in range(3)])
        tracer.disable()
        m = eng.metrics
        assert m.overlapped_tick_share() >= 0.9
        assert m.snapshot()["overlapped_tick_share"] >= 0.9
        assert "overlap=" in eng.log_line()
    ticks = [s for s in tracer.spans() if s.name == "loop/tick_dispatch"]
    flags = [s.args["in_flight"] for s in ticks]
    # warm-up's second call runs on the first's outputs, as the loop's
    assert flags[:3] == [0, 1, 0] and set(flags) == {0, 1}
    assert sum(flags[2:]) == m.overlapped_ticks
    assert len(ticks) - 2 == m.base.count("decode_tick")


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_end_of_sequence_is_learnt_a_tick_late(engine_lm, monkeypatch,
                                               layout):
    """With an ``eos_id`` the row ends at its end of sequence: the
    token the tick in flight computed for it is dropped at the read
    (one row-tick more than the tokens served), and the freed slot's
    next tenant, admitted while that tick was unread, decodes its own
    tokens.  (The tiny model's greedy rollouts repeat one token, so
    the row that ends is a seeded sampled one.)"""
    model, var = engine_lm
    first = ([1, 2, 3], 12, dict(temperature=3.0, top_p=0.95, seed=11))
    second = ([4, 5, 6, 7], 7, {})
    with _engine(model, var, slots=1, **_LAYOUTS[layout]) as eng:
        roll, tenant = _drive(eng, [first, second])
        # a token first seen a few ticks in becomes the end of sequence
        eos = next(t for i, t in enumerate(roll)
                   if 3 <= i < 11 and t not in roll[:i])
        want = roll[:roll.index(eos) + 1]
        assert eos not in tenant
        eng.eos_id = eos
        rows = _row_ticks(eng, monkeypatch)
        before = _counts(eng)
        assert _drive(eng, [first, second]) == [want, tenant]
        assert eng.metrics.finished("eos") == 1
        served = len(want) - 1 + len(tenant) - 1
        _, _, tokens, _ = _counts(eng) - before
        assert tokens == served
        assert sum(rows) == served + 1  # the one computed and dropped
        assert eng._flight is None and eng._kv.pages_in_use == 0


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_truncation_and_contention_with_a_tick_in_flight(engine_lm,
                                                         layout):
    """A deadline that passes while decoding truncates to a prefix of
    the rollout and the slot's next tenant decodes its own tokens;
    under page contention (paged: evictions and pauses while ticks are
    in flight) seeded sampled and greedy requests are served the tokens
    of an engine with room for all."""
    model, var = engine_lm
    with _engine(model, var, slots=1, max_len=2048, prompt_buckets=(8,),
                 prefill_batch_sizes=(1,), **_LAYOUTS[layout]) as eng:
        cut = eng.submit([1, 2, 3], 2000, deadline_ms=150)
        after = eng.submit([2, 3, 4], 6)
        got = list(cut.result(120))
        assert 1 <= len(got) < 2000
        assert eng.metrics.finished("deadline") == 1
        assert list(after.result(120)) == _direct_greedy(
            model, var, [2, 3, 4], 6)
    assert got[:24] == _direct_greedy(model, var, [1, 2, 3], 24)[:len(got)]
    requests = [([1 + i, 2 + i, 3 + i], 12,
                 dict(temperature=0.9, top_p=0.9, seed=90 + i) if i % 2
                 else {}) for i in range(5)]
    with _engine(model, var, slots=4, **_LAYOUTS[layout]) as eng:
        want = _drive(eng, requests)
    # 6 usable pages of 4 tokens: two requests at their longest fill it
    tight = dict(num_pages=7) if layout == "paged" else {}
    with _engine(model, var, slots=4, **_LAYOUTS[layout], **tight) as eng:
        assert _drive(eng, requests) == want
        if layout == "paged":
            assert eng.metrics.page_evictions > 0
            assert eng._kv.pages_in_use == 0
        assert eng.metrics.overlapped_ticks > 0


def _turn(eng, now=200.0):
    """One loop turn's budget, round and retirement, by hand."""
    eng._budget_pages()
    emitted, n_emit = eng._round()
    eng._retire(emitted, n_emit, now)
    return [bool(b) for b in n_emit]


@pytest.mark.parametrize("case", ["evict", "new_tenant", "deadline",
                                  "pause", "spec_extent"])
@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_a_token_belongs_to_the_slot_it_was_dispatched_for(
        engine_lm, layout, case):
    """The pipeline turn by turn on an engine without a loop thread: a
    tick's token is delivered only if the slot still holds the
    ``_Slot`` it was enqueued for.  An eviction and a deadline between
    dispatch and read drop it; a new tenant keeps the token and key its
    admission wrote; a paused row takes the token already computed for
    it and resumes from the device's own state.  The extent is the
    round's: the tick advances it as it enqueues, a verify by what it
    accepted."""
    model, var = engine_lm
    kw = dict(_LAYOUTS[layout], slots=3)
    if case == "spec_extent":
        eng = _still(model, var, draft=(model, var), draft_k=3, **kw)
        _bind(eng, 0, 1), _bind(eng, 2, 2)
        n_emit = np.array([2, 0, 4], np.int32)
        eng._run_propose = lambda: None
        eng._run_verify = lambda props: (np.zeros((3, 4), np.int32),
                                         n_emit)
        emitted, got = eng._spec_round()
        assert list(got) == [2, 0, 4] and emitted.shape == (3, 4)
        assert list(eng._host_len) == [5, 0, 7]
        eng.close()
        return
    eng = _still(model, var, **kw)
    a = _bind(eng, 0, 1, deadline=150.0 if case == "deadline" else None)
    b = _bind(eng, 1, 2)
    st_a, st_b = eng._slot_state[:2]
    assert _turn(eng, now=100.0) == [False] * 3  # fills the pipeline
    assert eng._flight.owners[:2] == [st_a, st_b]
    assert list(eng._host_len) == [4, 4, 0]
    assert list(eng._limit) == [66, 66, 0]
    assert _turn(eng, now=100.0) == [True, True, False]
    assert list(eng._host_len) == [5, 5, 0]      # two enqueued, one read
    assert len(st_a.generated) == len(st_b.generated) == 2
    assert eng._flight.args["in_flight"] == 1
    if case == "evict":
        eng._evict(1)
        assert list(eng._pending) == [b] and eng._host_len[1] == 0
        # the tick in flight ran row 1 for the evicted _Slot
        assert _turn(eng) == [True, False, False]
        assert len(st_b.generated) == 2 and len(st_a.generated) == 3
        assert list(eng._flight.mask) == [True, False, False]
        assert _turn(eng) == [True, False, False]
        # the overlapped ticks are counted as each is read and serves:
        # a tick nobody is left to be served by is read, not counted
        assert eng.metrics.overlapped_ticks == 2
        eng._evict(0)
        assert _turn(eng) == [False] * 3 and eng._flight is None
        assert eng.metrics.overlapped_ticks == 2
    elif case == "new_tenant":
        eng._evict(1)
        c = _bind(eng, 1, 7, tok0=5)
        c.key[:] = (11, 13)
        eng._keys[1] = c.key
        kept = eng._keys[0].copy()
        assert _turn(eng) == [True, False, False]  # read first, whole
        assert eng._flight.args["in_flight"] == 0
        assert eng._flight.owners[1] is eng._slot_state[1]
        assert eng._slot_state[1].generated == [5]
        assert eng._tokens[1] == 5 and list(eng._keys[1]) == [11, 13]
        # greedy rows split their keys too: row 0's came off the device
        assert list(eng._keys[0]) != list(kept)
        assert not eng._host_rows.any()
        assert _turn(eng) == [True, True, False]
        assert len(eng._slot_state[1].generated) == 2
        assert len(st_b.generated) == 2 and not b.fut.done()
    elif case == "deadline":
        # the read after the deadline truncates row 0; the tick already
        # in flight for it is dropped a turn later
        assert _turn(eng, now=151.0) == [True, True, False]
        assert list(a.fut.result(0)) == st_a.generated
        assert len(st_a.generated) == 3 and eng._slot_state[0] is None
        assert _turn(eng, now=152.0) == [False, True, False]
        assert len(st_a.generated) == 3
    else:
        ref = _still(model, var, **kw)
        _bind(ref, 0, 1), _bind(ref, 1, 2)
        for _ in range(8):
            _turn(ref)
        fund = eng._ensure_pages     # no room for row 1: the page
        eng._ensure_pages = lambda s, n: s != 1 and fund(s, n)  # policy
        assert _turn(eng) == [True, True, False]   # pauses it; computed
        assert not eng._active[1]                  # before, delivered
        assert list(eng._flight.mask) == [True, False, False]
        assert _turn(eng) == [True, False, False]
        assert _turn(eng) == [True, False, False]
        eng._ensure_pages = fund    # ... and resumes it: the row goes
        # on from the token and key the device held for it
        assert _turn(eng) == [True, False, False]
        assert eng._flight.args["in_flight"] == 1
        assert list(eng._flight.mask) == [True, True, False]
        assert _turn(eng) == [True, True, False]
        # the paused row's tokens are those of a row never paused
        n = len(st_b.generated)
        assert n == 4
        assert st_b.generated == ref._slot_state[1].generated[:n]
        assert st_a.generated == ref._slot_state[0].generated[:7]
        ref.close()
    eng.close()


@pytest.mark.parametrize("draft", [False, True], ids=["plain", "draft"])
def test_chunk_declares_its_own_batch_one_write(engine_lm, draft):
    """The combination the other declared-program tests leave out: a
    chunked engine whose declared batch sizes do not hold 1 compiles a
    batch-1 slot write a lane for the chunk's staging cache."""
    model, var = engine_lm
    kw = {}
    if draft:
        small = _lm(layers=1)
        kw = dict(draft=(small, small.init(jax.random.PRNGKey(1))),
                  draft_k=2)
    lanes = 2 if draft else 1
    with _engine(model, var, max_len=48, prefill_batch_sizes=(2,),
                 prefill_chunk=8, **kw) as eng:
        # the round, then a lane: 2 buckets + write(2) + chunk + write(1)
        assert eng.declared_programs() == lanes + 5 * lanes
        assert eng.recompiles == eng.declared_programs()
        prompt = np.random.RandomState(5).randint(0, VOCAB, (13,))
        got = eng.generate(prompt, 5, timeout=120)
        assert list(got) == _direct_greedy(model, var, prompt, 5)
        assert eng.recompiles == eng.declared_programs()
        assert eng.warmup() == 0


@pytest.mark.parametrize("kind", ["dense", "paged", "draft"])
def test_close_releases_device_buffers(engine_lm, kind):
    """``close()`` lets go of the device itself: with the collector
    off, a cache leaf of every lane is gone when it returns (the
    parameters stay: the caller holds them)."""
    import gc
    import weakref

    model, var = engine_lm
    kw = {"paged": dict(kv_layout="paged", page_size=4), "dense": {},
          "draft": dict(draft=(model, var), draft_k=2, max_len=48)}[kind]
    eng = _engine(model, var, **kw)
    assert len(eng.generate([1, 2, 3], 4, timeout=120)) == 4
    gc.disable()
    try:
        leaves = [weakref.ref(jax.tree_util.tree_leaves(lane.cache)[0])
                  for lane in eng._lanes]
        assert all(ref() is not None for ref in leaves)
        eng.close()
        assert not eng._loop_thread.is_alive()
        assert [ref() for ref in leaves] == [None] * len(leaves)
        assert all(lane.params is None and not lane.programs
                   for lane in eng._lanes)
    finally:
        gc.enable()
    eng.close()  # idempotent


def test_decode_production_arms_gates(read_each_tick_first):
    """ISSUE 14 acceptance on the long-context mixed-traffic bench:
    paged serves 2x the slots inside the dense arm's fixed HBM-estimate
    budget (HbmLedger is the meter), int8 at least halves cache bytes
    with parity within tolerance, the speculative arm reports its
    acceptance rate at >= 1.0x dense tokens/s, sampling is reproducible
    per seed, and every arm serves with zero steady-state recompiles.
    Speculation has to pay for its synchronous round, so the dense arm
    it is held against runs the same one: every tick read before the
    next is enqueued."""
    bench = pytest.importorskip("bench")
    read_each_tick_first()

    rec = bench.decode_production_arms(n_requests=8)
    if rec["spec_speedup"] < 1.0 or rec["paged"]["peak_active_slots"] \
            <= rec["dense"]["peak_active_slots"]:
        rec = bench.decode_production_arms(n_requests=8)  # noisy box
    arms = ("dense", "sampling", "paged", "int8_kv", "speculative")
    for arm in arms:
        assert rec[arm]["steady_state_recompiles"] == 0, (arm, rec)
        assert rec[arm]["prefill_chunks"] > 0, (arm, rec)
    assert rec["sampling"]["seed_reproducible"], rec
    # paged: 2x slots, fixed pool, peak resident within dense budget
    assert rec["paged"]["peak_active_slots"] \
        > rec["dense"]["peak_active_slots"], rec
    assert rec["paged_budget_ok"], rec
    assert rec["paged"]["peak_pages_in_use"] > 0, rec
    # int8: at least 2x cache-byte reduction, tokens within tolerance
    assert rec["int8_bytes_ratio"] <= 0.5, rec
    assert rec["int8_kv"]["token_agreement"] >= 0.9, rec
    # speculative: acceptance reported, no slowdown vs dense greedy
    assert rec["speculative"]["spec_acceptance_rate"] > 0.0, rec
    assert rec["spec_speedup"] >= 1.0, rec


# ------------------------------------- one timeline (ISSUE 26 tentpole)
@pytest.fixture
def tracer():
    from bigdl_tpu import telemetry

    tr = telemetry.get_tracer()
    tr.disable()
    tr.clear()
    yield tr
    tr.disable()
    tr.clear()


def test_loop_spans_tile_the_decode_loop(engine_lm, tracer):
    """The structure of a traced run: the seven top-level ``loop/*``
    spans, all on the loop's thread, none overlapping another, every
    child inside its parent.  (How much of the wall time they cover is
    a timing, and a chip trace's to say: ``loop_host_share.serve``.)"""
    model, var = engine_lm
    with _engine(model, var, slots=4, kv_layout="paged",
                 page_size=4) as eng:
        tracer.enable()
        futs = [eng.submit(np.arange(1, 7), 8) for _ in range(10)]
        for f in futs:
            f.result(120)
        tracer.disable()
    spans = tracer.spans()
    loop = sorted((s for s in spans if s.name.startswith("loop/")),
                  key=lambda s: s.t0)
    assert {s.name for s in loop} == {
        "loop/drain_queue", "loop/admit", "loop/chunk_step",
        "loop/budget_pages", "loop/tick_dispatch", "loop/tick_wait",
        "loop/retire"}
    assert len({s.tid for s in loop}) == 1
    assert all(a.t1 <= b.t0 for a, b in zip(loop, loop[1:]))
    # children lie inside their parent, on the same thread
    for child, parent in (("prefill_dispatch", "loop/admit"),
                          ("prefill_wait", "loop/admit"),
                          ("host_sample", "loop/admit"),
                          ("slot_write", "loop/admit"),
                          ("deliver", "loop/retire")):
        kids = [s for s in spans if s.name == child]
        assert kids, child
        for k in kids:
            assert any(p.name == parent and p.tid == k.tid
                       and p.t0 <= k.t0 and k.t1 <= p.t1
                       for p in loop), (child, parent)
    admits = [s.args["admitted"] for s in loop if s.name == "loop/admit"]
    assert sum(admits) == 10
    # a turn's spans share the index of the tick it runs (the turn
    # that was under way at enable() set none)
    ticks = [s for s in loop if s.name == "loop/retire"]
    corrs = {s.corr for s in loop} - {None}
    assert all(c.startswith("tick:") for c in corrs)
    assert len(corrs) >= len(ticks) - 1


def test_tick_dispatch_counts_the_pages_held(engine_lm, tracer):
    """``loop/tick_dispatch`` carries the pages the slots hold (the
    allocator's count, host side): what a tick's attention has to read
    of the ``slots x pages`` extent.  A dense engine has none."""
    model, var = engine_lm
    with _engine(model, var, kv_layout="paged", page_size=4,
                 slots=2) as eng:
        tracer.enable()
        eng.generate([1, 2, 3, 4, 5, 6], 10, timeout=120)
        tracer.disable()
    held = [s.args["pages_held"] for s in tracer.spans()
            if s.name == "loop/tick_dispatch"]
    # 6 prompt tokens then 9 ticks at 4 tokens a page: 2 pages grow to 4
    assert len(held) >= 9 and held == sorted(held)
    assert held[0] == 2 and held[-1] == 4
    tracer.clear()
    with _engine(model, var, slots=2) as eng:
        tracer.enable()
        eng.generate([1, 2, 3], 4, timeout=120)
        tracer.disable()
    ticks = [s for s in tracer.spans() if s.name == "loop/tick_dispatch"]
    assert ticks and all(set(s.args) == {"sampled_rows", "in_flight"}
                         and s.args["sampled_rows"] == 0 for s in ticks)
    # the tick that fills the pipeline goes in from the host mirrors,
    # the request's other two on their predecessor's outputs
    assert [s.args["in_flight"] for s in ticks] == [0, 1, 1]


@pytest.mark.parametrize("layout", ["paged", "dense"])
def test_engine_under_a_live_profiler_session(engine_lm, tracer, tmp_path,
                                              until, layout):
    """What only a ``--trace 1`` run executes, on the CPU: the profiler
    session turns the tracer on, ``_run_tick`` fetches the model's
    counters into the span's ``args``, every ``loop/*`` span is a
    ``TraceAnnotation``, and the benchmark's span readers read the
    ring.  Tokens are those of an untraced engine; ``sampled_rows`` on
    ``loop/tick_dispatch`` (ring and xplane stat) is zero on greedy
    turns, positive while a sampled request decodes, and
    ``ServingMetrics`` counts the same ticks."""
    import glob
    import os

    from jax.profiler import ProfileData

    from benchmark.device import device_only
    from benchmark.run import read_metric

    model, var = engine_lm
    kw = dict(kv_layout="paged", page_size=4) if layout == "paged" else {}
    rs = np.random.RandomState(3)
    greedy = [(rs.randint(0, VOCAB, (t,)), 6, {}) for t in (3, 7, 5)]
    mixed = [(rs.randint(0, VOCAB, (t,)), 8,
              dict(temperature=0.9, top_p=0.9, seed=40 + t) if t % 2
              else {}) for t in (4, 5, 6, 7)]

    def drive(eng, requests):
        futs = [eng.submit(p, n, **opts) for p, n, opts in requests]
        return [list(f.result(120)) for f in futs]

    with _engine(model, var, **kw) as eng:
        want = drive(eng, greedy), drive(eng, mixed)
    with _engine(model, var, **kw) as eng:
        jax.profiler.start_trace(str(tmp_path),
                                 profiler_options=device_only())
        try:
            deadline = time.monotonic() + 30
            while not tracer.enabled and time.monotonic() < deadline:
                time.sleep(0.005)  # the idle loop polls every 5 ms
            assert tracer.enabled
            got_greedy = drive(eng, greedy)
            n_greedy = len([s for s in tracer.spans()
                            if s.name == "loop/tick_dispatch"])
            got_mixed = drive(eng, mixed)
        finally:
            jax.profiler.stop_trace()
        sampled_ticks = eng.metrics.sampled_ticks
        share = eng.metrics.sampled_tick_share()
        assert "sampled=" in eng.log_line()
    assert (got_greedy, got_mixed) == want
    ticks = [s for s in tracer.spans() if s.name == "loop/tick_dispatch"]
    rows = [s.args["sampled_rows"] for s in ticks]
    assert n_greedy >= 5 and rows[:n_greedy] == [0] * n_greedy
    assert max(rows) >= 1 and 0 in rows
    assert sum(1 for r in rows if r) == sampled_ticks > 0
    assert 0.0 < share < 1.0
    assert all(("pages_held" in s.args) == (layout == "paged")
               for s in ticks)
    # the same numbers as stats of the xplane's events
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    (plane,) = [p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU"]
    stats = [dict(ev.stats) for ln in plane.lines for ev in ln.events
             if ev.name == "loop/tick_dispatch"]
    assert [st["sampled_rows"] for st in stats] == rows
    if layout == "paged":
        assert [st["pages_held"] for st in stats] == \
            [s.args["pages_held"] for s in ticks]
    # the span readers of a traced benchmark run read this ring
    run = {"kind": "decode"}
    for metric in ("loop_host_share.serve", "token_gap_p95_ms.serve"):
        assert read_metric(metric, run) > 0
    cost = read_metric("admit_cost_ms.serve", run)
    assert cost is None or isinstance(cost, float)
    # a session that opens and closes under decoding requests: a tick
    # enqueued dark is read lit, and the last lit one is read dark
    tracer.clear()
    long = [(rs.randint(0, VOCAB, (5,)), 2000,
             dict(temperature=1.5, top_p=0.95, seed=7 + i) if i else {})
            for i in range(2)]

    def lit_ticks():
        return [s for s in tracer.spans()
                if s.name == "loop/tick_dispatch"]

    with _engine(model, var, max_len=2048, prompt_buckets=(8,),
                 prefill_batch_sizes=(1,), **kw) as eng:
        want_long = drive(eng, long)
        decoded = eng.metrics.decoded_tokens
        futs = [eng.submit(p, n, **opts) for p, n, opts in long]
        until(lambda: eng.metrics.decoded_tokens >= decoded + 20)
        jax.profiler.start_trace(str(tmp_path / "under_way"),
                                 profiler_options=device_only())
        try:
            until(lambda: len(lit_ticks()) >= 20)
        finally:
            jax.profiler.stop_trace()
        assert not any(f.done() for f in futs)  # closed under decoding
        assert [list(f.result(120)) for f in futs] == want_long
        until(lambda: not tracer.enabled)
    ticks = lit_ticks()
    # the first lit tick went in on its dark predecessor's outputs
    assert ticks[0].args["in_flight"] == 1
    assert all(s.args["sampled_rows"] == 1 and
               ("pages_held" in s.args) == (layout == "paged")
               for s in ticks)
    waits = [s for s in tracer.spans() if s.name == "loop/tick_wait"]
    assert 0 <= len(ticks) - len(waits) <= 1


def test_token_times_ttft_and_gaps(engine_lm, tracer):
    """Every future carries one perf_counter time per returned token;
    ttft is the first of them less the submit, and the gaps_ms of the
    ring's loop/retire spans are the differences of token_times."""
    model, var = engine_lm
    with _engine(model, var, slots=2) as eng:
        eng.generate([1, 2], 2, timeout=120)  # leave warm-up behind
        eng.metrics.base.reset()
        tracer.enable()
        t_before = time.perf_counter()
        fut = eng.submit([1, 2, 3], 7)
        t_after = time.perf_counter()
        tokens = fut.result(120)
        ttft_s = eng.metrics.ttft_ms(50) / 1e3  # its one sample so far
        one = eng.submit([3], 1)  # finished by its prefill token
        one.result(120)
        tracer.disable()
        gap_p50 = eng.metrics.token_gap_ms(50)
    times = fut.token_times
    assert times.dtype == np.float64 and times.shape == tokens.shape == (7,)
    assert np.all(np.diff(times) >= 0)
    assert times[0] - t_after <= ttft_s <= times[0] - t_before
    assert one.token_times.shape == (1,)
    gaps = [g for s in tracer.spans() if s.name == "loop/retire"
            for g in s.args["gaps_ms"]]
    np.testing.assert_allclose(gaps, 1e3 * np.diff(times), rtol=0,
                               atol=1e-6)
    assert min(gaps) <= gap_p50 <= max(gaps)


def test_off_path_decode_loop_creates_no_span(engine_lm, tracer,
                                              monkeypatch):
    """No session, tracer off: the decode loop creates no Span and
    makes at most one profiler-state check per turn."""
    from bigdl_tpu.telemetry import tracer as tracer_mod

    checks, turns, made = [], [], []

    class CountedSpan(tracer_mod.Span):
        def __init__(self, *a, **k):
            made.append(a[0])
            super().__init__(*a, **k)

    def live():
        checks.append(1)
        return False

    model, var = engine_lm
    with _engine(model, var) as eng:
        real = eng._drain_queue  # called once per loop turn

        def counted(*a, **k):
            turns.append(1)
            return real(*a, **k)

        monkeypatch.setattr(tracer_mod, "Span", CountedSpan)
        monkeypatch.setattr(tracer_mod, "_profiler_live", live)
        monkeypatch.setattr(eng, "_drain_queue", counted)
        checks.clear()
        for f in [eng.submit([1, 2, 3], 30) for _ in range(3)]:
            f.result(120)
        n_checks, n_turns = len(checks), len(turns)
    assert n_turns >= 50 and n_checks <= n_turns + 1
    assert not made and len(tracer) == 0


def _scopes_in(jitted, *args):
    """Name-scope components of the lowered program's locations
    (``jit(f)/attention/...``, ``transpose(jvp(attention))/...``)."""
    import re

    text = jitted.lower(*args).as_text(debug_info=True)
    return set(re.findall(r"[/(]([a-z_0-9]+)(?=[/)])", text)), text


def test_device_scopes_name_the_train_step():
    import bigdl_tpu.optim as optim
    from bigdl_tpu.optim.optimizer import make_train_step

    model = _lm()
    var = model.init(jax.random.PRNGKey(0))
    method = optim.Adam(1e-3)
    step = jax.jit(make_train_step(
        model, nn.TimeDistributedCriterion(
            nn.ClassNLLCriterion(logits=True)),
        {"__all__": method}, grad_clip_norm=1.0))
    ids = jnp.zeros((2, 8), jnp.int32)
    scopes, text = _scopes_in(
        step, var["params"], var["state"],
        {"__all__": method.init_state(var["params"])},
        jnp.asarray(1, jnp.int32), jax.random.PRNGKey(0), ids, ids,
        [jnp.asarray(1e-3, jnp.float32)])
    assert {"embed", "attention", "ffn", "head", "loss", "clip",
            "optimizer"} <= scopes
    # the backward of a scope keeps its name
    assert "transpose(jvp(attention))" in text


def test_device_scopes_name_the_decode_programs(engine_lm):
    from bigdl_tpu.serving import decode_programs as decode

    model, var = engine_lm
    slots, page, pages = 2, 4, 8
    cache = model.init_paged_cache(slots * pages + 1, page, slots)
    table = np.zeros((slots, pages), np.int32)
    tok = np.zeros((slots,), np.int32)
    act = np.ones((slots,), bool)
    samp = (np.zeros((slots, 2), np.uint32), np.zeros((slots,), np.float32),
            np.zeros((slots,), np.int32), np.ones((slots,), np.float32))
    scopes, _ = _scopes_in(decode.build_paged_tick(model), var["params"],
                           var["state"], cache, table, tok, act, *samp)
    # off the TPU (and for Tq > 1 or an int8 pool anywhere) attention
    # gathers the extent; the kernel route is named below
    assert {"embed", "paged_append", "paged_gather", "attention", "ffn",
            "head", "sample"} <= scopes
    assert "paged_attention" not in scopes
    # prefill and the slot write run under a top scope of their own
    scopes, _ = _scopes_in(decode.build_prefill(model, page * pages),
                           var["params"], var["state"],
                           np.zeros((1, 4), np.int32),
                           np.ones((1,), np.int32))
    assert {"prefill", "attention", "head"} <= scopes
    scopes, _ = _scopes_in(decode.build_paged_write_slot(), cache,
                           table[0], model.init_cache(1, page * pages),
                           0, 0)
    assert "slot_write" in scopes


def test_device_scopes_name_the_paged_attention_kernel(
        interpreted_paged_attn):
    """The tick as the TPU runs it (one query token, float pool, page
    rows of whole lanes): ``paged_append`` then the ``paged_attn``
    kernel under ``attention/paged_attention``, and no gather."""
    from bigdl_tpu.serving import decode_programs as decode

    model = nn.Transformer(vocab_size=32, hidden_size=128, num_heads=4,
                           filter_size=64, num_layers=1, dropout=0.0,
                           causal=True)
    var = model.init(jax.random.PRNGKey(0))
    slots, page, pages = 2, 8, 4
    cache = model.init_paged_cache(slots * pages + 1, page, slots)
    samp = (np.zeros((slots, 2), np.uint32), np.zeros((slots,), np.float32),
            np.zeros((slots,), np.int32), np.ones((slots,), np.float32))
    scopes, text = _scopes_in(
        decode.build_paged_tick(model), var["params"], var["state"], cache,
        np.zeros((slots, pages), np.int32), np.zeros((slots,), np.int32),
        np.ones((slots,), bool), *samp)
    assert {"paged_append", "paged_attention", "attention"} <= scopes
    assert "paged_gather" not in scopes
    assert "attention/paged_attention" in text and "paged_attn" in text


def test_flash_forward_kernel_is_named():
    import importlib

    flash = importlib.import_module(
        "bigdl_tpu.ops.pallas.flash_attention")
    q = jnp.zeros((1, 2, 256, 64), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v: flash._flash_fwd_pallas(
        q, k, v, True, 0.125, 128, 128, True))(q, q, q)
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "flash_fwd"

