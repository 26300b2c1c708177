"""The decoder of Mamba-2, attention and latent routed-expert layers
(nn/hybrid_ssm.py) against its plain reference
(benchmark/references/hybrid_ssm_lm.py) at a small size on the CPU: the
chunked scan against the stepped recurrence, prefill and decode through
``DecodeEngine`` and its paged cache, the fixed state block a slot
(serving/paging.py, ops/paged_kv.py), the expert shares, the routed
layer's other families unchanged, and the new cell's files."""
import copy
import hashlib
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_hybrid_ssm as counts
from benchmark.references import hybrid_ssm_lm as ref
from bigdl_tpu.nn import hybrid_ssm
from bigdl_tpu.ops import paged_kv
from bigdl_tpu.serving import DecodeEngine, paging

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(vocab_size=64, hidden_size=32, hybrid_override_pattern="MEM*EM",
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
            ssm_state_size=8, conv_kernel=4, expand=2, chunk_size=8,
            n_routed_experts=16, num_experts_per_tok=4,
            moe_intermediate_size=16, moe_latent_size=8,
            moe_shared_expert_intermediate_size=24, n_shared_experts=1,
            routed_scaling_factor=5.0, norm_topk_prob=True, n_group=1,
            topk_group=1, norm_eps=1e-5, ssm_state_dtype="float32",
            time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
            experts_held=[0, 1, 2, 3, 8, 9, 10, 11])
# float32 program against the float32 reference: what differs is the
# order of the sums (logits of magnitude 3-4 agree to 1e-5); a product
# with operands rounded to bf16 moves them by 1e-2, and a state held in
# bf16 by more than 1e-3 (test_lower_precisions_fail_the_tolerance)
ATOL = 1e-4


def build(seed=0, **over):
    """Weights at the benchmark's scale (every matrix N(0, 1/fan_in),
    the embedding N(0, 1), the depthwise kernel N(0, 1/4)), norm
    weights, D and the router's bias off their neutral values so that
    each of them matters; A_log and dt_bias as Mamba-2 draws them."""
    cfg = dict(TINY, **over)
    model = hybrid_ssm.HybridSSMTransformer(**cfg)
    var = model.init(jax.random.PRNGKey(seed))
    flat, tree = jax.tree_util.tree_flatten_with_path(var["params"])
    out = []
    for i, (path, leaf) in enumerate(flat):
        key = jax.random.fold_in(jax.random.PRNGKey(seed + 1), i)
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        noise = jax.random.normal(key, leaf.shape)
        if name == "embed/weight":
            out.append(noise)
        elif name.endswith("conv_w"):
            out.append(0.5 * noise)
        elif leaf.ndim >= 2:
            out.append(noise / np.sqrt(leaf.shape[-2]))
        elif name.endswith(("A_log", "dt_bias")):
            out.append(leaf)
        elif name.endswith(("router/bias", "conv_b")):
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
                                         0, vocab), np.int32)


# ------------------------------------------------------------- the scan
def stepped(x, dt, a, b, c, state):
    """The recurrence one step after the other (the reference's form)."""
    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, None, :]
        return s, jnp.einsum("ngrps,ngs->ngrp", s, c_t,
                             precision=jax.lax.Precision.HIGHEST)

    last, y = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), last


@pytest.mark.parametrize("t,chunk", [(300, 128), (2100, 1024), (7, 8)])
def test_chunked_scan_is_the_stepped_recurrence(t, chunk):
    """``ssd_scan`` at the published chunk of 128 over a length that is
    no multiple of it, across a boundary of the engine's 2048-token
    prompt chunks (2100 rows from a state carried in), and shorter than
    one chunk."""
    n, g, r, p, s = 2, 2, 3, 4, 5
    ks = jax.random.split(jax.random.PRNGKey(t), 6)
    x = jax.random.normal(ks[0], (n, t, g, r, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (n, t, g, r)) - 3.0)
    dt = dt.at[1, t // 2:].set(0.0)            # padding moves nothing
    a = -jnp.exp(jax.random.uniform(ks[2], (g, r), maxval=2.7))
    b = jax.random.normal(ks[3], (n, t, g, s))
    c = jax.random.normal(ks[4], (n, t, g, s))
    state = jax.random.normal(ks[5], (n, g, r, p, s))
    y, last = hybrid_ssm.ssd_scan(x, dt, a, b, c, state, chunk)
    want_y, want_last = stepped(x, dt, a, b, c, state)
    np.testing.assert_allclose(y, want_y, atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(last, want_last, atol=2e-4, rtol=1e-4)
    # the padded row's state is the one its last real step left
    _, half = stepped(x[1:, :t // 2], dt[1:, :t // 2], a, b[1:, :t // 2],
                      c[1:, :t // 2], state[1:])
    np.testing.assert_allclose(last[1:], half, atol=2e-4, rtol=1e-4)


# ------------------------------------------------------------ the reference
def test_full_forward_logits_match_the_reference(tiny):
    model, var, cfg = tiny
    ids = ids_of(1, 2, 37)                 # five scan chunks, not whole
    got, _ = model.apply(var["params"], var["state"], ids)
    for row in range(2):
        want = ref.logits_fn(var["params"], ids[row], cfg)
        assert float(jnp.abs(want).max()) > 1.0
        np.testing.assert_allclose(got[row], want, atol=ATOL)


def test_lower_precisions_fail_the_tolerance(tiny):
    """The nearest precision below the test's: the reference with its
    products' operands rounded to bf16, and the program with its state
    held in bf16, are outside ``ATOL``."""
    model, var, cfg = tiny
    ids = ids_of(1, 40)
    want = ref.logits_fn(var["params"], ids, cfg)
    low = ref.logits_fn(var["params"], ids, cfg, "bf16")
    assert float(jnp.abs(low - want).max()) > 10 * ATOL
    bf16 = hybrid_ssm.HybridSSMTransformer(
        **dict(cfg, ssm_state_dtype="bfloat16"))
    cache = bf16.init_cache(1, 48)
    assert cache["layer0"]["ssm"].dtype == jnp.bfloat16
    _, cache = bf16.prefill(var["params"], var["state"], ids[None, :8],
                            cache)
    worst = 0.0
    for j in range(8, 40):
        logits, cache = bf16.decode_step(var["params"], var["state"], cache,
                                         jnp.asarray(ids[j:j + 1]))
        if j + 1 < 40:
            worst = max(worst, float(jnp.abs(logits[0] - want[j]).max()))
    assert worst > 10 * ATOL


@pytest.mark.parametrize("prompt,steps", [(9, 14), (21, 8)])
def test_prefill_then_decode_through_the_dense_cache(tiny, prompt, steps):
    model, var, cfg = tiny
    ids = ids_of(2 + prompt, prompt + steps)
    want = ref.logits_fn(var["params"], ids, cfg)
    cache = model.init_cache(1, 64)
    last, cache = model.prefill(var["params"], var["state"],
                                ids[None, :prompt], cache)
    np.testing.assert_allclose(last[0], want[prompt - 1], atol=ATOL)
    for j in range(prompt, prompt + steps - 1):
        last, cache = model.decode_step(var["params"], var["state"], cache,
                                        jnp.asarray(ids[j:j + 1]))
        np.testing.assert_allclose(last[0], want[j], atol=ATOL)


def test_pads_of_a_bucket_do_not_move_the_state(tiny):
    """A prompt padded to a bucket and the same prompt unpadded leave
    the same state, convolution history and next logits; decoding on
    from either gives the same logits."""
    model, var, _ = tiny
    ids = ids_of(3, 13)
    padded = np.zeros((1, 24), np.int32)
    padded[0, :13] = ids
    padded[0, 13:] = ids_of(4, 11)             # pads that are no zeros
    outs = []
    for x, lengths in ((ids[None], None), (padded, jnp.asarray([13]))):
        cache = model.init_cache(1, 64)
        last, cache = model.prefill(var["params"], var["state"], x, cache,
                                    lengths)
        steps = []
        for tok in ids_of(5, 6):
            logits, cache = model.decode_step(var["params"], var["state"],
                                              cache, jnp.asarray([tok]))
            steps.append(logits)
        outs.append((last, cache, steps))
    (l0, c0, s0), (l1, c1, s1) = outs
    np.testing.assert_allclose(l1, l0, atol=1e-5)
    for lk in ("layer0", "layer2", "layer5"):
        for leaf in ("ssm", "conv"):
            np.testing.assert_allclose(c1[lk][leaf], c0[lk][leaf],
                                       atol=1e-5)
    np.testing.assert_allclose(jnp.stack(s1), jnp.stack(s0), atol=1e-5)
    assert int(c1["layer0"]["length"][0]) == 19


def test_the_state_is_a_block_a_slot(tiny):
    """Mamba-2 layers declare their state and history as blocks, the
    attention layer its K and V rows; a routed layer keeps nothing."""
    model, _, _ = tiny
    state = model.decode_state()
    assert sorted(state) == ["layer0", "layer2", "layer3", "layer5"]
    assert state["layer3"] == {"k": (2, 8), "v": (2, 8)}
    assert state["layer0"] == {"ssm": paged_kv.Block((8, 8, 8), "float32"),
                               "conv": paged_kv.Block((3, 64 + 2 * 2 * 8))}
    assert paged_kv.page_bytes(4, state["layer0"]) == 0
    pool = model.init_paged_cache(9, 4, 3, jnp.bfloat16)
    assert pool["layer0"]["ssm"].shape == (3, 8, 8, 8)
    assert pool["layer0"]["ssm"].dtype == jnp.float32
    assert pool["layer0"]["conv"].dtype == jnp.bfloat16
    assert pool["layer3"]["k"].shape == (9, 4, 16)
    assert model.decode_extents() == dict.fromkeys(state)


# ------------------------------------------------------------ paged cache
def paged(model, slots=3, max_len=64, page=4):
    kv = paging.PagedCache(slots, max_len, page,
                           paging.default_num_pages(slots, max_len, page))
    cache = kv.init_cache(model, jnp.float32)
    return kv, cache, kv.build_write()


def test_paged_decode_logits_and_what_a_slot_holds(tiny):
    """Prefill, the slot write, then the paged tick with another slot
    idle: the reference's logits at every position; the idle slot's
    block does not move, the counters say which slots hold a block."""
    model, var, cfg = tiny
    slot, prompt, steps = 1, 11, 20
    ids = ids_of(6, prompt + steps)
    want = ref.logits_fn(var["params"], ids, cfg)
    kv, cache, write = paged(model)
    assert kv.block_bytes == 3 * (8 * 8 * 8 + 3 * 96) * 4
    assert kv.page_bytes == 2 * 4 * 16 * 4          # the attention layer
    idle = {lk: np.asarray(c["ssm"][2]) for lk, c in cache.items()
            if "ssm" in c}
    dense = model.init_cache(1, 64)
    _, dense = model.prefill(var["params"], var["state"],
                             ids[None, :prompt], dense)
    assert kv.span_args() == {"pages_held": 0, "state_blocks_held": 0}
    assert kv.reserve(slot, prompt + 1)
    cache = write(cache, *kv.write_extra(slot), dense, 0, slot)
    assert kv.span_args()["state_blocks_held"] == 1
    assert kv.resident_bytes() == kv.owned(slot) * kv.page_bytes \
        + kv.block_bytes
    active = np.arange(3) == slot
    tokens = np.zeros((3,), np.int32)
    for j in range(steps):
        length = prompt + j
        assert kv.reserve(slot, length + 1)
        tokens[slot] = ids[length]
        logits, cache, counters = model.decode_step_paged(
            var["params"], var["state"], cache, kv.tick_extra()[0],
            jnp.asarray(tokens), jnp.asarray(active))
        np.testing.assert_allclose(logits[slot], want[length], atol=ATOL)
        assert counters["expert_tokens"].shape == (2, 8)
    for lk, block in idle.items():
        np.testing.assert_array_equal(cache[lk]["ssm"][2], block)
    kv.release(slot)
    assert kv.span_args() == {"pages_held": 0, "state_blocks_held": 0}
    assert kv.resident_bytes() == 0


def test_a_reused_slot_starts_from_a_zero_state(tiny):
    """Serve a request, release its slot, and serve another from the
    same slot: its logits are those of a fresh slot (the reference's),
    whatever state the first request left."""
    model, var, cfg = tiny
    kv, cache, write = paged(model, slots=1, max_len=48, page=4)
    active = jnp.ones((1,), bool)
    for seed, prompt, steps in ((7, 17, 12), (8, 5, 16)):
        ids = ids_of(seed, prompt + steps)
        want = ref.logits_fn(var["params"], ids, cfg)
        dense = model.init_cache(1, 48)
        _, dense = model.prefill(var["params"], var["state"],
                                 ids[None, :prompt], dense)
        assert kv.reserve(0, prompt + 1)
        if seed == 8:   # what the first request left is still there
            assert float(jnp.abs(cache["layer0"]["ssm"]).max()) > 0
        cache = write(cache, *kv.write_extra(0), dense, 0, 0)
        for j in range(steps):
            assert kv.reserve(0, prompt + j + 1)
            logits, cache, _ = model.decode_step_paged(
                var["params"], var["state"], cache, kv.tick_extra()[0],
                jnp.asarray(ids[prompt + j:prompt + j + 1]), active)
            np.testing.assert_allclose(logits[0], want[prompt + j],
                                       atol=ATOL)
        kv.release(0)


# ------------------------------------------------------------------ engine
@pytest.fixture(scope="module")
def engine(tiny):
    model, var, _ = tiny
    eng = DecodeEngine(model, var, slots=3, max_len=80,
                       prompt_buckets=[8], prefill_batch_sizes=[1, 2],
                       kv_layout="paged", page_size=4, prefill_chunk=16)
    yield eng
    eng.close()


def test_engine_declares_and_compiles_its_programs(engine):
    # tick, prefill 1x8 and 2x8, write 1 and 2, the chunk
    assert engine.declared_programs() == engine.recompiles == 6
    assert engine._kv.band is None
    assert engine._kv.tick_extra()[0].shape == (3, 20)


@pytest.mark.parametrize("prompt_len,steps", [(5, 24), (8, 6), (37, 20),
                                              (16, 12)])
def test_engine_logits_are_the_references(tiny, engine, prompt_len, steps):
    """Bucketed prefill (<= 8) and chunked prefill (> 8: chunks of 16,
    the state carried from chunk to chunk, 37 across two boundaries),
    then the tick: every served token is the reference's best at its
    position, and the logits the engine's programs give for the served
    sequence are the reference's."""
    model, var, cfg = tiny
    prompt = ids_of(10 + prompt_len, prompt_len)
    got = engine.generate(prompt, steps, timeout=300)
    gaps = ref.served_gaps(var["params"], prompt, got, cfg, pad_to=8)
    assert gaps["gaps"].max() == 0.0
    assert engine.recompiles == 6
    assert engine._kv.pages_in_use == 0
    assert engine._kv.span_args()["state_blocks_held"] == 0
    # the chunk program's own logits at each chunk's last row
    ids = np.concatenate([prompt, got[:-1]])
    want = ref.logits_fn(var["params"], ids, cfg)
    staging = engine._target.staging()
    for lo in range(0, prompt_len, 16):
        hi = min(lo + 16, prompt_len)
        chunk = np.zeros((1, 16), np.int32)
        chunk[0, :hi - lo] = prompt[lo:hi]
        last, staging = engine._target.chunk(staging, chunk,
                                             np.array([hi - lo], np.int32))
        np.testing.assert_allclose(last[0], want[hi - 1], atol=ATOL)


def test_a_finished_requests_blocks_are_its_last_state(tiny, engine):
    """``submit(keep_blocks=True)``: the future holds the blocks its
    slot ended with, the reference's state after the prompt and every
    served token but the last, for a chunked and a bucketed prompt
    served side by side; a request that does not ask gets none."""
    _, var, cfg = tiny
    prompts = [ids_of(60, 37), ids_of(61, 6)]
    futs = [engine.submit(p, 18, keep_blocks=True) for p in prompts]
    plain = engine.submit(ids_of(62, 5), 4)
    for prompt, fut in zip(prompts, futs):
        served = fut.result(300)
        ids = np.concatenate([prompt, served[:-1]])
        want = ref.final_states(var["params"], ids, cfg, pad_to=64)
        assert sorted(want) == sorted(fut.blocks) == ["layer0", "layer2",
                                                     "layer5"]
        for lk, state in want.items():
            assert set(fut.blocks[lk]) == {"ssm", "conv"}
            got = np.asarray(fut.blocks[lk]["ssm"]).reshape(state.shape)
            np.testing.assert_allclose(got, state, atol=ATOL)
        # one more token fed is another state
        past = ref.final_states(var["params"], np.append(ids, served[-1]),
                                cfg, pad_to=64)
        assert np.abs(past["layer0"] - want["layer0"]).max() > 10 * ATOL
    plain.result(300)
    assert plain.blocks is None
    assert engine.recompiles == 6


def test_the_references_final_states_ignore_pads(tiny):
    """The state after the last real token whatever the padding, and a
    state held in bfloat16 between steps lies well away from it."""
    _, var, cfg = tiny
    ids = ids_of(63, 29)
    a = ref.final_states(var["params"], ids, cfg, pad_to=32)
    b = ref.final_states(var["params"], ids, cfg, pad_to=96)
    low = ref.final_states(var["params"], ids, cfg, pad_to=32,
                           state_dtype="bfloat16")
    for lk in a:
        np.testing.assert_allclose(a[lk], b[lk], atol=1e-6)
        gap = np.linalg.norm(low[lk] - a[lk]) / np.linalg.norm(a[lk])
        assert gap > 1e-3, (lk, gap)


def test_engine_rows_in_one_tick_and_the_tick_spans_counters(tiny, engine):
    from bigdl_tpu.telemetry import get_tracer

    _, var, cfg = tiny
    tracer = get_tracer()
    prompts = [ids_of(40 + i, n) for i, n in enumerate((6, 19, 7))]
    tracer.clear()
    tracer.enable()
    try:
        futs = [engine.submit(p, 10) for p in prompts]
        got = [f.result(300) for f in futs]
    finally:
        tracer.disable()
    for p, g in zip(prompts, got):
        assert ref.served_gaps(var["params"], p, g, cfg,
                               pad_to=8)["gaps"].max() == 0.0
    ticks = [s.args for s in tracer.spans()
             if s.name == "loop/tick_dispatch"]
    assert ticks and max(a["state_blocks_held"] for a in ticks) >= 2
    assert all(a["state_blocks_held"] <= 3 for a in ticks)
    rows = [s.args["rows"] for s in tracer.spans()
            if s.name == "prefill_dispatch"]
    assert rows and set(rows) <= {8, 16}
    traced = counts.traced_ticks({"traffic": {"page_size": 4}})
    assert traced and all(np.shape(t["expert_tokens"]) == (2, 8)
                          for t in traced)
    tracer.clear()


# ----------------------------------------------------------- expert shares
def test_the_four_shares_of_the_experts_sum_to_the_uncut_layer():
    """Four chips that hold a quarter of the experts each: their routed
    parts, and the shared expert counted once, sum to what the layer
    with every expert gives (in the latent space, before ``W_up``,
    which is linear)."""
    from bigdl_tpu.nn.routed import RoutedExperts

    whole = RoutedExperts(32, 16, 16, 4, activation="relu2",
                          latent_size=8, shared_width=24,
                          routed_scaling_factor=5.0)
    params = whole.init_params(jax.random.PRNGKey(3))
    params = jax.tree_util.tree_map(lambda v: v * 10.0, params)
    x = jax.random.normal(jax.random.PRNGKey(4), (40, 32))
    want, counts_all = whole.apply_counted(params, x)
    total = 0.0
    for share in range(4):
        held = list(range(4 * share, 4 * share + 4))
        part = RoutedExperts(32, 16, 16, 4, activation="relu2",
                             latent_size=8, shared_width=24,
                             routed_scaling_factor=5.0, experts_held=held)
        p = dict(params, experts=jax.tree_util.tree_map(
            lambda w: w[np.asarray(held)], params["experts"]))
        y, c = part.apply_counted(p, x, include_shared=share == 0)
        np.testing.assert_array_equal(c, counts_all[4 * share:
                                                    4 * share + 4])
        total = total + y
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(total, want, atol=1e-4, rtol=1e-5)
    # and the reference computes one share as the layer does
    cfg = dict(TINY, experts_held=[0, 1, 2, 3], n_routed_experts=16,
               num_experts_per_tok=4, routed_scaling_factor=5.0)
    part = RoutedExperts(32, 16, 16, 4, activation="relu2", latent_size=8,
                         shared_width=24, routed_scaling_factor=5.0,
                         experts_held=[0, 1, 2, 3])
    p = dict(params, experts=jax.tree_util.tree_map(
        lambda w: w[:4], params["experts"]))
    got, _ = part.apply_counted(p, x)
    with jax.default_matmul_precision("highest"):
        mine, _ = ref.routed(x, p, cfg, "reference")
    np.testing.assert_allclose(got, mine, atol=1e-4, rtol=1e-5)


# ------------------------------------------- the routed layer's families
def _digest(family: str) -> str:
    """SHA-256 of the bf16 logits of a tiny routed model of ``family``:
    the full forward over 300 tokens (the routed buffer's long-batch
    branch), a prefill and four decode steps."""
    if family == "latent":
        import test_latent_moe as fam
    else:
        import test_window_moe as fam
    model, var, _ = fam.build(seed=4)
    params = jax.tree_util.tree_map(lambda v: v.astype(jnp.bfloat16),
                                    var["params"])
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(11), (2, 300),
                                        0, 96))
    h = hashlib.sha256()
    logits, _ = jax.jit(model.apply)(params, var["state"], ids)
    h.update(np.asarray(logits, np.float32).tobytes())
    cache = model.init_cache(2, 320, jnp.bfloat16)
    last, cache = jax.jit(model.prefill)(params, var["state"],
                                         ids[:, :290], cache)
    h.update(np.asarray(last, np.float32).tobytes())
    step = jax.jit(model.decode_step)
    for j in range(4):
        last, cache = step(params, var["state"], cache,
                           jnp.asarray(ids[:, 290 + j]))
        h.update(np.asarray(last, np.float32).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("family,digest", [
    ("latent",
     "c8259cca729aa5f0bfb508857f637fb3c8541bf6777a9504ebcb49f36243eef2"),
    ("window",
     "ec79a78c504c73dd7f0798eb85670427eb463b7f0f65b0c4442d1a97ccde5508")])
def test_the_gated_families_logits_are_bit_equal_to_before(family, digest):
    """The routed layer took the two-matrix expert and the latent space
    as arguments; the gigachat and trinity families' logits are what
    they were before, bit for bit (the digests of the parent commit)."""
    assert _digest(family) == digest


# ------------------------------------------------------------------- counts
def test_operations_and_bytes_against_a_hand_count():
    cfg = TINY
    d, di, h, p, n, g, conv = 32, 64, 8, 8, 8, 2, 96
    mamba = d * (di + conv + h) + di * d + 5 * conv + 3 * h + di
    attn = 2 * d * 4 * 8 + 2 * d * 2 * 8
    moe = d * 16 + 2 * d * 8 + 2 * d * 24
    expert = 2 * 8 * 16
    once = 3 * mamba + attn + 2 * moe + d * 64
    assert counts.resident_params(cfg) == {"read_every_tick": once,
                                           "one_expert": expert}
    model = hybrid_ssm.HybridSSMTransformer(**cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    assert counts.parameter_count(cfg) == sum(
        int(np.prod(x.shape))
        for x in jax.tree_util.tree_leaves(shapes["params"]))
    step = counts.ssm_step_cost(cfg, 3)
    assert step == {"flops": 3 * 4 * h * p * n,
                    "bytes": 3 * 4 * (2 * h * p * n + 2 * di + 2 * g * n
                                      + h)}
    # 3 rows, 40 K/V rows held, 3 blocks, 10 assignments on 6 experts
    got = counts.tick_cost(cfg, 3, 40, 3, 10, 6)
    assert got["flops"] == 3 * 2 * once + 40 * 4 * 2 * 2 * 8 \
        + 10 * 2 * expert + 3 * step["flops"]
    assert got["bytes"] == 2 * (once + 6 * expert + 3 * d) \
        + 2 * (40 * 2 * 2 * 8 + 3 * 2 * 4 * 8) + 3 * step["bytes"]
    scan = counts.ssd_cost(cfg, 13)           # two chunks of 8
    assert scan["flops"] == 2 * 16 * (8 * g * n + 8 * h * p + 2 * h * p * n)
    assert scan["bytes"] == 4 * (16 * (2 * h * p + 2 * g * n + h)
                                 + 2 * h * p * n)


# ---------------------------------------------------------------- the files
def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


CONFIG = ("configs", "nemotron3-super-11of88-ep4share.json")


def test_published_numbers_are_all_in_the_configuration_file():
    cell = load(*CONFIG)
    # the model card's published config, kept beside the tests
    with open(os.path.join(os.path.dirname(__file__),
                           "nemotron3_super_published.json")) as f:
        row = json.load(f)
    assert row["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
    assert cell["source"] == row["source_url"]
    assert cell["published"] == row["config"]
    assert cell["reduced"] == ["num_hidden_layers",
                               "hybrid_override_pattern", "n_routed_experts",
                               "vocab_size", "num_nextn_predict_layers"]
    for key, value in row["config"].items():
        assert cell[key] == value or key in cell["reduced"], key
    assert set(cell["reduced_how"]) == set(cell["reduced"])
    assert {"attention_without_rotary", "router_reads_full_hidden",
            "latent_projections", "ssm_state_float32", "weights"} \
        <= set(cell["assumed"])
    assert cell["deployment"]["chips_sharing_a_layer"] == 4
    m = cell["model"]
    pattern = row["config"]["hybrid_override_pattern"]
    assert m["hybrid_override_pattern"] == pattern[26:37] == "EMEMEMEMEM*"
    assert m["experts_held"] == list(range(128))
    assert (m["n_routed_experts"], m["vocab_size"]) == (512, 32768)
    for key in ("hidden_size", "mamba_num_heads", "mamba_head_dim",
                "n_groups", "ssm_state_size", "conv_kernel", "expand",
                "chunk_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "num_experts_per_tok", "moe_intermediate_size",
                "moe_latent_size", "moe_shared_expert_intermediate_size",
                "routed_scaling_factor", "norm_topk_prob", "time_step_min",
                "time_step_max", "time_step_floor"):
        assert m[key] == row["config"][key], key


def test_the_configuration_builds_the_class_at_4_648_billion():
    from benchmark import weights
    from benchmark.drivers import decode_hybrid_ssm

    cell = load(*CONFIG)
    model = decode_hybrid_ssm.build_model(cell)
    assert isinstance(model, hybrid_ssm.HybridSSMTransformer)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(shapes["params"]))
    assert n == counts.parameter_count(cell["model"]) == 4648163712
    assert round(2 * n / 2 ** 30, 2) == 8.66
    # every leaf has an init rule; each matrix gets 1/sqrt(fan_in)
    for path, leaf in zip(weights.leaf_paths(shapes),
                          jax.tree_util.tree_leaves(shapes)):
        kind, number = weights._rule_for(path, cell["serve"]["init"])
        if leaf.ndim >= 2 and "embed" not in path:
            fan_in = 4 if path.endswith("conv_w") else leaf.shape[-2]
            centered = path.endswith(("mamba/w_out", "experts/wd",
                                      "shared/wd"))
            assert kind == ("normal_centered" if centered else "normal")
            assert number == pytest.approx(fan_in ** -0.5, rel=1e-4), path
        elif "embed" not in path and kind not in (
                decode_hybrid_ssm.SPECIAL):
            assert (kind, number) == ("const", 0.0 if path.endswith(
                ("bias", "conv_b")) else 1.0), path


def test_the_drivers_time_step_draws():
    """``A_log`` is log U(1, 16), ``dt_bias`` the inverse softplus of a
    draw log-uniform in the time step's bounds, both the model's own
    ``Mamba2Mixer.draw_decay``; every other leaf is the generic
    rules'."""
    from benchmark.drivers import decode_hybrid_ssm

    config = load(*CONFIG)
    config["model"] = dict(TINY)
    config["serve"]["dtype"] = "float32"
    model = decode_hybrid_ssm.build_model(config)
    var = decode_hybrid_ssm.make_variables(config, model, 2 ** 31 + 7)
    again = decode_hybrid_ssm.make_variables(config, model, 2 ** 31 + 7)
    mamba = var["params"]["layer0"]["mamba"]
    # the model's own draw, one definition
    from benchmark import weights
    paths = weights.leaf_paths(var)
    key = jax.random.fold_in(weights.seed_key(2 ** 31 + 7), 1 << 20)
    for leaf in ("A_log", "dt_bias"):
        i = paths.index(f"params/layer0/mamba/{leaf}")
        want = model.layers[0].attn.draw_decay(jax.random.fold_in(key, i))
        np.testing.assert_array_equal(mamba[leaf], want[leaf])
    a = np.exp(np.asarray(mamba["A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 1.0
    dt = np.log1p(np.exp(np.asarray(mamba["dt_bias"], np.float64)))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    assert not np.array_equal(mamba["A_log"],
                              var["params"]["layer2"]["mamba"]["A_log"])
    np.testing.assert_array_equal(mamba["dt_bias"],
                                  again["params"]["layer0"]["mamba"][
                                      "dt_bias"])
    np.testing.assert_array_equal(mamba["D"], 1.0)
    # the second matrices after a positive activation sum to zero over
    # their inputs, at the rule's scale
    params = var["params"]
    for w, std in ((mamba["w_out"], 8192 ** -0.5),
                   (params["layer1"]["ffn"]["experts"]["wd"], 2688 ** -0.5),
                   (params["layer1"]["ffn"]["shared"]["wd"], 5376 ** -0.5)):
        w = np.asarray(w, np.float64)
        assert np.abs(w.sum(axis=-2)).max() < 1e-5
        assert w.std() == pytest.approx(std, rel=0.1)


def test_the_cells_files_hold_its_geometry_and_readers():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"]
                if w["name"] == "nemotron-decode-closed128")
    assert cell["chips"] == 1 \
        and cell["config"] == "nemotron3-super-11of88-ep4share"
    mix = load("traffic", cell["traffic"] + ".json")
    assert mix["driver"] == "decode_hybrid_ssm"
    assert mix["clients"] == mix["slots"] == 128 and mix["strata"] == 32
    assert (mix["max_len"], mix["page_size"], mix["prefill_chunk"],
            mix["prompt_buckets"], mix["prefill_batch_sizes"]) \
        == (8192, 64, 2048, [512, 2048], [1])
    assert mix["prompt_tokens"] == {"median": 512, "sigma": 1.0,
                                    "min": 128, "max": 6144}
    assert mix["output_tokens"] == {"median": 768, "sigma": 0.5,
                                    "min": 128, "max": 1536}
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= mix["max_len"]
    assert (mix["supply_requests_per_s"], mix["lead_in_s"],
            mix["trace_seconds"], mix["check_requests"]) \
        == (16.0, 24.0, 3, 4)
    limits = load("cells", "nemotron-decode-closed128.json")["limits"]
    assert set(limits) == {"served_mean_gap", "ssm_state_gap"}
    listed = [m["name"] for m in bench["per_layer"]
              if "nemotron-decode-closed128" in m.get("workloads", [])]
    assert sorted(listed) == sorted(
        [n + ".moe_serve" for n in (
            "tick_ms_p50", "device_idle_share", "prefill_device_share",
            "expert_load_max_over_mean")]
        + [n + ".ssm_moe_serve" for n in (
            "tick_mfu", "tick_hbm_roofline", "ssm_step_roofline",
            "ssd_prefill_roofline", "moe_experts_roofline")])
    for name in listed:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           name + ".py")), name
    e2e = next(m for m in bench["end_to_end"]
               if m["name"] == "decode_tokens_per_s")
    assert "nemotron-decode-closed128" in e2e["workloads"]
    # what the chip holds at the cell's size: weights 8.66 GiB, five
    # layers' state blocks 2.54 GiB, the attention layer's K/V 1.0 GiB
    model = hybrid_ssm.HybridSSMTransformer(**load(*CONFIG)["model"])
    kv = paging.PagedCache(128, 8192, 64,
                           paging.default_num_pages(128, 8192, 64))
    pool = jax.eval_shape(lambda: kv.init_cache(model, jnp.bfloat16))
    assert round(128 * kv.block_bytes / 2 ** 30, 2) == 2.54
    assert round((kv.num_pages - 1) * kv.page_bytes / 2 ** 30, 2) == 1.0
    assert pool["layer10"]["k"].shape == (kv.num_pages, 64, 256)


# --------------------------------------------------------------- benchmark
def tiny_cell():
    """The cell's own files at tiny widths (the published widths stay
    in the files): the driver runs end to end on the CPU in seconds."""
    config = load(*CONFIG)
    config["model"] = dict(TINY, hybrid_override_pattern="EMEM*")
    config["serve"]["dtype"] = "float32"
    mix = load("traffic", "decode-reasoning-closed128.json")
    mix.update(slots=4, clients=4, strata=4, supply_requests_per_s=800.0,
               max_len=96, page_size=8, prompt_buckets=[8, 16],
               prefill_chunk=16, lead_in_s=0.5,
               prompt_tokens={"median": 12, "sigma": 0.8, "min": 2,
                              "max": 48},
               output_tokens={"median": 16, "sigma": 0.5, "min": 2,
                              "max": 40})
    return copy.deepcopy({
        "name": "tiny-nemotron", "chips": 1, "config": config,
        "traffic": mix, "limits": {"served_logit_gap": 1e-3,
                                   "served_mean_gap": 1e-4,
                                   "ssm_state_gap": 1e-4}})


def test_the_state_check_fails_a_state_held_in_bf16(monkeypatch):
    """The driver's state check on the tiny cell: the Mamba-2 layer in
    float32 reads the stepped recurrence to the order of its sums; the
    same check with the configuration's state in bfloat16 (the planted
    fault) fails the cell's limit, through the chunked and the bucketed
    prompt alike."""
    from benchmark.drivers import decode_hybrid_ssm

    monkeypatch.setattr(ref, "PAD_TO", (32, 96))
    limit = load("cells", "nemotron-decode-closed128.json")["limits"][
        "ssm_state_gap"]
    cell = tiny_cell()
    requests = decode_hybrid_ssm.state_requests(
        cell["traffic"], 2 ** 31 + 9, 2.0, TINY["vocab_size"])
    assert len(requests) == decode_hybrid_ssm.STATE_REQUESTS
    assert requests[0]["prompt"].size > cell["traffic"]["prefill_chunk"]
    assert min(r["prompt"].size for r in requests) \
        <= max(cell["traffic"]["prompt_buckets"])
    good = decode_hybrid_ssm.state_check(cell, 2 ** 31 + 9, 2.0)
    cell["config"]["model"]["ssm_state_dtype"] = "bfloat16"
    bad = decode_hybrid_ssm.state_check(cell, 2 ** 31 + 9, 2.0)
    assert good["ssm_state_gap"] < limit / 30
    assert bad["ssm_state_gap"] > 5 * limit
    assert jax.config.jax_default_matmul_precision is None


def test_benchmark_driver_end_to_end_and_its_readers(monkeypatch):
    """``drivers/decode_hybrid_ssm`` on the tiny cell: correct against
    the plain reference, chunked prompts among them; the fp8 control is
    not; the new readers read the traced ticks' counters and nothing
    without them."""
    from benchmark import check
    from benchmark.device import CompileCount
    from benchmark.drivers import decode_hybrid_ssm
    from benchmark.run import read_metric
    from bigdl_tpu.telemetry import get_tracer

    cell = tiny_cell()
    device = {"platform": "cpu", "kind": "cpu", "count": 1,
              "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
    monkeypatch.setattr(ref, "PAD_TO", (32, 96))     # the cell's: 2048..
    monkeypatch.setattr(ref, "HEAD_ROWS", 8)
    tracer = get_tracer()
    tracer.clear()
    tracer.enable()
    try:
        run = decode_hybrid_ssm.run(
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
    # the state check: float32 against the stepped recurrence, and the
    # reference's own state held in bf16 fails the cell's limit
    state_limit = load("cells", "nemotron-decode-closed128.json")[
        "limits"]["ssm_state_gap"]
    assert run["numbers"]["ssm_state_gap"] < state_limit / 30
    assert run["control_numbers"]["ssm_state_gap"] > 5 * state_limit
    run["trace"] = {"by_module": {"jit_tick": [2e-3, 2],
                                  "jit_chunk": [1e-3, 1]},
                    "busy_s": 4e-3, "window_s": 1.0}
    tick_ops = [["multiply_reduce_fusion", "mixer/ssm", 4e-4],
                ["fusion.7", "ffn/moe/experts", 1e-4],
                ["ragged-dot-none.1 tpu_custom_call", "-", 3e-4],
                ["fusion.1", "-", 6e-4]]
    run["program_ops"] = {
        "jit_tick": {"runs": 2, "ops": tick_ops},
        "jit_chunk": {"runs": 1, "ops": [["fusion.3", "mixer/ssd", 2e-4]]},
        "jit_prefill": {"runs": 2, "ops": [
            ["fusion.4", "prefill/mixer/ssd", 3e-4]]}}
    names = ("tick_mfu", "tick_hbm_roofline", "ssm_step_roofline",
             "ssd_prefill_roofline", "moe_experts_roofline")
    got = {n: read_metric(n + ".ssm_moe_serve", run) for n in names}
    assert all(v is not None and v > 0 for v in got.values()), got
    for n in ("prefill_device_share", "expert_load_max_over_mean",
              "tick_ms_p50", "device_idle_share"):
        assert read_metric(n + ".moe_serve", run) is not None, n
    # the scan's reader counts the chunk's and the prefills' rows
    runs = counts.ssd_runs(run)
    assert runs[0] == 16 and len(runs) == 3 and runs[1] == runs[2]
    assert 8 <= runs[1] <= 16
    # the state step's calls are counted: without them nothing to read
    run["program_ops"]["jit_tick"]["ops"] = tick_ops[1:]
    assert read_metric("ssm_step_roofline.ssm_moe_serve", run) is None
    # a program without state blocks (the parent's): nothing to read
    for s in tracer.spans():
        if s.name == "loop/tick_dispatch" and s.args:
            s.args.pop("state_blocks_held", None)
    run["program_ops"]["jit_tick"]["ops"] = tick_ops
    for n in ("tick_mfu", "tick_hbm_roofline", "ssm_step_roofline",
              "moe_experts_roofline"):
        assert read_metric(n + ".ssm_moe_serve", run) is None, n
    tracer.clear()
