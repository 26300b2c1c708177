"""The decoder of Mamba-2 and attention layers with a dense MLP in each,
muP scalars and a tied head (nn/granite_hybrid.py) against its plain
reference (benchmark/references/granite_hybrid_lm.py) at a small size on
the CPU: the full forward, prefill and decode through ``DecodeEngine``
and its paged pool, the fixed state block a slot, the four scalars and
the tied head, the configuration at its published size, the operation
counts and the cell's files and driver."""
import copy
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_granite_hybrid as counts
from benchmark.references import granite_hybrid_lm as ref
from bigdl_tpu.nn import granite_hybrid
from bigdl_tpu.ops import paged_kv
from bigdl_tpu.serving import DecodeEngine, paging

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(vocab_size=64, hidden_size=64, shared_intermediate_size=96,
            layer_types=["mamba", "mamba", "attention", "mamba"],
            num_attention_heads=4, num_key_value_heads=2,
            mamba_n_heads=8, mamba_d_head=16, mamba_n_groups=1,
            mamba_d_state=8, mamba_d_conv=4, mamba_expand=2,
            mamba_chunk_size=8, embedding_multiplier=12.0,
            attention_multiplier=0.0625, residual_multiplier=0.22,
            logits_scaling=8.0, rms_norm_eps=1e-5,
            ssm_state_dtype="float32")
SCALARS = ("embedding_multiplier", "attention_multiplier",
           "residual_multiplier", "logits_scaling")
# float32 program against the float32 reference: what differs is the
# order of the sums (logits of magnitude up to 2 agree to 2e-6 over a
# residual stream of RMS 135); operands rounded to bf16 move them by
# 3.5e-2 and a state held in bf16 by more than 1e-3
# (test_lower_precisions_fail_the_tolerance)
ATOL = 1e-4


def build(seed=0, **over):
    """Weights by the benchmark's rules (configs/granite-4.0-h-micro.json
    ``serve.init``) at this size: a matrix that reads a normed row
    N(0, 1/fan_in), the depthwise kernel N(0, 1/4), a branch's output
    matrix N(0, (60/0.22)^2/fan_in) and the embedding N(0, 1/4), so that
    the logits spread by a unit or two and four layers' branches, not
    the token's own embedding, set the last hidden state (at the rules'
    6/0.22 a position's best token was its own at 83% of positions, and
    greedy decoding repeated one token); norm weights, D and the conv
    bias off their neutral values so that each of them matters; A_log
    and dt_bias as Mamba-2 draws them."""
    cfg = dict(TINY, **over)
    model = granite_hybrid.GraniteHybridTransformer(**cfg)
    var = model.init(jax.random.PRNGKey(seed))
    flat, tree = jax.tree_util.tree_flatten_with_path(var["params"])
    out = []
    for i, (path, leaf) in enumerate(flat):
        key = jax.random.fold_in(jax.random.PRNGKey(seed + 1), i)
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        noise = jax.random.normal(key, leaf.shape)
        if name == "embed/weight":
            out.append(0.5 * noise)
        elif name.endswith("conv_w"):
            out.append(0.5 * noise)
        elif name.endswith(("w_out", "wo", "wd")):
            out.append(noise * (60 / 0.22) / np.sqrt(leaf.shape[-2]))
        elif leaf.ndim >= 2:
            out.append(noise / np.sqrt(leaf.shape[-2]))
        elif name.endswith(("A_log", "dt_bias")):
            out.append(leaf)
        elif name.endswith("conv_b"):
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


# ------------------------------------------------------------ the reference
def test_full_forward_logits_match_the_reference(tiny):
    model, var, cfg = tiny
    ids = ids_of(1, 2, 37)                 # five scan chunks, not whole
    got, _ = model.apply(var["params"], var["state"], ids)
    for row in range(2):
        want = ref.logits_fn(var["params"], ids[row], cfg)
        assert float(jnp.std(want)) > 0.5
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
    bf16 = granite_hybrid.GraniteHybridTransformer(
        **dict(cfg, ssm_state_dtype="bfloat16"))
    cache = bf16.init_cache(1, 48)
    assert cache["layer0"]["ssm"].dtype == jnp.bfloat16
    _, cache = bf16.prefill(var["params"], var["state"], ids[None, :8],
                            cache)
    worst = 0.0
    for j in range(8, 39):
        logits, cache = bf16.decode_step(var["params"], var["state"], cache,
                                         jnp.asarray(ids[j:j + 1]))
        worst = max(worst, float(jnp.abs(logits[0] - want[j]).max()))
    assert worst > 10 * ATOL


@pytest.mark.parametrize("scalar", SCALARS)
def test_each_mu_p_scalar_counts(tiny, scalar):
    """The program with one of the four scalars set to 1 (the weights
    as they are) is far outside the tolerance of the reference that
    keeps it."""
    _, var, cfg = tiny
    ids = ids_of(2, 1, 30)
    want = ref.logits_fn(var["params"], ids[0], cfg)
    off = granite_hybrid.GraniteHybridTransformer(**dict(cfg,
                                                         **{scalar: 1.0}))
    got, _ = off.apply(var["params"], var["state"], ids)
    assert float(jnp.abs(got[0] - want).max()) > 100 * ATOL


def test_the_head_is_the_embedding_transposed(tiny):
    """No ``head`` leaf: the logits are ``RMSNorm(h) E^T /
    logits_scaling``, and moving a row of ``E`` moves that token's
    logit (and its embedding) alone at a fixed hidden state."""
    model, var, cfg = tiny
    params = var["params"]
    assert "head" not in params and set(params) >= {"embed", "ln_f"}
    h = jax.random.normal(jax.random.PRNGKey(5), (3, 64)) * 7.0
    got = model._head(params, h)
    normed = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-5) \
        * params["ln_f"]["weight"]
    np.testing.assert_allclose(got, normed @ params["embed"]["weight"].T / 8,
                               rtol=1e-5, atol=1e-5)
    moved = dict(params, embed={"weight": params["embed"]["weight"].at[
        7].add(1.0)})
    diff = np.asarray(model._head(moved, h) - got)
    assert np.abs(diff[:, 7]).min() > 1e-3
    assert np.abs(np.delete(diff, 7, axis=1)).max() == 0.0


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


def test_the_state_is_a_block_a_slot(tiny):
    """Every Mamba-2 layer declares its state and history as blocks,
    the attention layer its K and V rows."""
    model, _, _ = tiny
    state = model.decode_state()
    assert sorted(state) == ["layer0", "layer1", "layer2", "layer3"]
    assert state["layer2"] == {"k": (2, 16), "v": (2, 16)}
    assert state["layer0"] == {
        "ssm": paged_kv.Block((8, 16, 8), "float32"),
        "conv": paged_kv.Block((3, 128 + 2 * 8))}
    pool = model.init_paged_cache(9, 4, 3, jnp.bfloat16)
    assert pool["layer3"]["ssm"].shape == (3, 8, 16, 8)
    assert pool["layer3"]["ssm"].dtype == jnp.float32
    assert pool["layer2"]["k"].shape == (9, 4, 32)


# ------------------------------------------------------------ paged cache
def paged(model, slots=3, max_len=64, page=4):
    kv = paging.PagedCache(slots, max_len, page,
                           paging.default_num_pages(slots, max_len, page))
    cache = kv.init_cache(model, jnp.float32)
    return kv, cache, kv.build_write()


def test_paged_decode_and_the_state_blocks_held(tiny):
    """Two slots prefilled and written, then the paged tick with the
    third idle: the reference's logits in both; ``state_blocks_held``
    counts the live slots (2), not slots times Mamba-2 layers, and the
    tick's bytes count each live slot's block once in every one of the
    three Mamba-2 layers."""
    model, var, cfg = tiny
    prompts, steps = {0: 11, 2: 6}, 10
    ids = {s: ids_of(30 + s, n + steps) for s, n in prompts.items()}
    want = {s: ref.logits_fn(var["params"], ids[s], cfg) for s in ids}
    kv, cache, write = paged(model)
    idle = {lk: np.asarray(c["ssm"][1]) for lk, c in cache.items()
            if "ssm" in c}
    assert kv.span_args() == {"pages_held": 0, "state_blocks_held": 0}
    for slot, n in prompts.items():
        dense = model.init_cache(1, 64)
        _, dense = model.prefill(var["params"], var["state"],
                                 ids[slot][None, :n], dense)
        assert kv.reserve(slot, n + 1)
        cache = write(cache, *kv.write_extra(slot), dense, 0, slot)
    assert kv.span_args()["state_blocks_held"] == 2
    assert kv.block_bytes == 3 * (8 * 16 * 8 + 3 * 144) * 4
    active = np.isin(np.arange(3), list(prompts))
    for j in range(steps):
        tokens = np.zeros((3,), np.int32)
        for slot, n in prompts.items():
            assert kv.reserve(slot, n + j + 1)
            tokens[slot] = ids[slot][n + j]
        logits, cache, counters = model.decode_step_paged(
            var["params"], var["state"], cache, kv.tick_extra()[0],
            jnp.asarray(tokens), jnp.asarray(active))
        assert counters == {}
        for slot, n in prompts.items():
            np.testing.assert_allclose(logits[slot], want[slot][n + j],
                                       atol=ATOL)
    for lk, block in idle.items():
        np.testing.assert_array_equal(cache[lk]["ssm"][1], block)
    blocks = kv.span_args()["state_blocks_held"]
    assert blocks == 2
    one = counts.tick_cost(cfg, 2, 0, 0)
    two = counts.tick_cost(cfg, 2, 0, blocks)
    step = counts.ssm_step_cost(cfg, 1)
    conv = counts.conv_cost(cfg, 1)
    assert two["bytes"] - one["bytes"] == 3 * 2 * (step["bytes"]
                                                   + conv["bytes"])
    for slot in prompts:
        kv.release(slot)
    assert kv.span_args() == {"pages_held": 0, "state_blocks_held": 0}


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
            assert float(jnp.abs(cache["layer3"]["ssm"]).max()) > 0
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


@pytest.mark.parametrize("prompt_len,steps", [(5, 24), (37, 20)])
def test_engine_logits_are_the_references(tiny, engine, prompt_len, steps):
    """Bucketed prefill (<= 8) and chunked prefill (37: chunks of 16,
    the state carried from chunk to chunk), then the tick: every served
    token is the reference's best at its position, and the chunk
    program's logits at each chunk's last row are the reference's."""
    model, var, cfg = tiny
    assert engine.declared_programs() == engine.recompiles == 6
    prompt = ids_of(10 + prompt_len, prompt_len)
    got = engine.generate(prompt, steps, timeout=300)
    gaps = ref.served_gaps(var["params"], prompt, got, cfg, pad_to=8)
    assert gaps["gaps"].max() == 0.0
    assert len(set(got.tolist())) > 3       # no token repeated throughout
    assert engine.recompiles == 6
    assert engine._kv.span_args()["state_blocks_held"] == 0
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


def test_engine_tick_spans_count_the_live_slots(tiny, engine):
    """Three requests side by side: the tick's ``state_blocks_held`` is
    the slots holding a block (at most 3), and the traced ticks the
    readers read carry it."""
    from bigdl_tpu.telemetry import get_tracer

    _, var, cfg = tiny
    prompts = [ids_of(40 + i, n) for i, n in enumerate((6, 19, 7))]
    tracer = get_tracer()
    tracer.clear()
    tracer.enable()
    try:
        futs = [engine.submit(p, 10, keep_blocks=True) for p in prompts]
        got = [f.result(300) for f in futs]
    finally:
        tracer.disable()
    for p, g, f in zip(prompts, got, futs):
        assert ref.served_gaps(var["params"], p, g, cfg,
                               pad_to=8)["gaps"].max() == 0.0
        want = ref.final_states(var["params"], np.concatenate([p, g[:-1]]),
                                cfg, pad_to=32)
        assert sorted(want) == sorted(f.blocks) == ["layer0", "layer1",
                                                   "layer3"]
        for lk, state in want.items():
            np.testing.assert_allclose(
                np.asarray(f.blocks[lk]["ssm"]).reshape(state.shape),
                state, atol=ATOL)
    ticks = [s.args for s in tracer.spans()
             if s.name == "loop/tick_dispatch"]
    assert ticks and max(a["state_blocks_held"] for a in ticks) >= 2
    assert all(a["state_blocks_held"] <= 3 for a in ticks)
    traced = counts.traced_ticks({"traffic": {"page_size": 4}})
    assert traced and all(t["blocks"] <= 3 for t in traced)
    tracer.clear()


# ------------------------------------------------------------------- counts
def test_operations_and_bytes_against_a_hand_count():
    cfg = TINY
    d, di, h, p, n, conv, width = 64, 128, 8, 16, 8, 144, 96
    mamba = d * (di + conv + h) + di * d + 5 * conv + 3 * h + di
    attn = 2 * d * 4 * 16 + 2 * d * 2 * 16
    mlp = 3 * d * width
    total = d * 64 + 9 * d + 3 * mamba + attn + 4 * mlp
    assert counts.parameter_count(cfg) == total
    model = granite_hybrid.GraniteHybridTransformer(**cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    assert total == sum(int(np.prod(x.shape))
                        for x in jax.tree_util.tree_leaves(shapes["params"]))
    step = counts.ssm_step_cost(cfg, 3)
    assert step == {"flops": 3 * 4 * h * p * n,
                    "bytes": 3 * 4 * (2 * h * p * n + 2 * di + 2 * n + h)}
    assert counts.conv_cost(cfg, 3)["bytes"] == 3 * 2 * 3 * conv * 2
    assert counts.mlp_cost(cfg, 5) == {
        "flops": 5 * 2 * 4 * mlp, "bytes": 2 * (4 * mlp + 5 * 4 * 2 * d)}
    # 3 rows, 40 K/V rows held, 3 blocks
    got = counts.tick_cost(cfg, 3, 40, 3)
    assert got["flops"] == 3 * 2 * total + 40 * 4 * 2 * 2 * 16 \
        + 3 * step["flops"]
    assert got["bytes"] == 2 * (total + 3 * d) \
        + 2 * (40 * 2 * 2 * 16 + 3 * 2 * 4 * 16) \
        + 3 * (step["bytes"] + 3 * 2 * 3 * conv * 2)
    scan = counts.ssd_cost(cfg, 13)           # two chunks of 8
    assert scan["flops"] == 2 * 16 * (8 * n + 8 * h * p + 2 * h * p * n)


# ---------------------------------------------------------------- the files
def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


CONFIG = ("configs", "granite-4.0-h-micro.json")
CELL = "granite-decode-chat64"


def test_the_configuration_holds_every_published_key_and_cuts_nothing():
    cell = load(*CONFIG)
    assert cell["source"] == ("https://huggingface.co/ibm-granite/"
                              "granite-4.0-h-micro/blob/main/config.json")
    assert cell["reduced"] == []
    pub = cell["published"]
    assert pub["model_type"] == "granitemoehybrid"
    for key, value in pub.items():
        assert cell[key] == value, key
    assert pub["num_local_experts"] == 0 and pub["tie_word_embeddings"]
    assert pub["position_embedding_type"] == "nope"
    kinds = pub["layer_types"]
    assert len(kinds) == pub["num_hidden_layers"] == 40
    assert [i for i, k in enumerate(kinds) if k == "attention"] \
        == [5, 15, 25, 35]
    m = cell["model"]
    assert m["layer_types"] == kinds
    assert m["shared_intermediate_size"] == pub["shared_intermediate_size"]
    for key in ("vocab_size", "hidden_size", "num_attention_heads",
                "num_key_value_heads", "mamba_n_heads", "mamba_d_head",
                "mamba_n_groups", "mamba_d_state", "mamba_d_conv",
                "mamba_expand", "mamba_chunk_size", "embedding_multiplier",
                "attention_multiplier", "residual_multiplier",
                "logits_scaling", "rms_norm_eps"):
        assert m[key] == pub[key], key
    assert {"ssm_state_float32", "weights", "max_len", "conv_orientation",
            "time_step"} <= set(cell["assumed"])
    assert cell["deployment"]["chips_sharing_a_layer"] == 1
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "granite-4.0-h-micro")
    assert entry["file"] == "benchmark/" + "/".join(CONFIG)
    assert entry["source"] == cell["source"] and entry["reduced"] == []


def test_the_configuration_builds_the_class_at_3_191_billion():
    from benchmark import weights
    from benchmark.drivers import decode_granite_hybrid as driver

    cell = load(*CONFIG)
    model = driver.build_model(cell)
    assert isinstance(model, granite_hybrid.GraniteHybridTransformer)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(shapes["params"]))
    assert n == counts.parameter_count(cell["model"]) == 3191396096
    assert round(2 * n / 2 ** 30, 2) == 5.94
    layer = shapes["params"]["layer0"]
    assert layer["mamba"]["w_in"].shape == (2048, 4096 + 4352 + 64)
    assert layer["mamba"]["conv_w"].shape == (4, 4352)
    assert layer["mlp"]["wg"].shape == (2048, 8192)
    assert shapes["params"]["layer5"]["attn"]["wk"].shape == (2048, 512)
    # every leaf has an init rule: a matrix reading a normed row gets
    # 1/sqrt(fan_in), a branch's output (6/0.22)/sqrt(fan_in)
    for path, leaf in zip(weights.leaf_paths(shapes),
                          jax.tree_util.tree_leaves(shapes)):
        kind, number = weights._rule_for(path, cell["serve"]["init"])
        if leaf.ndim >= 2 and "embed" not in path:
            if path.endswith("conv_w"):
                assert (kind, number) == ("normal", 0.5)
                continue
            fan_in = leaf.shape[-2]
            out = path.endswith(("mamba/w_out", "attn/wo", "mlp/wd"))
            assert kind == ("normal_centered" if path.endswith("w_out")
                            else "normal"), path
            want = (6 / 0.22 if out else 1.0) * fan_in ** -0.5
            assert number == pytest.approx(want, rel=1e-4), path
        elif "embed" in path:
            assert (kind, number) == ("normal", 0.125)
        elif kind not in driver.hybrid.SPECIAL:
            assert (kind, number) == ("const", 0.0 if path.endswith(
                "conv_b") else 1.0), path


def test_the_cells_files_hold_its_geometry_and_readers():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "granite-4.0-h-micro"
    mix = load("traffic", cell["traffic"] + ".json")
    assert cell["traffic"] == "decode-chat-closed64"
    assert mix["driver"] == "decode_granite_hybrid"
    assert mix["clients"] == mix["slots"] == 64 and mix["strata"] == 32
    assert (mix["max_len"], mix["page_size"], mix["prefill_chunk"],
            mix["prompt_buckets"], mix["prefill_batch_sizes"]) \
        == (4096, 64, 2048, [512, 2048], [1, 4])
    assert mix["prompt_tokens"] == {"median": 384, "sigma": 0.8,
                                    "min": 32, "max": 3072}
    assert mix["output_tokens"] == {"median": 192, "sigma": 0.7,
                                    "min": 16, "max": 1024}
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= mix["max_len"]
    assert (mix["supply_requests_per_s"], mix["lead_in_s"],
            mix["trace_seconds"], mix["check_requests"]) \
        == (24.0, 16.0, 3, 4)
    limits = load("cells", CELL + ".json")["limits"]
    assert set(limits) == {"served_mean_gap", "ssm_state_gap"}
    listed = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])]
    assert sorted(listed) == sorted(
        [n + ".moe_serve" for n in (
            "tick_ms_p50", "device_idle_share", "prefill_device_share")]
        + [n + ".ssm_dense_serve" for n in (
            "tick_hbm_roofline", "ssm_step_roofline",
            "ssd_prefill_roofline", "mlp_roofline")]
        + [n + ".setup" for n in (
            "setup_program_s", "setup_trace_s", "setup_compile_s")])
    for name in listed:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           name + ".py")), name
    e2e = next(m for m in bench["end_to_end"]
               if m["name"] == "decode_tokens_per_s")
    assert CELL in e2e["workloads"]
    # what the chip holds at the cell's size: weights 5.94 GiB, 36
    # layers' state blocks 4.56 GiB, four attention layers' K/V 2.0 GiB
    model = granite_hybrid.GraniteHybridTransformer(**load(*CONFIG)["model"])
    kv = paging.PagedCache(64, 4096, 64,
                           paging.default_num_pages(64, 4096, 64))
    pool = jax.eval_shape(lambda: kv.init_cache(model, jnp.bfloat16))
    assert round(64 * kv.block_bytes / 2 ** 30, 2) == 4.56
    assert round((kv.num_pages - 1) * kv.page_bytes / 2 ** 30, 2) == 2.0
    assert pool["layer5"]["k"].shape == (kv.num_pages, 64, 512)
    assert pool["layer0"]["ssm"].shape == (64, 64, 64, 128)


# --------------------------------------------------------------- benchmark
def tiny_cell():
    """The cell's own files at tiny widths (the published widths stay
    in the files): the driver runs end to end on the CPU in seconds."""
    config = load(*CONFIG)
    config["model"] = dict(TINY)
    config["serve"]["dtype"] = "float32"
    # the rules' stds are the published widths'; at hidden 64 the
    # embedding's 0.125 would give logits of a tenth, so it takes 0.5
    config["serve"]["init"] = [["params/embed/weight", "normal", 0.5]] \
        + config["serve"]["init"][1:]
    mix = load("traffic", "decode-chat-closed64.json")
    mix.update(slots=4, clients=4, strata=4, supply_requests_per_s=800.0,
               max_len=96, page_size=8, prompt_buckets=[8, 16],
               prefill_chunk=16, lead_in_s=0.5,
               prompt_tokens={"median": 12, "sigma": 0.8, "min": 2,
                              "max": 48},
               output_tokens={"median": 16, "sigma": 0.5, "min": 2,
                              "max": 40})
    return copy.deepcopy({
        "name": "tiny-granite", "chips": 1, "config": config,
        "traffic": mix, "limits": {"served_mean_gap": 1e-4,
                                   "ssm_state_gap": 1e-4}})


def test_the_state_check_fails_a_state_held_in_bf16(monkeypatch):
    """The driver's state check on the tiny cell: the Mamba-2 layer in
    float32 reads the stepped recurrence to the order of its sums; the
    same check with the configuration's state in bfloat16 (the planted
    fault) fails the cell's limit."""
    from benchmark.drivers import decode_granite_hybrid as driver

    monkeypatch.setattr(ref, "PAD_TO", (32, 96))
    limit = load("cells", CELL + ".json")["limits"]["ssm_state_gap"]
    cell = tiny_cell()
    good = driver.state_check(cell, 2 ** 31 + 9, 2.0)
    cell["config"]["model"]["ssm_state_dtype"] = "bfloat16"
    bad = driver.state_check(cell, 2 ** 31 + 9, 2.0)
    assert good["ssm_state_gap"] < limit / 30
    assert bad["ssm_state_gap"] > 5 * limit
    assert jax.config.jax_default_matmul_precision is None


def test_benchmark_driver_end_to_end_and_its_readers(monkeypatch):
    """``drivers/decode_granite_hybrid`` on the tiny cell: correct
    against the plain reference, chunked prompts among them; the fp8
    control is not; the new readers read the traced ticks' counters
    and nothing without them."""
    from benchmark import check
    from benchmark.device import CompileCount
    from benchmark.drivers import decode_granite_hybrid as driver
    from benchmark.run import read_metric
    from bigdl_tpu.telemetry import get_tracer

    cell = tiny_cell()
    device = {"platform": "cpu", "kind": "cpu", "count": 1,
              "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
    monkeypatch.setattr(ref, "PAD_TO", (32, 96))     # the cell's: 1024..
    monkeypatch.setattr(ref, "HEAD_ROWS", 8)
    tracer = get_tracer()
    tracer.clear()
    tracer.enable()
    try:
        run = driver.run(
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
    state_limit = load("cells", CELL + ".json")["limits"]["ssm_state_gap"]
    assert run["numbers"]["ssm_state_gap"] < state_limit / 30
    assert run["control_numbers"]["ssm_state_gap"] > 5 * state_limit
    run["trace"] = {"by_module": {"jit_tick": [2e-3, 2],
                                  "jit_chunk": [1e-3, 1]},
                    "busy_s": 4e-3, "window_s": 1.0}
    tick_ops = [["multiply_reduce_fusion", "mixer/ssm", 4e-4],
                ["fusion.7", "mlp", 1e-4],
                ["convolution.2", "mlp", 2e-4],
                ["fusion.1", "-", 6e-4]]
    run["program_ops"] = {
        "jit_tick": {"runs": 2, "ops": tick_ops},
        "jit_chunk": {"runs": 1, "ops": [["fusion.3", "mixer/ssd", 2e-4]]},
        "jit_prefill": {"runs": 2, "ops": [
            ["fusion.4", "prefill/mixer/ssd", 3e-4]]}}
    names = ("tick_hbm_roofline", "ssm_step_roofline",
             "ssd_prefill_roofline", "mlp_roofline")
    got = {n: read_metric(n + ".ssm_dense_serve", run) for n in names}
    assert all(v is not None and v > 0 for v in got.values()), got
    for n in ("prefill_device_share", "tick_ms_p50", "device_idle_share"):
        assert read_metric(n + ".moe_serve", run) is not None, n
    # the MLP's share is its three matrices a layer over the two
    # operations under ``mlp`` and the unscoped one (XLA's weight
    # prefetches), 4.5e-4 s a tick
    tick = counts.mean_tick(run)
    want = counts.mlp_cost(TINY, tick["active"])["bytes"] / 1e11
    assert got["mlp_roofline"] == pytest.approx(100 * want / 4.5e-4)
    # without the scopes' operations nothing to read
    run["program_ops"]["jit_tick"]["ops"] = tick_ops[3:]
    for n in ("ssm_step_roofline", "mlp_roofline"):
        assert read_metric(n + ".ssm_dense_serve", run) is None, n
    # a program without state blocks (the parent's): nothing to read
    for s in tracer.spans():
        if s.name == "loop/tick_dispatch" and s.args:
            s.args.pop("state_blocks_held", None)
    run["program_ops"]["jit_tick"]["ops"] = tick_ops
    for n in ("tick_hbm_roofline", "ssm_step_roofline", "mlp_roofline"):
        assert read_metric(n + ".ssm_dense_serve", run) is None, n
    tracer.clear()
