"""Deviceless Mosaic gate, in-process: the main path's Pallas kernels at
their real widths must compile for the v5e through the installed
libtpu's XLA:TPU compiler — no chip.  This catches what interpret-mode
tests accept (scoped-VMEM overflows, unaligned slices, a kernel that
cannot be partitioned, a silent XLA route) at no chip time.

The topology is described inside a module-scoped fixture and nowhere
else: only one process may hold libtpu, so nothing here may load it
while a module is imported (every xdist worker imports every file).
The full inventory stays with ``tools/tpu_aot_check.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tools import kernel_shapes as KS
from tools.lm_bench import LM_DEFAULTS

S = jax.ShapeDtypeStruct
BF16, F32 = jnp.bfloat16, jnp.float32

LM_QKV = KS.FLASH[1]  # the LM cell's attention: 8 x 12 x 2048 x 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Replicated sharding on one described chip, with the process set
    up as tools/tpu_aot_check.py sets itself up: kernels routed to
    Pallas although the backend is the CPU, the target's tuned table
    installed, the persistent compile cache off (a deviceless entry can
    be written but never read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    from bigdl_tpu.ops.pallas import tuning

    mp = pytest.MonkeyPatch()
    mp.setenv("BIGDL_TPU_FORCE_PALLAS", "1")
    for knob in ("BIGDL_TPU_FUSED_DISABLE", "BIGDL_TPU_FUSED_CONV3_DISABLE",
                 "BIGDL_TPU_INT8_PALLAS_DISABLE"):
        mp.delenv(knob, raising=False)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    table_was = tuning.get_tuned_table()
    path = tuning.table_path(topo.devices[0].device_kind)
    tuning.set_tuned_table(tuning.TunedTable.load(path) if path else None)
    yield NamedSharding(Mesh(np.array(topo.devices[:1]), ("d",)), P())
    tuning.set_tuned_table(table_was)
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()
    mp.undo()


def _compiled(fn, sharding, *structs, donate=()):
    """TPU-compile ``fn``."""
    return jax.jit(fn, in_shardings=sharding, out_shardings=sharding,
                   donate_argnums=donate).lower(*structs).compile()


def _on(sharding):
    """The engine's own builders (``serving/decode_programs.py``) placed
    on ``sharding``: what they donate is part of what is compiled."""
    return dict(in_shardings=sharding, out_shardings=sharding)


def _compile(fn, sharding, *structs, donate=()):
    """TPU-compile ``fn`` and return the compiled program's text."""
    return _compiled(fn, sharding, *structs, donate=donate).as_text()


def _loss(fn):
    """Scalarize ``fn``'s outputs so its backward compiles too."""
    return lambda *a: sum(jnp.sum(o.astype(F32))
                          for o in jax.tree_util.tree_leaves(fn(*a)))


# name -> (family, inventory shape, differentiate?): the ResNet-50
# batch-256 stage-1 shapes, the LM cell's attention, the FFN int8 shape
B, H, T, D = LM_QKV
KERNEL_CASES = {
    "fused_matmul_fwd": ("fused_matmul", KS.MATMUL[0], False),
    "fused_matmul_bwd": ("fused_matmul", KS.MATMUL[0], True),
    "conv3_fwd": ("fused_conv3x3", (KS.BATCH,) + KS.CONV3[0], False),
    "flash_lm_fwd": ("flash_attention", (B, H, T, T, D), False),
    "flash_lm_bwd": ("flash_attention", (B, H, T, T, D), True),
    "int8_matmul": ("int8_matmul", KS.INT8[0], False),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_compiles_for_v5e_at_real_width(one_chip, case):
    from bigdl_tpu.ops.pallas import report
    from tools.autotune import _candidate_fn

    family, shape, backward = KERNEL_CASES[case]
    fn, structs, _ = _candidate_fn(family, shape)
    if backward:
        fn = jax.grad(_loss(fn), argnums=tuple(range(len(structs))))
    before = report.report().get(family, {})
    n_fallbacks = len(report.fallbacks())
    text = _compile(fn, one_chip, *structs)
    assert "tpu_custom_call" in text
    after = report.report()[family]
    assert after["pallas"] > before.get("pallas", 0)
    assert report.fallbacks()[n_fallbacks:] == []


def test_flash_bwd_kernel_compiles_at_lm_shape(one_chip):
    """The attention gradient at the LM cell's shape is the Pallas
    backward kernel, not the blockwise XLA scan."""
    from bigdl_tpu.ops.pallas import report
    from bigdl_tpu.ops.pallas.flash_attention import flash_attention

    before = report.report().get("flash_attention_bwd", {}).get("pallas", 0)
    n_fallbacks = len(report.fallbacks())
    text = _compile(
        jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True).astype(F32)), argnums=(0, 1, 2)),
        one_chip, *[S(LM_QKV, BF16)] * 3)
    assert "flash_bwd" in text and " while(" not in text
    assert report.report()["flash_attention_bwd"]["pallas"] == before + 1
    assert report.fallbacks()[n_fallbacks:] == []


def test_flash_partitions_over_dp_tp_mesh(topo, one_chip):
    """The LM attention under a data=2 x model=2 mesh of described
    chips: the kernel wraps itself in a shard_map over both axes
    (ops/pallas/partition.py) and the partitioned program still holds
    the Mosaic call."""
    from bigdl_tpu.ops.pallas.partition import kernel_mesh_scope
    from bigdl_tpu.parallel.mesh import MeshConfig, make_mesh
    from tools.autotune import _candidate_fn

    flash, _, _ = _candidate_fn("flash_attention", (B, H, T, T, D))
    mesh = make_mesh(MeshConfig(data=2, model=2), topo.devices)
    qkv = NamedSharding(mesh, P("data", "model"))

    def attn(q):
        with kernel_mesh_scope(mesh):
            return flash(q)

    text = _compile(attn, qkv, S(LM_QKV, BF16))
    assert "tpu_custom_call" in text


def test_flash_bwd_partitions_over_dp_tp_mesh(topo, one_chip):
    """The gradient under the same mesh: the backward kernel runs inside
    the kernel's shard_map on each chip's batch and heads."""
    from bigdl_tpu.ops.pallas.flash_attention import flash_attention
    from bigdl_tpu.ops.pallas.partition import kernel_mesh_scope
    from bigdl_tpu.parallel.mesh import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(data=2, model=2), topo.devices)
    qkv = NamedSharding(mesh, P("data", "model"))

    def loss(q, k, v):
        with kernel_mesh_scope(mesh):
            return jnp.sum(flash_attention(q, k, v, causal=True).astype(F32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), qkv,
                    *[S(LM_QKV, BF16)] * 3)
    assert "flash_bwd" in text


# the decode cell's geometry (benchmark/traffic/decode-steady.json):
# 32 slots x 2048 tokens in pages of 16, the worst-case pool
CELL_SLOTS, CELL_MAX_LEN, CELL_PAGE = 32, 2048, 16
CELL_VOCAB = 50272  # benchmark/configs/opt-125m.json: the tick's logits


def _lm_layer_and_pool(kv_dtype=None):
    """The LM at its cell widths, depth cut to one layer (a layer's
    program does not depend on how many follow), and its paged pool at
    the decode cell's geometry, as shapes."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.serving.paging import default_num_pages

    d = LM_DEFAULTS
    model = nn.Transformer(
        vocab_size=CELL_VOCAB, hidden_size=d["hiddenSize"],
        num_heads=d["numHeads"], filter_size=d["filterSize"],
        num_layers=1, dropout=0.0, causal=True)
    pages = default_num_pages(CELL_SLOTS, CELL_MAX_LEN, CELL_PAGE)
    assert pages == 4097
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        pages, CELL_PAGE, CELL_SLOTS, F32, kv_dtype=kv_dtype))
    return model, cache


def _pool_in_place(text, cache):
    """The compiled program reaches the donated pool where it lies: no
    ``copy`` has a pool-shaped operand or result, and every pool leaf
    is an aliased output."""
    import re

    leaves = jax.tree_util.tree_leaves(cache)
    shapes = {"[" + ",".join(map(str, leaf.shape)) + "]"
              for leaf in leaves if leaf.ndim > 1}
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= \S+ copy(-start)?\(", line)
              and any(shape in line for shape in shapes)]
    assert copies == []
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    assert aliased and aliased.group(1).count("alias") == len(leaves)


def _sampling_is_gated(compiled, vocab, temp_mib):
    """The compiled tick holds one ``conditional``; every sort over the
    vocabulary lies in a computation only its branches reach (a routed
    layer's own sorts are narrower and stay where they are), so a tick
    whose rows are all greedy runs none.  Temporaries stay under
    ``temp_mib``: what the ungated epilogue compiled to at the same
    shape (PR 30) and, where the conditional's operand moved them from
    fast memory to HBM, the logits."""
    import re

    text = compiled.as_text()
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name and line.startswith("}"):
            name = None
        elif name:
            bodies[name].append(line)
    conds = [line for body in bodies.values() for line in body
             if re.search(r"\sconditional\(", line)]
    assert len(conds) == 1
    branches = re.search(r"branch_computations=\{([^}]*)\}", conds[0])
    reach, todo = set(), re.findall(r"[\w.\-]+", branches.group(1))
    while todo:
        name = todo.pop()
        if name in bodies and name not in reach:
            reach.add(name)
            todo += re.findall(r"%([\w.\-]+)", " ".join(bodies[name]))
    wide = re.compile(r"\[%d,%d\]\S* sort\(" % (CELL_SLOTS, vocab))
    sorts = [name for name, body in bodies.items() for line in body
             if wide.search(line)]
    assert sorts and set(sorts) <= reach, sorts
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= temp_mib * 2 ** 20, temp


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_paged_decode_tick_compiles_at_lm_width(one_chip, kv_dtype):
    """The paged decode tick at the decode cell's geometry: the append
    scatters into the donated pool in place and, on the float pool,
    attention is the ``paged_attn`` kernel reading it in place (the
    int8 pool gathers).  The sampling work over ``f32[32,50272]`` lies
    inside the tick's one conditional."""
    from bigdl_tpu.ops.pallas import report
    from bigdl_tpu.serving.decode_programs import build_paged_tick

    model, cache = _lm_layer_and_pool(kv_dtype)
    var = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    slots = CELL_SLOTS
    before = report.report().get("paged_attention", {}).get("pallas", 0)
    compiled = build_paged_tick(model, **_on(one_chip)).lower(
        var["params"], var["state"],
        cache, S((slots, CELL_MAX_LEN // CELL_PAGE), jnp.int32),
        S((slots,), jnp.int32), S((slots,), jnp.bool_),
        S((slots, 2), jnp.uint32), S((slots,), F32),
        S((slots,), jnp.int32), S((slots,), F32)).compile()
    text = compiled.as_text()
    _pool_in_place(text, cache)
    if kv_dtype is None:  # the cell's pool; ungated 13.67 MiB, now 13.54
        _sampling_is_gated(compiled, CELL_VOCAB, 13.67)
    took = report.report().get("paged_attention", {}).get("pallas", 0)
    assert took == before + (kv_dtype is None)
    assert ("tpu_custom_call" in text) == (kv_dtype is None)


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
@pytest.mark.parametrize("bucket", [64, 1024])
def test_paged_slot_write_is_in_place_at_lm_width(one_chip, bucket,
                                                  kv_dtype):
    """The slot write (one prefill row into a slot's pages) at the
    decode cell's geometry, for the smallest and largest bucket."""
    from bigdl_tpu.serving.decode_programs import build_paged_write_slot

    model, cache = _lm_layer_and_pool(kv_dtype)
    batch = jax.eval_shape(lambda: model.init_cache(4, bucket, F32))
    text = build_paged_write_slot(**_on(one_chip)).lower(
        cache, S((CELL_MAX_LEN // CELL_PAGE,), jnp.int32), batch,
        S((), jnp.int32), S((), jnp.int32)).compile().as_text()
    _pool_in_place(text, cache)


def test_paged_attn_kernel_compiles_at_cell_shape(one_chip):
    """``paged_attn`` alone at the inventory shape (32 slots, 12 heads
    of 64, pages of 16, 128 pages a slot)."""
    from bigdl_tpu.ops.pallas.paged_attention import paged_attn

    slots, heads, dim, page, per_slot = KS.PAGED_ATTN[0]
    pool = S((slots * per_slot + 1, page, heads * dim), F32)
    text = _compile(
        lambda q, k, v, table, kv_len: paged_attn(
            q, k, v, table, kv_len, num_heads=heads),
        one_chip, S((slots, 1, heads * dim), F32), pool, pool,
        S((slots, per_slot), jnp.int32), S((slots,), jnp.int32))
    assert "tpu_custom_call" in text


def test_latent_moe_tick_compiles_at_published_widths(one_chip):
    """The paged tick of the latent-attention decoder with routed
    experts at the published widths (one routed layer; bf16; the
    cell's 32 slots x 8192 in pages of 16): the latent pool is donated
    and aliased with no whole-pool copy, attention is the
    ``latent_paged_attn`` kernel reading it in place, the expert
    products are the grouped-matmul kernel."""
    import json
    import os

    from bigdl_tpu.nn.latent import LatentMoETransformer
    from bigdl_tpu.ops.pallas import report
    from bigdl_tpu.serving.decode_programs import build_paged_tick
    from bigdl_tpu.serving.paging import default_num_pages

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "gigachat3.1-702b-ep16share.json")) as f:
        cfg = json.load(f)["model"]
    model = LatentMoETransformer(**dict(cfg, num_hidden_layers=1,
                                        first_k_dense_replace=0))
    slots, max_len, page = 32, 8192, 16
    pages = default_num_pages(slots, max_len, page)
    var = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), BF16))
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        pages, page, slots, BF16))
    assert cache["layer0"]["latent"].shape == (pages, page, 640)
    before = report.report().get("latent_paged_attention", {}).get(
        "pallas", 0)
    compiled = build_paged_tick(model, **_on(one_chip)).lower(
        var["params"], var["state"],
        cache, S((slots, max_len // page), jnp.int32),
        S((slots,), jnp.int32), S((slots,), jnp.bool_),
        S((slots, 2), jnp.uint32), S((slots,), F32),
        S((slots,), jnp.int32), S((slots,), F32)).compile()
    text = compiled.as_text()
    _pool_in_place(text, cache)
    # ungated 4.89 MiB + 2.03 of logits (32 x 16032 f32, padded)
    _sampling_is_gated(compiled, cfg["vocab_size"], 7.0)
    assert report.report()["latent_paged_attention"]["pallas"] == before + 1
    assert "latent_paged_attn" in text and "ragged-dot" in text


def test_latent_serving_kernels_compile_at_cell_shapes(one_chip):
    """``latent_paged_attn`` and ``flash_prefix`` alone at their
    inventory shapes (the latent cell's tick and chunk)."""
    from bigdl_tpu.ops.pallas.flash_attention import (
        prefix_blocks, prefix_flash_attention)
    from bigdl_tpu.ops.pallas.latent_attention import latent_paged_attn

    slots, heads, row, value, page, per_slot = KS.LATENT_PAGED_ATTN[0]
    text = _compile(
        lambda q, pool, table, kv_len: latent_paged_attn(
            q, pool, table, kv_len, value_width=value, sm_scale=0.1),
        one_chip, S((slots, heads, row), BF16),
        S((slots * per_slot + 1, page, row), BF16),
        S((slots, per_slot), jnp.int32), S((slots,), jnp.int32))
    assert "latent_paged_attn" in text
    b, h, t, s, d = KS.FLASH_PREFIX[0]
    blocks = prefix_blocks(t, s)
    assert blocks == (512, 1024)
    text = _compile(
        lambda q, k, v, off: prefix_flash_attention(
            q, k, v, off, sm_scale=0.1, blocks=blocks),
        one_chip, S((b, h, t, d), BF16), S((b, h, s, d), BF16),
        S((b, h, s, d), BF16), S((b,), jnp.int32))
    assert "flash_prefix" in text


# the window-and-full cell's geometry (benchmark/traffic/
# decode-mixedlen-closed32.json): 32 slots x 32768 in pages of 64
SWA_SLOTS, SWA_MAX_LEN, SWA_PAGE, SWA_WINDOW = 32, 32768, 64, 2048
SWA_HEADS, SWA_KV_HEADS, SWA_DIM = 32, 4, 128


def test_window_serving_kernels_compile_at_cell_shapes(one_chip):
    """``paged_attn`` with grouped heads and a first row over a bf16
    pool, and the banded kernel as the chunk (``flash_prefix``: a band,
    no band) and the bucketed prefill (``flash_fwd``) run it, at the
    window-and-full cell's shapes."""
    from bigdl_tpu.ops.pallas.flash_attention import (
        band_blocks, banded_flash_attention, flash_attention)
    from bigdl_tpu.ops.pallas.paged_attention import paged_attn

    per_slot = SWA_MAX_LEN // SWA_PAGE
    pool = S((SWA_SLOTS * 33 + 1, SWA_PAGE, SWA_KV_HEADS * SWA_DIM), BF16)
    text = _compile(
        lambda q, k, v, table, kv_len, first: paged_attn(
            q, k, v, table, kv_len, first, num_heads=SWA_HEADS,
            kv_heads=SWA_KV_HEADS),
        one_chip, S((SWA_SLOTS, SWA_HEADS, SWA_DIM), BF16), pool, pool,
        S((SWA_SLOTS, per_slot), jnp.int32), S((SWA_SLOTS,), jnp.int32),
        S((SWA_SLOTS,), jnp.int32))
    assert "paged_attn" in text
    t = 2048
    blocks = band_blocks(t, SWA_MAX_LEN, SWA_HEADS // SWA_KV_HEADS)
    assert blocks == (128, 512)
    q = S((1, SWA_HEADS, t, SWA_DIM), BF16)
    kv = S((1, SWA_KV_HEADS, SWA_MAX_LEN, SWA_DIM), BF16)
    for window in (SWA_WINDOW, None):
        text = _compile(
            lambda q, k, v, off: banded_flash_attention(
                q, k, v, off, sm_scale=0.1, window=window, blocks=blocks),
            one_chip, q, kv, kv, S((1,), jnp.int32))
        assert "flash_prefix" in text
    fresh = S((1, SWA_KV_HEADS, t, SWA_DIM), BF16)
    text = _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        one_chip, q, fresh, fresh)
    assert "flash_fwd" in text


@pytest.mark.parametrize("shape", KS.GROUPED_MATMUL,
                         ids=lambda s: "x".join(map(str, s)))
def test_grouped_matmul_compiles_at_chunk_shapes(one_chip, shape):
    """The routed experts' long-buffer kernel at both routed cells'
    chunk shapes, at the tile the rule picks there (whole K: the weight
    block's double buffer has to fit the scoped VMEM it asks for)."""
    from bigdl_tpu.ops.pallas import grouped_matmul as gm

    m, k, n, g, rows = shape
    tiling = gm.routes((m, k), (g, k, n), BF16, rows)
    assert tiling
    text = _compile(
        lambda x, w, c: gm.grouped_matmul(x, w, c, tiling=tiling),
        one_chip, S((m, k), BF16), S((g, k, n), BF16), S((g,), jnp.int32))
    assert "grouped_matmul" in text


def test_window_moe_tick_compiles_at_published_widths(one_chip):
    """The paged tick of the decoder with window and full layers at the
    published widths (one window and one full layer, both routed over
    all 128 experts; bf16; the cell's 32 slots x 32768 in pages of 64):
    both extents' pools are donated and aliased with no whole-pool
    copy, attention is ``paged_attn`` reading them in place, the expert
    products are the grouped-matmul kernel."""
    import json
    import os

    from bigdl_tpu.nn.window_moe import WindowMoETransformer
    from bigdl_tpu.ops.pallas import report
    from bigdl_tpu.serving import paging
    from bigdl_tpu.serving.decode_programs import build_paged_tick

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "trinity-mini-5of32.json")) as f:
        cfg = json.load(f)["model"]
    model = WindowMoETransformer(**dict(
        cfg, num_hidden_layers=2, num_dense_layers=0,
        layer_types=["sliding_attention", "full_attention"]))
    kv = paging.PagedCache(
        SWA_SLOTS, SWA_MAX_LEN, SWA_PAGE,
        paging.default_num_pages(SWA_SLOTS, SWA_MAX_LEN, SWA_PAGE))
    var = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), BF16))
    cache = jax.eval_shape(lambda: kv.init_cache(model, BF16))
    assert cache["layer0"]["k"].shape == (SWA_SLOTS * 33 + 1, SWA_PAGE, 512)
    assert cache["layer1"]["k"].shape == (SWA_SLOTS * 512 + 1, SWA_PAGE,
                                          512)
    before = report.report().get("paged_attention", {}).get("pallas", 0)
    compiled = build_paged_tick(model, **_on(one_chip)).lower(
        var["params"], var["state"], cache,
        S((2, SWA_SLOTS, SWA_MAX_LEN // SWA_PAGE), jnp.int32),
        S((SWA_SLOTS,), jnp.int32), S((SWA_SLOTS,), jnp.bool_),
        S((SWA_SLOTS, 2), jnp.uint32), S((SWA_SLOTS,), F32),
        S((SWA_SLOTS,), jnp.int32), S((SWA_SLOTS,), F32)).compile()
    text = compiled.as_text()
    _pool_in_place(text, cache)
    assert report.report()["paged_attention"]["pallas"] == before + 2
    assert "paged_attn" in text and "ragged-dot" in text


def test_hybrid_ssm_tick_and_chunk_compile_at_published_widths(one_chip):
    """The Mamba-2 hybrid's paged tick and prompt chunk at the published
    widths (one Mamba-2, one routed and the attention layer; bf16; the
    cell's 128 slots x 8192 in pages of 64): every pool leaf, the state
    blocks among them, donated and aliased with no whole-pool copy; a
    layer's state step is one fusion that reads the f32 block once and
    writes it once beside ``y``; attention is ``paged_attn``; the
    chunk's expert products are the grouped-matmul kernel."""
    import json
    import os
    import re

    from bigdl_tpu.nn.hybrid_ssm import HybridSSMTransformer
    from bigdl_tpu.ops.pallas import report
    from bigdl_tpu.serving import paging
    from bigdl_tpu.serving.decode_programs import (build_paged_tick,
                                                   build_prefill_chunk)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "nemotron3-super-11of88-ep4share.json")) as f:
        cfg = json.load(f)["model"]
    model = HybridSSMTransformer(**dict(cfg, hybrid_override_pattern="ME*"))
    slots, max_len, page, chunk = KS.HYBRID_DECODE
    kv = paging.PagedCache(slots, max_len, page,
                           paging.default_num_pages(slots, max_len, page))
    var = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), BF16))
    cache = jax.eval_shape(lambda: kv.init_cache(model, BF16))
    state = cache["layer0"]["ssm"]
    assert state.shape == (slots, 128, 64, 128) and state.dtype == F32
    before = report.report().get("paged_attention", {}).get("pallas", 0)
    compiled = build_paged_tick(model, **_on(one_chip)).lower(
        var["params"], var["state"], cache,
        S((slots, max_len // page), jnp.int32),
        S((slots,), jnp.int32), S((slots,), jnp.bool_),
        S((slots, 2), jnp.uint32), S((slots,), F32),
        S((slots,), jnp.int32), S((slots,), F32)).compile()
    text = compiled.as_text()
    _pool_in_place(text, cache)
    assert report.report()["paged_attention"]["pallas"] == before + 1
    assert "paged_attn" in text and "ragged-dot" in text
    block = "f32[%d,128,64,128]" % slots
    steps = [line for line in text.splitlines()
             if re.search(r"= \(.*%s.*\) fusion\(" % re.escape(block), line)
             and "mixer/ssm" in line]
    assert len(steps) == 1, steps
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 28
    staging = jax.eval_shape(lambda: model.init_cache(1, max_len, BF16))
    chunked = build_prefill_chunk(model, **_on(one_chip)).lower(
        var["params"], var["state"], staging, S((1, chunk), jnp.int32),
        S((1,), jnp.int32)).compile()
    assert "grouped_matmul" in chunked.as_text()
    assert chunked.memory_analysis().temp_size_in_bytes < 2 ** 30
