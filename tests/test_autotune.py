"""Kernel autotuner tests (ISSUE 13): the TunedTable artifact, the
candidate-space staleness contract, the dispatch injection seam, the
flash fit_block edge cases, and the fused-block remat memory win.

All CPU-runnable.  The Mosaic feasibility of the candidates themselves
is the sweep's job (tools/autotune.py, deviceless) — here we test the
plumbing: a table entry must demonstrably change what dispatch traces,
and an entry outside the declared candidate space must demonstrably
NOT (recorded as ``stale``, never silently applied).
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.ops.pallas import report
from bigdl_tpu.ops.pallas import tuning
from bigdl_tpu.ops.pallas.tuning import (TunedTable, candidates,
                                         default_params, entry_key,
                                         parse_key)


@pytest.fixture
def probe_table():
    """Swap in a fresh table for the test, restore the live one after
    (the committed tuned/*.json auto-loads in every process)."""
    prev = tuning.get_tuned_table()
    table = TunedTable(device_kind="test")
    tuning.set_tuned_table(table)
    report.reset()
    yield table
    tuning.set_tuned_table(prev)
    report.reset()


# ---------------------------------------------------------------------------
# table format
# ---------------------------------------------------------------------------
def test_entry_key_roundtrip():
    key = entry_key("fused_matmul", (802816, 64, 64))
    assert key == "fused_matmul/802816x64x64"
    assert parse_key(key) == ("fused_matmul", (802816, 64, 64))
    with pytest.raises(KeyError):
        entry_key("not_a_family", (1, 2))
    for bad in ("fused_matmul", "fused_matmul/", "nope/1x2"):
        with pytest.raises(ValueError):
            parse_key(bad)


def test_table_persist_load_roundtrip(tmp_path):
    t = TunedTable(device_kind="TPU v5 lite")
    t.add("fused_matmul", (256, 128, 128), {"bm": 64},
          source="deviceless", cost={"bytes_accessed": 123},
          ranked=[{"params": {"bm": 64}, "bytes_accessed": 123}])
    t.reject("flash_attention", (1, 2, 1024, 1024, 128),
             {"bq": 1024, "bk": 1024}, "Unsupported implicit dim change")
    path = str(tmp_path / "table.json")
    assert t.persist(path) == path

    back = TunedTable.load(path)
    assert back.device_kind == "TPU v5 lite"
    assert len(back) == 1
    assert back.lookup("fused_matmul", (256, 128, 128)) == {"bm": 64}
    assert back.lookup("fused_matmul", (256, 128, 256)) is None
    rej = back.rejected["flash_attention/1x2x1024x1024x128"]
    assert rej[0]["params"] == {"bq": 1024, "bk": 1024}
    assert "implicit dim" in rej[0]["reason"]


def test_table_load_rejects_bad_schema_and_keys(tmp_path):
    bad_schema = tmp_path / "bad_schema.json"
    bad_schema.write_text(json.dumps({"schema": "v0", "entries": {}}))
    with pytest.raises(ValueError, match="schema"):
        TunedTable.load(str(bad_schema))

    bad_key = tmp_path / "bad_key.json"
    bad_key.write_text(json.dumps({
        "schema": tuning.SCHEMA,
        "entries": {"nonsense": {"params": {"bm": 8}}}}))
    with pytest.raises(ValueError, match="malformed"):
        TunedTable.load(str(bad_key))


# ---------------------------------------------------------------------------
# flash fit_block edge cases (the bk second-minor fix)
# ---------------------------------------------------------------------------
def test_fit_block_edges():
    from bigdl_tpu.ops.pallas.flash_attention import fit_block

    # n <= cap: the whole axis is always a legal block
    assert fit_block(512, 1024) == 512
    assert fit_block(96, 1024) == 96
    # plain power-of-two tiling
    assert fit_block(2048, 1024) == 1024
    assert fit_block(384, 256) == 128
    # q blocks are lane dims: only 128-multiples are legal, so s=1032
    # (no 128-multiple divisor) has NO q block...
    assert fit_block(1032, 1024) is None
    # ...but as a k/v block (second-minor) multiple=8 tiles it at 344
    assert fit_block(1032, 1024, multiple=8) == 344
    # prime-ish lengths never tile
    assert fit_block(1025, 1024) is None
    assert fit_block(1025, 1024, multiple=8) is None


def test_flash_candidates_legal():
    """Every declared flash candidate obeys Mosaic's block rules: bq is
    a 128-multiple (or the whole q axis), bk divides s and is an
    8-multiple (or the whole kv axis)."""
    b, h, t, s, d = 1, 2, 1024, 1032, 128
    cands = candidates("flash_attention", (b, h, t, s, d))
    assert cands, "1032 must be tunable via the multiple=8 bk rule"
    for c in cands:
        assert t % c["bq"] == 0
        assert c["bq"] == t or c["bq"] % 128 == 0
        assert s % c["bk"] == 0
        assert c["bk"] == s or c["bk"] % 8 == 0
    assert {"bq": 1024, "bk": 344} in cands


def test_defaults_inside_candidate_space():
    """Where the hand picker draws from the same geometric series as
    the sweep, its choice must be a member of the declared candidate
    space (so the sweep can mark the incumbent).  Membership only ever
    gates TABLE entries — the dgrad picker's scoped-VMEM halving can
    legitimately land between the series' points (e.g. bm=224 at
    12544x2048x512) and still dispatch as ``default``."""
    shapes = {
        "fused_matmul": (256, 128, 128),
        "fused_matmul_wgrad": (256, 64, 128),
        "int8_matmul": (256, 128, 128),
        "flash_attention": (1, 2, 1024, 1024, 128),
    }
    for kernel, shape in shapes.items():
        d = default_params(kernel, shape)
        if any(v is None for v in d.values()):
            continue  # picker says XLA; nothing to be a member
        assert d in candidates(kernel, shape), (kernel, shape, d)

    # the dgrad off-series default: legal (divides m), just not listed
    d = default_params("fused_matmul_dgrad", (12544, 2048, 512))
    assert d["bm"] is not None and 12544 % d["bm"] == 0


# ---------------------------------------------------------------------------
# the dispatch injection seam
# ---------------------------------------------------------------------------
def test_resolve_table_default_stale(probe_table):
    shape = (256, 128, 128)
    # miss -> hand-picked defaults, recorded as such
    out = tuning.resolve("fused_matmul", shape, {"bm": 256})
    assert out == {"bm": 256}
    assert report.last_params("fused_matmul", shape)["source"] == "default"

    # a valid candidate overrides the default
    probe_table.add("fused_matmul", shape, {"bm": 64})
    assert {"bm": 64} in candidates("fused_matmul", shape)
    out = tuning.resolve("fused_matmul", shape, {"bm": 256})
    assert out == {"bm": 64}
    assert report.last_params("fused_matmul", shape)["source"] == "table"

    # an entry outside the candidate space is STALE: defaults win
    probe_table.add("fused_matmul", shape, {"bm": 100})
    out = tuning.resolve("fused_matmul", shape, {"bm": 256})
    assert out == {"bm": 256}
    assert report.last_params("fused_matmul", shape)["source"] == "stale"


def test_resolve_disabled_by_env(probe_table, monkeypatch):
    shape = (256, 128, 128)
    probe_table.add("fused_matmul", shape, {"bm": 64})
    monkeypatch.setenv("BIGDL_TPU_TUNE", "0")
    out = tuning.resolve("fused_matmul", shape, {"bm": 256})
    assert out == {"bm": 256}
    assert report.last_params("fused_matmul", shape)["source"] == "default"


def test_injected_params_reach_the_lowered_program(probe_table,
                                                  monkeypatch):
    """The acceptance check: a table entry with a distinctive block
    size must be visible in the traced program — the pallas_call grid
    follows bm, so bm=64 on m=256 means a 4-step grid where the
    hand-picked bm=256 gives 1."""
    monkeypatch.setenv("BIGDL_TPU_FORCE_PALLAS", "1")
    from bigdl_tpu.ops.pallas.fused_matmul import (_pick_bm,
                                                   fused_matmul_bn)

    m, k, n = 256, 128, 128
    assert _pick_bm(m, k, n, 4) == 256  # the default this must beat
    probe_table.add("fused_matmul", (m, k, n), {"bm": 64})

    x = jnp.zeros((m, k), jnp.float32)
    w = jnp.zeros((k, n), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda a, b: fused_matmul_bn(a, b)[0])(x, w)

    rec = report.last_params("fused_matmul", (m, k, n))
    assert rec["source"] == "table"
    assert rec["params"] == {"bm": 64}

    from bigdl_tpu.analysis.core import iter_eqns

    grids = [tuple(eqn.params["grid_mapping"].grid)
             for eqn, _ in iter_eqns(jaxpr)
             if eqn.primitive.name == "pallas_call"]
    assert grids, "dispatch did not trace a pallas_call"
    assert (m // 64,) in grids, grids


# ---------------------------------------------------------------------------
# fused-block remat: the HBM-capacity leg
# ---------------------------------------------------------------------------
def _block_chain_step(blocks):
    def loss_fn(params, states, x):
        new_states = []
        for blk, p, s in zip(blocks, params, states):
            x, ns = blk.apply(p, s, x, training=True)
            new_states.append(ns)
        return jnp.sum(x.astype(jnp.float32)), new_states

    def step(params, states, x):
        (loss, new_states), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, states, x)
        new_params = jax.tree_util.tree_map(
            lambda p, g: p - 0.1 * g.astype(p.dtype), params, grads)
        return new_params, new_states, loss

    return step


@pytest.mark.parametrize("remat", ["1", "0"])
def test_fused_block_remat_gate_in_jaxpr(remat, monkeypatch):
    """BIGDL_TPU_FUSED_REMAT gates a remat2 equation into (out of) the
    traced backward of the fused block chain."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.analysis.core import iter_eqns

    monkeypatch.setenv("BIGDL_TPU_FUSED_REMAT", remat)
    blocks = [nn.FusedBottleneck(64, 16, stride=1) for _ in range(2)]
    params = [b.init_params(jax.random.PRNGKey(i))
              for i, b in enumerate(blocks)]
    states = [b.init_state() for b in blocks]
    x = jax.ShapeDtypeStruct((2, 8, 8, 64), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(_block_chain_step(blocks))(params, states, x)
    has_remat = any(eqn.primitive.name == "remat2"
                    for eqn, _ in iter_eqns(jaxpr))
    assert has_remat == (remat == "1")


def test_fused_block_remat_shrinks_temp_bytes(monkeypatch):
    """The point of the gate: XLA's compiled temp-buffer footprint
    (memory_analysis — the HbmLedger estimate path's raw material) must
    not grow when remat is on, and the backward must stop pinning the
    per-block conv residuals (bench.py --fused-ab measures the full
    256-batch envelope; PERF.md §fused-conv)."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.telemetry import costmodel

    def temps(remat_on):
        monkeypatch.setenv("BIGDL_TPU_FUSED_REMAT",
                           "1" if remat_on else "0")
        blocks = [nn.FusedBottleneck(64, 16, stride=1) for _ in range(2)]
        params = [b.init_params(jax.random.PRNGKey(i))
                  for i, b in enumerate(blocks)]
        states = [b.init_state() for b in blocks]
        x = jax.ShapeDtypeStruct((8, 14, 14, 64), jnp.bfloat16)
        lowered = jax.jit(_block_chain_step(blocks)).lower(
            params, states, x)
        cost = costmodel.program_cost("test:remat_ab", lowered=lowered,
                                      compiled=lowered.compile())
        return cost.temp_bytes

    on, off = temps(True), temps(False)
    assert on > 0 and off > 0, "CPU memory_analysis returned no temps"
    assert on <= off, (on, off)


# ---------------------------------------------------------------------------
# the live table is the RUNNING device kind's, never another chip's
# ---------------------------------------------------------------------------
def test_table_path_is_keyed_by_device_kind(monkeypatch):
    monkeypatch.delenv("BIGDL_TPU_TUNED_TABLE", raising=False)
    v5e = tuning.table_path("TPU v5 lite")
    assert v5e == tuning.table_file("TPU v5 lite")
    assert v5e.endswith("tuned/tpu-v5-lite.json")
    assert tuning.table_path("TPU v6 lite") is None  # no table committed
    # the tier runs on the CPU backend: defaults, not the v5e's tiles
    assert tuning.table_path() is None
    monkeypatch.setenv("BIGDL_TPU_TUNED_TABLE", "/some/explicit.json")
    assert tuning.table_path("TPU v5 lite") == "/some/explicit.json"


def test_unreadable_table_is_an_error_at_first_dispatch(tmp_path,
                                                        monkeypatch):
    prev = tuning.get_tuned_table()
    bad = tmp_path / "broken.json"
    bad.write_text("{not json")
    monkeypatch.setenv("BIGDL_TPU_TUNED_TABLE", str(bad))
    monkeypatch.setattr(tuning, "_TABLE_LOADED", False)
    try:
        with pytest.raises(ValueError):
            tuning.resolve("fused_matmul", (256, 128, 128), {"bm": 64})
    finally:
        tuning.set_tuned_table(prev)


def test_report_keeps_the_shape_of_every_non_pallas_route(probe_table):
    from bigdl_tpu.ops.pallas.fused_matmul import fused_matmul_bn

    x = jnp.ones((64, 32), jnp.float32)
    w = jnp.ones((32, 16), jnp.float32)
    fused_matmul_bn(x, w)                      # off-TPU: the XLA route
    fused_matmul_bn(x, w, interpret=True)      # the kernel, interpreted
    assert report.fallbacks() == [("fused_matmul", "xla", (64, 32, 16))]
    assert report.report()["fused_matmul"] == {"pallas": 1, "xla": 1}
