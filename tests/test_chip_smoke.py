"""chip_smoke.py on the CPU tier: the same phase code at tiny widths.

What the chip run proves (real widths, Mosaic, HBM) cannot be proven
here; what can is that every phase's control flow, checks and entry
points work — and that the script refuses to pass without a TPU: the
device phase fails, the kernel phase fails when no family took its
Pallas route, and the whole script exits non-zero with no result line.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke as cs

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = cs.Sizes(
    conv_argv=("-b", "8", "--syntheticSize", "32", "--dataset", "cifar10",
               "--depth", "8", "--classNum", "10"),
    conv_iters=5,
    lm_argv=("-b", "8", "--seqLen", "16", "--vocabSize", "64",
             "--hiddenSize", "32", "--numHeads", "4", "--filterSize", "64",
             "--numLayers", "2", "--dropout", "0.0", "--learningRate",
             "3e-4", "--syntheticSize", "4096"),
    lm_iters=4, multichip_iters=3,
    serve_model=(("vocab_size", 64), ("hidden_size", 32), ("num_heads", 4),
                 ("filter_size", 64), ("num_layers", 2)),
    serve_slots=4, serve_max_len=48, serve_page=8,
    serve_prompt_buckets=(8, 16), serve_prefill_batches=(1, 2),
    serve_requests=((5, 6), (12, 8), (16, 4), (3, 10), (9, 5)),
    k_matmul=(256, 128, 128), k_conv3=(4, 8, 8, 128, 128),
    k_flash=(2, 2, 128, 64), k_int8=(64, 128, 128))


def test_device_phase_refuses_the_cpu():
    with pytest.raises(cs.SmokeFailure, match="not a TPU"):
        cs.phase_device(1)


@pytest.mark.parametrize("phase,iters", [
    (cs.phase_train_conv, TINY.conv_iters),
    (cs.phase_train_lm, TINY.lm_iters),
])
def test_train_phase_steps_and_reports_finite_losses(phase, iters):
    out = phase(TINY)
    assert len(out["losses"]) == iters
    assert np.all(np.isfinite(out["losses"]))
    assert out["compile_s"] > 0 and out["steps_s"] > 0


def test_serve_phase_finishes_requests_with_flat_recompiles():
    out = cs.phase_serve(TINY)
    # tick + (2 buckets x 2 batches) prefills + 2 writes
    assert out["programs"] == 7
    assert out["oracle_exact"] == out["oracle_tokens"] == 6


def test_kernel_phase_fails_when_no_family_took_pallas():
    """Off the TPU every family routes to XLA: the numerics agree and
    the route check — the enforcement against hidden fallbacks — must
    fail the phase."""
    with pytest.raises(cs.SmokeFailure, match="no Pallas route"):
        cs.phase_kernels(TINY)


def test_inventory_shapes_cover_the_smoke_kernel_shapes():
    """The real-width kernel shapes the smoke runs are inventory shapes,
    so an XLA route at any of them fails the route check."""
    real = cs.Sizes()
    inv = cs._inventory_shapes()
    assert ("fused_matmul", real.k_matmul) in inv
    assert ("fused_conv3x3", real.k_conv3) in inv
    assert ("fused_conv3x3_dgrad", real.k_conv3) in inv
    b, h, t, d = real.k_flash
    assert ("flash_attention", (b, h, t, t, d)) in inv
    assert ("int8_matmul", real.k_int8) in inv


def test_multichip_phase_matches_one_device_and_shards_state():
    out = cs.phase_multichip(TINY)
    assert out["loss_rel_diff"] <= cs.BF16_TOL
    assert len(out["dp"]["losses"]) == TINY.multichip_iters
    assert len(out["tp"]["losses"]) == 1
    assert len(jax.devices()) == 8  # the tier's virtual topology


def test_script_exits_nonzero_without_a_tpu():
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        cwd=_REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "not a TPU" in r.stderr


def test_bench_default_mode_refuses_the_cpu(capsys):
    """bench.py's default mode measures the chip in this process or
    exits non-zero: no CPU record under a device metric's name."""
    import bench

    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    assert "resnet50_synth_train_throughput" not in capsys.readouterr().out


def test_compile_cache_goes_where_the_environment_says(monkeypatch):
    from bigdl_tpu.utils import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert compile_cache.enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == was  # nothing set
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(_REPO, ".jax_cache")
        assert compile_cache.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_cache_entries_counts_programs(tmp_path):
    from bigdl_tpu.utils.compile_cache import cache_entries

    assert cache_entries(str(tmp_path / "missing")) == 0
    (tmp_path / "jit_step-abc-cache").write_bytes(b"x")
    (tmp_path / "jit_step-abc-atime").write_bytes(b"x")
    assert cache_entries(str(tmp_path)) == 1
