"""graft-lint (bigdl_tpu/analysis): the clean zoo must lint clean, and
every seeded-defect fixture must trip exactly its rule — the linter's
own regression gate, fast enough for tier-1 (everything traces via
eval_shape/make_jaxpr; nothing executes)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import analysis
from bigdl_tpu.analysis import fixtures as fx
from bigdl_tpu.analysis import report as rpt
from bigdl_tpu.analysis.core import Finding, suppressed
from bigdl_tpu.analysis.rules.collectives import check_permutation


# ---------------------------------------------------------------------------
# the full clean zoo
# ---------------------------------------------------------------------------
def test_clean_zoo_lints_with_zero_findings():
    results, errors = analysis.lint()
    assert not errors, f"targets failed to trace: {errors}"
    dirty = {k: [str(f) for f in v] for k, v in results.items() if v}
    assert not dirty, f"clean tree produced findings: {dirty}"
    # the registry really covers the zoo + plans + inventory
    kinds = {t.kind for t in analysis.all_targets()}
    assert kinds == {"model", "train_step", "inventory"}
    assert len(results) >= 15


def test_telemetry_step_parity_holds_under_a_setup_scope():
    """``telemetry_step_parity`` stages the instrumented step inside a
    set-up scope too (its compile listener live): the step's jaxpr is
    still byte-identical, and the scope reached the ring."""
    from bigdl_tpu import telemetry

    tr = telemetry.get_tracer()
    tr.clear()
    results, errors = analysis.lint(["telemetry_step_parity"])
    assert not errors and results == {"telemetry_step_parity": []}
    assert [s.args for s in tr.spans() if s.name == "compile"] == [
        {"program": "lint_step"}]
    tr.clear()


# ---------------------------------------------------------------------------
# seeded defects: each trips exactly its rule
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(fx.all_fixtures()))
def test_fixture_trips_exactly_its_rule(name):
    expected_rule, build = fx.get_fixture(name)
    expected = ({expected_rule} if isinstance(expected_rule, str)
                else set(expected_rule))
    findings = analysis.lint_context(build())
    assert findings, f"fixture {name} produced no findings"
    rules = {f.rule for f in findings}
    assert rules == expected, (
        f"fixture {name} expected only {expected}, got {rules}: "
        f"{[str(f) for f in findings]}")


def test_fixture_findings_carry_source_and_equation():
    _, build = fx.get_fixture("debug_callback")
    (f,) = [f for f in analysis.lint_context(build())
            if f.rule == "host-transfer"]
    assert f.primitive == "debug_print"
    assert "fixtures.py" in f.source
    assert f.equation  # jaxpr equation rendering present


def test_dtype_churn_round_trip_flagged_only_in_reduced_precision():
    from bigdl_tpu.analysis.core import LintContext

    def f(x):
        return x.astype(jnp.float32).astype(jnp.bfloat16)

    jaxpr = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4, 4), jnp.bfloat16))
    bf16_ctx = LintContext(name="churn", kind="train_step", jaxpr=jaxpr,
                           meta={"compute_dtype": "bfloat16"})
    findings = analysis.lint_context(bf16_ctx, only=["dtype-hygiene"])
    assert len(findings) == 1 and "churn" in findings[0].message
    # without a declared compute dtype the same trace is not judged
    plain_ctx = LintContext(name="churn", kind="model", jaxpr=jaxpr)
    assert not analysis.lint_context(plain_ctx, only=["dtype-hygiene"])


# ---------------------------------------------------------------------------
# JSON contract: rule, model, equation source for every finding
# ---------------------------------------------------------------------------
def test_json_report_names_rule_model_and_equation_source():
    _, build = fx.get_fixture("undonated_step")
    ctx = build()
    results = {ctx.name: analysis.lint_context(ctx)}
    blob = json.loads(rpt.render_json(results, {}))
    assert blob["summary"]["findings"] >= 1
    [t] = blob["targets"].values()
    for f in t["findings"]:
        assert f["rule"] == "donation"
        assert f["target"] == "fixture:undonated_step"
        assert f["equation"] and f["primitive"] == "jit"


# ---------------------------------------------------------------------------
# per-site suppression
# ---------------------------------------------------------------------------
def test_suppression_comment(tmp_path):
    src = tmp_path / "mod.py"
    src.write_text("x = 1  # graft-lint: disable=host-transfer\n"
                   "y = 2\n")
    hit = Finding(rule="host-transfer", target="t", message="m",
                  source=f"{src}:1")
    miss = Finding(rule="host-transfer", target="t", message="m",
                   source=f"{src}:2")
    other = Finding(rule="donation", target="t", message="m",
                    source=f"{src}:1")
    assert suppressed(hit)
    assert not suppressed(miss)
    assert not suppressed(other)  # disable= names a different rule


# ---------------------------------------------------------------------------
# ppermute structure checker
# ---------------------------------------------------------------------------
def test_permutation_checker():
    assert check_permutation([(0, 1), (1, 2), (2, 3)], 4) is None  # chain
    assert check_permutation([(i, (i + 1) % 4) for i in range(4)],
                             4) is None                            # ring
    assert check_permutation([], 4)                        # empty
    assert check_permutation([(0, 1), (0, 2)], 4)          # dup source
    assert check_permutation([(0, 1), (2, 1)], 4)          # dup dest
    assert check_permutation([(0, 1), (2, 3)], 4)          # disconnected
    assert check_permutation([(0, 5)], 4)                  # out of range


# ---------------------------------------------------------------------------
# CLI entry (in-process; the tool sets its own env idempotently)
# ---------------------------------------------------------------------------
def test_cli_exit_codes():
    import tools.graft_lint as gl

    assert gl.main(["--target", "lenet", "--target", "kernel_inventory"]) \
        == 0
    assert gl.main(["--fixture", "undonated_step"]) == 1
    assert gl.main(["--list"]) == 0


# ---------------------------------------------------------------------------
# plan metadata (parallel/) surfaced for rule 3
# ---------------------------------------------------------------------------
def test_plan_info_exposed_by_dp_builder():
    import bigdl_tpu.nn as nn
    from bigdl_tpu import models
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.parallel import MeshConfig, make_mesh, plan_info
    from bigdl_tpu.parallel.data_parallel import build_dp_train_step

    mesh = make_mesh(MeshConfig(data=4), jax.devices()[:4])
    info = plan_info(mesh)
    assert info.active_axes == frozenset({"data"})
    assert info.degree("data") == 4 and info.degree("model") == 1
    assert info.degree("nope") is None

    _, placement = build_dp_train_step(
        models.LeNet5(), nn.ClassNLLCriterion(logits=True),
        {"__all__": SGD(1e-2)}, mesh)
    assert placement["plan"] == info


# ---------------------------------------------------------------------------
# per-shard fallback recording (ops/pallas) feeding rule 5's runtime twin
# ---------------------------------------------------------------------------
def test_pallas_local_fallback_recorded():
    from bigdl_tpu.ops.pallas import report as kernel_report
    from bigdl_tpu.ops.pallas.fused_matmul import fused_matmul_bn
    from bigdl_tpu.ops.pallas.partition import kernel_mesh_scope
    from bigdl_tpu.parallel import MeshConfig, make_mesh

    rs = np.random.RandomState(0)
    # m=8 routes to Pallas globally (bm=8) but the per-shard rows over
    # data=4 are 2 — no tile divides them, the local path must fall
    # back AND record that it did
    x = jnp.asarray(rs.randn(8, 32), jnp.float32)
    w = jnp.asarray(rs.randn(32, 16), jnp.float32)
    ref = fused_matmul_bn(x, w, interpret=True)
    mesh = make_mesh(MeshConfig(data=4), jax.devices()[:4])
    kernel_report.reset()
    with kernel_mesh_scope(mesh):
        got = jax.jit(lambda x_: fused_matmul_bn(
            x_, w, interpret=True))(x)
    counts = kernel_report.report()["fused_matmul"]
    assert counts.get("pallas_local_xla", 0) >= 1, counts
    for r, g in zip(ref, got):
        np.testing.assert_allclose(np.asarray(r), np.asarray(g),
                                   rtol=1e-5, atol=1e-5)


def test_shard_kernel_call_refuses_reduce_with_single_output():
    from bigdl_tpu.ops.pallas.partition import shard_kernel_call

    with pytest.raises(AssertionError, match="reduce_outputs"):
        shard_kernel_call(
            lambda x: (x,), (jnp.ones((4, 4)),),
            dim_axes=((None, None),), out_dim_axes=((None, None),),
            reduce_outputs=(0,), single_output=True)
