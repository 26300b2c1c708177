"""The yardstick's own arithmetic: trace reduction, operation counts,
the traffic generator, the weights, the files BENCHMARK.json names."""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from conftest import ROOT, TINY_LM


def test_trace_reduction_on_the_recorded_fixture():
    from benchmark import trace_reduce as tr

    r = tr.reduce_trace(os.path.join(ROOT, "benchmark", "fixtures",
                                     "tiny.xplane.txt"))
    # events [0,10] [5,20] [30,40] [60,80] us: busy 50 of an 80 us window
    assert r["window_s"] == pytest.approx(80e-6)
    assert r["busy_s"] == pytest.approx(50e-6)
    assert r["by_name"]["fusion.1"] == [pytest.approx(20e-6), 2]
    assert r["by_name"]["jvp__.14 tpu_custom_call"] == \
        [pytest.approx(15e-6), 1]
    assert tr.seconds_matching(r["by_name"], tr.COLLECTIVES.pattern) == \
        (pytest.approx(20e-6), 1)
    assert r["by_module"] == {"jit_train_step": [pytest.approx(80e-6), 1]}
    assert r["idle_gaps"][0] == ["before all-reduce.7",
                                 pytest.approx(20e-6)]


def test_a_trace_with_no_device_operation_is_a_reading():
    """An idle traced span: busy 0, idle all of the span, nothing to
    name, and no exception (a traced run used to end with exit 1)."""
    from benchmark import trace_reduce as tr
    from benchmark import trace_scopes
    from benchmark.run import read_metric

    path = os.path.join(ROOT, "benchmark", "fixtures", "idle.xplane.txt")
    assert tr.window_of({0: []}) is None and tr.window_of({}) is None
    r = tr.reduce_trace(path, span_s=3.0)
    assert (r["busy_s"], r["window_s"]) == (0.0, 3.0)
    assert r["device_ops"] == r["idle_gaps"] == [] and not r["by_module"]
    assert trace_scopes.read(path) == {}
    run = {"kind": "decode", "trace": r}
    assert read_metric("device_idle_share.serve", run) == 100.0
    assert read_metric("prefill_device_share.moe_serve", run) is None


def test_a_cell_judged_otherwise_reads_the_same_run_by_its_own_names():
    """A per-layer metric moves one end-to-end metric, so the routed
    cell (tokens a second) reads the tick and the idle share under
    names of its own; ``.serve`` stays the latency-judged cell's."""
    from benchmark.run import read_metric

    run = {"kind": "decode", "ticks": 7, "tick_ms_p50": 12.4,
           "trace": {"busy_s": 2.4, "window_s": 3.0}}
    for suffix in ("serve", "moe_serve"):
        assert read_metric("tick_ms_p50." + suffix, run) == 12.4
        assert read_metric("device_idle_share." + suffix, run) == \
            pytest.approx(20.0)
    assert read_metric("tick_ms_p50.moe_serve", dict(run, ticks=0)) is None
    assert read_metric("device_idle_share.moe_serve",
                       dict(run, trace=None)) is None


def test_flops_against_hand_counts():
    from benchmark import flops

    cfg = {"hidden_size": 768, "filter_size": 3072, "num_layers": 12,
           "vocab_size": 50272}
    f = flops.lm_train_flops(cfg, 8, 2048)
    assert f["blocks"] == pytest.approx(8.35e12, rel=5e-3)
    assert f["head"] == pytest.approx(3.80e12, rel=5e-3)
    assert f["attention"] == pytest.approx(1.86e12, rel=5e-3)
    assert f["total"] == pytest.approx(1.40e13, rel=5e-3)
    # ResNet-50: ~4.1 G multiply-adds an image forward -> ~24 GFLOP f+b
    assert flops.resnet50_train_flops() == pytest.approx(24.5e9, rel=0.03)
    # one flash call at 8 x 12 x 2048 x 64: compute-bound on a v5e
    cost = flops.flash_fwd_cost(8, 12, 2048, 64)
    assert cost["flops"] == pytest.approx(1.855e12 / 3 / 12, rel=1e-3)
    assert flops.roofline_seconds(
        cost, {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}) == \
        pytest.approx(cost["flops"] / 197e12)


def test_traffic_is_a_pure_function_of_the_seed():
    from benchmark import traffic

    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "decode-loaded.json")) as f:
        mix = json.load(f)
    a = traffic.request_stream(mix, 2 ** 31 + 5, 20.0, 50272)
    b = traffic.request_stream(mix, 2 ** 31 + 5, 20.0, 50272)
    c = traffic.request_stream(mix, 7, 20.0, 50272)
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, b))
    # another seed: the same set of sizes, in another order
    sizes = lambda s: sorted((r["prompt"].size, r["max_new"]) for r in s)
    assert sizes(a) == sizes(c)
    assert [r["max_new"] for r in a] != [r["max_new"] for r in c]
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert all(lo <= r["prompt"].size <= hi for r in a)
    assert a[0]["due"] < 0 and any(r["measured"] for r in a)
    x1, y1 = traffic.lm_tokens(3, 8, 16, 97)
    x2, _ = traffic.lm_tokens(3, 8, 16, 97)
    assert np.array_equal(x1, x2) and np.array_equal(x1[:, 1:], y1[:, :-1])
    assert len({row.tobytes() for row in x1}) == 8  # rows all differ


def test_weights_come_from_the_seed_by_the_rules():
    import bigdl_tpu.nn as nn
    import jax

    from benchmark import weights

    model = nn.Transformer(dropout=0.0, causal=True, **TINY_LM)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "opt-125m.json")) as f:
        rules = json.load(f)["init"]
    a = weights.make_variables(model, rules, 2 ** 31 + 9)
    b = weights.make_variables(model, rules, 2 ** 31 + 9)
    c = weights.make_variables(model, rules, 9)
    leaves = lambda t: jax.tree_util.tree_leaves(t)
    assert all(np.array_equal(x, y) for x, y in zip(leaves(a), leaves(b)))
    assert not np.array_equal(a["params"]["embed"]["weight"],
                              c["params"]["embed"]["weight"])
    assert float(np.std(a["params"]["embed"]["weight"])) == \
        pytest.approx(0.01, rel=0.1)
    assert np.all(np.asarray(a["params"]["ln_f"]["weight"]) == 1.0)
    assert np.all(np.asarray(a["params"]["layer0"]["ffn"]["b1"]) == 0.0)


def test_every_name_in_benchmark_json_has_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    have = lambda *p: os.path.isfile(os.path.join(ROOT, "benchmark", *p))
    configs = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    cells = set()
    for w in bench["workloads"]:
        assert name.match(w["name"]) and w["config"] in configs
        assert have("traffic", w["traffic"] + ".json")
        assert have("cells", w["name"] + ".json")
        assert len(w["why"]) <= 200
        cells.add(w["name"])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert {"setup_s", "decode_tokens_per_s"} <= set(e2e)
    for m in e2e.values():
        assert name.match(m["name"]) and 0 < m["bound"] <= 0.1
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert have("metrics", m["name"] + ".py"), m["name"]
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        # every cell that reads it reports the metric it should move
        assert set(m["workloads"]) <= set(
            e2e[m["moves"]].get("workloads", cells)), m["name"]
    for cell in cells:  # setup_s, another end-to-end metric, a per-layer one
        reports = lambda m: cell in m.get("workloads", [cell])
        assert sum(reports(m) for m in bench["end_to_end"]) >= 2
        assert any(reports(m) for m in bench["per_layer"])


def test_runner_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(2 ** 31 + 1), "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120)
    assert out.returncode != 0
    assert "not a TPU" in out.stderr and not out.stdout.strip()
