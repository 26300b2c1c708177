"""Offline Mosaic lowering check — no chip needed.

Compiles every Pallas kernel shape the fused paths hit (shared
inventory: tools/kernel_shapes.py) through the REAL XLA:TPU compiler
against a deviceless v5e topology (the installed libtpu;
jax.experimental.topologies).  This catches the failure class that
interpret-mode tests accept — Mosaic rejections (scoped-VMEM
overflows, unsupported block shapes) — without spending chip time.

    python tools/tpu_aot_check.py            # all kernels, v5e target
    python tools/tpu_aot_check.py --quick    # one shape per kernel

Exit 0 = every kernel LOWERED AND COMPILED for TPU; any Mosaic
rejection or silent XLA fallback (kernel routing didn't pick Pallas)
is a failure.  Execution/numerics still need the chip — the kernel
phase of chip_smoke.py (or tools/kernel_smoke.py for every shape) does
that; this tool is the no-chip gate for every Pallas edit.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# force-route to the Pallas kernels (the process backend is CPU), and
# don't block on cloud metadata
os.environ["BIGDL_TPU_FORCE_PALLAS"] = "1"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
# inherited disable knobs (e.g. from an unfused bench A/B shell) would
# route kernels to XLA and read as a fake routing regression here
for _k in ("BIGDL_TPU_FUSED_DISABLE", "BIGDL_TPU_FUSED_CONV3_DISABLE",
           "BIGDL_TPU_INT8_PALLAS_DISABLE"):
    os.environ.pop(_k, None)

t0 = time.perf_counter()


def mark(msg):
    print(f"[{time.perf_counter() - t0:7.1f}s] {msg}", flush=True)


def main(argv=None):
    p = argparse.ArgumentParser("tpu_aot_check")
    p.add_argument("--quick", action="store_true",
                   help="one shape per kernel family")
    p.add_argument("--step", action="store_true",
                   help="also compile the bench's FULL fused ResNet-50 "
                        "train step (batch 256, bf16) and print its "
                        "HBM/FLOP analysis — graph-level Mosaic + "
                        "memory-fit evidence (slow: tens of minutes)")
    p.add_argument("--unfused", action="store_true",
                   help="with --step: compile the UNFUSED step instead "
                        "(XLA convs + separate BN) — the offline "
                        "fused-vs-unfused HBM comparison")
    p.add_argument("--lm-step", action="store_true",
                   help="also compile lm_bench's full Transformer-LM "
                        "train step (flash attention, batch 8 x 2048) "
                        "deviceless")
    p.add_argument("--multichip", action="store_true",
                   help="compile the COMPOSED train steps against "
                        "deviceless multi-chip topologies: dp x tp and "
                        "pp x dp on v5e:2x2, dp x pp x tp on v5e:2x4 — "
                        "GPT2-small shapes (with --quick: tiny shapes "
                        "for CI).  Proves the GSPMD partitioning of the "
                        "sharded Pallas kernels (shard_map wrappers) "
                        "and records per-device HBM per composed step")
    p.add_argument("--table", nargs="?", const="", default=None,
                   metavar="PATH",
                   help="re-validate a persisted tuned table "
                        "(tools/autotune.py output; default: the "
                        "target kind's committed table): every entry "
                        "must still "
                        "be inside the declared candidate space AND "
                        "re-lower deviceless — stale or infeasible "
                        "entries fail with the offending shape named")
    p.add_argument("--topology", default="v5e:1x1",
                   help="deviceless target (default the bench chip)")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tools import kernel_shapes as KS

    topo = topologies.get_topology_desc(
        topology_name=args.topology, platform="tpu",
        chips_per_host_bounds=[1, 1, 1])
    mesh = Mesh(np.array(topo.devices), ("d",))
    sh = NamedSharding(mesh, P())
    kind = topo.devices[0].device_kind
    mark(f"deviceless target: {kind}")

    from bigdl_tpu.ops.pallas import tuning

    if args.table is not None:
        return _table_check(args.table or tuning.table_path(kind), sh,
                            mark)

    # dispatch keys the tuned table by the RUNNING device kind (the CPU
    # here): install the target's table, so what compiles is what the
    # chip would run
    table_path = tuning.table_path(kind)
    if table_path:
        tuning.set_tuned_table(tuning.TunedTable.load(table_path))
    mark(f"tuned table: {table_path or 'none (hand-picked params)'}")

    from bigdl_tpu.ops.pallas import report as kernel_report
    from bigdl_tpu.ops.pallas import fused_matmul as fm
    from bigdl_tpu.ops.pallas.flash_attention import flash_attention
    from bigdl_tpu.ops.pallas.int8_matmul import int8_matmul_dequant

    failures = 0

    def aot(tag, fn, *shapes, kernel=None):
        """Lower + TPU-compile fn(*ShapeDtypeStructs); assert the
        Pallas path was chosen (not a silent XLA fallback — global
        routing AND the per-shard bm/bimg re-pick inside shard_map)."""
        nonlocal failures
        snap = kernel_report.report().get(kernel, {}) if kernel else {}
        before = snap.get("pallas", 0)
        local_before = snap.get("pallas_local_xla", 0)
        try:
            jitted = jax.jit(fn, in_shardings=sh, out_shardings=sh)
            jitted.lower(*shapes).compile()
            if kernel is not None:
                snap = kernel_report.report().get(kernel, {})
                if snap.get("pallas", 0) <= before:
                    failures += 1
                    mark(f"{tag}: XLA FALLBACK (kernel not routed)")
                    return
                if snap.get("pallas_local_xla", 0) > local_before:
                    failures += 1
                    mark(f"{tag}: PER-SHARD XLA FALLBACK (local shape "
                         "no longer tiles inside shard_map)")
                    return
            mark(f"{tag}: OK")
        except Exception as e:
            failures += 1
            mark(f"{tag}: FAIL {str(e)[:160]}")

    b = KS.BATCH
    S = jax.ShapeDtypeStruct

    conv3 = KS.CONV3[:1] if args.quick else KS.CONV3
    for h, w, c, n in conv3:
        aot(f"conv3 {h}x{w}x{c}->{n} fwd",
            lambda a, b_, c_, d: fm.fused_conv3x3_bn(
                a, b_, prologue_scale=c_, prologue_bias=d, relu=True),
            S((b, h, w, c), jnp.bfloat16), S((3, 3, c, n), jnp.bfloat16),
            S((c,), jnp.float32), S((c,), jnp.float32),
            kernel="fused_conv3x3")

    mms = KS.MATMUL[:1] if args.quick else KS.MATMUL
    for m, k, n in mms:
        aot(f"mm {m}x{k}x{n} fwd",
            lambda a, b_, c_, d: fm.fused_matmul_bn(
                a, b_, prologue_scale=c_, prologue_bias=d, relu=True),
            S((m, k), jnp.bfloat16), S((k, n), jnp.bfloat16),
            S((k,), jnp.float32), S((k,), jnp.float32),
            kernel="fused_matmul")

        def scalar(a, b_, c_, d):
            y, s, q = fm.fused_matmul_bn(
                a, b_, prologue_scale=c_, prologue_bias=d, relu=True)
            return (jnp.sum(y.astype(jnp.float32)) + jnp.sum(s)
                    + jnp.sum(q))

        aot(f"mm {m}x{k}x{n} bwd",
            jax.grad(scalar, argnums=(0, 1, 2)),
            S((m, k), jnp.bfloat16), S((k, n), jnp.bfloat16),
            S((k,), jnp.float32), S((k,), jnp.float32))

    os.environ["BIGDL_TPU_FUSED_CONV3_BWD"] = "1"
    try:
        bwd = KS.CONV3_BWD[:1] if args.quick else KS.CONV3_BWD
        for h, w, c, n in bwd:
            def scalar3(a, b_, c_, d):
                y, s, q = fm.fused_conv3x3_bn(
                    a, b_, prologue_scale=c_, prologue_bias=d, relu=True)
                return (jnp.sum(y.astype(jnp.float32)) + jnp.sum(s)
                        + jnp.sum(q))

            aot(f"conv3 {h}x{w}x{c}->{n} bwd(dgrad)",
                jax.grad(scalar3, argnums=(0, 1, 2)),
                S((b, h, w, c), jnp.bfloat16),
                S((3, 3, c, n), jnp.bfloat16),
                S((c,), jnp.float32), S((c,), jnp.float32),
                kernel="fused_conv3x3_dgrad")
    finally:
        os.environ.pop("BIGDL_TPU_FUSED_CONV3_BWD", None)

    int8s = KS.INT8[:1] if args.quick else KS.INT8
    for m, k, n in int8s:
        aot(f"int8 mm {m}x{k}x{n}",
            lambda a, b_, s_: int8_matmul_dequant(a, b_, s_),
            S((m, k), jnp.int8), S((k, n), jnp.int8),
            S((n,), jnp.float32), kernel="int8_matmul")

    for bq, hq, tq, dq in (KS.FLASH[:1] if args.quick else KS.FLASH):
        aot(f"flash_attention {bq}x{hq}x{tq}x{dq}",
            lambda q: flash_attention(q, q, q, causal=True),
            S((bq, hq, tq, dq), jnp.bfloat16), kernel="flash_attention")
        aot(f"flash_attention bwd {bq}x{hq}x{tq}x{dq}",
            jax.grad(lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, causal=True).astype(jnp.float32)),
                argnums=(0, 1, 2)),
            *[S((bq, hq, tq, dq), jnp.bfloat16)] * 3,
            kernel="flash_attention_bwd")

    from bigdl_tpu.ops.pallas.paged_attention import paged_attn

    for sl, hh, dd, pg, per in KS.PAGED_ATTN:
        pool = S((sl * per + 1, pg, hh * dd), jnp.float32)
        aot(f"paged_attn {sl}x{hh}x{dd} pages {per}x{pg}",
            lambda q, k, v, t, n, hh=hh: paged_attn(q, k, v, t, n,
                                                    num_heads=hh),
            S((sl, 1, hh * dd), jnp.float32), pool, pool,
            S((sl, per), jnp.int32), S((sl,), jnp.int32))

    from bigdl_tpu.ops.pallas.flash_attention import (
        prefix_blocks, prefix_flash_attention)
    from bigdl_tpu.ops.pallas.latent_attention import latent_paged_attn

    for sl, hh, cc, vw, pg, per in KS.LATENT_PAGED_ATTN:
        aot(f"latent_paged_attn {sl}x{hh}x{cc} pages {per}x{pg}",
            lambda q, p_, t, n, vw=vw: latent_paged_attn(
                q, p_, t, n, value_width=vw, sm_scale=0.1),
            S((sl, hh, cc), jnp.bfloat16),
            S((sl * per + 1, pg, cc), jnp.bfloat16),
            S((sl, per), jnp.int32), S((sl,), jnp.int32))
    for bq, hq, tq, sq, dq in KS.FLASH_PREFIX:
        aot(f"flash_prefix {bq}x{hq}x{tq}x{sq}x{dq}",
            lambda q, k, off, tq=tq, sq=sq: prefix_flash_attention(
                q, k, k, off, sm_scale=0.1, blocks=prefix_blocks(tq, sq)),
            S((bq, hq, tq, dq), jnp.bfloat16),
            S((bq, hq, sq, dq), jnp.bfloat16), S((bq,), jnp.int32))

    from bigdl_tpu.ops.pallas.grouped_matmul import (
        grouped_matmul, tiling_for)

    for m, k, n, g, rows in KS.GROUPED_MATMUL:
        aot(f"grouped_matmul {m}x{k}x{n} over {g}",
            lambda x, w, c, t=tiling_for(rows, k, n): grouped_matmul(
                x, w, c, tiling=t),
            S((m, k), jnp.bfloat16), S((g, k, n), jnp.bfloat16),
            S((g,), jnp.int32))

    if not args.quick:
        failures += _hybrid_serve_check(sh, mark)
    if args.step:
        failures += _step_check(sh, mark, fused=not args.unfused)
    if args.lm_step:
        failures += _lm_step_check(sh, mark)
    if args.multichip:
        failures += _multichip_check(mark, quick=args.quick)

    mark(f"paths: {kernel_report.report()}")
    mark("ALL LOWERED" if failures == 0 else f"{failures} FAILURES")
    return 1 if failures else 0


def _hybrid_serve_check(sh, mark) -> int:
    """The Mamba-2 hybrid cell's tick and prompt chunk at the published
    widths (benchmark/configs/nemotron3-super-11of88-ep4share.json, all
    eleven layers, bf16) and the cell's geometry: the pools donated and
    aliased, the temporaries each program needs."""
    import json

    import jax
    import jax.numpy as jnp

    from bigdl_tpu.nn.hybrid_ssm import HybridSSMTransformer
    from bigdl_tpu.serving import paging
    from bigdl_tpu.serving.decode_programs import (build_paged_tick,
                                                   build_prefill_chunk)
    from tools import kernel_shapes as KS

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "nemotron3-super-11of88-ep4share.json")) as f:
        model = HybridSSMTransformer(**json.load(f)["model"])
    slots, max_len, page, chunk = KS.HYBRID_DECODE
    kv = paging.PagedCache(slots, max_len, page,
                           paging.default_num_pages(slots, max_len, page))
    S, bf16 = jax.ShapeDtypeStruct, jnp.bfloat16
    on = dict(in_shardings=sh, out_shardings=sh)
    var = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), bf16))
    pool = jax.eval_shape(lambda: kv.init_cache(model, bf16))
    staging = jax.eval_shape(lambda: model.init_cache(1, max_len, bf16))
    failures = 0
    for tag, build, args in (
            ("tick", build_paged_tick, (
                pool, S((slots, max_len // page), jnp.int32),
                S((slots,), jnp.int32), S((slots,), jnp.bool_),
                S((slots, 2), jnp.uint32), S((slots,), jnp.float32),
                S((slots,), jnp.int32), S((slots,), jnp.float32))),
            ("chunk", build_prefill_chunk, (
                staging, S((1, chunk), jnp.int32), S((1,), jnp.int32)))):
        try:
            mem = build(model, **on).lower(
                var["params"], var["state"], *args).compile(
                ).memory_analysis()
            mark(f"hybrid {tag}: OK, arguments "
                 f"{mem.argument_size_in_bytes / 2**30:.2f} GiB, aliased "
                 f"{mem.alias_size_in_bytes / 2**30:.2f} GiB, temporaries "
                 f"{mem.temp_size_in_bytes / 2**20:.0f} MiB")
        except Exception as e:
            failures += 1
            mark(f"hybrid {tag}: FAIL {str(e)[:160]}")
    return failures


def _table_check(path, sh, mark) -> int:
    """Re-validate a persisted tuned table (tools/autotune.py output).

    Every entry must (a) still sit inside its family's declared
    candidate space — the same membership test tuning.resolve applies
    at dispatch, so a STALE verdict here means dispatch is silently
    ignoring that entry — and (b) still lower + compile through the
    deviceless Mosaic pipeline via the exact injection seam dispatch
    uses.  Failures name the offending (family, shape).  Returns the
    exit code (0 = table fully live)."""
    import jax

    from bigdl_tpu.ops.pallas import report as kernel_report
    from bigdl_tpu.ops.pallas import tuning
    from tools.autotune import _candidate_fn

    if not path or not os.path.exists(path):
        mark("--table: no tuned table found (run tools/autotune.py "
             "--sweep, or pass the path)")
        return 1
    try:
        table = tuning.TunedTable.load(path)
    except Exception as e:
        mark(f"--table: {path}: {e}")
        return 1
    mark(f"validating {len(table)} entries from {path} "
         f"(device_kind={table.device_kind!r})")
    failures = 0
    for key, ent in sorted(table.entries.items()):
        kernel, shape = tuning.parse_key(key)
        params = ent["params"]
        try:
            cands = tuning.candidates(kernel, shape)
        except Exception:
            cands = []
        if params not in cands:
            failures += 1
            mark(f"{key}: STALE — {params} fell out of the declared "
                 "candidate space (dispatch falls back to hand-picked "
                 "params and records source=stale)")
            continue
        fn_or_make, structs, checks = _candidate_fn(kernel, shape)
        probe = tuning.TunedTable(device_kind=table.device_kind)
        probe.add(kernel, shape, params)
        tuning.set_tuned_table(probe)
        try:
            fn = fn_or_make if checks else fn_or_make(
                params[next(iter(params))])
            jax.jit(fn, in_shardings=sh,
                    out_shardings=sh).lower(*structs).compile()
        except Exception as e:
            failures += 1
            mark(f"{key}: INFEASIBLE — {params} no longer lowers: "
                 f"{str(e)[:160]}")
            continue
        finally:
            tuning.set_tuned_table(None)
        if checks:
            rep = kernel_report.last_params(kernel, shape)
            if rep.get("source") != "table" or rep.get("params") != params:
                failures += 1
                mark(f"{key}: NOT APPLIED — dispatch resolved "
                     f"{rep or 'nothing'} instead of the entry")
                continue
        mark(f"{key}: OK {params}")
    mark("TABLE OK" if failures == 0 else f"{failures} TABLE FAILURES")
    return 1 if failures else 0


def _step_check(sh, mark, fused: bool = True) -> int:
    """Compile the bench's full train step — SAME construction as
    bench.py (shared build_bench_model/build_train_step, including
    donated state so the HBM numbers match the real bench executable) —
    against the deviceless target; report peak-HBM and FLOP analysis.
    Returns failure count."""
    try:
        import jax
        import jax.numpy as jnp

        from bench import build_bench_model, build_train_step
        from tools import kernel_shapes as KS

        batch, res = KS.BATCH, 224
        model, crit = build_bench_model(fused=fused)
        step, methods = build_train_step(model, crit, in_shardings=sh,
                                         out_shardings=sh)
        variables = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0)))
        params, mstate = variables["params"], variables["state"]
        opt = jax.eval_shape(
            lambda: {"__all__": methods["__all__"].init_state(
                jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), params))})
        S = jax.ShapeDtypeStruct
        mark(f"train-step: lowering (full ResNet-50, fused={fused}, "
             f"batch {batch})")
        compiled = step.lower(
            params, mstate, opt, S((), jnp.int32),
            S((2,), jnp.uint32), S((batch, res, res, 3), jnp.bfloat16),
            S((batch,), jnp.int32), [S((), jnp.float32)],
        ).compile()
        mem = compiled.memory_analysis()
        gb = 1 / (1024 ** 3)
        mark("train-step: COMPILED; HBM args "
             f"{mem.argument_size_in_bytes * gb:.2f}GB + temps "
             f"{mem.temp_size_in_bytes * gb:.2f}GB + out "
             f"{mem.output_size_in_bytes * gb:.2f}GB (v5e HBM 16GB)")
        cost = compiled.cost_analysis()
        ca = cost[0] if isinstance(cost, (list, tuple)) else cost
        if ca and ca.get("flops"):
            mark(f"train-step: XLA-counted {ca['flops'] / 1e12:.2f} "
                 "TFLOP/step (excludes custom-call kernel interiors)")
        return 0
    except Exception as e:
        mark(f"train-step: FAIL {str(e)[:300]}")
        return 1


def _multichip_check(mark, quick: bool = False) -> int:
    """Compile the COMPOSED train steps against deviceless multi-chip
    topologies (VERDICT r4 next #3): dp x tp and pp x dp on v5e:2x2,
    dp x pp x tp on v5e:2x4 — through the real GSPMD partitioner and
    Mosaic, at GPT2-small shapes (tiny with ``quick`` for CI).  Also
    the compile-level proof that the sharded-kernel shard_map wrappers
    (ops/pallas/partition.py) lower: each leg asserts flash attention
    actually routed to Pallas (no silent XLA fallback).  Reports
    per-device HBM (args + temps + out) per leg.  Returns failure
    count."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies

    import bigdl_tpu.nn as nn
    from bigdl_tpu.ops.pallas import report as kernel_report
    from bigdl_tpu.optim import AdamW
    from bigdl_tpu.parallel.data_parallel import build_dp_train_step
    from bigdl_tpu.parallel.mesh import DATA_AXIS, MeshConfig, make_mesh
    from bigdl_tpu.parallel.pipeline import pipelined_transformer_lm
    from bigdl_tpu.parallel.tensor_parallel import (
        TRANSFORMER_RULES,
        make_param_shardings,
    )
    from tools.lm_bench import LM_DEFAULTS, build_lm

    if quick:
        vocab, hidden, heads, filt, layers = 512, 128, 4, 256, 4
        batch, seq = 8, 256
    else:
        d = LM_DEFAULTS
        vocab, hidden, heads, filt, layers = (
            d["vocabSize"], d["hiddenSize"], d["numHeads"],
            d["filterSize"], d["numLayers"])
        # seq 1024 keeps the three deviceless compiles tractable while
        # staying in flash attention's Pallas regime
        batch, seq = 8, 1024

    S = jax.ShapeDtypeStruct
    gb = 1 / (1024 ** 3)
    failures = 0

    def leg(tag, topo_name, bounds, cfg, make_model, shardings_fn):
        nonlocal failures
        try:
            topo = topologies.get_topology_desc(
                topology_name=topo_name, platform="tpu",
                chips_per_host_bounds=bounds)
            mesh = make_mesh(cfg, topo.devices)
            model = make_model(mesh)
            crit = nn.TimeDistributedCriterion(
                nn.ClassNLLCriterion(logits=True))
            methods = {"__all__": AdamW(3e-4)}
            flash_before = kernel_report.report().get(
                "flash_attention", {}).get("pallas", 0)
            step, _ = build_dp_train_step(
                model, crit, methods, mesh,
                param_shardings=shardings_fn(mesh, model),
                compute_dtype=jnp.bfloat16)
            variables = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0)))
            params, mstate = variables["params"], variables["state"]
            opt = jax.eval_shape(
                lambda: {"__all__": methods["__all__"].init_state(
                    jax.tree_util.tree_map(
                        lambda s: jnp.zeros(s.shape, s.dtype), params))})
            mark(f"{tag}: lowering (batch {batch} x {seq}, "
                 f"mesh {dict(mesh.shape)})")
            compiled = step.lower(
                params, mstate, opt, S((), jnp.int32),
                S((2,), jnp.uint32), S((batch, seq), jnp.int32),
                S((batch, seq), jnp.int32), [S((), jnp.float32)],
            ).compile()
            mem = compiled.memory_analysis()
            mark(f"{tag}: COMPILED; per-device HBM args "
                 f"{mem.argument_size_in_bytes * gb:.2f}GB + temps "
                 f"{mem.temp_size_in_bytes * gb:.2f}GB + out "
                 f"{mem.output_size_in_bytes * gb:.2f}GB (v5e 16GB)")
            flash_after = kernel_report.report().get(
                "flash_attention", {}).get("pallas", 0)
            if flash_after <= flash_before:
                mark(f"{tag}: XLA FALLBACK (flash attention not routed)")
                failures += 1
        except Exception as e:
            failures += 1
            mark(f"{tag}: FAIL {str(e)[:300]}")

    # --- leg A: dp x tp (Megatron rules) on v5e:2x2 -------------------
    leg("multichip dp2 x tp2",
        "v5e:2x2", [2, 2, 1], MeshConfig(data=2, model=2),
        lambda mesh: build_lm(vocab, hidden, heads, filt, layers)[0],
        lambda mesh, model: make_param_shardings(
            mesh,
            jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0)))["params"],
            TRANSFORMER_RULES))

    # --- leg B: pp x dp (pipe schedule, flash inside the manual
    # stage body) on v5e:2x2 -------------------------------------------
    leg("multichip pp2 x dp2",
        "v5e:2x2", [2, 2, 1], MeshConfig(data=2, pipe=2),
        lambda mesh: pipelined_transformer_lm(
            vocab_size=vocab, hidden_size=hidden, num_heads=heads,
            filter_size=filt, num_layers=layers, mesh=mesh,
            num_microbatches=4, dropout=0.0, causal=True,
            data_axis=DATA_AXIS),
        lambda mesh, model: model.param_shardings(mesh))

    # --- leg C: dp x pp x tp composed on v5e:2x4 — flash nests a
    # shard_map over 'model' inside the manual pipe/data stage body ----
    leg("multichip dp2 x pp2 x tp2",
        "v5e:2x4", [2, 4, 1], MeshConfig(data=2, pipe=2, model=2),
        lambda mesh: pipelined_transformer_lm(
            vocab_size=vocab, hidden_size=hidden, num_heads=heads,
            filter_size=filt, num_layers=layers, mesh=mesh,
            num_microbatches=4, dropout=0.0, causal=True,
            data_axis=DATA_AXIS),
        lambda mesh, model: model.param_shardings(
            mesh, tp_rules=TRANSFORMER_RULES))

    return failures


def _lm_step_check(sh, mark) -> int:
    """Compile lm_bench's full Transformer-LM train step (shared
    build_lm, AdamW, bf16, flash attention; batch 8 x seq 2048)
    against the deviceless target.  Returns failure count."""
    try:
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.optim.optimizer import make_train_step
        from bigdl_tpu.ops.pallas import report as kernel_report
        from tools.lm_bench import LM_DEFAULTS, build_lm

        batch, seqlen = LM_DEFAULTS["batchSize"], LM_DEFAULTS["seqLen"]
        model, crit, methods = build_lm()
        flash_before = kernel_report.report().get(
            "flash_attention", {}).get("pallas", 0)
        bwd_before = kernel_report.report().get(
            "flash_attention_bwd", {}).get("pallas", 0)
        step = jax.jit(
            make_train_step(model, crit, methods,
                            compute_dtype=jnp.bfloat16),
            donate_argnums=(0, 1, 2), in_shardings=sh, out_shardings=sh)
        variables = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0)))
        params, mstate = variables["params"], variables["state"]
        opt = jax.eval_shape(
            lambda: {"__all__": methods["__all__"].init_state(
                jax.tree_util.tree_map(
                    lambda s_: jnp.zeros(s_.shape, s_.dtype), params))})
        S = jax.ShapeDtypeStruct
        mark(f"lm-step: lowering (Transformer-LM, batch {batch} x "
             f"{seqlen})")
        compiled = step.lower(
            params, mstate, opt, S((), jnp.int32),
            S((2,), jnp.uint32), S((batch, seqlen), jnp.int32),
            S((batch, seqlen), jnp.int32), [S((), jnp.float32)],
        ).compile()
        mem = compiled.memory_analysis()
        gb = 1 / (1024 ** 3)
        mark("lm-step: COMPILED; HBM args "
             f"{mem.argument_size_in_bytes * gb:.2f}GB + temps "
             f"{mem.temp_size_in_bytes * gb:.2f}GB + out "
             f"{mem.output_size_in_bytes * gb:.2f}GB (v5e HBM 16GB)")
        flash_after = kernel_report.report().get(
            "flash_attention", {}).get("pallas", 0)
        if flash_after <= flash_before:
            # ops/attention falls back to XLA attention on any flash
            # failure — a compiled step without the kernel is exactly
            # the silent-fallback class this tool exists to refuse
            mark("lm-step: XLA FALLBACK (flash attention not routed)")
            return 1
        if kernel_report.report().get(
                "flash_attention_bwd", {}).get("pallas", 0) <= bwd_before:
            mark("lm-step: XLA FALLBACK (flash backward not routed)")
            return 1
        return 0
    except Exception as e:
        mark(f"lm-step: FAIL {str(e)[:300]}")
        return 1


if __name__ == "__main__":
    sys.exit(main())
