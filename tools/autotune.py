"""Offline Pallas block/tile autotuner (ISSUE 13).

The kernels' hand pickers are conservative estimates; PERF.md's
evidence says tile choice is the biggest lever left (flash attention's
128 -> 1024 block change alone was 5x).  This tool sweeps each kernel
family's declared candidate space (ops/pallas/tuning.py) over the shared
shape inventory (tools/kernel_shapes.py):

1. every candidate is injected as a one-entry :class:`TunedTable` and
   the kernel's REAL dispatch path is lowered + compiled through the
   deviceless Mosaic pipeline (the tools/tpu_aot_check.py mechanism —
   local libtpu, no hardware), so acceptance means "Mosaic lowered this
   exact tile via the exact injection seam dispatch uses";
2. survivors are stamped via ``telemetry.costmodel.autotune_stamp`` and
   ranked — fewest XLA-counted HBM bytes, then smallest temps, then the
   LARGEST block (fewer grid steps / deeper pipelining, the PERF.md
   lesson); Mosaic rejections are recorded per candidate with the
   compiler's reason, as data, never dropped;
3. the winner per (family, shape) persists to ``tuned/<device_kind>
   .json`` — the table kernel dispatch consults (tuning.resolve) and
   ``tools/tpu_aot_check.py --table`` re-validates.

Deviceless ranking cannot see runtime: the staged ``--chip`` step (run
on the chip, one process) re-times each entry's
top-k candidates on hardware and overwrites the winner with measured
milliseconds (entry ``source`` flips ``deviceless`` -> ``chip``).

    python tools/autotune.py --sweep              # full inventory
    python tools/autotune.py --smoke              # CI: 1 shape/family,
                                                  # tiny candidate set
    python tools/autotune.py --chip --top-k 3     # on chip: time top-3

Exit 0 = every swept (family, shape) is covered by an accepted entry or
a recorded rejection list, and at least one family accepted (Mosaic
rejections of single candidates are recorded and reported, not a tool
failure).  ``--strict`` additionally fails on any
family with zero accepted entries.
"""
from __future__ import annotations

import argparse
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

t0 = time.perf_counter()


def mark(msg):
    print(f"[{time.perf_counter() - t0:7.1f}s] {msg}", flush=True)


def _deviceless_env():
    """tpu_aot_check.py's environment: force-route to Pallas while the
    process backend stays CPU; compile against a deviceless topology."""
    os.environ["BIGDL_TPU_FORCE_PALLAS"] = "1"
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    for k in ("BIGDL_TPU_FUSED_DISABLE", "BIGDL_TPU_FUSED_CONV3_DISABLE",
              "BIGDL_TPU_INT8_PALLAS_DISABLE", "BIGDL_TPU_TUNED_TABLE"):
        os.environ.pop(k, None)


def _sweep_plan(KS, quick: bool, families):
    """Registry coverage: [(family, shape)] — every Pallas call-site
    shape in tools/kernel_shapes.py, one entry per tunable family."""
    plan = []
    for h, w, c, n in (KS.CONV3[:1] if quick else KS.CONV3):
        plan.append(("fused_conv3x3", (KS.BATCH, h, w, c, n)))
    for h, w, c, n in (KS.CONV3_BWD[:1] if quick else KS.CONV3_BWD):
        plan.append(("fused_conv3x3_dgrad", (KS.BATCH, h, w, c, n)))
    for m, k, n in (KS.MATMUL[:1] if quick else KS.MATMUL):
        plan.append(("fused_matmul", (m, k, n)))
        plan.append(("fused_matmul_dgrad", (m, k, n)))
        plan.append(("fused_matmul_wgrad", (m, k, n)))
    for m, k, n in (KS.INT8[:1] if quick else KS.INT8):
        plan.append(("int8_matmul", (m, k, n)))
    for b, h, t, d in (KS.FLASH[:1] if quick else KS.FLASH):
        plan.append(("flash_attention", (b, h, t, t, d)))
    if families:
        plan = [(f, s) for f, s in plan if f in families]
    return plan


def _candidate_fn(family, shape):
    """(fn, arg_structs, checks_injection) whose deviceless compile
    exercises ``family``'s Pallas kernel at ``shape``.

    Forward families go through the PUBLIC dispatch (the injected table
    steers them via tuning.resolve — acceptance proves the seam);
    dgrad/wgrad go to the private pallas entries, whose in-function
    resolve picks the injected params past the conservative halving
    loops.  conv3-dgrad takes its tile as an argument (resolve lives in
    the custom_vjp bwd rule), so the candidate is passed explicitly and
    ``checks_injection`` is False for it.
    """
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.pallas import fused_matmul as fm

    S = jax.ShapeDtypeStruct
    bf16, f32 = jnp.bfloat16, jnp.float32

    if family == "fused_matmul":
        m, k, n = shape

        def fn(a, b_, c_, d):
            return fm.fused_matmul_bn(a, b_, prologue_scale=c_,
                                      prologue_bias=d, relu=True)

        return fn, (S((m, k), bf16), S((k, n), bf16),
                    S((k,), f32), S((k,), f32)), True

    if family == "fused_matmul_dgrad":
        m, k, n = shape

        def fn(dy, y, dss, dsq, w, x, ps, pb):
            return fm._dgrad_pallas(dy, y, dss, dsq, w, x, ps, pb,
                                    True, True, 8, False)

        return fn, (S((m, n), bf16), S((m, n), bf16), S((n,), f32),
                    S((n,), f32), S((k, n), bf16), S((m, k), bf16),
                    S((k,), f32), S((k,), f32)), True

    if family == "fused_matmul_wgrad":
        m, k, n = shape
        bm_row = fm._pick_bm(m, k, n, 2) or 8

        def fn(x, ps, pb, dy, y, dss, dsq):
            return fm._wgrad_pallas(x, ps, pb, dy, y, dss, dsq,
                                    True, True, bm_row, False)

        return fn, (S((m, k), bf16), S((k,), f32), S((k,), f32),
                    S((m, n), bf16), S((m, n), bf16), S((n,), f32),
                    S((n,), f32)), True

    if family == "fused_conv3x3":
        b, h, w, c, co = shape

        def fn(a, b_, c_, d):
            return fm.fused_conv3x3_bn(a, b_, prologue_scale=c_,
                                       prologue_bias=d, relu=True)

        return fn, (S((b, h, w, c), bf16), S((3, 3, c, co), bf16),
                    S((c,), f32), S((c,), f32)), True

    if family == "fused_conv3x3_dgrad":
        b, h, w, ci, co = shape

        def make(bimg):
            def fn(dy, y, dss, dsq, wt, x, ps, pb):
                return fm._conv3_dgrad_pallas(dy, y, dss, dsq, wt, x,
                                              ps, pb, True, True, bimg,
                                              False)
            return fn

        return make, (S((b, h, w, co), bf16), S((b, h, w, co), bf16),
                      S((co,), f32), S((co,), f32),
                      S((3, 3, ci, co), bf16), S((b, h, w, ci), bf16),
                      S((ci,), f32), S((ci,), f32)), False

    if family == "flash_attention":
        from bigdl_tpu.ops.pallas.flash_attention import flash_attention
        b, h, t, s, d = shape

        def fn(q):
            return flash_attention(q, q, q, causal=True)

        return fn, (S((b, h, t, d), bf16),), True

    if family == "int8_matmul":
        from bigdl_tpu.ops.pallas.int8_matmul import int8_matmul_dequant
        m, k, n = shape

        def fn(a, b_, s_):
            return int8_matmul_dequant(a, b_, s_)

        return fn, (S((m, k), jnp.int8), S((k, n), jnp.int8),
                    S((n,), f32)), True

    raise KeyError(family)


def _rank_key(cost, params):
    # fewest HBM bytes, then smallest temps, then LARGEST block (fewer
    # grid steps; PERF.md's flash 128->1024 lesson says bigger wins ties)
    vol = math.prod(int(v) for v in params.values())
    return (cost.bytes_accessed, cost.temp_bytes, -vol)


def _sweep(args):
    _deviceless_env()
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bigdl_tpu.ops.pallas import report as kernel_report
    from bigdl_tpu.ops.pallas import tuning
    from bigdl_tpu.telemetry import costmodel
    from tools import kernel_shapes as KS

    topo = topologies.get_topology_desc(
        topology_name=args.topology, platform="tpu",
        chips_per_host_bounds=[1, 1, 1])
    mesh = Mesh(np.array(topo.devices), ("d",))
    sh = NamedSharding(mesh, P())
    kind = topo.devices[0].device_kind
    mark(f"deviceless target: {kind}")

    out = args.out or tuning.table_file(kind)
    table = tuning.TunedTable(device_kind=kind)
    plan = _sweep_plan(KS, quick=args.smoke or args.quick,
                       families=args.families)
    uncovered, family_accepts = [], {}

    for family, shape in plan:
        cands = tuning.candidates(family, shape)
        if args.max_candidates:
            cands = cands[:args.max_candidates]
        incumbent = tuning.default_params(family, shape)
        tag = tuning.entry_key(family, shape)
        if not cands:
            # the family itself routes this shape to XLA — coverage by
            # an explicit rejection, so the table says why
            table.reject(family, shape, {},
                         "empty candidate space (kernel routes to XLA)")
            mark(f"{tag}: no candidates (XLA-routed shape)")
            continue
        scored = []
        for params in cands:
            # fresh closure per candidate: identical function objects
            # would hit jax's trace cache and silently reuse the FIRST
            # candidate's resolve decision for every later one
            fn_or_make, structs, checks = _candidate_fn(family, shape)
            probe = tuning.TunedTable(device_kind=kind)
            probe.add(family, shape, params)
            tuning.set_tuned_table(probe)
            try:
                fn = fn_or_make if checks else fn_or_make(
                    params[next(iter(params))])
                lowered = jax.jit(
                    fn, in_shardings=sh, out_shardings=sh).lower(*structs)
                compiled = lowered.compile()
            except Exception as e:
                table.reject(family, shape, params, str(e))
                continue
            finally:
                tuning.set_tuned_table(None)
            if checks:
                rep = kernel_report.last_params(family, shape)
                if rep.get("source") != "table" or \
                        rep.get("params") != params:
                    table.reject(
                        family, shape, params,
                        f"candidate not applied by dispatch "
                        f"(resolved {rep or 'nothing'})")
                    continue
            cost = costmodel.autotune_stamp(
                family, shape, params, lowered=lowered, compiled=compiled)
            scored.append((params, cost))
        nrej = len(table.rejected.get(tag, []))
        if not scored:
            if nrej == 0:
                uncovered.append((family, shape))
            mark(f"{tag}: 0/{len(cands)} accepted "
                 f"({nrej} rejections recorded)")
            continue
        scored.sort(key=lambda pc: _rank_key(pc[1], pc[0]))
        best, best_cost = scored[0]
        marker = " (=default)" if best == incumbent else \
            f" (default {incumbent})"
        table.add(
            family, shape, best, source="deviceless",
            cost={"bytes_accessed": best_cost.bytes_accessed,
                  "temp_bytes": best_cost.temp_bytes,
                  "flops": best_cost.flops},
            ranked=[{"params": p,
                     "bytes_accessed": c.bytes_accessed,
                     "temp_bytes": c.temp_bytes} for p, c in scored])
        family_accepts[family] = family_accepts.get(family, 0) + 1
        mark(f"{tag}: {len(scored)}/{len(cands)} accepted, "
             f"best {best}{marker}")

    table.persist(out)
    mark(f"persisted {len(table)} entries + "
         f"{sum(len(v) for v in table.rejected.values())} rejections "
         f"-> {out}")
    swept_families = {f for f, _ in plan}
    dead = sorted(f for f in swept_families if f not in family_accepts)
    if dead:
        mark(f"families with zero accepted candidates: "
             f"{', '.join(dead)}")
    if uncovered:
        mark("UNCOVERED (no entry, no rejection): "
             + ", ".join(tuning.entry_key(f, s) for f, s in uncovered))
        return 1
    if not family_accepts:
        mark("FAILED: no family accepted any candidate")
        return 1
    if args.strict and dead:
        mark("FAILED (--strict): families without accepted entries")
        return 1
    mark("SWEEP OK")
    return 0


def _chip(args):
    """Staged on-chip step: re-time each entry's ranked top-k with real
    inputs and overwrite the winner with measured ms (source 'chip').
    Run on the chip, as the only process that holds it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.ops.pallas import tuning

    if jax.default_backend() != "tpu":
        mark("FAILED: --chip needs a TPU backend "
             "(deviceless ranking is --sweep)")
        return 1
    kind = jax.devices()[0].device_kind
    path = args.out or tuning.table_path()
    if not path or not os.path.exists(path):
        mark("FAILED: no tuned table to re-time (run --sweep first)")
        return 1
    table = tuning.TunedTable.load(path)
    mark(f"re-timing {len(table)} entries on {kind} (top-{args.top_k})")
    rng = np.random.RandomState(0)

    def _vals_for(structs):
        vals = []
        for s in structs:
            if s.dtype == jnp.int8:
                vals.append(jnp.asarray(
                    rng.randint(-127, 127, s.shape), jnp.int8))
            else:
                vals.append(jnp.asarray(
                    rng.standard_normal(s.shape), s.dtype))
        return vals

    for key, ent in sorted(table.entries.items()):
        family, shape = tuning.parse_key(key)
        ranked = ent.get("ranked") or [{"params": ent["params"]}]
        vals = None
        timed = []
        for rec in ranked[:args.top_k]:
            params = rec["params"]
            # fresh closure per candidate (jit-cache identity, as in
            # the sweep)
            fn_or_make, structs, checks = _candidate_fn(family, shape)
            if vals is None:
                vals = _vals_for(structs)
            probe = tuning.TunedTable(device_kind=kind)
            probe.add(family, shape, params)
            tuning.set_tuned_table(probe)
            try:
                fn = fn_or_make if checks else fn_or_make(
                    params[next(iter(params))])
                jitted = jax.jit(fn)
                jax.block_until_ready(jitted(*vals))  # warmup compile
                t = time.perf_counter()
                for _ in range(args.iters):
                    out = jitted(*vals)
                jax.block_until_ready(out)
                ms = (time.perf_counter() - t) * 1e3 / args.iters
                timed.append((params, ms))
                mark(f"{key}: {params} -> {ms:.3f} ms")
            except Exception as e:
                table.reject(family, shape, params, f"chip: {e}")
                mark(f"{key}: {params} FAILED on chip: {str(e)[:120]}")
            finally:
                tuning.set_tuned_table(None)
        if timed:
            timed.sort(key=lambda pm: pm[1])
            best, ms = timed[0]
            table.add(family, shape, best, source="chip",
                      cost={"ms": ms, **(ent.get("cost") or {})},
                      ranked=[{"params": p, "ms": m} for p, m in timed])
    table.persist(path)
    mark(f"persisted chip-ranked table -> {path}")
    return 0


def main(argv=None):
    p = argparse.ArgumentParser("autotune")
    p.add_argument("--sweep", action="store_true",
                   help="deviceless candidate sweep over the full "
                        "tools/kernel_shapes.py inventory")
    p.add_argument("--smoke", action="store_true",
                   help="CI smoke: one shape per family, candidate set "
                        "capped at 2, output under /tmp unless --out")
    p.add_argument("--quick", action="store_true",
                   help="one shape per family (full candidate sets)")
    p.add_argument("--chip", action="store_true",
                   help="staged on-chip step: time each entry's top-k "
                        "and re-rank by measured ms")
    p.add_argument("--families", type=lambda s: set(s.split(",")),
                   default=None, help="comma-separated family filter")
    p.add_argument("--max-candidates", type=int, default=0,
                   help="cap candidates per shape (0 = all)")
    p.add_argument("--top-k", type=int, default=3,
                   help="--chip: candidates to time per entry")
    p.add_argument("--iters", type=int, default=20,
                   help="--chip: timing iterations per candidate")
    p.add_argument("--out", default=None,
                   help="table path (default tuned/<device_kind>.json)")
    p.add_argument("--strict", action="store_true",
                   help="fail if any family accepted zero candidates")
    p.add_argument("--topology", default="v5e:1x1",
                   help="deviceless target (default the bench chip)")
    args = p.parse_args(argv)

    if args.chip:
        return _chip(args)
    if args.smoke:
        args.max_candidates = args.max_candidates or 2
        args.out = args.out or os.path.join(
            os.environ.get("TMPDIR", "/tmp"),
            f"bigdl_tpu_tuned_smoke_{os.getpid()}.json")
    if not (args.sweep or args.smoke or args.quick):
        p.error("pick one of --sweep / --smoke / --quick / --chip")
    return _sweep(args)


if __name__ == "__main__":
    sys.exit(main())
