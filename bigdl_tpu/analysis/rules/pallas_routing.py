"""Rule ``pallas-routing``: every inventoried shape must take Pallas.

The fused kernels all carry a trace-time precheck (tile divisibility,
VMEM budget) and silently fall back to plain XLA when it fails — the
right *runtime* behaviour, but a shape in ``tools/kernel_shapes.py``
is there precisely because a bench hot path hits it, and a fallback
there is a perf regression nobody sees (ADVICE r5: the per-shard
``bm=None`` path was invisible to every report).  This rule re-runs
the kernels' own pickers — the same functions the dispatch uses, so
the audit can never drift from the code — over the whole inventory and
flags any shape that would not route to Pallas.

Two further audits ride on the same rule (ISSUE 13):

* tuned tables attached as ``meta["tuned_tables"]`` (every committed
  table on the ``kernel_inventory`` target) are checked entry-by-entry
  against
  the declared candidate spaces — the membership test
  ``tuning.resolve`` applies at dispatch, so a finding here means
  dispatch is silently ignoring that entry (recording ``stale``) and
  the table needs a re-sweep;
* a context with ``meta["expect_remat"]`` (the fused-block backward
  target) must carry a ``remat2`` equation in its jaxpr — the fused
  block's custom_vjp residuals otherwise pin ~4 GB of extra HBM temps
  across the backward (PERF.md §fused-conv).
"""
from __future__ import annotations

import numpy as np

from bigdl_tpu.analysis.core import (Finding, LintContext, Rule,
                                     iter_eqns, register)


@register
class PallasRoutingRule(Rule):
    name = "pallas-routing"
    doc = ("statically verify every fused-path shape in the kernel "
           "inventory routes to a Pallas kernel (tile-divisibility "
           "precheck), not a silent XLA fallback")

    def check(self, ctx: LintContext):
        yield from self._check_tuned_table(ctx)
        yield from self._check_remat(ctx)
        inv = ctx.meta.get("inventory")
        if inv is None:
            return
        # bind the submodules, not the same-named package attrs (the
        # package re-exports `flash_attention` the function, which
        # shadows the module on plain `import ... as`)
        import importlib

        fa = importlib.import_module("bigdl_tpu.ops.pallas.flash_attention")
        fm = importlib.import_module("bigdl_tpu.ops.pallas.fused_matmul")
        i8 = importlib.import_module("bigdl_tpu.ops.pallas.int8_matmul")

        def fail(kernel, shape, why):
            return Finding(
                rule=self.name, target=ctx.name,
                message=f"{kernel} {shape}: would fall back to XLA "
                        f"({why})",
                primitive=kernel,
                source=getattr(inv, "__file__", "") and
                f"{inv.__file__}:1" or "")

        itemsize = 2  # bf16 activations everywhere in the inventory
        batch = getattr(inv, "BATCH", 0)
        for h, w, c, n in getattr(inv, "CONV3", ()):
            if fm._pick_bimg(batch, h, w, c, n, itemsize) is None:
                yield fail("fused_conv3x3", (batch, h, w, c, n),
                           "no image-block fits the VMEM budget")
            if 9 * c * n * itemsize > 8 * 1024 * 1024:
                yield fail("fused_conv3x3", (h, w, c, n),
                           "weight block exceeds the resident budget")
        for h, w, c, n in getattr(inv, "CONV3_BWD", ()):
            if fm._pick_bimg_dgrad(batch, h, w, c, n, itemsize) is None:
                yield fail("fused_conv3x3_dgrad", (batch, h, w, c, n),
                           "no dgrad image-block fits the VMEM budget")
        for m, k, n in getattr(inv, "MATMUL", ()):
            if fm._pick_bm(m, k, n, itemsize) is None:
                yield fail("fused_matmul", (m, k, n),
                           "no row tile divides M within the VMEM "
                           "budget")
            if not fm._weights_fit(k, n, itemsize):
                yield fail("fused_matmul", (m, k, n),
                           "resident (K, N) weight block over budget")
        for m, k, n in getattr(inv, "INT8", ()):
            if i8._pick_bm(m, k, n) is None:
                yield fail("int8_matmul", (m, k, n),
                           "no row tile divides M within the VMEM "
                           "budget")
            elif k % 128 or n % 128:
                yield fail("int8_matmul", (m, k, n),
                           "K/N not 128-lane aligned")
            elif k * n > 8 * 1024 * 1024:
                yield fail("int8_matmul", (m, k, n),
                           "resident weight block over budget")
        gm = importlib.import_module("bigdl_tpu.ops.pallas.grouped_matmul")
        for m, k, n, g, rows in getattr(inv, "GROUPED_MATMUL", ()):
            tiling = gm.tiling_for(rows, k, n)
            if tiling is None or m % tiling[0]:
                yield fail("grouped_matmul", (m, k, n, g),
                           "no row tile divides M or no weight block "
                           "of K rows fits the VMEM budget")
        flash = getattr(inv, "FLASH", None)
        if flash is not None:
            shapes = [flash] if isinstance(flash[0], (int, np.integer)) \
                else list(flash)
            for b, hh, t, d in shapes:
                if fa.fit_block(t, 1024) is None:
                    yield fail("flash_attention", (b, hh, t, d),
                               "sequence length has no 128-multiple "
                               "block divisor")
                elif fa.bwd_blocks(t, t, d, itemsize) is None:
                    yield fail("flash_attention_bwd", (b, hh, t, d),
                               "no 128-multiple block divisor under the "
                               "backward's cap, or the sequence's dQ "
                               "over its VMEM budget")

    def _check_tuned_table(self, ctx: LintContext):
        """Every tuned-table entry must still be inside its family's
        declared candidate space — the exact membership test dispatch
        (tuning.resolve) applies, so a finding means the entry is dead
        weight: dispatch records ``stale`` and uses hand-picked params."""
        from bigdl_tpu.ops.pallas import tuning

        for table in ctx.meta.get("tuned_tables", ()):
            src = str(getattr(table, "path", "") or "")
            for key, ent in sorted(table.entries.items()):
                try:
                    kernel, shape = tuning.parse_key(key)
                except ValueError:
                    yield Finding(rule=self.name, target=ctx.name,
                                  message=f"malformed tuned-table key "
                                          f"'{key}'", source=src)
                    continue
                params = ent.get("params", {})
                try:
                    cands = tuning.candidates(kernel, shape)
                except Exception:
                    cands = []
                if params not in cands:
                    yield Finding(
                        rule=self.name, target=ctx.name,
                        message=f"{kernel} {shape}: tuned-table entry "
                                f"{params} is outside the declared "
                                "candidate space — dispatch falls back "
                                "to hand-picked params (source=stale); "
                                "re-run tools/autotune.py --sweep",
                        primitive=kernel, source=src)

    def _check_remat(self, ctx: LintContext):
        """A context declaring ``expect_remat`` (the fused-block
        backward target) must contain a ``remat2`` equation: without
        it every fused kernel's raw-output residual stays live across
        the whole backward (PERF.md: +4 GB of HBM temps at batch 256,
        batch 512 stops fitting)."""
        if not ctx.meta.get("expect_remat") or ctx.jaxpr is None:
            return
        for eqn, _ in iter_eqns(ctx.jaxpr):
            if eqn.primitive.name == "remat2":
                return
        yield Finding(
            rule=self.name, target=ctx.name,
            message="no remat2 equation in the traced backward: the "
                    "fused block's conv residuals are not "
                    "rematerialized (BIGDL_TPU_FUSED_REMAT off, or "
                    "jax.checkpoint dropped from _FusedResBlock.apply) "
                    "— the backward pins every raw conv output in HBM",
            primitive="remat2")
