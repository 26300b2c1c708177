"""Rule ``donation``: train steps must donate their state buffers.

A train step that does not donate params/opt-state doubles its HBM
footprint — the old and new trees are both live across the update.  On
a 16GB v5e that is the difference between batch 256 fitting and an OOM
that only reproduces on chip.  Statically: the target's top-level
``jit`` equation must donate at least ``meta['donate_expected']``
invars (the param + opt-state leaf count), or any at all when the
expectation is not provided.
"""
from __future__ import annotations

from bigdl_tpu.analysis.core import LintContext, Rule, register


@register
class DonationRule(Rule):
    name = "donation"
    doc = ("flag train steps whose params/opt-state buffers are not "
           "donated to the compiled step")

    def check(self, ctx: LintContext):
        if ctx.jaxpr is None or ctx.kind != "train_step":
            return
        expected = int(ctx.meta.get("donate_expected", 0))
        # the jitted step traces to a single top-level jit equation
        jits = [e for e in ctx.jaxpr.jaxpr.eqns
                if e.primitive.name == "jit"
                and "donated_invars" in e.params]
        if not jits:
            yield self.finding(
                ctx, "no jitted step found (target not built through "
                     "jax.jit?) — donation cannot be verified")
            return
        for eqn in jits:
            donated = sum(bool(d) for d in eqn.params["donated_invars"])
            total = len(eqn.params["donated_invars"])
            name = eqn.params.get("name", "<fn>")
            if donated == 0:
                yield self.finding(
                    ctx, f"step '{name}' donates 0 of {total} input "
                         "buffers — params/opt-state are copied, "
                         "doubling live HBM", eqn)
            elif donated < expected:
                yield self.finding(
                    ctx, f"step '{name}' donates {donated} buffers but "
                         f"the params+opt-state trees hold {expected} "
                         "leaves — some state is still copied", eqn)
