"""Rule ``host-transfer``: callbacks reachable from jitted hot paths.

``pure_callback`` / ``io_callback`` / ``debug_callback`` /
``debug_print`` (``jax.debug.print``) round-trip device -> host -> device on every step;
on TPU that stalls the whole ICI-synchronous program.  A debug print
left in a train step ships green through CPU tests and shows up only
as a mystery 10x on chip — exactly the class graft-lint exists to
refuse.  Infeed/outfeed are flagged for the same reason.
"""
from __future__ import annotations

from bigdl_tpu.analysis.core import LintContext, Rule, iter_eqns, register

_HOST_PRIMS = {
    "pure_callback": "host round-trip on every execution",
    "io_callback": "ordered host side-effect in the hot path",
    "debug_callback": "debug callback left in jitted code",
    "debug_print": "debug print left in jitted code",
    "infeed": "host infeed stalls the synchronous program",
    "outfeed": "host outfeed stalls the synchronous program",
}


@register
class HostTransferRule(Rule):
    name = "host-transfer"
    doc = ("flag pure_callback/io_callback/debug_callback/debug_print/"
           "infeed/outfeed primitives reachable from jitted hot paths")

    def check(self, ctx: LintContext):
        if ctx.jaxpr is None:
            return
        for eqn, _ in iter_eqns(ctx.jaxpr):
            why = _HOST_PRIMS.get(eqn.primitive.name)
            if why is None and "callback" in eqn.primitive.name:
                why = "host callback in the hot path"
            if why is not None:
                cb = eqn.params.get("callback")
                detail = f" ({cb})" if cb is not None else ""
                yield self.finding(
                    ctx, f"{eqn.primitive.name}: {why}{detail}", eqn)
