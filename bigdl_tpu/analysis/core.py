"""graft-lint core: findings, the rule registry, and jaxpr walking.

The analysis operates purely at trace level: every target is reduced to
a ``ClosedJaxpr`` via ``jax.make_jaxpr`` over shape/dtype structs
(``jax.eval_shape`` templates) — no device, no execution, no compile —
and rules walk the equation graph.  This is what lets the whole zoo and
every parallel plan be audited per commit on a CPU-only box: the
failure classes that matter (f64 promotions, host callbacks in hot
paths, wrong collective axes, missing donation, Pallas shapes that
silently fall back to XLA) are all visible in the jaxpr or in the
kernel routing prechecks, long before Mosaic or a chip is involved.

Per-site suppression: append ``# graft-lint: disable=<rule>[,<rule>]``
to the offending source line; findings whose source resolves to that
line are dropped (``disable=all`` silences every rule for the line).
"""
from __future__ import annotations

import linecache
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Tuple

from jax._src import core as jcore
from jax._src import source_info_util

# --------------------------------------------------------------------------
# findings
# --------------------------------------------------------------------------

_SUPPRESS_RE = re.compile(r"#\s*graft-lint:\s*disable=([\w,\-]+)")


@dataclass
class Finding:
    """One rule violation, carrying enough context to act on it."""

    rule: str        # rule name, e.g. "dtype-hygiene"
    target: str      # lint target (model / train step) name
    message: str     # human-readable description
    primitive: str = ""      # offending primitive, if equation-level
    equation: str = ""       # short jaxpr equation rendering
    source: str = ""         # "file:line" of the offending user code

    def as_dict(self) -> dict:
        return {
            "rule": self.rule,
            "target": self.target,
            "message": self.message,
            "primitive": self.primitive,
            "equation": self.equation,
            "source": self.source,
        }

    def __str__(self) -> str:
        loc = f" [{self.source}]" if self.source else ""
        eq = f"\n      {self.equation}" if self.equation else ""
        return f"{self.target}: {self.rule}: {self.message}{loc}{eq}"


def suppressed(finding: Finding) -> bool:
    """True when the finding's source line opts out via the
    ``# graft-lint: disable=<rule>`` comment."""
    if not finding.source or ":" not in finding.source:
        return False
    path, _, line_s = finding.source.rpartition(":")
    try:
        line = linecache.getline(path, int(line_s))
    except ValueError:
        return False
    m = _SUPPRESS_RE.search(line)
    if not m:
        return False
    rules = {r.strip() for r in m.group(1).split(",")}
    return "all" in rules or finding.rule in rules


# --------------------------------------------------------------------------
# rule registry
# --------------------------------------------------------------------------

@dataclass
class LintContext:
    """What a rule sees for one target."""

    name: str                 # target name
    kind: str                 # "model" | "train_step" | "inventory"
    jaxpr: Optional[object]   # ClosedJaxpr (None for inventory targets)
    meta: Dict = field(default_factory=dict)
    # meta keys used by the shipped rules:
    #   plan:            parallel.mesh.PlanInfo (rule collective-axes)
    #   compute_dtype:   the step's intended compute dtype (dtype-hygiene)
    #   donate_expected: minimum donated buffer count (donation)
    #   inventory:       kernel-shape inventory module (pallas-routing)


class Rule:
    """Base class: subclasses set ``name``/``doc`` and yield Findings."""

    name: str = ""
    doc: str = ""

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        raise NotImplementedError

    def finding(self, ctx: LintContext, message: str, eqn=None) -> Finding:
        prim, eq_str, src = "", "", ""
        if eqn is not None:
            prim = eqn.primitive.name
            eq_str = format_eqn(eqn)
            src = eqn_source(eqn) or ""
        return Finding(rule=self.name, target=ctx.name, message=message,
                       primitive=prim, equation=eq_str, source=src)


_RULES: List[Rule] = []


def register(rule_cls):
    """Class decorator adding a rule to the global registry."""
    _RULES.append(rule_cls())
    return rule_cls


def all_rules() -> Tuple[Rule, ...]:
    return tuple(_RULES)


def run_rules(ctx: LintContext,
              only: Optional[Iterable[str]] = None) -> List[Finding]:
    wanted = set(only) if only is not None else None
    out: List[Finding] = []
    for rule in _RULES:
        if wanted is not None and rule.name not in wanted:
            continue
        for f in rule.check(ctx):
            if not suppressed(f):
                out.append(f)
    return out


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------

def _subjaxprs(params: dict) -> Iterator[jcore.Jaxpr]:
    """Every Jaxpr reachable from an equation's params (pjit/scan/cond/
    while/shard_map/custom_vjp/remat/pallas_call all stash theirs under
    different keys — walk values generically)."""
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for item in vs:
            if isinstance(item, jcore.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, jcore.Jaxpr):
                yield item


def iter_eqns(jaxpr) -> Iterator[Tuple[jcore.JaxprEqn, jcore.Jaxpr]]:
    """Yield ``(eqn, enclosing_jaxpr)`` over the whole nested program."""
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    seen = set()
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        for eqn in j.eqns:
            yield eqn, j
            stack.extend(_subjaxprs(eqn.params))


def eqn_source(eqn) -> Optional[str]:
    """'file:line' of the user frame that staged the equation."""
    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return None
    return f"{frame.file_name}:{frame.start_line}"


def format_eqn(eqn, width: int = 140) -> str:
    """One-line jaxpr equation rendering, truncated."""
    try:
        s = str(eqn).replace("\n", " ")
    except Exception:
        s = eqn.primitive.name
    s = re.sub(r"\s+", " ", s).strip()
    return s if len(s) <= width else s[: width - 3] + "..."


def producers(jaxpr: jcore.Jaxpr) -> Dict[object, jcore.JaxprEqn]:
    """var -> the equation producing it (one level, no recursion)."""
    out: Dict[object, jcore.JaxprEqn] = {}
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            out[v] = eqn
    return out


def use_counts(jaxpr: jcore.Jaxpr) -> Dict[object, int]:
    """var -> number of uses inside this jaxpr (outvars count as uses)."""
    counts: Dict[object, int] = {}

    def bump(v):
        if isinstance(v, jcore.Var):
            counts[v] = counts.get(v, 0) + 1

    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            bump(v)
    for v in jaxpr.outvars:
        bump(v)
    return counts
