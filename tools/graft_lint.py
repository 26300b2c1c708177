"""graft-lint CLI — static audit of the zoo, parallel plans, and Pallas
routing with NO hardware (docs/graft_lint.md).

Every target is traced to a jaxpr via eval_shape/make_jaxpr (no device,
no execution, no XLA compile) and the rule engine walks the equations:
dtype hygiene, host transfers, collective/sharding axes, donation, and
the kernel-shape routing precheck.

    python tools/graft_lint.py --all              # full registry
    python tools/graft_lint.py --all --json       # machine report
    python tools/graft_lint.py --target lenet --target dp_train_step
    python tools/graft_lint.py --fixture undonated_step   # must exit 1
    python tools/graft_lint.py --list

Exit 0 = every audited target clean; any finding or trace error is
non-zero.  This is the standing pre-merge gate (run_tests.sh runs it
after the pytest tier).
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# CPU-only, 8 virtual devices so mesh/plan targets trace without a chip
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()


def main(argv=None):
    ap = argparse.ArgumentParser(
        "graft_lint", description="jaxpr-level static analysis gate")
    ap.add_argument("--all", action="store_true",
                    help="lint every registry target")
    ap.add_argument("--target", action="append", default=[],
                    help="lint a named target (repeatable)")
    ap.add_argument("--fixture", action="append", default=[],
                    help="lint a seeded-defect fixture (repeatable; "
                         "expected to produce findings -> exit 1)")
    ap.add_argument("--rule", action="append", default=[],
                    help="restrict to the named rule(s)")
    ap.add_argument("--json", nargs="?", const="-", default=None,
                    metavar="PATH",
                    help="emit the JSON report (to PATH, or stdout)")
    ap.add_argument("--list", action="store_true",
                    help="list targets, fixtures, and rules")
    args = ap.parse_args(argv)

    from bigdl_tpu import analysis
    from bigdl_tpu.analysis import fixtures as fx
    from bigdl_tpu.analysis import report as rpt

    if args.list:
        print("targets:")
        for t in analysis.all_targets():
            print(f"  {t.name:<24} [{t.kind}] {t.note}")
        print("fixtures (seeded defects):")
        for name, (rule, _) in sorted(fx.all_fixtures().items()):
            rules = rule if isinstance(rule, str) else ", ".join(rule)
            print(f"  {name:<24} trips {rules}")
        print("rules:")
        for r in analysis.all_rules():
            print(f"  {r.name:<24} {r.doc}")
        return 0

    if not (args.all or args.target or args.fixture):
        ap.error("nothing to lint: pass --all, --target, or --fixture")

    only = args.rule or None
    names = None if args.all else (args.target or [])
    results, errors = ({}, {})
    if args.all or args.target:
        results, errors = analysis.lint(names, only)
    for name in args.fixture:
        _, build = fx.get_fixture(name)
        try:
            ctx = build()
            results[ctx.name] = analysis.lint_context(ctx, only)
        except Exception as e:  # noqa: BLE001
            errors[f"fixture:{name}"] = f"{type(e).__name__}: {e}"

    text = rpt.render_text(results, errors)
    if args.json is not None:
        blob = rpt.render_json(results, errors)
        if args.json == "-":
            print(blob)
            print(text, file=sys.stderr)
        else:
            with open(args.json, "w") as f:
                f.write(blob + "\n")
            print(text)
    else:
        print(text)
    dirty = sum(len(v) for v in results.values()) + len(errors)
    return 1 if dirty else 0


if __name__ == "__main__":
    sys.exit(main())
