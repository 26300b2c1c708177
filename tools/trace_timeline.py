"""One timeline out of one profiler trace: the program's host spans
(plane ``/host:CPU``, written by ``telemetry.Tracer.span``) beside the
device's operations (``/device:TPU:0``), on the session's one clock.

    python tools/trace_timeline.py <trace dir or .xplane.pb> [--top 10]

Prints one JSON object:

* ``host_spans``: ``{name: [count, seconds]}`` of the host plane;
* ``scopes``: ``{program: {scope: seconds}}`` (the scope paths through
  ``benchmark/trace_scopes.py``) — device time of each
  compiled program by the ``jax.named_scope`` of its operations
  (``attention``, ``head``, the decode tick's ``attention/paged_append``
  and ``attention/paged_attention`` — ``attention/paged_gather`` where
  the gather path runs...; a backward pass reads
  ``<scope> (backward)``), outermost operations only, with the
  heaviest operations of each scope by instruction name;
* ``idle_gaps``: the longest stretches with no operation on the device,
  each with the operation that ended it and the host spans that cover
  it.

The PERF.md §5 tables are this tool's output over a traced run of
``benchmark/run.py`` (docs/observability.md).
"""
import argparse
import json
import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import trace_reduce, trace_scopes  # noqa: E402


def read(path: str, top: int = 10) -> dict:
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    profile = trace_reduce.load(path)
    host = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, ln.name)
            for p in profile.planes if p.name == "/host:CPU"
            for ln in p.lines for ev in ln.events]
    host_spans = defaultdict(lambda: [0, 0.0])
    for s, e, name, _ in host:
        host_spans[name][0] += 1
        host_spans[name][1] += (e - s) * 1e-9

    device = next((p for p in sorted(profile.planes, key=lambda p: p.name)
                   if trace_reduce.DEVICE_PLANE.match(p.name)), None)
    scopes, gaps = {}, []
    if device is not None:
        lines = {ln.name: ln for ln in device.lines}
        ops = trace_scopes.outermost(lines[trace_reduce.OPS_LINE].events,
                                     trace_scopes.op_scopes(path)) \
            if trace_reduce.OPS_LINE in lines else []
        modules = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns,
             re.sub(r"\(\d+\)$", "", ev.name))
            for ev in (lines["XLA Modules"].events
                       if "XLA Modules" in lines else ()))
        acc = defaultdict(lambda: defaultdict(
            lambda: [0.0, defaultdict(float)]))
        runs = defaultdict(int)
        for _, _, name in modules:
            runs[name] += 1
        i = 0
        for ev, scope in ops:
            while i < len(modules) and modules[i][1] <= ev.start_ns:
                i += 1
            program = modules[i][2] if i < len(modules) \
                and modules[i][0] <= ev.start_ns else "-"
            rec = acc[program][scope]
            rec[0] += ev.duration_ns * 1e-9
            rec[1][trace_reduce.op_name(ev)] += ev.duration_ns * 1e-9
        for program, by_scope in acc.items():
            scopes[program] = {
                "runs": runs.get(program, 0),
                "scopes": {
                    scope: {"seconds": sec, "top_ops": sorted(
                        ([k, v] for k, v in names.items()),
                        key=lambda kv: -kv[1])[:3]}
                    for scope, (sec, names) in sorted(
                        by_scope.items(), key=lambda kv: -kv[1][0])}}
        edge = ops[0][0].start_ns if ops else 0
        for ev, _ in ops:
            if ev.start_ns > edge:
                gaps.append((ev.start_ns - edge, edge, ev.start_ns,
                             trace_reduce.op_name(ev)))
            edge = max(edge, ev.start_ns + ev.duration_ns)
        gaps.sort(reverse=True)
    idle = []
    for length, g0, g1, ended_by in gaps[:top]:
        cover = sorted(
            ((min(e, g1) - max(s, g0), e - s, name)
             for s, e, name, _ in host if s < g1 and e > g0),
            key=lambda c: (-c[0], c[1]))
        idle.append({"seconds": length * 1e-9, "ended_by": ended_by,
                     "host_spans": [[name, round(100.0 * ov / length, 1)]
                                    for ov, _, name in cover[:3]]})
    return {"xplane": path,
            "host_spans": dict(sorted(host_spans.items(),
                                      key=lambda kv: -kv[1][1])[:4 * top]),
            "scopes": scopes, "idle_gaps": idle}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("tools/trace_timeline.py")
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    print(json.dumps(read(args.path, args.top), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
