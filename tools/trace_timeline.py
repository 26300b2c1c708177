"""One timeline out of one profiler trace: the program's host spans
(plane ``/host:CPU``, written by ``telemetry.Tracer.span``) beside the
device's operations (``/device:TPU:0``), on the session's one clock.

    python tools/trace_timeline.py <trace dir or .xplane.pb> [--top 10]

Prints one JSON object:

* ``host_spans``: ``{name: [count, seconds]}`` of the host plane;
* ``scopes``: ``{program: {scope: seconds}}`` — device time of each
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

from benchmark import trace_reduce  # noqa: E402

WRAPPED = re.compile(r"^(transpose\()?(jvp\()?([^()]*)\)*$")


def scope_of(op_name: str) -> str:
    """``jit(step)/jit(main)/transpose(jvp(attention))/while/body/mul``
    -> ``attention (backward)``: the name scopes below the jit frames,
    nested ones joined by ``/``; ``-`` for an operation under none."""
    parts = [p for p in op_name.split(";")[0].split("/") if not p.startswith(
        ("jit(", "pjit(", "jit_", "while", "body", "cond", "closed_call",
         "checkpoint", "remat", "custom_vjp_call", "custom_jvp_call"))]
    scopes = []
    for part in parts[:-1]:  # the last is the primitive itself
        m = WRAPPED.match(part)
        name = m.group(3) if m else part
        if name:  # ``jvp()``: differentiated, under no scope
            scopes.append(name + (" (backward)" if m and m.group(1)
                                  else ""))
    return "/".join(scopes) or "-"


def op_names(path: str) -> dict:
    """``{operation's event name: its op_name}`` of the first device
    plane.  The profiler keeps an operation's ``op_name`` metadata
    (``jit(step)/attention/dot_general``) as the stat ``tf_op`` of the
    event's *metadata*, which ``ProfileData`` does not hand out: read
    from the file's wire format (XSpace.planes=1; XPlane.name=2,
    event_metadata=4, stat_metadata=5; XEventMetadata.name=2, stats=5;
    XStat.metadata_id=1, str_value=5, ref_value=7)."""
    from bigdl_tpu.interop import protowire as pw

    if not path.endswith(".pb"):
        return {}
    with open(path, "rb") as f:
        space = pw.fields(f.read())
    for plane in pw.get_messages(space, 1):
        if not trace_reduce.DEVICE_PLANE.match(pw.get_str(plane, 2)):
            continue
        stat_names = {}
        for entry in pw.get_messages(plane, 5):
            meta = pw.get_message(entry, 2)
            stat_names[pw.get_int(meta, 1)] = pw.get_str(meta, 2)
        out = {}
        for entry in pw.get_messages(plane, 4):
            meta = pw.get_message(entry, 2)
            for stat in pw.get_messages(meta, 5):
                if stat_names.get(pw.get_int(stat, 1)) == "tf_op":
                    out[pw.get_str(meta, 2)] = pw.get_str(stat, 5) or \
                        stat_names.get(pw.get_int(stat, 7), "")
        return out
    return {}


def outermost(events, names: dict):
    """``[(event, scope)]`` of the events not nested inside an earlier
    one of the same line; an operation without an ``op_name`` of its
    own (a ``while``) takes the scope of the first one nested in it."""
    out, edge = [], -1
    for ev in sorted(events, key=lambda e: (e.start_ns, -e.duration_ns)):
        scope = scope_of(names[ev.name]) if names.get(ev.name) else None
        if ev.start_ns >= edge:
            out.append([ev, scope])
            edge = ev.start_ns + ev.duration_ns
        elif out[-1][1] is None:
            out[-1][1] = scope
    return [(ev, scope or "-") for ev, scope in out]


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
        ops = outermost(lines[trace_reduce.OPS_LINE].events,
                        op_names(path)) \
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
