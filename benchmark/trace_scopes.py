"""Device time of a traced run by compiled program, operation and named
scope: what the roofline readers of single layers divide by.

The profiler keeps an operation's ``jax.named_scope`` path
(``jit(tick)/attention/mla_attention/...``) as the stat ``tf_op`` of the
event's *metadata*, which ``jax.profiler.ProfileData`` does not hand
out, so the metadata is read from the file's wire format (XSpace.planes
= 1; XPlane.name = 2, event_metadata = 4, stat_metadata = 5;
XEventMetadata.name = 2, stats = 5; XStat.metadata_id = 1, str_value =
5, ref_value = 7).  Events, lines and planes come through
``benchmark/trace_reduce.py`` like every other reading of the trace.
Each operation is counted once: one nested inside another of its line
(the body of a ``while``) is the outer one's time.
"""
from __future__ import annotations

import os
import re

from benchmark import trace_reduce

WRAPPED = re.compile(r"^(transpose\()?(jvp\()?([^()]*)\)*$")
FRAMES = ("jit(", "pjit(", "jit_", "while", "body", "cond", "closed_call",
          "checkpoint", "remat", "custom_vjp_call", "custom_jvp_call")


# ---- the wire format: varints and length-delimited fields ----------------
def _varint(buf: bytes, pos: int):
    value = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, pos
        shift += 7


def _fields(buf: bytes) -> dict:
    """``{field number: [value, ...]}`` of one message: ints for
    varints, bytes for length-delimited fields; fixed-width fields are
    skipped."""
    out, pos = {}, 0
    while pos < len(buf):
        tag, pos = _varint(buf, pos)
        number, kind = tag >> 3, tag & 7
        if kind == 0:
            value, pos = _varint(buf, pos)
        elif kind == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif kind in (1, 5):
            pos += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        out.setdefault(number, []).append(value)
    return out


def _text(fields: dict, number: int) -> str:
    values = fields.get(number)
    return values[-1].decode("utf-8", "replace") if values else ""


def _int(fields: dict, number: int) -> int:
    values = fields.get(number)
    return values[-1] if values else 0


def op_scopes(path: str) -> dict:
    """``{operation's event name: its named-scope path}`` of the first
    device plane of ``path`` (an ``.xplane.pb``; a text fixture holds
    no metadata stats)."""
    if not path.endswith(".pb"):
        return {}
    with open(path, "rb") as f:
        space = _fields(f.read())
    for raw in space.get(1, ()):
        plane = _fields(raw)
        if not trace_reduce.DEVICE_PLANE.match(_text(plane, 2)):
            continue
        stat_names = {}
        for entry in plane.get(5, ()):
            meta = _fields(_fields(entry)[2][-1])
            stat_names[_int(meta, 1)] = _text(meta, 2)
        out = {}
        for entry in plane.get(4, ()):
            meta = _fields(_fields(entry)[2][-1])
            for raw_stat in meta.get(5, ()):
                stat = _fields(raw_stat)
                if stat_names.get(_int(stat, 1)) == "tf_op":
                    out[_text(meta, 2)] = _text(stat, 5) or \
                        stat_names.get(_int(stat, 7), "")
        return out
    return {}


def scope_of(op_name: str) -> str:
    """``jit(tick)/jit(main)/attention/mla_attention/dot_general`` ->
    ``attention/mla_attention``: the name scopes below the jit frames,
    nested ones joined by ``/``; ``-`` for an operation under none."""
    parts = [p for p in op_name.split(";")[0].split("/")
             if not p.startswith(FRAMES)]
    scopes = []
    for part in parts[:-1]:  # the last is the primitive itself
        m = WRAPPED.match(part)
        name = m.group(3) if m else part
        if name:
            scopes.append(name + (" (backward)" if m and m.group(1)
                                  else ""))
    return "/".join(scopes) or "-"


def outermost(events, scopes: dict):
    """``[(event, scope)]`` of the events not nested inside an earlier
    one of the same line; an operation without a scope path of its own
    (a ``while``) takes the scope of the first one nested in it."""
    out, edge = [], -1
    for ev in sorted(events, key=lambda e: (e.start_ns, -e.duration_ns)):
        scope = scope_of(scopes[ev.name]) if scopes.get(ev.name) else None
        if ev.start_ns >= edge:
            out.append([ev, scope])
            edge = ev.start_ns + ev.duration_ns
        elif out[-1][1] is None:
            out[-1][1] = scope
    return [(ev, scope or "-") for ev, scope in out]


def read(trace: str) -> dict:
    """``{program: {"runs": n, "ops": [[operation, scope, seconds],
    ...]}}`` of device 0 over the traced span: each outermost operation
    of the line ``XLA Ops`` under the run of the compiled program
    (line ``XLA Modules``) it starts in, summed by operation name and
    scope.  ``trace`` is the profiler's directory or one xplane file;
    a trace in which no program ran on the device gives nothing."""
    path = trace if os.path.isfile(trace) \
        else trace_reduce.find_xplane(trace)
    profile = trace_reduce.load(path)
    device = next((p for p in sorted(profile.planes, key=lambda p: p.name)
                   if trace_reduce.DEVICE_PLANE.match(p.name)), None)
    lines = {ln.name: ln for ln in device.lines} if device else {}
    if "XLA Modules" not in lines:  # no program ran in the traced span
        return {}
    modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                      re.sub(r"\(\d+\)$", "", ev.name))
                     for ev in lines["XLA Modules"].events)
    out = {}
    for _, _, program in modules:
        out.setdefault(program, {"runs": 0, "ops": {}})["runs"] += 1
    outer = outermost(lines[trace_reduce.OPS_LINE].events, op_scopes(path))
    i = 0
    for ev, scope in outer:
        while i < len(modules) and modules[i][1] <= ev.start_ns:
            i += 1
        if i < len(modules) and modules[i][0] <= ev.start_ns:
            ops = out[modules[i][2]]["ops"]
            key = (trace_reduce.op_name(ev), scope)
            ops[key] = ops.get(key, 0.0) + ev.duration_ns * 1e-9
    return {program: {"runs": rec["runs"],
                      "ops": [[name, scope, sec] for (name, scope), sec
                              in sorted(rec["ops"].items(),
                                        key=lambda kv: -kv[1])]}
            for program, rec in out.items()}


def seconds_per_run(program_ops: dict, program: str, scope: str = "",
                    kernel: str = ""):
    """Device seconds per run of the compiled programs whose name holds
    ``program``, of the operations that lie under the named scope
    ``scope`` (anywhere in the path: a kernel's own name nests below
    it) or whose own name holds ``kernel`` (a kernel XLA makes itself
    keeps its name and loses the scope): each operation once.  Nothing
    where the trace has no such program or no such operation."""
    seconds = runs = 0
    for name, rec in (program_ops or {}).items():
        if program not in name:
            continue
        hit = [sec for op, path, sec in rec["ops"]
               if (scope and f"/{scope}/" in f"/{path}/")
               or (kernel and kernel in op)]
        if hit:
            seconds += sum(hit)
            runs += rec["runs"]
    return seconds / runs if runs else None


def by_scope(program_ops: dict) -> dict:
    """``{program: {scope: seconds}}``, heaviest first: PERF.md section
    5's tables."""
    out = {}
    for program, rec in program_ops.items():
        acc = {}
        for _, scope, sec in rec["ops"]:
            acc[scope] = acc.get(scope, 0.0) + sec
        out[program] = dict(sorted(acc.items(), key=lambda kv: -kv[1]))
    return out
