"""From a profiler trace (``.xplane.pb``) to busy time, idle gaps and
device time by operation name, through ``jax.profiler.ProfileData``.

A device is a plane named ``/device:TPU:<n>``.  Its line ``XLA Ops``
holds one event per operation that ran on the chip (start and duration
in ns); where a plane has no such line, every line but the step and
module summaries is read.  Busy time is the union of the events'
intervals clipped to the window; idle is the window less busy.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SUMMARY_LINES = ("Steps", "XLA Modules", "Framework Ops", "Source code",
                 "Framework Name Scope")
COLLECTIVES = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute",
    re.IGNORECASE)


def fresh_trace_dir() -> str:
    """``<checkout>/.trace``, emptied: where a traced run lets the
    profiler write."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".trace")
    shutil.rmtree(path, ignore_errors=True)
    return path


def reduce_and_remove(trace_dir: str, span_s: float) -> dict:
    reduced = reduce_trace(find_xplane(trace_dir), span_s=span_s)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return reduced


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData

    if path.endswith((".txt", ".pbtxt")):
        with open(path) as f:
            return ProfileData.from_serialized_xspace(
                ProfileData.text_proto_to_serialized_xspace(f.read()))
    return ProfileData.from_file(path)


TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(ev) -> str:
    """An operation's name: the instruction's own name (the profiler
    gives the whole HLO line: ``%fusion.3 = f32[...] fusion(...)``); a
    custom call also carries its target, so a Pallas kernel reads
    ``<instruction> tpu_custom_call``."""
    head, _, rest = ev.name.partition(" = ")
    target = TARGET.search(rest) if "custom-call(" in rest else None
    head = head.lstrip("%")
    return f"{head} {target.group(1)}" if target else head


def device_events(profile) -> Dict[int, List[Tuple[float, float, str]]]:
    """``{device ordinal: [(start_s, end_s, name), ...]}`` sorted by
    start, of the operations that ran on each device."""
    out: Dict[int, List[Tuple[float, float, str]]] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = list(plane.lines)
        picked = [ln for ln in lines if ln.name == OPS_LINE] or \
            [ln for ln in lines if ln.name not in SUMMARY_LINES]
        events = [(ev.start_ns * 1e-9,
                   (ev.start_ns + ev.duration_ns) * 1e-9, op_name(ev))
                  for ln in picked for ev in ln.events]
        events.sort()
        out[int(m.group(1))] = events
    return out


def module_times(profile, t0: float, t1: float) -> Dict[str, List[float]]:
    """``{program name: [seconds, calls]}`` on device 0, from the line
    ``XLA Modules`` (one event per run of a compiled program; the run
    id in brackets is dropped)."""
    out: Dict[str, List[float]] = {}
    planes = sorted((p for p in profile.planes
                     if DEVICE_PLANE.match(p.name)), key=lambda p: p.name)
    for ln in (planes[0].lines if planes else ()):
        if ln.name != "XLA Modules":
            continue
        for ev in ln.events:
            s, e = ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9
            s, e = max(s, t0), min(e, t1)
            if e <= s:
                continue
            rec = out.setdefault(re.sub(r"\(\d+\)$", "", ev.name), [0.0, 0])
            rec[0] += e - s
            rec[1] += 1
    return out


def window_of(events_by_device) -> Optional[Tuple[float, float]]:
    """First start to last end of the device's operations; nothing
    where the trace holds none."""
    starts = [ev[0][0] for ev in events_by_device.values() if ev]
    ends = [max(e for _, e, _ in ev)
            for ev in events_by_device.values() if ev]
    if not starts:
        return None
    return min(starts), max(ends)


def busy_seconds(events, t0: float, t1: float) -> float:
    """Union of the intervals of ``events`` inside ``[t0, t1]``."""
    busy, cur_s, cur_e = 0.0, None, None
    for s, e, _ in events:
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy


def idle_gaps(events, t0: float, t1: float, top: int = 10):
    """The longest stretches with no operation on the device, each
    named by the operation that ended it (the trace holds no host span
    yet: PERF.md, for the tracing issue)."""
    gaps, edge = [], t0
    for s, e, name in events:
        if s > edge and s <= t1:
            gaps.append((s - edge, name))
        edge = max(edge, e)
    if t1 > edge:
        gaps.append((t1 - edge, "end of window"))
    gaps.sort(reverse=True)
    return [[f"before {name}", sec] for sec, name in gaps[:top]]


def time_by_name(events, t0: float, t1: float) -> Dict[str, List[float]]:
    """``{operation name: [seconds, calls]}`` inside the window.  Nested
    events (a fusion inside a while) each count under their own name."""
    out: Dict[str, List[float]] = {}
    for s, e, name in events:
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        rec = out.setdefault(name, [0.0, 0])
        rec[0] += e - s
        rec[1] += 1
    return out


def seconds_matching(by_name, pattern: str) -> Tuple[float, int]:
    rx = re.compile(pattern)
    hits = [v for k, v in by_name.items() if rx.search(k)]
    return sum(v[0] for v in hits), sum(v[1] for v in hits)


def reduce_trace(path: str, window: Optional[Tuple[float, float]] = None,
                 top: int = 10, span_s: float = 0.0) -> dict:
    """Everything the metric readers need from one trace.  A trace in
    which no operation ran on the device is a reading too: busy 0 and
    idle all of the traced span (``span_s``, by the host's clock), with
    no operation and no gap to name."""
    profile = load(path)
    events = device_events(profile)
    window = window or window_of(events)
    if window is None:
        return {"window_s": span_s, "busy_s": 0.0, "devices": {},
                "by_name": {}, "by_module": {}, "device_ops": [],
                "idle_gaps": []}
    t0, t1 = window
    per_device = {}
    for dev, evs in events.items():
        per_device[dev] = {
            "busy_s": busy_seconds(evs, t0, t1),
            "by_name": time_by_name(evs, t0, t1),
            "gaps": idle_gaps(evs, t0, t1, top),
        }
    busy = sum(d["busy_s"] for d in per_device.values()) / len(per_device)
    first = per_device[min(per_device)]
    ops = sorted(first["by_name"].items(), key=lambda kv: -kv[1][0])
    return {"window_s": t1 - t0, "busy_s": busy, "devices": per_device,
            "by_name": first["by_name"],
            "by_module": module_times(profile, t0, t1),
            "device_ops": [[k, v[0]] for k, v in ops[:top]],
            "idle_gaps": first["gaps"]}
