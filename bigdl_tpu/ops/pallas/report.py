"""Trace-time kernel path registry.

CPU interpret mode can accept a kernel that Mosaic rejects on the real
chip, and a silent XLA fallback then ships unnoticed until a human
profiles.  Every Pallas entry point therefore records which path its
trace-time selection took — and, for every dispatch that did NOT take
Pallas, at which shape — so chip_smoke.py and the bench can assert
``pallas`` was taken on chip, turning a lowering regression into a red
artifact instead of a perf mystery.

Counters are per-process and bump at *trace* time (inside jit they
bump once per compilation, not per step) — exactly the signal wanted:
"was the kernel chosen and did it lower".
"""
from __future__ import annotations

import os
from collections import defaultdict

_COUNTS: dict = defaultdict(lambda: {"pallas": 0, "xla": 0})
# every dispatch that did not take Pallas: (kernel, path, shape)
_FALLBACKS: list = []
# (kernel, shape) -> {"params": {...}, "source": "table"|"default"|"stale"}
# — the tuning-injection decision trail (ops/pallas/tuning.py.resolve);
# "stale" means a table entry existed but fell outside the declared
# candidate space, so dispatch fell back to the hand-picked params
_PARAMS: dict = {}


def force_pallas() -> bool:
    """BIGDL_TPU_FORCE_PALLAS=1: route to the Pallas kernels even when
    the default backend is not TPU — used by tools/tpu_aot_check.py,
    which AOT-compiles every kernel against a DEVICELESS v5e topology
    (the installed libtpu, no chip) so Mosaic rejections are caught
    offline (the failure class interpret-mode tests miss)."""
    return os.environ.get("BIGDL_TPU_FORCE_PALLAS", "") not in ("", "0")


def record(kernel: str, path: str, shape=None) -> None:
    """``path`` is 'pallas', 'xla' (the trace-time fallback), or
    'pallas_local_xla' (a per-shard fallback INSIDE a shard_map body:
    the global shape routed to Pallas but the local row/image count no
    longer tiles).  ``shape`` is the dispatch's problem shape, kept for
    the non-Pallas routes (:func:`fallbacks`)."""
    counts = _COUNTS[kernel]
    counts[path] = counts.get(path, 0) + 1
    if path != "pallas" and shape is not None:
        _FALLBACKS.append((kernel, path, tuple(int(d) for d in shape)))
    # mirror the selection into the X-ray program registry so the
    # kernel shows in tools/xray.py with its route as static config —
    # a steady-state route flip (pallas -> xla) becomes a forensic
    # naming `static route`, not a silent fallback.  Lazy import +
    # never-raise: this runs at trace time inside jit.
    try:
        from bigdl_tpu.telemetry.programs import (
            get_program_registry,
            signature_of,
        )

        get_program_registry().register_compile(
            f"pallas:{kernel}",
            signature_of({}, static={"route": path}),
            expected=(path == "pallas"))
    except Exception:
        pass


def record_params(kernel: str, shape, params: dict, source: str) -> None:
    """Record the block/tile params a dispatch resolved for ``kernel``
    at ``shape`` and where they came from (``table`` — the tuned table;
    ``default`` — the hand picker; ``stale`` — a table entry that fell
    outside the candidate space, i.e. a recorded fallback).  Mirrored
    into the X-ray registry only for non-default sources so a stale
    table shows up in forensics without doubling every compile record.
    """
    _PARAMS[(kernel, tuple(int(d) for d in shape))] = {
        "params": dict(params), "source": source}
    if source == "default":
        return
    try:
        from bigdl_tpu.telemetry.programs import (
            get_program_registry,
            signature_of,
        )

        get_program_registry().register_compile(
            f"pallas:{kernel}:tuning",
            signature_of({}, static={
                "shape": "x".join(str(int(d)) for d in shape),
                "source": source}),
            expected=(source == "table"))
    except Exception:
        pass


def last_params(kernel: str, shape) -> dict:
    """The most recent :func:`record_params` entry for this call site
    (``{}`` if the kernel never resolved params for the shape)."""
    return dict(_PARAMS.get(
        (kernel, tuple(int(d) for d in shape)), {}))


def params_report() -> dict:
    """{(kernel, shape): {'params': ..., 'source': ...}} snapshots."""
    return {k: dict(v) for k, v in _PARAMS.items()}


def report() -> dict:
    """{kernel: {'pallas': n, 'xla': n}} since process start."""
    return {k: dict(v) for k, v in _COUNTS.items()}


def fallbacks() -> list:
    """[(kernel, path, shape)] of every dispatch since process start
    that routed to 'xla' or 'pallas_local_xla'."""
    return list(_FALLBACKS)


def reset() -> None:
    _COUNTS.clear()
    _PARAMS.clear()
    _FALLBACKS.clear()
