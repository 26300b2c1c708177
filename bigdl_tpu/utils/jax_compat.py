"""Backend bridges: the few jax queries whose answer depends on which
backend runs (XLA:CPU in tests, TPU on the chip), flattened to plain
dicts so callers never branch on the backend themselves.

Written against the installed toolchain (jax 0.9.0); shard_map, the
ambient abstract mesh and ``pltpu.CompilerParams`` are used directly
where they are needed.
"""
from __future__ import annotations

from typing import Optional

import jax

__all__ = ["cost_analysis", "device_memory_stats"]


def cost_analysis(stage) -> dict:
    """XLA cost analysis of a ``Lowered`` or ``Compiled`` stage as a
    flat ``{metric: float}`` dict (keys like ``flops``,
    ``bytes accessed``); empty when the backend offers none."""
    ca = stage.cost_analysis() or {}
    return {str(k): float(v) for k, v in ca.items()
            if isinstance(v, (int, float))}


def device_memory_stats(device=None) -> Optional[dict]:
    """``device.memory_stats()`` as a flat ``{key: number}`` dict
    (keys like ``bytes_in_use``, ``peak_bytes_in_use``,
    ``bytes_limit``), or None when the backend offers nothing —
    XLA:CPU returns None, and the HBM ledger (telemetry/programs.py)
    then falls back to ``memory_analysis`` estimates."""
    if device is None:
        device = jax.local_devices()[0]
    fn = getattr(device, "memory_stats", None)
    if fn is None:
        return None
    try:
        stats = fn()
    except Exception:
        return None
    if not isinstance(stats, dict) or not stats:
        return None
    return {str(k): v for k, v in stats.items()
            if isinstance(v, (int, float))}
