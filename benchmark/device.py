"""What the benchmark asks of jax itself: the device, its peaks, the
compile cache, compile requests and the runtime's memory peak."""
from __future__ import annotations

import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def find_device(chips: int) -> dict:
    """The accelerator as jax reports it; no TPU, a kind the peaks table
    lacks, or another count than the cell asks for ends the run."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: jax found platform "
                         f"{devs[0].platform!r}, not a TPU")
    if len(devs) != chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chip(s), "
                         f"jax reports {len(devs)}")
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    if devs[0].device_kind not in peaks:
        raise SystemExit(f"benchmark: no peaks for device kind "
                         f"{devs[0].device_kind!r} in benchmark/peaks.json")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "peaks": peaks[devs[0].device_kind]}


def enable_cache() -> str:
    import jax
    from bigdl_tpu.utils.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    # the small init programs compile in under jax's 1 s threshold and
    # would otherwise be compiled again by every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCount:
    """Counts backend compile requests (a cache hit counts too)."""

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs


def memory_peak_bytes() -> int:
    """Peak on the fullest chip, as the runtime counts it: the arrays in
    use at their peak plus the region the runtime reserves for programs'
    temporaries at its peak (this runtime keeps the two apart, and
    ``peak_bytes_in_use`` alone leaves every temporary out: PERF.md)."""
    import jax

    def peak(d):
        st = d.memory_stats() or {}
        return int(st.get("peak_bytes_in_use", 0)) + int(
            st.get("peak_bytes_reserved", 0))

    return max(peak(d) for d in jax.devices())


def device_only():
    """Profiler options that leave the host's Python calls out: the
    reduction reads the device planes only, and a trace with every
    Python call of the input pipeline takes a minute to write."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    return options
