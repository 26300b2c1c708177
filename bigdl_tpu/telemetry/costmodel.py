"""Cost/MFU accounting for compiled programs.

Every compiled program (train step, serving forward, decode tick) is
stamped at warmup with XLA's ``cost_analysis()`` / ``memory_analysis()``
flops + bytes.  From the stamp we derive model-flops-utilization (MFU)
and bytes/s per step, surfaced into ``Metrics`` / ``log_line()`` /
JSONL, and persist a per-program cost table that ``tools/autotune.py``
can later consult for block/tile selection.

Peak FLOP/s is resolved per device kind from the one table below
(override with ``BIGDL_TPU_PEAK_FLOPS``).  A device kind that is not in
the table is an error, not a default, and a host-CPU backend has no
peak at all: MFU is a device metric and a CPU run reports none.
Disable the whole subsystem with ``BIGDL_TPU_COST_DISABLE=1``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Optional

from ..utils import jax_compat

# per-chip peak dense (bf16) FLOP/s from the public TPU specs, matched
# as substrings of the lowercased device_kind — the one peaks table of
# the repo (bench.py and the tools read it through
# peak_flops_per_device)
_PEAK_BY_KIND = (
    ("v6 lite", 918e12),
    ("v6e", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def cost_accounting_enabled() -> bool:
    """``BIGDL_TPU_COST_DISABLE=1`` turns all stamping into no-ops."""
    return os.environ.get("BIGDL_TPU_COST_DISABLE", "0") != "1"


def peak_flops_per_device(device=None) -> float:
    """Peak dense FLOP/s of one device (``BIGDL_TPU_PEAK_FLOPS`` wins).
    Raises ``LookupError`` for a device kind the table does not hold:
    an assumed peak would print a made-up utilization."""
    env = os.environ.get("BIGDL_TPU_PEAK_FLOPS")
    if env:
        return float(env)
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = str(device.device_kind)
    for key, peak in _PEAK_BY_KIND:
        if key in kind.lower():
            return peak
    raise LookupError(
        f"no peak FLOP/s on record for device_kind {kind!r}: add it to "
        "telemetry/costmodel._PEAK_BY_KIND or set BIGDL_TPU_PEAK_FLOPS")


def mfu(flops_per_step: float, step_time_s: float, *, n_devices: int = 1,
        peak: Optional[float] = None) -> Optional[float]:
    """Model-flops-utilization of one step across ``n_devices``; None
    on a host-CPU backend, which has no peak to be utilized."""
    if not flops_per_step or not step_time_s or step_time_s <= 0:
        return 0.0
    if peak is None:
        import jax

        if jax.default_backend() == "cpu":
            return None
        peak = peak_flops_per_device()
    return flops_per_step / (step_time_s * peak * max(1, n_devices))


@dataclasses.dataclass
class ProgramCost:
    """One compiled program's cost stamp (flops + bytes at warmup)."""

    name: str
    flops: float = 0.0
    bytes_accessed: float = 0.0
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    generated_code_bytes: int = 0
    n_devices: int = 1
    stamped_unix: float = 0.0

    def mfu(self, step_time_s: float,
            peak: Optional[float] = None) -> Optional[float]:
        return mfu(self.flops, step_time_s, n_devices=self.n_devices,
                   peak=peak)

    def bytes_per_s(self, step_time_s: float) -> float:
        if not self.bytes_accessed or step_time_s <= 0:
            return 0.0
        return self.bytes_accessed / step_time_s

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, blob: dict) -> "ProgramCost":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in blob.items() if k in fields})


def program_cost(name: str, *, lowered=None, compiled=None,
                 n_devices: int = 1) -> ProgramCost:
    """Extract a :class:`ProgramCost` from a Lowered and/or Compiled.

    Prefers the lowered-stage analysis (no backend compile); memory
    numbers only exist on the compiled stage.
    """
    ca = jax_compat.cost_analysis(lowered) if lowered is not None else {}
    if not ca and compiled is not None:
        ca = jax_compat.cost_analysis(compiled)
    mem = compiled.memory_analysis() if compiled is not None else None

    def _m(attr):
        return int(getattr(mem, attr, 0) or 0)

    return ProgramCost(
        name=name,
        flops=float(ca.get("flops", 0.0) or 0.0),
        bytes_accessed=float(ca.get("bytes accessed", 0.0) or 0.0),
        argument_bytes=_m("argument_size_in_bytes"),
        output_bytes=_m("output_size_in_bytes"),
        temp_bytes=_m("temp_size_in_bytes"),
        generated_code_bytes=_m("generated_code_size_in_bytes"),
        n_devices=max(1, int(n_devices)),
        stamped_unix=time.time(),
    )


def stamp_jitted(name: str, jitted, *args, table: "CostTable" = None,
                 n_devices: int = 1, **kwargs) -> Optional[ProgramCost]:
    """Lower ``jitted`` (trace only, no backend compile) and stamp it.

    Returns the stamp, or None when cost accounting is disabled or the
    lowering itself fails (never propagates — accounting is optional).
    """
    if not cost_accounting_enabled():
        return None
    try:
        lowered = jitted.lower(*args, **kwargs)
    except Exception:
        return None
    cost = program_cost(name, lowered=lowered, n_devices=n_devices)
    (table if table is not None else get_cost_table()).add(cost)
    return cost


def stamp_compiled(name: str, compiled, *, lowered=None,
                   table: "CostTable" = None,
                   n_devices: int = 1) -> Optional[ProgramCost]:
    """Stamp an already-compiled program (flops + memory numbers)."""
    if not cost_accounting_enabled():
        return None
    cost = program_cost(name, lowered=lowered, compiled=compiled,
                        n_devices=n_devices)
    (table if table is not None else get_cost_table()).add(cost)
    return cost


def autotune_stamp(kernel: str, shape, params: dict, *, lowered=None,
                   compiled=None, table: "CostTable" = None,
                   n_devices: int = 1) -> ProgramCost:
    """Stamp one autotune candidate compile under a canonical name.

    ``tools/autotune.py`` lowers every block/tile candidate through the
    deviceless Mosaic pipeline and ranks the survivors by these stamps;
    naming them ``autotune:<kernel>/<dims>:<k=v,...>`` puts the sweep's
    ranking inputs in the same :class:`CostTable` namespace the step
    programs use, so a persisted cost table carries the evidence behind
    a tuned entry.  Always returns the stamp (the sweep needs it even
    when accounting is globally disabled); only the table insertion
    honors ``BIGDL_TPU_COST_DISABLE``.
    """
    dims = "x".join(str(int(d)) for d in shape)
    kv = ",".join(f"{k}={int(v)}" for k, v in sorted(params.items()))
    cost = program_cost(f"autotune:{kernel}/{dims}:{kv}",
                        lowered=lowered, compiled=compiled,
                        n_devices=n_devices)
    if cost_accounting_enabled():
        (table if table is not None else get_cost_table()).add(cost)
    return cost


class CostTable:
    """Thread-safe per-program cost registry, persistable as JSON."""

    def __init__(self):
        self._lock = threading.Lock()
        self._programs: dict = {}

    def add(self, cost: ProgramCost) -> None:
        with self._lock:
            self._programs[cost.name] = cost

    def get(self, name: str) -> Optional[ProgramCost]:
        with self._lock:
            return self._programs.get(name)

    def programs(self) -> dict:
        with self._lock:
            return dict(self._programs)

    def records(self) -> list:
        with self._lock:
            return [c.as_dict() for _, c in sorted(self._programs.items())]

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._programs)

    def persist(self, path: str) -> str:
        """Atomic write-then-rename of the table (autotune input)."""
        blob = {"record": "cost_table", "unix_time": time.time(),
                "programs": self.records()}
        tmp = f"{path}.{os.getpid()}.part"
        with open(tmp, "w") as f:
            json.dump(blob, f, indent=2, sort_keys=True)
        os.replace(tmp, path)
        return path

    @classmethod
    def load(cls, path: str) -> "CostTable":
        table = cls()
        try:
            with open(path) as f:
                blob = json.load(f)
        except (OSError, ValueError):
            return table
        for rec in blob.get("programs", []):
            try:
                table.add(ProgramCost.from_dict(rec))
            except (TypeError, ValueError):
                continue
        return table


_GLOBAL_TABLE = CostTable()


def get_cost_table() -> CostTable:
    """The process-wide cost table (shipped by TelemetryShipper)."""
    return _GLOBAL_TABLE
