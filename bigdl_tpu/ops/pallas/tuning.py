"""Searched block/tile parameters for the Pallas kernels (ISSUE 13).

The kernels' block pickers (``_pick_bm``, ``_pick_bimg``, ``fit_block``)
are conservative hand estimates — the right *default*, but PERF.md's
evidence says tile choice is the biggest lever left (flash attention's
128 -> 1024 block change alone was 5x).  This module is the seam between
those defaults and a searched table:

* every kernel family declares a finite **candidate space**
  (``candidates``) — the same budget math the hand pickers use, widened
  so the offline sweep can explore past the conservative caps;
* ``tools/autotune.py --sweep`` lowers every candidate through the
  deviceless Mosaic pipeline (the tools/tpu_aot_check.py mechanism:
  compile success + VMEM feasibility are free, no hardware), ranks the
  survivors by their CostTable stamps, and persists a
  :class:`TunedTable` (``tuned/<device_kind>.json``);
* kernel dispatch calls :func:`resolve` — table params when present
  *and still inside the declared candidate space*, hand-picked values
  otherwise, with the decision recorded in ``ops/pallas/report.py`` so
  the graft-lint ``pallas-routing`` rule and the X-ray can audit it.

A table entry that has drifted out of the candidate space (the kernel's
budget math changed, the shape changed) is a **stale** entry: dispatch
falls back to the hand-picked value and records ``stale`` — never a
silent crash, never a silently wrong tile.  ``tools/tpu_aot_check.py
--table`` re-lowers every entry deviceless so staleness fails CI with
the offending shape named.

Env knobs (docs/observability.md):

* ``BIGDL_TPU_TUNED_TABLE=<path>`` — load this table at first kernel
  dispatch (default: the committed ``tuned/<device_kind>.json`` of the
  RUNNING device kind; no table for this device means hand-picked
  params everywhere, recorded as ``source=default`` — never another
  chip's tiles).
* ``BIGDL_TPU_TUNE=0`` — ignore any table entirely (A/B escape hatch).
"""
from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "TunedTable", "candidates", "default_params", "entry_key",
    "get_tuned_table", "resolve", "set_tuned_table", "table_file",
    "table_path", "tuning_enabled",
]

SCHEMA = "bigdl_tpu_tuned_table_v1"

# every tunable kernel family and its parameter names, in the order the
# sweep reports them.  The *_dgrad/_wgrad families are separate entries
# because their working sets differ from the forward's (PERF.md: the
# dgrad VMEM overflow came from reusing the forward estimate).
FAMILIES: Dict[str, Tuple[str, ...]] = {
    "fused_matmul": ("bm",),
    "fused_matmul_dgrad": ("bm",),
    "fused_matmul_wgrad": ("bk",),
    "fused_conv3x3": ("bimg",),
    "fused_conv3x3_dgrad": ("bimg",),
    "flash_attention": ("bq", "bk"),
    "int8_matmul": ("bm",),
}


def entry_key(kernel: str, shape: Sequence[int]) -> str:
    """Stable JSON key: ``<family>/<d0>x<d1>x...``."""
    if kernel not in FAMILIES:
        raise KeyError(f"unknown kernel family '{kernel}' "
                       f"(have: {', '.join(sorted(FAMILIES))})")
    return kernel + "/" + "x".join(str(int(d)) for d in shape)


def parse_key(key: str) -> Tuple[str, Tuple[int, ...]]:
    kernel, _, dims = key.partition("/")
    if kernel not in FAMILIES or not dims:
        raise ValueError(f"malformed tuned-table key '{key}'")
    return kernel, tuple(int(d) for d in dims.split("x"))


# --------------------------------------------------------------------------
# candidate spaces
# --------------------------------------------------------------------------
def candidates(kernel: str, shape: Sequence[int]) -> List[Dict[str, int]]:
    """The declared candidate space for ``kernel`` at ``shape`` — the
    finite set of param dicts the sweep enumerates and the *only*
    values :func:`resolve` will accept from a table (membership here is
    the staleness check, shared with the ``pallas-routing`` rule)."""
    import importlib

    shape = tuple(int(d) for d in shape)
    if kernel in ("fused_matmul", "fused_matmul_dgrad",
                  "fused_matmul_wgrad", "fused_conv3x3",
                  "fused_conv3x3_dgrad"):
        fm = importlib.import_module("bigdl_tpu.ops.pallas.fused_matmul")
        return fm.candidate_params(kernel, shape)
    if kernel == "flash_attention":
        fa = importlib.import_module(
            "bigdl_tpu.ops.pallas.flash_attention")
        return fa.candidate_params(shape)
    if kernel == "int8_matmul":
        i8 = importlib.import_module("bigdl_tpu.ops.pallas.int8_matmul")
        return i8.candidate_params(shape)
    raise KeyError(f"unknown kernel family '{kernel}'")


def default_params(kernel: str, shape: Sequence[int]
                   ) -> Optional[Dict[str, Any]]:
    """What the hand pickers would choose (None values = XLA fallback).
    Used by the sweep to mark the incumbent candidate."""
    import importlib

    shape = tuple(int(d) for d in shape)
    fm = importlib.import_module("bigdl_tpu.ops.pallas.fused_matmul")
    if kernel == "fused_matmul":
        m, k, n = shape
        return {"bm": fm._pick_bm(m, k, n, 2)}
    if kernel == "fused_matmul_dgrad":
        m, k, n = shape
        bm = fm._pick_bm(m, k, n, 2)
        if bm is None:
            return {"bm": None}
        # mirror _dgrad_pallas's scoped-vmem halving (prologue case)
        while bm % 2 == 0 and 4 * bm * (5 * k + 2 * n) > 14 * 1024 * 1024:
            bm //= 2
        return {"bm": bm}
    if kernel == "fused_matmul_wgrad":
        m, k, n = shape
        bk = k
        while bk * n * 4 > 4 * 1024 * 1024 and bk % 2 == 0:
            bk //= 2
        return {"bk": bk}
    if kernel == "fused_conv3x3":
        b, h, w, c, co = shape
        return {"bimg": fm._pick_bimg(b, h, w, c, co, 2)}
    if kernel == "fused_conv3x3_dgrad":
        b, h, w, ci, co = shape
        return {"bimg": fm._pick_bimg_dgrad(b, h, w, ci, co, 2)}
    if kernel == "flash_attention":
        fa = importlib.import_module(
            "bigdl_tpu.ops.pallas.flash_attention")
        b, h, t, s, d = shape
        return {"bq": fa.fit_block(t, 1024),
                "bk": fa.fit_block(s, 1024, multiple=8)}
    if kernel == "int8_matmul":
        i8 = importlib.import_module("bigdl_tpu.ops.pallas.int8_matmul")
        m, k, n = shape
        return {"bm": i8._pick_bm(m, k, n)}
    raise KeyError(f"unknown kernel family '{kernel}'")


# --------------------------------------------------------------------------
# the persisted table
# --------------------------------------------------------------------------
class TunedTable:
    """shape -> params, as persisted by ``tools/autotune.py``.

    ``entries[key] = {"params": {...}, "source": "deviceless"|"chip",
    "cost": {...}, "ranked": [...]}``; ``rejected[key]`` keeps every
    candidate Mosaic refused (with the reason) so the sweep's negative
    results are data, not silence.
    """

    def __init__(self, device_kind: str = "",
                 entries: Optional[Dict[str, dict]] = None,
                 rejected: Optional[Dict[str, list]] = None,
                 path: Optional[str] = None):
        self.device_kind = device_kind
        self.entries: Dict[str, dict] = dict(entries or {})
        self.rejected: Dict[str, list] = {
            k: list(v) for k, v in (rejected or {}).items()}
        self.path = path

    # -- construction ------------------------------------------------------
    @classmethod
    def load(cls, path: str) -> "TunedTable":
        with open(path) as f:
            doc = json.load(f)
        if doc.get("schema") != SCHEMA:
            raise ValueError(
                f"{path}: not a tuned table (schema="
                f"{doc.get('schema')!r}, want {SCHEMA!r})")
        for key in doc.get("entries", {}):
            parse_key(key)  # malformed keys fail loudly at load
        return cls(device_kind=doc.get("device_kind", ""),
                   entries=doc.get("entries", {}),
                   rejected=doc.get("rejected", {}), path=path)

    def persist(self, path: str) -> str:
        doc = {
            "schema": SCHEMA,
            "device_kind": self.device_kind,
            "entries": self.entries,
            "rejected": self.rejected,
        }
        tmp = path + ".tmp"
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, path)  # atomic: a killed sweep can't corrupt
        self.path = path
        return path

    # -- mutation (sweep-side) --------------------------------------------
    def add(self, kernel: str, shape: Sequence[int],
            params: Dict[str, int], source: str = "deviceless",
            cost: Optional[dict] = None,
            ranked: Optional[list] = None) -> None:
        self.entries[entry_key(kernel, shape)] = {
            "params": {k: int(v) for k, v in params.items()},
            "source": source,
            **({"cost": cost} if cost else {}),
            **({"ranked": ranked} if ranked else {}),
        }

    def reject(self, kernel: str, shape: Sequence[int],
               params: Dict[str, int], reason: str) -> None:
        self.rejected.setdefault(entry_key(kernel, shape), []).append(
            {"params": {k: int(v) for k, v in params.items()},
             "reason": reason[:500]})

    # -- lookup (dispatch-side) -------------------------------------------
    def lookup(self, kernel: str, shape: Sequence[int]
               ) -> Optional[Dict[str, int]]:
        ent = self.entries.get(entry_key(kernel, shape))
        return dict(ent["params"]) if ent else None

    def __len__(self) -> int:
        return len(self.entries)


# --------------------------------------------------------------------------
# process-wide table + dispatch resolution
# --------------------------------------------------------------------------
_LOCK = threading.Lock()
_TABLE: Optional[TunedTable] = None
_TABLE_LOADED = False


def tuning_enabled() -> bool:
    return os.environ.get("BIGDL_TPU_TUNE", "") != "0"


def tuned_dir() -> str:
    """The directory of committed tables, ``tuned/`` under the repo
    root."""
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))), "tuned")


def table_file(device_kind: str) -> str:
    """``tuned/<device-kind>.json`` — where the sweep persists, and
    dispatch looks for, the table of one device kind
    (``"TPU v5 lite"`` -> ``tuned/tpu-v5-lite.json``)."""
    return os.path.join(tuned_dir(),
                        device_kind.lower().replace(" ", "-") + ".json")


def table_path(device_kind: Optional[str] = None) -> Optional[str]:
    """Where the live table comes from: ``BIGDL_TPU_TUNED_TABLE`` when
    set, else the committed table of ``device_kind`` (default: the
    running ``jax.devices()[0].device_kind``) when there is one.  A
    deviceless compile names its target kind; nothing ever loads
    another chip's table."""
    env = os.environ.get("BIGDL_TPU_TUNED_TABLE")
    if env:
        return env
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    path = table_file(device_kind)
    return path if os.path.exists(path) else None


def get_tuned_table() -> Optional[TunedTable]:
    """The process-wide table, lazily loaded once; None when there is
    no table for the running device.  A table that exists but cannot be
    read is an error, raised at the first kernel dispatch."""
    global _TABLE, _TABLE_LOADED
    with _LOCK:
        if not _TABLE_LOADED:
            path = table_path()
            _TABLE = TunedTable.load(path) if path else None
            _TABLE_LOADED = True
        return _TABLE


def set_tuned_table(table: Optional[TunedTable]) -> None:
    """Inject/clear the live table (tests, bench A/B arms)."""
    global _TABLE, _TABLE_LOADED
    with _LOCK:
        _TABLE = table
        _TABLE_LOADED = True


def resolve(kernel: str, shape: Sequence[int],
            defaults: Dict[str, Any]) -> Dict[str, Any]:
    """Dispatch-time param resolution — THE injection hook.

    Returns ``defaults`` overridden by the table entry for
    ``(kernel, shape)`` when one exists and its params are still inside
    the declared candidate space.  Every outcome is recorded in
    ``report.py`` (``source`` = ``table`` / ``default`` / ``stale``) so
    silent fallback is impossible.  ``defaults`` may carry ``None``
    values (the hand picker's own XLA-fallback verdict) — those pass
    through untouched on a table miss.
    """
    from bigdl_tpu.ops.pallas import report as _report

    shape = tuple(int(d) for d in shape)
    final = dict(defaults)
    source = "default"
    if tuning_enabled():
        table = get_tuned_table()
        entry = table.lookup(kernel, shape) if table is not None else None
        if entry is not None:
            try:
                ok = entry in candidates(kernel, shape)
            except Exception:
                ok = False
            if ok:
                final.update(entry)
                source = "table"
            else:
                source = "stale"
    _report.record_params(kernel, shape, final, source)
    return final
