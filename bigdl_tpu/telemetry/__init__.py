"""Unified telemetry: span tracing + exporters + anomaly watchdogs
(docs/observability.md; the TPU-era grow-out of the reference's
per-step ``Metrics`` printouts, optim/Metrics.scala:31-123).

One process-global, thread-safe span timeline feeds three consumers:

* ``ui.perfetto.dev`` via the Chrome ``trace_event`` exporter,
* TensorBoard via the from-scratch ``visualization`` writer,
* the canonical newline-JSON metrics dump ``bench.py`` artifacts use,

plus a :class:`Watchdog` that flags anomalies (step-time spikes,
steady-state recompiles, prefetch starvation, queue saturation,
deferred-NaN drains) as they happen.

Instrumentation is strictly host-side: the compiled programs are
byte-identical with tracing on or off (graft-lint target
``telemetry_step_parity`` enforces this), and a disabled tracer costs
one attribute check per record site.

The Program X-ray (telemetry/programs.py) extends the plane to the
device/compiler side: a process-wide registry of compiled programs
with signature fingerprints, recompile forensics that name the
changed axis, and an HBM ledger with headroom warnings
(``tools/xray.py`` renders the table).
"""
from bigdl_tpu.telemetry.cluster import (
    ClusterAggregator,
    FederatedWatchdog,
    TelemetryShipper,
)
from bigdl_tpu.telemetry.debug_server import (
    DebugServer,
    attach_engine,
    bound_address,
    debug_port,
    get_debug_server,
    prometheus_text,
)
from bigdl_tpu.telemetry.flightrecorder import (
    FlightRecorder,
    flight_enabled,
    get_flight_recorder,
)
from bigdl_tpu.telemetry.costmodel import (
    CostTable,
    ProgramCost,
    get_cost_table,
    mfu,
    peak_flops_per_device,
)
from bigdl_tpu.telemetry.export import (
    chrome_trace,
    metrics_record,
    read_metrics_jsonl,
    write_chrome_trace,
    write_metrics_jsonl,
    write_scalars,
)
from bigdl_tpu.telemetry.numerics import (
    NUMERICS_EVENT,
    NUMERICS_SAMPLE,
    PROVENANCE_EVENT,
    RECOVERY_EVENT,
    NumericsMonitor,
    NumericsSpec,
    nan_provenance,
    subsample_tree,
)
from bigdl_tpu.telemetry.requests import (
    Attribution,
    ExemplarReservoir,
    RequestLedger,
    assemble_request_trees,
)
from bigdl_tpu.telemetry.workload import (
    WorkloadRecorder,
    load_workload,
)
from bigdl_tpu.telemetry.programs import (
    HbmLedger,
    ProgramRecord,
    ProgramRegistry,
    ProgramSignature,
    diff_signatures,
    get_hbm_ledger,
    get_program_registry,
    signature_of,
    xray_enabled,
)
from bigdl_tpu.telemetry.tracer import (
    CAT_DATA,
    CAT_DECODE,
    CAT_HOST,
    CAT_SERVE,
    CAT_TRAIN,
    Span,
    Tracer,
    correlate,
    disable,
    enable,
    enabled,
    get_correlation,
    get_tracer,
    set_correlation,
)
from bigdl_tpu.telemetry.watchdog import Watchdog

__all__ = [
    "Span", "Tracer", "Watchdog",
    "TelemetryShipper", "ClusterAggregator", "FederatedWatchdog",
    "DebugServer", "get_debug_server", "attach_engine",
    "bound_address", "debug_port", "prometheus_text",
    "FlightRecorder", "get_flight_recorder", "flight_enabled",
    "CostTable", "ProgramCost", "get_cost_table", "mfu",
    "peak_flops_per_device",
    "NumericsMonitor", "NumericsSpec", "nan_provenance",
    "subsample_tree", "NUMERICS_SAMPLE", "NUMERICS_EVENT",
    "PROVENANCE_EVENT", "RECOVERY_EVENT",
    "ProgramRegistry", "ProgramRecord", "ProgramSignature",
    "HbmLedger", "signature_of", "diff_signatures",
    "get_program_registry", "get_hbm_ledger", "xray_enabled",
    "get_tracer", "enable", "disable", "enabled",
    "correlate", "set_correlation", "get_correlation",
    "chrome_trace", "write_chrome_trace", "write_scalars",
    "metrics_record", "write_metrics_jsonl", "read_metrics_jsonl",
    "RequestLedger", "Attribution", "ExemplarReservoir",
    "assemble_request_trees",
    "WorkloadRecorder", "load_workload",
    "CAT_TRAIN", "CAT_DATA", "CAT_SERVE", "CAT_DECODE", "CAT_HOST",
]
