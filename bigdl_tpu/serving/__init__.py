"""High-throughput serving subsystem (docs/serving.md).

The serving-side mirror of the async training engine: shape-bucketed
AOT-compiled forwards, continuous micro-batching with pipelined
dispatch, admission control, and tail-latency metrics.
``optim.PredictionService`` remains as a thin back-compat facade over
:class:`ServingEngine`.
"""

from bigdl_tpu.serving.bucketing import Bucket, BucketGrid
from bigdl_tpu.serving.decode import DecodeEngine
from bigdl_tpu.serving.decode_programs import (
    build_draft_propose,
    build_paged_tick,
    build_paged_write_slot,
    build_prefill,
    build_prefill_chunk,
    build_sampling_tick,
    build_spec_verify,
    build_write_slot,
    deviceless_decode_check,
    sample_logits,
)
from bigdl_tpu.serving.paging import OutOfPagesError, PageAllocator
from bigdl_tpu.serving.engine import (
    DeadlineExceededError,
    EngineClosedError,
    QueueFullError,
    ServingEngine,
    ServingError,
    ServingFuture,
)
from bigdl_tpu.serving.metrics import ServingMetrics
from bigdl_tpu.serving.warmup import build_forward, deviceless_bucket_check

__all__ = [
    "Bucket",
    "BucketGrid",
    "DecodeEngine",
    "ServingEngine",
    "ServingError",
    "ServingFuture",
    "ServingMetrics",
    "QueueFullError",
    "DeadlineExceededError",
    "EngineClosedError",
    "OutOfPagesError",
    "PageAllocator",
    "build_draft_propose",
    "build_forward",
    "build_paged_tick",
    "build_paged_write_slot",
    "build_prefill",
    "build_prefill_chunk",
    "build_sampling_tick",
    "build_spec_verify",
    "build_write_slot",
    "deviceless_bucket_check",
    "deviceless_decode_check",
    "sample_logits",
]
