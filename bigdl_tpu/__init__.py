"""bigdl_tpu — a TPU-native distributed deep-learning framework.

Provides the capabilities of BigDL 1.x (Torch-style layer zoo, Keras API,
distributed synchronous SGD with a sharded optimizer, data pipeline, model
interop, quantized inference) re-designed for TPU:

* the tensor core is ``jax.numpy`` on device arrays (reference:
  spark/dl/.../bigdl/tensor, 13.6k LoC of strided JVM tensors — collapsed
  to XLA, see SURVEY.md §2.1);
* modules are pure functions over parameter pytrees (init/apply), with a
  Torch-style stateful facade for API parity with
  ``AbstractModule.forward/backward`` (reference nn/abstractnn/AbstractModule.scala);
* the distributed engine is pjit/GSPMD over a ``jax.sharding.Mesh`` —
  XLA collectives over ICI replace the Spark BlockManager all-reduce
  (reference parameters/AllReduceParameter.scala);
* Pallas kernels cover what XLA fusion does not (fused/ring attention,
  int8 matmul) where the reference called into MKL-DNN/BigQuant JNI.
"""
import time as _time

_T_IMPORT = _time.perf_counter()  # the ``setup/import`` span starts here

from bigdl_tpu.version import __version__

from bigdl_tpu.utils.logger import init_logging as _init_logging

_init_logging()  # canonical training log lines visible by default

from bigdl_tpu import utils  # noqa: F401  (Engine, Table, config)
from bigdl_tpu import nn  # noqa: F401
from bigdl_tpu import optim  # noqa: F401
from bigdl_tpu import dataset  # noqa: F401
from bigdl_tpu import parallel  # noqa: F401
from bigdl_tpu import serving  # noqa: F401  (bucketed serving engine)
from bigdl_tpu import telemetry  # noqa: F401  (span tracing + watchdogs)

from bigdl_tpu.telemetry.tracer import get_tracer as _get_tracer

_get_tracer().add_setup_span("setup/import", _T_IMPORT, _time.perf_counter())
