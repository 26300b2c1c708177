"""Offline elastic-recovery check — no chip needed.

Compiles the two programs an elastic recovery dispatches first through
the REAL XLA:TPU compiler against a deviceless topology (the
tools/tpu_aot_check.py machinery):

* the **resharded-restore step** — the identity program
  :func:`bigdl_tpu.distributed.checkpoint.build_reshard_step` jits to
  move a checkpoint written on one mesh layout (dp=4) onto a different
  dp x tp layout (2x2) and a shrunken dp=2 layout over the same chips;
* the **compressed-allreduce train step** — the first step a re-formed
  generation runs when ``BIGDL_TPU_GRAD_COMPRESS`` is set.

A recovery window is the worst possible moment to discover a program
does not lower: the mesh was just re-formed, the job is down until the
step compiles.  Exit 0 = every checked program compiled for TPU.

    python tools/elastic_aot_check.py
    python tools/elastic_aot_check.py --topology v5e:2x2
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# deviceless compiles touch no hardware: the process backend stays the
# CPU and libtpu does not ask a cloud metadata server (same
# environment as tools/tpu_aot_check.py)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

t0 = time.perf_counter()


def mark(msg):
    print(f"[{time.perf_counter() - t0:7.1f}s] {msg}", flush=True)


def _check(tag, thunk):
    try:
        thunk()
        mark(f"{tag}: OK")
        return 0
    except Exception as e:
        mark(f"{tag}: FAIL {str(e)[:200]}")
        return 1


def main(argv=None):
    p = argparse.ArgumentParser("elastic_aot_check")
    p.add_argument("--topology", default="v5e:2x2",
                   help="deviceless target (4 chips: enough for a "
                        "4 -> 2x2 reshard)")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    import bigdl_tpu.nn as nn
    from bigdl_tpu import models
    from bigdl_tpu.distributed.checkpoint import build_reshard_step
    from bigdl_tpu.distributed.compression import (
        build_compressed_dp_train_step)
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.parallel.mesh import (MeshConfig, make_mesh,
                                         shard_leading_dim)

    topo = topologies.get_topology_desc(
        topology_name=args.topology, platform="tpu",
        chips_per_host_bounds=[2, 2, 1])
    devices = list(topo.devices)
    mark(f"deviceless target {args.topology}: {len(devices)} chips")
    mesh41 = make_mesh(MeshConfig(data=len(devices)), devices)
    mesh22 = make_mesh(MeshConfig(data=len(devices) // 2, model=2),
                       devices)
    mesh2 = make_mesh(MeshConfig(data=len(devices) // 2),
                      devices[: len(devices) // 2])

    model = models.LeNet5()
    var = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    params = var["params"]
    src = shard_leading_dim(mesh41, params)

    failures = 0
    # the jitted reshard step only relayouts across the SAME device set
    # (shrinking to fewer chips goes through the file-based restore,
    # which is host-side); 4 -> 2x2 is the on-device relayout case
    step = build_reshard_step(src, shard_leading_dim(mesh22, params))
    failures += _check("reshard dp=4 -> dp=2 x tp=2",
                       lambda: step.lower(params).compile())

    from bigdl_tpu.analysis.targets import _step_args

    methods = {"__all__": SGD(1e-2)}
    sargs, _n = _step_args(model, methods, (8, 28, 28, 1), "float32",
                           (8,))
    # the first program each re-formed generation compiles: the
    # compressed step at the old world size AND at the shrunken one
    for tag, m in (("compressed bf16-wire train step (dp=4)", mesh41),
                   ("compressed bf16-wire train step (dp=2, shrunken "
                    "generation)", mesh2)):
        cstep, _ = build_compressed_dp_train_step(
            model, nn.ClassNLLCriterion(logits=True), methods, m,
            wire_dtype="bf16")
        failures += _check(
            tag, lambda s=cstep: s.lower(*sargs).compile())

    mark("ALL PROGRAMS LOWERED" if failures == 0
         else f"{failures} FAILURES")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
