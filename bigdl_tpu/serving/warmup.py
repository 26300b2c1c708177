"""AOT warmup for the serving engine's bucket grid.

Every declared bucket is compiled ahead of the first request via the
``jit(...).lower(...).compile()`` AOT path, so steady-state traffic
never pays a compile on the request path and the engine's recompile
counter equals the declared bucket count right after startup — any
later growth is a visible bucket miss, never a silent stall.

The same lowering path runs devicelessly against a TPU topology (the
``tools/tpu_aot_check.py`` machinery): :func:`deviceless_bucket_check`
compiles the grid through the real XLA:TPU pipeline with no chip, so
a serving rollout can prove its whole grid lowers before any chip time
is spent (``tools/serving_aot_check.py``).
"""
from __future__ import annotations

from typing import Callable, Optional

from bigdl_tpu.serving.bucketing import Bucket, BucketGrid


def build_forward(model) -> Callable:
    """The eval-mode forward the engine compiles per bucket — kept as a
    named top-level builder so graft-lint's ``serving_forward`` target
    audits exactly what serves (analysis/targets.py)."""

    def fwd(params, state, x):
        out, _ = model.apply(params, state, x, training=False)
        return out

    return fwd


def bucket_struct(bucket: Bucket, dtype):
    """ShapeDtypeStruct for a bucket's padded input batch."""
    import jax

    return jax.ShapeDtypeStruct((bucket.batch,) + tuple(bucket.dims), dtype)


def compile_bucket(jit_fwd, params, state, bucket: Bucket, dtype):
    """AOT-compile one bucket's forward; returns the executable."""
    return jit_fwd.lower(params, state,
                         bucket_struct(bucket, dtype)).compile()


def deviceless_bucket_check(model, grid: BucketGrid, dtype=None,
                            topology: str = "v5e:1x1",
                            log: Optional[Callable[[str], None]] = None
                            ) -> int:
    """Compile every declared bucket against a deviceless TPU topology
    (no chip — the offline Mosaic-gate machinery).  Returns
    the failure count; ``log`` receives one line per bucket."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    dtype = dtype or jnp.float32
    log = log or (lambda s: None)
    topo = topologies.get_topology_desc(
        topology_name=topology, platform="tpu",
        chips_per_host_bounds=[1, 1, 1])
    mesh = Mesh(np.array(topo.devices), ("d",))
    sh = NamedSharding(mesh, P())
    var = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    jit_fwd = jax.jit(build_forward(model), in_shardings=sh,
                      out_shardings=sh)
    failures = 0
    for bucket in grid.declared_buckets():
        tag = f"bucket {bucket.batch}x{'x'.join(map(str, bucket.dims))}"
        try:
            compile_bucket(jit_fwd, var["params"], var["state"], bucket,
                           dtype)
            log(f"{tag}: OK")
        except Exception as e:
            failures += 1
            log(f"{tag}: FAIL {str(e)[:200]}")
    return failures
