"""Per-shape microbench of the fused conv+BN Pallas kernels on chip.

Times fused_matmul_bn (fwd and fwd+bwd) against the equivalent XLA
sequence for every 1x1-conv shape in ResNet-50 at batch 256 — the
kernel-level ground truth behind the bench.py step-level number, and
the fast iteration loop for block-size tuning (chip time is scarce).

    python tools/fused_bench.py [--batch 256] [--bwd]

One JSON line per shape.  On CPU it smoke-runs tiny shapes only.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bigdl_tpu.ops.pallas.fused_matmul import fused_matmul_bn  # noqa: E402

# (H*W at this stage, K, N, prologue?) for ResNet-50's 1x1 convs
# (conv1/conv3 of each stage + the four projections)
SHAPES = [
    ("s1_conv1", 56 * 56, 64, 64, False),
    ("s1_conv3", 56 * 56, 64, 256, True),
    ("s1_proj", 56 * 56, 64, 256, False),
    ("s1b_conv1", 56 * 56, 256, 64, False),
    ("s2_conv1", 56 * 56, 256, 128, False),
    ("s2_conv3", 28 * 28, 128, 512, True),
    ("s2_proj", 28 * 28, 256, 512, False),
    ("s2b_conv1", 28 * 28, 512, 128, False),
    ("s3_conv1", 28 * 28, 512, 256, False),
    ("s3_conv3", 14 * 14, 256, 1024, True),
    ("s3_proj", 14 * 14, 512, 1024, False),
    ("s3b_conv1", 14 * 14, 1024, 256, False),
    ("s4_conv1", 14 * 14, 1024, 512, False),
    ("s4_conv3", 7 * 7, 512, 2048, True),
    ("s4_proj", 7 * 7, 1024, 2048, False),
    ("s4b_conv1", 7 * 7, 2048, 512, False),
]


def _sync(x):
    return float(jnp.sum(x).astype(jnp.float32))


def timed_with_backend(kernel_name, f, args, steps):
    """Time f and report which path its trace took — a silent XLA
    fallback must not be labelled as the fused kernel's time."""
    from bigdl_tpu.ops.pallas import report as kreport

    before = kreport.report().get(kernel_name, {}).get("pallas", 0)
    dt = time_fn(f, args, steps)
    after = kreport.report().get(kernel_name, {}).get("pallas", 0)
    return dt, ("pallas" if after > before else "xla-fallback")


def time_fn(f, args, steps=30, warmup=3):
    out = None
    for _ in range(warmup):
        out = f(*args)
    _sync(out[0] if isinstance(out, (tuple, list)) else out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = f(*args)
    _sync(out[0] if isinstance(out, (tuple, list)) else out)
    return (time.perf_counter() - t0) / steps


def xla_ref(x, w, ps, pb, prologue):
    if prologue:
        u = jnp.maximum(x.astype(jnp.float32) * ps + pb, 0).astype(x.dtype)
    else:
        u = x
    y = jax.lax.dot_general(u, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    yb = y.astype(x.dtype)
    return yb, jnp.sum(y, 0), jnp.sum(y * y, 0)


# stride-1 conv2 shapes per stage: (H, C) with C->C 3x3
CONV3_SHAPES = [
    ("s1_conv2", 56, 64),
    ("s2_conv2", 28, 128),
    ("s3_conv2", 14, 256),
    ("s4_conv2", 7, 512),
]


def bench_conv3(args, on_tpu):
    from bigdl_tpu.ops.pallas.fused_matmul import (_conv3_xla,
                                                   fused_conv3x3_bn)

    shapes = CONV3_SHAPES if on_tpu else CONV3_SHAPES[:1]
    batch = args.batch if on_tpu else 2
    for name, hw, c in shapes:
        h = hw if on_tpu else 6
        x = jax.random.normal(jax.random.PRNGKey(0), (batch, h, h, c),
                              jnp.bfloat16)
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, c, c),
                              jnp.bfloat16) * 0.05
        ps = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (c,))) + 0.5
        pb = jax.random.normal(jax.random.PRNGKey(3), (c,)) * 0.1

        fused = jax.jit(lambda a, b: fused_conv3x3_bn(a, b, ps, pb))
        ref = jax.jit(lambda a, b: _conv3_xla(a, b, ps, pb, True, True))
        fwd_fused, backend = timed_with_backend(
            "fused_conv3x3", fused, (x, w), args.steps)
        rec = {"shape": name, "batch": batch, "h": h, "c": c,
               "backend": backend,
               "fwd_fused_ms": round(1e3 * fwd_fused, 3),
               "fwd_xla_ms": round(1e3 * time_fn(ref, (x, w),
                                                 args.steps), 3)}
        print(json.dumps(rec), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--bwd", action="store_true",
                    help="also time fwd+bwd (value_and_grad)")
    ap.add_argument("--conv3", action="store_true",
                    help="also bench the fused 3x3 conv kernel")
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()

    on_tpu = jax.devices()[0].platform == "tpu"
    if args.conv3:
        bench_conv3(args, on_tpu)
    shapes = SHAPES if on_tpu else SHAPES[:1]
    batch = args.batch if on_tpu else 2

    for name, hw, k, n, prologue in shapes:
        m = batch * hw
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (m, k), jnp.bfloat16)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n),
                              jnp.bfloat16) * 0.05
        ps = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (k,))) + 0.5
        pb = jax.random.normal(jax.random.PRNGKey(3), (k,)) * 0.1

        fused = jax.jit(lambda a, b: fused_matmul_bn(
            a, b, ps if prologue else None, pb if prologue else None,
            relu=True))
        ref = jax.jit(lambda a, b: xla_ref(a, b, ps, pb, prologue))

        fwd_fused, backend = timed_with_backend(
            "fused_matmul", fused, (x, w), args.steps)
        rec = {"shape": name, "m": m, "k": k, "n": n,
               "prologue": prologue, "backend": backend,
               "fwd_fused_ms": round(1e3 * fwd_fused, 3),
               "fwd_xla_ms": round(1e3 * time_fn(ref, (x, w),
                                                 args.steps), 3)}
        if args.bwd:
            def loss_fused(a, b):
                y, s, q = fused_matmul_bn(
                    a, b, ps if prologue else None,
                    pb if prologue else None, relu=True)
                return (jnp.sum(y.astype(jnp.float32)) + jnp.sum(s)
                        + 1e-6 * jnp.sum(q))

            def loss_ref(a, b):
                y, s, q = xla_ref(a, b, ps, pb, prologue)
                return (jnp.sum(y.astype(jnp.float32)) + jnp.sum(s)
                        + 1e-6 * jnp.sum(q))

            gf = jax.jit(jax.grad(loss_fused, argnums=(0, 1)))
            gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1)))
            rec["bwd_fused_ms"] = round(1e3 * time_fn(gf, (x, w),
                                                      args.steps), 3)
            rec["bwd_xla_ms"] = round(1e3 * time_fn(gr, (x, w),
                                                    args.steps), 3)
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
