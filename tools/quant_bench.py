"""Weight-only int8 inference bench on the real chip (VERDICT r2 #7).

The reference claims up to 2x int8 inference speedup on VNNI Xeons
(docs/docs/whitepaper.md:192, fig 10).  Round 2 measured the TPU analog
on ResNet-50 and found dynamic int8 ~2x SLOWER (PERF.md) because XLA's
TPU emitter keeps integer convs off the MXU; the predicted TPU win is
``weight_only=True`` on a WEIGHT-bound model.  This script measures it:
Transformer-LM inference, bf16 vs int8-weights-dequantized-on-the-fly,
plus a large-FC MLP as the most weight-bound extreme.

Run (one process per chip):
    python tools/quant_bench.py

Prints a JSON line per config; paste results into PERF.md.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __import__("os").path.join(
    __import__("os").path.dirname(__file__), ".."))

import bigdl_tpu.nn as nn  # noqa: E402
from bigdl_tpu.nn.quantized import quantize  # noqa: E402


def _time_fwd(model, variables, x, steps=20, warmup=2):
    fwd = jax.jit(lambda p, s, a: model.apply(p, s, a, training=False)[0])
    p, s = variables["params"], variables["state"]
    out = None
    for _ in range(warmup):
        out = fwd(p, s, x)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fwd(p, s, x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps


def _param_bytes(tree):
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree)
               if hasattr(a, "dtype"))


def bench_config(name, model, x):
    variables = model.init(jax.random.PRNGKey(0))
    # bf16 reference
    bf = {
        "params": jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 else a, variables["params"]),
        "state": variables["state"],
    }
    t_bf = _time_fwd(model, bf, x)
    qmodel, qvars = quantize(model, variables, weight_only=True)
    t_q = _time_fwd(qmodel, qvars, x)
    # full int8: s8 x s8 -> s32 on the MXU via the Pallas kernel
    # (ops/pallas/int8_matmul.py; XLA integer dot where ineligible)
    dmodel, dvars = quantize(model, variables, weight_only=False)
    t_d = _time_fwd(dmodel, dvars, x)
    from bigdl_tpu.ops.pallas import report as kernel_report

    i8 = kernel_report.report().get("int8_matmul", {})
    rec = {
        "config": name,
        "bf16_ms": round(1e3 * t_bf, 3),
        "weight_only_int8_ms": round(1e3 * t_q, 3),
        "dynamic_int8_ms": round(1e3 * t_d, 3),
        "speedup_weight_only": round(t_bf / t_q, 3),
        "speedup_dynamic": round(t_bf / t_d, 3),
        "int8_matmul_pallas_calls": i8.get("pallas", 0),
        "bf16_param_mb": round(_param_bytes(bf["params"]) / 2 ** 20, 1),
        "int8_param_mb": round(_param_bytes(qvars["params"]) / 2 ** 20, 1),
        "device": str(getattr(jax.devices()[0], "device_kind",
                              jax.devices()[0].platform)),
    }
    print(json.dumps(rec), flush=True)
    return rec


def main():
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu:
        print(json.dumps({"error": "not on TPU", "device": str(dev)}),
              flush=True)

    scale = 1 if on_tpu else 0  # tiny shapes off-chip (smoke only)

    # Transformer LM inference, batch 8 x 512 tokens
    d = 1024 if scale else 64
    model = nn.Transformer(
        vocab_size=32000 if scale else 128, hidden_size=d,
        num_heads=16 if scale else 4, filter_size=4 * d,
        num_layers=12 if scale else 2, dropout=0.0, causal=True)
    b, t = (8, 512) if scale else (2, 16)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, 32000 if scale else 128, (b, t)))
    bench_config("transformer_lm", model, ids)

    # Large-FC MLP: the most weight-bound case (batch 8)
    wdim = 8192 if scale else 64
    mlp = nn.Sequential(
        nn.Linear(wdim, wdim), nn.ReLU(),
        nn.Linear(wdim, wdim), nn.ReLU(),
        nn.Linear(wdim, 1000 if scale else 16))
    xb = jnp.asarray(np.random.RandomState(1).rand(
        8 if scale else 2, wdim), jnp.bfloat16)
    bench_config("large_fc_mlp", mlp, xb)


if __name__ == "__main__":
    main()
