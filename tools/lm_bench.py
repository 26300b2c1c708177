"""Transformer-LM training throughput on one chip: the second headline
bench next to bench.py's ResNet-50 (reference analog:
models/utils/DistriOptimizerPerf over a sequence config).

Exercises the flash-attention kernel on its real lowering path (the
model auto-selects it for mask-free causal attention) and reports
tokens/sec + MFU from XLA's own cost analysis.

    python tools/lm_bench.py                     # GPT-2-small-ish
    python tools/lm_bench.py --seqLen 4096 -b 4  # long-context
"""
import argparse
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


# the bench's canonical configuration — single source for the argparse
# defaults, build_lm, and tools/tpu_aot_check.py --lm-step
LM_DEFAULTS = dict(batchSize=8, seqLen=2048, vocabSize=32000,
                   hiddenSize=768, numHeads=12, filterSize=3072,
                   numLayers=12)


def build_lm(vocab_size: int = LM_DEFAULTS["vocabSize"],
             hidden_size: int = LM_DEFAULTS["hiddenSize"],
             num_heads: int = LM_DEFAULTS["numHeads"],
             filter_size: int = LM_DEFAULTS["filterSize"],
             num_layers: int = LM_DEFAULTS["numLayers"]):
    """The bench's canonical Transformer-LM (GPT2-small-ish) + loss +
    optimizer — shared with tools/tpu_aot_check.py --lm-step so the
    offline compile cannot drift from this bench's configuration."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import AdamW

    model = nn.Transformer(
        vocab_size=vocab_size, hidden_size=hidden_size,
        num_heads=num_heads, filter_size=filter_size,
        num_layers=num_layers, dropout=0.0, causal=True)
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(logits=True))
    methods = {"__all__": AdamW(3e-4)}
    return model, crit, methods


def main():
    d = LM_DEFAULTS
    ap = argparse.ArgumentParser()
    ap.add_argument("-b", "--batchSize", type=int, default=d["batchSize"])
    ap.add_argument("--seqLen", type=int, default=d["seqLen"])
    ap.add_argument("--vocabSize", type=int, default=d["vocabSize"])
    ap.add_argument("--hiddenSize", type=int, default=d["hiddenSize"])
    ap.add_argument("--numHeads", type=int, default=d["numHeads"])
    ap.add_argument("--filterSize", type=int, default=d["filterSize"])
    ap.add_argument("--numLayers", type=int, default=d["numLayers"])
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.optim.optimizer import make_train_step
    from bigdl_tpu.ops.pallas import report as kernel_report
    from bigdl_tpu.telemetry import costmodel
    from bigdl_tpu.utils import jax_compat
    from bigdl_tpu.utils.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"lm_bench.py measures the chip: jax found platform "
                 f"{dev.platform!r}, not a TPU")
    enable_compile_cache()
    peak = costmodel.peak_flops_per_device(dev)  # unknown kind raises

    model, crit, methods = build_lm(
        args.vocabSize, args.hiddenSize, args.numHeads, args.filterSize,
        args.numLayers)
    step = jax.jit(
        make_train_step(model, crit, methods, compute_dtype=jnp.bfloat16),
        donate_argnums=(0, 1, 2))

    variables = model.init(jax.random.PRNGKey(0))
    params, mstate = variables["params"], variables["state"]
    opt = {"__all__": methods["__all__"].init_state(params)}
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randint(0, args.vocabSize,
                               (args.batchSize, args.seqLen)))
    t = jnp.asarray(rs.randint(0, args.vocabSize,
                               (args.batchSize, args.seqLen)))
    lrs = [jnp.asarray(3e-4, jnp.float32)]

    compiled = step.lower(params, mstate, opt, jnp.asarray(0, jnp.int32),
                          jax.random.PRNGKey(0), x, t, lrs).compile()
    flops = jax_compat.cost_analysis(compiled).get("flops")

    for i in range(2):
        params, mstate, opt, loss = compiled(
            params, mstate, opt, jnp.asarray(i, jnp.int32),
            jax.random.PRNGKey(i), x, t, lrs)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for i in range(args.steps):
        params, mstate, opt, loss = compiled(
            params, mstate, opt, jnp.asarray(i, jnp.int32),
            jax.random.PRNGKey(i), x, t, lrs)
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / args.steps

    tokens = args.batchSize * args.seqLen
    if not flops:
        # 6 * params * tokens (dense-LM rule of thumb), attention extra
        n_par = sum(int(p.size) for p in
                    jax.tree_util.tree_leaves(params))
        flops = 6.0 * n_par * tokens
    mfu = flops / dt / peak
    fa = kernel_report.report().get("flash_attention", {})
    rec = {
        "metric": "transformer_lm_train_throughput",
        "value": round(tokens / dt, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(mfu / 0.50, 4),
        "detail": {
            "batch": args.batchSize, "seq_len": args.seqLen,
            "layers": args.numLayers, "hidden": args.hiddenSize,
            "step_time_ms": round(1000 * dt, 2),
            "mfu": round(mfu, 4),
            "device": {"platform": dev.platform,
                       "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "flash_attention_pallas": fa.get("pallas", 0),
        },
    }
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
