"""Capture an xplane trace of the ResNet-50 train step on chip, then
summarize device time by XLA-op bucket — the PERF.md "what the profiler
says" table in one command (reference analog: nn/mkldnn/Perf.scala +
the reference's per-module getTimes).

    python tools/profile_step.py                  # fused model
    BIGDL_TPU_BENCH_UNFUSED=1 python tools/profile_step.py

Writes the raw trace to --logdir (default /tmp/xplane_profile) for
TensorBoard, and prints a per-bucket ms/step table parsed from the
trace proto (wire-level, no tensorboard dependency).
"""
import argparse
import glob
import gzip
import os
import sys
import time
from collections import defaultdict

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def capture(logdir: str, batch: int, steps: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu.models import ResNet50
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import make_train_step

    fused = not os.environ.get("BIGDL_TPU_BENCH_UNFUSED")
    model = ResNet50(class_num=1000, stem="space_to_depth", fused=fused)
    crit = nn.ClassNLLCriterion(logits=True)
    methods = {"__all__": SGD(0.1, momentum=0.9)}
    step = jax.jit(
        make_train_step(model, crit, methods,
                        compute_dtype=jnp.bfloat16),
        donate_argnums=(0, 1, 2))

    variables = model.init(jax.random.PRNGKey(0))
    params, mstate = variables["params"], variables["state"]
    opt = {"__all__": methods["__all__"].init_state(params)}
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(batch, 224, 224, 3), jnp.bfloat16)
    t = jnp.asarray(rs.randint(0, 1000, (batch,)))
    lrs = [jnp.asarray(0.1, jnp.float32)]

    # compile + warm
    for i in range(2):
        params, mstate, opt, loss = step(
            params, mstate, opt, jnp.asarray(i, jnp.int32),
            jax.random.PRNGKey(i), x, t, lrs)
    float(loss)
    print(f"warmed (fused={fused}); tracing {steps} steps", flush=True)

    jax.profiler.start_trace(logdir)
    for i in range(steps):
        params, mstate, opt, loss = step(
            params, mstate, opt, jnp.asarray(i, jnp.int32),
            jax.random.PRNGKey(i), x, t, lrs)
    jax.block_until_ready(loss)
    jax.profiler.stop_trace()
    return fused


# --- minimal xplane proto reader (public tensorflow profiler protos) ---
# XSpace: planes=1; XPlane: name=2, lines=3, event_metadata=4(map) /
#   stat_metadata=5; XLine: events=4 (verified empirically on a
#   captured trace); XEvent: metadata_id=1, duration_ps=3;
#   XEventMetadata(map entry): value=2; XEventMetadata: id=1 name=2
def summarize(logdir: str, steps: int):
    from bigdl_tpu.interop import protowire as pw

    files = sorted(glob.glob(
        os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        print("no xplane.pb found under", logdir)
        return
    by_bucket = defaultdict(float)
    total = 0.0
    for path in files:
        data = open(path, "rb").read()
        space = pw.fields(data)
        for plane in pw.get_messages(space, 1):
            pname = pw.get_str(plane, 2)
            # device compute planes: '/device:TPU:0' on chip; the CPU
            # fallback capture uses '/host:CPU' (still useful locally)
            if not ("TPU" in pname or "/device" in pname
                    or pname == "/host:CPU"):
                continue
            meta = {}
            for entry in pw.get_messages(plane, 4):
                em = pw.get_message(entry, 2)
                if em is not None:
                    meta[pw.get_int(em, 1, 0)] = pw.get_str(em, 2)
            for line in pw.get_messages(plane, 3):
                for ev in pw.get_messages(line, 4):
                    mid = pw.get_int(ev, 1, 0)
                    dur_ps = pw.get_int(ev, 3, 0)
                    name = meta.get(mid, str(mid))
                    # bucket by fusion kind (the PERF.md table shape)
                    base = name.split(".")[0].split("(")[0]
                    by_bucket[base] += dur_ps / 1e9  # -> ms
                    total += dur_ps / 1e9
    if not by_bucket:
        print("no device events parsed")
        return
    print(f"\ndevice time by op bucket (ms over {steps} steps; "
          f"{total:.1f} ms total, {total / steps:.2f} ms/step):")
    for name, ms in sorted(by_bucket.items(), key=lambda kv: -kv[1])[:18]:
        print(f"  {ms / steps:8.3f} ms/step  {name}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--logdir", default="/tmp/xplane_profile")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--summarize-only", action="store_true",
                    help="parse an existing --logdir without running")
    args = ap.parse_args()
    if not args.summarize_only:
        os.makedirs(args.logdir, exist_ok=True)
        capture(args.logdir, args.batch, args.steps)
    summarize(args.logdir, args.steps)


if __name__ == "__main__":
    main()
