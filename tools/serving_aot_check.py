"""Offline serving-warmup check — no chip needed.

Compiles every declared bucket of a serving grid AND every program of
the cached-decode engine (grid tick, prefill buckets, slot writes)
through the REAL XLA:TPU compiler against a deviceless topology (the
tools/tpu_aot_check.py machinery), so a serving rollout proves its
whole warmup surface lowers — and therefore AOT warmup cannot stall or
fail at startup on the chip — before any chip time is spent.

    python tools/serving_aot_check.py                  # bench's serve model+grid
    python tools/serving_aot_check.py --decode         # decode engine only
    python tools/serving_aot_check.py --topology v5e:1x1

Exit 0 = every checked program compiled for TPU.
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


def main(argv=None):
    p = argparse.ArgumentParser("serving_aot_check")
    p.add_argument("--topology", default="v5e:1x1",
                   help="deviceless target (default the bench chip)")
    p.add_argument("--decode", action="store_true",
                   help="check only the cached-decode engine's programs")
    p.add_argument("--no-decode", action="store_true",
                   help="skip the decode-engine programs")
    args = p.parse_args(argv)

    from bench import (SERVE_BATCH_SIZES, SERVE_BUCKETS,
                       build_decode_model, build_serve_model)
    from bigdl_tpu.serving import (BucketGrid, deviceless_bucket_check,
                                   deviceless_decode_check)
    from tools.kernel_shapes import (DECODE_CHUNK, DECODE_DRAFT_K,
                                     DECODE_DRAFT_MODEL, DECODE_MAX_LEN,
                                     DECODE_PAGE, DECODE_PAGES,
                                     DECODE_PREFILL_BATCH,
                                     DECODE_PROMPT_BUCKETS, DECODE_SLOTS)

    failures = 0
    if not args.decode:
        model = build_serve_model()
        grid = BucketGrid(SERVE_BUCKETS, SERVE_BATCH_SIZES)
        mark(f"deviceless target {args.topology}: "
             f"{len(grid.declared_buckets())} declared buckets")
        failures += deviceless_bucket_check(
            model, grid, topology=args.topology, log=mark)
    if not args.no_decode:
        import bigdl_tpu.nn as nn

        mark(f"decode engine ({DECODE_SLOTS} slots, max_len "
             f"{DECODE_MAX_LEN}): tick + "
             f"{len(DECODE_PROMPT_BUCKETS) * len(DECODE_PREFILL_BATCH)}"
             f" prefill buckets + {len(DECODE_PREFILL_BATCH)} writes + "
             f"paged fp/int8 ({DECODE_PAGES} pages of {DECODE_PAGE}) + "
             f"chunked prefill ({DECODE_CHUNK}) + speculative "
             f"(k={DECODE_DRAFT_K})")
        failures += deviceless_decode_check(
            build_decode_model(), slots=DECODE_SLOTS,
            max_len=DECODE_MAX_LEN,
            prompt_buckets=DECODE_PROMPT_BUCKETS,
            prefill_batch_sizes=DECODE_PREFILL_BATCH,
            page_size=DECODE_PAGE, num_pages=DECODE_PAGES,
            kv_dtype="int8", prefill_chunk=DECODE_CHUNK,
            draft_model=nn.Transformer(**DECODE_DRAFT_MODEL),
            draft_k=DECODE_DRAFT_K,
            topology=args.topology, log=mark)
    mark("ALL PROGRAMS LOWERED" if failures == 0
         else f"{failures} FAILURES")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
