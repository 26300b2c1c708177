#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

One process drives the main paths once through the entry points a user
would call, at the full width of the models the repo measures (random
weights from a seed), and checks what comes out by the repo's own
means:

    python chip_smoke.py             # one chip: device, train (conv),
                                     # train (LM), serve, kernels
    python chip_smoke.py --chips 4   # four chips: ONLY the dp=4 vs
                                     # one-device LM comparison and one
                                     # dp2 x tp2 step

Any phase failing means a non-zero exit and no result line.  There is
no CPU branch: when jax finds no TPU the script fails at once.  The
last line of standard output is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and everything else worth reading is printed on earlier lines.  The
script starts no child process that touches jax: a chip belongs to one
process at a time.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import os
import sys
import time
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

BF16_TOL = 2e-2  # relative to the reference's largest magnitude


class SmokeFailure(RuntimeError):
    """A phase found something wrong; the script exits non-zero."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What each phase runs.  The defaults are the real widths: the
    trainers' argv is what a user would type, the LM is
    ``tools/lm_bench.LM_DEFAULTS`` (GPT-2-small widths) and the kernel
    shapes come from ``tools/kernel_shapes.py``.  tests/test_chip_smoke.py
    builds a tiny instance to run the same phase code on the CPU."""

    seed: int = 0
    # train, conv: models/resnet_train.py (synthetic ImageNet).  The
    # recipe's maxLr 3.2 is for its global batch of 8192; scaled
    # linearly to batch 256 it is 0.1 (the first chip run, at 3.2,
    # climbed from loss 6.9 to 304 in 11 iterations)
    conv_argv: Tuple[str, ...] = (
        "-b", "256", "--syntheticSize", "1024", "--depth", "50",
        "--classNum", "1000", "--imageSize", "224", "--maxLr", "0.1")
    # 11 iterations: the engine logs at iterations 1, 11, ... and at
    # epoch ends, so the last canonical line is a steady-state one
    conv_iters: int = 11
    # train, LM: models/transformer_train.py at LM_DEFAULTS
    lm_argv: Tuple[str, ...] = (
        "-b", "8", "--seqLen", "2048", "--vocabSize", "32000",
        "--hiddenSize", "768", "--numHeads", "12", "--filterSize", "3072",
        "--numLayers", "12", "--dropout", "0.0", "--learningRate", "3e-4")
    lm_iters: int = 11
    multichip_iters: int = 11
    # serve: the LM's widths in a paged DecodeEngine
    serve_model: Tuple[Tuple[str, int], ...] = (
        ("vocab_size", 32000), ("hidden_size", 768), ("num_heads", 12),
        ("filter_size", 3072), ("num_layers", 12))
    serve_slots: int = 8
    serve_max_len: int = 512
    serve_page: int = 16
    serve_prompt_buckets: Tuple[int, ...] = (32, 128)
    serve_prefill_batches: Tuple[int, ...] = (1, 4)
    # (prompt length, new tokens): mixed lengths; the first is the
    # short request checked against the uncached oracle
    serve_requests: Tuple[Tuple[int, int], ...] = (
        (12, 16), (100, 24), (31, 32), (64, 8), (5, 24), (128, 16))
    # kernels: one inventory shape per family
    k_matmul: Tuple[int, int, int] = (256 * 56 * 56, 64, 64)
    k_conv3: Tuple[int, int, int, int, int] = (256, 56, 56, 64, 64)
    k_flash: Tuple[int, int, int, int] = (8, 12, 2048, 64)
    k_int8: Tuple[int, int, int] = (4096, 768, 3072)


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------
def say(msg: str) -> None:
    print(msg, flush=True)


def hbm(tag: str) -> None:
    """Print every device's memory_stats: bytes in use and the peak so
    far, as the runtime counts them (without program temporaries)."""
    for d in jax.devices():
        st = d.memory_stats() or {}
        say(f"[hbm] {tag}: device {d.id} in_use="
            f"{st.get('bytes_in_use', 0) / 2**30:.2f} GiB peak="
            f"{st.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB limit="
            f"{st.get('bytes_limit', 0) / 2**30:.2f} GiB")


class CompileMeter:
    """Sums what jax itself reports about compilation: seconds spent in
    backend compiles (a persistent-cache hit counts its retrieval), and
    the persistent cache's hits and misses.  Phases read deltas."""

    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.slow = []  # (function name, seconds) of compiles over 1 s
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            if secs >= 1.0:
                self.slow.append((fun_name, round(secs, 1)))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def read(self) -> dict:
        return {"compile_s": self.compile_s, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


_METER = None


def meter() -> CompileMeter:
    """The process's one meter, listening from its first use on."""
    global _METER
    if _METER is None:
        _METER = CompileMeter()
    return _METER


class LossTrace:
    """A train summary that keeps ``Loss`` by iteration (the engine
    writes each loss against the iteration that produced it)."""

    def __init__(self):
        self.losses = {}

    def add_scalar(self, tag, value, step):
        if tag == "Loss":
            self.losses[int(step)] = float(value)

    def sequence(self):
        return [self.losses[k] for k in sorted(self.losses)]


def rel_err(got, want) -> float:
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


def run_trainer(name: str, opt, iters: int) -> dict:
    """Run a configured Optimizer for ``iters`` iterations; check the
    loss and the iteration counter; return timings and losses."""
    import bigdl_tpu.optim as optim

    trace = LossTrace()
    opt.set_end_when(optim.Trigger.max_iteration(iters))
    opt.set_train_summary(trace)
    c0 = meter().compile_s
    t0 = time.perf_counter()
    opt.optimize()
    total = time.perf_counter() - t0
    compile_s = meter().compile_s - c0
    losses = trace.sequence()
    neval = next(iter(opt.optim_methods.values())).state["neval"]
    say(f"[{name}] engine={type(opt).__name__} iterations={neval} "
        f"compile_s={compile_s:.1f} steps_s={total - compile_s:.1f} "
        f"losses={[round(l, 4) for l in losses]}")
    check(neval == iters, f"{name}: iteration counter at {neval}, "
                          f"wanted {iters}")
    check(len(losses) == iters and all(np.isfinite(losses)),
          f"{name}: losses not finite or missing: {losses}")
    return {"compile_s": compile_s, "steps_s": total - compile_s,
            "losses": losses}


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------
def phase_device(chips: int) -> dict:
    """jax.devices(); fail unless the platform is a TPU.  No retry, no
    CPU branch."""
    devs = jax.devices()
    dev = devs[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    check(dev.platform == "tpu",
          f"jax found platform {dev.platform!r}, not a TPU: chip_smoke.py "
          "runs on the chip or not at all")
    check(len(devs) == chips,
          f"--chips {chips} but jax reports {len(devs)} device(s)")
    say(f"[device] {info} jax={jax.__version__} devices={devs}")
    return info


def phase_train_conv(sz: Sizes) -> dict:
    """ResNet through Optimizer.apply -> optimize() exactly as
    models/resnet_train.py builds it: synthetic ImageNet, bf16 compute,
    the async loop.  The driver's default model (XLA convolutions) is
    what a user gets and what runs here; the fused Pallas pipeline is
    covered kernel by kernel in :func:`phase_kernels`."""
    from bigdl_tpu.models import resnet_train

    t0 = time.perf_counter()
    opt, _ = resnet_train.build(list(sz.conv_argv))
    setup_s = time.perf_counter() - t0
    out = run_trainer("train-conv", opt, sz.conv_iters)
    out["setup_s"] = setup_s
    return out


def phase_train_lm(sz: Sizes) -> dict:
    """The Transformer LM through the same Optimizer path
    models/transformer_train.py uses; flash attention on."""
    from bigdl_tpu.models import transformer_train
    from bigdl_tpu.ops.pallas import report

    before = report.report().get("flash_attention", {}).get("pallas", 0)
    t0 = time.perf_counter()
    opt, _ = transformer_train.build(list(sz.lm_argv))
    setup_s = time.perf_counter() - t0
    out = run_trainer("train-lm", opt, sz.lm_iters)
    out["setup_s"] = setup_s
    out["flash_pallas_traces"] = report.report().get(
        "flash_attention", {}).get("pallas", 0) - before
    say(f"[train-lm] flash_attention Pallas traces in this phase: "
        f"{out['flash_pallas_traces']}")
    return out


def phase_serve(sz: Sizes) -> dict:
    """The LM widths in a serving.DecodeEngine (paged KV, the
    constructor's normal warmup); greedy requests of mixed prompt
    lengths; every request finishes, the short one agrees with the
    uncached forward, and the recompile counter stays flat."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.serving import DecodeEngine

    kw = dict(sz.serve_model)
    vocab = kw["vocab_size"]
    model = nn.Transformer(dropout=0.0, causal=True, **kw)
    variables = model.init(jax.random.PRNGKey(sz.seed))
    rs = np.random.RandomState(sz.seed)
    prompts = [rs.randint(0, vocab, (n,)) for n, _ in sz.serve_requests]

    t0 = time.perf_counter()
    eng = DecodeEngine(
        model, variables, slots=sz.serve_slots, max_len=sz.serve_max_len,
        prompt_buckets=sz.serve_prompt_buckets,
        prefill_batch_sizes=sz.serve_prefill_batches,
        kv_layout="paged", page_size=sz.serve_page)
    try:
        warm_s = time.perf_counter() - t0
        declared = eng.declared_programs()
        say(f"[serve] warmup compiled {eng.recompiles} programs "
            f"(declared {declared}) in {warm_s:.1f} s")
        check(eng.recompiles == declared,
              f"serve: warmup compiled {eng.recompiles} programs, the "
              f"grid declares {declared}")
        t1 = time.perf_counter()
        futs = [eng.submit(p, n_new)
                for p, (_, n_new) in zip(prompts, sz.serve_requests)]
        outs = [np.asarray(f.result(600)) for f in futs]
        run_s = time.perf_counter() - t1
        for (n, n_new), got in zip(sz.serve_requests, outs):
            check(got.shape == (n_new,),
                  f"serve: request (prompt {n}, new {n_new}) returned "
                  f"{got.shape[0]} tokens")
        after = eng.recompiles
        say(f"[serve] {len(outs)} requests, "
            f"{sum(len(o) for o in outs)} tokens in {run_s:.2f} s; "
            f"recompiles after warmup: {after - declared}")
        say(f"[serve] {eng.log_line()}")
        check(after == declared,
              f"serve: {after - declared} recompile(s) after warmup")
    finally:
        eng.close()

    # the uncached oracle on the short request, teacher-forced with the
    # engine's tokens: one causal forward over prompt + generated, padded
    # to a fixed length (causal: padding after a position cannot reach it)
    prompt, got = prompts[0], outs[0]
    ids = np.concatenate([prompt, got[:-1]])
    padded = np.zeros((1, max(sz.serve_prompt_buckets)), np.int32)
    padded[0, :ids.size] = ids
    logits = jax.jit(lambda p, s, x: model.apply(
        p, s, x, training=False)[0])(
            variables["params"], variables["state"], jnp.asarray(padded))
    rows = np.asarray(logits[0, prompt.size - 1:ids.size], np.float32)
    exact = int(np.sum(np.argmax(rows, axis=-1) == got))
    # where the argmax differs, the engine's token must tie the oracle's
    # best within bf16 tolerance (TPU matmuls round f32 through bf16)
    gap = rows.max(axis=-1) - rows[np.arange(got.size), got]
    tol = BF16_TOL * np.abs(rows).max()
    say(f"[serve] uncached oracle: {exact}/{got.size} tokens exact, "
        f"largest logit gap {gap.max():.4g} (tolerance {tol:.4g})")
    check(bool(np.all(gap <= tol)),
          f"serve: engine tokens disagree with the uncached forward "
          f"(gap {gap.max():.4g} > {tol:.4g})")
    return {"warmup_s": warm_s, "run_s": run_s, "programs": declared,
            "oracle_exact": exact, "oracle_tokens": int(got.size)}


def _inventory_shapes() -> set:
    """(family, shape) of every dispatch tools/kernel_shapes.py declares
    a Pallas route — the shapes that must never be seen on 'xla' (the
    autotune sweep's own reading of the inventory)."""
    from tools import kernel_shapes as KS
    from tools.autotune import _sweep_plan

    return set(_sweep_plan(KS, quick=False, families=None))


def phase_kernels(sz: Sizes) -> dict:
    """Every Pallas kernel family at one real width: compiled by
    Mosaic, run, and compared with its own XLA reference path to bf16
    tolerance.  Then the whole process's route table is read: a family
    that should have run with ``pallas == 0``, an ``xla`` route at an
    inventory shape, or any ``pallas_local_xla`` fails the smoke."""
    from bigdl_tpu.ops.attention import dot_product_attention
    from bigdl_tpu.ops.pallas import fused_matmul as fm
    from bigdl_tpu.ops.pallas import report, tuning
    from bigdl_tpu.ops.pallas.flash_attention import flash_attention
    from bigdl_tpu.ops.pallas.int8_matmul import int8_matmul_dequant

    key = jax.random.PRNGKey(sz.seed)
    timings = {}

    def compare(tag, pallas_fn, ref_fn, args):
        t0 = time.perf_counter()
        got = jax.block_until_ready(jax.jit(pallas_fn)(*args))
        dt = time.perf_counter() - t0
        want = jax.block_until_ready(jax.jit(ref_fn)(*args))
        errs = [rel_err(g, w) for g, w in zip(
            jax.tree_util.tree_leaves(got),
            jax.tree_util.tree_leaves(want))]
        timings[tag] = dt
        say(f"[kernels] {tag}: compile+run {dt:.1f} s, max rel err vs "
            f"XLA reference {max(errs):.2e}")
        check(max(errs) <= BF16_TOL,
              f"kernels: {tag} differs from its XLA reference "
              f"(rel err {max(errs):.3e} > {BF16_TOL})")

    def stats_loss(out):
        y, ssum, ssq = out  # all three cotangents live: 2y, 1, 1
        return (jnp.sum(y.astype(jnp.float32) ** 2) + jnp.sum(ssum)
                + jnp.sum(ssq))

    # fused matmul + BN stats, forward and backward
    m, k, n = sz.k_matmul
    k1, k2, k3 = jax.random.split(key, 3)
    mm_args = (jax.random.normal(k1, (m, k), jnp.bfloat16),
               jax.random.normal(k2, (k, n), jnp.bfloat16) * 0.1,
               jnp.ones((k,), jnp.float32),
               jax.random.normal(k3, (k,), jnp.float32) * 0.1)

    def mm(x, w, ps, pb):
        return fm.fused_matmul_bn(x, w, prologue_scale=ps,
                                  prologue_bias=pb, relu=True)

    def mm_ref(x, w, ps, pb):  # plain XLA ops, plain autodiff
        return fm._xla_fwd(x, w, ps, pb, True, True)

    compare(f"fused_matmul {m}x{k}x{n} fwd", mm, mm_ref, mm_args)
    compare(f"fused_matmul {m}x{k}x{n} bwd",
            jax.grad(lambda *a: stats_loss(mm(*a)), argnums=(0, 1, 2, 3)),
            jax.grad(lambda *a: stats_loss(mm_ref(*a)),
                     argnums=(0, 1, 2, 3)), mm_args)

    # fused 3x3 conv forward, and the dgrad kernel behind its opt-in
    b, h, w_, c, co = sz.k_conv3
    conv_args = (jax.random.normal(k1, (b, h, w_, c), jnp.bfloat16),
                 jax.random.normal(k2, (3, 3, c, co), jnp.bfloat16) * 0.05,
                 jnp.ones((c,), jnp.float32),
                 jax.random.normal(k3, (c,), jnp.float32) * 0.1)

    def conv(x, w, ps, pb):
        return fm.fused_conv3x3_bn(x, w, prologue_scale=ps,
                                   prologue_bias=pb, relu=True)

    def conv_ref(x, w, ps, pb):
        return fm._conv3_xla(x, w, ps, pb, True, True)

    def conv_ref_f32(x, w, ps, pb):
        """The same math in plain f32 ops, for the backward: autodiff
        cannot transpose _conv3_xla's bf16-in / f32-out convolution."""
        u = jnp.maximum(x.astype(jnp.float32) * ps + pb, 0.0)
        u = u.astype(x.dtype).astype(jnp.float32)  # the prologue's rounding
        yf = jax.lax.conv_general_dilated(
            u, w.astype(jnp.float32), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        y2 = yf.reshape(-1, yf.shape[-1])
        return (yf.astype(x.dtype), jnp.sum(y2, axis=0),
                jnp.sum(y2 * y2, axis=0))

    compare(f"conv3 {b}x{h}x{w_}x{c}->{co} fwd", conv, conv_ref, conv_args)
    prev = os.environ.get("BIGDL_TPU_FUSED_CONV3_BWD")
    os.environ["BIGDL_TPU_FUSED_CONV3_BWD"] = "1"  # read at trace time
    try:
        compare(f"conv3 {b}x{h}x{w_}x{c}->{co} bwd (dgrad kernel)",
                jax.grad(lambda *a: stats_loss(conv(*a)),
                         argnums=(0, 1, 2, 3)),
                jax.grad(lambda *a: stats_loss(conv_ref_f32(*a)),
                         argnums=(0, 1, 2, 3)), conv_args)
    finally:
        if prev is None:
            os.environ.pop("BIGDL_TPU_FUSED_CONV3_BWD", None)
        else:
            os.environ["BIGDL_TPU_FUSED_CONV3_BWD"] = prev

    # flash attention at the LM's shape, forward and backward
    q = jax.random.normal(k1, sz.k_flash, jnp.bfloat16)
    kk = jax.random.normal(k2, sz.k_flash, jnp.bfloat16)
    v = jax.random.normal(k3, sz.k_flash, jnp.bfloat16)

    def attn(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def attn_ref(q, k, v):
        return dot_product_attention(q, k, v, causal=True,
                                     use_flash=False)

    tag = "flash " + "x".join(map(str, sz.k_flash))
    compare(tag + " fwd", attn, attn_ref, (q, kk, v))
    compare(tag + " bwd",
            jax.grad(lambda *a: jnp.mean(attn(*a).astype(jnp.float32) ** 2),
                     argnums=(0, 1, 2)),
            jax.grad(lambda *a: jnp.mean(
                attn_ref(*a).astype(jnp.float32) ** 2), argnums=(0, 1, 2)),
            (q, kk, v))

    # int8 matmul with the dequant epilogue
    m8, k8, n8 = sz.k_int8
    i8_args = (jax.random.randint(k1, (m8, k8), -127, 128).astype(jnp.int8),
               jax.random.randint(k2, (k8, n8), -127, 128).astype(jnp.int8),
               jax.random.uniform(k3, (n8,), jnp.float32, 1e-4, 2e-4))

    def i8_ref(x, w, s):
        acc = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        return (acc.astype(jnp.float32) * s[None, :]).astype(jnp.bfloat16)

    compare(f"int8_matmul {m8}x{k8}x{n8}", int8_matmul_dequant, i8_ref,
            i8_args)

    # ---- the whole process's route table --------------------------------
    routes = report.report()
    say("[kernels] route table (trace-time counts, whole process):")
    for fam in sorted(routes):
        say(f"[kernels]   {fam:22s} {routes[fam]}")
    table = tuning.get_tuned_table()
    say(f"[kernels] tuned table: "
        f"{table.path if table is not None else None} "
        f"({len(table) if table is not None else 0} entries) for "
        f"device_kind {jax.devices()[0].device_kind!r}")
    for (fam, shape), rec in sorted(report.params_report().items()):
        say(f"[kernels]   params {fam} {'x'.join(map(str, shape))}: "
            f"{rec['params']} from {rec['source']}")
    fallbacks = report.fallbacks()
    for fam, path, shape in fallbacks:
        say(f"[kernels]   non-Pallas route: {fam} {path} {shape}")

    must_run = ("fused_matmul", "fused_conv3x3", "fused_conv3x3_dgrad",
                "flash_attention", "int8_matmul",
                "paged_attention")  # the serve phase's tick
    dead = [f for f in must_run if routes.get(f, {}).get("pallas", 0) == 0]
    check(not dead, f"kernels: no Pallas route taken by {dead}")
    inventory = _inventory_shapes()
    hidden = [(f, p, s) for f, p, s in fallbacks
              if p == "pallas_local_xla" or (f, s) in inventory]
    check(not hidden,
          f"kernels: XLA route at a shape the inventory declares Pallas, "
          f"or a per-shard fallback: {hidden}")
    stale = [k for k, r in report.params_report().items()
             if r["source"] == "stale"]
    check(not stale, f"kernels: stale tuned-table entries at {stale}")
    return {"compile_run_s": timings, "routes": routes}


def phase_multichip(sz: Sizes) -> dict:
    """Synchronous data-parallel SGD across every chip of the host —
    the system's reason to exist.  The LM trainer through
    Optimizer.apply -> DistriOptimizer on a data=N mesh with ZeRO-1,
    then the same seed and global batch on one device of this process;
    the two loss sequences must agree to bf16 tolerance and the state
    must really be spread over the N devices.  Then one dp x tp=2 step
    through transformer_train's --tp 2 path, which nests the flash
    kernel's shard_map in a sharded mesh on real devices."""
    import bigdl_tpu.optim as optim
    from bigdl_tpu.models import transformer_train

    n = len(jax.devices())
    opt, _ = transformer_train.build(list(sz.lm_argv))
    check(isinstance(opt, optim.DistriOptimizer),
          f"multichip: Optimizer.apply built {type(opt).__name__} on "
          f"{n} devices")
    check(dict(opt.mesh.shape)["data"] == n and opt.zero1,
          f"multichip: mesh {dict(opt.mesh.shape)} zero1={opt.zero1}")
    dp = run_trainer(f"dp{n}-zero1", opt, sz.multichip_iters)

    params, _, opt_states = opt._last_trees

    def spread(tree):
        """-> (devices the largest leaf lives on, its shard shape)."""
        leaf = max(jax.tree_util.tree_leaves(tree), key=lambda a: a.size)
        shards = leaf.addressable_shards
        return ({s.device.id for s in shards}, shards[0].data.shape,
                leaf.shape)

    p_dev, p_shard, p_shape = spread(params)
    o_dev, o_shard, o_shape = spread(opt_states)
    say(f"[dp{n}-zero1] largest param {p_shape}: shards {p_shard} on "
        f"devices {sorted(p_dev)}; largest optimizer-state leaf "
        f"{o_shape}: shards {o_shard} on devices {sorted(o_dev)}")
    check(len(p_dev) == n, f"multichip: params live on {sorted(p_dev)}")
    check(len(o_dev) == n and o_shard[0] * n == o_shape[0],
          f"multichip: optimizer state is not ZeRO-1 sharded over {n} "
          f"devices: leaf {o_shape} in shards {o_shard} on "
          f"{sorted(o_dev)}")
    hbm(f"after dp{n}")

    # the same run on ONE device of this process: a LocalOptimizer with
    # the distributed run's own settings (a second build gives a fresh,
    # identically seeded dataset)
    twin, _ = transformer_train.build(list(sz.lm_argv))
    ref = optim.LocalOptimizer(twin.model, twin.dataset, twin.criterion)
    ref.set_optim_methods(twin.optim_methods)
    ref.set_gradient_clipping_by_l2_norm(twin.grad_clip_norm)
    ref.set_compute_dtype(twin.compute_dtype)
    one = run_trainer("one-device", ref, sz.multichip_iters)
    err = max(abs(a - b) / max(abs(b), 1e-6)
              for a, b in zip(dp["losses"], one["losses"]))
    say(f"[dp{n}-zero1] loss vs one device: max rel diff {err:.2e}")
    check(err <= BF16_TOL,
          f"multichip: dp{n} losses {dp['losses']} != one-device "
          f"{one['losses']}")

    tp_opt, _ = transformer_train.build(list(sz.lm_argv) + ["--tp", "2"])
    check(dict(tp_opt.mesh.shape)["model"] == 2,
          f"multichip: --tp 2 built mesh {dict(tp_opt.mesh.shape)}")
    tp = run_trainer(f"dp{n // 2}xtp2", tp_opt, 1)
    hbm(f"after dp{n // 2}xtp2")
    return {"dp": dp, "one": one, "tp": tp, "loss_rel_diff": err}


# --------------------------------------------------------------------------
# entry
# --------------------------------------------------------------------------
def main(argv: Sequence[str] = None) -> int:
    ap = argparse.ArgumentParser("chip_smoke")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): the one-chip phases.  4: only "
                         "the multi-chip path and what it is compared "
                         "with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    # the canonical log lines belong on stdout: give the package logger
    # its handler before bigdl_tpu is imported (utils/logger.py then
    # leaves it alone instead of attaching its stderr default)
    handler = logging.StreamHandler(sys.stdout)
    handler.setFormatter(logging.Formatter(
        "%(asctime)s %(levelname)s %(name)s - %(message)s"))
    for name in ("bigdl_tpu", "chip_smoke"):
        lg = logging.getLogger(name)
        lg.addHandler(handler)
        lg.setLevel(logging.INFO)
        lg.propagate = False

    t_start = time.perf_counter()
    try:
        device = phase_device(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED in device: {e}", file=sys.stderr)
        return 1

    from bigdl_tpu.utils.compile_cache import (cache_entries,
                                               enable_compile_cache)

    cache = enable_compile_cache()
    n_before = cache_entries(cache)
    say(f"[cache] compile cache: {cache} ({n_before} entries before; "
        f"JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")

    sz = Sizes(seed=args.seed)
    phases = ([("multichip", phase_multichip)] if args.chips == 4 else
              [("train-conv", phase_train_conv), ("train-lm", phase_train_lm),
               ("serve", phase_serve), ("kernels", phase_kernels)])
    summary, failed = {}, []
    for name, fn in phases:
        say(f"===== phase {name} =====")
        t0 = time.perf_counter()
        m0, n_slow = meter().read(), len(meter().slow)
        try:
            summary[name] = fn(sz)
        except Exception as e:
            # later phases still run — one chip call should tell as much
            # as it can — but the result line is lost for good
            logging.getLogger("chip_smoke").exception(
                "phase %s failed", name)
            print(f"chip_smoke: FAILED in {name}: {e}", file=sys.stderr,
                  flush=True)
            failed.append(name)
            continue
        dt = time.perf_counter() - t0
        delta = {k: v - m0[k] for k, v in meter().read().items()}
        summary[name].update(
            phase_s=round(dt, 1), phase_compile_s=round(
                delta["compile_s"], 1),
            cache_hits=delta["cache_hits"],
            cache_misses=delta["cache_misses"])
        say(f"===== phase {name} ok in {dt:.1f} s (compile "
            f"{delta['compile_s']:.1f} s; persistent cache "
            f"{delta['cache_hits']} hits, {delta['cache_misses']} misses) "
            f"=====")
        say(f"[{name}] compiles over 1 s (a cache hit counts its "
            f"retrieval): {meter().slow[n_slow:]}")
        hbm(f"after {name}")

    n_after = cache_entries(cache)
    say(f"[cache] {cache}: {n_before} entries before, {n_after} after "
        f"({n_after - n_before} written by this run)")
    say("[summary] " + json.dumps(
        {"total_s": round(time.perf_counter() - t_start, 1),
         "phases": {k: {kk: vv for kk, vv in v.items()
                        if isinstance(vv, (int, float))}
                    for k, v in summary.items()}}))
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
