"""Driver ``decode_granite_hybrid``: ``decode_hybrid_ssm`` keyed to the
Granite 4.0-H configuration (``layer_types`` of ``mamba`` and
``attention``, a dense MLP in every layer, a tied head).

Draws: the model's own ``Mamba2Mixer.draw_decay`` for ``A_log`` and
``dt_bias`` (``mamba_decay``) and the centered second matrix of the
Mamba-2 mixer (``normal_centered``), both ``decode_hybrid_ssm``'s; every
other leaf by the generic rules (benchmark/weights.py).

**The state check** (``ssm_state_gap``) as ``decode_hybrid_ssm``'s:
after the timed run a second ``DecodeEngine`` of the cell's own slots,
pages, buckets and chunk serves ``STATE_REQUESTS`` of the seed's
requests (the longest prompt among them) through a model of the
configuration's widths and state dtype whose layers are
``STATE_LAYERS`` (the scaled embedding, one Mamba-2 layer with its MLP,
the tied head), in float32 at ``highest``, and hands back each slot's
state blocks at the end; the reference steps the recurrence over the
same tokens.  ``ssm_state_gap`` is the largest, over the requests, of
the state's distance from the reference's over the reference's norm;
with ``control`` the reference's own state held in bfloat16 is read the
same way.

The feeders, the window, the timing and the served-logit check are
``decode``'s; the model, the engine's options and the reading of a
trace are ``decode_model``'s.
"""
from __future__ import annotations

import copy
import importlib
import sys
import time

import jax
import numpy as np

from benchmark.drivers import decode, decode_model
from benchmark.drivers import decode_hybrid_ssm as hybrid

STATE_LAYERS = ["mamba"]

build_model = decode_model.build_model
make_variables = hybrid.make_variables
engine_options = decode_model.engine_options
device_time_by_program = decode_model.device_time_by_program


def state_check(cell: dict, seed: int, seconds: float,
                control: str = "") -> dict:
    """-> ``{"ssm_state_gap": ...}`` and, with ``control``, the same
    number for the reference's state held in bfloat16."""
    from bigdl_tpu.serving import DecodeEngine

    config = copy.deepcopy(cell["config"])
    mix = cell["traffic"]
    config["model"]["layer_types"] = STATE_LAYERS
    config["serve"]["dtype"] = "float32"
    t0 = time.perf_counter()
    model = build_model(config)
    variables = make_variables(config, model, seed)
    requests = hybrid.state_requests(mix, seed, seconds,
                                     config["model"]["vocab_size"])
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    eng = None
    try:
        eng = DecodeEngine(
            model, variables, slots=mix["slots"], max_len=mix["max_len"],
            prompt_buckets=mix["prompt_buckets"],
            prefill_batch_sizes=mix["prefill_batch_sizes"],
            kv_layout="paged", page_size=mix["page_size"],
            **engine_options(mix))
        futs = [eng.submit(r["prompt"], r["max_new"], keep_blocks=True)
                for r in requests]
        served = [np.asarray(f.result(hybrid.STATE_WAIT_S)) for f in futs]
        blocks = [jax.device_get(f.blocks) for f in futs]
    finally:
        if eng is not None:
            eng.close(drain=False, timeout=30.0)
        jax.config.update("jax_default_matmul_precision", was)
    t_ref = time.perf_counter()
    reference = importlib.import_module(
        "benchmark.references." + config["reference"])
    params = variables["params"]
    out = {"ssm_state_gap": 0.0}
    if control:
        out["control_ssm_state_gap"] = 0.0
    for r, tokens, got in zip(requests, served, blocks):
        ids = np.concatenate([r["prompt"], tokens[:-1]])
        want = reference.final_states(params, ids, config["model"])
        low = reference.final_states(params, ids, config["model"],
                                     state_dtype="bfloat16") \
            if control else {}
        for lk, s in want.items():
            mine = np.asarray(got[lk]["ssm"], np.float32).reshape(s.shape)
            out["ssm_state_gap"] = max(out["ssm_state_gap"],
                                       hybrid.relative_gap(mine, s))
            if control:
                out["control_ssm_state_gap"] = max(
                    out["control_ssm_state_gap"],
                    hybrid.relative_gap(low[lk], s))
    print(f"[decode] state check: {len(requests)} requests of "
          f"{[int(r['prompt'].size) for r in requests]} prompt and "
          f"{[int(t.size) for t in served]} served tokens through "
          f"{STATE_LAYERS} in float32 in {t_ref - t0:.1f} s, the "
          f"reference in {time.perf_counter() - t_ref:.1f} s: "
          + ", ".join(f"{k} {v:.4g}" for k, v in out.items()), flush=True)
    return out


def run(cell, device, seed, seconds, trace, t_start, compiles,
        control: str = "") -> dict:
    out = decode.run(cell, device, seed, seconds, trace, t_start,
                     compiles, control=control,
                     family=sys.modules[__name__])
    state = state_check(cell, seed, seconds, control)
    out["numbers"]["ssm_state_gap"] = state["ssm_state_gap"]
    if control:
        out["control_numbers"]["ssm_state_gap"] = \
            state["control_ssm_state_gap"]
    return out
