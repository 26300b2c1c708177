"""Operations and bytes of the decoder of Mamba-2 layers, grouped-query
attention layers and latent routed experts (nn/hybrid_ssm.py,
nn/routed.py), from shapes and the traced ticks' own counters.  ``cfg``
is the configuration file's ``model`` section.

A multiply-add counts 2; norms, the convolution, the gate and softmax
are left out, so a share of a peak computed from these counts reads
low, never high.  Bytes are what a tick has to read once: every weight
outside the routed experts (of the embedding only the rows looked up),
the experts that got a token, the K and V rows the attention layer
holds, and each live state block of a Mamba-2 layer read and written.
"""
from __future__ import annotations

import statistics

F32_BYTES = 4


def _dims(cfg: dict) -> dict:
    d, pattern = cfg["hidden_size"], cfg["hybrid_override_pattern"]
    di = cfg["expand"] * d
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, k = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    conv = di + 2 * g * n
    latent, width = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    return {
        "hidden": d, "d_inner": di, "ssm_heads": h, "ssm_head_dim": p,
        "groups": g, "states": n, "chunk": cfg["chunk_size"],
        "mamba_layers": pattern.count("M"),
        "attn_layers": pattern.count("*"),
        "routed_layers": pattern.count("E"),
        "layers": len(pattern),
        # W_in, W_out; the depthwise kernel, its bias, dt_bias, A_log,
        # D and the gated norm's weight
        "mamba": d * (di + conv + h) + di * d + (k + 1) * conv + 3 * h + di,
        "state": h * p * n, "conv": (k - 1) * conv,
        "heads": heads, "head_dim": hd, "kv_row": 2 * kv * hd,
        "attn": 2 * d * heads * hd + 2 * d * kv * hd,
        "router": d * cfg["n_routed_experts"], "latent": latent,
        "latent_proj": 2 * d * latent,
        "shared": 2 * d * cfg["moe_shared_expert_intermediate_size"],
        "expert": 2 * latent * width, "per_token": cfg["num_experts_per_tok"],
        "embed": d * cfg["vocab_size"], "head": d * cfg["vocab_size"],
    }


def parameter_count(cfg: dict) -> int:
    """Every parameter the model holds (norm weights and the router's
    bias included)."""
    x = _dims(cfg)
    held = len(cfg["experts_held"]) if cfg.get("experts_held") \
        else cfg["n_routed_experts"]
    return (x["embed"] + x["head"] + (x["layers"] + 1) * x["hidden"]
            + x["mamba_layers"] * x["mamba"] + x["attn_layers"] * x["attn"]
            + x["routed_layers"] * (held * x["expert"] + x["router"]
                                    + cfg["n_routed_experts"]
                                    + x["latent_proj"] + x["shared"]))


def resident_params(cfg: dict) -> dict:
    """Parameters by where a tick reads them."""
    x = _dims(cfg)
    once = (x["mamba_layers"] * x["mamba"] + x["attn_layers"] * x["attn"]
            + x["routed_layers"] * (x["router"] + x["latent_proj"]
                                    + x["shared"]) + x["head"])
    return {"read_every_tick": once, "one_expert": x["expert"]}


def ssm_step_cost(cfg: dict, blocks: float) -> dict:
    """One Mamba-2 layer's state step over ``blocks`` live slots: two
    multiply-adds an element of the state (its update, and ``y = S C``);
    the state read and written in f32, with x, B, C and delta read and y
    written (f32)."""
    x = _dims(cfg)
    flops = blocks * 2 * 2 * x["state"]
    per_slot = (2 * x["state"] + 2 * x["d_inner"]
                + 2 * x["groups"] * x["states"] + x["ssm_heads"]) * F32_BYTES
    return {"flops": flops, "bytes": blocks * per_slot}


def ssd_cost(cfg: dict, rows: float) -> dict:
    """One Mamba-2 layer's chunked scan over ``rows`` (padded to whole
    chunks): inside a chunk the C.B scores and their weighted sum over x,
    each chunk's own state and its reading by C; bytes: x, B, C and
    delta read, y written, the state read and written once (f32)."""
    x = _dims(cfg)
    q, h, p, n, g = (x["chunk"], x["ssm_heads"], x["ssm_head_dim"],
                     x["states"], x["groups"])
    rows = -(-rows // q) * q
    flops = 2 * rows * (q * g * n + q * h * p + 2 * h * p * n)
    nbytes = (rows * (2 * h * p + 2 * g * n + h) + 2 * x["state"]) * F32_BYTES
    return {"flops": flops, "bytes": nbytes}


def attn_decode_cost(cfg: dict, active: float, rows_read: float,
                     itemsize: int = 2) -> dict:
    """The attention layer in one tick over ``rows_read`` K/V rows
    (summed over the slots)."""
    x = _dims(cfg)
    flops = rows_read * x["heads"] * 2 * 2 * x["head_dim"]
    nbytes = (rows_read * x["kv_row"]
              + active * 2 * x["heads"] * x["head_dim"]) * itemsize
    return {"flops": flops, "bytes": nbytes}


def experts_cost(cfg: dict, assignments: float, touched: float,
                 itemsize: int = 2) -> dict:
    """One routed layer's two grouped products in one tick:
    ``assignments`` token-expert pairs landed on ``touched`` experts,
    each row a latent row in and out."""
    x = _dims(cfg)
    return {"flops": assignments * 2 * x["expert"],
            "bytes": (touched * x["expert"]
                      + assignments * 2 * x["latent"]) * itemsize}


def tick_cost(cfg: dict, active: float, rows: float, blocks: float,
              assignments: float, touched: float,
              itemsize: int = 2) -> dict:
    """One decode tick: ``rows`` K/V rows the attention layer reads,
    ``blocks`` live state blocks a Mamba-2 layer steps; ``assignments``
    and ``touched`` summed over the routed layers."""
    x = _dims(cfg)
    p = resident_params(cfg)
    attn = attn_decode_cost(cfg, active, rows, itemsize)
    ssm = ssm_step_cost(cfg, blocks)
    flops = (active * 2 * p["read_every_tick"] + x["attn_layers"]
             * attn["flops"] + assignments * 2 * x["expert"]
             + x["mamba_layers"] * ssm["flops"])
    nbytes = ((p["read_every_tick"] + touched * x["expert"]
               + active * x["hidden"]) * itemsize
              + x["attn_layers"] * attn["bytes"]
              + x["mamba_layers"] * ssm["bytes"])
    return {"flops": flops, "bytes": nbytes}


# ---- what the traced ticks held ------------------------------------------
def traced_ticks(run: dict) -> list:
    """One dict a traced tick (the tracer's ring holds the spans of the
    profiler session): ``seconds`` from the start of
    ``loop/tick_dispatch`` to the end of ``loop/tick_wait``, ``active``
    rows (``loop/retire``), ``rows`` (``pages_held`` x page size),
    ``blocks`` (``state_blocks_held``) and ``expert_tokens``.  Ticks
    without the three counters (a program without state blocks) are
    left out."""
    from bigdl_tpu.telemetry import get_tracer

    page = run["traffic"]["page_size"]
    spans = sorted((s for s in get_tracer().spans()
                    if s.name in ("loop/tick_dispatch", "loop/tick_wait",
                                  "loop/retire")), key=lambda s: s.t0)
    out, cur = [], None
    for s in spans:
        if s.name == "loop/tick_dispatch":
            cur = {"t0": s.t0, "args": s.args or {}}
        elif cur is not None and s.name == "loop/tick_wait":
            cur["seconds"] = s.t1 - cur["t0"]
        elif cur is not None and s.name == "loop/retire" \
                and "seconds" in cur:
            a = cur["args"]
            if all(k in a for k in ("expert_tokens", "pages_held",
                                    "state_blocks_held")):
                out.append({
                    "seconds": cur["seconds"],
                    "active": (s.args or {}).get("active", 0),
                    "rows": a["pages_held"] * page,
                    "blocks": a["state_blocks_held"],
                    "expert_tokens": a["expert_tokens"]})
            cur = None
    return out


def mean_tick(run: dict):
    """The traced ticks' means -> the arguments of :func:`tick_cost`,
    and the ticks' median seconds; nothing where no tick was traced."""
    ticks = traced_ticks(run)
    if not ticks:
        return None
    mean = statistics.fmean
    return {
        "active": mean(t["active"] for t in ticks),
        "rows": mean(t["rows"] for t in ticks),
        "blocks": mean(t["blocks"] for t in ticks),
        "assignments": mean(sum(map(sum, t["expert_tokens"]))
                            for t in ticks),
        "touched": mean(sum(sum(1 for n in layer if n)
                            for layer in t["expert_tokens"])
                        for t in ticks),
        "median_seconds": statistics.median(t["seconds"] for t in ticks),
        "ticks": len(ticks),
    }


def mean_tick_cost(run: dict):
    """``(mean_tick, tick_cost of it)`` or nothing."""
    tick = mean_tick(run)
    if tick is None:
        return None
    return tick, tick_cost(run["config"]["model"], tick["active"],
                           tick["rows"], tick["blocks"],
                           tick["assignments"], tick["touched"])



def ssd_runs(run: dict) -> list:
    """Rows of each run of the prompt programs in the traced span: the
    chunk program's ``prefill_chunk`` a run, a bucketed prefill the mean
    of the traced ``prefill_dispatch`` spans' ``rows`` (the padded
    bucket; a program without the count gives none)."""
    from bigdl_tpu.telemetry import get_tracer

    ops = run.get("program_ops") or {}
    runs = []
    for program, rec in ops.items():
        if "chunk" in program:
            runs += [run["traffic"]["prefill_chunk"]] * rec["runs"]
        elif "prefill" in program:
            rows = [(s.args or {}).get("rows") for s in get_tracer().spans()
                    if s.name == "prefill_dispatch"]
            rows = [r for r in rows if r]
            if rows:
                runs += [statistics.fmean(rows)] * rec["runs"]
    return runs
