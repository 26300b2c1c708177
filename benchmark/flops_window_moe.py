"""Operations and bytes of the decoder with window and full attention
layers mixed, grouped-query attention and routed experts
(nn/window_moe.py, nn/routed.py), from shapes and the traced ticks' own
counters.  ``cfg`` is the configuration file's ``model`` section.

A multiply-add counts 2; elementwise work, norms, rotary, the gate and
softmax are left out, so a share of a peak computed from these counts
reads low, never high.  Bytes are what a tick has to read once: every
weight outside the routed experts (of the embedding only the rows
looked up), the experts that got a token - an expert nobody chose is
not read - and the K and V rows attention reads: all a slot holds at a
full layer, no more than the window at a window layer.
"""
from __future__ import annotations

import statistics


def _dims(cfg: dict) -> dict:
    d, h, g, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["head_dim"])
    kinds = cfg["layer_types"]
    dense = cfg["num_dense_layers"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    return {
        "hidden": d, "heads": h, "kv_row": 2 * g * hd, "head_dim": hd,
        # Wq, Wg and Wo at H x D, Wk and Wv at G x D
        "attn": 3 * d * h * hd + 2 * d * g * hd,
        "window": cfg["sliding_window"],
        "window_layers": sum(k == "sliding_attention" for k in kinds),
        "full_layers": sum(k == "full_attention" for k in kinds),
        "layers": len(kinds), "dense_layers": dense,
        "routed_layers": len(kinds) - dense,
        "dense_ffn": 3 * d * cfg["intermediate_size"], "expert": expert,
        "per_token": cfg["num_experts_per_tok"],
        "shared": cfg.get("num_shared_experts", 0) * expert,
        "router": d * cfg["num_experts"],
        "embed": d * cfg["vocab_size"], "head": d * cfg["vocab_size"],
    }


def parameter_count(cfg: dict) -> int:
    """Every parameter the model holds (norm weights included)."""
    x = _dims(cfg)
    held = len(cfg["experts_held"]) if cfg.get("experts_held") \
        else cfg["num_experts"]
    norms = x["layers"] * (4 * x["hidden"] + 2 * x["head_dim"]) \
        + x["hidden"]
    return (x["embed"] + x["head"] + x["layers"] * x["attn"]
            + x["dense_layers"] * x["dense_ffn"] + x["routed_layers"] * (
                held * x["expert"] + x["shared"] + x["router"]
                + cfg["num_experts"]) + norms)


def resident_params(cfg: dict) -> dict:
    """Parameters by where a tick reads them."""
    x = _dims(cfg)
    once = (x["layers"] * x["attn"] + x["dense_layers"] * x["dense_ffn"]
            + x["routed_layers"] * (x["shared"] + x["router"]) + x["head"])
    return {"read_every_tick": once, "one_expert": x["expert"]}


def attn_decode_cost(cfg: dict, active: float, rows_read: float,
                     itemsize: int = 2) -> dict:
    """One layer's attention in one tick over ``rows_read`` K/V rows
    (summed over the slots): a score and a value product a query head
    and row; the rows' K and V, the queries and the outputs."""
    x = _dims(cfg)
    flops = rows_read * x["heads"] * 2 * 2 * x["head_dim"]
    nbytes = (rows_read * x["kv_row"]
              + active * 2 * x["heads"] * x["head_dim"]) * itemsize
    return {"flops": flops, "bytes": nbytes}


def experts_cost(cfg: dict, assignments: float, touched: float,
                 itemsize: int = 2) -> dict:
    """One layer's grouped expert products in one tick: ``assignments``
    token-expert pairs landed on ``touched`` of the experts."""
    x = _dims(cfg)
    flops = assignments * 2 * x["expert"]
    nbytes = (touched * x["expert"]
              + assignments * 2 * x["hidden"]) * itemsize
    return {"flops": flops, "bytes": nbytes}


def tick_cost(cfg: dict, active: float, full_rows: float,
              window_rows: float, assignments: float, touched: float,
              itemsize: int = 2) -> dict:
    """One decode tick.  ``full_rows`` / ``window_rows``: the K/V rows a
    full / a window layer reads, summed over the slots;
    ``assignments`` and ``touched`` are summed over the routed layers."""
    x = _dims(cfg)
    p = resident_params(cfg)
    full = attn_decode_cost(cfg, active, full_rows, itemsize)
    band = attn_decode_cost(cfg, active, window_rows, itemsize)
    flops = (active * 2 * p["read_every_tick"]
             + x["full_layers"] * full["flops"]
             + x["window_layers"] * band["flops"]
             + assignments * 2 * x["expert"])
    nbytes = ((p["read_every_tick"] + touched * x["expert"]
               + active * x["hidden"]) * itemsize
              + x["full_layers"] * full["bytes"]
              + x["window_layers"] * band["bytes"])
    return {"flops": flops, "bytes": nbytes}


def chunk_cost(cfg: dict, tokens: float, context: float,
               itemsize: int = 2) -> dict:
    """A prefill chunk of ``tokens`` rows appended behind ``context``
    cached rows: every row through the weights it touches (the head at
    the one row read), attention over the causal keys, of a window
    layer those inside the band.  Bytes: every weight once (a chunk of
    thousands of rows touches every expert)."""
    x = _dims(cfg)
    per_row = (x["layers"] * x["attn"] + x["dense_layers"] * x["dense_ffn"]
               + x["routed_layers"] * (x["per_token"] * x["expert"]
                                       + x["shared"] + x["router"]))
    seen_full = tokens * (context + (tokens + 1) / 2.0)
    seen_band = sum(min(context + i + 1, x["window"])
                    for i in range(int(tokens)))
    attention = x["heads"] * 2 * 2 * x["head_dim"] * (
        x["full_layers"] * seen_full + x["window_layers"] * seen_band)
    weights = parameter_count(cfg) - x["embed"]
    return {"flops": tokens * 2 * per_row + 2 * x["head"] + attention,
            "bytes": (weights + tokens * x["hidden"]) * itemsize}


# ---- what the traced ticks held ------------------------------------------
def traced_ticks(run: dict) -> list:
    """One dict a traced tick (the tracer's ring holds the spans of the
    profiler session): ``seconds`` from the start of
    ``loop/tick_dispatch`` to the end of ``loop/tick_wait``, ``active``
    rows (``loop/retire``), ``full_rows`` (``pages_held`` x page size),
    ``window_rows_held`` (``window_pages_held`` x page size) and
    ``expert_tokens`` (routed layers x experts).  Ticks without the
    three counters (a program that keeps one extent) are left out."""
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
                                    "window_pages_held")):
                out.append({
                    "seconds": cur["seconds"],
                    "active": (s.args or {}).get("active", 0),
                    "full_rows": a["pages_held"] * page,
                    "window_rows_held": a["window_pages_held"] * page,
                    "expert_tokens": a["expert_tokens"]})
            cur = None
    return out


def mean_tick(run: dict):
    """The traced ticks' means -> the arguments of :func:`tick_cost`,
    and the ticks' median seconds; nothing where no tick was traced.
    A window layer has to read ``min(held, window)`` rows of a slot: the
    rows its pool holds, and no more than the window a row."""
    ticks = traced_ticks(run)
    if not ticks:
        return None
    mean = statistics.fmean
    window = run["config"]["model"]["sliding_window"]
    return {
        "active": mean(t["active"] for t in ticks),
        "full_rows": mean(t["full_rows"] for t in ticks),
        "window_rows": mean(min(t["window_rows_held"],
                                t["active"] * window) for t in ticks),
        "window_rows_held": mean(t["window_rows_held"] for t in ticks),
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
    return tick, tick_cost(
        run["config"]["model"], tick["active"], tick["full_rows"],
        tick["window_rows"], tick["assignments"], tick["touched"])
