"""Operations and bytes of the latent-attention decoder with routed
experts (nn/latent.py, nn/routed.py), from shapes and the traced ticks'
own counters.  ``cfg`` is the configuration file's ``model`` section.

A multiply-add counts 2; elementwise work, norms, rotary and softmax
are left out, so a share of a peak computed from these counts reads
low, never high.  Decode is counted in the absorbed form the tick runs:
per token held and head, a 576-wide score and a 512-wide value product.
Bytes are what a tick has to read once: every weight outside the routed
experts (of the embedding only the rows looked up), the held experts
that got a token - an expert nobody chose is not read - and the latent
rows the slots hold.
"""
from __future__ import annotations

import statistics


def _dims(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    d, qr, kvr = cfg["hidden_size"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    mla = (d * qr + qr * h * (nope + rope) + d * (kvr + rope)
           + kvr * h * (nope + vd) + h * vd * d)
    expert = 3 * d * cfg["moe_intermediate_size"]
    return {
        "heads": h, "latent": kvr + rope, "value": kvr, "mla": mla,
        "layers": layers, "routed_layers": layers - dense,
        "dense_ffn": 3 * d * cfg["intermediate_size"], "expert": expert,
        "shared": cfg.get("n_shared_experts", 0) * expert,
        "router": d * cfg["n_routed_experts"],
        "head": d * cfg["vocab_size"], "hidden": d,
    }


def resident_params(cfg: dict) -> dict:
    """Parameters by where a tick reads them."""
    x = _dims(cfg)
    dense_layers = x["layers"] - x["routed_layers"]
    once = (x["layers"] * x["mla"] + dense_layers * x["dense_ffn"]
            + x["routed_layers"] * (x["shared"] + x["router"]) + x["head"])
    return {"read_every_tick": once, "one_expert": x["expert"]}


def mla_decode_cost(cfg: dict, active: float, tokens_held: float,
                    itemsize: int = 2) -> dict:
    """One layer's absorbed attention in one tick: ``tokens_held`` is
    the sum over the slots of the latent rows they hold."""
    x = _dims(cfg)
    flops = tokens_held * x["heads"] * 2 * (x["latent"] + x["value"])
    nbytes = (tokens_held * x["latent"]
              + active * x["heads"] * (x["latent"] + x["value"])) * itemsize
    return {"flops": flops, "bytes": nbytes}


def experts_cost(cfg: dict, assignments: float, touched: float,
                 itemsize: int = 2) -> dict:
    """One layer's grouped expert products in one tick: ``assignments``
    token-expert pairs landed on ``touched`` of the held experts."""
    x = _dims(cfg)
    flops = assignments * 2 * x["expert"]
    nbytes = (touched * x["expert"]
              + assignments * 2 * x["hidden"]) * itemsize
    return {"flops": flops, "bytes": nbytes}


def tick_cost(cfg: dict, active: float, tokens_held: float,
              assignments: float, touched: float, itemsize: int = 2) -> dict:
    """One decode tick.  ``assignments`` and ``touched`` are summed over
    the routed layers."""
    x = _dims(cfg)
    p = resident_params(cfg)
    attn = mla_decode_cost(cfg, active, tokens_held, itemsize)
    flops = (active * 2 * p["read_every_tick"] + x["layers"] * attn["flops"]
             + assignments * 2 * x["expert"])
    nbytes = ((p["read_every_tick"] + touched * x["expert"]) * itemsize
              + x["layers"] * tokens_held * x["latent"] * itemsize)
    return {"flops": flops, "bytes": nbytes}


# ---- what the traced ticks held ------------------------------------------
def traced_ticks(run: dict) -> list:
    """One dict a traced tick (the tracer's ring holds the spans of the
    profiler session): ``seconds`` from the start of
    ``loop/tick_dispatch`` to the end of ``loop/tick_wait``,
    ``active`` rows (``loop/retire``), ``tokens_held`` (pages held x
    page size) and ``expert_tokens`` (routed layers x held experts).
    Ticks without the counters (a program that has none) are left
    out."""
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
            if "expert_tokens" in a and "pages_held" in a:
                out.append({
                    "seconds": cur["seconds"],
                    "active": (s.args or {}).get("active", 0),
                    "tokens_held": a["pages_held"] * page,
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
        "tokens_held": mean(t["tokens_held"] for t in ticks),
        "assignments": mean(sum(map(sum, t["expert_tokens"]))
                            for t in ticks),
        "touched": mean(sum(sum(1 for n in layer if n)
                            for layer in t["expert_tokens"])
                        for t in ticks),
        "median_seconds": statistics.median(t["seconds"] for t in ticks),
        "ticks": len(ticks),
    }
