"""Operations and bytes of the decoder of Mamba-2 and grouped-query
attention layers, each with a dense gated MLP and a tied head
(nn/granite_hybrid.py), from shapes and the traced ticks' own counters.
``cfg`` is the configuration file's ``model`` section.

A multiply-add counts 2; norms, the convolution, the gates and softmax
are left out, so a share of a peak computed from these counts reads low,
never high.  Bytes are what a tick has to read once: every weight (the
embedding once, as the head; of the lookup only the rows looked up), the
K and V rows the attention layers hold, and each live state block and
convolution history of a Mamba-2 layer read and written.
"""
from __future__ import annotations

import statistics

F32_BYTES = 4


def _dims(cfg: dict) -> dict:
    d, kinds = cfg["hidden_size"], list(cfg["layer_types"])
    di = cfg["mamba_expand"] * d
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n, k = cfg["mamba_n_groups"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    conv = di + 2 * g * n
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    return {
        "hidden": d, "d_inner": di, "ssm_heads": h, "ssm_head_dim": p,
        "groups": g, "states": n, "chunk": cfg["mamba_chunk_size"],
        "mamba_layers": kinds.count("mamba"),
        "attn_layers": kinds.count("attention"), "layers": len(kinds),
        # W_in, W_out; the depthwise kernel, its bias, dt_bias, A_log,
        # D and the gated norm's weight
        "mamba": d * (di + conv + h) + di * d + (k + 1) * conv + 3 * h + di,
        "state": h * p * n, "conv": (k - 1) * conv,
        "heads": heads, "head_dim": hd, "kv_row": 2 * kv * hd,
        "attn": 2 * d * heads * hd + 2 * d * kv * hd,
        "mlp": 3 * d * cfg["shared_intermediate_size"],
        "embed": d * cfg["vocab_size"],
    }


def parameter_count(cfg: dict) -> int:
    """Every parameter the model holds: the embedding once (it is the
    head too), two norms a layer and the final one."""
    x = _dims(cfg)
    return (x["embed"] + (2 * x["layers"] + 1) * x["hidden"]
            + x["mamba_layers"] * x["mamba"] + x["attn_layers"] * x["attn"]
            + x["layers"] * x["mlp"])


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


def conv_cost(cfg: dict, blocks: float, itemsize: int = 2) -> dict:
    """One Mamba-2 layer's convolution history over ``blocks`` live
    slots, read and written."""
    x = _dims(cfg)
    return {"flops": 0, "bytes": blocks * 2 * x["conv"] * itemsize}


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
    """One attention layer in one tick over ``rows_read`` K/V rows
    (summed over the slots)."""
    x = _dims(cfg)
    flops = rows_read * x["heads"] * 2 * 2 * x["head_dim"]
    nbytes = (rows_read * x["kv_row"]
              + active * 2 * x["heads"] * x["head_dim"]) * itemsize
    return {"flops": flops, "bytes": nbytes}


def mlp_cost(cfg: dict, active: float, itemsize: int = 2) -> dict:
    """Every layer's MLP in one tick: its three matrices read once, each
    row in and out."""
    x = _dims(cfg)
    return {"flops": active * 2 * x["layers"] * x["mlp"],
            "bytes": (x["layers"] * x["mlp"]
                      + active * x["layers"] * 2 * x["hidden"]) * itemsize}


def tick_cost(cfg: dict, active: float, rows: float, blocks: float,
              itemsize: int = 2) -> dict:
    """One decode tick: ``rows`` K/V rows each attention layer reads,
    ``blocks`` live state blocks each Mamba-2 layer steps."""
    x = _dims(cfg)
    weights = parameter_count(cfg)      # every one, the head's included
    attn = attn_decode_cost(cfg, active, rows, itemsize)
    ssm = ssm_step_cost(cfg, blocks)
    conv = conv_cost(cfg, blocks, itemsize)
    flops = (active * 2 * weights + x["attn_layers"] * attn["flops"]
             + x["mamba_layers"] * ssm["flops"])
    nbytes = ((weights + active * x["hidden"]) * itemsize
              + x["attn_layers"] * attn["bytes"]
              + x["mamba_layers"] * (ssm["bytes"] + conv["bytes"]))
    return {"flops": flops, "bytes": nbytes}


# ---- what the traced ticks held ------------------------------------------
def traced_ticks(run: dict) -> list:
    """One dict a traced tick (the tracer's ring holds the spans of the
    profiler session): ``seconds`` from the start of
    ``loop/tick_dispatch`` to the end of ``loop/tick_wait``, ``active``
    rows (``loop/retire``), ``rows`` (``pages_held`` x page size) and
    ``blocks`` (``state_blocks_held``: live slots, each holding one
    block in every Mamba-2 layer).  Ticks without the two counters are
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
            if "pages_held" in a and "state_blocks_held" in a:
                out.append({"seconds": cur["seconds"],
                            "active": (s.args or {}).get("active", 0),
                            "rows": a["pages_held"] * page,
                            "blocks": a["state_blocks_held"]})
            cur = None
    return out


def mean_tick(run: dict):
    """The traced ticks' means -> the arguments of :func:`tick_cost`,
    and the ticks' median seconds; nothing where no tick was traced."""
    ticks = traced_ticks(run)
    if not ticks:
        return None
    mean = statistics.fmean
    return {"active": mean(t["active"] for t in ticks),
            "rows": mean(t["rows"] for t in ticks),
            "blocks": mean(t["blocks"] for t in ticks),
            "median_seconds": statistics.median(t["seconds"]
                                                for t in ticks),
            "ticks": len(ticks)}
