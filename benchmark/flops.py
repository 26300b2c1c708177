"""Operations and bytes the algorithm needs, from shapes alone.

A multiply-add counts 2.  Training counts forward + backward (3x the
forward's matmul/conv work), nothing recomputed.  Causal attention is
counted at the half of the score matrix it needs.  Elementwise work,
norms and softmax are left out (they are under 1% here), so a share of
the peak computed from these counts reads low, never high.
"""
from __future__ import annotations


# ---- transformer LM (nn.Transformer: packed-free q/k/v/o, relu FFN, tied head)
def lm_forward_flops(cfg: dict, batch: int, seq: int) -> dict:
    d, f = cfg["hidden_size"], cfg["filter_size"]
    layers, vocab = cfg["num_layers"], cfg["vocab_size"]
    tokens = batch * seq
    blocks = layers * tokens * 2 * (4 * d * d + 2 * d * f)
    # QK^T and PV: 2 matmuls of seq x seq x d per row, causal half
    attention = layers * batch * 2 * 2 * seq * seq * d * 0.5
    head = tokens * 2 * d * vocab
    return {"blocks": blocks, "attention": attention, "head": head,
            "total": blocks + attention + head}


def lm_train_flops(cfg: dict, batch: int, seq: int) -> dict:
    return {k: 3 * v for k, v in lm_forward_flops(cfg, batch, seq).items()}


def flash_fwd_cost(batch: int, heads: int, seq: int, head_dim: int,
                   itemsize: int = 2) -> dict:
    """The causal flash forward at one call's shapes: the half score
    matrix's two matmuls; q, k, v read once and o written once."""
    flops = batch * heads * 2 * 2 * seq * seq * head_dim * 0.5
    nbytes = 4 * batch * heads * seq * head_dim * itemsize
    return {"flops": flops, "bytes": nbytes}


def lm_tick_cost(cfg: dict, active: float, context: float,
                 weight_itemsize: int = 4, kv_itemsize: int = 4) -> dict:
    """One decode tick: ``active`` rows, each holding ``context`` tokens.
    Bytes: every weight once (the tied embedding serves as the head),
    plus the K and V of the tokens actually held."""
    d, f = cfg["hidden_size"], cfg["filter_size"]
    layers, vocab = cfg["num_layers"], cfg["vocab_size"]
    per_token = layers * 2 * (4 * d * d + 2 * d * f) + 2 * d * vocab
    attention = layers * 2 * 2 * context * d
    weights = (layers * (4 * d * d + 2 * d * f) + d * vocab) * weight_itemsize
    kv = active * context * layers * 2 * d * kv_itemsize
    return {"flops": active * (per_token + attention),
            "bytes": weights + kv}


def tokens_held_by_traced_ticks(run: dict):
    """Mean over the traced ticks of the tokens all their rows hold
    together: ``loop/tick_dispatch`` carries the pages the slots hold
    (``args.pages_held``; the tracer's ring holds the spans of the
    profiler session), so bytes and device time are of the same ticks.
    Nothing where no tick carries the counter."""
    import statistics

    from bigdl_tpu.telemetry import get_tracer

    pages = [s.args["pages_held"] for s in get_tracer().spans()
             if s.name == "loop/tick_dispatch" and s.args
             and "pages_held" in s.args]
    if not pages:
        return None
    return statistics.fmean(pages) * run["traffic"]["page_size"]


# ---- ResNet-50 v1 (He et al. 2015, Table 1), 224 px
def resnet50_forward_flops(image: int = 224, classes: int = 1000) -> float:
    """Convolutions and the classifier of ResNet-50 v1 for one image
    (multiply-add = 2): stem 7x7/2, then bottlenecks (3, 4, 6, 3) with
    the stride on the 3x3 (the torchvision/BigDL v1.5 placement differs
    only in where the stride sits; the count here follows the repo's
    model: stride on the first 1x1 would lower it by ~5%)."""
    def conv(h, cin, cout, k):
        return 2.0 * h * h * cin * cout * k * k

    h = image // 2
    total = conv(h, 3, 64, 7)
    h //= 2  # max pool
    cin = 64
    for width, blocks, stride in ((64, 3, 1), (128, 4, 2), (256, 6, 2),
                                  (512, 3, 2)):
        for b in range(blocks):
            s = stride if b == 0 else 1
            h_out = h // s
            total += conv(h, cin, width, 1)          # 1x1 at the input size
            total += conv(h_out, width, width, 3)    # 3x3 carries the stride
            total += conv(h_out, width, 4 * width, 1)
            if b == 0:
                total += conv(h_out, cin, 4 * width, 1)  # projection
            cin, h = 4 * width, h_out
    return total + 2.0 * cin * classes


def resnet50_train_flops(image: int = 224, classes: int = 1000) -> float:
    return 3.0 * resnet50_forward_flops(image, classes)


def roofline_seconds(cost: dict, peaks: dict) -> float:
    """The least time the chip could take for ``cost``."""
    return max(cost["flops"] / peaks["flops_per_s"],
               cost["bytes"] / peaks["hbm_bytes_per_s"])
