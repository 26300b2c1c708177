"""One general generator for every traffic mix: training data and
request streams, pure functions of the mix's parameters and ``--seed``.

A serving mix fixes its *set* of request sizes and arrival gaps from its
own ``base_seed``; ``--seed`` only reorders them and fills the prompts,
so every seed offers the same work.  A mix with ``clients`` is a closed
loop: the driver sends a client's next request when its last one is
answered, so ``due`` is the order of sending and the set is a supply
(``supply_requests_per_s``) of which a run consumes what the engine
serves; ``strata`` deals that supply into blocks of equal work, so that
what a window consumes does not depend on the seed's order either.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int = 0):
    return np.random.default_rng([int(seed), stream])


# ---- training data --------------------------------------------------------
def lm_tokens(seed: int, rows: int, seq_len: int, vocab: int):
    """Zipf-distributed token ids -> (inputs, next-token targets), each
    (rows, seq_len); every row is its own draw, so all rows differ."""
    p = 1.0 / np.arange(1, vocab + 1)
    cdf = np.cumsum(p / p.sum())
    u = rng_for(seed).random((rows, seq_len + 1))
    ids = np.minimum(np.searchsorted(cdf, u), vocab - 1).astype(np.int64)
    return ids[:, :-1], ids[:, 1:]


def images(seed: int, rows: int, size: int, classes: int):
    """Uniform-noise images with a per-class mean shift (the program's
    synthetic ImageNet, from the seed) -> (NHWC float32, labels)."""
    rng = rng_for(seed)
    x = rng.random((rows, size, size, 3), dtype=np.float32)
    y = rng.integers(0, classes, (rows,))
    x += (y[:, None, None, None] / (4.0 * classes)).astype(np.float32)
    return x, y


def training_data(kind: dict, traffic: dict, seed: int):
    if kind["kind"] == "lm_tokens":
        return lm_tokens(seed, traffic["rows"], traffic["seq_len"],
                         kind["vocab"])
    if kind["kind"] == "images":
        return images(seed, traffic["rows"], kind["size"], kind["classes"])
    raise ValueError(f"unknown training data kind {kind['kind']!r}")


# ---- request streams ------------------------------------------------------
def _lognormal(rng, n: int, spec: dict):
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def stratified_blocks(prompt_len, max_new, strata: int):
    """The set dealt into blocks of ``strata`` requests of equal work,
    by the set alone: sorted by prompt length and cut into ``strata``
    bands, a block holds one request of each band; within a band the
    longest answer goes to the block that has the fewest answer tokens
    so far.  -> indices, (blocks, strata); what does not fill a block
    is left out."""
    blocks = len(prompt_len) // strata
    bands = np.lexsort((max_new, prompt_len))[:blocks * strata].reshape(
        strata, blocks)
    out = np.empty((blocks, strata), np.int64)
    answer_tokens = np.zeros(blocks, np.int64)
    for k, band in enumerate(bands):
        band = band[np.argsort(-max_new[band], kind="stable")]
        out[np.argsort(answer_tokens, kind="stable"), k] = band
        answer_tokens += max_new[out[:, k]]
    return out


def request_stream(mix: dict, seed: int, seconds: float, vocab: int):
    """Open loop: arrivals at ``mix['rate']`` requests/s, ``lead_in_s``
    of them before the window opens (due < 0, not measured), then
    ``seconds`` of window.  Closed loop (``mix['clients']``): a supply
    of ``supply_requests_per_s`` over the same span, ``due`` its order;
    the driver marks what it sent inside the window as measured.
    -> list of dicts ``due, prompt, max_new, measured`` sorted by due."""
    lead = float(mix.get("lead_in_s", 0.0))
    closed = "clients" in mix
    rate = mix["supply_requests_per_s"] if closed else mix["rate"]
    n = int(round(rate * (lead + seconds)))
    base = rng_for(mix["base_seed"])
    gaps = base.exponential(1.0, n)
    if mix.get("burst"):  # arrivals in bursts: the gaps inside are zero
        size = base.integers(mix["burst"]["min"], mix["burst"]["max"] + 1, n)
        starts = np.cumsum(size)
        keep = np.zeros(n, bool)
        keep[starts[starts < n]] = True
        keep[0] = True
        gaps = np.where(keep, gaps, 0.0)
    gaps *= (lead + seconds) / gaps.sum()
    prompt_len = _lognormal(base, n, mix["prompt_tokens"])
    max_new = _lognormal(base, n, mix["output_tokens"])

    rng = rng_for(seed, 1)
    if mix.get("strata"):  # the seed orders the blocks and each inside
        blocks = stratified_blocks(prompt_len, max_new, mix["strata"])
        order = np.concatenate([rng.permutation(b) for b in
                                blocks[rng.permutation(len(blocks))]])
    else:
        order = rng.permutation(n)
    if closed:
        due = np.arange(len(order), dtype=np.float64)
    else:
        due = np.cumsum(gaps[rng.permutation(n)]) - gaps[0] - lead
        due = np.sort(due)
    out = []
    for i, j in enumerate(order):
        out.append({
            "due": float(due[i]),
            "prompt": rng.integers(0, vocab, int(prompt_len[j]),
                                   dtype=np.int64).astype(np.int32),
            "max_new": int(max_new[j]),
            "measured": bool(not closed and 0.0 <= due[i] < seconds),
        })
    return out
