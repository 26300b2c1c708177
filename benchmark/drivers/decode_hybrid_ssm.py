"""Driver ``decode_hybrid_ssm``: ``decode_model`` for a model with
Mamba-2 layers and relu**2 experts, with two draws of weights that the
generic rules (benchmark/weights.py) do not make and one comparison that
the served logits cannot make.

Draws:

* ``mamba_decay``: ``A_log`` and ``dt_bias`` as the model's own
  ``Mamba2Mixer.draw_decay`` makes them (log U(1, 16); the inverse
  softplus of a draw log-uniform in the time step's bounds);
* ``normal_centered`` (std): the second matrix of a unit whose
  activation has a positive mean (relu**2 in the experts, the Mamba-2
  mixer's gated norm), drawn N(0, std) with each output's column summing
  to zero over the inputs, so that the mean activation adds no direction
  common to every token.  Without it every token's residual stream
  leaned one way after a few layers and the routers sent every token to
  the same experts (PERF.md section 6).

**The state check** (``ssm_state_gap``).  At the served precision the
state's own precision cannot be seen: bf16 activations and the routing
they flip move the served logits, and the state blocks themselves, by
more than a state held in bf16 does (PERF.md section 2).  So after the
timed run a second ``DecodeEngine`` of the cell's own slots, pages,
buckets and chunk serves ``STATE_REQUESTS`` of the seed's requests (the
longest prompt among them, so that the chunked path runs) through a
model of the configuration's widths and state dtype whose layers are
``STATE_PATTERN`` (the embedding, one Mamba-2 layer, the head), in
float32 at ``highest``, and hands back each slot's state blocks at the
end (``submit(keep_blocks=True)``).  The reference steps the recurrence
over the same tokens; what differs in float32 is the order of the sums.
``ssm_state_gap`` is the largest, over the requests, of the state's
distance from the reference's over the reference's norm; with
``control`` the reference's own state held in bfloat16, the precision
below the configuration's, is read the same way.  (No attention layer:
``paged_attn`` takes no float32 product at ``highest``.)

Every other leaf, the model, the engine's options and the reading of a
trace are ``decode_model``'s; the feeders, the window, the timing and
the served-logit check are ``decode``'s.
"""
from __future__ import annotations

import copy
import functools
import importlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.drivers import decode, decode_model

SPECIAL = ("mamba_decay", "normal_centered")
STATE_PATTERN = "M"
STATE_REQUESTS = 4
STATE_WAIT_S = 120.0

build_model = decode_model.build_model
engine_options = decode_model.engine_options
device_time_by_program = decode_model.device_time_by_program


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _centered(std: float, key, shape, dtype):
    def draw(k, rows):
        w = std * jax.random.normal(k, rows, jnp.float32)
        return (w - w.mean(axis=-2, keepdims=True)).astype(dtype)

    if len(shape) == 2:
        return draw(key, shape)
    # one expert at a time: the f32 draw of a whole layer's experts
    # would take 1.4 GB beside the weights
    return jax.lax.map(lambda k: draw(k, shape[1:]),
                       jax.random.split(key, shape[0]))


def make_variables(config: dict, model, seed: int):
    from benchmark import weights

    rules = config["serve"]["init"]
    plain = [[p, "const", 0.0] if kind in SPECIAL else [p, kind, n]
             for p, kind, n in rules]
    variables = weights.make_variables(
        model, plain, seed,
        dtype=jnp.dtype(config["serve"].get("dtype", "float32")))
    paths = weights.leaf_paths(variables)
    leaves, treedef = jax.tree_util.tree_flatten(variables)
    del variables      # a placeholder is freed as its draw replaces it
    key = jax.random.fold_in(weights.seed_key(seed), 1 << 20)
    for i, path in enumerate(paths):
        kind, number = weights._rule_for(path, rules)
        shape, dtype = leaves[i].shape, leaves[i].dtype
        if kind == "normal_centered":
            leaves[i] = None
            leaves[i] = _centered(number, jax.random.fold_in(key, i),
                                  shape, dtype)
        elif kind == "mamba_decay":     # params/layer<i>/mamba/<leaf>
            _, layer, _, leaf = path.split("/")
            mixer = model.layers[int(layer[len("layer"):])].attn
            leaves[i] = mixer.draw_decay(jax.random.fold_in(key, i),
                                         dtype)[leaf]
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ------------------------------------------------------- the state check
def state_requests(mix: dict, seed: int, seconds: float, vocab: int):
    """The seed's requests the check serves: the one with the longest
    prompt, and others drawn from the seed."""
    from benchmark import traffic as gen

    stream = gen.request_stream(mix, seed, seconds, vocab)
    longest = max(range(len(stream)),
                  key=lambda i: stream[i]["prompt"].size)
    others = [i for i in range(len(stream)) if i != longest]
    picks = gen.rng_for(seed, 3).permutation(len(others))
    return [stream[longest]] + [stream[others[i]]
                                for i in picks[:STATE_REQUESTS - 1]]


def relative_gap(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def state_check(cell: dict, seed: int, seconds: float,
                control: str = "") -> dict:
    """-> ``{"ssm_state_gap": ...}`` and, with ``control``, the same
    number for the reference's state held in bfloat16."""
    from bigdl_tpu.serving import DecodeEngine

    config = copy.deepcopy(cell["config"])
    mix = cell["traffic"]
    config["model"]["hybrid_override_pattern"] = STATE_PATTERN
    config["serve"]["dtype"] = "float32"
    t0 = time.perf_counter()
    model = build_model(config)
    variables = make_variables(config, model, seed)
    requests = state_requests(mix, seed, seconds,
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
        served = [np.asarray(f.result(STATE_WAIT_S)) for f in futs]
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
                                       relative_gap(mine, s))
            if control:
                out["control_ssm_state_gap"] = max(
                    out["control_ssm_state_gap"], relative_gap(low[lk], s))
    print(f"[decode] state check: {len(requests)} requests of "
          f"{[int(r['prompt'].size) for r in requests]} prompt and "
          f"{[int(t.size) for t in served]} served tokens through "
          f"{STATE_PATTERN!r} in float32 in {t_ref - t0:.1f} s, the "
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
