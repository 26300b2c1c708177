"""Tiny cells for the CPU: the published widths stay in the cell files;
these shrink them so a driver runs end to end in seconds.  Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import copy
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CPU = {"platform": "cpu", "kind": "cpu", "count": 1,
       "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
TINY_LM = {"vocab_size": 97, "hidden_size": 32, "num_heads": 4,
           "filter_size": 64, "num_layers": 2}
TINY_ROUTED = dict(
    vocab_size=96, hidden_size=32, intermediate_size=48,
    moe_intermediate_size=16, num_hidden_layers=3, first_k_dense_replace=1,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=12,
    n_routed_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
    routed_scaling_factor=2.5, norm_topk_prob=True, n_shared_experts=1,
    rms_norm_eps=1e-6, rope_theta=100000, num_nextn_predict_layers=0,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 64,
                  "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096,
                  "rope_type": "yarn"},
    experts_held=[0, 1, 2, 3, 8, 9])


def _load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def _tiny_config():
    cfg = _load("configs", "opt-125m.json")
    cfg["model"] = dict(TINY_LM)
    cfg["train"]["argv"] = [
        "--vocabSize", "97", "--hiddenSize", "32", "--numHeads", "4",
        "--filterSize", "64", "--numLayers", "2", "--dropout", "0.0",
        "--learningRate", "3e-4"]
    cfg["train"]["data"]["vocab"] = 97
    return cfg


@pytest.fixture
def tiny_train_cell():
    mix = {"driver": "train", "batch": 4, "seq_len": 16, "rows": 4096,
           "argv": ["-b", "4", "--seqLen", "16", "--syntheticSize", "4096"],
           "warm_steps": 4, "trace_seconds": 1}
    return {"name": "tiny-train", "chips": 1, "config": _tiny_config(),
            "traffic": mix,
            "limits": _load("cells", "lm-train.json")["limits"]}


@pytest.fixture
def tiny_decode_cell():
    mix = _load("traffic", "decode-loaded.json")
    mix.update(slots=4, max_len=64, page_size=8, prompt_buckets=[8, 32],
               prefill_batch_sizes=[1, 2], rate=20.0, lead_in_s=0.5,
               prompt_tokens={"median": 8, "sigma": 1.0, "min": 2,
                              "max": 32},
               output_tokens={"median": 8, "sigma": 0.8, "min": 2,
                              "max": 24})
    return {"name": "tiny-decode", "chips": 1, "config": _tiny_config(),
            "traffic": mix,
            "limits": _load("cells", "lm-decode-loaded.json")["limits"]}


@pytest.fixture
def tiny_closed_cell():
    """The routed cell's own files at tiny widths: a closed loop of 4
    clients on 4 slots, its supply dealt into blocks of 4."""
    config = _load("configs", "gigachat3.1-702b-ep16share.json")
    config["model"] = dict(TINY_ROUTED)
    config["serve"]["dtype"] = "float32"
    mix = _load("traffic", "decode-longprompt-closed32.json")
    mix.update(slots=4, clients=4, strata=4, supply_requests_per_s=800.0,
               max_len=64, page_size=8, prompt_buckets=[8, 16],
               prefill_chunk=16, lead_in_s=0.5,
               prompt_tokens={"median": 16, "sigma": 0.8, "min": 2,
                              "max": 40},
               output_tokens={"median": 6, "sigma": 0.7, "min": 2,
                              "max": 16})
    return {"name": "tiny-closed", "chips": 1, "config": config,
            "traffic": mix, "limits": {"served_logit_gap": 1e-4,
                                       "served_mismatch_share": 0.02}}


@pytest.fixture
def drive():
    """Run a driver on a tiny cell with the look for a chip skipped, and
    judge the run as ``run.py`` does."""
    import importlib

    from benchmark import check
    from benchmark.device import CompileCount

    compiles = CompileCount()

    def go(cell, seconds=1.0, seed=2 ** 31 + 77, **kw):
        driver = importlib.import_module(
            "benchmark.drivers." + cell["traffic"]["driver"])
        run = driver.run(cell=copy.deepcopy(cell), device=CPU, seed=seed,
                         seconds=seconds, trace=False,
                         t_start=time.perf_counter(), compiles=compiles,
                         **kw)
        return run, check.judge(run["numbers"], cell["limits"],
                                run["flags"])

    return go
