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
    mix = _load("traffic", "decode-steady.json")
    mix.update(slots=4, max_len=64, page_size=8, prompt_buckets=[8, 32],
               prefill_batch_sizes=[1, 2], rate=20.0, lead_in_s=0.5,
               prompt_tokens={"median": 8, "sigma": 1.0, "min": 2,
                              "max": 32},
               output_tokens={"median": 8, "sigma": 0.8, "min": 2,
                              "max": 24})
    return {"name": "tiny-decode", "chips": 1, "config": _tiny_config(),
            "traffic": mix,
            "limits": _load("cells", "lm-decode-steady.json")["limits"]}


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
