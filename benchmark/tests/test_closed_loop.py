"""The closed loop: the stratified supply, the feeder that keeps
``clients`` requests in flight, ``decode_tokens_per_s`` against a hand
count, and the faults a closed-loop cell can have."""
import copy
import json
import os

import numpy as np
import pytest

from conftest import ROOT


def closed_mix():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "decode-longprompt-closed32.json")) as f:
        return json.load(f)


def sizes(stream):
    return [(r["prompt"].size, r["max_new"]) for r in stream]


def test_closed_supply_is_a_pure_function_of_the_seed():
    from benchmark import traffic

    mix = closed_mix()
    a = traffic.request_stream(mix, 2 ** 31 + 5, 48.0, 16032)
    b = traffic.request_stream(mix, 2 ** 31 + 5, 48.0, 16032)
    c = traffic.request_stream(mix, 7, 48.0, 16032)
    assert sizes(a) == sizes(b) and all(
        np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    # another seed: the same multiset of sizes, in another order
    assert sorted(sizes(a)) == sorted(sizes(c)) and sizes(a) != sizes(c)
    # a supply, not a schedule: due is the order of sending, and what
    # is measured is the driver's to say
    assert [r["due"] for r in a] == list(range(len(a)))
    assert not any(r["measured"] for r in a)
    span = mix["lead_in_s"] + 48.0
    assert len(a) == int(mix["supply_requests_per_s"] * span) \
        // mix["strata"] * mix["strata"]


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 77])
def test_every_block_holds_one_request_of_each_band(seed):
    from benchmark import traffic

    mix = closed_mix()
    stream = traffic.request_stream(mix, seed, 48.0, 16032)
    strata = mix["strata"]
    prompt = np.asarray([r["prompt"].size for r in stream])
    blocks = len(stream) // strata
    # a request's band: its rank by prompt length among the whole set
    rank = np.empty(len(stream), np.int64)
    rank[np.lexsort((np.asarray([r["max_new"] for r in stream]),
                     prompt))] = np.arange(len(stream))
    band = (rank // blocks).reshape(blocks, strata)
    assert all(sorted(row) == list(range(strata)) for row in band)
    # so every stretch of ``strata`` requests offers the same work
    answers = np.asarray([r["max_new"] for r in stream]).reshape(
        blocks, strata).sum(1)
    prompts = prompt.reshape(blocks, strata).sum(1)
    assert prompts.max() / prompts.min() < 1.05
    assert answers.max() / answers.min() < 1.06


def test_a_mix_without_strata_keeps_its_order():
    """``strata`` is what deals the blocks: the same closed mix without
    it is one permutation of the whole set."""
    from benchmark import traffic

    mix = closed_mix()
    plain = {k: v for k, v in mix.items() if k != "strata"}
    a = traffic.request_stream(plain, 3, 48.0, 16032)
    b = traffic.request_stream(mix, 3, 48.0, 16032)
    assert sorted(sizes(a)) == sorted(sizes(b)) and sizes(a) != sizes(b)


def test_closed_loop_driver_end_to_end(tiny_closed_cell, drive):
    run, verdict = drive(tiny_closed_cell, seconds=2.0)
    clients = tiny_closed_cell["traffic"]["clients"]
    assert verdict["correct"], verdict
    assert verdict["compared"]["supply_outlasted_window"] == [0, 0]
    assert run["attempted"] > 5 and run["failed"] == 0
    # the clients are never exceeded, and never idle while requests
    # remain: at the window's open and close and at its mean
    assert max(run["waiting"]) <= clients
    assert clients - 0.5 < run["in_flight_mean"] <= clients
    assert run["end_to_end"]["decode_tokens_per_s"] > 0


def test_decode_tokens_per_s_is_a_count_of_time_stamps():
    from benchmark.drivers import decode

    class R:
        sent, done = 0.0, 9.0

        def __init__(self, times):
            self.token_times = None if times is None else np.asarray(times)

    requests = [R([0.5, 1.0, 2.0]),        # sent in the lead-in
                R([1.0, 1.5, 3.0, 3.5]),   # straddles the close
                R([3.01, 4.0]), R(None)]   # after it; unanswered
    assert decode.tokens_stamped_in(requests, 1.0, 3.0) == 5
    a, b = R([]), R([])
    a.sent, a.done, b.sent, b.done = 0.0, 2.0, 1.5, None
    assert decode.in_flight_mean([a, b], 1.0, 3.0) == pytest.approx(1.25)


def test_exhausted_supply_is_not_correct(tiny_closed_cell, drive):
    cell = copy.deepcopy(tiny_closed_cell)
    cell["traffic"]["supply_requests_per_s"] = 40.0
    run, verdict = drive(cell, seconds=2.0)
    assert not verdict["correct"]
    assert verdict["compared"]["supply_outlasted_window"] == [1, 0]


def test_altered_token_in_the_closed_loop_is_not_correct(
        tiny_closed_cell, drive, monkeypatch):
    from bigdl_tpu.serving.decode import DecodeEngine

    real = DecodeEngine._run_tick
    vocab = tiny_closed_cell["config"]["model"]["vocab_size"]

    def altered(self):
        return (np.asarray(real(self)) + 1) % vocab

    monkeypatch.setattr(DecodeEngine, "_run_tick", altered)
    _, verdict = drive(tiny_closed_cell, seconds=2.0)
    assert not verdict["correct"], verdict
