"""Each driver end to end at tiny widths (the look for a chip skipped in
the test only), the control in a lower precision, and the timed path
broken underneath: ``correct`` has to come out false."""
import numpy as np
import pytest


def test_train_driver_end_to_end_is_correct(tiny_train_cell, drive):
    run, verdict = drive(tiny_train_cell)
    assert verdict["correct"], verdict
    assert run["iterations"] > 0 and run["end_to_end"][
        "train_records_per_s"] > 0
    assert run["flags"]["compiles_in_window"]
    from benchmark.run import read_metric

    run["trace"] = None
    run["trace_span"] = {"seconds": 1.0, "iterations": 3,
                         "data_stall_s": 0.1}
    assert read_metric("data_stall_share.train", run) == pytest.approx(10)
    assert read_metric("flash_fwd_roofline.train", run) is None


def test_decode_driver_end_to_end_is_correct(tiny_decode_cell, drive):
    run, verdict = drive(tiny_decode_cell, seconds=2.0)
    assert verdict["correct"], verdict
    assert run["attempted"] > 10 and run["failed"] == 0
    assert run["end_to_end"]["norm_latency_p95_ms"] > 0
    assert run["completed_tokens_per_s"] > 0
    assert run["ticks"] > 0 and 0 < run["slot_occupancy"] <= 1


def test_training_control_and_half_batch_are_not_correct(tiny_train_cell):
    """The reference in the program's place, in fp8 and with half of
    each batch left out, judged by the cell's own limits."""
    from benchmark import check, control

    readings = control.train_controls(tiny_train_cell, seed=2 ** 31 + 3)
    for name, numbers in readings.items():
        verdict = check.judge(numbers, tiny_train_cell["limits"], {})
        assert not verdict["correct"], (name, verdict)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_train_step_is_not_correct(tiny_train_cell, drive,
                                          monkeypatch, fault):
    import bigdl_tpu.optim.optimizer as engine

    real = engine.make_train_step

    def broken(*a, **kw):
        step = real(*a, **kw)

        def unchanged(params, state, opt_states, i, rng, x, y, lrs):
            loss = step(params, state, opt_states, i, rng, x, y, lrs)[3]
            return params, state, opt_states, loss

        def half(params, state, opt_states, i, rng, x, y, lrs):
            n = x.shape[0] // 2
            return step(params, state, opt_states, i, rng, x[:n], y[:n],
                        lrs)

        return unchanged if fault == "state_unchanged" else half

    monkeypatch.setattr(engine, "make_train_step", broken)
    _, verdict = drive(tiny_train_cell)
    assert not verdict["correct"], verdict


def test_altered_token_is_not_correct(tiny_decode_cell, drive,
                                      monkeypatch):
    from bigdl_tpu.serving.decode import DecodeEngine

    real = DecodeEngine._run_tick
    vocab = tiny_decode_cell["config"]["model"]["vocab_size"]

    def altered(self):
        return (np.asarray(real(self)) + 1) % vocab

    monkeypatch.setattr(DecodeEngine, "_run_tick", altered)
    _, verdict = drive(tiny_decode_cell, seconds=2.0)
    assert not verdict["correct"], verdict
    assert verdict["compared"]["served_logit_gap"][0] > \
        verdict["compared"]["served_logit_gap"][1]
