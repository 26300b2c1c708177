"""Test configuration.

Mirrors the reference's trick of simulating a 4-node cluster inside one
JVM (TEST/optim/DistriOptimizerSpec.scala:38-47 uses Engine.init(4, 4,
onSpark=true) with local[4]): here we force an 8-device virtual CPU
topology so every mesh/pjit/collective path runs on a laptop-grade host.
Must set env BEFORE jax is imported anywhere.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np
import pytest


@pytest.fixture(scope="session", autouse=True)
def _flight_bundle_quarantine(tmp_path_factory):
    """Tests that enable the tracer implicitly arm the flight recorder
    (``BIGDL_TPU_FLIGHT`` unset follows ``tracer.enabled``); without a
    flight dir its bundles would land in the repo checkout.  Quarantine
    them in a session tmp dir and disarm any lingering global recorder
    at session end so the interpreter-atexit dump cannot fire into
    closed logging streams."""
    prev = os.environ.get("BIGDL_TPU_FLIGHT_DIR")
    if prev is None:
        os.environ["BIGDL_TPU_FLIGHT_DIR"] = str(
            tmp_path_factory.mktemp("flight"))
    yield
    from bigdl_tpu.telemetry import flightrecorder

    flightrecorder.set_global(None)
    if prev is None:
        os.environ.pop("BIGDL_TPU_FLIGHT_DIR", None)


@pytest.fixture
def rng():
    import jax

    return jax.random.PRNGKey(0)


@pytest.fixture
def np_rng():
    return np.random.RandomState(0)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running accuracy-parity runs")


@pytest.fixture
def read_each_tick_first(monkeypatch):
    """Call it to make overlapping impossible in every ``DecodeEngine``:
    a turn enqueues its tick from the host mirrors and reads it before
    it returns, as the decode loop did before it kept a tick in flight
    (a tick found in flight is read first, and that is the turn).  The
    oracle for the overlapped loop, and the synchronous dense arm a
    speculative round is held against."""
    from bigdl_tpu.serving.decode import DecodeEngine

    def run_tick(self):
        flight, self._flight = self._flight, None
        if flight is None:
            flight = self._dispatch_tick(self._rows_due(), None)
        nxt, self._served = self._read_tick(flight, whole=True)
        return nxt

    return lambda: monkeypatch.setattr(DecodeEngine, "_run_tick", run_tick)


@pytest.fixture
def until():
    """``until(done)``: poll ``done()`` for at most 30 s, then assert."""
    import time

    def wait(done):
        deadline = time.monotonic() + 30
        while not done() and time.monotonic() < deadline:
            time.sleep(0.001)
        assert done()

    return wait


@pytest.fixture
def interpreted_paged_attn(monkeypatch):
    """Route ``apply_paged`` as on the TPU, with the ``paged_attn``
    kernel run by the Pallas interpreter (this tier has no chip)."""
    import functools

    from bigdl_tpu.ops.pallas import paged_attention

    monkeypatch.setenv("BIGDL_TPU_FORCE_PALLAS", "1")
    monkeypatch.setattr(
        paged_attention, "paged_attn",
        functools.partial(paged_attention.paged_attn, interpret=True))
