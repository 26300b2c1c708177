"""Multi-host (multi-PROCESS) distributed paths: ``put_batch``'s
process_count() > 1 branch, the jax.distributed join, and — VERDICT r4
missing #2 — the COMPOSED parallelism kinds crossing a real OS-process
boundary: dp across processes x tp within (dp_tp) and the pipeline
schedule spanning processes (pp).  Each 2-process run must match the
single-process 4-device run of the identical config — the TPU-era
analog of the reference's local[4] cluster simulation
(TEST/optim/DistriOptimizerSpec.scala:38-47).
"""
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "multihost_worker.py")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _env(local_devices: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={local_devices}")
    return env


# gloo's TCP transport occasionally mispairs buffers while the mesh's
# collectives are being set up (crash signature below, SIGABRT); it is
# a setup-time race in the transport, not a property of the program —
# retry the whole launch on a fresh port, fail on anything else
_GLOO_TRANSIENT = ("gloo::EnforceNotMet", "op.preamble.length",
                   "Connection reset by peer", "heartbeat timeout")


def _run_workers(mode: str, nproc: int, timeout: int = 420,
                 attempts: int = 3):
    """Launch ``nproc`` workers (2 local devices each; 4 when
    single-process) and return their parsed JSON lines."""
    for attempt in range(attempts):
        port = _free_port()
        env = _env(4 // nproc)
        procs = [
            subprocess.Popen(
                [sys.executable, WORKER, str(pid), str(nproc), str(port),
                 mode],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, cwd=REPO,
            )
            for pid in range(nproc)
        ]
        outs, errs, failed = [], [], False
        for p in procs:
            try:
                out, err = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                pytest.fail(f"multi-host worker hung (mode={mode})")
            errs.append(err)
            if p.returncode != 0:
                failed = True
                continue
            line = [l for l in out.splitlines() if l.startswith("{")][-1]
            outs.append(json.loads(line))
        if not failed:
            return sorted(outs, key=lambda o: o["pid"])
        transient = any(sig in err for err in errs
                        for sig in _GLOO_TRANSIENT)
        if not transient or attempt == attempts - 1:
            tail = "\n".join(err[-2000:] for err in errs if err)
            pytest.fail(f"multi-host worker failed (mode={mode}, "
                        f"attempt {attempt + 1}/{attempts}):\n{tail}")
    raise AssertionError("unreachable")


def _assert_lockstep(a, b, local_batch):
    assert a["global_devices"] == b["global_devices"] == 4
    assert a["local_devices"] == b["local_devices"] == 2
    assert a["local_batch"] == b["local_batch"] == local_batch
    # both processes saw the same assembled global batch
    assert a["gmean"] == b["gmean"]
    # lockstep SPMD: identical loss trajectory and final params
    assert a["losses"] == b["losses"]
    assert a["digest"] == b["digest"]
    assert np.isfinite(a["loss"])


def _assert_parity(two_proc, single):
    """2-process run reproduces the single-process 4-device run (same
    global batches, same mesh logic; collective reduction order may
    differ -> tight allclose, not bit-equal)."""
    assert single["global_devices"] == 4
    np.testing.assert_allclose(two_proc["gmean"], single["gmean"],
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(two_proc["losses"], single["losses"],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(two_proc["digest"], single["digest"],
                               rtol=1e-4, atol=0)


@pytest.mark.slow
def test_two_process_distributed_training():
    a, b = _run_workers("dp", 2)
    _assert_lockstep(a, b, local_batch=8)
    (single,) = _run_workers("dp", 1)
    _assert_parity(a, single)


@pytest.mark.slow
def test_two_process_dp_across_tp_within():
    """dp spans the process boundary, tp (Megatron rules) lives inside
    each process; parity vs the same mesh in one process."""
    a, b = _run_workers("dp_tp", 2)
    _assert_lockstep(a, b, local_batch=8)
    (single,) = _run_workers("dp_tp", 1)
    _assert_parity(a, single)


@pytest.mark.slow
def test_two_process_pipeline_spanning_processes():
    """pipe stages on different processes: every ppermute activation
    hop (fwd and transpose/bwd) crosses hosts; each process feeds the
    full batch (it addresses every data shard)."""
    a, b = _run_workers("pp", 2)
    # pp feeds the full batch from each process
    _assert_lockstep(a, b, local_batch=16)
    (single,) = _run_workers("pp", 1)
    _assert_parity(a, single)


# ---------------------------------------------------------------------------
# elastic fault tolerance (docs/distributed.md recovery state machine)
# ---------------------------------------------------------------------------
# load-tolerant elastic cadence: the default 3s stale timeout reads a
# descheduled-but-healthy peer as dead on a loaded CI box (a false
# peer_dead tears a generation down mid-test), so these runs keep the
# fast heartbeat but widen the staleness window; every wait below is
# derived from these knobs instead of hardcoded sleeps
_HEARTBEAT_S = 0.25
_STALE_S = 10.0


def _elastic_env(iters: int, ckpt_every: int) -> dict:
    env = _env(2)
    env["BIGDL_ELASTIC_ITERS"] = str(iters)
    env["BIGDL_ELASTIC_CKPT_EVERY"] = str(ckpt_every)
    env["BIGDL_TPU_ELASTIC_HEARTBEAT_S"] = str(_HEARTBEAT_S)
    env["BIGDL_TPU_ELASTIC_STALE_S"] = str(_STALE_S)
    # exercise the numerics observatory across the process boundary:
    # each worker's drained grad norms ship with its metrics snapshots
    # (the cluster grad-norm-skew acceptance path)
    env["BIGDL_TPU_NUMERICS"] = "1"
    # agents default the shared run dir to <workdir>/telemetry; the
    # direct-spawned baseline worker must stay unshipped
    env.pop("BIGDL_TPU_TELEMETRY_DIR", None)
    return env


def _set_elastic_knobs(monkeypatch):
    """The agents run in-process (threads): they read the cadence from
    os.environ, not the worker env dict."""
    monkeypatch.setenv("BIGDL_TPU_ELASTIC_HEARTBEAT_S", str(_HEARTBEAT_S))
    monkeypatch.setenv("BIGDL_TPU_ELASTIC_STALE_S", str(_STALE_S))


def _wait_until(cond, what: str, budget_s: float = 240.0):
    """Bounded poll on the heartbeat cadence: returns the moment
    ``cond`` holds, fails with ``what`` when the budget burns."""
    import time

    deadline = time.monotonic() + budget_s
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(_HEARTBEAT_S / 2)
    pytest.fail(f"timed out after {budget_s:.0f}s waiting for {what}")


def _join_agents(threads, results, budget_s: float = 420.0):
    """Join agent threads in stale-timeout slices up to a hard budget —
    a partial hang reports WHICH agent wedged and what the others
    returned, instead of a bare join timeout."""
    import time

    deadline = time.monotonic() + budget_s
    pending = list(threads)
    while pending and time.monotonic() < deadline:
        for t in list(pending):
            t.join(timeout=_STALE_S)
            if not t.is_alive():
                pending.remove(t)
    if pending:
        pytest.fail(
            f"agents still running after {budget_s:.0f}s: "
            f"pending={[t.name for t in pending]} results={results}")


def _agent_thread(agent, results, key):
    import threading

    def run():
        try:
            results[key] = agent.run()
        except Exception as e:  # surfaced by the joining test body
            results[key] = f"error: {e!r}"

    t = threading.Thread(target=run, name=f"agent-{key}", daemon=True)
    t.start()
    return t


def _composed_losses(workdir: str) -> dict:
    """iteration -> loss, preferring the NEWEST generation that
    recorded it (replayed iterations must agree anyway — resume is
    bit-equal — but the newest generation always covers the tail)."""
    import glob

    out = {}
    for path in sorted(glob.glob(os.path.join(workdir, "losses-g*.jsonl"))):
        for line in open(path):
            rec = json.loads(line)
            if rec["rank"] == 0:
                out[rec["it"]] = (rec["gen"], rec["loss"])
    return {it: loss for it, (gen, loss) in out.items()}


def _baseline_losses(tmpdir: str, iters: int, ckpt_every: int) -> dict:
    """Uninterrupted world-1 run of the same deterministic job."""
    wd = os.path.join(tmpdir, "baseline")
    os.makedirs(wd)
    env = _elastic_env(iters, ckpt_every)
    env.update(BIGDL_ELASTIC_WORKDIR=wd, BIGDL_ELASTIC_GEN="1",
               BIGDL_ELASTIC_RANK="0", BIGDL_ELASTIC_WORLD="1")
    subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.distributed.worker"],
        env=env, cwd=REPO, check=True, timeout=420,
        capture_output=True)
    return _composed_losses(wd)


@pytest.mark.slow
def test_elastic_kill9_survivor_reforms_and_matches_baseline(
        tmp_path, monkeypatch):
    """kill -9 one worker mid-run: its agent resigns (policy=shrink),
    the survivor's watchdog flags the dead peer, re-forms the mesh over
    generation 2 (world 1), restores the last COMMIT, and the composed
    loss curve matches an uninterrupted run (global batch stream is
    world-size invariant)."""
    import signal

    from bigdl_tpu.distributed.elastic import ElasticAgent

    _set_elastic_knobs(monkeypatch)
    iters, ckpt_every = 800, 20
    wd = str(tmp_path / "job")
    env = _elastic_env(iters, ckpt_every)
    results = {}
    a0 = ElasticAgent(wd, "h0", policy="restart", env=env,
                      rendezvous_timeout_s=180.0)
    a1 = ElasticAgent(wd, "h1", policy="shrink", env=env,
                      rendezvous_timeout_s=180.0)
    t0 = _agent_thread(a0, results, "h0")
    t1 = _agent_thread(a1, results, "h1")

    # wait for the first commit, then kill -9 h1's worker
    ckpt_root = os.path.join(wd, "ckpt")
    pid_file = os.path.join(wd, "worker-g1-h1.pid")
    _wait_until(
        lambda: os.path.isdir(ckpt_root) and any(
            os.path.exists(os.path.join(ckpt_root, d, "COMMIT"))
            for d in os.listdir(ckpt_root))
        and os.path.exists(pid_file),
        "the first commit + a live h1 worker pid")
    os.kill(int(open(pid_file).read()), signal.SIGKILL)

    _join_agents([t1, t0], results)
    assert results.get("h1") == "left", results
    assert results.get("h0") == "done", results

    # the survivor went through >= one re-formation
    report = json.load(open(os.path.join(wd, "agent-h0-watchdog.json")))
    assert report["counters"]["peer_failures"] >= 1
    gens = {int(f.split("-g")[1].split("-")[0])
            for f in os.listdir(wd) if f.startswith("losses-g")}
    assert max(gens) >= 2, gens

    # final generation finished the full budget on world 1
    final = json.load(open(os.path.join(
        wd, f"worker-result-g{max(gens)}-r0.json")))
    assert final["world"] == 1 and final["iterations"] == iters

    composed = _composed_losses(wd)
    assert set(composed) == set(range(1, iters + 1))
    baseline = _baseline_losses(str(tmp_path), iters, ckpt_every)
    its = sorted(baseline)
    np.testing.assert_allclose(
        [composed[i] for i in its], [baseline[i] for i in its],
        rtol=1e-4, atol=1e-5)

    # ---- cluster observability plane (ISSUE 8 acceptance) ------------
    # both agents and both generations of workers shipped into ONE run
    # dir; the offline merge must put each host on its own lane with
    # aligned clocks and the elastic sequence as ordered instants
    from bigdl_tpu.telemetry.cluster import ClusterAggregator

    agg = ClusterAggregator(os.path.join(wd, "telemetry")).load()
    assert {"h0", "h1"} <= set(agg.hosts)

    trace = agg.merge_trace()
    json.loads(json.dumps(trace))  # one valid trace_event JSON blob
    events = trace["traceEvents"]
    lanes = {e["args"]["name"].split()[0]: e["pid"] for e in events
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert {"h0", "h1"} <= set(lanes)
    assert all(e["ts"] >= 0 for e in events if "ts" in e)

    # aligned clocks: the two hosts' generation-1 span windows overlap
    # on the shared timeline (they trained it together)
    def lane_ts(host):
        return [e["ts"] for e in events
                if e.get("pid") == lanes[host] and e.get("ph") == "X"]

    h0_ts, h1_ts = lane_ts("h0"), lane_ts("h1")
    assert h0_ts and h1_ts
    assert min(h0_ts) <= max(h1_ts) and min(h1_ts) <= max(h0_ts)

    # death -> re-form -> restore -> resume, correlated across lanes:
    # h0's agent flags the dead peer, bumps to generation 2, the new
    # worker starts and replays the last commit
    def first_ts(name, **match):
        ts = [e["ts"] for e in events if e["name"] == name
              and all(e.get("args", {}).get(k) == v
                      for k, v in match.items())]
        return min(ts) if ts else None

    t_dead = first_ts("peer_dead")
    t_bump = first_ts("gen_bump", gen=2)
    t_start = first_ts("worker_start", gen=2)
    t_restore = first_ts("resharding_restore")
    assert None not in (t_dead, t_bump, t_start, t_restore), \
        (t_dead, t_bump, t_start, t_restore)
    assert t_dead < t_bump < t_start <= t_restore

    # cluster rollup sees real steps and world throughput
    summary = agg.cluster_summary()
    assert summary["cluster"]["step_p50_ms"] > 0
    assert summary["per_host"]["h0"]["n_steps"] > 0
    assert summary["cluster"]["world_throughput"] > 0
    assert "peer_dead" in summary["per_host"]["h0"]["events"]

    # ---- numerics observatory (ISSUE 11 acceptance) ------------------
    # BIGDL_TPU_NUMERICS=1 in the worker env: each host's drained grad
    # norms shipped with its metrics, so the rollup quantifies per-host
    # skew, the merged trace carries a grad-norm counter lane per host,
    # and cluster_top --json surfaces both for this 2-process run
    assert summary["per_host"]["h0"]["grad_norm"] > 0
    gskew = summary["cluster"]["grad_norm_skew"]
    assert gskew["hosts"] >= 1 and gskew["mean"] > 0
    gn_lanes = {e["pid"] for e in events
                if e.get("ph") == "C" and e["name"] == "grad norm"}
    assert lanes["h0"] in gn_lanes and lanes["h1"] in gn_lanes

    from tools import cluster_top

    rc = cluster_top.main([os.path.join(wd, "telemetry"), "--json"])
    assert rc == 0


@pytest.mark.slow
def test_elastic_join_grows_the_mesh(tmp_path, monkeypatch):
    """A runs alone; B shows up -> A's watchdog flags the join request,
    A drains + commits, both re-rendezvous into generation 2 (world 2)
    and finish in lockstep (equal digests)."""
    from bigdl_tpu.distributed.elastic import ElasticAgent
    from bigdl_tpu.distributed.rendezvous import FileRendezvous

    _set_elastic_knobs(monkeypatch)
    wd = str(tmp_path / "job")
    env = _elastic_env(1200, 25)
    results = {}
    a0 = ElasticAgent(wd, "h0", policy="restart", env=env,
                      rendezvous_timeout_s=180.0)
    t0 = _agent_thread(a0, results, "h0")

    # wait until A formed generation 1 alone, then bring B in
    probe = FileRendezvous(os.path.join(wd, "rendezvous"), "probe")

    def gen1_formed():
        m = probe.latest_generation()
        return bool(m and m["members"] == ["h0"])

    _wait_until(gen1_formed, "generation 1 to form", budget_s=120.0)
    a1 = ElasticAgent(wd, "h1", policy="restart", env=env,
                      rendezvous_timeout_s=180.0)
    t1 = _agent_thread(a1, results, "h1")

    _join_agents([t0, t1], results)
    assert results.get("h0") == "done", results
    assert results.get("h1") == "done", results

    gens = {int(f.split("-g")[1].split("-")[0])
            for f in os.listdir(wd) if f.startswith("losses-g")}
    assert max(gens) >= 2, gens
    finals = [json.load(open(os.path.join(
        wd, f"worker-result-g{max(gens)}-r{r}.json"))) for r in (0, 1)]
    assert all(f["world"] == 2 for f in finals)
    np.testing.assert_allclose(finals[0]["digest"], finals[1]["digest"],
                               rtol=1e-6)
    report = json.load(open(os.path.join(wd, "agent-h0-watchdog.json")))
    assert report["counters"]["peer_failures"] >= 1  # the join event
