"""Multi-host serve fabric tests (DESIGN.md §17): the wire protocol, the
cross-process router (admission, rendezvous affinity, host-drop requeue,
fleet observability), and the supporting primitives (``FaultPlan.lose_host``
determinism, ``StreamingHistogram.merged``, per-host ``ServeMetrics``
attribution, the ``serve_mesh`` local-devices fix).

Tier-1 tests drive the router with IN-PROCESS workers (daemon threads
dialing the router's real TCP socket — full protocol, no interpreter
spawn); the ``distributed``-marked tests use real worker subprocesses,
including a SIGKILL mid-flight and the ``jax.distributed`` bootstrap.
"""

import signal
import socket
import time

import numpy as np
import pytest

from repro.obs import StreamingHistogram
from repro.serve import (FaultPlan, HostDownError, QueueFullError,
                         ServeMetrics, SVDRequest, SVDRouter)
from repro.serve.wire import WireClosed, recv_msg, send_msg
from repro.serve.worker import spawn_worker_process, start_inprocess_worker

BW = 4
FAST_ENGINE = dict(backend="ref", batch_window_s=0.005)


def dense(seed, n=12):
    return np.random.default_rng(seed).standard_normal((n, n))


def check_sigma(req, matrix):
    ref = np.linalg.svd(matrix, compute_uv=False)
    err = float(np.abs(np.asarray(req.sigma) - ref).max() / ref.max())
    assert err < 1e-12, err


def key_of(n, uv=False):
    return (n, BW, "float64", False, uv)


def make_fleet(nhosts=2, *, engine_kwargs=FAST_ENGINE, **router_kwargs):
    router = SVDRouter(**router_kwargs)
    workers = [start_inprocess_worker(router.address, f"w{i}",
                                      engine_kwargs=dict(engine_kwargs))
               for i in range(nhosts)]
    assert router.wait_for_hosts(nhosts, timeout=60)
    return router, workers


# ---------------------------------------------------------------------------
# wire protocol
# ---------------------------------------------------------------------------

def test_wire_roundtrip_bit_exact():
    a, b = socket.socketpair()
    try:
        arrays = {"m": np.random.default_rng(0).standard_normal((7, 7)),
                  "v": np.arange(5, dtype=np.float32)}
        send_msg(a, {"type": "req", "rid": 3, "flag": True}, arrays)
        header, got = recv_msg(b)
        assert (header["type"], header["rid"], header["flag"]) == \
            ("req", 3, True)
        for name, arr in arrays.items():
            assert got[name].dtype == arr.dtype
            assert got[name].shape == arr.shape
            # fp64 must cross the wire BIT-exactly (the sigma oracle
            # downstream is 1e-12 relative; the transport adds zero).
            np.testing.assert_array_equal(got[name], arr)
    finally:
        a.close()
        b.close()


def test_wire_closed_on_eof():
    a, b = socket.socketpair()
    a.close()
    with pytest.raises(WireClosed):
        recv_msg(b)
    b.close()


def test_wire_noncontiguous_array_roundtrip():
    a, b = socket.socketpair()
    try:
        m = np.arange(36, dtype=np.float64).reshape(6, 6)[::2, 1::2]
        assert not m.flags.c_contiguous
        send_msg(a, {"type": "req"}, {"m": m})
        _, got = recv_msg(b)
        np.testing.assert_array_equal(got["m"], m)
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# router + in-process workers: serving, affinity, admission
# ---------------------------------------------------------------------------

def test_router_serves_and_attributes_hosts():
    router, _ = make_fleet(2)
    try:
        mats = [dense(i) for i in range(6)]
        futs = [router.submit(SVDRequest(uid=i, matrix=m, bw=BW))
                for i, m in enumerate(mats)]
        for m, f in zip(mats, futs):
            check_sigma(f.result(timeout=120), m)
        snap = router.metrics.snapshot()
        assert snap["completed"] == 6 and snap["failed"] == 0
        # Per-host attribution sums to the router totals, and the fleet
        # merged histogram's count is exactly the per-host sum.
        assert sum(h["completed"] for h in snap["hosts"].values()) == 6
        fleet = router.fleet()
        per_host = fleet["latency"]["per_host_summary"]
        assert (sum(s["count"] for s in per_host.values())
                == fleet["latency"]["merged_summary"]["count"] == 6)
        assert sorted(fleet["alive_hosts"]) == ["w0", "w1"]
    finally:
        router.stop()


def test_rendezvous_affinity_pins_buckets():
    router, _ = make_fleet(2)
    try:
        owner = router.owner_of(key_of(12))
        assert owner in ("w0", "w1")
        futs = [router.submit(SVDRequest(uid=i, matrix=dense(i), bw=BW))
                for i in range(4)]
        [f.result(timeout=120) for f in futs]
        snap = router.metrics.snapshot()
        # Every same-bucket request landed on the rendezvous owner.
        assert snap["hosts"][owner]["dispatched"] == 4
        other = "w1" if owner == "w0" else "w0"
        assert snap["hosts"].get(other, {}).get("dispatched", 0) == 0
        # The owner is a pure function of (host set, key).
        assert router.owner_of(key_of(12)) == owner
    finally:
        router.stop()


def test_admission_refusals_resolve_futures():
    router = SVDRouter(max_pending=1)
    try:
        bad = router.submit(SVDRequest(uid=0, matrix=np.zeros((3, 4)),
                                       bw=BW))
        with pytest.raises(ValueError):
            bad.result(timeout=5)
        # No hosts: the first submit parks unrouted (counts toward the
        # fleet-wide cap), the second is refused at admission.
        ok = router.submit(SVDRequest(uid=1, matrix=dense(1), bw=BW))
        full = router.submit(SVDRequest(uid=2, matrix=dense(2), bw=BW))
        with pytest.raises(QueueFullError):
            full.result(timeout=5)
        snap = router.metrics.snapshot()
        assert snap["rejected"] == 2 and snap["submitted"] == 1
        assert not ok.done()
    finally:
        router.stop(drain=False)


def test_submit_after_stop_rejects():
    router = SVDRouter()
    router.stop()
    fut = router.submit(SVDRequest(uid=0, matrix=dense(0), bw=BW))
    with pytest.raises(RuntimeError):
        fut.result(timeout=5)


def test_unrouted_request_drains_when_host_arrives():
    router = SVDRouter()
    try:
        m = dense(3)
        fut = router.submit(SVDRequest(uid=0, matrix=m, bw=BW))
        assert router.pending() == 1 and not fut.done()
        start_inprocess_worker(router.address, "w0",
                               engine_kwargs=dict(FAST_ENGINE))
        check_sigma(fut.result(timeout=120), m)
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# host-drop degradation (the §17 requeue guarantee)
# ---------------------------------------------------------------------------

def test_host_drop_requeues_inflight_exactly_once():
    # FaultPlan is deterministic: replaying the same seeded plan against
    # the same host list PREDICTS the victim, so the test can park a
    # burst on the victim's engine (long micro-batch window) before the
    # scripted heartbeat tick fires.
    victim = FaultPlan(seed=11, host_loss_at=(0,)).lose_host(["w0", "w1"])
    plan = FaultPlan(seed=11, host_loss_at=(0,))
    router, _ = make_fleet(
        2, engine_kwargs=dict(backend="ref", batch_window_s=0.75),
        heartbeat_s=60.0, heartbeat_timeout_s=120.0, faults=plan)
    try:
        n = next(c for c in range(8, 64)
                 if router.owner_of(key_of(c)) == victim)
        mats = [dense(i, n) for i in range(5)]
        futs, resolutions = [], []
        for i, m in enumerate(mats):
            f = router.submit(SVDRequest(uid=i, matrix=m, bw=BW))
            f.add_done_callback(lambda _f: resolutions.append(1))
            futs.append(f)
        time.sleep(0.1)          # land in the victim's batch window
        assert router.pending() == 5
        router._heartbeat_tick()     # deterministic tick (no wall clock)
        for m, f in zip(mats, futs):
            check_sigma(f.result(timeout=120), m)
        assert len(resolutions) == 5     # every future exactly once
        snap = router.metrics.snapshot()
        survivor = "w0" if victim == "w1" else "w1"
        assert snap["retried"] == 5
        assert snap["quarantined"] == 1
        assert f"host:{victim}" in snap["quarantined_buckets"]
        assert snap["hosts"][survivor]["requeued"] == 5
        assert snap["hosts"][survivor]["completed"] == 5
        assert victim not in router.alive_hosts()
        assert victim in router.fleet()["dead_hosts"]
        assert plan.snapshot()["host_loss"] == 1
    finally:
        router.stop()


def test_host_down_error_type():
    assert issubclass(HostDownError, ConnectionError)


def test_fault_plan_lose_host_deterministic():
    hosts = ["a", "b", "c"]
    p1 = FaultPlan(seed=5, host_loss_rate=0.5)
    p2 = FaultPlan(seed=5, host_loss_rate=0.5)
    seq1 = [p1.lose_host(hosts) for _ in range(20)]
    seq2 = [p2.lose_host(hosts) for _ in range(20)]
    assert seq1 == seq2
    assert any(v is not None for v in seq1)
    # Scripted ordinals consume the SAME draw count as probabilistic
    # ticks: a plan with no losses still advances its stream identically.
    p3 = FaultPlan(seed=5, host_loss_rate=0.0)
    for _ in range(7):
        assert p3.lose_host(hosts) is None
    assert p3.snapshot()["host_ticks"] == 7


# ---------------------------------------------------------------------------
# fleet observability
# ---------------------------------------------------------------------------

def test_hist_merged_mixed_and_empty():
    h1, h2 = StreamingHistogram(), StreamingHistogram()
    for v in (0.01, 0.02, 0.04):
        h1.add(v)
    h2.add(0.08)
    merged = StreamingHistogram.merged([h1, h2.to_dict()])
    assert merged.count == 4
    assert StreamingHistogram.merged([]).count == 0
    with pytest.raises(ValueError):
        StreamingHistogram.merged(
            [h1, StreamingHistogram(buckets_per_decade=3)])


def test_serve_metrics_host_attribution():
    m = ServeMetrics()
    m.add_host("w0", dispatched=2, completed=1)
    m.add_host("w1", requeued=3)
    snap = m.snapshot()
    assert snap["hosts"]["w0"] == {"dispatched": 2, "completed": 1,
                                   "failed": 0, "requeued": 0}
    assert snap["hosts"]["w1"]["requeued"] == 3


def test_collect_host_stats_and_fleet_render():
    router, _ = make_fleet(2)
    try:
        futs = [router.submit(SVDRequest(uid=i, matrix=dense(i), bw=BW))
                for i in range(3)]
        [f.result(timeout=120) for f in futs]
        stats = router.collect_host_stats(timeout=30)
        assert sorted(stats) == ["w0", "w1"]
        for payload in stats.values():
            assert "snapshot" in payload and "histograms" in payload
        from repro.obs import render_fleet_metrics
        text = render_fleet_metrics(router.fleet())
        assert 'repro_fleet_host_up{host="w0"} 1' in text
        assert "repro_fleet_hosts_alive 2" in text
        for line in text.splitlines():
            if line and not line.startswith("#"):
                name, _, value = line.rpartition(" ")
                float(value)
                assert name
    finally:
        router.stop()


def test_metrics_server_fleet_provider():
    import urllib.request
    from repro.obs import MetricsServer, render_fleet_metrics
    router, _ = make_fleet(1)
    server = MetricsServer(port=0)
    try:
        server.register("router", router.metrics)
        server.register_provider(
            "fleet", lambda: render_fleet_metrics(router.fleet()))
        router.submit(SVDRequest(uid=0, matrix=dense(0),
                                 bw=BW)).result(timeout=120)
        with urllib.request.urlopen(server.url, timeout=10) as resp:
            text = resp.read().decode("utf-8")
        assert "repro_fleet_hosts_alive 1" in text
        assert "repro_serve_requests_total" in text
    finally:
        server.stop()
        router.stop()


# ---------------------------------------------------------------------------
# serve_mesh: local-devices fix (unit-level — multi-process init needs
# real peers)
# ---------------------------------------------------------------------------

def test_serve_mesh_builds_from_local_devices(monkeypatch):
    import jax
    from repro.launch import mesh as meshmod

    local = [object(), object()]
    calls = {}

    def fake_make_mesh(shape, axes, devices=None, axis_types=None):
        calls.update(shape=shape, axes=axes, devices=devices)
        return "MESH"

    # The multi-process regime the fix targets: 2 local, 4 global.
    monkeypatch.setattr(jax, "local_devices", lambda: list(local))
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    monkeypatch.setattr(jax, "make_mesh", fake_make_mesh, raising=False)
    monkeypatch.setenv("REPRO_SERVE_MESH", "auto")
    assert meshmod.serve_mesh() == "MESH"
    # Built from jax.local_devices(), NEVER the global count: a mesh of 4
    # here would double-count the remote host's devices.
    assert calls["shape"] == (2,)
    assert calls["devices"] == local

    monkeypatch.setenv("REPRO_SERVE_MESH", "8")   # clamped to local count
    meshmod.serve_mesh()
    assert calls["shape"] == (2,)


def test_fleet_refuses_several_workers_on_a_tpu_host(monkeypatch):
    from repro.serve import worker as workermod

    monkeypatch.setattr(workermod, "tpu_attached", lambda: True)
    with pytest.raises(RuntimeError, match="TPU host"):
        workermod.check_fleet_fits(2, env={})
    workermod.check_fleet_fits(1, env={})              # one worker: all chips
    workermod.check_fleet_fits(4, env={"JAX_PLATFORMS": "cpu"})
    monkeypatch.setattr(workermod, "tpu_attached", lambda: False)
    workermod.check_fleet_fits(4, env={})              # CPU host


def test_init_distributed_unconfigured_is_noop(monkeypatch):
    from repro.launch import mesh as meshmod
    for var in ("REPRO_DIST_COORDINATOR", "REPRO_DIST_NUM_PROCESSES",
                "REPRO_DIST_PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    assert meshmod.init_distributed() is False
    assert meshmod.init_distributed(coordinator="127.0.0.1:1",
                                    num_processes=1,
                                    process_id=0) is False


# ---------------------------------------------------------------------------
# real worker subprocesses (CI's dedicated `distributed` step)
# ---------------------------------------------------------------------------

@pytest.mark.distributed
def test_subprocess_worker_roundtrip():
    router = SVDRouter()
    proc = spawn_worker_process(router.address, "w0", backend="ref")
    try:
        assert router.wait_for_hosts(1, timeout=240)
        mats = [dense(i) for i in range(3)]
        futs = [router.submit(SVDRequest(uid=i, matrix=m, bw=BW))
                for i, m in enumerate(mats)]
        for m, f in zip(mats, futs):
            check_sigma(f.result(timeout=300), m)
        info = router.fleet()["hosts"]["w0"]
        assert info["alive"] and info["devices"] >= 1
    finally:
        router.stop()
        try:
            proc.wait(timeout=30)
        except Exception:
            proc.kill()


@pytest.mark.distributed
def test_subprocess_sigkill_requeues_to_survivor():
    router = SVDRouter(heartbeat_s=0.25, heartbeat_timeout_s=2.0)
    procs = {f"w{i}": spawn_worker_process(router.address, f"w{i}",
                                           backend="ref", window_ms=500.0)
             for i in range(2)}
    try:
        assert router.wait_for_hosts(2, timeout=240)
        # Broadcast-warm so the survivor never compiles under load.
        router.warm([SVDRequest(uid=-1, matrix=dense(99), bw=BW)],
                    timeout=300)
        victim = router.owner_of(key_of(12))
        mats = [dense(i) for i in range(4)]
        futs = [router.submit(SVDRequest(uid=i, matrix=m, bw=BW))
                for i, m in enumerate(mats)]
        procs[victim].send_signal(signal.SIGKILL)
        for m, f in zip(mats, futs):
            check_sigma(f.result(timeout=300), m)
        snap = router.metrics.snapshot()
        assert snap["retried"] >= 1
        assert victim in router.fleet()["dead_hosts"]
        assert procs[victim].wait(timeout=30) is not None
    finally:
        router.stop()
        for p in procs.values():
            try:
                p.wait(timeout=30)
            except Exception:
                p.kill()


@pytest.mark.distributed
def test_jax_distributed_bootstrap_two_processes():
    # The workers join ONE multi-process jax via the coordination service
    # (no kill chaos here — a dead peer fatally cascades, DESIGN.md §17).
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    router = SVDRouter()
    procs = [spawn_worker_process(router.address, f"w{i}", backend="ref",
                                  devices=2, coordinator=coordinator,
                                  num_processes=2, process_id=i)
             for i in range(2)]
    try:
        assert router.wait_for_hosts(2, timeout=240)
        hosts = router.fleet()["hosts"]
        local_total = sum(v["devices"] for v in hosts.values())
        idx = sorted(v["process_index"] for v in hosts.values())
        assert idx == [0, 1]
        for v in hosts.values():
            assert v["processes"] == 2
            assert v["devices"] == 2
            assert v["global_devices"] == local_total == 4
        m = dense(7)
        check_sigma(router.submit(
            SVDRequest(uid=0, matrix=m, bw=BW)).result(timeout=300), m)
    finally:
        router.stop()
        for p in procs:
            try:
                p.wait(timeout=30)
            except Exception:
                p.kill()
