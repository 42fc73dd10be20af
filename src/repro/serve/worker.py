"""Worker host for the multi-host serve fabric (DESIGN.md §17).

One :class:`ServeWorker` wraps one :class:`~repro.serve.AsyncSVDEngine`
(today's full single-host fabric: micro-batching, fault ladder,
quarantine, degraded tier) behind a wire connection to the front-end
router (``serve/router.py``).  The worker is the *server of compute* but
the *client of the socket*: it dials the router's listen address, sends
one ``hello``, then answers ``req``/``ping``/``stats``/``stop`` frames
until the connection closes.  A closed connection means the router is
gone — the worker drains nothing (nobody is listening for results) and
exits.

Deliberately NOT coupled to ``jax.distributed``: the fabric's
multi-processness lives at the socket level, so killing one worker can
never cascade through the XLA coordination service and take the
survivors with it (measured: a dead peer under an active
``jax.distributed`` client fatally terminates every other process).
``--coordinator`` opts a worker in to the multi-process JAX bootstrap
(``launch.mesh.init_distributed``) for deployments that want
process-spanning meshes — tested in CI *without* kill chaos.

Three entry points:

* :class:`ServeWorker` — the protocol loop over an existing socket.
* :func:`start_inprocess_worker` — worker on a daemon thread in THIS
  process (tier-1-safe router tests: full wire protocol, no subprocess).
* :func:`spawn_worker_process` / ``python -m repro.serve.worker`` — a
  real worker process (the CI multihost gate and ``serve_load --hosts``).
"""

from __future__ import annotations

import argparse
import glob
import os
import socket
import subprocess
import sys
import threading

import numpy as np

from repro.serve.wire import WireClosed, recv_msg, send_msg

__all__ = ["ServeWorker", "start_inprocess_worker", "spawn_worker_process",
           "check_fleet_fits", "tpu_attached"]


class ServeWorker:
    """Protocol loop: one engine, one router connection.

    ``engine`` defaults to a fresh ``AsyncSVDEngine(**engine_kwargs)``
    built lazily in :meth:`serve_forever` (keeps construction — and the
    jax import — off the caller's thread for in-process workers).
    """

    def __init__(self, sock: socket.socket, *, host_id: str,
                 engine=None, engine_kwargs: dict | None = None):
        self.sock = sock
        self.host_id = str(host_id)
        self.engine = engine
        self.engine_kwargs = dict(engine_kwargs or {})
        self._send_lock = threading.Lock()
        self._pings = 0

    # ------------------------------------------------------------------

    def _send(self, header: dict, arrays=None) -> bool:
        """Send one frame; False (never raises) once the router is gone —
        a result with nobody to deliver it to is not a worker failure."""
        try:
            with self._send_lock:
                send_msg(self.sock, header, arrays)
            return True
        except (OSError, WireClosed):
            return False

    def _hello(self) -> None:
        import jax
        from repro.core.distributed import process_info
        pid_idx, nproc = process_info()
        self._send({"type": "hello", "host_id": self.host_id,
                    "pid": os.getpid(),
                    "devices": len(jax.local_devices()),
                    "global_devices": jax.device_count(),
                    "process_index": pid_idx, "processes": nproc})

    def _on_request(self, header: dict, arrays: dict) -> None:
        from repro.serve.engine import SVDRequest
        rid = int(header["rid"])
        req = SVDRequest(uid=int(header.get("uid", rid)),
                         matrix=arrays["matrix"],
                         bw=int(header.get("bw", 32)),
                         banded=bool(header.get("banded", False)),
                         compute_uv=bool(header.get("compute_uv", False)))
        fut = self.engine.submit(req, timeout_s=header.get("timeout_s"))
        fut.add_done_callback(lambda f, rid=rid, req=req:
                              self._send_result(rid, req, f))

    def _send_result(self, rid: int, req, fut) -> None:
        exc = fut.exception()
        if exc is not None:
            self._send({"type": "res", "rid": rid, "ok": False,
                        "error": str(exc),
                        "error_type": type(exc).__name__})
            return
        arrays = {"sigma": np.asarray(req.sigma)}
        if req.compute_uv:
            arrays["u"] = np.asarray(req.u)
            arrays["vt"] = np.asarray(req.vt)
        self._send({"type": "res", "rid": rid, "ok": True,
                    "tier": self.engine.metrics.tier_of_bucket(req.key())},
                   arrays)

    def _on_stats(self, header: dict) -> None:
        """Per-host observability payload: the engine's full metrics
        snapshot plus the latency histograms as mergeable dicts — the
        router folds these into the fleet view (DESIGN.md §16/§17)."""
        hists = self.engine.metrics.histograms()
        self._send({"type": "stats_res", "host_id": self.host_id,
                    "token": header.get("token"),
                    "snapshot": self.engine.metrics.snapshot(),
                    "histograms": {
                        "tiers": {t: h.to_dict()
                                  for t, h in hists["tiers"].items()},
                        "queue_age": hists["queue_age"].to_dict()},
                    "faults": (self.engine.faults.snapshot()
                               if self.engine.faults is not None else None)})

    # ------------------------------------------------------------------

    def serve_forever(self) -> None:
        """Run the protocol until ``stop`` or the router disconnects."""
        if self.engine is None:
            from repro.serve.async_engine import AsyncSVDEngine
            self.engine = AsyncSVDEngine(**self.engine_kwargs)
        self.engine.start()
        self._hello()
        drain = False
        try:
            while True:
                try:
                    header, arrays = recv_msg(self.sock)
                except WireClosed:
                    break                    # router gone: no drain target
                t = header.get("type")
                if t == "req":
                    self._on_request(header, arrays)
                elif t == "ping":
                    self._pings += 1
                    self._send({"type": "pong", "host_id": self.host_id,
                                "seq": header.get("seq"),
                                "pending": self.engine.pending(),
                                "health": self.engine.metrics.health()[
                                    "status"]})
                elif t == "stats":
                    self._on_stats(header)
                elif t == "stop":
                    drain = True
                    break
        finally:
            try:
                self.engine.stop(drain=drain)
            finally:
                try:
                    self.sock.close()
                except OSError:
                    pass


def start_inprocess_worker(address, host_id: str, *,
                           engine_kwargs: dict | None = None):
    """Run a worker on a daemon thread in this process, dialed into the
    router at ``address`` — the full wire protocol with no subprocess
    (tier-1-safe tests; the CI multihost gate uses real processes)."""
    sock = socket.create_connection(address, timeout=30)
    sock.settimeout(None)
    worker = ServeWorker(sock, host_id=host_id, engine_kwargs=engine_kwargs)
    thread = threading.Thread(target=worker.serve_forever,
                              name=f"ServeWorker-{host_id}", daemon=True)
    thread.start()
    return worker, thread


def tpu_attached() -> bool:
    """Whether this host has TPU chips, read from the device files so the
    caller's own JAX backend stays uninitialised."""
    return bool(glob.glob("/dev/accel[0-9]*") or glob.glob("/dev/vfio/[0-9]*"))


def check_fleet_fits(hosts: int, env: dict | None = None) -> None:
    """Refuse ``hosts >= 2`` worker processes on a TPU host.

    A chip belongs to one process at a time and every worker's JAX would
    open all of the host's chips, so the second worker could not start.
    Workers forced off the TPU (``JAX_PLATFORMS`` without ``tpu``) are
    fine; so is one worker, which drives every local chip itself.  Checked
    before spawning, from the device files: the parent must not open the
    chip either.
    """
    env = os.environ if env is None else env
    platforms = env.get("JAX_PLATFORMS", "").strip().lower()
    off_tpu = bool(platforms) and "tpu" not in platforms.split(",")
    if hosts >= 2 and not off_tpu and tpu_attached():
        raise RuntimeError(
            f"{hosts} worker processes on a TPU host: each would open every "
            f"chip and only the first can; run one worker per host (it "
            f"drives all local chips) or set JAX_PLATFORMS=cpu for the "
            f"workers")


def spawn_worker_process(address, host_id: str, *, backend: str = "auto",
                         window_ms: float = 5.0, devices: int = 0,
                         coordinator: str = "", num_processes: int = 0,
                         process_id: int = -1,
                         env: dict | None = None) -> subprocess.Popen:
    """Launch ``python -m repro.serve.worker`` as a real process.

    ``devices > 0`` forces that many host-platform XLA devices in the
    child (the SNIPPETS.md multi-process idiom); ``coordinator`` opts the
    child in to ``jax.distributed`` bootstrap.  The child inherits this
    interpreter and ``PYTHONPATH`` — callers outside ``src`` (the
    benchmark harness, CI) need no extra wiring.  Callers spawning more
    than one worker call :func:`check_fleet_fits` first."""
    host, port = address
    # `-c` entry rather than `-m repro.serve.worker`: the package __init__
    # already imports this module, so runpy would warn about (and shadow)
    # the copy in sys.modules.
    cmd = [sys.executable, "-c",
           "from repro.serve.worker import main; main()",
           "--connect", f"{host}:{port}", "--host-id", str(host_id),
           "--backend", backend, "--window-ms", str(window_ms)]
    if coordinator:
        cmd += ["--coordinator", coordinator,
                "--num-processes", str(num_processes),
                "--process-id", str(process_id)]
    child_env = dict(os.environ if env is None else env)
    if devices > 0:
        child_env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices} "
            + child_env.get("XLA_FLAGS", "")).strip()
    src = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    child_env["PYTHONPATH"] = (src + os.pathsep
                               + child_env.get("PYTHONPATH", "")).rstrip(
                                   os.pathsep)
    return subprocess.Popen(cmd, env=child_env)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Serve-fabric worker host (DESIGN.md §17)")
    ap.add_argument("--connect", required=True, metavar="HOST:PORT",
                    help="router listen address to dial")
    ap.add_argument("--host-id", required=True)
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--window-ms", type=float, default=5.0,
                    help="engine micro-batch window")
    ap.add_argument("--max-pending", type=int, default=4096)
    ap.add_argument("--coordinator", default="", metavar="HOST:PORT",
                    help="opt-in jax.distributed coordinator address "
                         "(multi-process JAX bootstrap; never combined "
                         "with kill chaos — see module docstring)")
    ap.add_argument("--num-processes", type=int, default=0)
    ap.add_argument("--process-id", type=int, default=-1)
    args = ap.parse_args(argv)

    # Bootstrap BEFORE the first jax device query locks the backend.
    if args.coordinator:
        from repro.launch.mesh import init_distributed
        init_distributed(coordinator=args.coordinator,
                         num_processes=args.num_processes,
                         process_id=args.process_id)
    import jax
    # fp64 requests need x64.  On a TPU their buckets resolve to the ref
    # backend (Pallas has no float64); the Pallas kernels keep their
    # operands and indices 32-bit either way, so f32 traffic is unaffected.
    jax.config.update("jax_enable_x64", True)
    from repro.launch import compile_cache
    from repro.launch.mesh import serve_mesh
    compile_cache.enable()

    host, _, port = args.connect.rpartition(":")
    sock = socket.create_connection((host, int(port)), timeout=60)
    sock.settimeout(None)
    worker = ServeWorker(sock, host_id=args.host_id, engine_kwargs=dict(
        backend=args.backend, batch_window_s=args.window_ms / 1e3,
        max_pending=args.max_pending, mesh=serve_mesh()))
    worker.serve_forever()
    return 0


if __name__ == "__main__":
    sys.exit(main())
