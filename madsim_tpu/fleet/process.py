"""Multiprocess fleet: real OS worker processes, pipes, and signals.

The deployment-shaped leg of the fabric: the coordinator stays in the
parent, each worker is a spawned process with its own JAX runtime and
engine, RPCs ride ``multiprocessing`` pipes, and preemption is a real
``SIGTERM`` handled by the worker's signal handler (checkpoint + lease
release + clean exit). The protocol objects are the SAME classes the
inline fabric runs — only the transport, the clock, and the scheduler
change — so the bitwise result contract carries over unchanged while
schedules become as nondeterministic as the OS makes them.

Scope: the CPU-mesh proof (``make chaos`` runs a small kill/SIGTERM
matrix here; tests mark it slow) and the template for a real deployment
where "pipe" becomes "TCP" and "spawn" becomes "your cluster
scheduler". Worker crash-kill is a parent-side SIGKILL; recovery is the
lease TTL doing its job.
"""
from __future__ import annotations

import os
import sys
from typing import Any, Dict, Optional

import numpy as np

from .rpc import RealClock, RetryPolicy, RpcError


def _wire_safe(kw: Dict[str, Any]) -> Dict[str, Any]:
    """Strip the process-local triage context from any SweepResult
    payload before it crosses the pipe: ``triage_ctx`` holds the live
    engine (jit closures — unpicklable by design), and the coordinator
    side never uses it (merged fleet results are 'reconstructed' and
    carry None there anyway)."""
    import dataclasses as _dc

    def scrub(v):
        if getattr(v, "triage_ctx", None) is not None:
            return _dc.replace(v, triage_ctx=None)
        return v

    out = {k: scrub(v) for k, v in kw.items()}
    if isinstance(out.get("msgs"), list):
        out["msgs"] = [{k: scrub(v) for k, v in m.items()}
                       for m in out["msgs"]]
    return out


def _spawned_backend():
    """``(platform, held)``: the JAX platform a spawned worker will get,
    and whether THIS process already holds it.

    Learned without taking the chip: from this process's backends when
    it has initialized them, else from ``JAX_PLATFORMS``, else from a
    short-lived probe process (which releases the chip as it exits)."""
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            import jax

            return jax.default_backend(), True
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return platforms.split(",")[0], False
    import subprocess

    probe = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.default_backend())"],
        capture_output=True, text=True, timeout=300)
    lines = probe.stdout.split()
    return (lines[-1] if probe.returncode == 0 and lines else "unknown",
            False)


def _check_one_process_per_chip(n_workers: int) -> None:
    """A TPU belongs to one process, and a spawned worker's JAX claims
    every chip of its host. Refuse at once what would otherwise fail or
    hang in the second worker's backend start."""
    platform, held = _spawned_backend()
    if platform != "tpu":
        return
    if held:
        raise RuntimeError(
            "process_fleet_sweep: this process has initialized JAX on the "
            "TPU and holds the chip, so no spawned worker can get it. Call "
            "it from a process that has not touched JAX's devices.")
    if n_workers > 1:
        raise RuntimeError(
            f"process_fleet_sweep: n_workers={n_workers} on a TPU host, "
            f"but each spawned worker claims every chip of the host, so "
            f"only one worker can hold them. Use n_workers=1, or spread "
            f"worlds over the chips in one process with sweep(mesh=...).")


class PipeTransport:
    """Worker-side transport: one request/response per call over the
    process's pipe to the coordinator."""

    def __init__(self, conn):
        self.conn = conn

    def call(self, method: str, worker_id: str, **kw):
        try:
            self.conn.send({"method": method, "worker_id": worker_id,
                            "kw": _wire_safe(kw)})
            resp = self.conn.recv()
        except (EOFError, OSError, BrokenPipeError) as exc:
            raise RpcError(f"coordinator pipe failed: {exc}") from exc
        if "err" in resp:
            raise RpcError(resp["err"])
        return resp["ok"]


def _worker_main(conn, worker_id: str, actor, cfg, seeds, faults,
                 checkpoint_dir: Optional[str],
                 checkpoint_every_chunks: int,
                 sweep_kwargs: Dict[str, Any]) -> None:
    """Entry point of a spawned worker process."""
    # Spawned fresh: the parent's test/CI environment (JAX_PLATFORMS,
    # XLA device-count flags, JAX_COMPILATION_CACHE_DIR) rides the
    # inherited env vars; the engine and all jit caches are rebuilt
    # here, as on any real fleet host. The persistent compilation cache
    # turns that rebuild into a disk load after the first worker
    # compiles — without it, N workers compile the identical sweep
    # program N times.
    from ..engine.core import DeviceEngine
    from .worker import Worker

    eng = DeviceEngine(actor, cfg)
    clock = RealClock()
    transport = PipeTransport(conn)
    w = Worker(worker_id, eng, np.asarray(seeds, np.uint64), transport,
               clock, faults=faults,
               retry=RetryPolicy(base_delay=0.05, max_delay=1.0),
               checkpoint_dir=checkpoint_dir,
               checkpoint_every_chunks=checkpoint_every_chunks,
               sweep_kwargs=sweep_kwargs)
    w.install_sigterm_handler()
    while True:
        try:
            did = w.run_once()
        except RpcError:
            break  # parent gone: nothing to report to
        if w.dead:
            break  # preempted (SIGTERM): lease released, exit cleanly
        if not did:
            try:
                if transport.call("poll_done", worker_id)["done"]:
                    break
            except RpcError:
                break
            clock.sleep(0.05)
    conn.close()
    sys.exit(0)


def process_fleet_sweep(actor, cfg, seeds, *, n_workers: int,
                        range_size: int,
                        faults: Optional[np.ndarray] = None,
                        lease_ttl: float = 5.0,
                        observe: Any = None,
                        checkpoint_dir: Optional[str] = None,
                        checkpoint_every_chunks: int = 4,
                        retry: Optional[RetryPolicy] = None,
                        kill_after_heartbeats: Optional[Dict[str, int]] = None,
                        preempt_after_heartbeats: Optional[Dict[str, int]]
                        = None,
                        max_restarts_per_worker: int = 1,
                        serve_timeout_s: float = 60.0,
                        **sweep_kwargs):
    """Run a fleet sweep with real worker processes (``spawn="process"``).

    ``kill_after_heartbeats`` / ``preempt_after_heartbeats`` map worker
    ids to a heartbeat count after which the parent SIGKILLs /
    SIGTERMs that worker — the process-mode chaos hooks (the inline
    fabric's richer ChaosConfig needs deterministic scheduling this
    mode deliberately gives up). A killed worker respawns up to
    ``max_restarts_per_worker`` times; its lease recovers via TTL
    expiry either way. ``lease_ttl`` is in SECONDS here.
    """
    import multiprocessing as mp
    import signal

    _check_one_process_per_chip(n_workers)
    from ..obs import observatory as _obsy
    from .coordinator import Coordinator

    seeds = np.asarray(seeds, np.uint64)
    clock = RealClock()
    emit, close = _obsy.make_observer(observe)
    coordinator = Coordinator(seeds, range_size=range_size,
                              lease_ttl=lease_ttl, clock=clock, emit=emit,
                              n_devices=1)
    if checkpoint_dir is not None:
        os.makedirs(checkpoint_dir, exist_ok=True)
    # Workers inherit env on spawn: hand them the parent's persistent
    # cache directory (the one compile-cache rule) so respawns and
    # workers 2..N load executables instead of recompiling them.
    from ..parallel import compile_cache

    os.environ[compile_cache.ENV_VAR] = compile_cache.cache_dir()
    del retry  # worker-side policy is fixed in _worker_main

    ctx = mp.get_context("spawn")
    conns: Dict[Any, str] = {}
    procs: Dict[str, Any] = {}
    hb_served: Dict[str, int] = {}
    restarts: Dict[str, int] = {}

    def spawn_worker(wid: str) -> None:
        parent_conn, child_conn = ctx.Pipe()
        # The fleet's multiprocess leg IS real concurrency, host-side by
        # design; sim determinism is preserved by the merge layer's
        # bitwise contract, not by the scheduler.
        p = ctx.Process(target=_worker_main,
                        args=(child_conn, wid, actor, cfg, seeds, faults,
                              checkpoint_dir, checkpoint_every_chunks,
                              sweep_kwargs),
                        daemon=True)
        p.start()
        child_conn.close()
        conns[parent_conn] = wid
        procs[wid] = p
        coordinator.emit("worker_spawned", worker=wid, pid=p.pid)

    for i in range(n_workers):
        wid = f"w{i}"
        spawn_worker(wid)
        hb_served[wid] = 0
        restarts[wid] = 0

    from multiprocessing.connection import wait as conn_wait

    t0 = clock.now()
    try:
        while not coordinator.done():
            if clock.now() - t0 > serve_timeout_s:
                raise TimeoutError(
                    f"process fleet did not converge in {serve_timeout_s}s; "
                    f"stats: {coordinator.stats}")
            coordinator.tick()
            # Reap dead processes; their leases recover via TTL.
            for wid, p in list(procs.items()):
                if p.exitcode is not None and p.exitcode != 0 and \
                        restarts[wid] < max_restarts_per_worker:
                    restarts[wid] += 1
                    coordinator.emit("worker_restarted", worker=wid,
                                     exitcode=p.exitcode)
                    spawn_worker(wid)
            ready = conn_wait(list(conns), timeout=0.05)
            for conn in ready:
                wid = conns[conn]
                try:
                    req = conn.recv()
                except (EOFError, OSError):
                    del conns[conn]
                    continue
                method, kw = req["method"], req["kw"]
                try:
                    out = getattr(coordinator, f"rpc_{method}")(
                        worker_id=req["worker_id"], **kw)
                    conn.send({"ok": out})
                except Exception as exc:  # noqa: BLE001 — to the worker
                    conn.send({"err": f"{type(exc).__name__}: {exc}"})
                if method == "heartbeat":
                    hb_served[wid] = hb_served.get(wid, 0) + 1
                    n = hb_served[wid]
                    if (kill_after_heartbeats or {}).get(wid) == n:
                        os.kill(procs[wid].pid, signal.SIGKILL)
                        coordinator.emit("worker_killed", worker=wid,
                                         via="SIGKILL")
                    elif (preempt_after_heartbeats or {}).get(wid) == n:
                        os.kill(procs[wid].pid, signal.SIGTERM)
                        coordinator.emit("worker_preempt_signaled",
                                         worker=wid, via="SIGTERM")
        stats = {"n_workers": n_workers, "spawn": "process",
                 "restarts": dict(restarts)}
        return coordinator.finalize(fleet_stats=stats)
    finally:
        for p in procs.values():
            if p.exitcode is None:
                p.terminate()
        # No worker outlives the call: one still shutting down holds the
        # TPU, and the caller's next JAX start would fail on the chip.
        for p in procs.values():
            p.join(timeout=5.0)
            if p.exitcode is None:
                p.kill()
                p.join()
        if close is not None:
            close()
