#!/usr/bin/env python3
"""Drive the device engine's main path once on the chip, and check it.

The path a user runs: ``madsim_tpu.parallel.sweep`` over seeded worlds,
then failing-seed replay, at the sizes users sweep. One process, which
imports JAX once and holds the chip throughout; nothing here spawns.

- Phase A, headline sweep: 3-node Raft election, 1 virtual second
  (BASELINE.json config 2), 524,288 seeds. No world live, no bug, no
  overflow; a rerun is bitwise equal; a 4,096-seed slice is bitwise
  equal between the chip and the CPU backend.
- Phase B, chaos: 5-node Raft replication with per-world kill/restart
  and link-clog schedules (BASELINE.json config 5, faults from
  ``make_fault_schedules``), 100,000 worlds, ``chunk_steps=16``. No
  world live, no bug, no overflow; a rerun is bitwise equal.
- Phase C, hunt and replay: a fused, recycled ``stop_on_first_bug`` hunt
  over 1,048,576 seeds of the ``buggy_double_vote`` config. It finds the
  bug; ``DeviceEngine.trace`` replays the first failing seed on the chip
  and ends at the bug; that seed's observation row equals, bitwise, a
  CPU-backend run of the seed alone.

``--multichip`` runs only the path across chips: phase A's sweep over
``seed_mesh()`` and over ``multihost_mesh(n_hosts=2)`` on every chip,
each bitwise equal per seed to ``seed_mesh(n_devices=1)``, with each
device holding W/n_devices worlds.

Each phase prints one JSON line on stdout; the last line is
``{"ok": true, "device": {...}}``. ``compile_s`` is the first call's
wall time less the warm rerun's; every time is informational, not a
baseline. Without a TPU, or outside a checkout, it exits non-zero and
prints no result.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
T0 = time.perf_counter()

HEADLINE_W = 524_288
XCHECK_W = 4_096
CHAOS_W = 100_000
HUNT_SEEDS = 1_048_576
HUNT_BATCH = 65_536


class CheckFailed(Exception):
    pass


def _device():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _emit(phase, w, compile_s, run_s, checks, **info):
    _log(f"phase {phase} done")
    print(json.dumps({"phase": phase, **_device(), "W": w,
                      "compile_s": round(compile_s, 3),
                      "run_s": round(run_s, 3), **info, "checks": checks}),
          flush=True)
    bad = [k for k, v in checks.items() if v is not True]
    if bad:
        raise CheckFailed(f"phase {phase}: failed checks {bad}")


def _log(msg):
    print(f"chip_smoke [{time.perf_counter() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _twice(fn):
    """Run ``fn`` cold then warm: (cold result, warm result, compile_s,
    run_s)."""
    first, t_first = _timed(fn)
    _log(f"cold call {t_first:.1f}s")
    second, t_run = _timed(fn)
    _log(f"warm call {t_run:.1f}s")
    return first, second, max(t_first - t_run, 0.0), t_run


def _obs_equal(a, b):
    return (set(a) == set(b)
            and all(a[k].shape == b[k].shape and (a[k] == b[k]).all()
                    for k in a))


def _clean(obs):
    return {"no_live_world": not obs["active"].any(),
            "no_bug": not obs["bug"].any(),
            "no_overflow": not obs["overflow"].any()}


def headline_engine():
    from madsim_tpu.engine import (DeviceEngine, EngineConfig, RaftActor,
                                   RaftDeviceConfig)

    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=28,
                       t_limit_us=1_000_000)
    return DeviceEngine(RaftActor(RaftDeviceConfig(n=3, log_cap=4)), cfg)


def phase_a(w=HEADLINE_W, xcheck_w=XCHECK_W):
    import numpy as np

    from madsim_tpu.engine.crosscheck import crosscheck_backends
    from madsim_tpu.parallel.sweep import sweep

    eng = headline_engine()
    seeds = np.arange(w)
    first, res, compile_s, run_s = _twice(
        lambda: sweep(None, eng.cfg, seeds, engine=eng))
    obs = res.observations
    (xc, xc_s) = _timed(lambda: crosscheck_backends(eng, seeds[:xcheck_w]))
    checks = {**_clean(obs),
              "rerun_bitwise": _obs_equal(first.observations, obs),
              f"tpu_vs_cpu_bitwise_{xcheck_w}":
                  xc["bitwise_equal"] == 1
                  and xc["platform_b"] == "cpu"}
    _emit("A", w, compile_s, run_s, checks,
          seeds_per_s=round(w / run_s, 1),
          elected=int(obs["leader_elected"].sum()),
          crosscheck_s=round(xc_s, 3))


def make_fault_schedules(n_worlds: int, n_nodes: int, t_limit_us: int,
                         seed: int = 0):
    """Per-world fault rows [time_us, op, a, b]: one kill+restart pair and
    one link clog+unclog window per world, at schedule-swept times."""
    import numpy as np

    from madsim_tpu.engine.core import (
        FAULT_KILL, FAULT_RESTART, FAULT_CLOG_LINK, FAULT_UNCLOG_LINK)

    rng = np.random.default_rng(seed)
    t_kill = rng.integers(t_limit_us // 10, t_limit_us // 2, n_worlds)
    t_restart = t_kill + rng.integers(50_000, t_limit_us // 4, n_worlds)
    victim = rng.integers(0, n_nodes, n_worlds)
    t_clog = rng.integers(t_limit_us // 10, t_limit_us // 2, n_worlds)
    t_unclog = t_clog + rng.integers(50_000, t_limit_us // 4, n_worlds)
    a = rng.integers(0, n_nodes, n_worlds)
    b = (a + 1 + rng.integers(0, n_nodes - 1, n_worlds)) % n_nodes
    rows = np.stack([
        np.stack([t_kill, np.full(n_worlds, FAULT_KILL), victim,
                  np.zeros(n_worlds)], axis=1),
        np.stack([t_restart, np.full(n_worlds, FAULT_RESTART), victim,
                  np.zeros(n_worlds)], axis=1),
        np.stack([t_clog, np.full(n_worlds, FAULT_CLOG_LINK), a, b], axis=1),
        np.stack([t_unclog, np.full(n_worlds, FAULT_UNCLOG_LINK), a, b], axis=1),
    ], axis=1).astype(np.int32)
    return rows


def phase_b(w=CHAOS_W):
    import numpy as np

    from madsim_tpu.engine import (DeviceEngine, EngineConfig, RaftActor,
                                   RaftDeviceConfig)
    from madsim_tpu.parallel.sweep import sweep

    t_limit_us = 3_000_000
    rcfg = RaftDeviceConfig(n=5, n_proposals=4, log_cap=16,
                            propose_start_us=1_000_000,
                            propose_interval_us=200_000)
    cfg = EngineConfig(n_nodes=5, outbox_cap=6, queue_cap=64,
                       t_limit_us=t_limit_us)
    eng = DeviceEngine(RaftActor(rcfg), cfg)
    faults = make_fault_schedules(w, 5, t_limit_us)
    first, res, compile_s, run_s = _twice(
        lambda: sweep(None, cfg, np.arange(w), faults=faults, engine=eng,
                      chunk_steps=16, max_steps=20_000))
    obs = res.observations
    checks = {**_clean(obs),
              "rerun_bitwise": _obs_equal(first.observations, obs)}
    _emit("B", w, compile_s, run_s, checks,
          seeds_per_s=round(w / run_s, 1),
          world_utilization=res.world_utilization,
          mean_committed=float(obs["max_commit"].mean()))


def phase_c(n_seeds=HUNT_SEEDS, batch=HUNT_BATCH):
    import jax
    import numpy as np

    from madsim_tpu.engine import (DeviceEngine, EngineConfig, RaftActor,
                                   RaftDeviceConfig)
    from madsim_tpu.engine.crosscheck import run_on
    from madsim_tpu.parallel.sweep import sweep

    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                       t_limit_us=2_000_000, stop_on_bug=True)
    eng = DeviceEngine(RaftActor(RaftDeviceConfig(
        n=3, buggy_double_vote=True)), cfg)
    seeds = np.arange(n_seeds)
    first, res, compile_s, run_s = _twice(
        lambda: sweep(None, cfg, seeds, engine=eng, chunk_steps=64,
                      fused=True, recycle=True, stop_on_first_bug=True,
                      batch_worlds=batch))
    found = bool(res.failing_seeds)
    checks = {"found_bug": found,
              "rerun_same_failing_seeds":
                  first.failing_seeds == res.failing_seeds}
    info = {"failing_seeds": len(res.failing_seeds)}
    if found:
        seed = int(res.failing_seeds[0])
        trace, trace_s = _timed(lambda: eng.trace(seed, max_steps=4_000))
        checks["trace_ends_at_bug"] = bool(
            trace and trace[-1].get("bug_raised"))
        row = int(np.flatnonzero(np.asarray(res.seeds) == seed)[0])
        chip = {k: np.asarray(v[row]) for k, v in res.observations.items()}
        cpu = {k: v[0] for k, v in eng.observe(run_on(
            eng, jax.devices("cpu")[0], [seed])).items()}
        checks["replay_row_tpu_vs_cpu_bitwise"] = _obs_equal(chip, cpu)
        info.update(seed=seed, trace_events=len(trace),
                    trace_s=round(trace_s, 3),
                    bug_time_us=int(chip["bug_time_us"]))
    _emit("C", batch, compile_s, run_s, checks, hunt_seeds=n_seeds, **info)


def phase_multichip(w=HEADLINE_W):
    """Phase A's sweep over every chip against one chip, per seed."""
    import jax
    import numpy as np

    from madsim_tpu.parallel import multihost_mesh, seed_mesh, shard_worlds
    from madsim_tpu.parallel.sweep import sharded_engine, sweep

    n = len(jax.devices())
    if n < 2:
        raise CheckFailed(f"--multichip needs several chips, found {n}")
    eng = headline_engine()
    seeds = np.arange(w)
    (one, one_s) = _timed(lambda: sweep(None, eng.cfg, seeds, engine=eng,
                                        mesh=seed_mesh(n_devices=1)))
    for name, mesh in (("seed_mesh", seed_mesh()),
                       ("multihost_mesh", multihost_mesh(n_hosts=2))):
        first, res, compile_s, run_s = _twice(
            lambda: sweep(None, eng.cfg, seeds, engine=eng, mesh=mesh))
        # Where the worlds live: one sharded chunk from a fresh batch.
        state, _bug, _n, _steps = sharded_engine(eng, mesh, chunk_steps=8)(
            shard_worlds(eng.init(seeds), mesh))
        shards = state.now.addressable_shards
        per_device = sorted(s.data.shape[0] for s in shards)
        checks = {**_clean(res.observations),
                  "rerun_bitwise": _obs_equal(first.observations,
                                              res.observations),
                  "bitwise_vs_one_chip": _obs_equal(res.observations,
                                                    one.observations),
                  "worlds_spread_evenly":
                      len({s.device for s in shards}) == n
                      and per_device == [w // n] * n}
        del state
        _emit(f"multichip:{name}", w, compile_s, run_s, checks,
              worlds_per_device=per_device,
              seeds_per_s=round(w / run_s, 1),
              one_chip_s_incl_compile=round(one_s, 3))


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the path across every chip of the host")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(HERE, "madsim_tpu")):
        sys.exit(f"chip_smoke: no madsim_tpu package beside {__file__}: "
                 "run it from a checkout of the repo")
    sys.path.insert(0, HERE)
    import madsim_tpu  # noqa: F401  (applies the compile-cache rule first)

    dev = _device()
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: no TPU found: JAX's default device is "
                 f"{dev['platform']} ({dev['kind']})")
    try:
        if args.multichip:
            phase_multichip()
        else:
            phase_a()
            phase_b()
            phase_c()
    except CheckFailed as exc:
        sys.exit(f"chip_smoke: {exc}")
    print(json.dumps({"ok": True, "device": _device()}), flush=True)


if __name__ == "__main__":
    main()
