"""The one general traffic generator and the loop that drives a cell.

A traffic file (``traffic/<name>.json``) is data: how many seeds each
unit of work sweeps, the keyword arguments of that ``sweep()`` call, the
per-world fault windows, what a finished unit must show, and how many of
its rows the check samples. A unit is one ``sweep()`` call over a fresh
range of seeds; the window runs units back to back, each timed from the
call until its failing seeds are on the host.

Everything here is drawn from the run's ``--seed``: the seed ranges
(disjoint between the warm-up unit and every unit of the window), the
fault schedules and the rows sampled for the check.
"""
from __future__ import annotations

import importlib
import time

import numpy as np

# The sweep API's fault-row op codes ([time_us, op, a, b] rows).
FAULT_OPS = {"kill": 0, "restart": 1, "clog_node": 2, "unclog_node": 3,
             "clog_link": 4, "unclog_link": 5}

OBS_FIELDS = ("now_us", "active", "steps", "delivered", "dropped",
              "overflow", "qmax", "bug", "bug_time_us", "queue_depth",
              "leader_elected", "first_leader_time_us", "elections_won",
              "max_commit", "max_term")


def merged(cfg: dict, traffic: dict) -> tuple[dict, dict]:
    """The engine and actor parameters of a cell: the configuration's,
    with the traffic's overrides on top."""
    return ({**cfg["engine"], **traffic.get("engine", {})},
            {**cfg["raft"], **traffic.get("raft", {})})


def _attr(path: str):
    mod, name = path.split(":")
    return getattr(importlib.import_module(mod), name)


def build_engine(cfg: dict, traffic: dict):
    """The system under test, built from the configuration's sizes."""
    sysd = cfg["system"]
    engine, actor = merged(cfg, traffic)
    ecfg = _attr(sysd["engine_config"])(**engine)
    act = _attr(sysd["actor"])(_attr(sysd["actor_config"])(**actor))
    from madsim_tpu.engine import DeviceEngine

    return DeviceEngine(act, ecfg)


class Generator:
    """Seeds and fault schedules of every unit, from ``--seed``."""

    def __init__(self, traffic: dict, engine: dict, seed: int):
        self.w = int(traffic["seeds_per_unit"])
        self.windows = traffic.get("faults") or []
        self.n_nodes = int(engine["n_nodes"])
        self.t_limit = int(engine["t_limit_us"])
        self.seed = int(seed)
        # Unit k sweeps seeds base + k*w .. base + (k+1)*w - 1.
        self.base = int(np.random.default_rng([self.seed, 0]).integers(
            0, 2 ** 62))

    def seeds(self, k: int) -> np.ndarray:
        start = np.uint64(self.base + k * self.w)
        return start + np.arange(self.w, dtype=np.uint64)

    def faults(self, k: int):
        """Per-world ``(w, 2 * windows, 4)`` rows: each window opens and
        closes once per world, at times drawn per world."""
        if not self.windows:
            return None
        rng = np.random.default_rng([self.seed, 1, k])
        w, t, n = self.w, self.t_limit, self.n_nodes
        blocks = []
        for win in self.windows:
            lo, hi = win["start"]
            t_on = rng.integers(int(t * lo), int(t * hi), w)
            t_off = t_on + rng.integers(int(win["length_us"]),
                                        int(t * win["length_to"]), w)
            a = rng.integers(0, n, w)
            b = np.zeros(w, np.int64)
            if win["target"] == "link":
                b = (a + 1 + rng.integers(0, n - 1, w)) % n
            on, off = (FAULT_OPS[o] for o in win["ops"])
            blocks.append(np.stack([t_on, np.full(w, on), a, b], axis=1))
            blocks.append(np.stack([t_off, np.full(w, off), a, b], axis=1))
        return np.stack(blocks, axis=1).astype(np.int32)


class Unit:
    """What one finished unit leaves for the metrics and the check."""

    def __init__(self, k, t0, t1, n, res, rows):
        self.k, self.t0, self.t1, self.n = k, t0, t1, n
        self.utilization = float(res.world_utilization)
        self.dispatches = int(res.loop_stats["dispatches"])
        obs = res.observations
        steps = np.asarray(obs["steps"])
        self.failing = int(np.count_nonzero(obs["bug"]))
        self.events = int(steps.sum(dtype=np.int64))
        self.admitted = int(np.count_nonzero(steps))
        self.live = int(np.count_nonzero(obs["active"]))
        self.unrun = n - self.admitted
        self.rows = rows


def sample_rows(traffic: dict, gen: Generator, k: int, seeds, faults,
                obs) -> list:
    """Rows of unit ``k`` the check compares: a draw from the admitted
    rows, the longest-running rows and some failing ones."""
    chk = traffic["check"]
    rng = np.random.default_rng([gen.seed, 2, k])
    steps = np.asarray(obs["steps"])
    admitted = np.flatnonzero(steps > 0)
    pick = []
    if admitted.size:
        pick += list(rng.choice(admitted, min(chk["rows_per_unit"],
                                              admitted.size), replace=False))
        k_long = chk["longest_per_unit"]
        if k_long:
            pick += list(np.argpartition(steps, -k_long)[-k_long:])
    failing = np.flatnonzero(np.asarray(obs["bug"]))
    if failing.size and chk["failing_per_unit"]:
        pick += list(rng.choice(failing, min(chk["failing_per_unit"],
                                             failing.size), replace=False))
    rows = []
    for i in sorted(set(int(i) for i in pick)):
        rows.append({
            "seed": int(seeds[i]),
            "faults": None if faults is None else faults[i].tolist(),
            "row": {f: int(np.asarray(obs[f][i])) for f in OBS_FIELDS},
        })
    return rows


def run_unit(sweep, eng, mesh, traffic, gen, k, annotate):
    """One unit of work, timed from the call until its failing seeds are
    on the host. Returns (t0, t1, result, seeds, faults)."""
    with annotate("bench:make_unit"):
        seeds = gen.seeds(k)
        faults = gen.faults(k)
    t0 = time.perf_counter()
    with annotate("bench:sweep"):
        res = sweep(None, eng.cfg, seeds, faults=faults, engine=eng,
                    mesh=mesh, **traffic["sweep_kwargs"])
        res.failing_seeds  # noqa: B018 — the hunt's answer, on the host
    t1 = time.perf_counter()
    return t0, t1, res, seeds, faults


def window(sweep, eng, mesh, traffic, gen, seconds, tracer):
    """Units back to back until ``seconds`` have passed since the window
    opened; a unit started inside the window runs to its end. ``tracer``
    captures the first units (run.py's Tracer). Returns (t_open, units)."""
    units = []
    tracer.start()
    t_open = time.perf_counter()
    k = 1                                   # unit 0 is the warm-up
    while time.perf_counter() - t_open < seconds:
        t0, t1, res, seeds, faults = run_unit(sweep, eng, mesh, traffic,
                                              gen, k, tracer.annotate)
        with tracer.annotate("bench:record"):
            rows = sample_rows(traffic, gen, k, seeds, faults,
                               res.observations)
            units.append(Unit(k, t0, t1, len(seeds), res, rows))
        del res
        tracer.after_unit(len(units))
        k += 1
    tracer.stop()
    return t_open, units
