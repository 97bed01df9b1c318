#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as one JSON line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names its configuration
(``configs/<config>.json``), its traffic (``traffic/<traffic>.json``) and
the chips it needs; its per-layer metrics are ``metrics/<name>.py``.
This file names none of them.

Set-up builds the system from the configuration, warms up one unit of
the cell's traffic at its real shapes (so every program is compiled or
loaded from the compile cache in ``<checkout>/.jax_cache``), and ends
where the measured window opens. The window runs units back to back for
``--seconds``. With ``--trace 1`` the first units of the window are
traced and the result carries the per-layer metrics instead of the
end-to-end ones. Afterwards the sampled answers are compared with the
configuration's plain reference; ``correct`` is that comparison.

Without a TPU, with fewer chips than the cell asks for, or with a device
kind missing from ``peaks.json``, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")

sys.path.insert(0, HERE)
import check  # noqa: E402
import spec  # noqa: E402


class CannotRun(Exception):
    """No system under test, no TPU, too few chips or an unknown chip."""


class NoReading(Exception):
    """A per-layer metric found nothing to read in a cell it lists."""


def log(msg: str) -> None:
    print(f"run.py [{time.perf_counter() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def import_system():
    """Import JAX and the system under test, with the compile cache in
    the checkout (the path is part of the cache key, so it is fixed)."""
    if not os.path.isdir(os.path.join(ROOT, "madsim_tpu")):
        raise CannotRun(f"no system under test: {ROOT}/madsim_tpu is missing")
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    # No eviction: with a size cap JAX keeps an access-time file beside
    # each entry, and a missing one makes every later write fail.
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    # The TPU runtime logs to a fixed /tmp/tpu_logs unless told otherwise.
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path.insert(0, ROOT)
    import madsim_tpu  # noqa: F401  (applies the compile-cache rule)
    import jax

    return jax


def chips(jax, n: int) -> list:
    """The first ``n`` TPU chips, or CannotRun."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise CannotRun(f"no TPU: JAX's devices are {devs[0].platform} "
                       f"({devs[0].device_kind})")
    if len(devs) < n:
        raise CannotRun(f"the cell needs {n} chips, JAX finds {len(devs)}")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if devs[0].device_kind not in peaks:
        raise CannotRun(f"device kind {devs[0].device_kind!r} is not in "
                       f"peaks.json")
    return devs[:n]


class Tracer:
    """Profiler capture of the window's first ``units`` units, inside one
    ``bench:traced`` host span; host spans name the harness's phases."""

    def __init__(self, profiler, on: bool, units: int):
        self.profiler, self.on, self.units = profiler, on, units
        self.active = False
        self.span = None

    def annotate(self, name: str):
        if self.active:
            return self.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def start(self) -> None:
        if not self.on:
            return
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        self.profiler.start_trace(TRACE_DIR)
        self.active = True
        self.span = self.profiler.TraceAnnotation("bench:traced")
        self.span.__enter__()

    def after_unit(self, n_done: int) -> None:
        if self.active and n_done >= self.units:
            self.stop()

    def stop(self) -> None:
        if self.active:
            self.span.__exit__(None, None, None)
            self.profiler.stop_trace()
            self.active = False


def measure(jax, cell: dict, cfg: dict, traffic: dict, seed: int,
            seconds: float, trace: bool, devices: list,
            sweep=None) -> dict:
    """Set-up, window and trace reduction of one run. ``sweep`` is the
    system's sweep entry point (a test may hand in a broken one)."""
    import workload
    from madsim_tpu.parallel import seed_mesh

    if sweep is None:
        from madsim_tpu.parallel.sweep import sweep
    engine_p, _ = workload.merged(cfg, traffic)
    eng = workload.build_engine(cfg, traffic)
    mesh = seed_mesh(devices)
    gen = workload.Generator(traffic, engine_p, seed)
    nothing = lambda name: contextlib.nullcontext()  # noqa: E731
    workload.run_unit(sweep, eng, mesh, traffic, gen, 0, nothing)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f}s; window opens")

    compiles = []                 # backend compiles inside the window

    def listener(event, secs, **kw):
        if counting and event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    counting = True
    jax.monitoring.register_event_duration_secs_listener(listener)
    tracer = Tracer(jax.profiler, trace, int(traffic["trace_units"]))
    t_open, units = workload.window(sweep, eng, mesh, traffic, gen, seconds,
                                    tracer)
    counting = False
    t_close = units[-1].t1
    n_compiles = len(compiles)
    secs = np.array([u.t1 - u.t0 for u in units])
    q = np.percentile(secs, [0, 50, 95, 100])
    slow = np.flatnonzero(secs > 1.25 * q[1])
    log(f"window: {len(units)} units in {t_close - t_open:.3f}s, "
        f"{n_compiles} compiles inside it; unit seconds min/median/p95/max "
        + "/".join(f"{x:.4f}" for x in q)
        + f"; {slow.size} over 1.25x the median "
        + str([(int(i), round(float(secs[i]), 4)) for i in slow]))
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    del eng
    reduction = None
    if trace:
        import trace_reduce

        t0 = time.perf_counter()
        reduction = trace_reduce.Reduction(
            trace_reduce.find_xplane(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t0:.3f}s")
    return {"setup_s": setup_s, "t_open": t_open, "t_close": t_close,
            "units": units, "memory_peak_bytes": peak,
            "window_compiles": n_compiles, "trace": reduction,
            "traced_units": units[:tracer.units] if trace else []}


def end_to_end(name: str, run: dict) -> float:
    """The end-to-end metrics, over all the work and time of the window."""
    units = run["units"]
    if name == "setup_s":
        return run["setup_s"]
    if name == "seeds_per_s":
        return sum(u.n for u in units) / (run["t_close"] - run["t_open"])
    if name == "hunt_s_p95":
        return float(np.percentile([u.t1 - u.t0 for u in units], 95))
    raise spec.SpecError(f"no end-to-end metric named {name!r}")


def decide(cfg: dict, traffic: dict, units: list, control=None) -> dict:
    """The comparison with the plain reference: the numbers compared, each
    with its limit, and which units failed."""
    import workload

    ref = spec.reference(cfg)
    engine_p, raft_p = workload.merged(cfg, traffic)
    rows = [r for u in units for r in u.rows]
    t0 = time.perf_counter()
    want = check.reference_rows(ref, engine_p, raft_p, rows)
    if control is not None:
        # The control: the reference with one guarantee broken, put in
        # the program's place.
        got = check.reference_rows(ref, engine_p, raft_p, rows,
                                   control=control)
        rows = [{**r, "row": {k: int(v) for k, v in g.items()}}
                for r, g in zip(rows, got)]
    bad = check.mismatched(rows, want)
    log(f"reference: {len(rows)} rows in {time.perf_counter() - t0:.3f}s, "
        f"{len(bad)} differ")
    for b in bad[:3]:
        log(f"  seed {b['seed']}: {b['diff']}")
    bad_seeds = {b["seed"] for b in bad}
    failed = 0
    for u in units:
        own = check.numbers(traffic, [u], sum(r["seed"] in bad_seeds
                                              for r in u.rows))
        failed += not check.passed(own)
    checks = check.numbers(traffic, units, len(bad))
    return {"checks": checks, "correct": check.passed(checks),
            "failed": failed, "rows": len(rows)}


def per_layer(bench: dict, cell_name: str, run: dict) -> dict:
    """The cell's per-layer metrics. One that finds nothing to read in a
    cell it lists (a module renamed, a span gone) fails the run."""
    # What a per-layer metric's ``read(ctx)`` sees.
    ctx = SimpleNamespace(units=run["units"],
                          traced_units=run["traced_units"],
                          trace=run["trace"])
    metrics = {}
    for m in spec.per_layer_for(bench, cell_name):
        value = spec.metric_reader(m["name"]).read(ctx)
        if value is None:
            raise NoReading(f"metric {m['name']} found nothing to read")
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def result(bench: dict, cell: dict, run: dict, verdict: dict,
           devices: list, trace: bool) -> dict:
    metrics = {}
    if trace:
        metrics = per_layer(bench, cell["name"], run)
    else:
        for m in spec.end_to_end_for(bench, cell["name"]):
            metrics[m["name"]] = {"value": end_to_end(m["name"], run),
                                  "unit": m["unit"]}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    out = {"correct": verdict["correct"], "attempted": len(run["units"]),
           "failed": verdict["failed"], "metrics": metrics, "device": device}
    if trace:
        red = run["trace"]
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_ns / 1e9
        out["breakdown"] = {"device_ops": red.top_ops(10),
                            "idle_gaps": red.idle_gaps(10)}
    out["window_compiles"] = run["window_compiles"]
    out["checks"] = verdict["checks"]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    try:
        bench, cell, cfg, traffic = spec.load(args.workload)
        jax = import_system()
        devices = chips(jax, int(cell["chips"]))
    except (CannotRun, spec.SpecError, OSError, ImportError) as exc:
        print(f"run.py: {exc}", file=sys.stderr)
        return 2
    log(f"{cell['name']} on {len(devices)} x {devices[0].device_kind}, "
        f"seed {args.seed}")
    run = measure(jax, cell, cfg, traffic, args.seed, args.seconds,
                  bool(args.trace), devices)
    verdict = decide(cfg, traffic, run["units"])
    try:
        out = result(bench, cell, run, verdict, devices, bool(args.trace))
    except NoReading as exc:
        print(f"run.py: {exc}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    for name, c in verdict["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
