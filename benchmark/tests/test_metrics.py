"""The per-layer metrics of each cell, read from a trace recorded on a
v5e: a metric that finds nothing to read in a cell it lists fails the
run instead of dropping out of the result."""
import os
from types import SimpleNamespace

import pytest

import run
import spec
import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "fixture.xplane.pb")


def _run(red):
    units = [SimpleNamespace(utilization=0.5, dispatches=1, events=1000)
             for _ in range(3)]
    return {"units": units, "traced_units": units, "trace": red}


@pytest.fixture(scope="module")
def red():
    pytest.importorskip("jax")
    return tr.Reduction(FIXTURE)


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  spec.load_benchmark()["workloads"]])
def test_every_metric_reads_or_fails_the_run(red, cell):
    bench = spec.load_benchmark()
    # The fixture's toy program is no superstep program: a cell whose
    # metrics read the step's modules must fail, the others read all.
    names = {m["name"] for m in spec.per_layer_for(bench, cell)}
    if "step_ns_per_event.sweep" in names:
        with pytest.raises(run.NoReading, match="step_ns_per_event"):
            run.per_layer(bench, cell, _run(red))
    else:
        got = run.per_layer(bench, cell, _run(red))
        assert set(got) == names
        assert all(0 < m["value"] for m in got.values())
    # Without a trace, the device's metrics find nothing either.
    with pytest.raises(run.NoReading):
        run.per_layer(bench, cell, {**_run(red), "trace": None})


def test_step_metric_reads_matching_modules(red):
    reader = spec.metric_reader("step_ns_per_event.sweep")
    renamed = SimpleNamespace(module_ns=lambda pattern: (
        red.module_ns(r"^jit_") if pattern == reader.MODULE else (0, 0)))
    ctx = SimpleNamespace(trace=renamed, traced_units=_run(red)["units"])
    ns, _ = red.module_ns(r"^jit_")
    assert reader.read(ctx) == pytest.approx(ns / 3000)
