"""The log-compaction cell at test size on the CPU: the program reads
``correct`` against its plain reference, and the control and each planted
fault read it false (as ``test_control.py`` and ``test_faults.py`` do for
the other cells, with a small-size loader of this cell's own)."""
import dataclasses

import numpy as np
import pytest

import run
import spec

CELL = "raft3snap.uncrash"
SEEDS = 64


@pytest.fixture(scope="module")
def snap_cell():
    jax = run.import_system()
    _, cell, cfg, traffic = spec.load(CELL)
    traffic["seeds_per_unit"] = SEEDS
    return jax, cell, cfg, traffic, jax.devices()[:int(cell["chips"])]


def _measure(snap_cell, seed, sweep=None):
    jax, cell, cfg, traffic, devices = snap_cell
    return run.measure(jax, cell, cfg, traffic, seed, 0.5, False, devices,
                       sweep=sweep)


def test_program_passes_and_control_fails(snap_cell):
    _, _, cfg, traffic, _ = snap_cell
    m = _measure(snap_cell, 2 ** 31 + 7)
    prog = run.decide(cfg, traffic, m["units"])
    assert prog["correct"], prog["checks"]
    assert prog["rows"] > 0
    ctrl = run.decide(cfg, traffic, m["units"], control=cfg["control"])
    assert not ctrl["correct"]
    assert ctrl["checks"]["rows_mismatched"]["value"] > 0


def _verdict(snap_cell, sweep=None):
    _, _, cfg, traffic, _ = snap_cell
    return run.decide(cfg, traffic,
                      _measure(snap_cell, 2 ** 31 + 11, sweep)["units"])


def test_step_that_returns_its_state_unchanged(snap_cell, monkeypatch):
    from madsim_tpu.engine.core import DeviceEngine

    monkeypatch.setattr(DeviceEngine, "_run_steps_impl",
                        lambda self, state, k: state)
    assert not _verdict(snap_cell)["correct"]


def test_half_of_the_batch_left_out(snap_cell):
    from madsim_tpu.parallel.sweep import sweep

    def half(actor, cfg, seeds, faults=None, **kw):
        h = len(seeds) // 2
        res = sweep(actor, cfg, seeds[:h],
                    faults=None if faults is None else faults[:h], **kw)
        obs = {k: np.concatenate([v, v]) for k, v in res.observations.items()}
        return dataclasses.replace(res, observations=obs, bug=obs["bug"],
                                   seeds=seeds)

    assert not _verdict(snap_cell, sweep=half)["correct"]


def test_answer_altered_where_it_is_produced(snap_cell, monkeypatch):
    from madsim_tpu.engine.core import DeviceEngine

    observe = DeviceEngine.observe_device

    def altered(self, state):
        out = observe(self, state)
        return {**out, "max_commit": out["max_commit"] + 1}

    monkeypatch.setattr(DeviceEngine, "observe_device", altered)
    assert not _verdict(snap_cell)["correct"]
