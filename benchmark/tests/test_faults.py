"""A run with the timed path broken underneath reads ``correct`` false:
once for each fault a cell can have (every cell runs on one chip, so
none can lose an exchange between chips). Everything but the look for a
chip runs as in ``run.py``, at test size on the CPU."""
import dataclasses

import numpy as np
import pytest

import run

CELLS = ["raft3.sweep", "raft5.chaos", "raft3.hunt"]


def _verdict(small_cell, name, sweep=None):
    jax, cell, cfg, traffic, devices = small_cell(name)
    m = run.measure(jax, cell, cfg, traffic, 2 ** 31 + 11, 0.5, False,
                    devices, sweep=sweep)
    return run.decide(cfg, traffic, m["units"])


def _with_obs(res, obs):
    return dataclasses.replace(res, observations=obs, bug=obs["bug"])


@pytest.mark.parametrize("name", CELLS)
def test_step_that_returns_its_state_unchanged(small_cell, name,
                                               monkeypatch):
    from madsim_tpu.engine.core import DeviceEngine

    monkeypatch.setattr(DeviceEngine, "_run_steps_impl",
                        lambda self, state, k: state)
    assert not _verdict(small_cell, name)["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_half_of_the_batch_left_out(small_cell, name):
    from madsim_tpu.parallel.sweep import sweep

    def half(actor, cfg, seeds, faults=None, **kw):
        h = len(seeds) // 2
        res = sweep(actor, cfg, seeds[:h],
                    faults=None if faults is None else faults[:h], **kw)
        obs = {k: np.concatenate([v, v]) for k, v in res.observations.items()}
        return dataclasses.replace(_with_obs(res, obs), seeds=seeds)

    assert not _verdict(small_cell, name, sweep=half)["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_answer_altered_where_it_is_produced(small_cell, name,
                                            monkeypatch):
    from madsim_tpu.engine.core import DeviceEngine

    observe = DeviceEngine.observe_device

    def altered(self, state):
        out = observe(self, state)
        return {**out, "delivered": out["delivered"] + 1}

    monkeypatch.setattr(DeviceEngine, "observe_device", altered)
    assert not _verdict(small_cell, name)["correct"]

