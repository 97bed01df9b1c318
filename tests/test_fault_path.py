"""The step without its fault path (``DeviceEngine._form``).

A sweep in which no world can hold a fault event (no fault rows at init
or at any refill, no ``search=``) runs the step traced without
``apply_fault``, the pop's pause mask, the stale and dead checks and the
push's clog and generation lookups: only fault events write those lanes.
The contract: its results equal, bit for bit, the same seeds run through
the full step, which one disabled fault row per world forces (time -1,
so F = 1 and nothing ever fires). ``loop_stats["fault_path"]`` says
which form ran, and the fault-free form refuses fault rows.
"""
import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from madsim_tpu.engine import DeviceEngine, EngineConfig, RaftActor, RaftDeviceConfig
from madsim_tpu.parallel.sweep import sweep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name, **engine):
    with open(os.path.join(REPO, "benchmark", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    return (RaftDeviceConfig(**cfg["raft"]),
            EngineConfig(**{**cfg["engine"], **engine}))


def _raft3():
    return _config("raft3")


def _hunt():
    # benchmark/traffic/hunt.json's engine over raft3, the bug planted.
    rcfg, ecfg = _config("raft3", queue_cap=64, t_limit_us=2_000_000)
    return dataclasses.replace(rcfg, buggy_double_vote=True), ecfg


def _raft3snap():
    # 10% message loss and no crash rows: the loss drop runs in the
    # fault-free form.
    return _config("raft3snap")


# name -> (configs, seeds, sweep keywords)
CASES = {
    "raft3_pipelined": (_raft3, 64, {}),
    "raft3_serial": (_raft3, 32, {"pipeline": False, "chunk_steps": 64}),
    "raft3_fused_hunt": (_hunt, 96, {"fused": True, "recycle": True,
                                     "batch_worlds": 32, "chunk_steps": 64}),
    "raft3snap_loss": (_raft3snap, 16, {}),
}
_ENGINES: dict = {}


def _engine(name):
    if name not in _ENGINES:
        rcfg, ecfg = CASES[name][0]()
        _ENGINES[name] = DeviceEngine(RaftActor(rcfg), ecfg)
    return _ENGINES[name]


def _disabled_rows(n):
    """One disabled row per world: F = 1, so the full step runs, and no
    fault ever fires."""
    rows = np.zeros((n, 1, 4), np.int32)
    rows[:, :, 0] = -1
    return rows


@pytest.mark.parametrize("name", sorted(CASES))
def test_fault_free_form_is_bitwise_the_full_step(name):
    eng = _engine(name)
    _, n, kw = CASES[name]
    seeds = np.arange(n, dtype=np.uint64) + np.uint64(2**31 + 7)
    free = sweep(None, eng.cfg, seeds, engine=eng, **kw)
    full = sweep(None, eng.cfg, seeds, faults=_disabled_rows(n),
                 engine=eng, **kw)
    assert free.loop_stats["fault_path"] == 0
    assert full.loop_stats["fault_path"] == 1
    assert set(free.observations) == set(full.observations)
    for field, got in free.observations.items():
        np.testing.assert_array_equal(got, full.observations[field],
                                      err_msg=field)
    np.testing.assert_array_equal(free.bug, full.bug)
    assert not free.observations["active"].any()
    assert free.observations["delivered"].min() > 0
    if name == "raft3snap_loss":
        # Without a crash, every InstallSnapshot follows a lost message.
        assert free.observations["installs"].sum() > 0
    if name == "raft3_fused_hunt":
        assert free.bug.any()


def test_choice_follows_the_fault_rows():
    eng = _engine("raft3_serial")
    seeds = np.arange(16)
    kw = dict(engine=eng, chunk_steps=64, max_steps=64)
    width0 = sweep(None, eng.cfg, seeds, faults=np.zeros((0, 4), np.int32),
                   **kw)
    assert width0.loop_stats["fault_path"] == 0
    shared = sweep(None, eng.cfg, seeds,
                   faults=np.array([[-1, 0, 0, 0], [-1, 1, 0, 0]], np.int32),
                   **kw)
    assert shared.loop_stats["fault_path"] == 1


def test_guided_search_keeps_the_fault_path():
    from madsim_tpu.search import (GuidedPairActor, GuidedPairConfig,
                                   engine_config, family_schedule)
    from madsim_tpu.search.family import (HUNT_NODES, HUNT_ROWS,
                                          hunt_search_config)

    acfg = GuidedPairConfig(n=HUNT_NODES)
    cfg = engine_config(acfg)
    res = sweep(GuidedPairActor(acfg), cfg, np.arange(32),
                faults=family_schedule(HUNT_ROWS, acfg),
                search=hunt_search_config(True), recycle=True,
                batch_worlds=16, chunk_steps=32, max_steps=256)
    assert res.loop_stats["fault_path"] == 1


def test_fault_free_step_has_no_fault_scope():
    eng = _engine("raft3_serial")
    state = eng.init(np.arange(8))

    def text(e):
        return jax.jit(e._batched_step).lower(state).as_text(debug_info=True)

    full = text(eng)
    free = text(eng._form(False))
    assert "madsim/fault" in full and "madsim/pop" in full
    assert "madsim/fault" not in free and "madsim/pop" in free
    assert eng._form(True) is eng
    assert eng._form(False) is eng._form(False)
    assert eng.fault_path and not eng._form(False).fault_path


def test_fault_free_form_refuses_fault_rows():
    eng = _engine("raft3_serial")
    free = eng._form(False)
    seeds = np.arange(8)
    row = np.array([[-1, 0, 0, 0]], np.int32)
    with pytest.raises(ValueError, match="without its fault path"):
        free.init(seeds, faults=row)
    state = free.init(seeds)
    mask = np.ones(8, bool)
    with pytest.raises(ValueError, match="without its fault path"):
        free.refill(state, mask, seeds, faults=row)
    with pytest.raises(ValueError, match="without its fault path"):
        free.refill(state, mask, seeds,
                    faults=jax.numpy.asarray(_disabled_rows(8)))
    lo = jax.numpy.zeros((8,), jax.numpy.uint32)
    with pytest.raises(ValueError, match="without its fault path"):
        free.refill_traced(state, jax.numpy.asarray(mask), lo, lo,
                           jax.numpy.asarray(_disabled_rows(8)))
    # Width 0 is no fault row: the refill goes through.
    state = free.refill(state, mask, seeds, faults=np.zeros((0, 4), np.int32))
    assert np.asarray(state.active).all()
