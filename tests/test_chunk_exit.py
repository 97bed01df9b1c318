"""The chunk loop's exit on all-frozen shards (docs/perf.md "Chunk exit on
frozen shards").

``DeviceEngine._run_steps_impl`` runs a chunk as blocks of
``EXIT_BLOCK`` steps and stops at the first block boundary where no world
of its batch (its shard, under ``shard_map``) is live. The step is the
identity on a frozen world, so the result must equal a plain fixed-length
``lax.scan`` of the batched step leaf for leaf, and the executed-step
count (``_steps_executed``) must equal the steps the loop really ran.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu.engine import (
    DeviceEngine,
    EngineConfig,
    PBActor,
    PBDeviceConfig,
    RaftActor,
    RaftDeviceConfig,
    TPCActor,
    TPCDeviceConfig,
)
from madsim_tpu.engine.core import EXIT_BLOCK
from madsim_tpu.parallel import seed_mesh, shard_worlds
from madsim_tpu.parallel.sweep import sharded_engine, sweep

W = 16


def _raft3():
    # benchmark/configs/raft3.json: 3-server election, 1 virtual second;
    # every world freezes between steps 112 and 130.
    return DeviceEngine(
        RaftActor(RaftDeviceConfig(n=3, log_cap=4, elect_min_us=150_000,
                                   elect_max_us=300_000, heartbeat_us=50_000,
                                   n_proposals=0)),
        EngineConfig(n_nodes=3, queue_cap=28, outbox_cap=4,
                     t_limit_us=1_000_000, stop_on_bug=True))


def _pb():
    # Freezes between steps 153 and 182.
    return DeviceEngine(
        PBActor(PBDeviceConfig(n=3, n_writes=4)),
        EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                     t_limit_us=1_500_000, loss_rate=0.05))


def _tpc():
    # Buggy, stop_on_bug: freezes between steps 7 and 42.
    return DeviceEngine(
        TPCActor(TPCDeviceConfig(n=4, n_txns=4, buggy_presumed_commit=True)),
        EngineConfig(n_nodes=4, outbox_cap=5, queue_cap=64,
                     t_limit_us=1_500_000, loss_rate=0.1, stop_on_bug=True))


FAMILIES = {"raft": _raft3, "pb": _pb, "tpc": _tpc}
_ENGINES: dict = {}


def _engine(family):
    if family not in _ENGINES:
        _ENGINES[family] = FAMILIES[family]()
    return _ENGINES[family]


def _reference(eng, state, k):
    """k steps as one fixed scan, and per step whether any world was live
    before it."""
    def body(s, _):
        return eng._batched_step(s), jnp.any(s.active)

    out, live = jax.jit(lambda s: jax.lax.scan(body, s, None, length=k))(
        state)
    return out, np.asarray(live)


def _ran(live, k):
    """Steps the block loop runs: block b runs iff a world is live at its
    first step (frozen worlds never wake)."""
    return sum(min(EXIT_BLOCK, k - b) for b in range(0, k, EXIT_BLOCK)
               if live[b]) if k > EXIT_BLOCK else k


def _assert_leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# 256: every family freezes mid-chunk; 32: the batch is live at every
# block boundary; 40: not a multiple of 16, the remainder runs.
@pytest.mark.parametrize("k", [256, 32, 40],
                         ids=["freezes", "never_freezes", "k40"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_chunk_exit_equals_fixed_scan(family, k):
    eng = _engine(family)
    state = eng.init(np.arange(W))
    want, live = _reference(eng, state, k)
    got = jax.jit(eng._run_steps_impl, static_argnums=1)(state, k)
    _assert_leaves_equal(got, want)
    ran = int(eng._steps_executed(state.steps, got, k))
    assert ran == _ran(live, k)
    if k == 256:
        assert ran < k and not np.asarray(got.active).any()
    else:
        assert ran == k


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_chunk_exit_per_shard_on_two_devices(family):
    """Each shard stops on its own: shard 0 starts frozen and runs no
    step, shard 1 runs until its own worlds freeze."""
    eng = _engine(family)
    k = 256
    mesh = seed_mesh(n_devices=2)
    state = eng.init(np.arange(W))
    state = state._replace(active=state.active.at[:W // 2].set(False))
    want, _ = _reference(eng, state, k)
    half = jax.tree.map(lambda x: x[W // 2:], state)
    _, live1 = _reference(eng, half, k)
    got, _bug, n_active, shard_steps = sharded_engine(
        eng, mesh, chunk_steps=k)(shard_worlds(state, mesh))
    _assert_leaves_equal(got, want)
    assert int(n_active) == 0
    assert int(shard_steps) == _ran(live1, k)    # shard 0 ran none
    assert 0 < int(shard_steps) < k


def _top_level_primitives(eng, k):
    state = eng.init(np.arange(4))
    jaxpr = jax.make_jaxpr(lambda s: eng._run_steps_impl(s, k))(state)
    return {e.primitive.name for e in jaxpr.jaxpr.eqns}


def test_short_chunk_is_the_plain_scan():
    eng = _engine("raft")
    assert "while" not in _top_level_primitives(eng, EXIT_BLOCK)
    assert "scan" in _top_level_primitives(eng, EXIT_BLOCK)
    assert "while" in _top_level_primitives(eng, 512)


def test_world_utilization_counts_executed_steps():
    """A raft3-sized sweep at the default 512-step chunk: the chunk exit
    leaves out most slot-steps, utilization is live steps over executed
    ones, and the serial and pipelined loops count alike."""
    eng = _engine("raft")
    seeds = np.arange(64)
    ser, pip = (sweep(None, eng.cfg, seeds, engine=eng, chunk_steps=512,
                      pipeline=p) for p in (False, True))
    for res in (ser, pip):
        assert res.world_utilization > 0.7, res.world_utilization
        assert res.loop_stats["slot_steps_skipped"] > 0
        live = int(np.asarray(res.observations["steps"]).sum())
        planned = 64 * 512 * res.loop_stats["chunks"]
        executed = planned - res.loop_stats["slot_steps_skipped"]
        assert res.world_utilization == pytest.approx(live / executed)
    assert ser.world_utilization == pip.world_utilization
    assert ser.loop_stats["slot_steps_skipped"] == \
        pip.loop_stats["slot_steps_skipped"]
    assert ser.steps_run == pip.steps_run
    np.testing.assert_array_equal(ser.n_active_history, pip.n_active_history)
    for k in ser.observations:
        np.testing.assert_array_equal(ser.observations[k],
                                      pip.observations[k])
