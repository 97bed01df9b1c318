"""The fused Pallas step kernel (engine/pallas_step.py, PR "Roofline
round 2").

The one contract: ``EngineConfig(pallas=True)`` is **bitwise identical**
to the lax step across whole trajectories — the kernel body IS the
vmapped step function, so any divergence means the Pallas plumbing
(constant hoisting, input/output aliasing, block specs) corrupted
state. On CPU the kernel runs in interpret mode (the auto default), so
this file is also what keeps the TPU kernel's CPU fallback green.
``pallas=False`` stays the default: tier-1 compiles the existing lax
programs unchanged.
"""
import dataclasses

import jax
import numpy as np
import pytest

from madsim_tpu.engine import (
    DeviceEngine,
    EngineConfig,
    FAULT_KILL,
    FAULT_RESTART,
    RaftActor,
    RaftDeviceConfig,
)

SEEDS = np.arange(16)


def _leaves_equal(a, b):
    paths = [jax.tree_util.keystr(p) for p, _
             in jax.tree_util.tree_flatten_with_path(a)[0]]
    return [pth for pth, x, y in zip(paths, jax.tree.leaves(a),
                                     jax.tree.leaves(b))
            if not np.array_equal(np.asarray(x), np.asarray(y))]


@pytest.fixture(scope="module")
def raft_pair():
    """One lax + one pallas engine on the shared bug config (module
    scope: the compile dominates this file's runtime)."""
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                       t_limit_us=1_500_000, stop_on_bug=False)
    mk = lambda: RaftActor(RaftDeviceConfig(n=3, n_proposals=2,  # noqa: E731
                                            buggy_double_vote=True))
    return (DeviceEngine(mk(), cfg),
            DeviceEngine(mk(), dataclasses.replace(cfg, pallas=True)),
            mk, cfg)


def test_pallas_off_by_default():
    cfg = EngineConfig(n_nodes=3)
    assert cfg.pallas is False and cfg.pallas_interpret is None


def test_pallas_run_bitwise_identical_incl_faults(raft_pair):
    lax_eng, pls_eng, _, _ = raft_pair
    faults = np.array([[300_000, FAULT_KILL, 0, 0],
                       [700_000, FAULT_RESTART, 0, 0]], np.int32)
    sl = lax_eng.run(lax_eng.init(SEEDS, faults=faults), 2_000)
    sp = pls_eng.run(pls_eng.init(SEEDS, faults=faults), 2_000)
    mism = _leaves_equal(sl, sp)
    assert not mism, f"pallas vs lax diverged on: {mism}"
    assert np.asarray(sp.bug).any()  # the trajectory actually found bugs


def test_pallas_run_steps_bitwise_identical(raft_pair):
    lax_eng, pls_eng, _, _ = raft_pair
    sl, sp = lax_eng.init(SEEDS), pls_eng.init(SEEDS)
    for _ in range(3):
        sl = lax_eng.run_steps(sl, 150)
        sp = pls_eng.run_steps(sp, 150)
        mism = _leaves_equal(sl, sp)
        assert not mism, f"pallas vs lax diverged mid-run on: {mism}"


def test_pallas_overflow_mid_batch_bitwise_identical():
    """A queue too small for the traffic: handlers overflow mid-outbox.
    The kernel must reproduce the partial-insert/overflow-flag dataflow
    exactly."""
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=8,
                       t_limit_us=2_000_000, stop_on_bug=False)
    mk = lambda: RaftActor(RaftDeviceConfig(n=3, n_proposals=2))  # noqa: E731
    lax_eng = DeviceEngine(mk(), cfg)
    pls_eng = DeviceEngine(mk(), dataclasses.replace(cfg, pallas=True))
    sl = lax_eng.run(lax_eng.init(SEEDS), 3_000)
    sp = pls_eng.run(pls_eng.init(SEEDS), 3_000)
    mism = _leaves_equal(sl, sp)
    assert not mism, f"pallas vs lax diverged on: {mism}"
    assert np.asarray(sp.overflow).any(), (
        "config failed to overflow — the overflow-mid-batch path went "
        "unexercised; shrink queue_cap")


def test_pallas_world_block_grid_bitwise_identical(raft_pair):
    """pallas_block grids the kernel over the world axis (the VMEM-fit
    knob on TPU); it must stay bitwise identical to the monolithic
    kernel."""
    lax_eng, _, mk, cfg = raft_pair
    sl = lax_eng.run(lax_eng.init(SEEDS), 1_000)
    eng = DeviceEngine(mk(), dataclasses.replace(
        cfg, pallas=True, pallas_block=4))
    sb = eng.run(eng.init(SEEDS), 1_000)
    mism = _leaves_equal(sl, sb)
    assert not mism, f"pallas_block=4 diverged on: {mism}"


def test_pallas_block_validation():
    with pytest.raises(ValueError, match="pallas_block"):
        EngineConfig(n_nodes=3, pallas=True, pallas_block=0)


def test_pallas_block_must_divide_the_batch(raft_pair):
    """A block that does not divide W raises; it used to fall back to
    one block in silence."""
    _, _, mk, cfg = raft_pair
    eng = DeviceEngine(mk(), dataclasses.replace(
        cfg, pallas=True, pallas_block=5))
    with pytest.raises(ValueError, match="pallas_block=5 does not divide"):
        eng.run(eng.init(SEEDS), 10)


@pytest.mark.parametrize("interpret", [None, False])
def test_pallas_refuses_mosaic_lowering_on_tpu(raft_pair, monkeypatch,
                                               interpret):
    """On a TPU backend (steered here: the suite runs on the CPU) the
    kernel is refused at construction, naming both Mosaic gaps, instead
    of an assertion deep in Mosaic at first trace."""
    from madsim_tpu.engine import pallas_step

    monkeypatch.setattr(pallas_step.jax, "default_backend", lambda: "tpu")
    _, _, mk, cfg = raft_pair
    with pytest.raises(NotImplementedError) as ei:
        DeviceEngine(mk(), dataclasses.replace(
            cfg, pallas=True, pallas_interpret=interpret))
    for gap in ("rank >= 1", "_gather_lowering_rule"):
        assert gap in str(ei.value)


def test_pallas_state_is_donated_through_the_kernel():
    """The registry's jitted kernel step donates its input state, and
    the aliasing survives the pallas_call (input_output_aliases): the
    ledger's alias_fraction floor for engine.pallas_step rides on this.
    """
    from madsim_tpu.analysis import budgets as B

    floor = B.budget_for(B.load_ledger(), "engine.pallas_step",
                         "alias_fraction")
    assert floor is not None and floor >= 0.99, (
        "engine.pallas_step lost its full-donation floor in the ledger")
