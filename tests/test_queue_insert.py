"""Single-pass outbox insertion (queue.push_many), the carried queue-depth
lane, buffer donation, and the per-step op budget (PR "Single-pass outbox
insertion, incremental queue depth, and donated step buffers").

The load-bearing contract: ``push_many`` (and the engine built on it) is
**bitwise identical** to the statically unrolled sequential push chain it
replaced. The sequential path is kept alive behind
``EngineConfig(sequential_insert=True)`` precisely so these tests can run
whole trajectories both ways and compare every state leaf.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu.engine import (
    DeviceEngine, EngineConfig, RaftActor, RaftDeviceConfig,
    PBActor, PBDeviceConfig, TPCActor, TPCDeviceConfig,
    FAULT_KILL, FAULT_PAUSE, FAULT_RESTART, FAULT_SET_LATENCY, INF_TIME,
)
from madsim_tpu.engine.queue import (
    Event, depth, empty_queue, pop, pop_indexed, push, push_many,
)


def _random_events(rng, m, p):
    times = rng.integers(0, 120, m)
    # INF_TIME events must be dropped without consuming a slot.
    times = np.where(rng.random(m) < 0.2, int(INF_TIME), times)
    return Event(
        time=jnp.asarray(times, jnp.int32),
        kind=jnp.asarray(rng.integers(0, 6, m), jnp.int32),
        flags=jnp.asarray(rng.integers(0, 4, m), jnp.int32),
        src=jnp.asarray(rng.integers(0, 4, m), jnp.int32),
        dst=jnp.asarray(rng.integers(0, 4, m), jnp.int32),
        gen=jnp.asarray(rng.integers(0, 256, m), jnp.int32),
        payload=jnp.asarray(rng.integers(0, 1000, (m, p)), jnp.int32),
    )


def _push_sequentially(q, evs, enable):
    oks = []
    for i in range(evs.time.shape[0]):
        ev = Event(time=evs.time[i], kind=evs.kind[i], flags=evs.flags[i],
                   src=evs.src[i], dst=evs.dst[i], gen=evs.gen[i],
                   payload=evs.payload[i])
        q, ok = push(q, ev, enable=bool(enable[i]))
        oks.append(bool(ok))
    return q, oks


def _queues_equal(a, b):
    return (np.array_equal(a.time, b.time) and np.array_equal(a.meta, b.meta)
            and np.array_equal(a.payload, b.payload))


# ---------------------------------------------------------------------------
# Queue-level equivalence: push_many == the sequential push chain
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("trials,caps,ms", [
    (60, (2, 70), (1, 9)),
    # Wide batches (init's whole fault schedule) take the sorted slot
    # assignment instead of the unrolled chain: same contract.
    (3, (200, 260), (100, 240)),
], ids=["unrolled", "sorted"])
def test_push_many_matches_sequential_chain_randomized(trials, caps, ms):
    """Randomized queues (pre-filled, holey after pops) x event batches
    (INF times, disabled slots, more events than capacity): the fused
    insert must reproduce the chain's slot assignment, ok flags, and
    inserted count exactly."""
    rng = np.random.default_rng(0)
    for trial in range(trials):
        cap = int(rng.integers(*caps))
        m = int(rng.integers(*ms))
        p = int(rng.integers(1, 5))
        q = empty_queue(cap, p)
        for _ in range(int(rng.integers(0, cap + 1))):
            q, _ = push(q, Event.make(time=int(rng.integers(0, 50)),
                                      kind=int(rng.integers(0, 6)),
                                      payload_words=p))
        for _ in range(int(rng.integers(0, 5))):  # punch holes
            q, _, _ = pop(q)
        evs = _random_events(rng, m, p)
        enable = rng.random(m) < 0.8
        q_seq, oks = _push_sequentially(q, evs, enable)
        q_fused, ok_f, n_ins = push_many(q, evs, jnp.asarray(enable))
        assert _queues_equal(q_seq, q_fused), f"trial {trial}"
        assert oks == [bool(x) for x in ok_f], f"trial {trial}"
        assert int(depth(q_fused)) - int(depth(q)) == int(n_ins), f"trial {trial}"


def test_push_many_overflow_mid_batch():
    """More enabled events than free slots: the first n_free (in event
    order) land, the rest report ok=False and write nothing."""
    q = empty_queue(4, 2)
    q, _ = push(q, Event.make(time=5, kind=1, payload_words=2))
    q, _ = push(q, Event.make(time=6, kind=2, payload_words=2))
    evs = Event(time=jnp.asarray([10, 11, 12, 13], jnp.int32),
                kind=jnp.asarray([7, 8, 9, 10], jnp.int32),
                flags=jnp.zeros((4,), jnp.int32), src=jnp.zeros((4,), jnp.int32),
                dst=jnp.zeros((4,), jnp.int32), gen=jnp.zeros((4,), jnp.int32),
                payload=jnp.zeros((4, 2), jnp.int32))
    q2, ok, n_ins = push_many(q, evs)
    assert [bool(x) for x in ok] == [True, True, False, False]
    assert int(n_ins) == 2
    assert int(depth(q2)) == 4
    q_seq, oks = _push_sequentially(q, evs, np.ones(4, bool))
    assert _queues_equal(q_seq, q2) and oks == [True, True, False, False]


def test_push_many_inf_time_dropped_without_slot():
    q = empty_queue(2, 2)
    evs = Event(time=jnp.asarray([int(INF_TIME), 7, 8], jnp.int32),
                kind=jnp.asarray([1, 2, 3], jnp.int32),
                flags=jnp.zeros((3,), jnp.int32), src=jnp.zeros((3,), jnp.int32),
                dst=jnp.zeros((3,), jnp.int32), gen=jnp.zeros((3,), jnp.int32),
                payload=jnp.zeros((3, 2), jnp.int32))
    q2, ok, n_ins = push_many(q, evs)
    # The INF event is dropped ok=True and the two real events still fit.
    assert [bool(x) for x in ok] == [True, True, True]
    assert int(n_ins) == 2
    _, ev, found = pop(q2)
    assert bool(found) and int(ev.kind) == 2


def test_push_many_clear_fuses_the_pop():
    """push_many(q, ..., clear=(slot, found)) == pop the slot first, then
    push — including the popped slot being immediately reusable."""
    rng = np.random.default_rng(1)
    for trial in range(40):
        cap = int(rng.integers(2, 20))
        p = int(rng.integers(1, 4))
        q = empty_queue(cap, p)
        for _ in range(int(rng.integers(0, cap + 1))):
            q, _ = push(q, Event.make(time=int(rng.integers(0, 50)),
                                      kind=int(rng.integers(0, 6)),
                                      payload_words=p))
        m = int(rng.integers(1, 6))
        evs = _random_events(rng, m, p)
        enable = jnp.asarray(rng.random(m) < 0.8)
        q_pop, _ev, found, slot = pop_indexed(q)
        a, ok_a, n_a = push_many(q_pop, evs, enable)
        b, ok_b, n_b = push_many(q, evs, enable, clear=(slot, found))
        assert _queues_equal(a, b), f"trial {trial}"
        assert np.array_equal(ok_a, ok_b) and int(n_a) == int(n_b)


# ---------------------------------------------------------------------------
# Engine-level equivalence: whole trajectories, all three actor families
# ---------------------------------------------------------------------------

def _leaves_bitwise_equal(a, b):
    mismatched = []
    paths = [jax.tree_util.keystr(pth)
             for pth, _ in jax.tree_util.tree_flatten_with_path(a)[0]]
    for path, x, y in zip(paths, jax.tree.leaves(a), jax.tree.leaves(b)):
        if not np.array_equal(np.asarray(x), np.asarray(y)):
            mismatched.append(path)
    return mismatched


def _run_both_ways(actor, cfg, seeds, faults=None, max_steps=5_000):
    fused = DeviceEngine(actor, cfg)
    seq = DeviceEngine(actor, dataclasses.replace(cfg, sequential_insert=True))
    sf = fused.run(fused.init(seeds, faults=faults), max_steps)
    ss = seq.run(seq.init(seeds, faults=faults), max_steps)
    mism = _leaves_bitwise_equal(sf, ss)
    assert not mism, f"fused vs sequential diverged on: {mism}"
    return fused, sf


def test_raft_trajectories_bitwise_equal_incl_faults():
    actor = RaftActor(RaftDeviceConfig(n=3, n_proposals=2,
                                       buggy_double_vote=True))
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                       t_limit_us=2_500_000, stop_on_bug=False)
    faults = np.array([[400_000, FAULT_KILL, 0, 0],
                       [900_000, FAULT_RESTART, 0, 0]], np.int32)
    _run_both_ways(actor, cfg, np.arange(48), faults=faults)


def test_raft_overflow_mid_batch_bitwise_equal():
    """A queue too small for the traffic: worlds overflow mid-outbox
    (some of a handler's sends land, the rest drop) and the two engines
    must still agree bitwise — including the overflow flag."""
    actor = RaftActor(RaftDeviceConfig(n=3, n_proposals=2))
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=8,
                       t_limit_us=2_000_000, stop_on_bug=False)
    eng, state = _run_both_ways(actor, cfg, np.arange(48))
    assert eng.observe(state)["overflow"].any(), (
        "config failed to overflow — the overflow-mid-batch path went "
        "unexercised; shrink queue_cap")


def test_raft_inf_saturated_sends_bitwise_equal():
    """Latency hot-set near int32 max: deliveries at ~2e9 µs make the
    *next* hop saturate to INF_TIME and drop at push. Both engines must
    drop identically."""
    actor = RaftActor(RaftDeviceConfig(n=3))
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                       t_limit_us=2**31 - 2, stop_on_bug=False)
    slow = np.array([[0, FAULT_SET_LATENCY, 2_000_000_000, 2_147_483_646]],
                    np.int32)
    _run_both_ways(actor, cfg, np.arange(16), faults=slow, max_steps=2_000)


def test_raft_pause_all_ineligible_pops_bitwise_equal():
    """Every node paused, nothing ever eligible: pop finds nothing on a
    non-empty queue, worlds freeze — identically in both engines."""
    actor = RaftActor(RaftDeviceConfig(n=3))
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                       t_limit_us=2_000_000)
    faults = np.array([[0, FAULT_PAUSE, 0, 0],
                       [0, FAULT_PAUSE, 1, 0],
                       [0, FAULT_PAUSE, 2, 0]], np.int32)
    eng, state = _run_both_ways(actor, cfg, np.arange(8), faults=faults,
                                max_steps=2_000)
    obs = eng.observe(state)
    assert not obs["active"].any() and not obs["bug"].any()
    assert (obs["queue_depth"] > 0).all()  # frozen with buffered events


def test_pb_trajectories_bitwise_equal():
    actor = PBActor(PBDeviceConfig(n=3, n_writes=4))
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                       t_limit_us=1_500_000, loss_rate=0.05)
    _run_both_ways(actor, cfg, np.arange(48))


def test_tpc_trajectories_bitwise_equal():
    actor = TPCActor(TPCDeviceConfig(n=4, n_txns=4,
                                     buggy_presumed_commit=True))
    cfg = EngineConfig(n_nodes=4, outbox_cap=5, queue_cap=64,
                       t_limit_us=1_500_000, loss_rate=0.1)
    _run_both_ways(actor, cfg, np.arange(48))


# ---------------------------------------------------------------------------
# The carried depth lane
# ---------------------------------------------------------------------------

def test_carried_depth_equals_recomputed_reduction():
    """WorldState.qdepth (maintained incrementally by pop/push_many) must
    equal the O(Q) recomputed reduction at every observation point, over
    mixed push/pop/overflow/pause trajectories."""
    configs = [
        # overflow-heavy (tiny queue), clean, and pause-buffered worlds
        (RaftActor(RaftDeviceConfig(n=3, n_proposals=2)),
         EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=8,
                      t_limit_us=2_000_000, stop_on_bug=False), None),
        (RaftActor(RaftDeviceConfig(n=3, buggy_double_vote=True)),
         EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                      t_limit_us=2_000_000), None),
        (RaftActor(RaftDeviceConfig(n=3)),
         EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                      t_limit_us=2_000_000),
         np.array([[100_000, FAULT_PAUSE, 0, 0],
                   [500_000, FAULT_KILL, 1, 0]], np.int32)),
    ]
    for actor, cfg, faults in configs:
        eng = DeviceEngine(actor, cfg)
        state = eng.init(np.arange(32), faults=faults)
        for _ in range(6):  # several mid-run checkpoints, not just the end
            state = eng.run_steps(state, 100)
            carried = np.asarray(state.qdepth)
            recomputed = np.asarray(jax.vmap(depth)(state.queue))
            np.testing.assert_array_equal(carried, recomputed)
        # qmax is the high-water mark of the carried value.
        assert (np.asarray(state.qmax) >= np.asarray(state.qdepth)).all()
        assert (np.asarray(eng.observe(state)["queue_depth"])
                == recomputed).all()


# ---------------------------------------------------------------------------
# Op budget + donated memory (the two tier-1 regression gates)
# ---------------------------------------------------------------------------

# Cost-model flops per world-step for the time_to_first_bug engine config
# (3-node, queue_cap=64), measured via compiled.cost_analysis() on the CPU
# backend. Measured 7727 after the single-pass insert landed (the
# pre-rewrite step measured 21469 — a 2.8x reduction). The budget now
# lives in the checked-in ledger `madsim_tpu/analysis/budgets.json`
# (engine.run entry) — ONE source of truth shared with `make tracelint`
# — regenerated via `tools/update_budgets.py --reason '...'` IN THE SAME
# PR as any change that legitimately alters the step's op count, with
# the new measurement in docs/perf.md.
from madsim_tpu.analysis import budgets as _budgets

_LEDGER = _budgets.load_ledger()
FLOPS_PER_WORLD_STEP_BUDGET = _budgets.budget_for(
    _LEDGER, "engine.run", "flops_per_world")
PEAK_OVER_STATE_BUDGET = _budgets.budget_for(
    _LEDGER, "engine.run", "peak_over_arg")
assert FLOPS_PER_WORLD_STEP_BUDGET and PEAK_OVER_STATE_BUDGET, (
    "analysis/budgets.json lost its engine.run budgets — regenerate via "
    "tools/update_budgets.py")


def _bug_config_engine():
    rcfg = RaftDeviceConfig(n=3, buggy_double_vote=True)
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                       t_limit_us=2_000_000, stop_on_bug=False)
    return DeviceEngine(RaftActor(rcfg), cfg)


# Compile BYPASSING the persistent compilation cache (conftest.py): an
# executable deserialized from the cache loses parts of its cost/memory
# statistics (alias_size_in_bytes reads 0), which would let the budget
# gates below silently pass-or-fail on cache state instead of on the
# program. The shared implementation lives in analysis/budgets.py, next
# to the ledger the measurements feed.
_compile_fresh = _budgets.compile_fresh


def test_step_op_budget_regression():
    eng = _bug_config_engine()
    w = 256
    state = eng.init(np.arange(w))
    comp = _compile_fresh(eng._run.lower(state, 4_000))
    ca = comp.cost_analysis()
    per_world = float(ca["flops"]) / w
    assert per_world <= FLOPS_PER_WORLD_STEP_BUDGET, (
        f"step costs {per_world:.0f} cost-model flops/world-step, over the "
        f"recorded budget {FLOPS_PER_WORLD_STEP_BUDGET}. If the increase "
        "is intentional, re-measure and update the budget in this file "
        "and docs/perf.md in the same PR.")


def test_donated_run_peak_memory():
    """The donated run path aliases the whole input state (no double
    buffer): peak ≈ state + loop temporaries must stay under 1.2x the
    argument size (it was ~2.7x before donation + the single-pass
    insert's temp work)."""
    eng = _bug_config_engine()
    state = eng.init(np.arange(1024))
    comp = _compile_fresh(eng._run.lower(state, 4_000))
    ma = comp.memory_analysis()
    assert ma.alias_size_in_bytes == ma.argument_size_in_bytes, (
        "donation did not alias the full input state")
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    ratio = peak / ma.argument_size_in_bytes
    assert ratio <= PEAK_OVER_STATE_BUDGET, (
        f"donated-run peak is {ratio:.3f}x the argument state "
        f"(temp {ma.temp_size_in_bytes} B); the no-double-buffer "
        f"contract (analysis/budgets.json engine.run) allows at most "
        f"{PEAK_OVER_STATE_BUDGET}x")


def test_run_donates_its_input_state():
    """The documented contract: the state passed to run()/run_steps() is
    dead afterwards — reading it raises. (This is what the sweep, bench
    and every in-repo caller rely on; anyone holding the argument must
    rebind instead.)"""
    eng = _bug_config_engine()
    state = eng.init(np.arange(8))
    out = eng.run(state, max_steps=50)
    jax.block_until_ready(out)
    with pytest.raises(RuntimeError, match="deleted|donated"):
        _ = np.asarray(state.now)


# ---------------------------------------------------------------------------
# The TPU's select forms (lanes.gathers_are_cheap() False) == the gather
# and scatter forms the CPU compiles, whole trajectories
# ---------------------------------------------------------------------------

_FAULTS = np.array([[400_000, FAULT_KILL, 0, 0],
                    [900_000, FAULT_RESTART, 0, 0]], np.int32)


@pytest.mark.parametrize("family", ["raft_faults", "raft_overflow",
                                    "raft5_wide_faults", "pb", "tpc"])
def test_select_forms_bitwise_equal_gather_forms(family, monkeypatch):
    from madsim_tpu.engine import lanes

    faults = None
    if family == "raft_faults":
        actor = RaftActor(RaftDeviceConfig(n=3, n_proposals=2,
                                           buggy_double_vote=True))
        cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                           t_limit_us=2_500_000, stop_on_bug=False)
        faults = _FAULTS
    elif family == "raft_overflow":
        actor = RaftActor(RaftDeviceConfig(n=3, n_proposals=2))
        cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=8,
                           t_limit_us=2_000_000, stop_on_bug=False)
    elif family == "raft5_wide_faults":
        # 80 fault rows: init's wide push takes the sorted slot path.
        actor = RaftActor(RaftDeviceConfig(n=5, n_proposals=2, log_cap=8))
        cfg = EngineConfig(n_nodes=5, outbox_cap=6, queue_cap=128,
                           t_limit_us=1_500_000, stop_on_bug=False)
        t = 100_000 + 10_000 * np.arange(40)
        faults = np.concatenate([
            np.stack([t, np.full(40, FAULT_KILL), t % 5, 0 * t], 1),
            np.stack([t + 5_000, np.full(40, FAULT_RESTART), t % 5, 0 * t],
                     1)]).astype(np.int32)
    elif family == "pb":
        actor = PBActor(PBDeviceConfig(n=3, n_writes=4))
        cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                           t_limit_us=1_500_000, loss_rate=0.05)
    else:
        actor = TPCActor(TPCDeviceConfig(n=4, n_txns=4,
                                         buggy_presumed_commit=True))
        cfg = EngineConfig(n_nodes=4, outbox_cap=5, queue_cap=64,
                           t_limit_us=1_500_000, loss_rate=0.1)
    seeds = np.arange(24)
    gather = DeviceEngine(actor, cfg)
    sg = gather.run(gather.init(seeds, faults=faults), 4_000)
    monkeypatch.setattr(lanes, "gathers_are_cheap", lambda: False)
    select = DeviceEngine(actor, cfg)
    ss = select.run(select.init(seeds, faults=faults), 4_000)
    mism = _leaves_bitwise_equal(sg, ss)
    assert not mism, f"select vs gather forms diverged on: {mism}"


@pytest.mark.parametrize("default, cheap", [
    (None, True), ("cpu", True), ("tpu", False), ("cpu_device", True)])
def test_form_follows_the_default_device(default, cheap):
    """The form follows the platform a program is traced for: a set
    default device wins over the default backend, so the CPU leg of a
    chip-vs-CPU crosscheck compiles the CPU's own program."""
    from madsim_tpu.engine import lanes

    if default == "cpu_device":
        default = jax.devices("cpu")[0]
    with jax.default_device(default):
        assert lanes.gathers_are_cheap() is cheap
