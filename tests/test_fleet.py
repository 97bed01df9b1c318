"""Fleet fabric (madsim_tpu/fleet, docs/fleet.md): leased seed ranges,
crash-identical recovery, duplicate-completion crosschecks.

The headline contract (ISSUE 7 acceptance): a 2+-worker fleet sweep
with injected worker kills, lease expiries, duplicated completions,
SIGTERM preemptions, and torn checkpoints returns a SweepResult whose
CONTRACT fields — seed ids, bug flags, per-seed observations (incl. the
``m_*`` metrics frames), coverage ledger hits/first-seen — are bitwise
identical to BOTH a crash-free fleet run and a single-host ``sweep()``
over the same seeds, for raft/pb/tpc. Fabric telemetry (histories,
loop_stats) legitimately differs and is excluded.
"""
import json

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
from madsim_tpu.fleet import (
    ChaosConfig,
    Coordinator,
    FleetIntegrityError,
    LeaseTable,
    RetryPolicy,
    SeedRange,
    VirtualClock,
    fleet_sweep,
    split_ranges,
)
from madsim_tpu.parallel.sweep import sweep

RCFG = RaftDeviceConfig(n=3, buggy_double_vote=True)
ECFG = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                    t_limit_us=1_500_000, stop_on_bug=True)
SWEEP_KW = dict(chunk_steps=64, max_steps=20_000)

# The full failure mix in one config: explicit kill, preemption, lease
# expiry via the kill, duplicated completions, transient RPC failures.
CHAOS = ChaosConfig(seed=11, kill_at=(("w0", 2),),
                    preempt_at=(("w1", 5),),
                    duplicate_all_completions=True,
                    drop_rpc_rate=0.25, drop_heartbeat_rate=0.1,
                    restart_after=2)


@pytest.fixture(scope="module")
def raft_eng():
    # metrics=True so the acceptance check covers the coverage ledger
    # and the per-seed m_* metrics frames too.
    import dataclasses

    return DeviceEngine(RaftActor(RCFG),
                        dataclasses.replace(ECFG, metrics=True))


RAFT_SEEDS = np.arange(64)


@pytest.fixture(scope="module")
def raft_single(raft_eng):
    """Single-host reference over RAFT_SEEDS — computed once; every
    fleet leg in this module compares against the same run."""
    return sweep(None, raft_eng.cfg, RAFT_SEEDS, engine=raft_eng,
                 **SWEEP_KW)


def assert_contract_equal(a, b):
    """The crash-identical contract: ids, bug flags, observations
    (metrics frames included), coverage ledger."""
    np.testing.assert_array_equal(a.seeds, b.seeds)
    np.testing.assert_array_equal(a.bug, b.bug)
    assert set(a.observations) == set(b.observations)
    for k in a.observations:
        np.testing.assert_array_equal(a.observations[k], b.observations[k],
                                      err_msg=k)
    assert a.failing_seeds == b.failing_seeds
    assert (a.coverage is None) == (b.coverage is None)
    if a.coverage is not None:
        np.testing.assert_array_equal(a.coverage.hits, b.coverage.hits)
        np.testing.assert_array_equal(a.coverage.first_seen_seed,
                                      b.coverage.first_seen_seed)
        assert a.coverage.distinct_behaviors == b.coverage.distinct_behaviors


# ---------------------------------------------------------------------------
# Protocol units (no device work)
# ---------------------------------------------------------------------------

def test_split_ranges_tiles_and_is_deterministic():
    rs = split_ranges(100, 32)
    assert [r.range_id for r in rs] == [0, 1, 2, 3]
    assert rs[0].lo == 0 and rs[-1].hi == 100
    assert sum(r.n_seeds for r in rs) == 100
    assert split_ranges(100, 32) == rs  # pure function of the inputs
    with pytest.raises(ValueError):
        split_ranges(10, 0)


def test_lease_table_expiry_reissue_and_dedup():
    table = LeaseTable(split_ranges(8, 4), ttl=5)
    a = table.issue("w0", now=0)
    b = table.issue("w1", now=0)
    assert a.range.range_id == 0 and b.range.range_id == 1
    assert table.issue("w0", now=0) is None  # nothing pending
    # Heartbeat extends; a stale lease id is refused.
    assert table.heartbeat(a.lease_id, "w0", now=3)
    assert not table.heartbeat(999, "w0", now=3)
    assert not table.heartbeat(a.lease_id, "w1", now=3)  # wrong holder
    # w1 never heartbeats: its lease expires and the range re-queues.
    reaped = table.expire(now=6)
    assert [l.range.range_id for l in reaped] == [1]
    c = table.issue("w0", now=6)
    assert c.range.range_id == 1 and c.generation == 1
    # The ORIGINAL holder completes anyway: accepted (first), and the
    # re-issued holder's later completion resolves as a duplicate.
    first, _ = table.complete(1, b.lease_id)
    assert first
    dup, _ = table.complete(1, c.lease_id)
    assert not dup
    # Voluntary release re-queues immediately with the checkpoint.
    assert table.release(a.lease_id, "w0", checkpoint="/tmp/ck.npz")
    d = table.issue("w1", now=7)
    assert d.range.range_id == 0 and d.checkpoint == "/tmp/ck.npz"


def test_retry_backoff_is_deterministic_and_jittered():
    p = RetryPolicy(seed=3, base_delay=1.0, jitter=0.5)
    q = RetryPolicy(seed=3, base_delay=1.0, jitter=0.5)
    d = [p.delay("w0:acquire", a) for a in range(4)]
    assert d == [q.delay("w0:acquire", a) for a in range(4)]  # replayable
    assert d[1] > d[0] and d[2] > d[1]  # exponential growth survives jitter
    assert d != [p.delay("w1:acquire", a) for a in range(4)]  # desynced


def test_call_with_retry_exhaustion_and_success():
    from madsim_tpu.fleet import RetryExhausted, RpcError, call_with_retry

    clock = VirtualClock()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RpcError("boom")
        return "ok"

    assert call_with_retry(flaky, RetryPolicy(max_attempts=5), clock,
                           "t") == "ok"
    assert calls["n"] == 3
    assert clock.now() > 0  # backoff advanced the fabric clock
    with pytest.raises(RetryExhausted):
        call_with_retry(lambda: (_ for _ in ()).throw(RpcError("x")),
                        RetryPolicy(max_attempts=2), clock, "t2")


def _fake_result(seeds, bug_at=()):
    obs = {"bug": np.isin(np.arange(len(seeds)), bug_at),
           "steps": np.ones(len(seeds), np.int32)}
    from madsim_tpu.parallel.sweep import SweepResult

    return SweepResult(seeds=np.asarray(seeds, np.uint64), bug=obs["bug"],
                       observations=obs, steps_run=1, n_devices=1)


def test_duplicate_mismatch_raises_integrity_error():
    """A double-reported range whose two executions disagree bitwise is
    the one unrecoverable fleet fault: nondeterminism. It must raise,
    never silently pick a winner."""
    clock = VirtualClock()
    coord = Coordinator(np.arange(8), range_size=8, lease_ttl=10,
                        clock=clock)
    lease = coord.rpc_acquire(worker_id="w0")
    ok = _fake_result(np.arange(8))
    coord.rpc_complete(worker_id="w0", lease_id=lease["lease_id"],
                       range_id=0, result=ok)
    # Identical duplicate: crosschecked and absorbed.
    out = coord.rpc_complete(worker_id="w1", lease_id=lease["lease_id"],
                             range_id=0, result=_fake_result(np.arange(8)))
    assert out["duplicate"]
    assert coord.stats["duplicates_crosschecked"] == 1
    with pytest.raises(FleetIntegrityError, match="bitwise"):
        coord.rpc_complete(worker_id="w1", lease_id=lease["lease_id"],
                           range_id=0,
                           result=_fake_result(np.arange(8), bug_at=(3,)))


def test_merge_requires_tiling_ranges():
    from madsim_tpu.fleet import merge_range_results

    with pytest.raises(ValueError, match="not completed"):
        merge_range_results(np.arange(8), [SeedRange(0, 0, 8)], {}, 1)


# ---------------------------------------------------------------------------
# The chaos matrix (the tier-1 acceptance contract)
# ---------------------------------------------------------------------------

def test_chaos_matrix_raft(raft_eng, raft_single, tmp_path):
    """Raft (with coverage + metrics): single-host == clean fleet ==
    chaotic fleet, with every failure mode injected at once — and the
    chaos demonstrably happened (kills, expiries, duplicates, retries,
    preemption all nonzero)."""
    single = raft_single
    clean = fleet_sweep(None, raft_eng.cfg, RAFT_SEEDS, engine=raft_eng,
                        n_workers=2, range_size=16, **SWEEP_KW)
    chaotic = fleet_sweep(None, raft_eng.cfg, RAFT_SEEDS, engine=raft_eng,
                          n_workers=2, range_size=16, chaos=CHAOS,
                          checkpoint_dir=str(tmp_path / "ck"),
                          **SWEEP_KW)
    assert_contract_equal(single, clean)
    assert_contract_equal(single, chaotic)
    assert single.failing_seeds, "matrix must exercise failing seeds"
    fleet_stats = chaotic.loop_stats["fleet"]
    assert fleet_stats["kills"] >= 1
    assert fleet_stats["preemptions"] >= 1
    assert fleet_stats["leases_expired"] >= 1
    assert fleet_stats["leases_reissued"] >= 1
    assert fleet_stats["duplicate_completions"] >= 1
    assert fleet_stats["duplicates_crosschecked"] == \
        fleet_stats["duplicate_completions"]
    assert fleet_stats["rpc_retries"] >= 1


def test_chaos_matrix_pb():
    eng = DeviceEngine(
        PBActor(PBDeviceConfig(n=3, n_writes=4)),
        EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                     t_limit_us=1_500_000, loss_rate=0.05))
    seeds = np.arange(32)
    single = sweep(None, eng.cfg, seeds, engine=eng, **SWEEP_KW)
    clean = fleet_sweep(None, eng.cfg, seeds, engine=eng, n_workers=2,
                        range_size=8, **SWEEP_KW)
    chaotic = fleet_sweep(None, eng.cfg, seeds, engine=eng, n_workers=2,
                          range_size=8, chaos=CHAOS, **SWEEP_KW)
    assert_contract_equal(single, clean)
    assert_contract_equal(single, chaotic)
    assert chaotic.loop_stats["fleet"]["kills"] >= 1


def test_chaos_matrix_tpc():
    eng = DeviceEngine(
        TPCActor(TPCDeviceConfig(n=4, n_txns=4, buggy_presumed_commit=True)),
        EngineConfig(n_nodes=4, outbox_cap=5, queue_cap=64,
                     t_limit_us=1_500_000, loss_rate=0.1))
    seeds = np.arange(32)
    single = sweep(None, eng.cfg, seeds, engine=eng, **SWEEP_KW)
    clean = fleet_sweep(None, eng.cfg, seeds, engine=eng, n_workers=2,
                        range_size=8, **SWEEP_KW)
    chaotic = fleet_sweep(None, eng.cfg, seeds, engine=eng, n_workers=2,
                          range_size=8, chaos=CHAOS, **SWEEP_KW)
    assert_contract_equal(single, clean)
    assert_contract_equal(single, chaotic)
    assert single.failing_seeds  # buggy config: bug attribution survives


def test_fleet_composes_with_multihost_mesh(raft_eng, raft_single):
    """The DCN×ICI leg: every worker sweeps its leases on the 2-D
    multihost mesh (psum over dcn+worlds inside each lease) and the
    merged result still equals the single-host reference."""
    from madsim_tpu.parallel.mesh import multihost_mesh

    single = raft_single
    mesh2d = multihost_mesh(n_hosts=2)
    assert mesh2d.devices.shape == (2, 4)
    fleet = fleet_sweep(None, raft_eng.cfg, RAFT_SEEDS, engine=raft_eng,
                        mesh=mesh2d, n_workers=2, range_size=16,
                        chaos=ChaosConfig(seed=5, kill_at=(("w1", 3),),
                                          restart_after=1),
                        **SWEEP_KW)
    assert_contract_equal(single, fleet)
    assert fleet.loop_stats["fleet"]["kills"] == 1


# ---------------------------------------------------------------------------
# Preemption + checkpoint recovery
# ---------------------------------------------------------------------------

def test_preemption_releases_lease_and_resumes_checkpoint(raft_eng,
                                                          raft_single,
                                                          tmp_path):
    """SIGTERM path: the preempted worker's lease re-queues immediately
    with its checkpoint attached; the next holder RESUMES (bit-exactly)
    instead of replaying, and the result is still contract-identical."""
    single = raft_single
    recs = []
    fleet = fleet_sweep(
        None, raft_eng.cfg, RAFT_SEEDS, engine=raft_eng, n_workers=2,
        range_size=32, observe=recs.append,
        checkpoint_dir=str(tmp_path / "ck"), checkpoint_every_chunks=1,
        chaos=ChaosConfig(seed=2, preempt_at=(("w0", 2),),
                          restart_after=2),
        **SWEEP_KW)
    assert_contract_equal(single, fleet)
    stats = fleet.loop_stats["fleet"]
    assert stats["preemptions"] >= 1
    assert stats["checkpoints_recovered"] >= 1
    events = [r["event"] for r in recs]
    assert "worker_preempted" in events
    assert "lease_released" in events
    assert "lease_resumed" in events
    rel = next(r for r in recs if r["event"] == "worker_preempted")
    assert rel["checkpoint"], "preemption must release WITH a checkpoint"


def test_torn_checkpoint_recovers_by_rerun(raft_eng, raft_single,
                                           tmp_path):
    """Crash-corrupted checkpoint: the killed worker's file is torn; the
    next holder's resume hits the hardened loader's CheckpointError,
    discards the file, re-runs fresh — same bitwise result."""
    single = raft_single
    recs = []
    fleet = fleet_sweep(
        None, raft_eng.cfg, RAFT_SEEDS, engine=raft_eng, n_workers=2,
        range_size=32, observe=recs.append,
        checkpoint_dir=str(tmp_path / "ck"), checkpoint_every_chunks=1,
        chaos=ChaosConfig(seed=4, kill_at=(("w0", 3),),
                          tear_checkpoint_on_kill=True, restart_after=2),
        **SWEEP_KW)
    assert_contract_equal(single, fleet)
    stats = fleet.loop_stats["fleet"]
    assert stats["kills"] >= 1
    assert stats["checkpoints_discarded"] >= 1
    events = [r["event"] for r in recs]
    assert "checkpoint_torn" in events
    assert "checkpoint_corrupt" in events


# ---------------------------------------------------------------------------
# Telemetry stream
# ---------------------------------------------------------------------------

def test_fleet_telemetry_jsonl_and_watch(raft_eng, tmp_path):
    """The observatory stream gains per-worker lease/retry/re-lease
    records: JSONL sink, schema'd records, and `obs watch` renders a
    fleet summary."""
    import io

    from madsim_tpu.obs.observatory import watch

    seeds = np.arange(32)
    path = str(tmp_path / "fleet.jsonl")
    fleet_sweep(None, raft_eng.cfg, seeds, engine=raft_eng, n_workers=2,
                range_size=8, observe=path,
                chaos=ChaosConfig(seed=9, kill_at=(("w1", 2),),
                                  drop_rpc_rate=0.3, restart_after=1),
                **SWEEP_KW)
    with open(path) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    assert recs, "stream must not be empty"
    assert all(r["schema"] == "madsim.fleet.telemetry/1" for r in recs)
    events = {r["event"] for r in recs}
    assert {"lease_issued", "heartbeat", "completion",
            "fleet_summary"} <= events
    assert "worker_killed" in events and "lease_expired" in events
    assert "rpc_retry" in events
    # Re-issued lease records carry the generation + reissued flag.
    reissues = [r for r in recs
                if r["event"] == "lease_issued" and r.get("reissued")]
    assert reissues and all(r["generation"] >= 1 for r in reissues)
    out = io.StringIO()
    assert watch(path, out=out) == 0
    text = out.getvalue()
    assert "fleet:" in text and "crosschecked" in text


def test_fleet_stalls_loudly_when_unrecoverable(raft_eng):
    """All workers dead + restarts disabled must raise FleetStalledError
    — and its message must name each stuck range with its holding
    worker, lease generation, and last-heartbeat bookkeeping (the PR 12
    satellite: diagnostics, not a bare range count). Under the default
    lease prefetch BOTH of the dead worker's leases are outstanding —
    the report must name the running one AND the prefetched one, with
    the prefetched lease annotated as queued behind the running lease
    (a prefetched lease must not read as a hung sweep)."""
    from madsim_tpu.fleet import FleetStalledError

    with pytest.raises(FleetStalledError, match="dead") as exc:
        fleet_sweep(None, raft_eng.cfg, np.arange(16), engine=raft_eng,
                    n_workers=1, range_size=8,
                    chaos=ChaosConfig(seed=1, kill_at=(("w0", 1),),
                                      restart_after=-1),
                    **SWEEP_KW)
    msg = str(exc.value)
    assert "range 0: held by w0" in msg
    assert "last heartbeat" in msg and "heartbeats" in msg
    assert "expires t=" in msg
    # The prefetched lease: held by the same worker, explicitly marked.
    assert "range 1: held by w0" in msg
    assert "prefetched behind lease 0" in msg


def test_fleet_stall_report_without_prefetch(raft_eng):
    """prefetch=0 restores the one-lease-per-quantum fabric: a stalled
    single-worker fleet holds only its running range; the other range
    is reported pending for re-issue."""
    from madsim_tpu.fleet import FleetStalledError

    with pytest.raises(FleetStalledError, match="dead") as exc:
        fleet_sweep(None, raft_eng.cfg, np.arange(16), engine=raft_eng,
                    n_workers=1, range_size=8, prefetch=0,
                    chaos=ChaosConfig(seed=1, kill_at=(("w0", 1),),
                                      restart_after=-1),
                    **SWEEP_KW)
    msg = str(exc.value)
    assert "range 0: held by w0" in msg
    assert "prefetched" not in msg
    assert "range 1: pending" in msg


# ---------------------------------------------------------------------------
# Fabric cost disciplines (ISSUE 17): persistent sessions, prefetch,
# coalesced control plane — counted, not vibes
# ---------------------------------------------------------------------------

def test_session_run_group_bitwise_equals_solo_sweeps(raft_eng):
    """The tentpole's correctness gate: every per-range result a
    SweepSession.run_group emits is bitwise interchangeable (contract
    fields) with a fresh solo ``sweep()`` of that range — including the
    SECOND group, which rides the session's recycled standing slots
    (``refill`` path) instead of a fresh device init."""
    from madsim_tpu.fleet.merge import contract_mismatches
    from madsim_tpu.parallel import SweepSession

    sess = SweepSession(engine=raft_eng, mesh=None, **SWEEP_KW)
    groups = [np.arange(48, dtype=np.uint64),
              np.arange(100, 148, dtype=np.uint64)]
    for gi, seeds in enumerate(groups):
        parts = [{"seeds": seeds[lo:lo + 16], "faults": None}
                 for lo in range(0, 48, 16)]
        results = sess.run_group(parts)
        assert len(results) == 3
        for part, res in zip(parts, results):
            solo = sweep(None, raft_eng.cfg, part["seeds"],
                         engine=raft_eng, **SWEEP_KW)
            assert contract_mismatches(solo, res) == []
            assert res.loop_stats["session_group"] == 3
            assert res.loop_stats["session_reused_slots"] == (gi > 0)
    # 6 leases rode the session; only the very first paid an install.
    assert sess.reuse_hits == 5


def test_session_grouped_adds_no_device_fetches(raft_eng, monkeypatch):
    """Counted discipline: a grouped session quantum performs NO more
    host pulls through the sanctioned ``_fetch`` hook than the same
    ranges swept solo (the grouped pipelined loop still pays ONE scalar
    fetch per superstep and one ledger pull — for the whole group
    instead of per range)."""
    import importlib

    from madsim_tpu.parallel import SweepSession

    # The package re-exports the sweep FUNCTION under the module's
    # name, so fetch the module object explicitly.
    sweep_mod = importlib.import_module("madsim_tpu.parallel.sweep")

    seeds = np.arange(200, 248, dtype=np.uint64)
    counter = {"n": 0}
    real_fetch = sweep_mod._fetch

    def counting_fetch(x):
        counter["n"] += 1
        return real_fetch(x)

    monkeypatch.setattr(sweep_mod, "_fetch", counting_fetch)
    solo_fetches = 0
    for lo in range(0, 48, 16):
        counter["n"] = 0
        sweep_mod.sweep(None, raft_eng.cfg, seeds[lo:lo + 16],
                        engine=raft_eng, **SWEEP_KW)
        solo_fetches += counter["n"]
    sess = SweepSession(engine=raft_eng, mesh=None, **SWEEP_KW)
    counter["n"] = 0
    sess.run_group([{"seeds": seeds[lo:lo + 16], "faults": None}
                    for lo in range(0, 48, 16)])
    grouped_fetches = counter["n"]
    assert grouped_fetches <= solo_fetches, \
        (f"grouped quantum pulled {grouped_fetches} times vs "
         f"{solo_fetches} solo — the session must not add device syncs")


def test_fleet_control_rpcs_bounded_per_lease(raft_eng, raft_single):
    """The coalesced control plane's gate, measured: a clean fleet's
    non-heartbeat transport turns per issued lease stay within the
    named constant (fleet.MAX_CONTROL_RPCS_PER_LEASE) — one acquire
    turn covers a worker's whole prefetched quantum and one batched
    turn reports it."""
    from madsim_tpu.fleet import MAX_CONTROL_RPCS_PER_LEASE

    fleet = fleet_sweep(None, raft_eng.cfg, RAFT_SEEDS, engine=raft_eng,
                        n_workers=2, range_size=16, **SWEEP_KW)
    assert_contract_equal(raft_single, fleet)
    stats = fleet.loop_stats["fleet"]
    assert stats["leases_prefetched"] >= 1
    assert stats["grouped_leases"] >= 2
    assert stats["session_reuse_hits"] >= 1
    assert stats["control_rpcs_per_lease"] <= MAX_CONTROL_RPCS_PER_LEASE
    turns = stats["rpc_turns"]
    # 4 ranges over 2 workers: one acquire turn per worker quantum plus
    # at most a few idle polls; completions ride batched turns.
    assert turns["acquire"] <= 2 * MAX_CONTROL_RPCS_PER_LEASE
    assert turns.get("batch", 0) >= 2
    assert turns.get("complete", 0) == 0  # completions only ride batches
    assert stats["acquire_s"] >= 0.0 and stats["sweep_s"] > 0.0
    assert "merge_s" in stats


# ---------------------------------------------------------------------------
# Multiprocess leg (real processes + signals) — excluded from tier-1
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_process_fleet_smoke(tmp_path):
    """Two real worker processes over pipes, one SIGKILLed mid-lease:
    the merged result still equals the single-host reference (recovery
    via lease TTL + respawn). Marked slow: each spawned worker pays a
    fresh JAX import + compile."""
    eng = DeviceEngine(RaftActor(RCFG), ECFG)
    seeds = np.arange(24)
    single = sweep(None, ECFG, seeds, engine=eng, **SWEEP_KW)
    fleet = fleet_sweep(RaftActor(RCFG), ECFG, seeds, n_workers=2,
                        range_size=8, spawn="process", lease_ttl=5.0,
                        checkpoint_dir=str(tmp_path / "ck"),
                        kill_after_heartbeats={"w0": 1},
                        serve_timeout_s=300.0, **SWEEP_KW)
    np.testing.assert_array_equal(single.bug, fleet.bug)
    for k in single.observations:
        np.testing.assert_array_equal(single.observations[k],
                                      fleet.observations[k], err_msg=k)


@pytest.mark.parametrize("held,n_workers,match", [
    (True, 1, "holds the chip"),
    (False, 2, "n_workers=2 on a TPU host"),
])
def test_process_fleet_refuses_a_second_process_on_the_chip(
        monkeypatch, held, n_workers, match):
    """On a TPU host (steered here: the suite runs on the CPU) a worker
    that cannot get the chip is refused before anything spawns, instead
    of failing or hanging in the worker's backend start."""
    from madsim_tpu.fleet import process as fp

    monkeypatch.setattr(fp, "_spawned_backend", lambda: ("tpu", held))
    with pytest.raises(RuntimeError, match=match):
        fp.process_fleet_sweep(RaftActor(RCFG), ECFG, np.arange(8),
                               n_workers=n_workers, range_size=4,
                               **SWEEP_KW)


def test_spawned_backend_reads_this_process_without_a_probe():
    """This process has initialized JAX on the CPU: the check answers
    from it (no probe process) and lets CPU fleets through."""
    import jax

    from madsim_tpu.fleet import process as fp

    jax.devices()
    assert fp._spawned_backend() == ("cpu", True)
    fp._check_one_process_per_chip(4)
