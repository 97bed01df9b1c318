"""Pipelined sweep orchestration (docs/perf.md "Pipelined orchestration").

The contract under test: the dispatch-ahead, superstepped loop
(``sweep(pipeline=True)``, the default) returns results — per-seed
observations, failing-seed attribution, per-chunk occupancy history —
bitwise identical to the serial per-chunk reference loop
(``pipeline=False``), for every actor family and every loop mode
(plain / recycled / compacted / stop_on_first_bug / max_steps /
checkpointed), while crossing the host boundary only with the intended
occupancy/bug scalars per superstep and cutting host dispatches by the
superstep fan-in.
"""
import importlib

import numpy as np
import pytest

# The package re-exports the sweep FUNCTION as an attribute named like
# the submodule; resolve the module itself for the _fetch hook.
sweep_mod = importlib.import_module("madsim_tpu.parallel.sweep")
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
from madsim_tpu.parallel.sweep import sweep


@pytest.fixture(scope="module")
def raft_eng():
    # The flagship family with an injected bug: occupancy actually drops
    # across chunks (stop_on_bug freezes buggy worlds), exercising the
    # recycle/compact thresholds.
    rcfg = RaftDeviceConfig(n=3, buggy_double_vote=True)
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                      t_limit_us=1_500_000, stop_on_bug=True)
    return DeviceEngine(RaftActor(rcfg), cfg)


@pytest.fixture(scope="module")
def pb_eng():
    return DeviceEngine(
        PBActor(PBDeviceConfig(n=3, n_writes=4)),
        EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                     t_limit_us=1_500_000, loss_rate=0.05))


@pytest.fixture(scope="module")
def tpc_eng():
    return DeviceEngine(
        TPCActor(TPCDeviceConfig(n=4, n_txns=4, buggy_presumed_commit=True)),
        EngineConfig(n_nodes=4, outbox_cap=5, queue_cap=64,
                     t_limit_us=1_500_000, loss_rate=0.1))


def both_loops(eng, seeds, **kw):
    ser = sweep(None, eng.cfg, seeds, engine=eng, pipeline=False, **kw)
    pip = sweep(None, eng.cfg, seeds, engine=eng, pipeline=True, **kw)
    return ser, pip


def assert_bitwise_equal(ser, pip):
    assert ser.steps_run == pip.steps_run
    np.testing.assert_array_equal(ser.n_active_history, pip.n_active_history)
    np.testing.assert_array_equal(ser.n_active_chunks, pip.n_active_chunks)
    for k in ser.observations:
        np.testing.assert_array_equal(ser.observations[k],
                                      pip.observations[k], err_msg=k)
    assert ser.failing_seeds == pip.failing_seeds
    # Same executed chunks, same utilization accounting.
    assert ser.loop_stats["chunks"] == pip.loop_stats["chunks"]
    assert ser.world_utilization == pip.world_utilization


def test_pipelined_matches_serial_raft_all_modes(raft_eng):
    """Every loop mode of the flagship family: the dispatch-ahead
    superstep loop is bitwise the serial loop, including the early exits
    (stop_on_first_bug / max_steps) where the in-flight superstep must be
    a pass-through no-op."""
    seeds = np.arange(200)  # not a mesh multiple: stream tail exercised
    for kw in (dict(chunk_steps=64, max_steps=10_000),
               dict(chunk_steps=64, max_steps=10_000,
                    recycle=True, batch_worlds=48),
               dict(chunk_steps=64, max_steps=10_000, compact=True),
               dict(chunk_steps=64, max_steps=10_000,
                    stop_on_first_bug=True),
               dict(chunk_steps=64, max_steps=128),
               dict(chunk_steps=64, max_steps=10_000,
                    stop_on_first_bug=True, recycle=True, batch_worlds=16)):
        ser, pip = both_loops(raft_eng, seeds, **kw)
        assert_bitwise_equal(ser, pip)
    assert pip.loop_stats["pipelined"] and not ser.loop_stats["pipelined"]


def test_pipelined_matches_serial_pb(pb_eng):
    seeds = np.arange(96)
    ser, pip = both_loops(pb_eng, seeds, chunk_steps=64, max_steps=10_000)
    assert_bitwise_equal(ser, pip)
    ser, pip = both_loops(pb_eng, seeds, chunk_steps=64, max_steps=10_000,
                          recycle=True, batch_worlds=32)
    assert_bitwise_equal(ser, pip)


def test_pipelined_matches_serial_tpc(tpc_eng):
    seeds = np.arange(96)
    ser, pip = both_loops(tpc_eng, seeds, chunk_steps=64, max_steps=10_000)
    assert_bitwise_equal(ser, pip)
    ser, pip = both_loops(tpc_eng, seeds, chunk_steps=64, max_steps=10_000,
                          recycle=True, batch_worlds=32)
    assert_bitwise_equal(ser, pip)


def test_pipelined_checkpoint_interplay(raft_eng, tmp_path):
    """Checkpointing + pipelining: donation stays disabled while the
    async writer may read a submitted state (a donated buffer would be
    invalidated mid-read — this test crashing or corrupting would catch
    it), the snapshot cadence still lands durable states, and a resumed
    pipelined sweep continues bit-exactly."""
    seeds = np.arange(40)
    kw = dict(chunk_steps=128, max_steps=4_000)
    full_ser = sweep(None, raft_eng.cfg, seeds, engine=raft_eng,
                     pipeline=False, **kw)
    path = str(tmp_path / "pipe.npz")
    full_pip = sweep(None, raft_eng.cfg, seeds, engine=raft_eng,
                     pipeline=True, checkpoint_path=path,
                     checkpoint_every_chunks=1, **kw)
    for k in full_ser.observations:
        np.testing.assert_array_equal(full_ser.observations[k],
                                      full_pip.observations[k], err_msg=k)
    # Interrupted pipelined sweep (2 chunks), then a pipelined resume:
    # the merged trajectory equals the unbroken run's, bit for bit.
    path2 = str(tmp_path / "resume.npz")
    sweep(None, raft_eng.cfg, seeds, engine=raft_eng, chunk_steps=128,
          max_steps=256, checkpoint_path=path2, checkpoint_every_chunks=1)
    resumed = sweep(None, raft_eng.cfg, seeds, engine=raft_eng,
                    chunk_steps=128, max_steps=4_000, checkpoint_path=path2,
                    resume=True)
    for k in full_ser.observations:
        np.testing.assert_array_equal(full_ser.observations[k],
                                      resumed.observations[k], err_msg=k)


def test_n_active_chunk_index_contract(raft_eng):
    """``n_active_chunks`` records the executed-chunk index each history
    entry was measured at: entrywise aligned, strictly increasing, and
    identical between the serial, pipelined, AND fused loops (the
    measurement sequence is per-chunk in all three — pipelining only
    delays when the host READS it, and the fused loop records the chunk
    index inside the device program, so a mega-dispatch of K chunks
    lands K correctly-indexed entries, not one skewed batch)."""
    seeds = np.arange(200)
    kw = dict(chunk_steps=64, max_steps=10_000, recycle=True,
              batch_worlds=48)
    ser, pip = both_loops(raft_eng, seeds, **kw)
    fus = sweep(None, raft_eng.cfg, seeds, engine=raft_eng, fused=True,
                **kw)
    for res in (ser, pip, fus):
        assert res.n_active_chunks.shape == res.n_active_history.shape
        assert (np.diff(res.n_active_chunks) > 0).all()
        assert res.n_active_chunks[0] == 0
        assert res.n_active_chunks[-1] == res.loop_stats["chunks"] - 1
    np.testing.assert_array_equal(ser.n_active_chunks, pip.n_active_chunks)
    np.testing.assert_array_equal(ser.n_active_chunks, fus.n_active_chunks)
    np.testing.assert_array_equal(ser.n_active_history,
                                  fus.n_active_history)


def test_sync_discipline_counted_fetches(raft_eng, monkeypatch):
    """Tier-1 sync discipline: in the steady-state superstep loop, the
    ONLY device→host pulls are the per-superstep occupancy/bug scalar
    batches (a few hundred bytes), plus one bucketed frozen-tail slice
    per retirement event and the single final merge — never a full
    per-world observation pull mid-loop. Counted via the sweep module's
    ``_fetch`` hook, through which every loop-side pull is routed."""
    calls = []
    real_fetch = sweep_mod._fetch

    def counting_fetch(tree):
        out = real_fetch(tree)
        import jax
        nbytes = sum(np.asarray(x).nbytes for x in jax.tree.leaves(out))
        calls.append(nbytes)
        return out

    monkeypatch.setattr(sweep_mod, "_fetch", counting_fetch)
    seeds = np.arange(96)

    # Plain sweep: no retirement events at all. Pulls = one scalar batch
    # per superstep dispatch + the final slot-index fetch for the merge.
    res = sweep(None, raft_eng.cfg, seeds, engine=raft_eng, chunk_steps=64,
                max_steps=10_000)
    st = res.loop_stats
    # One scalar batch per superstep READ; the one dispatched-ahead
    # superstep still in flight at the stop is never read at all.
    assert st["scalar_fetches"] <= st["dispatches"] \
        <= st["scalar_fetches"] + 1
    assert st["retire_fetches"] == 0
    assert len(calls) == st["scalar_fetches"] + 1  # + final idx fetch
    # Each steady-state pull is scalars + the K-wide history lane — a few
    # hundred bytes, never a per-world array of the 96-world batch.
    scalar_bytes = calls[:-1]
    assert max(scalar_bytes) <= 256, scalar_bytes

    # Recycled sweep: each refill/shrink adds exactly one (bucketed)
    # frozen-tail retirement pull; the steady-state pulls stay scalar.
    calls.clear()
    res = sweep(None, raft_eng.cfg, seeds, engine=raft_eng, chunk_steps=64,
                max_steps=10_000, recycle=True, batch_worlds=32)
    st = res.loop_stats
    assert st["retire_fetches"] >= 1
    assert st["scalar_fetches"] <= st["dispatches"] \
        <= st["scalar_fetches"] + 1
    assert len(calls) == st["scalar_fetches"] + st["retire_fetches"] + 1


def test_superstep_dispatch_reduction():
    """The tentpole's dispatch economics: on a long trajectory the
    adaptive superstep folds >= 4 chunks into one host dispatch (slow
    start doubles K up to superstep_max while supersteps run to plan)."""
    clean = DeviceEngine(
        RaftActor(RaftDeviceConfig(n=3, n_proposals=1)),
        EngineConfig(n_nodes=3, outbox_cap=4, t_limit_us=3_000_000))
    seeds = np.arange(48)
    # Fine chunks (8 steps) — exactly the granularity supersteps make
    # affordable, since the host no longer syncs per chunk.
    ser = sweep(None, clean.cfg, seeds, engine=clean, chunk_steps=8,
                max_steps=100_000, pipeline=False)
    pip = sweep(None, clean.cfg, seeds, engine=clean, chunk_steps=8,
                max_steps=100_000, pipeline=True)
    assert_bitwise_equal(ser, pip)
    # Serial pays one dispatch per chunk; the superstep loop must fold
    # the same chunks into <= 1/4 the dispatches.
    assert ser.loop_stats["dispatches"] == ser.loop_stats["chunks"]
    assert pip.loop_stats["chunks"] >= 32  # the workload really is long
    assert pip.loop_stats["dispatches"] * 4 <= pip.loop_stats["chunks"], \
        pip.loop_stats
    assert pip.loop_stats["chunks_per_dispatch"] >= 4
    # Dispatch-ahead really ran (one superstep in flight past the read).
    assert pip.loop_stats["dispatch_depth"] == 1


def test_binding_max_steps_respects_chunk_budget():
    """Review regression: the dispatch-ahead budget must reserve the
    planned chunks of the superstep already in the device queue but not
    yet read. With non-retiring worlds (the clean raft family stays at
    full occupancy for its first 6 chunks of 64 steps) and a binding
    ``max_steps`` in the c_max 5-8 window — where the adaptive K ramp
    (1, 1, 2, 4, ...) would otherwise overshoot — the pipelined loop
    must execute EXACTLY the serial loop's chunk budget, bitwise."""
    clean = DeviceEngine(
        RaftActor(RaftDeviceConfig(n=3, n_proposals=1)),
        EngineConfig(n_nodes=3, outbox_cap=4, t_limit_us=3_000_000))
    seeds = np.arange(24)
    for c_max in (5, 6, 7, 8):
        ser, pip = both_loops(clean, seeds, chunk_steps=64,
                              max_steps=64 * c_max)
        assert_bitwise_equal(ser, pip)
        assert pip.loop_stats["chunks"] <= c_max
        assert pip.steps_run <= 64 * c_max
    # In the fully non-retiring window the budget truly binds: the loop
    # runs the whole budget, never a chunk more.
    ser, pip = both_loops(clean, seeds, chunk_steps=64, max_steps=64 * 5)
    assert (pip.n_active_history == 24).all()  # nobody retired
    assert pip.loop_stats["chunks"] == 5 and pip.steps_run == 320


def test_zero_step_budget_runs_no_chunks():
    """Review regression: ``max_steps <= 0`` means a zero-chunk budget.
    The serial loop never enters its body; the pipelined loop must not
    force a min_one first chunk either."""
    clean = DeviceEngine(
        RaftActor(RaftDeviceConfig(n=3, n_proposals=1)),
        EngineConfig(n_nodes=3, outbox_cap=4, t_limit_us=3_000_000))
    seeds = np.arange(8)
    ser, pip = both_loops(clean, seeds, chunk_steps=64, max_steps=0)
    assert_bitwise_equal(ser, pip)
    assert ser.steps_run == pip.steps_run == 0
    assert ser.loop_stats["chunks"] == pip.loop_stats["chunks"] == 0
    assert pip.loop_stats["dispatches"] == 0
    assert pip.n_active_history.size == 0


@pytest.mark.parametrize("pipeline", [True, False])
def test_loop_stats_schema_both_paths(raft_eng, pipeline):
    """The documented ``loop_stats`` schema (docs/perf.md "Telemetry",
    docs/observability.md) holds on BOTH orchestration paths, with sane
    types and values — not just key presence on the default path."""
    res = sweep(None, raft_eng.cfg, np.arange(48), engine=raft_eng,
                chunk_steps=64, max_steps=2_048, pipeline=pipeline)
    ls = res.loop_stats
    documented = {"device_wait_s", "host_decision_s", "scalar_fetches",
                  "retire_fetches", "dispatch_depth", "dispatches_per_seed",
                  "seeds_per_dispatch", "epochs_on_device", "fused",
                  "pipelined", "superstep_max", "chunk_steps", "chunks",
                  "dispatches", "chunks_per_dispatch", "dispatch_s",
                  "retire_wait_s", "loop_wall_s", "slot_steps_skipped",
                  "prepare_s", "init_s", "upload_s", "assemble_s",
                  "identity_s", "identity_hashes", "fused_setup_cache_hits"}
    assert documented <= set(ls), sorted(ls)
    assert ls["pipelined"] is pipeline
    assert ls["fused"] is False
    assert ls["epochs_on_device"] == 0   # host loops never refill on device
    assert ls["seeds_per_dispatch"] == pytest.approx(
        48 / ls["dispatches"], abs=1e-3)
    for key in ("device_wait_s", "host_decision_s", "dispatch_s",
                "retire_wait_s", "loop_wall_s", "prepare_s", "init_s",
                "upload_s", "assemble_s", "identity_s"):
        assert isinstance(ls[key], float) and ls[key] >= 0.0, key
    for key in ("scalar_fetches", "retire_fetches", "dispatch_depth",
                "chunks", "dispatches", "superstep_max", "chunk_steps",
                "slot_steps_skipped", "identity_hashes",
                "fused_setup_cache_hits"):
        assert isinstance(ls[key], int) and ls[key] >= 0, key
    assert ls["chunks"] >= 1 and ls["dispatches"] >= 1
    assert ls["scalar_fetches"] >= 1
    assert ls["retire_fetches"] == 0       # plain sweep: nothing retires
    assert ls["chunk_steps"] == 64
    assert ls["superstep_max"] == (16 if pipeline else 1)
    assert ls["dispatches_per_seed"] == pytest.approx(
        ls["dispatches"] / 48, abs=1e-6)
    # Dispatch-ahead runs exactly one superstep deep; the serial loop
    # never dispatches ahead at all.
    assert ls["dispatch_depth"] == (1 if pipeline else 0)
    assert ls["loop_wall_s"] >= ls["host_decision_s"]


def test_superstep_telemetry_fields(raft_eng):
    """SweepResult.loop_stats carries the documented telemetry fields
    (docs/perf.md "Pipelined orchestration")."""
    res = sweep(None, raft_eng.cfg, np.arange(48), engine=raft_eng,
                chunk_steps=64, max_steps=512)
    need = {"pipelined", "fused", "chunks", "dispatches",
            "chunks_per_dispatch", "dispatches_per_seed",
            "seeds_per_dispatch", "epochs_on_device", "dispatch_depth",
            "device_wait_s", "host_decision_s", "dispatch_s",
            "retire_wait_s", "scalar_fetches", "retire_fetches",
            "loop_wall_s", "superstep_max", "chunk_steps"}
    assert need <= set(res.loop_stats), res.loop_stats
    assert res.loop_stats["device_wait_s"] >= 0.0
    assert res.loop_stats["dispatches_per_seed"] == pytest.approx(
        res.loop_stats["dispatches"] / 48, abs=1e-6)
