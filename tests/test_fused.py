"""Whole-hunt device residency (docs/perf.md "Whole-hunt residency").

The contract under test: ``sweep(fused=True)`` runs the ENTIRE
occupancy loop — compaction, retiring-tail harvest, coverage fold,
guided generation, refill, and the seed cursor — inside one device
program, and returns results bitwise identical to the serial and
pipelined host-orchestrated loops for every actor family and loop mode,
while the host issues O(1) mega-dispatches per batch: scalar ``_fetch``
batches mid-hunt, and ONE retired-observation pull at the end.

The only sanctioned divergence is ``world_utilization``: the fused tail
skips the dry-cursor shrink (every contract surface is
shrink-invariant), so a recycled hunt's tail runs at full width and the
issued-slot-steps denominator can differ. Everything else — ids,
observations, ``m_*`` metrics, occupancy history, the coverage ledger,
lineage lanes, the SearchReport — must match bit for bit.
"""
import importlib

import numpy as np
import pytest

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
    # Flagship family, metrics ON: the fused program carries the
    # coverage ledger fold in-loop, so the bitwise gate covers it too.
    rcfg = RaftDeviceConfig(n=3, buggy_double_vote=True)
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                       t_limit_us=1_500_000, stop_on_bug=True,
                       metrics=True)
    return DeviceEngine(RaftActor(rcfg), cfg)


@pytest.fixture(scope="module")
def pb_eng():
    # Metrics off: the coverage-free fused program variant.
    return DeviceEngine(
        PBActor(PBDeviceConfig(n=3, n_writes=4)),
        EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                     t_limit_us=1_500_000, loss_rate=0.05))


@pytest.fixture(scope="module")
def tpc_eng():
    return DeviceEngine(
        TPCActor(TPCDeviceConfig(n=4, n_txns=4,
                                 buggy_presumed_commit=True)),
        EngineConfig(n_nodes=4, outbox_cap=5, queue_cap=64,
                     t_limit_us=1_500_000, loss_rate=0.1))


@pytest.fixture(scope="module")
def paxos_eng():
    # The actorc DSL-only family: the fused chunk body is the compiled
    # spec's step, exercised through the same engine seam.
    from madsim_tpu.actorc.families.paxos import (PaxosActor, PaxosConfig,
                                                  engine_config)

    acfg = PaxosConfig()
    return DeviceEngine(PaxosActor(acfg), engine_config(acfg))


def all_loops(eng, seeds, **kw):
    ser = sweep(None, eng.cfg, seeds, engine=eng, pipeline=False, **kw)
    pip = sweep(None, eng.cfg, seeds, engine=eng, pipeline=True, **kw)
    fus = sweep(None, eng.cfg, seeds, engine=eng, fused=True, **kw)
    return ser, pip, fus


def assert_fused_bitwise(ref, fus):
    """Every contract surface bitwise; utilization deliberately NOT
    asserted (the fused tail runs at full width — module docstring)."""
    assert ref.steps_run == fus.steps_run
    np.testing.assert_array_equal(ref.n_active_history,
                                  fus.n_active_history)
    np.testing.assert_array_equal(ref.n_active_chunks,
                                  fus.n_active_chunks)
    for k in ref.observations:
        np.testing.assert_array_equal(ref.observations[k],
                                      fus.observations[k], err_msg=k)
    assert ref.failing_seeds == fus.failing_seeds
    assert ref.loop_stats["chunks"] == fus.loop_stats["chunks"]
    if ref.coverage is not None:
        np.testing.assert_array_equal(ref.coverage.hits,
                                      fus.coverage.hits)
        np.testing.assert_array_equal(ref.coverage.first_seen_seed,
                                      fus.coverage.first_seen_seed)
        np.testing.assert_array_equal(ref.coverage.novelty_curve,
                                      fus.coverage.novelty_curve)


def test_fused_matches_serial_raft_all_modes(raft_eng):
    """Every fused-legal loop mode of the flagship family: full-width
    with a BINDING max_steps cap (worlds are still active when the
    budget runs out — the truncated tail must harvest identically),
    recycled natural drain, and the recycled early-stop combination
    (early exit with a mega-dispatch in flight must not overrun).  The
    pipelined leg rides only the first two modes — serial==pipelined
    for every mode is already tier-1-gated in test_sweep_pipeline, so
    the new claim here is fused==serial.  Every mode variant traces its
    own fused mega-program (~5s each even on a warm persistent cache),
    so modes earn their slot by exercising a distinct fused code path
    — a plain uncapped full-width mode would re-trace a whole program
    to re-prove the drain that the recycled mode and the family tests
    below already gate."""
    seeds = np.arange(144)  # not a mesh multiple: stream tail exercised
    for i, kw in enumerate((
            dict(chunk_steps=64, max_steps=128),
            dict(chunk_steps=64, max_steps=1_280,
                 recycle=True, batch_worlds=48),
            dict(chunk_steps=64, max_steps=10_000,
                 stop_on_first_bug=True, recycle=True,
                 batch_worlds=48))):
        ser = sweep(None, raft_eng.cfg, seeds, engine=raft_eng,
                    pipeline=False, **kw)
        fus = sweep(None, raft_eng.cfg, seeds, engine=raft_eng,
                    fused=True, **kw)
        assert_fused_bitwise(ser, fus)
        if i == 0:
            # The cap must actually bind for the truncated-tail claim
            # (raft double-vote worlds drain naturally by ~step 256).
            assert ser.steps_run == 128
            assert np.asarray(ser.n_active_history)[-1] > 0
        if i < 2:
            pip = sweep(None, raft_eng.cfg, seeds, engine=raft_eng,
                        pipeline=True, **kw)
            assert_fused_bitwise(pip, fus)
    assert fus.loop_stats["fused"] and not fus.loop_stats["pipelined"]
    assert not ser.loop_stats["fused"] and not pip.loop_stats["fused"]


@pytest.mark.parametrize("family", ["pb", "tpc", "paxos"])
def test_fused_matches_serial_families(family, request):
    """Drain hunts of the remaining families (pb/tpc hand-written,
    paxos actorc-compiled), serial-vs-fused; the actorc family also
    rides the recycled refill path.  The pipelined loop is
    family-agnostic host logic already gated against serial per family
    in its own suite, and against fused on the flagship above — and
    recycled pb/tpc would re-trace two more whole programs to re-prove
    the refill seam that raft, paxos, and the guided pair already
    gate."""
    eng = request.getfixturevalue(f"{family}_eng")
    seeds = np.arange(64)
    modes = [dict(chunk_steps=64, max_steps=2_500)]
    if family == "paxos":
        modes.append(dict(chunk_steps=64, max_steps=2_500,
                          recycle=True, batch_worlds=32))
    for kw in modes:
        ser = sweep(None, eng.cfg, seeds, engine=eng, pipeline=False,
                    **kw)
        fus = sweep(None, eng.cfg, seeds, engine=eng, fused=True, **kw)
        assert_fused_bitwise(ser, fus)


# ---------------------------------------------------------------------------
# Guided hunts: harvest + generate + lineage inside the fused loop
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def hunt():
    from madsim_tpu.search import (GuidedPairActor, GuidedPairConfig,
                                   engine_config, family_schedule)
    from madsim_tpu.search.family import HUNT_NODES, HUNT_ROWS

    acfg = GuidedPairConfig(n=HUNT_NODES)
    cfg = engine_config(acfg)
    eng = DeviceEngine(GuidedPairActor(acfg), cfg)
    tmpl = family_schedule(HUNT_ROWS, acfg)
    return eng, cfg, tmpl


@pytest.mark.parametrize("guided", [True, False])
def test_fused_guided_hunt_bitwise(hunt, guided):
    """The guided (and matched random-baseline) hunt: child bytes,
    corpus decisions, lineage lanes, operator credits, and the
    SearchReport are identical when the harvest+generate fold runs as a
    ``lax.cond`` branch of the fused loop instead of a host-dispatched
    program at each refill — it is the same traced callable
    (search/generate.py ``generate_body``) either way."""
    from madsim_tpu.search.family import hunt_search_config

    eng, cfg, tmpl = hunt
    seeds = np.arange(96)
    kw = dict(engine=eng, faults=tmpl, max_steps=10_000_000,
              search=hunt_search_config(guided), recycle=True,
              batch_worlds=32, chunk_steps=32)
    ser = sweep(None, cfg, seeds, pipeline=False, **kw)
    fus = sweep(None, cfg, seeds, fused=True, **kw)
    assert_fused_bitwise(ser, fus)
    # SearchReport: the whole guided outcome surface.
    rs, rf = ser.search, fus.search
    assert (rs.generations, rs.inserted, rs.corpus_size) == \
        (rf.generations, rf.inserted, rf.corpus_size)
    for field in ("corpus_sched", "corpus_sig", "corpus_score",
                  "corpus_filled", "schedules", "corpus_entry",
                  "corpus_depth"):
        np.testing.assert_array_equal(getattr(rs, field),
                                      getattr(rf, field), err_msg=field)
    assert rs.operator_stats == rf.operator_stats
    for lane in ("parent1", "parent2", "ops", "depth"):
        np.testing.assert_array_equal(getattr(rs.lineage, lane),
                                      getattr(rf.lineage, lane),
                                      err_msg=lane)
    # Triage attribution: the materialized per-seed schedules.
    np.testing.assert_array_equal(ser.triage_ctx.faults,
                                  fus.triage_ctx.faults)


# ---------------------------------------------------------------------------
# Refusals: the checkpoint-interplay decision (docs/perf.md)
# ---------------------------------------------------------------------------

def test_fused_refuses_checkpoint(raft_eng, tmp_path):
    """Decision, tested: fused + checkpoint_path is a pointed refusal —
    no host-visible mid-hunt boundary exists where state, cursor, and
    retired observations are simultaneously consistent."""
    with pytest.raises(ValueError, match="fused=True cannot checkpoint"):
        sweep(None, raft_eng.cfg, np.arange(8), engine=raft_eng,
              fused=True, checkpoint_path=str(tmp_path / "x.npz"))


def test_fused_refuses_compact(raft_eng):
    with pytest.raises(ValueError, match="fused=True has no shrink"):
        sweep(None, raft_eng.cfg, np.arange(8), engine=raft_eng,
              fused=True, compact=True)


# ---------------------------------------------------------------------------
# Dispatch economics: the tentpole's acceptance gate
# ---------------------------------------------------------------------------

def test_fused_dispatch_reduction_and_fetch_discipline(raft_eng,
                                                       monkeypatch):
    """The headline numbers, counted through the ``_fetch`` hook: on the
    pinned recycled-hunt shape the fused loop needs >= 4x fewer host
    dispatches per seed than the pipelined loop, with zero added
    mid-loop fetches — one scalar batch per mega-dispatch and ONE
    end-of-hunt retirement pull, total."""
    calls = []
    real_fetch = sweep_mod._fetch

    def counting_fetch(tree):
        out = real_fetch(tree)
        import jax
        nbytes = sum(np.asarray(x).nbytes for x in jax.tree.leaves(out))
        calls.append(nbytes)
        return out

    monkeypatch.setattr(sweep_mod, "_fetch", counting_fetch)
    # Same shape as the recycled mode above: the programs are already
    # compiled, this test pays execution + the counting hook only.
    seeds = np.arange(144)
    kw = dict(chunk_steps=64, max_steps=1_280, recycle=True,
              batch_worlds=48)
    pip = sweep(None, raft_eng.cfg, seeds, engine=raft_eng, **kw)
    calls.clear()
    fus = sweep(None, raft_eng.cfg, seeds, engine=raft_eng, fused=True,
                **kw)
    assert_fused_bitwise(pip, fus)
    st = fus.loop_stats
    # One scalar batch per mega-dispatch, one retirement pull — nothing
    # else crosses the boundary.
    assert st["scalar_fetches"] == st["dispatches"]
    assert st["retire_fetches"] == 1
    assert len(calls) == st["scalar_fetches"] + 1
    # The mid-loop pulls are scalars + the two K-wide history lanes —
    # bounded by the chunk budget, never a per-world or per-seed array.
    scalar_bytes = calls[:-1]
    assert max(scalar_bytes) <= 8 * st["superstep_max"] + 64, scalar_bytes
    # >= 4x fewer dispatches per seed than the pipelined loop (the
    # tier-1 regression gate of the bench acceptance criterion).
    assert st["dispatches_per_seed"] * 4 <= \
        pip.loop_stats["dispatches_per_seed"], (st, pip.loop_stats)
    assert st["seeds_per_dispatch"] >= \
        4 * pip.loop_stats["seeds_per_dispatch"]
    # The whole hunt refilled on device, host cursor mirrors agree.
    assert st["epochs_on_device"] >= 1
    assert pip.loop_stats["epochs_on_device"] == 0


def test_fused_zero_step_budget_runs_no_chunks(raft_eng):
    """max_steps <= 0: zero chunks, but the live (init-state)
    observations still land — the serial loop's final observe() of an
    unstepped batch, reproduced by the zero-chunk pass-through
    mega-dispatch."""
    ser, pip, fus = all_loops(raft_eng, np.arange(8), chunk_steps=64,
                              max_steps=0)
    assert_fused_bitwise(ser, fus)
    assert fus.steps_run == 0
    assert fus.loop_stats["chunks"] == 0


def test_fused_loop_stats_schema(raft_eng):
    """The documented loop_stats schema on the fused path, plus the two
    new dispatch-economics keys on EVERY path."""
    res = sweep(None, raft_eng.cfg, np.arange(48), engine=raft_eng,
                chunk_steps=64, max_steps=2_048, fused=True)
    ls = res.loop_stats
    documented = {"device_wait_s", "host_decision_s", "scalar_fetches",
                  "retire_fetches", "dispatch_depth",
                  "dispatches_per_seed", "seeds_per_dispatch",
                  "epochs_on_device", "pipelined", "fused",
                  "superstep_max", "chunk_steps", "chunks", "dispatches",
                  "chunks_per_dispatch", "dispatch_s", "retire_wait_s",
                  "loop_wall_s", "prepare_s", "init_s", "upload_s",
                  "assemble_s", "identity_s", "identity_hashes",
                  "fused_setup_cache_hits"}
    assert documented <= set(ls), sorted(ls)
    assert ls["fused"] is True and ls["pipelined"] is False
    assert ls["identity_hashes"] == 0
    assert ls["fused_setup_cache_hits"] in (0, 1)
    assert isinstance(ls["seeds_per_dispatch"], float)
    assert isinstance(ls["epochs_on_device"], int)
    assert ls["seeds_per_dispatch"] == pytest.approx(
        48 / ls["dispatches"], abs=1e-3)
    # And on the host paths the keys exist with the fused-off values.
    for pipeline in (True, False):
        res = sweep(None, raft_eng.cfg, np.arange(48), engine=raft_eng,
                    chunk_steps=64, max_steps=2_048, pipeline=pipeline)
        assert {"seeds_per_dispatch", "epochs_on_device",
                "fused"} <= set(res.loop_stats)
        assert res.loop_stats["epochs_on_device"] == 0
        assert res.loop_stats["fused"] is False


# ---------------------------------------------------------------------------
# Setup before the program: the seed words and the cached buffers
# ---------------------------------------------------------------------------

_EDGE_SEEDS = np.array([0, 2**32 - 1, 2**32, 2**63, 2**64 - 1], np.uint64)


@pytest.mark.parametrize("case", ["contiguous", "strided", "big_endian",
                                  "padded"])
def test_seed_words_match_the_mask_and_shift_split(raft_eng, case):
    """The ``(n_b, 2)`` word table, and the lo/hi tables the fused setup
    program splits from it on the device, equal the ``& 0xFFFFFFFF`` /
    ``>> 32`` split, zero rows past the seeds; contiguous native seeds
    are viewed, not copied."""
    from madsim_tpu.parallel.mesh import seed_mesh

    seeds = {"contiguous": _EDGE_SEEDS,
             "strided": np.repeat(_EDGE_SEEDS, 2)[::2],
             "big_endian": _EDGE_SEEDS.astype(">u8"),
             "padded": _EDGE_SEEDS}[case]
    n = len(seeds)
    n_b = sweep_mod._pow2_at_least(n + 1) if case == "padded" else n
    want = np.asarray(seeds, np.uint64)
    lo = (want & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (want >> np.uint64(32)).astype(np.uint32)

    words = sweep_mod._seed_words(seeds, n_b)
    assert words.shape == (n_b, 2)
    np.testing.assert_array_equal(words[:n, 0], lo)
    np.testing.assert_array_equal(words[:n, 1], hi)
    np.testing.assert_array_equal(words[n:], 0)
    if case == "contiguous":
        assert np.shares_memory(words, seeds)

    setup, _ = sweep_mod._fused_setup(
        raft_eng, seed_mesh(), raft_eng.init(np.arange(8)), w=8,
        n_ids_b=n_b, f_rows=0, lineage_on=False)
    lo_d, hi_d = setup(words.reshape(-1), np.int32(8))[:2]
    np.testing.assert_array_equal(np.asarray(lo_d)[:n], lo)
    np.testing.assert_array_equal(np.asarray(hi_d)[:n], hi)
    np.testing.assert_array_equal(np.asarray(lo_d)[n:], 0)


def test_fused_setup_cache_serves_a_second_hunt_in_the_bucket(raft_eng):
    """Two fused hunts whose seed counts share a power-of-two bucket: the
    second reuses the engine's setup program (no ``eval_shape``, no new
    trace), and its rows equal those of a fresh engine's hunt."""
    kw = dict(chunk_steps=64, max_steps=2_048, fused=True, recycle=True,
              batch_worlds=16)
    sweep(None, raft_eng.cfg, np.arange(1_000, 1_040), engine=raft_eng,
          **kw)
    seeds = np.arange(2_000, 2_056)            # 40 and 56 both bucket to 64
    second = sweep(None, raft_eng.cfg, seeds, engine=raft_eng, **kw)
    assert second.loop_stats["fused_setup_cache_hits"] == 1
    fresh_eng = DeviceEngine(raft_eng.actor, raft_eng.cfg)
    fresh = sweep(None, raft_eng.cfg, seeds, engine=fresh_eng, **kw)
    assert fresh.loop_stats["fused_setup_cache_hits"] == 0
    assert_fused_bitwise(fresh, second)
