"""Coverage-guided fault-schedule search (madsim_tpu/search/).

The closed-fuzzer-loop contract (docs/search.md):

- the guided sweep is BITWISE reproducible — identical across re-runs
  and across ``pipeline=True/False`` (the mutation lanes are counter-
  based splitmix64, the corpus fold is sequential and deterministic);
- guided search measurably beats the matched random-mutation baseline
  on the conjunction family (the staircase argument) and, in the
  canonical hunts, on the actorc Paxos family too;
- corpus + per-slot schedule state survives checkpoint→resume
  bit-exactly through the PR 7 aux-array channel;
- ``search=None`` sweeps compile the exact pre-search programs (the
  guided run reuses the same superstep runners — only NEW cache entries
  appear, keyed separately);
- zero added host syncs: corpus telemetry rides the retire pulls the
  loop already pays (counted through the ``_fetch`` hook);
- a chaotic guided fleet equals a clean one bitwise;
- ``DeviceEngine.refill`` takes first-class per-slot ``(W, F, 4)``
  schedules — device arrays with no host sync — with dim errors naming
  both dims.

Compile budget: every sweep here shares ONE module-scoped family engine
and the same (batch_worlds=32, chunk_steps=32) shapes, so the jit and
persistent caches amortize across the whole file.
"""
import importlib
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu.engine import DeviceEngine
from madsim_tpu.engine.checkpoint import CheckpointError
from madsim_tpu.search import (
    EMPTY_NOVELTY,
    GuidedPairActor,
    GuidedPairConfig,
    SearchConfig,
    corpus_init,
    engine_config,
    family_schedule,
)
from madsim_tpu.search.family import HUNT_NODES, HUNT_ROWS, hunt_search_config
from madsim_tpu.search.hunts import pair_hunt, paxos_hunt

sweep_mod = importlib.import_module("madsim_tpu.parallel.sweep")
sweep = sweep_mod.sweep

# Shared sweep shapes (see module docstring).
BATCH = dict(recycle=True, batch_worlds=32, chunk_steps=32)


@pytest.fixture(scope="module")
def hunt():
    """One family engine for the whole file (jit caches are
    per-instance; rebuilding would recompile every program)."""
    acfg = GuidedPairConfig(n=HUNT_NODES)
    cfg = engine_config(acfg)
    eng = DeviceEngine(GuidedPairActor(acfg), cfg)
    tmpl = family_schedule(HUNT_ROWS, acfg)
    return eng, cfg, tmpl


def _guided(eng, cfg, tmpl, n_seeds, guided=True,
            max_steps=10_000_000, **kw):
    return sweep(None, cfg, np.arange(n_seeds), engine=eng, faults=tmpl,
                 max_steps=max_steps, search=hunt_search_config(guided),
                 **BATCH, **kw)


# ---------------------------------------------------------------------------
# splitmix64 lanes: device == host, counter-based
# ---------------------------------------------------------------------------

def test_splitmix_device_matches_host_fleet_prng():
    """The device lanes are bit-identical to the fleet fabric's host
    splitmix64 applied at offset counters — one PRNG definition across
    the repo (fleet/rpc.py is the reference)."""
    from madsim_tpu.fleet.rpc import splitmix64 as host_mix
    from madsim_tpu.search.rng import _u32, lanes_u32, splitmix64_dev

    mask = (1 << 64) - 1
    for x in (0, 1, 0xDEADBEEFCAFEBABE, mask, 1234567890123456789):
        hi, lo = splitmix64_dev((_u32((x >> 32) & 0xFFFFFFFF),
                                 _u32(x & 0xFFFFFFFF)))
        assert ((int(hi) << 32) | int(lo)) == host_mix(x)
    gamma = 0x9E3779B97F4A7C15
    x0 = (jnp.uint32(0x12345678), jnp.uint32(0x9ABCDEF0))
    lanes = np.asarray(lanes_u32(x0, 9))
    base = (0x12345678 << 32) | 0x9ABCDEF0
    for i in range(9):
        assert int(lanes[i]) == host_mix((base + i * gamma) & mask) \
            & 0xFFFFFFFF


def test_lanes_are_pure_functions_of_seed_id_generation():
    from madsim_tpu.search.rng import lanes_u32, stream_key

    ids = jnp.arange(6, dtype=jnp.int32)
    a = np.asarray(lanes_u32(stream_key(7, ids, 3), 4))
    b = np.asarray(lanes_u32(stream_key(7, ids, 3), 4))
    c = np.asarray(lanes_u32(stream_key(7, ids, 4), 4))
    d = np.asarray(lanes_u32(stream_key(8, ids, 3), 4))
    assert (a == b).all()
    assert not (a == c).all() and not (a == d).all()
    # Distinct slots get distinct streams.
    assert len({tuple(r) for r in a}) == a.shape[0]


# ---------------------------------------------------------------------------
# Corpus: novelty scoring + sequential insertion
# ---------------------------------------------------------------------------

def test_corpus_novelty_and_harvest(hunt):
    from madsim_tpu.search.corpus import harvest_fold, novelty

    _eng, _cfg, tmpl = hunt
    corp = corpus_init(4, tmpl)
    # Template entry: sig 0, score 0, filled.
    assert int(np.asarray(corp.filled).sum()) == 1
    # Novelty against {sig 0}: the popcount of the candidate signature.
    assert int(novelty(jnp.uint32(0b1011), corp)) == 3
    assert int(novelty(jnp.uint32(0), corp)) == 0

    sched = jnp.broadcast_to(jnp.asarray(tmpl), (3,) + tmpl.shape)
    sigs = jnp.asarray([0b1011, 0b1011, 0], jnp.uint32)
    mask = jnp.asarray([True, True, True])
    corp2, n_ins = harvest_fold(corp, sched, sigs, mask, min_novelty=1)
    # World 0 inserts (novel); world 1 is now distance 0 to it — skipped;
    # world 2 is distance 0 to the template — skipped.
    assert int(n_ins) == 1
    assert int(np.asarray(corp2.filled).sum()) == 2
    assert int(np.asarray(corp2.inserted)) == 1
    # Empty corpus scores EMPTY_NOVELTY.
    empty = corp._replace(filled=jnp.zeros((4,), bool))
    assert int(novelty(jnp.uint32(1), empty)) == EMPTY_NOVELTY


def test_children_valid_and_keyed_by_generation(hunt):
    from madsim_tpu.search.mutate import make_children

    eng, cfg, tmpl = hunt
    scfg = hunt_search_config(True)
    corp = corpus_init(8, tmpl)
    ids = jnp.arange(16, dtype=jnp.int32)
    c1 = np.asarray(make_children(scfg, cfg, corp, ids, jnp.int32(1)))
    c1b = np.asarray(make_children(scfg, cfg, corp, ids, jnp.int32(1)))
    c2 = np.asarray(make_children(scfg, cfg, corp, ids, jnp.int32(2)))
    assert (c1 == c1b).all() and not (c1 == c2).all()
    en = c1[..., 0] >= 0
    assert (c1[en][:, 1] >= 0).all() and (c1[en][:, 1] <= 9).all()
    node_op = (c1[en][:, 1] <= 5) | (c1[en][:, 1] >= 8)
    assert (c1[en][node_op][:, 2:] >= 0).all()
    assert (c1[en][node_op][:, 2:] < cfg.n_nodes).all()
    # Disabled rows are canonical DISABLED_ROW sentinels.
    assert (c1[~en] == np.array([-1, 0, 0, 0], np.int32)).all()


# ---------------------------------------------------------------------------
# The guided sweep: determinism, the staircase gap, triage hand-off
# ---------------------------------------------------------------------------

def test_guided_sweep_bitwise_rerun_and_pipeline(hunt):
    eng, cfg, tmpl = hunt
    a = _guided(eng, cfg, tmpl, 128, stop_on_first_bug=True)
    b = _guided(eng, cfg, tmpl, 128, stop_on_first_bug=True)
    c = _guided(eng, cfg, tmpl, 128, stop_on_first_bug=True,
                pipeline=False)
    assert a.failing_seeds, "the guided hunt must reach the bug"
    for other in (b, c):
        assert (a.bug == other.bug).all()
        for k in a.observations:
            np.testing.assert_array_equal(
                np.asarray(a.observations[k]),
                np.asarray(other.observations[k]), err_msg=k)
        assert (a.search.schedules == other.search.schedules).all()
        assert (a.search.corpus_sched == other.search.corpus_sched).all()
        assert (a.search.corpus_sig == other.search.corpus_sig).all()
        assert a.search.generations == other.search.generations
        assert a.search.inserted == other.search.inserted
        np.testing.assert_array_equal(a.coverage.hits, other.coverage.hits)


def test_guided_beats_random_on_the_family(hunt):
    """The acceptance gate's core claim at test scale: on the
    conjunction family, guided search reaches the bug inside a budget
    the matched random-mutation baseline cannot (the full measured gap
    — ~73 vs ~409 seeds — is `make fuzz-demo`)."""
    eng, cfg, tmpl = hunt
    g = _guided(eng, cfg, tmpl, 128, stop_on_first_bug=True)
    r = _guided(eng, cfg, tmpl, 128, guided=False, stop_on_first_bug=True)
    assert g.failing_seeds, "guided search missed the bug in budget"
    assert not r.failing_seeds, \
        "random baseline found the bug inside the guided budget — the " \
        "family lost its staircase gap (retune search/family.py)"
    # The novelty curve actually grew: feedback is flowing.
    assert g.search.corpus_size > 1
    assert g.coverage.novelty_curve[-1] > 1


@pytest.mark.parametrize("make", [pair_hunt, paxos_hunt],
                         ids=["pair", "paxos"])
def test_canonical_hunt_guided_reaches_the_bug_first(make):
    """The canonical hunts of search/hunts.py (`make fuzz-demo`'s pair
    family, `make actorc-demo`'s forgetful-acceptor Paxos) at a 256-seed
    budget: guided search reaches the bug in strictly fewer seeds than
    the matched random baseline, which may miss it (pair: 73 seeds,
    Paxos: 191; random finds neither), and the find descends from at
    least one mutation."""
    h = make()
    eng = DeviceEngine(h.actor, h.cfg)

    def hunt_once(guided):
        return sweep(None, h.cfg, np.arange(256), engine=eng,
                     faults=h.template, stop_on_first_bug=True,
                     search=h.search(guided), **h.sweep_kw)

    g, r = hunt_once(True), hunt_once(False)
    assert g.failing_seeds, f"guided search missed the {h.name} bug"
    assert not r.failing_seeds or \
        g.failing_seeds[0] < r.failing_seeds[0], \
        (g.failing_seeds[0], r.failing_seeds[0])
    assert g.search.lineage_depth() >= 1


def test_guided_find_triages_to_the_two_target_restarts(hunt):
    """Every find pipes unchanged through triage: the materialized
    per-seed schedule lands in triage_ctx, ddmin converges to exactly
    the two target restarts, 1-minimal."""
    eng, cfg, tmpl = hunt
    res = _guided(eng, cfg, tmpl, 128, stop_on_first_bug=True)
    s0 = res.failing_seeds[0]
    # The materialized schedule is what the failing world actually ran.
    assert res.search.schedules.shape[1:] == tmpl.shape
    assert res.triage_ctx.faults is res.search.schedules
    mr = res.minimize(chunk_steps=32, max_steps=20_000)
    assert mr.seed == s0
    assert mr.final_rows == 2 and mr.one_minimal
    acfg = GuidedPairConfig(n=HUNT_NODES)
    assert sorted(int(x) for x in mr.schedule[:, 2]) == \
        [acfg.node_a, acfg.node_b]


def test_search_validation_errors(hunt):
    eng, cfg, tmpl = hunt
    scfg = hunt_search_config(True)
    with pytest.raises(ValueError, match="recycle=True"):
        sweep(None, cfg, np.arange(8), engine=eng, faults=tmpl,
              chunk_steps=32, max_steps=256, search=scfg)
    with pytest.raises(ValueError, match="fault-schedule template"):
        sweep(None, cfg, np.arange(8), engine=eng, max_steps=256,
              search=scfg, **BATCH)
    acfg = GuidedPairConfig(n=HUNT_NODES)
    import dataclasses as dc

    eng_off = DeviceEngine(GuidedPairActor(acfg),
                           dc.replace(cfg, metrics=False))
    with pytest.raises(ValueError, match="metrics=True"):
        sweep(None, eng_off.cfg, np.arange(8), engine=eng_off,
              faults=tmpl, max_steps=256, search=scfg, **BATCH)
    with pytest.raises(ValueError, match="min_novelty"):
        SearchConfig(min_novelty=0)
    with pytest.raises(ValueError, match="cumulative"):
        SearchConfig(disable_pct=60, time_pct=60)


# ---------------------------------------------------------------------------
# Checkpoint → resume: the corpus survives bit-exactly (aux channel)
# ---------------------------------------------------------------------------

def test_guided_checkpoint_resume_bit_exact(hunt, tmp_path):
    eng, cfg, tmpl = hunt
    seeds_n = 96
    unbroken = _guided(eng, cfg, tmpl, seeds_n)
    path = str(tmp_path / "guided.npz")
    _part = _guided(eng, cfg, tmpl, seeds_n, max_steps=64 * 32,
                    checkpoint_path=path, checkpoint_every_chunks=4)
    full = _guided(eng, cfg, tmpl, seeds_n, checkpoint_path=path,
                   resume=True)
    assert (unbroken.bug == full.bug).all()
    for k in unbroken.observations:
        np.testing.assert_array_equal(
            np.asarray(unbroken.observations[k]),
            np.asarray(full.observations[k]), err_msg=k)
    assert (unbroken.search.schedules == full.search.schedules).all()
    assert (unbroken.search.corpus_sched == full.search.corpus_sched).all()
    assert (unbroken.search.corpus_sig == full.search.corpus_sig).all()
    assert (unbroken.search.corpus_score == full.search.corpus_score).all()
    assert unbroken.search.generations == full.search.generations
    assert unbroken.search.inserted == full.search.inserted
    np.testing.assert_array_equal(unbroken.coverage.hits,
                                  full.coverage.hits)


def test_guided_plain_checkpoint_mixups_refused(hunt, tmp_path):
    eng, cfg, tmpl = hunt
    path = str(tmp_path / "guided.npz")
    _guided(eng, cfg, tmpl, 96, max_steps=64 * 32, checkpoint_path=path,
            checkpoint_every_chunks=4)
    # Guided checkpoint, plain resume: refused with a pointed error.
    with pytest.raises(CheckpointError, match="guided"):
        sweep(None, cfg, np.arange(96), engine=eng, faults=tmpl,
              max_steps=10_000_000, checkpoint_path=path, resume=True,
              **BATCH)
    # Plain checkpoint, guided resume: refused too.
    plain = str(tmp_path / "plain.npz")
    sweep(None, cfg, np.arange(96), engine=eng, faults=tmpl,
          max_steps=64 * 32, checkpoint_path=plain,
          checkpoint_every_chunks=4, **BATCH)
    with pytest.raises(CheckpointError, match="plain"):
        _guided(eng, cfg, tmpl, 96, checkpoint_path=plain, resume=True)


# ---------------------------------------------------------------------------
# Sync discipline + compile identity
# ---------------------------------------------------------------------------

def test_guided_sweep_adds_zero_host_syncs(hunt, monkeypatch):
    """Corpus syncs ride the existing cadence: every pull is either a
    per-superstep scalar fetch or a retire pull the plain recycled loop
    pays too — counted through the one sanctioned ``_fetch`` hook."""
    eng, cfg, tmpl = hunt
    calls = []
    real_fetch = sweep_mod._fetch

    def counting_fetch(tree):
        calls.append(1)
        return real_fetch(tree)

    monkeypatch.setattr(sweep_mod, "_fetch", counting_fetch)
    res = _guided(eng, cfg, tmpl, 96)
    st = res.loop_stats
    assert st["retire_fetches"] >= 1          # refills happened
    assert len(calls) == st["scalar_fetches"] + st["retire_fetches"] + 1


def test_search_none_compiles_exact_pre_search_programs(hunt):
    """A ``search=None`` sweep touches no search machinery: no searcher
    or schedule-tail programs are built, and its compaction programs are
    the ``with_sched=False`` variants. A guided sweep then REUSES the
    very same superstep cache entries (the chunk/superstep programs are
    untouched by search — its one new program lives under its own
    keys), so the op-budget ledger of the sweep programs is untouched by
    construction."""
    eng, cfg, tmpl = hunt
    eng.__dict__.pop("_searcher_cache", None)
    eng.__dict__.pop("_sched_tail_cache", None)
    # The module-scoped engine already ran guided sweeps: diff against
    # the pre-existing program sets instead of demanding emptiness.
    compact_pre = set(eng.__dict__.get("_compactor_cache", {}))
    plain = sweep(None, cfg, np.arange(96), engine=eng, faults=tmpl,
                  max_steps=10_000_000, **BATCH)
    assert plain.search is None
    assert "_searcher_cache" not in eng.__dict__
    assert "_sched_tail_cache" not in eng.__dict__
    new_compact = set(eng.__dict__["_compactor_cache"]) - compact_pre
    assert all(not k[-1] for k in new_compact)  # with_sched=False only
    sstep_keys = set(eng.__dict__["_sharded_superstep_cache"])
    _g = _guided(eng, cfg, tmpl, 96)
    # The guided run added search-keyed programs only — the superstep
    # runners it dispatched are the SAME cache entries the plain sweep
    # compiled.
    assert set(eng.__dict__["_sharded_superstep_cache"]) == sstep_keys
    assert eng.__dict__["_searcher_cache"]


# ---------------------------------------------------------------------------
# Fleet: chaotic guided fleet == clean guided fleet (bitwise)
# ---------------------------------------------------------------------------

def test_fleet_guided_chaotic_equals_clean(hunt):
    """The chaos-matrix leg under guided refill: kills/expiries cost
    wall time, never results. (Guided fleet results are deterministic
    per (seeds, range partitioning, SearchConfig) — each range evolves
    its own corpus, so fleet != single-host here by design; the
    invariance that matters is chaos-invariance, docs/search.md.)"""
    from madsim_tpu.fleet import fleet_sweep
    from madsim_tpu.fleet.chaos import ChaosConfig

    eng, cfg, tmpl = hunt
    seeds = np.arange(96)
    kw = dict(engine=eng, faults=tmpl, chunk_steps=32,
              max_steps=10_000_000, recycle=True, batch_worlds=32,
              search=hunt_search_config(True))
    clean = fleet_sweep(None, cfg, seeds, n_workers=2, range_size=48,
                        **kw)
    chaotic = fleet_sweep(None, cfg, seeds, n_workers=2, range_size=48,
                          chaos=ChaosConfig(seed=7, kill_at=(("w1", 2),),
                                            restart_after=2), **kw)
    assert (clean.bug == chaotic.bug).all()
    for k in clean.observations:
        np.testing.assert_array_equal(
            np.asarray(clean.observations[k]),
            np.asarray(chaotic.observations[k]), err_msg=k)


# ---------------------------------------------------------------------------
# DeviceEngine.refill: first-class per-slot schedules
# ---------------------------------------------------------------------------

def test_refill_per_slot_dim_validation_names_both_dims(hunt):
    eng, cfg, tmpl = hunt
    faults = np.broadcast_to(tmpl, (8,) + tmpl.shape).copy()
    st = eng.init(np.arange(8, dtype=np.uint64), faults=faults)
    st = eng.run_steps(st, 64)
    mask = np.zeros(8, bool)
    mask[2:5] = True
    seeds = np.arange(100, 108, dtype=np.uint64)
    with pytest.raises(ValueError, match=r"leading dim 5.*8 slots"):
        eng.refill(st, mask, seeds, faults=faults[:5])
    with pytest.raises(ValueError, match=r"leading dim 5.*8 slots"):
        eng.refill(st, mask, seeds, faults=jnp.asarray(faults[:5]))
    with pytest.raises(ValueError, match="per-slot"):
        eng.refill(st, mask, seeds, faults=jnp.asarray(tmpl))


def test_refill_device_schedule_path_bitwise_equals_host(hunt):
    """The device (W, F, 4) override — the path the search generator
    feeds — initializes worlds bit-identically to the validated host
    path for the same values, with no host pull of the schedules."""
    eng, cfg, tmpl = hunt
    faults = np.broadcast_to(tmpl, (8,) + tmpl.shape).copy()
    faults[4:, 0, 2] = 1
    mask = np.zeros(8, bool)
    mask[2:5] = True
    seeds = np.arange(100, 108, dtype=np.uint64)

    st_a = eng.init(np.arange(8, dtype=np.uint64), faults=faults)
    st_a = eng.run_steps(st_a, 64)
    st_b = eng.init(np.arange(8, dtype=np.uint64), faults=faults)
    st_b = eng.run_steps(st_b, 64)
    host = eng.refill(st_a, mask, seeds, faults=faults)
    dev = eng.refill(st_b, mask, seeds, faults=jnp.asarray(faults))
    oh, od = jax.device_get((eng.observe_device(host),
                             eng.observe_device(dev)))
    for k in oh:
        np.testing.assert_array_equal(np.asarray(oh[k]),
                                      np.asarray(od[k]), err_msg=k)
