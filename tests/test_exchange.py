"""Cross-range corpus exchange (madsim_tpu/fleet/exchange.py,
docs/fleet.md "Corpus exchange").

The PR 12 contract:

- the HOST merge fold is bit-identical to the DEVICE corpus insertion
  fold (the PR 9 twin-parity pattern);
- exchange epochs are structural (range-id partition) and the barrier
  is keyed to completed lease quanta, so a chaotic exchanged fleet —
  kills mid-epoch (kill→re-lease re-seeds from the last merged epoch),
  torn publishes, duplicated completions, dropped RPCs — equals a
  crash-free exchanged fleet BITWISE, including the materialized
  per-seed schedules and the merged corpus;
- epoch-0 ranges are bitwise identical to a non-exchanged fleet's, and
  a single-epoch exchange (cadence >= range count) is bitwise identical
  to ``exchange=None`` end to end — the machinery is invisible when
  there is nothing to exchange;
- duplicate publishes dedupe by range id with bitwise crosscheck
  (tampered duplicates raise FleetIntegrityError); torn publishes are
  discarded and re-sent;
- the coordinator's exchange state persists (fsync+rename) and a
  resumed coordinator re-derives every merged epoch bit-exactly;
- ``sweep(search_corpus=)`` seeding with the template-initialized
  corpus is bitwise invisible and adds ZERO host syncs (counted
  through the ``_fetch`` hook).

Compile budget: one module-scoped family engine at the same
(batch_worlds=32, chunk_steps=32) shapes as tests/test_search.py, so
the jit + persistent caches amortize.
"""
import importlib
import json

import numpy as np
import pytest

from madsim_tpu.engine import DeviceEngine
from madsim_tpu.fleet import (
    ChaosConfig,
    CorpusExchange,
    ExchangeConfig,
    FleetIntegrityError,
    FleetStalledError,
    TornPayloadError,
    fleet_sweep,
    split_ranges,
)
from madsim_tpu.fleet.exchange import (
    GEN_STRIDE,
    corpus_payload,
    payload_corpus,
)
from madsim_tpu.search import (
    GuidedPairActor,
    GuidedPairConfig,
    engine_config,
    family_schedule,
)
from madsim_tpu.search.corpus import (
    EMPTY_NOVELTY,
    HostCorpus,
    corpus_init,
    harvest_fold,
    host_corpus_init,
    host_harvest_fold,
    merge_corpus,
)
from madsim_tpu.search.family import HUNT_NODES, HUNT_ROWS, hunt_search_config

sweep_mod = importlib.import_module("madsim_tpu.parallel.sweep")
sweep = sweep_mod.sweep

BATCH = dict(recycle=True, batch_worlds=32, chunk_steps=32)
N_SEEDS = 96
RANGE = 48  # > batch_worlds, so refills (and harvests) actually run


@pytest.fixture(scope="module")
def hunt():
    acfg = GuidedPairConfig(n=HUNT_NODES)
    cfg = engine_config(acfg)
    eng = DeviceEngine(GuidedPairActor(acfg), cfg)
    return eng, cfg, family_schedule(HUNT_ROWS, acfg)


def _fleet(eng, cfg, tmpl, exchange=None, chaos=None, n_workers=2,
           n_seeds=N_SEEDS, range_size=RANGE, **kw):
    return fleet_sweep(None, cfg, np.arange(n_seeds), engine=eng,
                       faults=tmpl, n_workers=n_workers,
                       range_size=range_size, max_steps=10_000_000,
                       search=hunt_search_config(True), exchange=exchange,
                       chaos=chaos, **BATCH, **kw)


def assert_bitwise(a, b, search=True):
    np.testing.assert_array_equal(a.seeds, b.seeds)
    np.testing.assert_array_equal(a.bug, b.bug)
    assert set(a.observations) == set(b.observations)
    for k in a.observations:
        np.testing.assert_array_equal(np.asarray(a.observations[k]),
                                      np.asarray(b.observations[k]),
                                      err_msg=k)
    if search:
        assert (a.search is None) == (b.search is None)
        if a.search is not None:
            np.testing.assert_array_equal(a.search.schedules,
                                          b.search.schedules)
            for f in ("corpus_sched", "corpus_sig", "corpus_score",
                      "corpus_filled", "corpus_entry", "corpus_depth"):
                np.testing.assert_array_equal(
                    getattr(a.search, f), getattr(b.search, f), err_msg=f)
            # The lineage surface (obs/lineage.py) is chaos-invariant
            # too: ancestry attribution and operator accounting must
            # not depend on kills, duplicates, or torn publishes.
            la, lb = a.search.lineage, b.search.lineage
            assert (la is None) == (lb is None)
            if la is not None:
                for f in ("parent1", "parent2", "ops", "depth"):
                    np.testing.assert_array_equal(
                        getattr(la, f), getattr(lb, f),
                        err_msg=f"lineage.{f}")
                assert a.search.operator_stats == b.search.operator_stats


# ---------------------------------------------------------------------------
# The twin: host merge fold == device insertion fold, bit for bit
# ---------------------------------------------------------------------------

def test_host_fold_parity_with_device(hunt):
    """The exchange merge rides host_harvest_fold, which must reproduce
    the device harvest_fold exactly — ties, novelty gating, worst-first
    replacement, empty-corpus scoring — else a seeded range would
    derive different children than the chaos contract demands."""
    import jax.numpy as jnp

    _eng, _cfg, tmpl = hunt
    rng = np.random.RandomState(7)
    for trial in range(12):
        k = int(rng.randint(1, 7))
        w = int(rng.randint(1, 9))
        mn = int(rng.randint(1, 5))
        sched = rng.randint(-1, 60, size=(w, tmpl.shape[0], 4)) \
            .astype(np.int32)
        sigs = rng.randint(0, 2**32, size=(w,),
                           dtype=np.uint64).astype(np.uint32)
        mask = rng.rand(w) < 0.7
        entries = rng.randint(1, 500, size=(w,)).astype(np.int32)
        depths = rng.randint(0, 9, size=(w,)).astype(np.int32)
        dev = corpus_init(k, tmpl)
        host = host_corpus_init(k, tmpl)
        for _round in range(2):  # fold twice: non-fresh corpus state too
            dev, nd, dnov, dins = harvest_fold(
                dev, jnp.asarray(sched), jnp.asarray(sigs),
                jnp.asarray(mask), mn, entries=jnp.asarray(entries),
                depths=jnp.asarray(depths), with_masks=True)
            host, nh, hnov, hins = host_harvest_fold(
                host, sched, sigs, mask, mn, entries=entries,
                depths=depths, with_masks=True)
            assert int(nd) == nh
            # The outcome-fold masks the operator table credits from
            # (obs/lineage.py) must agree too — the host/device
            # outcome-fold parity half of the PR 13 contract.
            np.testing.assert_array_equal(np.asarray(dnov), hnov)
            np.testing.assert_array_equal(np.asarray(dins), hins)
            for name in ("sched", "sig", "score", "filled", "entry",
                         "depth"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(dev, name)),
                    np.asarray(getattr(host, name)),
                    err_msg=f"trial {trial} field {name}")
            sigs = rng.randint(0, 2**32, size=(w,),
                               dtype=np.uint64).astype(np.uint32)
    # Host init matches the device init arrays (the epoch-0 seed).
    d0, h0 = corpus_init(4, tmpl), host_corpus_init(4, tmpl)
    for name in ("sched", "sig", "score", "filled", "entry", "depth"):
        np.testing.assert_array_equal(np.asarray(getattr(d0, name)),
                                      np.asarray(getattr(h0, name)))


# ---------------------------------------------------------------------------
# Epoch partition, barrier, merge chain (pure host units)
# ---------------------------------------------------------------------------

def _mk_exchange(n_ranges=4, every=2, k=4, tmpl=None, **kw):
    tmpl = tmpl if tmpl is not None else family_schedule(HUNT_ROWS)
    return CorpusExchange(ranges=split_ranges(n_ranges * 8, 8),
                          every=every, template=tmpl, corpus_k=k,
                          min_novelty=1, **kw)


def _snap(tmpl, k=4, sigs=(9,)):
    c = host_corpus_init(k, tmpl)
    sched = np.broadcast_to(tmpl, (len(sigs),) + tmpl.shape)
    c, _ = host_harvest_fold(c, sched, np.asarray(sigs, np.uint32),
                             np.ones(len(sigs), bool), 1)
    return c


def test_epoch_barrier_and_merge_chain():
    tmpl = family_schedule(HUNT_ROWS)
    ex = _mk_exchange(n_ranges=4, every=2, tmpl=tmpl)
    assert [ex.epoch_of(r) for r in range(4)] == [0, 0, 1, 1]
    assert ex.gen0_of(0) == 0 and ex.gen0_of(2) == GEN_STRIDE
    # Epoch-0 ranges are eligible from the start; epoch-1 blocked.
    assert ex.eligible(0) and ex.eligible(1)
    assert not ex.eligible(2)
    assert "exchange barrier" in ex.blocked_reason(2)
    assert ex.seed_corpus(0) is None  # epoch 0 = template (no payload)
    s0, s1 = _snap(tmpl, sigs=(9,)), _snap(tmpl, sigs=(12,))
    assert ex.publish(0, corpus_payload(s0), worker="w0")["accepted"]
    assert not ex.eligible(2)  # half-published epoch: still blocked
    assert ex.publish(1, corpus_payload(s1), worker="w1")["accepted"]
    # Barrier lifted; the merged corpus is the manual range-id fold.
    assert ex.eligible(2) and ex.merged_through() == 1
    want, _ = merge_corpus(ex.base, s0, 1)
    want, _ = merge_corpus(want, s1, 1)
    got = ex.seed_corpus(2)
    for name in ("sched", "sig", "score", "filled"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)))
    assert ex.stats["epochs_merged"] == 1


def test_duplicate_publish_dedupe_tamper_and_torn():
    tmpl = family_schedule(HUNT_ROWS)
    ex = _mk_exchange(n_ranges=2, every=1, tmpl=tmpl)
    snap = _snap(tmpl, sigs=(9,))
    assert ex.publish(0, corpus_payload(snap))["accepted"]
    # Bitwise-identical duplicate (restarted worker): absorbed.
    out = ex.publish(0, corpus_payload(snap))
    assert out["accepted"] and out["duplicate"]
    assert ex.stats["publishes_duplicate"] == 1
    # Tampered duplicate: the determinism contract is broken — loud.
    bad = HostCorpus(sched=snap.sched.copy(), sig=snap.sig.copy(),
                     score=snap.score.copy(), filled=snap.filled.copy(),
                     entry=snap.entry.copy(), depth=snap.depth.copy())
    bad.sig[0] ^= np.uint32(1)
    with pytest.raises(FleetIntegrityError, match="bitwise"):
        ex.publish(0, corpus_payload(bad))
    # Torn publish: checksum mismatch → discarded, resend requested.
    torn = corpus_payload(_snap(tmpl, sigs=(5,)))
    torn["sched"] = torn["sched"].copy()
    torn["sched"].flat[0] ^= 1
    out = ex.publish(1, torn)
    assert not out["accepted"] and out["torn"]
    assert ex.stats["publishes_torn"] == 1
    assert not ex.has(1)
    # The clean re-send goes through.
    assert ex.publish(1, corpus_payload(_snap(tmpl, sigs=(5,))))["accepted"]
    # Shape tears and checksum validation at the payload layer.
    with pytest.raises(TornPayloadError, match="checksum"):
        payload_corpus(torn)
    with pytest.raises(TornPayloadError, match="missing"):
        payload_corpus({"sched": torn["sched"]})
    with pytest.raises(TornPayloadError, match="entries"):
        payload_corpus(corpus_payload(snap), corpus_k=9)


def test_coordinator_crash_resume_is_bit_exact(tmp_path):
    """Coordinator killed between merge and broadcast: a fresh exchange
    reloading the persisted snapshots re-derives the identical merged
    corpus (the merge is a deterministic fold of the stored inputs),
    and continuing publishes into the resumed exchange ends at the same
    final chain as the uninterrupted one."""
    tmpl = family_schedule(HUNT_ROWS)
    path = str(tmp_path / "exchange_state.npz")
    a = _mk_exchange(n_ranges=4, every=2, tmpl=tmpl, state_path=path)
    snaps = [_snap(tmpl, sigs=(int(s),)) for s in (9, 12, 33, 70)]
    a.publish(0, corpus_payload(snaps[0]))
    a.publish(1, corpus_payload(snaps[1]))  # epoch 0 merged + persisted
    assert a.merged_through() == 1
    # "Crash": build a brand-new exchange from the same fleet shape and
    # resume from disk. The merged chain must match bit for bit.
    b = _mk_exchange(n_ranges=4, every=2, tmpl=tmpl, state_path=path)
    assert b.resume(path) == 2
    assert b.merged_through() == 1
    for name in ("sched", "sig", "score", "filled"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.merged_epoch(0), name)),
            np.asarray(getattr(b.merged_epoch(0), name)), err_msg=name)
    # Continue both to the end: identical final chains.
    for ex in (a, b):
        ex.publish(2, corpus_payload(snaps[2]))
        ex.publish(3, corpus_payload(snaps[3]))
        assert ex.merged_through() == 2
    for name in ("sched", "sig", "score", "filled"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.merged_epoch(1), name)),
            np.asarray(getattr(b.merged_epoch(1), name)), err_msg=name)
    # A mismatched fleet shape is refused loudly.
    with pytest.raises(FleetIntegrityError, match="different fleet"):
        _mk_exchange(n_ranges=4, every=1, tmpl=tmpl).resume(path)


# ---------------------------------------------------------------------------
# The fleet legs (device sweeps; shapes shared with test_search)
# ---------------------------------------------------------------------------

def test_exchanged_fleet_chaotic_equals_clean_and_workers_invariant(hunt):
    """The acceptance matrix in one pass: a clean exchanged fleet ==
    a chaotic one (kill mid-epoch → re-lease re-seeded from the last
    merged epoch, duplicated completions, torn publish, dropped RPCs)
    == a single-worker fleet over the same partition+cadence — bitwise
    on ids/observations/bug/schedules/merged corpus."""
    eng, cfg, tmpl = hunt
    clean = _fleet(eng, cfg, tmpl, exchange=ExchangeConfig(every=1))
    chaotic = _fleet(
        eng, cfg, tmpl, exchange=ExchangeConfig(every=1),
        chaos=ChaosConfig(seed=7, kill_at=(("w1", 2),),
                          duplicate_all_completions=True,
                          tear_publish_at=(("w0", 1),),
                          drop_rpc_rate=0.2, restart_after=2))
    solo = _fleet(eng, cfg, tmpl, exchange=ExchangeConfig(every=1),
                  n_workers=1)
    assert_bitwise(clean, chaotic)
    assert_bitwise(clean, solo)
    st = chaotic.loop_stats["fleet"]
    assert st["kills"] >= 1, "the kill→re-lease leg must have fired"
    assert st["leases_reissued"] >= 1
    assert st["publishes_torn"] >= 1
    assert st["duplicate_completions"] >= 1
    assert st["epochs_merged"] == 2
    # The exchange visibly did something: a later epoch was seeded and
    # the merged corpus grew past the template.
    workers = st["workers"]
    assert sum(w["corpus_seeded"] for w in workers.values()) >= 1
    assert clean.search is not None
    assert clean.search.corpus_size >= 2


def test_epoch0_matches_plain_fleet_and_seeding_changes_later_epochs(hunt):
    """Epoch-0 ranges run at generation offset 0 from the template
    corpus — bitwise identical to a non-exchanged fleet's — while
    seeded epochs run different children (the exchange actually bites).
    And with a cadence spanning every range (single epoch), the whole
    exchanged fleet is bitwise == exchange=None: the machinery is
    invisible when there is nothing to exchange."""
    eng, cfg, tmpl = hunt
    plain = _fleet(eng, cfg, tmpl, exchange=None)
    exchanged = _fleet(eng, cfg, tmpl, exchange=ExchangeConfig(every=1))
    # Epoch 0 = seeds [0, RANGE): bitwise equal to the plain fleet.
    for k in plain.observations:
        np.testing.assert_array_equal(
            np.asarray(plain.observations[k])[:RANGE],
            np.asarray(exchanged.observations[k])[:RANGE], err_msg=k)
    # Epoch 1 = seeds [RANGE, N): the merged-corpus seeding + stream
    # offset changed the children somewhere.
    assert any(
        not np.array_equal(np.asarray(plain.observations[k])[RANGE:],
                           np.asarray(exchanged.observations[k])[RANGE:])
        for k in plain.observations), \
        "exchange seeding left epoch-1 ranges bitwise unchanged — the " \
        "merged corpus is not reaching the sweeps"
    # Single epoch (cadence >= range count): end-to-end bitwise == None.
    single = _fleet(eng, cfg, tmpl, exchange=ExchangeConfig(every=2),
                    n_workers=1)
    assert_bitwise(plain, single, search=False)
    assert plain.search is None and single.search is not None
    st = single.loop_stats["fleet"]
    assert st["publishes"] == 2 and st["epochs_merged"] == 1


def test_exchange_reaches_a_bug_no_range_reaches_alone(hunt):
    """The fleet-level staircase: 64-seed ranges are shorter than the
    ~73 seeds the pair bug needs, so no range of an independent fleet
    reaches it alone, while the exchanged fleet (320 seeds, 2 workers)
    chains corpus progress across epochs and does (39 seeds into its
    fifth range): more bugs, in fewer seeds into a range."""
    eng, cfg, tmpl = hunt
    kw = dict(n_seeds=320, range_size=64, stop_on_first_bug=True)
    independent = _fleet(eng, cfg, tmpl, **kw)
    exchanged = _fleet(eng, cfg, tmpl, exchange=ExchangeConfig(every=1),
                       **kw)
    assert not independent.failing_seeds, \
        "a 64-seed range reached the pair bug alone: the family lost " \
        "its staircase (retune search/family.py)"
    assert exchanged.failing_seeds, \
        "the exchange did not chain corpus progress across epochs"


def test_exchanged_fleet_resumes_coordinator_state_end_to_end(hunt,
                                                             tmp_path):
    """A second fleet run over a pre-populated exchange state (the
    coordinator crash→restart shape): every range's snapshot is already
    published, so publishes dedupe as bitwise-checked duplicates and
    the final result equals the fresh run exactly."""
    eng, cfg, tmpl = hunt
    path = str(tmp_path / "exchange_state.npz")
    fresh = _fleet(eng, cfg, tmpl,
                   exchange=ExchangeConfig(every=1, state_path=path))
    resumed = _fleet(eng, cfg, tmpl,
                     exchange=ExchangeConfig(every=1, state_path=path))
    assert_bitwise(fresh, resumed)
    st = resumed.loop_stats["fleet"]
    # All snapshots were already on disk: the re-publishes are
    # crosschecked duplicates, and the barrier never blocked.
    assert st["publishes"] == 0
    assert st["publishes_duplicate"] == 2


# ---------------------------------------------------------------------------
# sweep(search_corpus=): bitwise-invisible seeding, zero extra syncs
# ---------------------------------------------------------------------------

def test_search_corpus_template_seed_bitwise_invisible_and_no_new_syncs(
        hunt, monkeypatch):
    eng, cfg, tmpl = hunt
    scfg = hunt_search_config(True)

    def run(**kw):
        calls = []
        real = sweep_mod._fetch

        def counting(tree):
            calls.append(1)
            return real(tree)

        monkeypatch.setattr(sweep_mod, "_fetch", counting)
        try:
            res = sweep(None, cfg, np.arange(64), engine=eng, faults=tmpl,
                        max_steps=10_000_000, search=scfg, **BATCH, **kw)
        finally:
            monkeypatch.setattr(sweep_mod, "_fetch", real)
        return res, len(calls)

    base, n_base = run()
    seeded, n_seeded = run(
        search_corpus=host_corpus_init(scfg.corpus, tmpl))
    # The template-initialized host corpus IS corpus_init: bitwise
    # invisible, and the host→device seeding adds zero _fetch calls.
    assert n_seeded == n_base
    assert (base.bug == seeded.bug).all()
    for k in base.observations:
        np.testing.assert_array_equal(np.asarray(base.observations[k]),
                                      np.asarray(seeded.observations[k]),
                                      err_msg=k)
    np.testing.assert_array_equal(base.search.schedules,
                                  seeded.search.schedules)
    np.testing.assert_array_equal(base.search.corpus_sched,
                                  seeded.search.corpus_sched)
    assert base.search.generations == seeded.search.generations


def test_search_corpus_and_gen0_validation(hunt):
    eng, cfg, tmpl = hunt
    scfg = hunt_search_config(True)
    with pytest.raises(ValueError, match="search=SearchConfig"):
        sweep(None, cfg, np.arange(8), engine=eng, faults=tmpl,
              max_steps=256,
              search_corpus=host_corpus_init(scfg.corpus, tmpl), **BATCH)
    with pytest.raises(ValueError, match="search=SearchConfig"):
        sweep(None, cfg, np.arange(8), engine=eng, faults=tmpl,
              max_steps=256, search_gen0=GEN_STRIDE, **BATCH)
    # Wrong K: the error names both dims (corpus entries vs config).
    with pytest.raises(ValueError, match=r"\(K, F, 4\).*corpus=32"):
        sweep(None, cfg, np.arange(8), engine=eng, faults=tmpl,
              max_steps=256, search=scfg,
              search_corpus=host_corpus_init(scfg.corpus // 2, tmpl),
              **BATCH)
    # Exchange-side validation at the fleet entry.
    with pytest.raises(ValueError, match="search=SearchConfig"):
        fleet_sweep(None, cfg, np.arange(16), engine=eng, faults=tmpl,
                    exchange=ExchangeConfig(), max_steps=256, **BATCH)
    with pytest.raises(ValueError, match="inline"):
        fleet_sweep(None, cfg, np.arange(16), engine=eng, faults=tmpl,
                    exchange=ExchangeConfig(), search=hunt_search_config(
                        True), spawn="process", max_steps=256, **BATCH)
    with pytest.raises(ValueError, match="every"):
        ExchangeConfig(every=0)


# ---------------------------------------------------------------------------
# FleetStalledError detail (satellite): names ranges, holders, beats
# ---------------------------------------------------------------------------

def test_stalled_error_names_ranges_holders_and_heartbeats(hunt):
    eng, cfg, tmpl = hunt
    with pytest.raises(FleetStalledError) as exc:
        fleet_sweep(None, cfg, np.arange(64), engine=eng, faults=tmpl,
                    n_workers=1, range_size=16, max_steps=10_000_000,
                    search=hunt_search_config(True),
                    exchange=ExchangeConfig(every=1),
                    chaos=ChaosConfig(seed=1, kill_at=(("w0", 1),),
                                      restart_after=-1), **BATCH)
    msg = str(exc.value)
    # The stuck range, its holder, and the heartbeat bookkeeping are in
    # the message — plus the exchange-barrier diagnosis for the ranges
    # the merge gate is holding back.
    assert "range 0: held by w0" in msg
    assert "last heartbeat" in msg and "expires t=" in msg
    assert "exchange barrier" in msg
