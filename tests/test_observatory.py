"""Sweep observatory (docs/observability.md "The sweep observatory"):
live telemetry stream, Prometheus snapshots, profiler capture windows,
and the `watch` CLI.

The load-bearing contracts: telemetry/profiling are host-side
observation only (observe-on and profile-on sweeps are bitwise
identical to plain ones), and the telemetry stream adds ZERO device→host
syncs — every record is built from the scalar batch the loop fetched
anyway (counted via the sweep module's ``_fetch`` hook, exactly like
tests/test_sweep_pipeline.py's sync-discipline test).
"""
import dataclasses
import importlib
import io
import json
import os

import numpy as np
import pytest

sweep_mod = importlib.import_module("madsim_tpu.parallel.sweep")
from madsim_tpu.engine import (
    DeviceEngine,
    EngineConfig,
    FAULT_KILL,
    FAULT_RESTART,
    RaftActor,
    RaftDeviceConfig,
)
from madsim_tpu.obs import observatory
from madsim_tpu.obs.cli import main as obs_main
from madsim_tpu.parallel.sweep import sweep

RAFT_FAULTS = np.array([[300_000, FAULT_KILL, 0, 0],
                        [700_000, FAULT_RESTART, 0, 0]], np.int32)

# The documented progress-record schema (docs/observability.md).
TELEMETRY_KEYS = {
    "schema", "elapsed_s", "chunks", "steps", "batch_worlds", "n_active",
    "occupancy", "seeds_total", "seeds_admitted", "seeds_done",
    "seeds_per_s", "world_utilization", "dispatch_depth", "bug_seen",
    "eta_s",
}


@pytest.fixture(scope="module")
def eng_on():
    rcfg = RaftDeviceConfig(n=3, n_proposals=2, buggy_double_vote=True)
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                      t_limit_us=1_500_000, metrics=True)
    return DeviceEngine(RaftActor(rcfg), cfg)


@pytest.fixture(scope="module")
def eng_off():
    rcfg = RaftDeviceConfig(n=3, n_proposals=2, buggy_double_vote=True)
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                      t_limit_us=1_500_000)
    return DeviceEngine(RaftActor(rcfg), cfg)


# ---------------------------------------------------------------------------
# Tier-1: telemetry schema on both orchestration paths (the
# test_loop_stats_schema_both_paths sibling for the observatory layer)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pipeline", [True, False])
def test_telemetry_schema_both_paths(eng_on, pipeline):
    records = []
    res = sweep(None, eng_on.cfg, np.arange(24), engine=eng_on,
                chunk_steps=64, max_steps=2_048, faults=RAFT_FAULTS,
                pipeline=pipeline, observe=records.append)
    progress = [r for r in records if r.get("event") != "summary"]
    summary = [r for r in records if r.get("event") == "summary"]
    # One progress record per host read, plus exactly one summary.
    assert len(progress) == res.loop_stats["scalar_fetches"]
    assert len(summary) == 1
    for rec in progress:
        assert TELEMETRY_KEYS <= set(rec), sorted(rec)
        assert rec["schema"] == "madsim.sweep.telemetry/1"
        assert isinstance(rec["elapsed_s"], float) and rec["elapsed_s"] >= 0
        for key in ("chunks", "steps", "batch_worlds", "n_active",
                    "seeds_total", "seeds_admitted", "seeds_done",
                    "dispatch_depth"):
            assert isinstance(rec[key], int) and rec[key] >= 0, key
        assert 0.0 <= rec["occupancy"] <= 1.0
        assert rec["seeds_done"] <= rec["seeds_total"] == 24
        assert rec["eta_s"] is None or rec["eta_s"] >= 0.0
        # Coverage riders (metrics engine): distinct count + bucket width.
        assert rec["coverage_buckets"] == 256
        assert 0 <= rec["coverage_distinct"] <= 256
    # elapsed_s is monotonic within the stream (perf_counter-based).
    els = [r["elapsed_s"] for r in progress]
    assert els == sorted(els)
    # Progress coverage_distinct matches the result's novelty curve tail.
    assert progress[-1]["coverage_distinct"] == int(
        res.coverage.novelty_curve[-1])
    s = summary[0]
    assert s["loop_stats"] == res.loop_stats
    assert s["failing_seeds"] == len(res.failing_seeds)
    assert s["coverage"]["distinct_behaviors"] == \
        res.coverage.distinct_behaviors
    json.dumps(records)  # the whole stream is plain JSON


def test_telemetry_adds_zero_fetches_and_is_invisible(eng_on, monkeypatch):
    """Tier-1 sync discipline, observatory edition: with coverage AND a
    telemetry observer on, the loop still performs exactly one scalar
    _fetch per superstep (the novelty lane rides the same batch) plus
    the single final merge pull — and the observed sweep's results are
    bitwise identical to an unobserved one."""
    plain = sweep(None, eng_on.cfg, np.arange(40), engine=eng_on,
                  chunk_steps=64, max_steps=3_000, faults=RAFT_FAULTS)
    calls = []
    real_fetch = sweep_mod._fetch

    def counting_fetch(tree):
        out = real_fetch(tree)
        import jax
        calls.append(sum(np.asarray(x).nbytes
                         for x in jax.tree.leaves(out)))
        return out

    monkeypatch.setattr(sweep_mod, "_fetch", counting_fetch)
    records = []
    res = sweep(None, eng_on.cfg, np.arange(40), engine=eng_on,
                chunk_steps=64, max_steps=3_000, faults=RAFT_FAULTS,
                observe=records.append)
    st = res.loop_stats
    assert len(calls) == st["scalar_fetches"] + 1  # + final merge pull
    # Steady-state pulls stay a few hundred bytes even with the novelty
    # lane aboard — never a per-world array.
    assert max(calls[:-1]) <= 320, calls
    assert len(records) == st["scalar_fetches"] + 1  # + summary record
    for k, v in plain.observations.items():
        np.testing.assert_array_equal(v, res.observations[k], err_msg=k)
    np.testing.assert_array_equal(plain.coverage.hits, res.coverage.hits)


# ---------------------------------------------------------------------------
# Emitters: JSONL stream, watch CLI, Prometheus snapshots
# ---------------------------------------------------------------------------

def test_jsonl_stream_watch_cli_and_prometheus(eng_on, tmp_path, capsys):
    stream = str(tmp_path / "tele.jsonl")
    res = sweep(None, eng_on.cfg, np.arange(24), engine=eng_on,
                chunk_steps=64, max_steps=3_000, faults=RAFT_FAULTS,
                observe=stream)
    lines = [json.loads(ln) for ln in open(stream)]
    assert lines[-1]["event"] == "summary"
    assert len(lines) == res.loop_stats["scalar_fetches"] + 1

    # Summary mode of the CLI.
    prom = str(tmp_path / "snap.prom")
    rc = obs_main(["watch", stream, "--prom", prom])
    out = capsys.readouterr().out
    assert rc == 0
    assert "distinct behaviors" in out and "failing" in out
    text = open(prom).read()
    assert "# TYPE madsim_sweep_elapsed_s gauge" in text
    assert f"madsim_sweep_seeds_total {24}" in text

    # Follow mode over a completed stream: tails every record, prints
    # the summary, and returns without blocking.
    buf = io.StringIO()
    rc = observatory.watch(stream, follow=True, interval=0.01, out=buf)
    assert rc == 0
    tail = buf.getvalue()
    assert tail.count("chunks=") >= res.loop_stats["scalar_fetches"]
    assert "behaviors=" in tail

    # Missing file → usage-style exit.
    assert observatory.watch(str(tmp_path / "nope.jsonl")) == 2


def test_watch_renders_exchange_records_interleaved(tmp_path):
    """The `madsim.fleet.exchange/1` schema (PR 12): `watch --follow`
    renders exchange events interleaved with the sweep and fleet
    schemas, and the summary mode rolls them up — round-tripped through
    a real JSONL stream."""
    stream = str(tmp_path / "mixed.jsonl")
    records = [
        {"schema": "madsim.sweep.telemetry/1", "elapsed_s": 0.5,
         "chunks": 3, "n_active": 8, "batch_worlds": 16,
         "seeds_total": 32, "seeds_done": 4, "seeds_per_s": 8.0},
        {"schema": "madsim.fleet.telemetry/1", "event": "lease_issued",
         "t": 1, "worker": "w0", "range_id": 0, "lease_id": 0,
         "generation": 0},
        {"schema": "madsim.fleet.exchange/1", "event": "publish", "t": 2,
         "worker": "w0", "range_id": 0, "epoch": 0, "bytes": 3360,
         "duplicate": False, "corpus_size": 2},
        {"schema": "madsim.fleet.exchange/1", "event": "merge", "t": 3,
         "epoch": 0, "ranges_merged": 2, "corpus_inserted": 5,
         "corpus_size": 6, "corpus_gen": 1, "epochs_merged": 1},
        {"schema": "madsim.fleet.exchange/1", "event": "broadcast",
         "t": 4, "worker": "w1", "range_id": 2, "epoch": 1,
         "from_epoch": 0, "bytes": 3360},
        {"schema": "madsim.fleet.exchange/1", "event": "publish_torn",
         "t": 5, "worker": "w1", "range_id": 2, "epoch": 1,
         "error": "checksum mismatch"},
        {"schema": "madsim.sweep.telemetry/1", "event": "summary",
         "elapsed_s": 1.0, "seeds_total": 32, "failing_seeds": 0,
         "world_utilization": 0.9, "loop_stats": {"chunks": 6,
                                                  "dispatches": 3}},
    ]
    with open(stream, "w", encoding="utf-8") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")

    # Follow mode: one rendered line per record, all three schemas
    # interleaved in stream order.
    buf = io.StringIO()
    assert observatory.watch(stream, follow=True, interval=0.01,
                             out=buf) == 0
    tail = buf.getvalue()
    assert "[exchange]" in tail
    assert "publish" in tail and "merge" in tail and "broadcast" in tail
    assert "epoch=0" in tail and "ranges_merged=2" in tail
    assert "corpus_inserted=5" in tail and "corpus_gen=1" in tail
    assert "bytes=3360" in tail
    assert "publish_torn" in tail and "error=checksum mismatch" in tail
    assert "[w0]" in tail and "lease_issued" in tail  # fleet schema
    assert "chunks=3" in tail                         # sweep schema

    # Summary mode: the exchange rollup line sits beside the sweep
    # summary.
    buf = io.StringIO()
    assert observatory.watch(stream, out=buf) == 0
    text = buf.getvalue()
    assert "exchange: 1 epoch(s) merged, 5 corpus insert(s)" in text
    assert "1 torn publish(es) discarded" in text
    assert "merged corpus: 6 entries after epoch 0" in text
    assert "final: 0 failing of 32 seeds" in text


def test_exchange_stream_from_real_fleet_run(tmp_path):
    """End-to-end: an exchanged guided fleet writes its telemetry to a
    JSONL sink; the stream carries all three schemas and `watch`
    summarizes it without error."""
    from madsim_tpu.fleet import ExchangeConfig, fleet_sweep
    from madsim_tpu.search import (
        GuidedPairActor,
        GuidedPairConfig,
        engine_config,
        family_schedule,
    )
    from madsim_tpu.search.family import HUNT_NODES, HUNT_ROWS, \
        hunt_search_config

    acfg = GuidedPairConfig(n=HUNT_NODES)
    eng = DeviceEngine(GuidedPairActor(acfg), engine_config(acfg))
    stream = str(tmp_path / "fleet.jsonl")
    fleet_sweep(None, eng.cfg, np.arange(96), engine=eng,
                faults=family_schedule(HUNT_ROWS, acfg), n_workers=2,
                range_size=48, recycle=True, batch_worlds=32,
                chunk_steps=32, max_steps=10_000_000,
                search=hunt_search_config(True),
                exchange=ExchangeConfig(every=1), observe=stream)
    recs = [json.loads(ln) for ln in open(stream) if ln.strip()]
    schemas = {r.get("schema") for r in recs}
    assert "madsim.fleet.exchange/1" in schemas
    assert "madsim.fleet.telemetry/1" in schemas
    ex = [r for r in recs if r.get("schema") == "madsim.fleet.exchange/1"]
    events = {r["event"] for r in ex}
    assert {"publish", "merge", "broadcast"} <= events
    merge = next(r for r in ex if r["event"] == "merge")
    assert {"epoch", "ranges_merged", "corpus_inserted",
            "corpus_size"} <= set(merge)
    pub = next(r for r in ex if r["event"] == "publish")
    assert pub["bytes"] > 0
    buf = io.StringIO()
    assert observatory.watch(stream, out=buf) == 0
    assert "exchange:" in buf.getvalue()


def test_make_observer_contract(tmp_path):
    assert observatory.make_observer(None) == (None, None)
    sink = []
    emit, close = observatory.make_observer(sink.append)
    emit({"x": 1})
    assert sink == [{"x": 1}] and close is None
    with pytest.raises(TypeError, match="observe"):
        observatory.make_observer(42)
    path = tmp_path / "s.jsonl"
    emit, close = observatory.make_observer(str(path))
    emit({"a": True})
    close()
    assert json.loads(path.read_text()) == {"a": True}


def test_prometheus_text_shape():
    text = observatory.prometheus_text(
        {"seeds_per_s": 12.5, "bug_seen": True, "note": "skip-me",
         "eta_s": None, "loop_stats": {"nested": 1}})
    assert "madsim_sweep_seeds_per_s 12.5" in text
    assert "madsim_sweep_bug_seen 1" in text
    assert "note" not in text and "nested" not in text


# ---------------------------------------------------------------------------
# Profiler capture window
# ---------------------------------------------------------------------------

def test_profile_dir_captures_and_stays_invisible(eng_off, tmp_path):
    """sweep(profile_dir=...) lands a device-timeline capture under the
    directory and changes nothing about the results (bitwise) or the
    dispatch schedule."""
    plain = sweep(None, eng_off.cfg, np.arange(24), engine=eng_off,
                  chunk_steps=64, max_steps=2_048)
    pdir = str(tmp_path / "prof")
    prof = sweep(None, eng_off.cfg, np.arange(24), engine=eng_off,
                 chunk_steps=64, max_steps=2_048, profile_dir=pdir,
                 profile_window=(0, 2))
    files = [os.path.join(r, fn) for r, _d, fns in os.walk(pdir)
             for fn in fns]
    assert files, "profiler window captured nothing"
    for k, v in plain.observations.items():
        np.testing.assert_array_equal(v, prof.observations[k], err_msg=k)
    assert plain.loop_stats["dispatches"] == prof.loop_stats["dispatches"]


# The host spans of one call (docs/observability.md "Loop spans"): the
# phases every sweep() loop has, plus the dispatch span each loop names.
_PHASES = {"madsim:prepare", "madsim:init", "madsim:wait", "madsim:decide",
           "madsim:pull", "madsim:assemble"}
_LOOPS = {
    "serial": ({"pipeline": False}, {"madsim:upload", "madsim:chunk"}),
    "pipelined": ({}, {"madsim:upload", "madsim:superstep"}),
    "fused": ({"fused": True, "recycle": True, "batch_worlds": 8},
              {"madsim:upload", "madsim:fused_hunt"}),
    # SweepSession.run_group installs a standing batch: no upload phase.
    "run_group": (None, {"madsim:superstep"}),
}
_NEW_SECONDS = ("prepare_s", "init_s", "upload_s", "assemble_s")


def _madsim_spans(trace_dir):
    """(line, start_ns, end_ns, name) of every ``madsim:*`` host span in
    the capture under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(line.name, int(e.start_ns),
                     int(e.start_ns + e.duration_ns), e.name)
                    for e in line.events if e.name.startswith("madsim:")]
    return out


@pytest.mark.parametrize("loop", sorted(_LOOPS))
def test_loop_spans_nest_in_the_call_on_the_host_plane(eng_off, tmp_path,
                                                       loop):
    """Every phase span of one call is on the host plane of a capture
    started outside the sweep, inside that call's ``madsim:sweep`` span,
    on the calling thread; sibling spans do not overlap; each loop_stats
    seconds key they feed is a non-negative float."""
    import jax

    from madsim_tpu.parallel.sweep import SweepSession

    kw, own = _LOOPS[loop]
    pdir = str(tmp_path / "trace")
    with jax.profiler.trace(pdir):
        if kw is None:
            sess = SweepSession(engine=eng_off, chunk_steps=64,
                                max_steps=2_048)
            results = sess.run_group([{"seeds": np.arange(8)},
                                      {"seeds": np.arange(8, 20)}])
        else:
            results = [sweep(None, eng_off.cfg, np.arange(24),
                             engine=eng_off, chunk_steps=64,
                             max_steps=2_048, **kw)]
    spans = _madsim_spans(pdir)
    roots = [s for s in spans if s[3] == "madsim:sweep"]
    assert len(roots) == 1, roots
    line, lo, hi, _ = roots[0]
    children = sorted(s for s in spans if s[3] != "madsim:sweep")
    assert {s[3] for s in children} == _PHASES | own
    for s in children:
        assert s[0] == line and lo <= s[1] <= s[2] <= hi, s
    for a, b in zip(children, children[1:]):
        assert a[2] <= b[1], (a, b)
    for res in results:
        ls = res.loop_stats
        for key in _NEW_SECONDS + ("dispatch_s", "device_wait_s",
                                   "host_decision_s", "retire_wait_s"):
            assert isinstance(ls[key], float) and ls[key] >= 0.0, key


def test_profile_window_validation(eng_off, tmp_path):
    with pytest.raises(ValueError, match="profile_window"):
        sweep(None, eng_off.cfg, np.arange(8), engine=eng_off,
              chunk_steps=64, max_steps=256,
              profile_dir=str(tmp_path / "p"), profile_window=(3, 3))
    # window is ignored entirely when no profile_dir is given.
    observatory.ProfilerWindow(None, (9, 9)).before_dispatch()


def test_watch_renders_fused_search_cadence():
    """Fused-hunt search telemetry (docs/observability.md "Fused-sweep
    cadence"): records labeled with ``epochs_on_device`` render as
    explicit per-mega-dispatch rollups, and the summary rollup notes
    ``fused=true`` — while unlabeled (host-refill) records keep the
    per-refill rendering."""
    fused_rec = {"schema": "madsim.search.telemetry/1", "event": "refill",
                 "elapsed_s": 1.25, "generation": 3, "corpus_size": 17,
                 "corpus_inserted": 16, "refill_novel": 2,
                 "refill_inserted": 2, "epochs_on_device": 5}
    host_rec = {k: v for k, v in fused_rec.items()
                if k != "epochs_on_device"}
    line = observatory.render_search_event(fused_rec)
    assert "epochs_on_device=5 (per-mega-dispatch rollup)" in line
    assert "epochs_on_device" not in \
        observatory.render_search_event(host_rec)
    rollup = "\n".join(observatory.render_search_summary([fused_rec]))
    assert "fused=true" in rollup and "mega-dispatch rollup" in rollup
    assert "5 refill epoch(s) ran on device" in rollup
    host_rollup = "\n".join(observatory.render_search_summary([host_rec]))
    assert "fused" not in host_rollup and "refill(s)" in host_rollup
