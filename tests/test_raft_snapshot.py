"""Raft with log compaction (RaftDeviceConfig.snapshot_interval > 0): the
lab 2D deployment of benchmark/configs/raft3snap.json against its plain
reference, the bug flag on planted divergences, the client stream's queue
footprint, and the programs without compaction left as they were."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from madsim_tpu.engine import DeviceEngine, EngineConfig, RaftActor, RaftDeviceConfig
from madsim_tpu.engine.lanes import split_wide
from madsim_tpu.engine.queue import FLAG_FAULT, Event, unpack_meta
from madsim_tpu.engine.raft_actor import K_INSTALL, K_PROPOSE, LEADER

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "benchmark", "configs")
OBS_FIELDS = ("now_us", "active", "steps", "delivered", "dropped",
              "overflow", "qmax", "bug", "bug_time_us", "queue_depth",
              "leader_elected", "first_leader_time_us", "elections_won",
              "max_commit", "max_term")


def _module(name):
    spec = importlib.util.spec_from_file_location(
        f"test_ref_{name}", os.path.join(CONFIGS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


SNAP = _module("raft_snap_reference")
PLAIN = _module("raft_reference")


def _engine(engine, raft):
    return DeviceEngine(RaftActor(RaftDeviceConfig(**raft)),
                        EngineConfig(**engine))


def _faults(w, t_limit, n, seed):
    """The uncrash traffic's schedules: 4 kill/restart windows per world,
    each 50-150 ms on a server drawn per window."""
    rng = np.random.default_rng(seed)
    rows = []
    for lo, hi in ((0.2, 0.35), (0.4, 0.55), (0.6, 0.75), (0.8, 0.9)):
        on = rng.integers(int(t_limit * lo), int(t_limit * hi), w)
        off = on + rng.integers(50_000, 150_000, w)
        node = rng.integers(0, n, w)
        zero = np.zeros(w, np.int64)
        rows += [np.stack([on, zero, node, zero], 1),
                 np.stack([off, zero + 1, node, zero], 1)]
    return np.stack(rows, 1).astype(np.int32)


class _Watched(SNAP.World):
    """The reference, noting whether a crash ever hit the leader."""

    killed_leader = False

    def _fault(self, op, a, b):
        if op == SNAP.KILL and self.s[a].role == SNAP.LEADER:
            self.killed_leader = True
        return super()._fault(op, a, b)


@pytest.mark.parametrize("t_limit_us,w", [(3_000_000, 64), (1_500_000, 256)],
                         ids=["full", "short"])
def test_rows_equal_the_reference(t_limit_us, w):
    cfg = _config("raft3snap")
    engine = dict(cfg["engine"], t_limit_us=t_limit_us)
    raft = cfg["raft"]
    eng = _engine(engine, raft)
    seeds = np.arange(w, dtype=np.uint64) + np.uint64(2 ** 33 + 17)
    faults = _faults(w, t_limit_us, raft["n"], t_limit_us)
    obs = eng.observe(eng.run(eng.init(seeds, faults=faults), 20_000))
    leaders_killed = 0
    for i in range(w):
        ref = _Watched(int(seeds[i]), engine, raft, faults[i].tolist()).run()
        got = {f: int(np.asarray(obs[f][i])) for f in OBS_FIELDS}
        assert got == {k: int(v) for k, v in ref.row().items()}, int(seeds[i])
        assert int(obs["snapshots"][i]) == ref.snapshots
        assert int(obs["installs"][i]) == ref.installs
        leaders_killed += ref.killed_leader
    assert not np.asarray(obs["active"]).any()
    assert not np.asarray(obs["bug"]).any()
    # The set exercises compaction, InstallSnapshot and a crashed leader's
    # restart.
    assert (np.asarray(obs["snapshots"]) > 0).all()
    assert (np.asarray(obs["installs"]) > 0).mean() > 0.5
    assert leaders_killed > 0


def _world(state, i=0):
    return jax.tree.map(lambda x: x[i], state)


def _settled(eng, steps):
    """World 0 of a fault-free run after ``steps`` steps: a leader, and
    two servers whose commit indices pass both their snapshots."""
    state = eng.init(np.arange(1, dtype=np.uint64) + np.uint64(99))
    for _ in range(steps):
        state = eng.step(state)
    s = _world(state).astate
    snap, commit = np.asarray(s.snap_idx), np.asarray(s.commit)
    assert (snap >= 10).all() and int(np.asarray(s.role).max()) == LEADER
    return _world(state), s, snap, commit


@pytest.fixture(scope="module")
def snap_engine():
    cfg = _config("raft3snap")
    return _engine(dict(cfg["engine"], loss_rate=0.0), cfg["raft"])


def test_bug_flag_on_a_committed_entry_altered(snap_engine):
    eng = snap_engine
    ws, s, snap, commit = _settled(eng, 900)
    actor, L = eng.actor, eng.actor.rcfg.log_cap
    assert not bool(actor.invariant(eng.cfg, s))
    i, j = 0, 1
    hi = int(min(commit[i], commit[j]))
    assert hi > max(snap[i], snap[j])
    cmd = s.log_cmd.at[i, (hi - 1) % L].add(1)
    assert bool(actor.invariant(eng.cfg, s._replace(log_cmd=cmd)))


def test_bug_flag_on_a_snapshot_installed_with_a_wrong_digest(snap_engine):
    eng = snap_engine
    ws, s, snap, commit = _settled(eng, 900)
    actor, cfg = eng.actor, eng.cfg
    lead = int(np.argmax(np.asarray(s.role)))
    f = (lead + 1) % 3
    # The follower as a server that lost everything would come back.
    z = lambda x: x.at[f].set(0)  # noqa: E731
    s = s._replace(snap_idx=z(s.snap_idx), snap_term=z(s.snap_term),
                   snap_digest=z(s.snap_digest), log_len=z(s.log_len),
                   commit=z(s.commit), applied_digest=z(s.applied_digest))
    assert not bool(actor.invariant(cfg, s))

    def install(digest):
        lo, hi = split_wide(jax.lax.bitcast_convert_type(
            jnp.uint32(digest), jnp.int32))
        ev = Event.make(time=ws.now, kind=K_INSTALL,
                        payload_words=cfg.payload_words, src=lead, dst=f,
                        payload=[int(s.term[lead]), lead, int(snap[lead]),
                                 int(s.snap_term[lead]), int(lo), int(hi),
                                 int(commit[lead])])
        s2, _, _, _ = actor.handle(cfg, s, ev, ws.now, ws.rng)
        assert int(s2.snap_idx[f]) == int(snap[lead])
        return bool(actor.invariant(cfg, s2))

    right = int(np.asarray(s.snap_digest)[lead])
    assert not install(right)
    assert install(right ^ 1)


def test_client_stream_holds_at_most_one_event_per_server():
    cfg = _config("raft3snap")
    eng = _engine(cfg["engine"], cfg["raft"])
    n, w = cfg["raft"]["n"], 32
    state = eng.init(np.arange(w), faults=_faults(w, 3_000_000, n, 5))

    def most(q):
        kind, flags, *_ = unpack_meta(q.meta)
        client = (q.time != np.iinfo(np.int32).max) & (kind == K_PROPOSE) \
            & ((flags & FLAG_FAULT) == 0)
        return jnp.max(jnp.sum(client, axis=-1))

    def body(c, _):
        s, m = c
        s = eng._batched_step(s)
        return (s, jnp.maximum(m, most(s.queue))), None

    (state, peak), _ = jax.jit(lambda s: jax.lax.scan(
        body, (s, most(s.queue)), None, length=4_000))(state)
    assert not np.asarray(state.active).any()
    assert int(peak) == n
    assert int(np.asarray(eng.observe(state)["max_commit"]).min()) > 100


def _leaves(tree):
    return [(tuple(x.shape), str(x.dtype)) for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("name", ["raft3", "raft5"])
def test_without_compaction_the_program_is_unchanged(name):
    """snapshot_interval 0: the state carries the same leaves, init queues
    the same events, the observation has the same keys, and the rows
    still equal the plain reference's (with raft5's crash and clog)."""
    cfg = _config(name)
    engine, raft = cfg["engine"], cfg["raft"]
    assert RaftDeviceConfig(**raft).snapshot_interval == 0
    eng = _engine(engine, raft)
    n, L = raft["n"], raft["log_cap"]
    state = eng.init(np.arange(8))
    s = _world(state).astate
    want = [((n,), "int16"), ((n,), "int8"), ((n,), "int8"), ((n,), "int32"),
            ((n,), "int16"), ((n,), "int16"), ((n, L), "int16"),
            ((n, L), "int16"), ((n, n), "int16"), ((n, n), "int16"),
            ((n,), "int16"), ((), "int32"), ((), "int32"),
            ((n, 4), "int32")]
    assert _leaves(s) == want
    assert eng.actor.num_kinds == 7 and len(eng.actor.kind_names) == 7
    events = eng.actor.init(eng.cfg, jax.tree.map(lambda x: x[0],
                                                  state.rng))[1]
    kinds = [int(e.kind) for e in events]
    assert kinds == [0] * n + [K_PROPOSE] * (n * raft["n_proposals"])
    assert all(int(e.flags) == 0 for e in events[n:])
    faults = None
    if name == "raft5":
        faults = np.array([[1_200_000, 0, 1, 0], [1_500_000, 1, 1, 0],
                           [1_300_000, 4, 2, 3], [1_700_000, 5, 2, 3]],
                          np.int32)
    seeds = np.arange(16, dtype=np.uint64) + np.uint64(2 ** 40)
    obs = eng.observe(eng.run(eng.init(seeds, faults=faults), 20_000))
    assert set(obs) >= set(OBS_FIELDS)
    assert "snapshots" not in obs and "installs" not in obs
    for i in range(len(seeds)):
        ref = PLAIN.reference_row(int(seeds[i]), engine, raft,
                                  [] if faults is None else faults.tolist())
        assert {f: int(np.asarray(obs[f][i])) for f in OBS_FIELDS} == \
            {k: int(v) for k, v in ref.items()}


def test_compaction_needs_its_shapes():
    with pytest.raises(ValueError):
        RaftActor(RaftDeviceConfig(log_cap=24, snapshot_interval=10))
    with pytest.raises(ValueError):
        RaftActor(RaftDeviceConfig(log_cap=8, snapshot_interval=10))
    eng = DeviceEngine(RaftActor(RaftDeviceConfig(snapshot_interval=10,
                                                  log_cap=16)),
                       EngineConfig(n_nodes=3, outbox_cap=4))
    with pytest.raises(ValueError, match="n \\+ 2"):
        eng.init(np.arange(2))


def test_control_breaks_the_reference():
    """The configuration's control (a restart that loses the snapshot and
    log) changes some of the reference's rows."""
    cfg = _config("raft3snap")
    engine = dict(cfg["engine"], t_limit_us=1_500_000)
    raft = cfg["raft"]
    faults = _faults(16, 1_500_000, 3, 3)
    differ = 0
    for i in range(16):
        a = SNAP.reference_row(1000 + i, engine, raft, faults[i].tolist())
        b = SNAP.reference_row(1000 + i, engine, raft, faults[i].tolist(),
                               control=cfg["control"])
        differ += a != b
    assert differ > 0
    with pytest.raises(ValueError):
        SNAP.World(1, engine, raft, control="no_such")
