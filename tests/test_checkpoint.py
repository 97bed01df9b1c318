"""Checkpoint/resume: a split run must be bit-identical to an unbroken one.

The crosscheck-style assertion VERDICT r2 item 9 specifies: save mid-run,
reload (fresh engine object — nothing shared), continue, compare every
state leaf bitwise against a run that never stopped.
"""
import jax
import numpy as np
import pytest

from madsim_tpu.engine import (
    DeviceEngine, EngineConfig, RaftActor, RaftDeviceConfig,
    CheckpointError, load_checkpoint, save_checkpoint,
)

RCFG = RaftDeviceConfig(n=3, n_proposals=2)
ECFG = EngineConfig(n_nodes=3, outbox_cap=4, t_limit_us=2_000_000)


def _leaves_equal(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_split_run_bit_identical(tmp_path):
    path = tmp_path / "ckpt.npz"
    eng = DeviceEngine(RaftActor(RCFG), ECFG)

    unbroken = eng.run_steps(eng.init(np.arange(16)), 800)

    half = eng.run_steps(eng.init(np.arange(16)), 400)
    save_checkpoint(eng, half, path)
    # Fresh engine object: nothing survives but the file.
    eng2 = DeviceEngine(RaftActor(RCFG), ECFG)
    resumed = load_checkpoint(eng2, path)
    assert _leaves_equal(half, resumed), "load must restore state bitwise"
    finished = eng2.run_steps(resumed, 400)
    assert _leaves_equal(unbroken, finished), \
        "a split run must be bit-identical to an unbroken run"


def test_checkpoint_rejects_wrong_config(tmp_path):
    path = tmp_path / "ckpt.npz"
    eng = DeviceEngine(RaftActor(RCFG), ECFG)
    save_checkpoint(eng, eng.init(np.arange(4)), path)
    other = DeviceEngine(
        RaftActor(RaftDeviceConfig(n=5, log_cap=16)),
        EngineConfig(n_nodes=5, outbox_cap=6))
    with pytest.raises(CheckpointError, match="different engine config"):
        load_checkpoint(other, path)
    # Same EngineConfig but different ACTOR config must also be rejected
    # (same shapes — only the fingerprint can catch it).
    tweaked = DeviceEngine(
        RaftActor(RaftDeviceConfig(n=3, n_proposals=2, heartbeat_us=10_000)),
        ECFG)
    with pytest.raises(CheckpointError, match="different engine config"):
        load_checkpoint(tweaked, path)


def test_sweep_resume_rejects_different_seeds(tmp_path):
    from madsim_tpu.parallel.sweep import sweep

    path = str(tmp_path / "sweep.npz")
    eng = DeviceEngine(RaftActor(RCFG), ECFG)
    sweep(None, ECFG, np.arange(100, 124), engine=eng, chunk_steps=64,
          max_steps=64, checkpoint_path=path)
    with pytest.raises(CheckpointError, match="seeds_sha256"):
        sweep(None, ECFG, np.arange(24), engine=eng, chunk_steps=64,
              max_steps=64, checkpoint_path=path, resume=True)


def test_sweep_resume_rejects_wrong_world_count(tmp_path):
    """Defense-in-depth behind the seeds-hash gate: a checkpoint whose
    metadata matches but whose state holds a different world count must
    raise CheckpointError, not shard a mis-shaped batch. (Reachable only
    via a forged/corrupted checkpoint — the seeds hash normally pins the
    padded width — so the file is forged here.)"""
    import hashlib

    from madsim_tpu.parallel.sweep import sweep

    path = str(tmp_path / "sweep.npz")
    seeds = np.arange(24)
    eng = DeviceEngine(RaftActor(RCFG), ECFG)
    # Metadata for the 24-seed sweep, wrapped around a 16-world state.
    meta = {
        "seeds_sha256": hashlib.sha256(
            seeds.astype(np.uint64).tobytes()).hexdigest(),
        "faults_sha256": hashlib.sha256(b"none").hexdigest(),
    }
    save_checkpoint(eng, eng.init(np.arange(16)), path, extra_meta=meta)
    with pytest.raises(CheckpointError, match="16 worlds"):
        sweep(None, ECFG, seeds, engine=eng, chunk_steps=64,
              max_steps=64, checkpoint_path=path, resume=True)


@pytest.fixture(scope="module")
def heng():
    """One shared engine for the hardening tests below: they exercise
    file-level behavior (fsync ordering, torn files, aux arrays), so a
    single compiled engine + one batch shape keeps them cheap."""
    return DeviceEngine(RaftActor(RCFG), ECFG)


def test_crash_between_write_and_rename_keeps_previous(tmp_path,
                                                       monkeypatch, heng):
    """A writer dying between the tmp write and the rename must leave
    the PREVIOUS checkpoint intact and loadable — the atomic-replace
    contract under the exact crash the fsync+rename dance exists for."""
    from madsim_tpu.engine import checkpoint as ckpt_mod

    path = tmp_path / "ckpt.npz"
    eng = heng
    half = eng.run_steps(eng.init(np.arange(8)), 200)
    save_checkpoint(eng, half, path)

    # A different state for the crashing re-save. Built from a fresh
    # init, NOT by stepping ``half``: run_steps donates its input, and
    # on the CPU backend host views of donated buffers can alias the
    # memory XLA then overwrites — ``half`` must stay alive untouched
    # for the comparison below.
    later = eng.run_steps(eng.init(np.arange(8)), 400)

    def dying_replace(src, dst):
        raise OSError("simulated crash between write and rename")

    monkeypatch.setattr(ckpt_mod.os, "replace", dying_replace)
    with pytest.raises(OSError, match="simulated crash"):
        save_checkpoint(eng, later, path)
    monkeypatch.undo()

    # The published path still holds the FIRST snapshot, bit-intact, and
    # resume proceeds from it to the same place an unbroken run reaches.
    recovered = load_checkpoint(eng, path)
    assert _leaves_equal(half, recovered), \
        "a crashed re-save must not touch the previous checkpoint"
    assert _leaves_equal(later, eng.run_steps(recovered, 200))


def test_save_fsyncs_before_rename(tmp_path, monkeypatch, heng):
    """Durability ordering: the tmp file's bytes must be fsync'd BEFORE
    os.replace publishes the name (without it, a machine crash can
    publish a name pointing at unflushed, torn bytes)."""
    from madsim_tpu.engine import checkpoint as ckpt_mod

    order = []
    real_fsync, real_replace = ckpt_mod.os.fsync, ckpt_mod.os.replace
    monkeypatch.setattr(ckpt_mod.os, "fsync",
                        lambda fd: (order.append("fsync"), real_fsync(fd)))
    monkeypatch.setattr(
        ckpt_mod.os, "replace",
        lambda a, b: (order.append("replace"), real_replace(a, b)))
    save_checkpoint(heng, heng.init(np.arange(8)), tmp_path / "c.npz")
    assert "fsync" in order and "replace" in order
    assert order.index("fsync") < order.index("replace")


def test_corrupt_checkpoint_reports_path_and_recovery(tmp_path, heng):
    """Truncated and garbage files raise CheckpointError naming the file
    and the recovery options — never a bare zipfile/numpy internal."""
    path = tmp_path / "ckpt.npz"
    eng = heng
    save_checkpoint(eng, eng.init(np.arange(8)), path)
    good = path.read_bytes()

    # Truncation (torn write) and garbage (disk corruption).
    for bad in (good[:137], b"not an npz at all"):
        path.write_bytes(bad)
        with pytest.raises(CheckpointError) as exc_info:
            load_checkpoint(eng, path)
        msg = str(exc_info.value)
        assert str(path) in msg, "must name the corrupt file"
        assert "recovery options" in msg
        assert "zipfile" not in msg.lower().replace("badzipfile", "")


def test_sweep_resume_on_corrupt_checkpoint_reports(tmp_path, heng):
    """resume=True over a corrupt file surfaces the same actionable
    CheckpointError (path + recovery options) through the sweep."""
    from madsim_tpu.parallel.sweep import sweep

    path = tmp_path / "sweep.npz"
    eng = heng
    sweep(None, ECFG, np.arange(8), engine=eng, chunk_steps=64,
          max_steps=64, checkpoint_path=str(path))
    path.write_bytes(path.read_bytes()[:100])
    with pytest.raises(CheckpointError, match="recovery options"):
        sweep(None, ECFG, np.arange(8), engine=eng, chunk_steps=64,
              max_steps=64, checkpoint_path=str(path), resume=True)


def test_checkpoint_extra_arrays_round_trip(tmp_path, heng):
    """save(extra_arrays=...) / load(with_aux=True): named host arrays
    ride beside the state leaves (the recycled sweep's cursor/index/
    retired-observation carrier); plain loads ignore them."""
    path = tmp_path / "aux.npz"
    eng = heng
    state = eng.init(np.arange(8))
    aux_in = {"cursor": np.int64(17),
              "idx": np.arange(8, dtype=np.int32),
              "ret_steps": np.asarray([5, 9], np.int32)}
    save_checkpoint(eng, state, path, extra_arrays=aux_in)
    loaded, aux = load_checkpoint(eng, path, with_aux=True)
    assert _leaves_equal(state, loaded)
    assert set(aux) == set(aux_in)
    for k in aux_in:
        np.testing.assert_array_equal(aux[k], aux_in[k])
    # Backward-shaped call: aux invisible unless asked for.
    assert _leaves_equal(state, load_checkpoint(eng, path))


def test_sweep_resumes_from_checkpoint(tmp_path):
    from madsim_tpu.parallel.sweep import sweep

    path = str(tmp_path / "sweep.npz")
    seeds = np.arange(24)
    eng = DeviceEngine(RaftActor(RCFG), ECFG)
    full = sweep(None, ECFG, seeds, engine=eng, chunk_steps=128,
                 max_steps=4_000)

    # Interrupted sweep: only 2 chunks, checkpointing as it goes.
    eng2 = DeviceEngine(RaftActor(RCFG), ECFG)
    partial = sweep(None, ECFG, seeds, engine=eng2, chunk_steps=128,
                    max_steps=256, checkpoint_path=path,
                    checkpoint_every_chunks=1)
    assert partial.steps_run == 256
    # "Process restart": new engine, resume from disk, run to completion.
    eng3 = DeviceEngine(RaftActor(RCFG), ECFG)
    resumed = sweep(None, ECFG, seeds, engine=eng3, chunk_steps=128,
                    max_steps=4_000, checkpoint_path=path, resume=True)

    for key in full.observations:
        assert np.array_equal(full.observations[key],
                              resumed.observations[key]), key
    assert np.array_equal(full.bug, resumed.bug)


# ---------------------------------------------------------------------------
# Sweep identity: what is hashed, and only when a checkpoint reads it
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def seng():
    return DeviceEngine(RaftActor(RCFG), ECFG)


def _record_sha256(monkeypatch):
    """Patch ``hashlib.sha256`` to record the bytes of every call; returns
    the record and the real constructor."""
    import hashlib

    real = hashlib.sha256
    calls = []

    def recording(data=b"", **kw):
        calls.append(bytes(data))
        return real(data, **kw)

    monkeypatch.setattr(hashlib, "sha256", recording)
    return calls, real


@pytest.mark.parametrize("loop", ["serial", "pipelined", "fused",
                                  "checkpoint"])
def test_sweep_hashes_seeds_only_for_a_checkpoint(seng, tmp_path,
                                                  monkeypatch, loop):
    """Only a checkpoint reads ``seeds_sha256``, so a sweep without one
    never hashes its seed array (no sha256 call over more than 64 bytes);
    a checkpointed sweep hashes it once, in ``madsim:identity``, and
    writes the same identity as before."""
    from madsim_tpu.engine.checkpoint import read_meta
    from madsim_tpu.parallel.sweep import sweep

    path = str(tmp_path / "sweep.npz")
    kw = {"serial": {"pipeline": False}, "pipelined": {},
          "fused": {"fused": True},
          "checkpoint": {"checkpoint_path": path}}[loop]
    seeds = np.arange(100, 124, dtype=np.uint64)

    def run():
        return sweep(None, ECFG, seeds, engine=seng, chunk_steps=64,
                     max_steps=64, **kw)

    run()                      # compile outside the record
    calls, real = _record_sha256(monkeypatch)
    res = run()
    if loop == "checkpoint":
        assert calls.count(seeds.tobytes()) == 1
        assert res.loop_stats["identity_hashes"] == 1
        extra = read_meta(path)["extra"]
        assert extra == {
            "seeds_sha256": real(seeds.tobytes()).hexdigest(),
            "faults_sha256": real(b"none").hexdigest()}
    else:
        assert [len(c) for c in calls if len(c) > 64] == []
        assert res.loop_stats["identity_hashes"] == 0
        assert res.loop_stats["identity_s"] == 0.0


@pytest.mark.parametrize("schedule", ["shared", "per_world", "none"])
def test_faults_sha256_is_the_padded_rows_hash(seng, tmp_path, schedule):
    """``faults_sha256`` (on the result and in the checkpoint) is sha256
    over the int32 rows, per-world ones padded to the mesh-rounded seed
    count with repeats of row 0, and of ``b"none"`` without faults."""
    import hashlib

    from madsim_tpu.engine.checkpoint import read_meta
    from madsim_tpu.parallel.mesh import seed_mesh
    from madsim_tpu.parallel.sweep import sweep

    n = 20
    pad = (-n) % seed_mesh().devices.size
    rows = np.array([[200_000, 0, 1, 0], [600_000, 1, 1, 0]], np.int32)
    if schedule == "shared":
        faults, key = rows, rows.tobytes()
    elif schedule == "per_world":
        faults = rows + np.arange(n, dtype=np.int32)[:, None, None] * \
            np.array([1_000, 0, 0, 0], np.int32)
        key = np.concatenate([faults, faults[:1].repeat(pad, axis=0)],
                             axis=0).tobytes()
    else:
        faults, key = None, b"none"
    path = str(tmp_path / "sweep.npz")
    res = sweep(None, ECFG, np.arange(n), faults=faults, engine=seng,
                chunk_steps=64, max_steps=64, checkpoint_path=path)
    want = hashlib.sha256(key).hexdigest()
    assert read_meta(path)["extra"]["faults_sha256"] == want
    assert res.faults_sha256 == (None if faults is None else want)
