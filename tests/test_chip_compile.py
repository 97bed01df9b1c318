"""The chip's compiler on the main path, without the chip.

Compiles chip_smoke.py's programs for a described TPU v5e
(jax.experimental.topologies): what the compiler refuses here would
otherwise cost a chip run to find. The topology is described only inside
the module fixture — never at import, in a skipif, in parametrize or in
conftest.py — because one process at a time may load libtpu, and xdist
workers must all collect the same tests. The persistent compilation
cache is off around these compiles: an executable for a described device
is written to it but cannot be read back without the chip. The fixture
also steers ``lanes.gathers_are_cheap`` to the TPU's answer: traced
here, the engine would otherwise see the CPU backend and compile its
gather form.

Also the CPU checks that need no topology: chip_smoke.py refuses to run
without a TPU, and its phase B passes its checks on the CPU at a small
width.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

HBM_BYTES = 16 * 2**30     # one v5e chip
# Cost gate of the form the chip runs (the budget ledger compiles the
# CPU's): XLA's v5e cost model of phase A's step per world, measured at
# 6,228 flops and 15,312 bytes (PR 21), with the ledger's 1.15 headroom.
# The gather form this replaced was ~5.5 MB per world.
V5E_STEP_FLOPS_PER_WORLD = 7_163
V5E_STEP_BYTES_PER_WORLD = 17_609
# The same step traced without its fault path (DeviceEngine._form), the
# form a sweep without fault rows runs: sized at 4,990 flops and 13,564
# bytes per world, with the same 1.15 headroom.
V5E_FREE_STEP_FLOPS_PER_WORLD = 5_738
V5E_FREE_STEP_BYTES_PER_WORLD = 15_598


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from madsim_tpu.engine import lanes

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(lanes, "gathers_are_cheap", lambda: False)
        yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _mesh(devices):
    from madsim_tpu.parallel import seed_mesh

    return seed_mesh(devices=list(devices))


def _state_shapes(eng, w, sharding):
    """WorldState leaves as shapes at ``w`` worlds on ``sharding``: the
    layout comes from a tiny CPU init (no device can hold the real one
    here)."""
    import jax

    small = eng.init(np.arange(8))
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((w,) + x.shape[1:], x.dtype,
                                       sharding=sharding), small)


def test_headline_step_fits_one_chip(topo):
    """Phase A's step program at W=524,288 compiles for one v5e, its
    arguments plus temporaries fit the chip's 16 GiB, and it holds no
    gather or scatter (each costs ~0.5 MB per world on the TPU), and its
    cost model stays under the v5e step budget."""
    import jax

    from madsim_tpu.parallel.mesh import world_sharding

    eng = chip_smoke.headline_engine()
    mesh = _mesh(topo.devices[:1])
    state = _state_shapes(eng, chip_smoke.HEADLINE_W, world_sharding(mesh))
    comp = jax.jit(eng._batched_step, donate_argnums=0).lower(
        state).compile()
    ma = comp.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert 0 < used < HBM_BYTES, used
    hlo = comp.as_text()
    assert " gather(" not in hlo and " scatter(" not in hlo
    ca = comp.cost_analysis()
    w = chip_smoke.HEADLINE_W
    assert ca["flops"] / w <= V5E_STEP_FLOPS_PER_WORLD, ca["flops"] / w
    assert ca["bytes accessed"] / w <= V5E_STEP_BYTES_PER_WORLD, \
        ca["bytes accessed"] / w


def test_headline_step_without_fault_path_fits_one_chip(topo):
    """Phase A's step as a sweep without fault rows runs it, traced
    without its fault path: it compiles for one v5e with no gather,
    scatter or ``madsim/fault`` op, fits the chip, and its cost model
    stays under the fault-free step budget, so the saving cannot quietly
    come back."""
    import jax

    from madsim_tpu.parallel.mesh import world_sharding

    eng = chip_smoke.headline_engine()._form(False)
    mesh = _mesh(topo.devices[:1])
    state = _state_shapes(eng, chip_smoke.HEADLINE_W, world_sharding(mesh))
    comp = jax.jit(eng._batched_step, donate_argnums=0).lower(
        state).compile()
    ma = comp.memory_analysis()
    assert 0 < ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES
    hlo = comp.as_text()
    assert " gather(" not in hlo and " scatter(" not in hlo
    assert "madsim/fault" not in hlo and "madsim/pop" in hlo
    ca = comp.cost_analysis()
    w = chip_smoke.HEADLINE_W
    assert ca["flops"] / w <= V5E_FREE_STEP_FLOPS_PER_WORLD, ca["flops"] / w
    assert ca["bytes accessed"] / w <= V5E_FREE_STEP_BYTES_PER_WORLD, \
        ca["bytes accessed"] / w


def test_headline_superstep_compiles_for_one_chip(topo):
    """The default sweep's superstep (512-step chunks, so the chunk loop
    is blocks of 16 steps that stop once the shard has frozen) at
    W=524,288 compiles for one v5e, updates the state in place and fits
    the chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from madsim_tpu.parallel.mesh import scalar_spec, world_sharding
    from madsim_tpu.parallel.sweep import sharded_superstep

    eng = chip_smoke.headline_engine()
    mesh = _mesh(topo.devices[:1])
    state = _state_shapes(eng, chip_smoke.HEADLINE_W, world_sharding(mesh))
    rep = NamedSharding(mesh, scalar_spec())
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    flag = jax.ShapeDtypeStruct((), jnp.bool_, sharding=rep)
    comp = sharded_superstep(eng, mesh, 512, 16, donate=True).lower(
        state, i32, flag, i32).compile()
    ma = comp.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES
    assert ma.alias_size_in_bytes >= 0.99 * ma.argument_size_in_bytes
    assert comp.as_text().count(" while(") >= 3   # superstep, blocks, scan


def test_fused_hunt_compiles_for_one_chip(topo):
    """Phase C's whole-hunt fused program (recycled, no search/coverage)
    at its real geometry on a one-device mesh of the described chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from madsim_tpu.engine import (DeviceEngine, EngineConfig, RaftActor,
                                   RaftDeviceConfig)
    from madsim_tpu.parallel.mesh import scalar_spec, world_sharding
    from madsim_tpu.parallel.sweep import _fused_hunt

    eng = DeviceEngine(
        RaftActor(RaftDeviceConfig(n=3, buggy_double_vote=True)),
        EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                     t_limit_us=2_000_000, stop_on_bug=True))
    mesh = _mesh(topo.devices[:1])
    w, n_ids_b, k = chip_smoke.HUNT_BATCH, chip_smoke.HUNT_SEEDS, 4096
    ws, rep = world_sharding(mesh), NamedSharding(mesh, scalar_spec())

    def sds(shape, dtype, sh=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    state = _state_shapes(eng, w, ws)
    obs = jax.eval_shape(eng.observe_device, eng.init(np.arange(8)))
    bufs = {name: sds((n_ids_b + 1,) + s.shape[1:], s.dtype)
            for name, s in obs.items()}
    tabs = {"lo": sds((n_ids_b,), jnp.uint32),
            "hi": sds((n_ids_b,), jnp.uint32)}
    i32 = sds((), jnp.int32)
    runner = _fused_hunt(eng, mesh, None, w=w, n_ids_b=n_ids_b, f_rows=0,
                         chunk_steps=64, k_bucket=k, cov_k=None,
                         lineage_on=False, fault_mode="none", recycle=True)
    comp = runner.lower(state, sds((w,), jnp.int32, ws), i32, i32, bufs,
                        (), (), tabs, i32, i32, i32, sds((), jnp.bool_),
                        i32).compile()
    ma = comp.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES


def test_snapshot_step_fits_one_chip(topo):
    """The Raft step with log compaction (benchmark/configs/raft3snap.json,
    the raft3snap.uncrash cell's 65,536 worlds) compiles for one v5e, fits
    the chip, and holds no gather or scatter."""
    import json

    import jax

    from madsim_tpu.engine import (DeviceEngine, EngineConfig, RaftActor,
                                   RaftDeviceConfig)
    from madsim_tpu.parallel.mesh import world_sharding

    with open(os.path.join(REPO, "benchmark", "configs",
                           "raft3snap.json")) as f:
        cfg = json.load(f)
    eng = DeviceEngine(RaftActor(RaftDeviceConfig(**cfg["raft"])),
                       EngineConfig(**cfg["engine"]))
    mesh = _mesh(topo.devices[:1])
    state = _state_shapes(eng, 65_536, world_sharding(mesh))
    comp = jax.jit(eng._batched_step, donate_argnums=0).lower(
        state).compile()
    ma = comp.memory_analysis()
    assert 0 < ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES
    hlo = comp.as_text()
    assert " gather(" not in hlo and " scatter(" not in hlo


def test_sharded_chunk_all_reduces_over_four_chips(topo):
    """sharded_engine's chunk at W=524,288 over a mesh of all four
    described chips compiles, and its bug/active scalars cross chips in
    an all-reduce."""
    from madsim_tpu.parallel.mesh import world_sharding
    from madsim_tpu.parallel.sweep import sharded_engine

    assert len(topo.devices) == 4
    eng = chip_smoke.headline_engine()
    mesh = _mesh(topo.devices)
    state = _state_shapes(eng, chip_smoke.HEADLINE_W, world_sharding(mesh))
    comp = sharded_engine(eng, mesh, chunk_steps=8).lower(state).compile()
    assert "all-reduce" in comp.as_text()


def test_chip_smoke_refuses_the_cpu(tmp_path):
    """chip_smoke.py exits non-zero and prints no result without a TPU,
    and outside a checkout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout == ""
    assert "no TPU found" in out.stderr
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
    out = subprocess.run([sys.executable, str(alone)], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout == ""


def test_chip_smoke_phase_b_on_cpu(capsys):
    """Phase B (5-node Raft under per-world kill/restart and link clogs
    from ``make_fault_schedules``) passes its checks on the CPU at 256
    worlds: ``_emit`` raises ``CheckFailed`` on any false check, and the
    printed line carries each one."""
    chip_smoke.phase_b(w=256)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "B" and line["W"] == 256
    for check in ("no_live_world", "no_bug", "no_overflow", "rerun_bitwise"):
        assert line["checks"][check] is True, check
