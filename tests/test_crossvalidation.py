"""Round-2 cross-engine validation surfaces: clock skew, backend
crosscheck, and the shared injected bug (buggy_double_vote) that both the
host model and the device actor must detect (VERDICT r1 items 2-3)."""
import numpy as np
import pytest

import madsim_tpu as ms
from madsim_tpu import time as simtime


def test_clock_skew_applies_to_system_time_only():
    rt = ms.Runtime(seed=9)
    rt.set_time_limit(30.0)

    async def main():
        h = ms.Handle.current()
        fast = h.create_node(name="fast", ip="10.0.0.1")
        slow = h.create_node(name="slow", ip="10.0.0.2")
        h.set_clock_skew(fast, +30.0)
        h.set_clock_skew(slow, -5.0)
        out = {}

        async def read(name):
            out[name] = (simtime.system_time(), simtime.monotonic())

        await fast.spawn(read("fast"))
        await slow.spawn(read("slow"))
        await ms.task.spawn(read("main"))
        # Wall clocks diverge by exactly the skew...
        assert out["fast"][0] - out["main"][0] == pytest.approx(30.0, abs=1e-6)
        assert out["slow"][0] - out["main"][0] == pytest.approx(-5.0, abs=1e-6)
        # ...monotonic clocks (and hence timer order) do not.
        assert out["fast"][1] == pytest.approx(out["slow"][1], abs=1e-3)
        # Hot re-skew takes effect immediately.
        h.set_clock_skew(fast, -1.0)
        await fast.spawn(read("fast2"))
        assert out["fast2"][0] - out["fast"][0] < 0  # clock jumped backwards

    rt.block_on(main())


def test_postgres_select_now_observes_server_skew():
    from madsim_tpu.shims import postgres

    rt = ms.Runtime(seed=3)
    rt.set_time_limit(120.0)

    async def main():
        h = ms.Handle.current()
        server = postgres.SimPostgresServer()

        async def serve():
            await server.serve(("10.0.0.1", 5432))

        srv = h.create_node(name="pg", ip="10.0.0.1", init=serve)
        app = h.create_node(name="app", ip="10.0.0.2")
        h.set_clock_skew(srv, +30.0)
        done = ms.sync.SimFuture()

        async def body():
            while True:
                try:
                    conn = await postgres.connect("10.0.0.1", user="t")
                    break
                except OSError:
                    await simtime.sleep(0.05)
            rows = await conn.query("SELECT now()")
            await conn.close()
            done.set_result((float(rows[0][0]), simtime.system_time()))

        app.spawn(body())
        srv_now, app_now = await done
        assert srv_now - app_now == pytest.approx(30.0, abs=0.5)

    rt.block_on(main())


def test_host_model_finds_injected_double_vote_bug():
    """Sweeping seeds on the buggy host model must trip the election-safety
    checker at a nonzero rate (the device actor's side is
    test_device_actor_finds_injected_double_vote_bug)."""
    from madsim_tpu.models.raft import (
        RaftCluster, RaftOptions, RaftInvariantViolation)

    async def world():
        cluster = RaftCluster(3, RaftOptions(persist=False,
                                             buggy_double_vote=True))
        while simtime.monotonic() < 2.0:
            await simtime.sleep(0.05)

    hits = 0
    for seed in range(24):
        rt = ms.Runtime(seed=seed)
        rt.set_time_limit(60.0)
        try:
            rt.block_on(world())
        except RaftInvariantViolation:
            hits += 1
    assert hits > 0, "buggy host model never tripped the invariant checker"


def test_device_actor_finds_injected_double_vote_bug():
    from madsim_tpu.engine import (
        DeviceEngine, EngineConfig, RaftActor, RaftDeviceConfig)

    rcfg = RaftDeviceConfig(n=3, buggy_double_vote=True)
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                       t_limit_us=2_000_000, stop_on_bug=False)
    eng = DeviceEngine(RaftActor(rcfg), cfg)
    state = eng.run(eng.init(np.arange(512)), max_steps=4_000)
    obs = eng.observe(state)
    assert obs["bug"].sum() > 0, "device actor never flagged the bug"
    # bug_time is recorded for failing worlds.
    assert (obs["bug_time_us"][obs["bug"]] < 2**31 - 1).all()


def test_clean_device_actor_flags_no_bugs():
    from madsim_tpu.engine import (
        DeviceEngine, EngineConfig, RaftActor, RaftDeviceConfig)

    rcfg = RaftDeviceConfig(n=3)
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                       t_limit_us=2_000_000, stop_on_bug=False)
    eng = DeviceEngine(RaftActor(rcfg), cfg)
    state = eng.run(eng.init(np.arange(512)), max_steps=4_000)
    obs = eng.observe(state)
    assert obs["bug"].sum() == 0


def test_crosscheck_cpu_devices_bit_identical():
    """Backend crosscheck machinery on two CPU devices of the test mesh
    (chip_smoke.py runs the real TPU-vs-CPU version on the chip)."""
    import jax

    from madsim_tpu.engine import (
        DeviceEngine, EngineConfig, RaftActor, RaftDeviceConfig)
    from madsim_tpu.engine.crosscheck import crosscheck_backends

    devs = jax.devices("cpu")
    rcfg = RaftDeviceConfig(n=3)
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                       t_limit_us=500_000)
    eng = DeviceEngine(RaftActor(rcfg), cfg)
    out = crosscheck_backends(eng, np.arange(64), max_steps=2_000,
                              device_a=devs[0], device_b=devs[-1])
    assert out["bitwise_equal"] == 1


def test_tpc_bug_rates_comparable_host_vs_device():
    """Second cross-engine family (alongside Raft): the presumed-commit
    bug must be found by BOTH engines at comparable per-seed densities
    under the same loss rate, vote probability, and timeout ratios."""
    import madsim_tpu as ms
    from madsim_tpu.engine import DeviceEngine, EngineConfig, TPCActor, TPCDeviceConfig
    from madsim_tpu.models.tpc import run_tpc_world, TPCInvariantViolation

    loss = 0.1

    # Host: sequential seeds.
    cfg = ms.Config()
    cfg.net.packet_loss_rate = loss
    n_host = 48
    host_hits = 0
    for seed in range(n_host):
        rt = ms.Runtime(seed=seed, config=cfg)
        rt.set_time_limit(60.0)
        try:
            rt.block_on(run_tpc_world(buggy_presumed_commit=True))
        except TPCInvariantViolation:
            host_hits += 1
    host_rate = host_hits / n_host

    # Device: one vmapped batch, matched protocol constants.
    eng = DeviceEngine(
        TPCActor(TPCDeviceConfig(n=4, n_txns=6, buggy_presumed_commit=True)),
        EngineConfig(n_nodes=4, outbox_cap=5, queue_cap=64,
                     t_limit_us=2_000_000, loss_rate=loss))
    obs = eng.observe(eng.run(eng.init(np.arange(2048)), max_steps=8000))
    dev_rate = obs["bug"].mean()

    assert host_hits > 0, "host engine never found the presumed-commit bug"
    assert dev_rate > 0, "device engine never found the presumed-commit bug"
    ratio = host_rate / dev_rate
    assert 0.1 <= ratio <= 10.0, \
        f"bug densities diverge: host {host_rate:.3f} vs device {dev_rate:.3f}"

    # And both clean variants stay silent under the same chaos.
    clean_eng = DeviceEngine(
        TPCActor(TPCDeviceConfig(n=4, n_txns=6)),
        EngineConfig(n_nodes=4, outbox_cap=5, queue_cap=64,
                     t_limit_us=2_000_000, loss_rate=loss))
    assert not clean_eng.observe(
        clean_eng.run(clean_eng.init(np.arange(512)), max_steps=8000))["bug"].any()
    for seed in range(12):
        rt = ms.Runtime(seed=seed, config=cfg)
        rt.set_time_limit(60.0)
        rt.block_on(run_tpc_world())  # must not raise


def test_host_paused_leader_reelection_and_stepdown():
    """Host half of the pause cross-validation (device half:
    test_engine.py::test_pause_buffers_deliveries_and_reelects): pause the
    leader past the election timeout → a new leader is elected among the
    live nodes; on resume the stale leader sees the higher term and steps
    down (`runtime/mod.rs:251-268`, `task.rs:243-261`)."""
    from madsim_tpu.models.raft import LEADER, RaftCluster, RaftOptions

    async def world():
        h = ms.Handle.current()
        cluster = RaftCluster(3, RaftOptions(persist=False))
        old = await cluster.wait_for_leader()
        old_term = cluster.servers[old].term
        h.pause(cluster.nodes[old])

        # cluster.leader() keeps reporting the paused node's in-memory role
        # until someone outranks it — wait for a *different* leader at a
        # higher term.
        async def wait_new():
            while True:
                lead = cluster.leader()
                if (lead is not None and lead != old
                        and cluster.servers[lead].term > old_term):
                    return lead
                await simtime.sleep(0.05)

        new = await simtime.timeout(30.0, wait_new())
        h.resume(cluster.nodes[old])
        await simtime.sleep(3.0)  # buffered traffic flushes; stale term dies
        leaders = [i for i, s in cluster.servers.items() if s.role == LEADER]
        assert old not in leaders, "stale leader did not step down on resume"
        assert len(leaders) == 1
        return (old, new)

    seen = set()
    for seed in range(6):
        rt = ms.Runtime(seed=seed)
        rt.set_time_limit(120.0)
        seen.add(rt.block_on(world()))
    assert len(seen) > 1, "every seed elected the same pair — chaos is vacuous"
