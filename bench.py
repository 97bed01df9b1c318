"""Benchmark suite: all five BASELINE.json configs + backend crosscheck.

The headline (BASELINE.json metric): MadRaft 3-node seeds/sec on the batched
device engine, and its speedup over single-seed host (CPU) execution — the
reference's one-thread-per-seed model (`madsim/src/sim/runtime/builder.rs:
118-136`). The reference publishes no numbers (BASELINE.md); the other
configs mirror its harness definitions:

  1. rpc_pingpong       2-node RPC ping-pong, single seed, host engine
                        (`madsim/benches/rpc.rs:11-26`)
  1b. rpc_real          the same ping-pong on the production backend over
                        real loopback TCP — the transport the reference's
                        criterion bench actually measures
  2. madraft_3node      3-node leader election, W seeds vmapped (headline)
  3. grpc_chaos         gRPC echo under partition chaos
                        (`tonic-example/src/server.rs:281-332`)
  4. postgres_skew      postgres client<->server with clock-skew injection
  5. madraft_5node      5-node log replication x failure-schedule sweep
                        (device engine, per-world fault schedules)

Plus two cross-engine validations VERDICT r1 required:
  - crosscheck          TPU vs CPU bit-exact trajectory equality
  - time_to_first_bug   host vs device finding the same injected Raft bug
                        (buggy_double_vote), wall-clock to first detection

Prints ONE JSON line (driver contract): the headline metric with the other
config results embedded under "configs". Details go to stderr.
"""
import argparse
import json
import subprocess
import sys
import time as walltime

import numpy as np

SIM_SECONDS = 1.0  # virtual seconds of Raft per seed (headline config)
# Payload sweep mirroring `benches/rpc.rs:28-54`, shared by the sim and
# production RPC configs so their curves stay directly comparable.
PAYLOAD_SIZES = (16, 256, 4096, 65536, 1 << 20)


class BenchPing:
    """RPC request type for the ping-pong configs. Module-level because the
    real backend pickles payloads onto the wire (std-mode bincode analog)."""

    __slots__ = ("n",)

    def __init__(self, n):
        self.n = n


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def xla_cost_record(eng, state, max_steps: int) -> dict:
    """XLA's own per-step cost model for the compiled (donated) run path.

    Lowers ``eng._run`` at the given state's shapes (no execution — safe
    on a donated state) and records ``cost_analysis()`` flops/bytes and
    ``memory_analysis()`` sizes into the bench result, so per-iteration
    performance accounting is a tracked artifact per round (PRISM-style)
    instead of a one-off measurement. ``make smoke`` asserts the keys
    exist; the tier-1 op-budget test (tests/test_queue_insert.py) gates
    flops per world-step against a recorded budget. Never raises: on any
    analysis failure the keys are present with null values plus an
    ``error`` string, keeping the bench record intact.
    """
    import numpy as _np

    out = {"n_worlds": None, "max_steps": max_steps,
           "packed": bool(getattr(eng.cfg, "packed", False)),
           "flops_per_step": None, "flops_per_world_step": None,
           "bytes_accessed_per_step": None,
           "argument_size_bytes": None, "output_size_bytes": None,
           "temp_size_bytes": None, "aliased_bytes": None,
           "state_bytes_per_world": None,
           "peak_bytes_est": None, "peak_over_state": None}
    try:
        w = int(_np.asarray(state.now).shape[0])
        out["n_worlds"] = w
        comp = eng._run.lower(state, max_steps).compile()
        ca = comp.cost_analysis()
        flops = ca.get("flops")
        if flops is not None:
            out["flops_per_step"] = float(flops)
            out["flops_per_world_step"] = round(float(flops) / w, 2)
        ba = ca.get("bytes accessed")
        if ba is not None:
            out["bytes_accessed_per_step"] = float(ba)
        ma = comp.memory_analysis()
        arg = int(ma.argument_size_in_bytes)
        out.update({
            "argument_size_bytes": arg,
            "output_size_bytes": int(ma.output_size_in_bytes),
            "temp_size_bytes": int(ma.temp_size_in_bytes),
            "aliased_bytes": int(ma.alias_size_in_bytes),
        })
        peak = (arg + int(ma.output_size_in_bytes)
                + int(ma.temp_size_in_bytes) - int(ma.alias_size_in_bytes))
        out["peak_bytes_est"] = peak
        if arg:
            out["peak_over_state"] = round(peak / arg, 4)
        # The packed-lane regression surface (tracked by bench_diff and
        # gated by the budget ledger's state_bytes_per_world entry).
        out["state_bytes_per_world"] = round(arg / w, 2)
    except Exception as exc:  # noqa: BLE001 — observability must not fail the bench
        out["error"] = f"{type(exc).__name__}: {exc}"
    return out


# ---------------------------------------------------------------------------
# Config 1: RPC ping-pong, 2 nodes, single seed, host engine
# ---------------------------------------------------------------------------

def bench_rpc_pingpong(n_rounds: int) -> dict:
    """Round-trips/sec of the built-in RPC over the simulated network, plus
    payload-throughput points mirroring `benches/rpc.rs:28-54` sizes."""
    import madsim_tpu as ms
    from madsim_tpu.net import Endpoint, rpc
    from madsim_tpu import time as simtime

    Ping = BenchPing

    def world(payload: bytes, rounds: int):
        rt = ms.Runtime(seed=1)

        async def main():
            h = ms.Handle.current()

            async def server_init():
                ep = await Endpoint.bind("10.0.0.1:9000")

                # The reference's criterion handler consumes the data and
                # returns an empty sidecar (`benches/rpc.rs:35-38`); echoing
                # it back would double the measured wire traffic.
                async def handle(req, data):
                    return Ping(req.n + 1), b""

                rpc.add_rpc_handler_with_data(ep, Ping, handle)
                await simtime.sleep(1e6)

            h.create_node(name="server", ip="10.0.0.1", init=server_init)
            client = h.create_node(name="client", ip="10.0.0.2")
            done = ms.sync.SimFuture()

            async def client_body():
                ep = await Endpoint.bind("10.0.0.2:0")
                # Datagram sends are not retransmitted: the very first call
                # can race the server's bind, so retry it until the server
                # is up (the reference's tests use the same retry idiom).
                while True:
                    try:
                        await rpc.call_with_data(
                            ep, "10.0.0.1:9000", Ping(0), payload, timeout=0.2)
                        break
                    except TimeoutError:
                        pass
                # Virtual latency measured over the counted rounds only
                # (startup + retry traffic excluded).
                t_start = simtime.monotonic()
                for i in range(rounds):
                    await rpc.call_with_data(
                        ep, "10.0.0.1:9000", Ping(i), payload, timeout=5.0)
                done.set_result(simtime.monotonic() - t_start)

            client.spawn(client_body())
            return await done

        return rt.block_on(main())

    t0 = walltime.perf_counter()
    virt = world(b"", n_rounds)
    dt = walltime.perf_counter() - t0
    out = {"empty_rpc_roundtrips_per_sec": round(n_rounds / dt, 2),
           "virtual_latency_ms": round(virt / n_rounds * 1e3, 3)}

    data_rounds = max(16, n_rounds // 8)
    rates = {}
    for size in PAYLOAD_SIZES:
        payload = b"\xab" * size
        t0 = walltime.perf_counter()
        world(payload, data_rounds)
        dt = walltime.perf_counter() - t0
        rates[f"{size}B"] = round(data_rounds * size / dt / 1e6, 2)
    out["payload_mb_per_sec"] = rates
    log(f"rpc_pingpong: {out}")
    return out


# ---------------------------------------------------------------------------
# Config 1b: the same RPC ping-pong on the PRODUCTION backend — direct
# parity with the reference's criterion bench, which measures the std TCP
# transport over loopback (`madsim/benches/rpc.rs:11-56`).
# ---------------------------------------------------------------------------

def bench_rpc_real(n_rounds: int) -> dict:
    import os

    prior_backend = os.environ.get("MADSIM_BACKEND")
    prior_transport = os.environ.get("MADSIM_REAL_TRANSPORT")
    os.environ["MADSIM_BACKEND"] = "real"
    # Pin the first leg to TCP explicitly so a pre-set uds env can't turn
    # the tcp-vs-uds comparison into uds-vs-uds with a wrong label.
    os.environ["MADSIM_REAL_TRANSPORT"] = "tcp"
    try:
        import madsim_tpu as ms
        from madsim_tpu.net import Endpoint, rpc

        async def world(payload: bytes, rounds: int) -> float:
            server = await Endpoint.bind("127.0.0.1:0")

            # Reference handler shape: consume data, empty response sidecar
            # (`benches/rpc.rs:35-38`).
            async def handle(req, data):
                return BenchPing(req.n + 1), b""

            rpc.add_rpc_handler_with_data(server, BenchPing, handle)
            client = await Endpoint.bind("127.0.0.1:0")
            addr = server.local_addr()
            t0 = walltime.perf_counter()
            for i in range(rounds):
                await rpc.call_with_data(client, addr, BenchPing(i),
                                         payload, timeout=10.0)
            dt = walltime.perf_counter() - t0
            client.close()
            server.close()
            return dt

        dt = ms.run(world(b"", n_rounds))
        out = {"empty_rpc_roundtrips_per_sec": round(n_rounds / dt, 2),
               "empty_rpc_latency_us": round(dt / n_rounds * 1e6, 1)}
        rates = {}
        data_rounds = max(16, n_rounds // 8)
        for size in PAYLOAD_SIZES:
            dt = ms.run(world(b"\xab" * size, data_rounds))
            rates[f"{size}B"] = round(data_rounds * size / dt / 1e6, 2)
        out["payload_mb_per_sec"] = rates
        # The alternative wire transports on the same world: kernel UDS
        # instead of loopback TCP, and the shm bulk leg (UDS control +
        # shared-memory rings for >=32 KiB payloads — docs/transports.md).
        os.environ["MADSIM_REAL_TRANSPORT"] = "uds"
        dt = ms.run(world(b"", n_rounds))
        out["uds_empty_rpc_roundtrips_per_sec"] = round(n_rounds / dt, 2)
        out["uds_empty_rpc_latency_us"] = round(dt / n_rounds * 1e6, 1)
        os.environ["MADSIM_REAL_TRANSPORT"] = "shm"
        dt = ms.run(world(b"", n_rounds))
        out["shm_empty_rpc_latency_us"] = round(dt / n_rounds * 1e6, 1)
        shm_rates = {}
        for size in PAYLOAD_SIZES:
            dt = ms.run(world(b"\xab" * size, data_rounds))
            shm_rates[f"{size}B"] = round(data_rounds * size / dt / 1e6, 2)
        out["shm_payload_mb_per_sec"] = shm_rates
        log(f"rpc_real (production backend, tcp + uds + shm): {out}")
        return out
    finally:
        if prior_backend is None:
            os.environ.pop("MADSIM_BACKEND", None)
        else:
            os.environ["MADSIM_BACKEND"] = prior_backend
        if prior_transport is None:
            os.environ.pop("MADSIM_REAL_TRANSPORT", None)
        else:
            os.environ["MADSIM_REAL_TRANSPORT"] = prior_transport


# ---------------------------------------------------------------------------
# Config 2 (headline): MadRaft 3-node, device engine vs host single-seed
# ---------------------------------------------------------------------------

def host_seed_rate(n_seeds: int) -> dict:
    """Single-seed host engine baseline with an explicit per-event cost
    model (VERDICT r2 item 7): seeds/s, scheduler polls ("events")/s, and
    µs/poll, so the vs_baseline denominator is a measured quantity."""
    import madsim_tpu as ms
    from madsim_tpu.models.raft import RaftCluster, RaftOptions

    async def world():
        from madsim_tpu import time as simtime

        cluster = RaftCluster(3, RaftOptions(persist=False))
        try:
            await cluster.wait_for_leader(timeout=SIM_SECONDS)
        except TimeoutError:
            pass
        now = simtime.monotonic()
        if now < SIM_SECONDS:
            await simtime.sleep(SIM_SECONDS - now)
        return cluster.leader()

    t0 = walltime.perf_counter()
    elected = 0
    polls = 0
    for seed in range(n_seeds):
        rt = ms.Runtime(seed=seed)
        if rt.block_on(world()) is not None:
            elected += 1
        polls += rt.handle.task.poll_count
    dt = walltime.perf_counter() - t0
    out = {
        "seeds_per_sec": round(n_seeds / dt, 2),
        "events_per_sec": round(polls / dt, 1),
        "us_per_event": round(dt / polls * 1e6, 3),
        "events_per_seed": round(polls / n_seeds, 1),
        "elected": elected,
        "n_seeds": n_seeds,
    }
    log(f"host: {n_seeds} seeds in {dt:.2f}s ({out['seeds_per_sec']} seeds/s, "
        f"{out['events_per_sec']:.0f} events/s, {out['us_per_event']} us/event, "
        f"{elected}/{n_seeds} elected)")
    return out


def device_seed_rate(n_worlds: int, max_steps: int = 2_000) -> float:
    import jax

    from madsim_tpu.engine import DeviceEngine, EngineConfig, RaftActor, RaftDeviceConfig

    # State footprint sizes HBM traffic (queue + logs are rewritten every
    # step) and is the single biggest throughput knob. Measured over 262k
    # seeds (observe() reports qmax): queue high-water mark is 18 slots,
    # so queue_cap=28 carries 10 slots of headroom at ~1.9x the rate of
    # 64; the election-only headline never appends log entries, so
    # log_cap=4 replaces the default 16. The run still asserts overflow==0.
    rcfg = RaftDeviceConfig(n=3, log_cap=4)
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=28,
                       t_limit_us=int(SIM_SECONDS * 1e6))
    eng = DeviceEngine(RaftActor(rcfg), cfg)

    # Warmup: compile init + run on the same shapes.
    warm = eng.run(eng.init(np.arange(n_worlds)), max_steps=max_steps)
    jax.block_until_ready(warm)

    # Best of 3 timed runs of the same fixed computation.
    dt = float("inf")
    for _ in range(3):
        t0 = walltime.perf_counter()
        state = eng.init(np.arange(1_000_000, 1_000_000 + n_worlds))
        state = eng.run(state, max_steps=max_steps)
        jax.block_until_ready(state)
        dt = min(dt, walltime.perf_counter() - t0)

    obs = eng.observe(state)
    assert not obs["active"].any(), "worlds did not finish; raise max_steps"
    assert not obs["bug"].any(), "clean config must not flag bugs"
    assert not obs["overflow"].any(), \
        f"queue overflow (qmax={int(obs['qmax'].max())}): raise queue_cap"
    elected = int(obs["leader_elected"].sum())
    log(f"device[{jax.default_backend()}]: {n_worlds} seeds in {dt:.2f}s "
        f"({n_worlds / dt:.0f} seeds/s, {elected}/{n_worlds} elected, "
        f"mean {obs['steps'].mean():.0f} steps/world)")
    return n_worlds / dt


# ---------------------------------------------------------------------------
# Config 3: gRPC echo under partition chaos
# ---------------------------------------------------------------------------

def bench_grpc_chaos(n_clients: int, sim_seconds: float) -> dict:
    """Echoes/sec completed while a supervisor partitions and heals the
    network and restarts client nodes (`tonic-example/src/server.rs:281-332`
    semantics: progress must continue across chaos)."""
    import madsim_tpu as ms
    from madsim_tpu.net import NetSim
    from madsim_tpu.shims import grpc_sim
    from madsim_tpu import time as simtime

    class Echo:
        SERVICE_NAME = "bench.Echo"

        @grpc_sim.unary
        async def Say(self, request, context):
            return request

        @grpc_sim.bidi
        async def Stream(self, requests, context):
            async for r in requests:
                yield r

    completed = [0]

    def world():
        rt = ms.Runtime(seed=7)
        rt.set_time_limit(sim_seconds * 10 + 60)

        async def main():
            h = ms.Handle.current()
            server = grpc_sim.Server().add_service(Echo())

            async def serve():
                await server.serve(("10.0.0.1", 50051))

            srv = h.create_node(name="server", ip="10.0.0.1", init=serve)

            def client_init(i):
                async def body():
                    while True:
                        try:
                            ch = await grpc_sim.Channel.connect(("10.0.0.1", 50051))
                            while True:
                                rsp = await simtime.timeout(
                                    1.0, ch.unary("/bench.Echo/Say", completed[0]))
                                assert rsp is not None
                                completed[0] += 1
                        except (OSError, TimeoutError, grpc_sim.Status):
                            await simtime.sleep(0.05)

                return body

            clients = [h.create_node(name=f"cli{i}", ip=f"10.0.0.{i + 2}",
                                     init=client_init(i))
                       for i in range(n_clients)]

            sim = ms.simulator(NetSim)
            from madsim_tpu import rand
            rng = rand.thread_rng()
            t_end = sim_seconds
            while simtime.monotonic() < t_end:
                await simtime.sleep(rng.gen_range_f64(0.1, 0.3))
                act = rng.gen_range(0, 3)
                victim = clients[rng.gen_range(0, n_clients)]
                if act == 0:
                    sim.disconnect2(srv.id, victim.id)
                    await simtime.sleep(rng.gen_range_f64(0.05, 0.2))
                    sim.connect2(srv.id, victim.id)
                elif act == 1:
                    ms.Handle.current().restart(victim)
                else:
                    sim.disconnect(victim.id)   # clog the whole node
                    await simtime.sleep(rng.gen_range_f64(0.05, 0.2))
                    sim.connect(victim.id)

        rt.block_on(main())

    t0 = walltime.perf_counter()
    world()
    dt = walltime.perf_counter() - t0
    assert completed[0] > 0, "no gRPC progress under chaos"
    out = {"echoes_completed": completed[0],
           "echoes_per_wall_sec": round(completed[0] / dt, 2),
           "sim_seconds": sim_seconds, "n_clients": n_clients}
    log(f"grpc_chaos: {out}")
    return out


# ---------------------------------------------------------------------------
# Config 4: postgres client<->server with clock-skew injection
# ---------------------------------------------------------------------------

def bench_postgres_skew(n_queries: int) -> dict:
    """Queries/sec against the in-sim postgres server while the client and
    server wall clocks are skewed apart (and re-skewed mid-run). Asserts the
    client observes the skew via the server's now() and that queries keep
    succeeding — wall-clock skew must not affect protocol correctness."""
    import madsim_tpu as ms
    from madsim_tpu.shims import postgres
    from madsim_tpu import time as simtime

    stats = {}

    def world():
        rt = ms.Runtime(seed=3)
        rt.set_time_limit(600)

        async def main():
            h = ms.Handle.current()
            server = postgres.SimPostgresServer()

            async def serve():
                await server.serve(("10.0.0.1", 5432))

            srv = h.create_node(name="pg", ip="10.0.0.1", init=serve)
            app = h.create_node(name="app", ip="10.0.0.2")
            # Inject: server clock 30 s ahead, client 5 s behind.
            h.set_clock_skew(srv, +30.0)
            h.set_clock_skew(app, -5.0)
            done = ms.sync.SimFuture()

            async def body():
                while True:  # server bind race: retry the initial connect
                    try:
                        conn = await postgres.connect("10.0.0.1", user="bench")
                        break
                    except OSError:
                        await simtime.sleep(0.05)
                await conn.execute("CREATE TABLE kv (k, v)")
                # Extended-query protocol: all inserts/reads go through
                # Parse/Bind/Execute prepared statements, each pair inside
                # a transaction (VERDICT r2 item 5 done-criteria).
                ins = await conn.prepare("INSERT INTO kv VALUES ($1, $2)")
                sel = await conn.prepare("SELECT v FROM kv WHERE k = $1")
                for i in range(n_queries):
                    async with conn.transaction():
                        await conn.execute_prepared(ins, [str(i), f"v{i}"])
                    rows = await conn.query_prepared(sel, [str(i)])
                    assert rows[0].get("v") == f"v{i}"
                    if i == n_queries // 2:
                        # Hot re-skew mid-connection, plus a transaction
                        # rollback: its write must not survive.
                        ms.Handle.current().set_clock_skew(srv, -45.0)
                        try:
                            async with conn.transaction():
                                await conn.execute_prepared(
                                    ins, ["doomed", "x"])
                                raise RuntimeError("force rollback")
                        except RuntimeError:
                            pass
                        assert await conn.query_prepared(sel, ["doomed"]) == []
                srv_now = await conn.query("SELECT now()")
                await conn.close()
                done.set_result((srv_now[0][0], simtime.system_time()))

            app.spawn(body())
            srv_now, app_now = await done
            stats["server_now"] = srv_now
            stats["client_observed_skew_s"] = round(
                float(srv_now) - app_now, 1) if _floatable(srv_now) else None

        rt.block_on(main())

    t0 = walltime.perf_counter()
    world()
    dt = walltime.perf_counter() - t0
    out = {"queries_per_wall_sec": round(2 * n_queries / dt, 2),
           "n_queries": 2 * n_queries,
           "client_observed_skew_s": stats.get("client_observed_skew_s")}
    log(f"postgres_skew: {out}")
    return out


def _floatable(v) -> bool:
    try:
        float(v)
        return True
    except (TypeError, ValueError):
        return False


# ---------------------------------------------------------------------------
# Config 5: MadRaft 5-node log replication x failure-schedule sweep (device)
# ---------------------------------------------------------------------------

def make_fault_schedules(n_worlds: int, n_nodes: int, t_limit_us: int,
                         seed: int = 0) -> np.ndarray:
    """Per-world fault rows [time_us, op, a, b]: one kill+restart pair and
    one link clog+unclog window per world, at schedule-swept times."""
    from madsim_tpu.engine.core import (
        FAULT_KILL, FAULT_RESTART, FAULT_CLOG_LINK, FAULT_UNCLOG_LINK)

    rng = np.random.default_rng(seed)
    t_kill = rng.integers(t_limit_us // 10, t_limit_us // 2, n_worlds)
    t_restart = t_kill + rng.integers(50_000, t_limit_us // 4, n_worlds)
    victim = rng.integers(0, n_nodes, n_worlds)
    t_clog = rng.integers(t_limit_us // 10, t_limit_us // 2, n_worlds)
    t_unclog = t_clog + rng.integers(50_000, t_limit_us // 4, n_worlds)
    a = rng.integers(0, n_nodes, n_worlds)
    b = (a + 1 + rng.integers(0, n_nodes - 1, n_worlds)) % n_nodes
    rows = np.stack([
        np.stack([t_kill, np.full(n_worlds, FAULT_KILL), victim,
                  np.zeros(n_worlds)], axis=1),
        np.stack([t_restart, np.full(n_worlds, FAULT_RESTART), victim,
                  np.zeros(n_worlds)], axis=1),
        np.stack([t_clog, np.full(n_worlds, FAULT_CLOG_LINK), a, b], axis=1),
        np.stack([t_unclog, np.full(n_worlds, FAULT_UNCLOG_LINK), a, b], axis=1),
    ], axis=1).astype(np.int32)
    return rows


def bench_madraft_5node(n_worlds: int) -> dict:
    """5-node Raft with client proposals + per-world failure schedules,
    swept on the device engine (BASELINE config 5; the reference's analog is
    MADSIM_TEST_NUM=100000 with chaos, one thread per seed)."""
    import jax

    from madsim_tpu.engine import DeviceEngine, EngineConfig, RaftActor, RaftDeviceConfig
    from madsim_tpu.parallel.sweep import sweep

    t_limit_us = 3_000_000
    rcfg = RaftDeviceConfig(n=5, n_proposals=4, log_cap=16,
                            propose_start_us=1_000_000,
                            propose_interval_us=200_000)
    # Measured high-water mark: 58 slots over 100k fault-scheduled seeds;
    # 64 runs ~13% faster than 80 and the overflow assert below guards the
    # headroom. chunk_steps: 512 used to beat 128 because each chunk cost
    # a host sync; with superstepped dispatch (r8) the host pays one
    # dispatch per ~K chunks, so fine chunks now WIN — 16 measured ~15%
    # faster than 512 (utilization 0.94 vs 0.77: stragglers waste <16
    # masked steps instead of <512) at a 5.9x chunk-per-dispatch fold.
    cfg = EngineConfig(n_nodes=5, outbox_cap=6, queue_cap=64,
                       t_limit_us=t_limit_us)
    eng = DeviceEngine(RaftActor(rcfg), cfg)
    faults = make_fault_schedules(n_worlds, 5, t_limit_us)

    # Cost-model record for this engine config (capped batch: the model
    # is per-shape, flops_per_world_step is the tracked quantity; the
    # probe state dies before the timed sweep allocates).
    rec_w = min(n_worlds, 4_096)
    xla_cost = xla_cost_record(
        eng, eng.init(np.arange(rec_w), faults=faults[:rec_w]), 2_000)

    # Observability record (docs/observability.md): the same config swept
    # metrics-on at the capped batch. metrics is a STATIC engine knob, so
    # this uses its own engine and the timed sweep below stays the exact
    # metrics-off program; trajectories are bit-identical either way
    # (tier-1, tests/test_obs.py).
    import dataclasses as _dc

    eng_m = DeviceEngine(RaftActor(rcfg), _dc.replace(cfg, metrics=True))
    res_m = sweep(None, eng_m.cfg, np.arange(rec_w), faults=faults[:rec_w],
                  engine=eng_m, chunk_steps=16, max_steps=20_000)
    sim_metrics = {"n_worlds": rec_w, **res_m.metrics["aggregate"]}
    # Behavior-coverage rollup of the same probe (docs/observability.md
    # "reading the novelty curve"; `make smoke` asserts
    # distinct_behaviors > 1).
    coverage = res_m.coverage.to_json()
    del eng_m, res_m

    # Warmup compile on the SAME batch shape as the timed run (jit
    # specializes on shapes; a smaller warmup batch would leave the real
    # compile inside the timed window).
    res = sweep(None, cfg, np.arange(n_worlds), faults=faults, engine=eng,
                chunk_steps=16, max_steps=20_000)

    t0 = walltime.perf_counter()
    res = sweep(None, cfg, np.arange(n_worlds), faults=faults, engine=eng,
                chunk_steps=16, max_steps=20_000)
    dt = walltime.perf_counter() - t0

    obs = res.observations
    n_bug = int(obs["bug"].sum())
    assert n_bug == 0, f"clean 5-node config flagged {n_bug} bugs"
    assert not obs["overflow"].any(), \
        f"queue overflow (qmax={int(obs['qmax'].max())}): raise queue_cap"
    committed = obs["max_commit"]
    hist = res.n_active_history
    out = {"seeds_per_sec": round(n_worlds / dt, 2),
           "n_worlds": n_worlds,
           "mean_committed": round(float(committed.mean()), 2),
           "worlds_with_commits": int((committed > 0).sum()),
           "elected_frac": round(float(obs["leader_elected"].mean()), 4),
           # Occupancy telemetry (docs/perf.md "world recycling"): measured
           # per-chunk, not inferred from a one-off steps histogram.
           "world_utilization": round(res.world_utilization, 4),
           "n_chunks": int(hist.size),
           "n_active_history": [int(x) for x in hist],
           # Orchestration breakdown of the timed sweep (docs/perf.md
           # "Pipelined orchestration"): dispatch counts, superstep
           # fan-in, and the host/device wall split of the chunk loop.
           "sweep_loop": res.loop_stats,
           "xla_cost": xla_cost,
           # Fleet-aggregate simulation metrics of the metrics-on probe
           # sweep (docs/observability.md; asserted by `make smoke`).
           "sim_metrics": sim_metrics,
           # Behavior-coverage ledger rollup of the same probe sweep.
           "coverage": coverage}
    log(f"madraft_5node[{jax.default_backend()}]: {dt:.2f}s  {out}")
    return out


def bench_fleet_sweep(n_worlds: int) -> dict:
    """2-worker local fleet fabric vs single-host sweep on the same
    seeds (docs/fleet.md): measures the fabric's orchestration overhead
    — lease RPCs, heartbeats, per-range dispatch — so bench_diff tracks
    it round over round. The bitwise contract (fleet == single-host on
    ids/bugs/observations) is asserted inline; this bench exists for
    the RATE delta, the tier-1 chaos matrix owns the contract."""
    import jax

    from madsim_tpu.engine import DeviceEngine, EngineConfig, RaftActor, RaftDeviceConfig
    from madsim_tpu.fleet import fleet_sweep
    from madsim_tpu.parallel.sweep import sweep

    rcfg = RaftDeviceConfig(n=3, buggy_double_vote=True)
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                       t_limit_us=1_500_000)
    eng = DeviceEngine(RaftActor(rcfg), cfg)
    seeds = np.arange(n_worlds)
    kw = dict(chunk_steps=64, max_steps=100_000)
    n_ranges = 8

    # Warmup compiles both paths on the real shapes.
    single = sweep(None, cfg, seeds, engine=eng, **kw)
    fleet = fleet_sweep(None, cfg, seeds, engine=eng, n_workers=2,
                        range_size=-(-n_worlds // n_ranges), **kw)
    assert np.array_equal(single.bug, fleet.bug), \
        "fleet result diverged from single-host (bitwise contract)"

    t0 = walltime.perf_counter()
    single = sweep(None, cfg, seeds, engine=eng, **kw)
    dt_single = walltime.perf_counter() - t0
    t0 = walltime.perf_counter()
    fleet = fleet_sweep(None, cfg, seeds, engine=eng, n_workers=2,
                        range_size=-(-n_worlds // n_ranges), **kw)
    dt_fleet = walltime.perf_counter() - t0

    stats = fleet.loop_stats["fleet"]
    leases = max(1, stats["leases_issued"])
    out = {"n_worlds": n_worlds,
           "n_workers": 2,
           "n_ranges": stats["ranges"],
           "single_seeds_per_sec": round(n_worlds / dt_single, 2),
           "fleet_seeds_per_sec": round(n_worlds / dt_fleet, 2),
           # >0 = the fabric costs throughput vs one big batch (smaller
           # per-range batches + lease bookkeeping); the tracked number.
           # ISSUE 17 gate: <= 0.15 on this config (sessions + prefetch
           # + coalesced control plane; docs/fleet.md "Fabric cost
           # model").
           "fabric_overhead_frac": round(1 - dt_single / dt_fleet, 4),
           "leases_issued": stats["leases_issued"],
           "heartbeats": stats["heartbeats"],
           "fabric_ticks": stats["fabric_ticks"],
           # Per-phase breakdown of the fleet wall (docs/fleet.md
           # "Fabric cost model"): where each lease's time went, and
           # the counted control-plane discipline per lease.
           "acquire_ms": round(1000.0 * stats["acquire_s"] / leases, 3),
           "sweep_ms": round(1000.0 * stats["sweep_s"] / leases, 3),
           "merge_ms": round(1000.0 * stats.get("merge_s", 0.0), 3),
           "rpcs_per_lease": stats["rpcs_per_lease"],
           "control_rpcs_per_lease": stats["control_rpcs_per_lease"],
           "session_reuse_hits": stats["session_reuse_hits"],
           "leases_prefetched": stats["leases_prefetched"],
           "grouped_leases": stats["grouped_leases"]}
    log(f"fleet_sweep[{jax.default_backend()}]: single {dt_single:.2f}s "
        f"fleet {dt_fleet:.2f}s  {out}")
    return out


def bench_guided_hunt(budget: int) -> dict:
    """Coverage-guided schedule search vs the matched random-mutation
    baseline (docs/search.md; search/hunts.py), on the two canonical
    hunts the ROADMAP item-2 gate names:

    - pair family: seeds-to-bug under ``stop_on_first_bug`` (the bug is
      reachable ONLY through mutation; guided ~73 vs random ~409);
    - seeded raft double-vote: failing seeds found at the full budget
      (first-bug ties are expected — generation-1 children are shared
      by construction — so the hunting-power metric is bugs-at-budget).

    Both legs also record the novelty-curve area (sum of the per-chunk
    cumulative distinct-behavior counts — a bigger area = coverage grew
    earlier), tracked round over round by tools/bench_diff.py. The
    pair-leg ordering (guided strictly first) is asserted inline; the
    raft margin is gated end-to-end by `make fuzz-demo`.
    """
    import jax

    from madsim_tpu.engine import DeviceEngine
    from madsim_tpu.parallel.sweep import sweep
    from madsim_tpu.search.hunts import pair_hunt, paxos_hunt, raft_hunt

    def leg(hunt, stop_first: bool) -> dict:
        eng = DeviceEngine(hunt.actor, hunt.cfg)
        out = {"budget": budget}
        for mode, guided in (("guided", True), ("random", False)):
            t0 = walltime.perf_counter()
            res = sweep(None, hunt.cfg, np.arange(budget), engine=eng,
                        faults=hunt.template, stop_on_first_bug=stop_first,
                        search=hunt.search(guided), **hunt.sweep_kw)
            dt = walltime.perf_counter() - t0
            f = res.failing_seeds
            out[f"{mode}_seeds_to_bug"] = (int(f[0]) + 1) if f else None
            out[f"{mode}_bugs_found"] = len(f)
            out[f"{mode}_novelty_area"] = int(
                res.coverage.novelty_curve.sum())
            out[f"{mode}_generations"] = int(res.search.generations)
            out[f"{mode}_corpus_size"] = int(res.search.corpus_size)
            # Evolution-observatory accounting (obs/lineage.py): the
            # deepest ancestry chain materialized and the per-operator
            # outcome table — tracked round over round by
            # tools/bench_diff.py as the operator-credit signal.
            out[f"{mode}_lineage_depth"] = int(res.search.lineage_depth())
            out[f"{mode}_operator_stats"] = res.search.operator_stats
            out[f"{mode}_wall_s"] = round(dt, 3)
            if guided:
                # Dispatch economics of the guided leg (docs/perf.md
                # "Whole-hunt residency"; make smoke asserts the
                # seeds_per_dispatch / epochs_on_device keys).
                out["sweep_loop"] = res.loop_stats
        g, r = out["guided_seeds_to_bug"], out["random_seeds_to_bug"]
        # seeds-to-bug ratio; an un-found random leg counts as budget+1
        # (a lower bound on the true gap).
        if g is not None:
            out["speedup_lower_bound"] = round(
                (r if r is not None else budget + 1) / g, 2)
        return out

    pair = leg(pair_hunt(), stop_first=True)
    assert pair["guided_seeds_to_bug"] is not None, \
        "guided search missed the pair-family bug inside the budget"
    r = pair["random_seeds_to_bug"]
    assert r is None or pair["guided_seeds_to_bug"] < r, \
        f"guided ({pair['guided_seeds_to_bug']}) did not beat random " \
        f"({r}) on the pair family"
    raft = leg(raft_hunt(), stop_first=False)
    # The actorc-compiled DSL-only family (docs/actorc.md): multi-decree
    # Paxos, forgetful-acceptor consistency violation. Same gate shape
    # as the pair leg — guided must reach the bug strictly first
    # (measured: guided ~191, random not found in 512).
    paxos = leg(paxos_hunt(), stop_first=True)
    assert paxos["guided_seeds_to_bug"] is not None, \
        "guided search missed the Paxos forgetful-acceptor bug inside " \
        "the budget"
    rp = paxos["random_seeds_to_bug"]
    assert rp is None or paxos["guided_seeds_to_bug"] < rp, \
        f"guided ({paxos['guided_seeds_to_bug']}) did not beat random " \
        f"({rp}) on the Paxos family"
    out = {"n_seed_budget": budget, "pair": pair, "raft": raft,
           "paxos": paxos}
    log(f"guided_hunt[{jax.default_backend()}]: {out}")
    return out


def bench_guided_fleet(budget: int) -> dict:
    """Cross-range corpus exchange vs independent-corpus fleet
    (docs/fleet.md "Corpus exchange"), on the pair family at a range
    size DELIBERATELY too small to climb the staircase alone: 64-seed
    ranges under a ~73-seed bug mean an independent fleet can never
    reach it — partition-dependence made visible — while the exchanged
    fleet chains corpus progress across epochs and finds it. Records
    seeds-to-bug both ways (the acceptance gate: exchanged reaches the
    bug in no more seeds than the best independent range, asserted
    inline), bugs at budget, merge/publish traffic, and the exchange
    overhead fraction tools/bench_diff.py tracks round over round."""
    import jax

    from madsim_tpu.engine import DeviceEngine
    from madsim_tpu.fleet import ExchangeConfig, fleet_sweep
    from madsim_tpu.fleet.lease import split_ranges
    from madsim_tpu.search.hunts import pair_hunt

    hunt = pair_hunt()
    eng = DeviceEngine(hunt.actor, hunt.cfg)
    seeds = np.arange(budget)
    range_size = 64
    kw = dict(engine=eng, faults=hunt.template, search=hunt.search(True),
              stop_on_first_bug=True, **hunt.sweep_kw)

    def best_seeds_to_bug(res):
        """Fewest seeds INTO any one range before its first find (the
        per-range analog of guided_hunt's seeds-to-bug; None = no range
        found the bug)."""
        fails = sorted(int(s) for s in res.failing_seeds)
        per = [s - r.lo + 1 for r in split_ranges(budget, range_size)
               for s in fails if r.lo <= s < r.hi]
        return min(per) if per else None

    # Warmup compiles the engine + search programs on the real shapes so
    # the timed runs measure orchestration, not XLA.
    fleet_sweep(None, hunt.cfg, seeds[:range_size], n_workers=1,
                range_size=range_size, **kw)
    t0 = walltime.perf_counter()
    independent = fleet_sweep(None, hunt.cfg, seeds, n_workers=2,
                              range_size=range_size, **kw)
    dt_ind = walltime.perf_counter() - t0
    t0 = walltime.perf_counter()
    exchanged = fleet_sweep(None, hunt.cfg, seeds, n_workers=2,
                            range_size=range_size,
                            exchange=ExchangeConfig(every=1), **kw)
    dt_exc = walltime.perf_counter() - t0

    st = exchanged.loop_stats["fleet"]
    ind_best = best_seeds_to_bug(independent)
    exc_best = best_seeds_to_bug(exchanged)
    out = {
        "budget": budget, "range_size": range_size, "exchange_every": 1,
        "independent_seeds_to_bug": ind_best,
        "exchanged_seeds_to_bug": exc_best,
        "independent_bugs_found": len(independent.failing_seeds),
        "exchanged_bugs_found": len(exchanged.failing_seeds),
        "exchanged_first_global_seed": (
            int(exchanged.failing_seeds[0]) + 1
            if exchanged.failing_seeds else None),
        "epochs_merged": st["epochs_merged"],
        "merge_inserts": st["merge_inserts"],
        "publishes": st["publishes"],
        "publish_bytes": st["publish_bytes"],
        "broadcast_bytes": st["broadcast_bytes"],
        "merged_corpus_size": int(exchanged.search.corpus_size),
        # Fleet-level evolution observatory (obs/lineage.py): ancestry
        # depth across the exchanged epochs and the merged per-operator
        # outcome table (each range's table summed).
        "lineage_depth": int(exchanged.search.lineage_depth()),
        "operator_stats": exchanged.search.operator_stats,
        "independent_wall_s": round(dt_ind, 3),
        "exchanged_wall_s": round(dt_exc, 3),
        # >0 = the exchange costs wall time vs the independent fleet
        # (epoch barriers serialize rounds + merge/broadcast work).
        "exchange_overhead_frac": round(1 - dt_ind / dt_exc, 4),
    }
    # The acceptance gate: the exchanged fleet reaches the bug in no
    # more seeds-into-a-range than the best independent range (an
    # un-found independent leg counts as range_size+1, a lower bound).
    assert exc_best is not None, \
        "exchanged fleet missed the pair bug — exchange is not chaining " \
        "corpus progress across epochs (retune fleet/exchange.py)"
    assert exc_best <= (ind_best if ind_best is not None
                        else range_size + 1), \
        f"exchanged fleet needed {exc_best} seeds vs best independent " \
        f"range's {ind_best}"
    assert len(exchanged.failing_seeds) >= len(independent.failing_seeds)
    log(f"guided_fleet[{jax.default_backend()}]: {out}")
    return out


def bench_minimize_bug(n_rows: int) -> dict:
    """Batched ddmin schedule minimization on the known-minimal
    synthetic bug (docs/triage.md; triage/synthetic.py): an ``n_rows``
    restart schedule whose failure needs exactly two rows. Tracks the
    minimizer's round/candidate economy and wall time round over round
    (tools/bench_diff.py) — the metric is how cheaply a hunt's failure
    turns into a 1-minimal repro, not seeds/s."""
    import jax

    from madsim_tpu.engine import DeviceEngine
    from madsim_tpu.triage import (PairRestartActor, PairRestartConfig,
                                   minimize, pair_schedule)
    from madsim_tpu.triage.synthetic import engine_config

    acfg = PairRestartConfig()
    cfg = engine_config(acfg)
    eng = DeviceEngine(PairRestartActor(acfg), cfg)
    need = (n_rows // 6, (2 * n_rows) // 3)
    faults = pair_schedule(n_rows=n_rows, need=need, acfg=acfg)
    kw = dict(engine=eng, chunk_steps=32, max_steps=4_000)

    # Warmup: compiles every candidate-batch bucket the loop will use.
    res = minimize(None, cfg, 7, faults, **kw)
    t0 = walltime.perf_counter()
    res = minimize(None, cfg, 7, faults, **kw)
    dt = walltime.perf_counter() - t0

    assert res.final_rows == 2 and res.one_minimal, res.summary()
    assert (res.schedule == faults[list(need)]).all(), \
        f"minimizer missed the known-minimal rows {need}"
    out = {"n_rows": n_rows,
           "final_rows": res.final_rows,
           "rounds": res.rounds,
           "candidates_evaluated": res.candidates_evaluated,
           "one_minimal": bool(res.one_minimal),
           "wall_s": round(dt, 3),
           "candidates_per_sec": round(res.candidates_evaluated / dt, 1)
           if dt > 0 else None,
           "rounds_per_sec": round(res.rounds / dt, 2) if dt > 0 else None}
    log(f"minimize_bug[{jax.default_backend()}]: {dt:.2f}s  {out}")
    return out


# ---------------------------------------------------------------------------
# Cross-engine validation: TPU<->CPU bit-exactness
# ---------------------------------------------------------------------------

def bench_crosscheck(n_worlds: int) -> dict:
    import jax

    from madsim_tpu.engine import DeviceEngine, EngineConfig, RaftActor, RaftDeviceConfig
    from madsim_tpu.engine.crosscheck import crosscheck_backends

    rcfg = RaftDeviceConfig(n=3)
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                       t_limit_us=1_000_000)
    eng = DeviceEngine(RaftActor(rcfg), cfg)
    out = crosscheck_backends(eng, np.arange(n_worlds), max_steps=5_000)
    # Also crosscheck under fault schedules (exercises the fault path).
    faults = make_fault_schedules(n_worlds, 3, 1_000_000, seed=1)
    eng2 = DeviceEngine(RaftActor(rcfg), cfg)
    out_f = crosscheck_backends(eng2, np.arange(n_worlds), faults=faults,
                                max_steps=5_000)
    out["bitwise_equal_with_faults"] = out_f["bitwise_equal"]
    # The contract holds for every actor family, not just the flagship:
    # primary-backup and two-phase-commit crosscheck bitwise too (smaller
    # batches — the point is coverage, not throughput).
    from madsim_tpu.engine import (PBActor, PBDeviceConfig, TPCActor,
                                   TPCDeviceConfig)

    pb = DeviceEngine(
        PBActor(PBDeviceConfig(n=3, n_writes=4)),
        EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                     t_limit_us=1_500_000, loss_rate=0.05))
    out["bitwise_equal_pb"] = crosscheck_backends(
        pb, np.arange(min(n_worlds, 1024)), max_steps=5_000)["bitwise_equal"]
    tpc = DeviceEngine(
        TPCActor(TPCDeviceConfig(n=4, n_txns=4, buggy_presumed_commit=True)),
        EngineConfig(n_nodes=4, outbox_cap=5, queue_cap=64,
                     t_limit_us=1_500_000, loss_rate=0.1))
    out["bitwise_equal_tpc"] = crosscheck_backends(
        tpc, np.arange(min(n_worlds, 1024)), max_steps=5_000)["bitwise_equal"]
    log(f"crosscheck: {out}")
    return out


# ---------------------------------------------------------------------------
# Cross-engine validation: time to first bug, host vs device
# ---------------------------------------------------------------------------

def bench_time_to_first_bug(host_seeds_n: int, device_worlds: int) -> dict:
    """Both engines hunt the same injected bug (double voting breaking
    election safety, the buggy_double_vote switch present in BOTH
    models/raft.py and engine/raft_actor.py). Host = sequential seeds,
    reference style; device = one vmapped batch.

    Reported as *expected* wall seconds to first detection, derived from
    each engine's measured per-seed bug rate and seeds/sec (a single
    measured first-hit time is one geometric sample — pure luck). Also
    cross-validates that the two engines find the bug at comparable
    per-seed densities (the BASELINE.json second metric)."""
    import jax

    import madsim_tpu as ms
    from madsim_tpu.models.raft import (
        RaftCluster, RaftOptions, RaftInvariantViolation)
    from madsim_tpu.engine import DeviceEngine, EngineConfig, RaftActor, RaftDeviceConfig

    # Host: fixed number of seeds; count hits.
    async def world():
        from madsim_tpu import time as simtime

        cluster = RaftCluster(3, RaftOptions(persist=False,
                                             buggy_double_vote=True))
        while simtime.monotonic() < 2.0:
            await simtime.sleep(0.05)

    t0 = walltime.perf_counter()
    host_hits = 0
    for seed in range(host_seeds_n):
        rt = ms.Runtime(seed=seed)
        rt.set_time_limit(60.0)
        try:
            rt.block_on(world())
        except RaftInvariantViolation:
            host_hits += 1
    host_dt = walltime.perf_counter() - t0
    host_rate = host_hits / host_seeds_n
    host_sps = host_seeds_n / host_dt
    host_expected = (1.0 / host_rate) / host_sps if host_hits else None
    log(f"host bug hunt: {host_hits}/{host_seeds_n} seeds hit "
        f"({host_sps:.1f} seeds/s)")

    # Device: one batch of worlds with the same bug switch.
    rcfg = RaftDeviceConfig(n=3, buggy_double_vote=True)
    cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                       t_limit_us=2_000_000, stop_on_bug=False)
    eng = DeviceEngine(RaftActor(rcfg), cfg)
    warm = eng.run(eng.init(np.arange(device_worlds)), max_steps=4_000)
    jax.block_until_ready(warm)

    # init and run timed separately (docs/perf.md: init was previously
    # inside the window, hiding where bench-environment variance lives).
    t0 = walltime.perf_counter()
    state = eng.init(np.arange(device_worlds))
    jax.block_until_ready(state)
    init_dt = walltime.perf_counter() - t0
    t0 = walltime.perf_counter()
    state = eng.run(state, max_steps=4_000)
    jax.block_until_ready(state)
    run_dt = walltime.perf_counter() - t0
    obs = eng.observe(state)
    # Cost-model record at the exact shapes the timed run used (lower
    # only — the donated buffers are never re-executed).
    xla_cost = xla_cost_record(eng, state, 4_000)
    dev_dt = init_dt + run_dt
    n_bugs = int(obs["bug"].sum())
    assert n_bugs > 0, "device engine failed to find the injected bug"
    dev_rate = n_bugs / device_worlds
    # Measured world-utilization of the monolithic batch (docs/perf.md
    # "the straggler tail"): mean vs max masked steps across the batch.
    max_steps_run = int(obs["steps"].max())
    batch_util = (float(obs["steps"].mean()) / max_steps_run
                  if max_steps_run else 0.0)

    # World recycling (docs/perf.md): the same hunt streamed through a
    # bounded batch with stop_on_first_bug, refilling retired slots from
    # the seed cursor. Reports the per-chunk occupancy telemetry the
    # monolithic run cannot have.
    from madsim_tpu.parallel.sweep import sweep as device_sweep

    rcfg_s = RaftDeviceConfig(n=3, buggy_double_vote=True)
    cfg_s = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                         t_limit_us=2_000_000, stop_on_bug=True)
    eng_s = DeviceEngine(RaftActor(rcfg_s), cfg_s)
    batch_w = max(256, device_worlds // 8)
    # chunk_steps=64 (was 256): with supersteps the host no longer pays a
    # dispatch+sync per chunk, so fine-grained chunks are affordable and
    # buy 4x finer on-device stop_on_first_bug granularity — the device
    # exits within 64 steps of the first detection instead of 256.
    t0 = walltime.perf_counter()
    res = device_sweep(None, cfg_s, np.arange(device_worlds), engine=eng_s,
                       chunk_steps=64, max_steps=4_000,
                       stop_on_first_bug=True, recycle=True,
                       batch_worlds=batch_w)
    recycled_dt = walltime.perf_counter() - t0
    recycled = {
        "batch_worlds": batch_w,
        "world_utilization": round(res.world_utilization, 4),
        "n_chunks": int(res.n_active_history.size),
        "found_bug": bool(res.bug.any()),
        "wall_s_incl_compile": round(recycled_dt, 3),
    }
    # Whole-hunt residency (docs/perf.md): the SAME pinned hunt with the
    # occupancy loop fused into one device program — refill, compaction,
    # and the seed cursor run in-loop, so the host issues O(1)
    # mega-dispatches instead of one dispatch per epoch. Bitwise
    # equality with the pipelined run is tier-1 (tests/test_fused.py);
    # here the dispatch economics land in bench_results.json so
    # tools/bench_diff.py can hold the >=4x reduction round over round.
    t0 = walltime.perf_counter()
    res_f = device_sweep(None, cfg_s, np.arange(device_worlds),
                         engine=eng_s, chunk_steps=64, max_steps=4_000,
                         stop_on_first_bug=True, recycle=True,
                         batch_worlds=batch_w, fused=True)
    fused_dt = walltime.perf_counter() - t0
    assert res_f.failing_seeds == res.failing_seeds, \
        "fused hunt diverged from the pipelined hunt on the bench config"
    recycled["fused_wall_s_incl_compile"] = round(fused_dt, 3)
    recycled["fused_dispatch_reduction"] = round(
        res.loop_stats["dispatches_per_seed"]
        / max(res_f.loop_stats["dispatches_per_seed"], 1e-9), 2)
    # Observability record (docs/observability.md): the hunt config swept
    # metrics-on at a capped batch, with per-seed frames aggregated over
    # the fleet. Separate engine — metrics is a static knob; every timed
    # run above stays the exact metrics-off program.
    import dataclasses as _dc

    rec_w_m = min(device_worlds, 2_048)
    eng_m = DeviceEngine(RaftActor(rcfg), _dc.replace(cfg, metrics=True))
    res_m = device_sweep(None, eng_m.cfg, np.arange(rec_w_m), engine=eng_m,
                         chunk_steps=64, max_steps=4_000)
    sim_metrics = {"n_worlds": rec_w_m, **res_m.metrics["aggregate"]}
    coverage = res_m.coverage.to_json()
    del eng_m, res_m

    # Flight-recorder pricing (docs/observability.md "The flight
    # recorder"): the SAME monolithic batch with the K=64 per-world
    # event ring aboard (EngineConfig(blackbox=64)), timed against the
    # blackbox-off run above. The off run IS the baseline — bitwise
    # invisibility keeps it the exact pre-blackbox program — so the
    # deltas here price the opt-in: ring state per world, ring-write
    # flops, and the seeds/s tax. tools/bench_diff.py tracks all three
    # round over round; `make smoke` asserts the keys.
    bb_k = 64
    eng_b = DeviceEngine(RaftActor(rcfg), _dc.replace(cfg, blackbox=bb_k))
    warm_b = eng_b.run(eng_b.init(np.arange(device_worlds)),
                       max_steps=4_000)
    jax.block_until_ready(warm_b)
    del warm_b
    state_b = eng_b.init(np.arange(device_worlds))
    jax.block_until_ready(state_b)
    t0 = walltime.perf_counter()
    state_b = eng_b.run(state_b, max_steps=4_000)
    jax.block_until_ready(state_b)
    bb_run_dt = walltime.perf_counter() - t0
    xla_cost_b = xla_cost_record(eng_b, state_b, 4_000)
    obs_b = eng_b.observe(state_b)
    assert bool(np.array_equal(np.asarray(obs_b["bug"]),
                               np.asarray(obs["bug"]))), \
        "blackbox-on run diverged from blackbox-off on the bug vector"

    def _bb_delta(on, off, nd=2):
        return (round(on - off, nd)
                if on is not None and off is not None else None)

    blackbox = {
        "k": bb_k,
        "seeds_per_sec": round(device_worlds / bb_run_dt, 1),
        "seeds_per_sec_off": round(device_worlds / run_dt, 1),
        "seeds_per_sec_ratio": round(run_dt / bb_run_dt, 4),
        "state_bytes_per_world": xla_cost_b["state_bytes_per_world"],
        "state_bytes_per_world_off": xla_cost["state_bytes_per_world"],
        "state_bytes_per_world_delta": _bb_delta(
            xla_cost_b["state_bytes_per_world"],
            xla_cost["state_bytes_per_world"]),
        "flops_per_world_step": xla_cost_b["flops_per_world_step"],
        "flops_per_world_step_off": xla_cost["flops_per_world_step"],
        "flops_per_world_step_delta": _bb_delta(
            xla_cost_b["flops_per_world_step"],
            xla_cost["flops_per_world_step"]),
    }
    del eng_b, state_b, obs_b

    # Expected seeds to first bug = 1/rate; the device explores
    # device_worlds/dev_dt seeds per second.
    dev_expected = (1.0 / dev_rate) / (device_worlds / dev_dt)
    host_ci = _wilson_ci(host_hits, host_seeds_n)
    dev_ci = _wilson_ci(n_bugs, device_worlds)
    ci_overlap = host_ci[0] <= dev_ci[1] and dev_ci[0] <= host_ci[1]
    ratio = host_rate / dev_rate if dev_rate else float("inf")
    out = {
        "host_bug_rate": round(host_rate, 4),
        "host_bug_rate_ci95": [round(x, 4) for x in host_ci],
        "host_seeds_per_sec": round(host_sps, 2),
        "host_expected_s_to_first_bug": (round(host_expected, 3)
                                         if host_expected else None),
        "device_bug_rate": round(dev_rate, 4),
        "device_bug_rate_ci95": [round(x, 4) for x in dev_ci],
        "device_init_s": round(init_dt, 3),
        "device_run_s": round(run_dt, 3),
        "device_seeds_per_sec": round(device_worlds / dev_dt, 1),
        "device_run_seeds_per_sec": round(device_worlds / run_dt, 1),
        "device_expected_s_to_first_bug": round(dev_expected, 4),
        "device_first_failing_seed": int(np.argmax(obs["bug"])),
        "device_world_utilization": round(batch_util, 4),
        # Per-step XLA cost model of this engine config (the op-budget
        # regression axis; docs/perf.md "Single-pass insert + donation").
        "xla_cost": xla_cost,
        # Fleet-aggregate simulation metrics of the metrics-on probe
        # sweep (docs/observability.md; asserted by `make smoke`).
        "sim_metrics": sim_metrics,
        # Behavior-coverage ledger rollup of the same probe sweep
        # (docs/observability.md "reading the novelty curve").
        "coverage": coverage,
        # Flight-recorder on-vs-off pricing at K=64
        # (docs/observability.md "The flight recorder").
        "blackbox": blackbox,
        "recycled_hunt": recycled,
        # Orchestration breakdown of the recycled hunt's chunk loop
        # (docs/perf.md "Pipelined orchestration"): the acceptance axes
        # are host_decision_s vs loop_wall_s (stall fraction) and
        # chunks_per_dispatch (superstep fan-in).
        "sweep_loop": res.loop_stats,
        # The same hunt under whole-hunt residency (docs/perf.md
        # "Whole-hunt residency"): the acceptance axes are
        # seeds_per_dispatch / dispatches_per_seed (>=4x fewer than the
        # pipelined row above) and epochs_on_device (every refill epoch
        # the host no longer orchestrates).
        "sweep_loop_fused": res_f.loop_stats,
        # Statistical gate (docs/perf.md): Wilson-CI overlap, with a
        # bounded model-difference allowance (the two engines share the
        # bug mechanism, not the timing model) — replaces the toothless
        # [0.1, 10] band.
        "rates_comparable": bool(host_rate > 0 and dev_rate > 0
                                 and (ci_overlap or 1 / 3 <= ratio <= 3.0)),
        "rates_ci_overlap": bool(ci_overlap),
        "speedup": (round(host_expected / dev_expected, 1)
                    if host_expected else None),
    }
    log(f"time_to_first_bug: {out}")
    return out


def _wilson_ci(hits: int, n: int, z: float = 1.96):
    """Wilson 95% interval for a binomial rate (docs/perf.md gate)."""
    if n == 0:
        return (0.0, 1.0)
    p = hits / n
    denom = 1 + z * z / n
    center = (p + z * z / (2 * n)) / denom
    half = z * ((p * (1 - p) / n + z * z / (4 * n * n)) ** 0.5) / denom
    return (max(0.0, center - half), min(1.0, center + half))


# ---------------------------------------------------------------------------
# Config 8: the host<->device BRIDGE — sweep the UNMODIFIED rpc ping-pong
# host workload (config 1's world) across seeds with the device decision
# kernel (bridge/), vs the same seeds run sequentially on the pure host
# engine. Reports the honest speedup and where the time goes; per-seed
# trajectories are bit-identical across the two engines (tests/test_bridge).
# ---------------------------------------------------------------------------

def bench_bridge_sweep(n_host: int, n_bridge: int) -> dict:
    import madsim_tpu as ms
    from madsim_tpu import time as simtime
    from madsim_tpu.bridge import sweep
    from madsim_tpu.net import Endpoint, rpc

    ROUNDS = 20

    async def world():
        h = ms.Handle.current()

        async def server_init():
            ep = await Endpoint.bind("10.0.0.1:9000")

            async def handle(req, data):
                return BenchPing(req.n + 1), b""

            rpc.add_rpc_handler_with_data(ep, BenchPing, handle)
            await simtime.sleep(1e6)

        h.create_node(name="server", ip="10.0.0.1", init=server_init)
        client = h.create_node(name="client", ip="10.0.0.2")
        done = ms.sync.SimFuture()

        async def client_body():
            ep = await Endpoint.bind("10.0.0.2:0")
            for i in range(ROUNDS):
                await rpc.call_with_data(ep, "10.0.0.1:9000", BenchPing(i),
                                         b"x" * 64, timeout=5.0)
            done.set_result(True)

        client.spawn(client_body())

        async def _await(f):
            return await f

        return await simtime.timeout(600, _await(done))

    import os

    jobs = os.cpu_count() or 1
    out = {"world": f"rpc_pingpong x{ROUNDS} (bench config 1)",
           "jobs": jobs}

    t0 = walltime.perf_counter()
    polls = 0
    for seed in range(n_host):
        rt = ms.Runtime(seed=seed)
        assert rt.block_on(world())
        polls += rt.task.poll_count
    host_dt = walltime.perf_counter() - t0
    host_rate = n_host / host_dt
    out.update({
        "host_seeds_per_sec": round(host_rate, 1),
        "host_us_per_poll": round(host_dt / polls * 1e6, 2),
    })

    from madsim_tpu.bridge.runtime import sweep_profiled

    # Warm with the real world at the real W: the jitted step is process-
    # cached per (cap, k_events), so the later sweeps are steady state.
    # The headline rate comes from a PLAIN sweep (no profiling overhead);
    # the breakdown comes from a separate profiled sweep.
    t0 = walltime.perf_counter()
    sweep(world, list(range(n_bridge)))
    cold_dt = walltime.perf_counter() - t0
    t0 = walltime.perf_counter()
    outs = sweep(world, list(range(n_bridge)))
    dt = walltime.perf_counter() - t0
    assert all(o.error is None for o in outs)
    _outs_p, prof = sweep_profiled(world, list(range(n_bridge)))
    rate = n_bridge / dt
    out.update({
        "bridge_w": n_bridge,
        "bridge_seeds_per_sec": round(rate, 1),
        "bridge_cold_seeds_per_sec": round(n_bridge / cold_dt, 1),
        "bridge_vs_host": round(rate / host_rate, 2),
        "bridge_round_breakdown_ms": {
            k[:-2]: round(prof[k] / max(prof["rounds"], 1) * 1e3, 2)
            for k in ("host_s", "pack_s", "dispatch_s", "settle_s")},
        "bridge_rounds": prof["rounds"],
        # The bridge kernel's device-resident observability block,
        # aggregated over the fleet (docs/observability.md), plus the
        # per-slot behavior-coverage sketch over the same counters.
        "sim_metrics": prof.get("sim_metrics"),
        "coverage": prof.get("coverage"),
        "note": ("per-seed trajectories bit-identical to host "
                 "(tests/test_bridge.py); task bodies are serial Python, "
                 "so single-core speedup is Amdahl-bounded by the measured "
                 "~5-15% decision-kernel fraction — breakdown and ceiling "
                 "analysis in docs/bridge.md"),
    })
    # -- the forked worker pool (bridge/pool.py, ROADMAP item 4) ----------
    # J workers run the task bodies behind the SAME shared kernel, each
    # packing its slot slice straight into shared memory. Recorded per
    # (J, W): throughput vs host, the parent-observed per-phase wall
    # windows, and pool_overhead_frac = (pool - serial)/serial wall on
    # the same seeds — on a 1-core box the honest number is overhead,
    # not speedup (docs/bridge.md "Parallel task bodies"); a multi-core
    # runner's bridge_vs_host at J=4 is the scaling headline.
    from madsim_tpu.bridge.pool import sweep_pooled

    smoke = n_bridge <= 64
    pool: dict = {}
    for Wp in ((64,) if smoke else (64, 512)):
        pseeds = list(range(Wp))
        sweep(world, pseeds)  # warm this width's jit shapes off the clock
        t0 = walltime.perf_counter()
        outs = sweep(world, pseeds)
        serial_dt = walltime.perf_counter() - t0
        assert all(o.error is None for o in outs)
        for J in ((1, 2) if smoke else (1, 2, 4)):
            stats: dict = {}
            t0 = walltime.perf_counter()
            outs = sweep_pooled(world, pseeds, jobs=J, stats=stats)[0]
            pdt = walltime.perf_counter() - t0
            assert all(o.error is None for o in outs)
            rounds = max(stats["rounds"], 1)
            pool[f"j{J}_w{Wp}"] = {
                "seeds_per_sec": round(Wp / pdt, 1),
                "bridge_vs_host": round((Wp / pdt) / host_rate, 2),
                "pool_overhead_frac": round((pdt - serial_dt) / serial_dt,
                                            3),
                # Parent-observed phase windows: host = workers running
                # task bodies (+ fork barrier), pack = shared-memory
                # pack barrier, dispatch = the jitted kernel step,
                # settle = worker settle + drain chain.
                "host_ms_per_round": round(
                    stats["host_s"] / rounds * 1e3, 3),
                "pack_ms_per_round": round(
                    stats["pack_s"] / rounds * 1e3, 3),
                "dispatch_ms_per_round": round(
                    stats["dispatch_s"] / rounds * 1e3, 3),
                "settle_ms_per_round": round(
                    stats["settle_s"] / rounds * 1e3, 3),
                # The parent's OWN per-round Python work (reset apply +
                # bucket calc + broadcast bookkeeping, no waiting): the
                # pack loop is gone from the parent profile, so this
                # stays ~O(1) in W — compare across the w64/w512 rows.
                "parent_ms_per_round": round(
                    stats["parent_s"] / rounds * 1e3, 4),
                "rounds": stats["rounds"],
                "drain_rounds": stats["drain_rounds"],
            }
    out["pool"] = pool
    out["pool_note"] = (
        "jobs=J forked pool behind one shared kernel, bitwise == jobs=1 "
        "== serial (tests/test_bridge_pool.py); this box has "
        f"{jobs} core(s), so interpret bridge_vs_host at J>1 "
        "accordingly — on 1 core the gate is pool_overhead_frac, not "
        "speedup")
    log(f"bridge_sweep: {out}")
    return out


# ---------------------------------------------------------------------------
# Main
# ---------------------------------------------------------------------------

# Configs that never touch the device engine; every other config (and the
# 3-node headline) measures the chip and, without --smoke, refuses to run
# anywhere else.
_HOST_CONFIGS = frozenset({"rpc", "rpc_real", "grpc", "postgres"})


def _require_tpu() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"no TPU found: JAX's default device is {dev.platform} "
            f"({dev.device_kind}); device configs measure the chip only "
            f"(--smoke runs them on any backend)")


# (short name, JSON key, runner). Short names are the --only/--break-config
# vocabulary; runners take the parsed args.
_CONFIGS = [
    ("rpc", "rpc_pingpong",
     lambda a: bench_rpc_pingpong(64 if a.smoke else 1_000)),
    ("rpc_real", "rpc_real",
     lambda a: bench_rpc_real(256 if a.smoke else 2_000)),
    ("grpc", "grpc_chaos",
     lambda a: bench_grpc_chaos(n_clients=2 if a.smoke else 5,
                                sim_seconds=2.0 if a.smoke else 10.0)),
    ("postgres", "postgres_skew",
     lambda a: bench_postgres_skew(16 if a.smoke else 200)),
    ("crosscheck", "crosscheck",
     lambda a: bench_crosscheck(128 if a.smoke else 4_096)),
    ("bug", "time_to_first_bug",
     lambda a: bench_time_to_first_bug(
         host_seeds_n=16 if a.smoke else 128,
         device_worlds=1_024 if a.smoke else 65_536)),
    ("5node", "madraft_5node",
     lambda a: bench_madraft_5node(256 if a.smoke else 100_000)),
    ("fleet", "fleet_sweep",
     lambda a: bench_fleet_sweep(128 if a.smoke else 4_096)),
    ("minimize", "minimize_bug",
     lambda a: bench_minimize_bug(16 if a.smoke else 64)),
    ("guided", "guided_hunt",
     lambda a: bench_guided_hunt(256 if a.smoke else 512)),
    # Budget pinned at 320/512 regardless of --smoke depth: the
    # exchanged fleet's first find lands in epoch 4 (global seed ~294),
    # and per-range evolution is budget-prefix-stable, so 320 covers
    # the gate at smoke cost.
    ("gfleet", "guided_fleet",
     lambda a: bench_guided_fleet(320 if a.smoke else 512)),
    ("bridge", "bridge_sweep",
     lambda a: bench_bridge_sweep(n_host=16 if a.smoke else 64,
                                  n_bridge=64 if a.smoke else 512)),
]


def _child_argv(args, short: str) -> list:
    argv = [sys.executable, __file__, "--run-config", short]
    if args.smoke:
        argv.append("--smoke")
    if short == "3node":
        # Only the headline child consumes the sizing overrides.
        if args.worlds:
            argv += ["--worlds", str(args.worlds)]
        if args.host_seeds:
            argv += ["--host-seeds", str(args.host_seeds)]
    if args.break_config:
        argv += ["--break-config", args.break_config]
    return argv


def _run_config_subprocess(args, short: str, key: str) -> dict:
    """Run one config in a child process (VERDICT r2 item 3, hardened).

    Process isolation covers the crash classes in-process try/except cannot
    — XLA/C++ aborts, SIGSEGV, OOM kills — and, because the parent itself
    never initializes JAX, sequential children can each acquire the
    (single-process-locked) TPU cleanly."""
    import threading

    cmd = _child_argv(args, short)
    limit = 600 if args.smoke else 3600
    # Stream the child's stderr live (progress logs) while also keeping it
    # for the error tail; capture stdout (the one JSON line) separately.
    # Each pipe has exactly one reader thread — communicate() would race
    # the stderr pump for the same fd.
    child = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                             stderr=subprocess.PIPE, text=True)
    err_lines: list = []
    out_box: list = []

    def pump_err():
        for line in child.stderr:
            sys.stderr.write(line)
            sys.stderr.flush()
            err_lines.append(line)

    def pump_out():
        out_box.append(child.stdout.read())

    threads = [threading.Thread(target=pump_err, daemon=True),
               threading.Thread(target=pump_out, daemon=True)]
    for t in threads:
        t.start()
    try:
        child.wait(timeout=limit)
    except subprocess.TimeoutExpired:
        child.kill()
        child.wait()
        log(f"{key} FAILED: timeout after {limit}s")
        return {"error": f"timeout after {limit}s"}
    finally:
        for t in threads:
            t.join(timeout=5)
    if child.returncode != 0:
        tail = [ln.strip() for ln in err_lines[-3:]]
        log(f"{key} FAILED: rc={child.returncode}")
        return {"error": f"rc={child.returncode}: " + " | ".join(tail)}
    try:
        return json.loads(out_box[0].strip().splitlines()[-1])
    except (ValueError, IndexError) as exc:
        return {"error": f"bad child output: {exc}"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run (CI/verify)")
    ap.add_argument("--worlds", type=int, default=None)
    ap.add_argument("--host-seeds", type=int, default=None)
    ap.add_argument("--only", type=str, default=None,
                    help="comma list: 3node,rpc,rpc_real,grpc,postgres,"
                         "5node,fleet,minimize,guided,crosscheck,bug,"
                         "bridge (3node = the headline)")
    ap.add_argument("--break-config", type=str, default=None,
                    help="(testing) name of a config to force-fail, proving "
                         "failure isolation keeps the headline alive")
    ap.add_argument("--run-config", type=str, default=None,
                    help="(internal) child mode: run ONE config, print its "
                         "JSON dict, exit nonzero on failure")
    ap.add_argument("--in-process", action="store_true",
                    help="run configs in-process (debugging; loses native-"
                         "crash isolation)")
    args = ap.parse_args()

    shorts = {c[0] for c in _CONFIGS}
    _BREAKABLE = shorts | {"3node_device", "3node_host"}
    if args.break_config is not None and args.break_config not in _BREAKABLE:
        ap.error(f"--break-config must be one of {sorted(_BREAKABLE)}")

    def boom(*_a, **_kw):
        raise RuntimeError("forced failure (--break-config)")

    def pick(name, fn):
        return boom if args.break_config == name else fn

    def headline(args) -> dict:
        """Device + host headline rates; per-half errors go in the dict."""
        smoke = args.smoke
        # 512k worlds is the measured single-chip sweet spot (HBM-resident,
        # past the per-iteration overhead knee; 1M+ starts regressing).
        n_worlds = args.worlds or (256 if smoke else 524_288)
        n_host = args.host_seeds or (8 if smoke else 32)
        if not smoke:
            _require_tpu()
        out = {}
        try:
            out["dev_rate"] = pick("3node_device", device_seed_rate)(n_worlds)
        except Exception as exc:
            log(f"headline device FAILED: {type(exc).__name__}: {exc}")
            out["dev_error"] = f"{type(exc).__name__}: {exc}"
        try:
            host = pick("3node_host", host_seed_rate)(n_host)
            out["host"] = host
            out["host_rate"] = host["seeds_per_sec"]
        except Exception as exc:
            log(f"headline host baseline FAILED: {type(exc).__name__}: {exc}")
            out["host_error"] = f"{type(exc).__name__}: {exc}"
        return out

    if args.run_config is not None:
        # Child mode: one config, one JSON line, rc=1 on any failure.
        if args.run_config == "3node":
            print(json.dumps(headline(args)), flush=True)
            return
        for short, _key, runner in _CONFIGS:
            if short == args.run_config:
                if not args.smoke and short not in _HOST_CONFIGS:
                    _require_tpu()
                print(json.dumps(pick(short, runner)(args)), flush=True)
                return
        ap.error(f"--run-config must be one of {sorted(shorts | {'3node'})}")

    only = set(args.only.split(",")) if args.only else None

    def want(name: str) -> bool:
        return only is None or name in only

    # Headline FIRST (its number must survive anything later), then each
    # other config in its own child process, so a native-level crash
    # (SIGSEGV/abort/OOM) in any config cannot take the others down — and
    # the parent stays JAX-free throughout (the TPU is a single-process
    # resource, released as each sequential child exits).
    configs = {}
    dev_rate = host_rate = None
    if want("3node"):
        if args.in_process:
            h = headline(args)
        else:
            h = _run_config_subprocess(args, "3node", "headline")
        dev_rate, host_rate = h.get("dev_rate"), h.get("host_rate")
        if "host" in h:
            # The measured denominator of vs_baseline, with its per-event
            # cost model (events = scheduler polls).
            configs["host_engine"] = h["host"]
        errs = {k: v for k, v in h.items()
                if k in ("error", "dev_error", "host_error")}
        if errs:
            configs["headline_errors"] = errs

    for short, key, runner in _CONFIGS:
        if not want(short):
            continue
        if args.in_process:
            try:
                if not args.smoke and short not in _HOST_CONFIGS:
                    _require_tpu()
                configs[key] = pick(short, runner)(args)
            except Exception as exc:
                log(f"{key} FAILED: {type(exc).__name__}: {exc}")
                configs[key] = {"error": f"{type(exc).__name__}: {exc}"}
        else:
            configs[key] = _run_config_subprocess(args, short, key)

    result = {
        "metric": "madraft_3node_1s_seeds_per_sec",
        "value": round(dev_rate, 2) if dev_rate else None,
        "unit": "seeds/s",
        "vs_baseline": (round(dev_rate / host_rate, 2)
                        if dev_rate and host_rate else None),
        # vs_baseline denominator caveat (VERDICT r1/r2): the baseline is
        # THIS repo's host engine (Python coroutines over the native C++
        # RNG/timer/scheduler-decision core), not the reference's Rust
        # engine (not runnable here). configs.host_engine carries its
        # measured events/s and us/event so the denominator is a
        # quantified cost model, not a guess; the residual per-event cost
        # is Python coroutine frames (~60% of runtime), which native
        # bookkeeping cannot remove.
        "baseline_note": "host = this repo's engine (Python coroutines + "
                         "native C++ core), single-seed; see "
                         "configs.host_engine for events/s and us/event",
        "configs": configs,
    }
    # The durable record FIRST (VERDICT r5: two rounds lost their headline
    # numbers to truncated stdout tails) — `make smoke` asserts this file
    # parses and carries the headline keys. Written atomically so a killed
    # run can't leave a half-written JSON shadowing the previous record.
    import os
    import tempfile

    out_path = os.environ.get("MADSIM_BENCH_RESULTS", "bench_results.json")
    fd, tmp_path = tempfile.mkstemp(
        dir=os.path.dirname(out_path) or ".", suffix=".tmp")
    with os.fdopen(fd, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    os.replace(tmp_path, out_path)
    print(json.dumps(result), flush=True)
    # Outside --smoke a failed config is a failed run: a null or missing
    # number must not exit 0.
    failed = sorted(k for k, v in configs.items()
                    if isinstance(v, dict)
                    and {"error", "dev_error", "host_error"} & set(v))
    if failed and not args.smoke:
        log(f"FAILED configs: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
