"""Host-side half of the host↔device bridge: lockstep sweep of W worlds.

``sweep(world_fn, seeds)`` runs one *unmodified* host-engine workload —
any coroutine written against the madsim_tpu API (Endpoint/RPC, gRPC
shims, sleep/timeout, kill/restart/clog chaos) — for many seeds at once:

- each seed gets a full host world (executor, nodes, coroutines, NetSim
  mailboxes) exactly like ``Runtime``; task bodies always run on host —
  the one thing that cannot be vectorized (SURVEY §7 "hard parts");
- the *decision kernel* — timer wheel, next-event selection, virtual
  clock advance, per-message loss/latency sampling — lives on the device
  as [W]-shaped arrays, advanced by one jitted XLA step per lockstep
  round (`bridge/kernel.py`).

Determinism contract: per seed, a bridge world walks the **bit-identical
trajectory** of a plain ``Runtime`` world (same poll sequence, same
virtual timestamps, same RNG streams) — the property tested in
tests/test_bridge.py. It holds because every framework draw is addressed
as (seed, purpose-stream, counter) (`core/rng.py`) and the device samples
the same counters with the same integer math.

Reference parity: this is the batched analog of the multi-seed test
driver (`madsim/src/sim/runtime/builder.rs:118-136`) — the reference
fans seeds out to OS threads; here the per-seed decision work fans into
one device batch while hosts bodies run under the GIL, and seed batches
shard across chips via the parallel/ meshes.
"""
from __future__ import annotations

import copy
import inspect
import os
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from ..core import context
from ..core.config import Config
from ..core.rng import GlobalRng, loss_threshold
from ..core.runtime import Runtime
from ..core.task import Deadlock, TimeLimitExceeded
from ..core.timewheel import NANOS_PER_SEC, TIMER_MAX_NS, TimeRuntime, to_ns
from ..net.addr import ip_is_loopback, unspecified_for
from ..net.netsim import NetSim
from ..net.network import LOCALHOST_V4
from .kernel import BridgeKernel, HostBatch, StepOut, bucket


class _TimerHandle:
    """Cancellation handle for a bridge timer (TimerEntry.cancel parity)."""

    __slots__ = ("_time", "seq")

    def __init__(self, time: "BridgeTime", seq: int):
        self._time = time
        self.seq = seq

    def cancel(self) -> None:
        self._time.cancel_seq(self.seq)


class _Send(NamedTuple):
    ctr: int       # NET-stream counter of the loss draw (latency = ctr+1)
    base_ns: int   # elapsed at the send
    slot: int      # delivery lane slot (-1 for count-only sends)
    seq: int
    thr: int       # loss threshold (u64, clamped)
    lossall: bool  # loss rate >= 1.0
    lat_lo: int
    lat_w: int
    live: bool     # has a destination socket


class BridgeTime(TimeRuntime):
    """TimeRuntime whose wheel lives on the device: ``add_timer_at`` and
    ``cancel`` record lane operations; the sweep driver ships them each
    lockstep round and dispatches the popped events. The clock is a local
    mirror (host advances it during polls; the driver overwrites it with
    the device's post-advance value)."""

    def __init__(self, rng: GlobalRng, cap: int):
        super().__init__(rng)
        self._native_heap = None  # the wheel is device-resident
        self.cap = cap
        self._free = list(range(cap - 1, -1, -1))
        # slot -> (deadline, seq) recorded but not yet shipped this round.
        self.pending_add: Dict[int, Tuple[int, int]] = {}
        self.cancels: List[int] = []          # device-resident cancels
        self.sends: List[_Send] = []
        self.send_cbs: List[Optional[Callable]] = []
        self.callbacks: Dict[int, Tuple[Callable, int]] = {}

    # -- the TimeRuntime surface ------------------------------------------
    def add_timer_at(self, deadline_ns: int, callback: Callable[[], None]):
        # Same clamp as the host wheel (timewheel.py): TIMER_MAX_NS is one
        # below the device kernel's empty-lane sentinel, so an over-range
        # timer stays visible to has_timer instead of reading as "no timer"
        # (which would report a spurious Deadlock the host never sees).
        deadline_ns = min(max(deadline_ns, self.elapsed_ns), TIMER_MAX_NS)
        seq = self._seq
        self._seq += 1
        slot = self._alloc()
        self.pending_add[slot] = (deadline_ns, seq)
        self.callbacks[seq] = (callback, slot)
        return _TimerHandle(self, seq)

    def cancel_seq(self, seq: int) -> None:
        ent = self.callbacks.pop(seq, None)
        if ent is None:
            return  # already fired or cancelled
        _cb, slot = ent
        pend = self.pending_add.get(slot)
        if pend is not None and pend[1] == seq:
            del self.pending_add[slot]  # never reached the device
        else:
            self.cancels.append(slot)
        self._free.append(slot)

    def next_deadline_ns(self):  # pragma: no cover — driver-owned
        raise NotImplementedError("bridge worlds are driven by sweep()")

    def advance_to_next_event(self):  # pragma: no cover — driver-owned
        raise NotImplementedError("bridge worlds are driven by sweep()")

    # -- bridge bookkeeping ------------------------------------------------
    def _alloc(self) -> int:
        try:
            return self._free.pop()
        except IndexError:
            raise RuntimeError(
                f"bridge timer capacity exceeded ({self.cap} concurrent "
                "timers in one world); raise sweep(cap=...)") from None

    def record_send(self, ctr: int, thr: int, lossall: bool, lat_lo: int,
                    lat_w: int, cb: Optional[Callable]) -> None:
        live = cb is not None
        if live:
            slot = self._alloc()
            seq = self._seq
            self._seq += 1
            self.callbacks[seq] = (cb, slot)
        else:
            slot, seq = -1, 0
        self.sends.append(_Send(ctr, self.elapsed_ns, slot, seq,
                                min(thr, (1 << 64) - 1), lossall,
                                lat_lo, lat_w, live))
        self.send_cbs.append(cb)

    def fire(self, seq: int) -> None:
        ent = self.callbacks.pop(seq, None)
        if ent is None:
            return
        cb, slot = ent
        self._free.append(slot)
        cb()

    def drop_send(self, send: _Send) -> None:
        """A live send the device declared lost: release its lane slot."""
        ent = self.callbacks.pop(send.seq, None)
        if ent is not None:
            self._free.append(ent[1])

    def take_round(self):
        adds = self.pending_add
        cancels = self.cancels
        sends = self.sends
        self.pending_add = {}
        self.cancels = []
        self.sends = []
        self.send_cbs = []
        return adds, cancels, sends


class BridgeNetSim(NetSim):
    """NetSim whose datagram sampling runs on the device.

    The send-side processing delay and the connection-oriented paths
    (connect1 relays, whose latency value is needed inline for their
    sleep) keep drawing host-side from the same NET cursor — counters
    stay aligned with pure-host mode either way, because both modes
    consume exactly the same blocks in the same order."""

    async def send(self, node_id, port, dst, protocol, msg) -> None:
        await self.rand_delay()
        net = self.network
        dst_node = net.resolve_dest_node(node_id, dst, protocol)
        if dst_node is None:
            return
        ctr = self.rand.reserve(2)  # loss @ctr, latency @ctr+1 — on device
        if net.link_clogged(node_id, dst_node):
            return  # draws consumed, like the host test_link
        sockets = net.nodes[dst_node].sockets
        socket = sockets.get((dst, protocol))
        if socket is None:
            socket = sockets.get(((unspecified_for(dst[0]), dst[1]), protocol))
        cfg = net.config
        lo_ns = to_ns(cfg.send_latency[0])
        width = max(to_ns(cfg.send_latency[1]), lo_ns + 1) - lo_ns
        p = cfg.packet_loss_rate
        if socket is None:
            cb = None  # loss draw still decides stat.msg_count
        else:
            src_ip = (LOCALHOST_V4 if ip_is_loopback(dst[0])
                      else net.nodes[node_id].ip)
            src = (src_ip, port)

            def cb(socket=socket, src=src, dst=dst, msg=msg):
                socket.deliver(src, dst, msg)

        self.time.record_send(ctr, loss_threshold(p), p >= 1.0,
                              lo_ns, width, cb)


class BridgeRuntime(Runtime):
    """Runtime wired for the bridge: device-backed time + NetSim."""

    def __init__(self, seed: int = 0, config: Optional[Config] = None,
                 cap: int = 128):
        self._cap = cap
        super().__init__(seed=seed, config=config)

    def _make_time(self) -> BridgeTime:
        return BridgeTime(self.rand, self._cap)

    def _default_simulators(self) -> tuple:
        from ..fs import FsSim

        return (BridgeNetSim, FsSim)

    def block_on(self, coro):  # pragma: no cover
        raise NotImplementedError("bridge worlds are driven by sweep()")


class Outcome(NamedTuple):
    """Per-seed sweep outcome: exactly what ``Runtime.block_on`` would
    have returned (value) or raised (error)."""

    seed: int
    value: Any
    error: Optional[BaseException]


class _World:
    __slots__ = ("idx", "slot", "rt", "root", "done", "stat")

    def __init__(self, idx: int, slot: int, rt: BridgeRuntime, root):
        self.idx = idx          # position in the seed list (outcome row)
        self.slot = slot        # kernel batch row currently hosting it
        self.rt = rt
        self.root = root
        self.done = False
        self.stat = rt.handle.sims.get(NetSim).network.stat


def sweep(world_fn: Callable, seeds, *, config: Optional[Config] = None,
          configs: Optional[List[Config]] = None, cap: int = 128,
          k_events: int = 4, time_limit: Optional[float] = None,
          trace: bool = False, device: Optional[str] = None,
          jobs: int = 1, batch: Optional[int] = None) -> List[Outcome]:
    """Sweep an unmodified host workload over many seeds with the device
    decision kernel (`builder.rs:118-136`, batched).

    ``world_fn`` is called once per seed (with the seed if it accepts an
    argument) and must return the root coroutine. ``configs`` gives each
    world its own Config — the (seeds × configs) sweep axis. With
    ``trace=True`` each world records (task_id, elapsed_ns) per poll for
    trajectory-equality checks.

    ``jobs`` runs the Python task bodies of the W live worlds across a
    pool of forked worker processes behind ONE shared decision kernel
    (`bridge/pool.py`, the MADSIM_TEST_JOBS analog of
    `builder.rs:55-107`; the reference forks OS threads, which a GIL
    rules out for Python task bodies). Each worker owns a contiguous
    slot slice of the batch and packs it directly into shared memory, so
    the parent's per-round work is O(1) in W. Per-seed trajectories stay
    bit-identical to ``jobs=1`` for every J (tests/test_bridge_pool.py).
    Task bodies are CPU-bound Python, so jobs only helps up to the
    machine's core count; jobs=0 picks ``os.cpu_count()``.

    ``batch`` bounds how many worlds are live at once (world recycling,
    the host-side analog of ``parallel.sweep(recycle=True)``): seeds
    stream through ``batch`` kernel slots, each finished world's slot
    re-keyed (`BridgeKernel.reset_slot`) for the next seed. Memory and
    per-round pack width stay O(batch) however long the seed list, and
    every seed's trajectory stays bit-identical to an unbatched run
    (tests/test_bridge.py). The bound is the whole pool's: with
    ``jobs>1`` the ``batch`` kernel slots are SHARED, sliced across the
    workers, so the process tree's total stays O(batch)."""
    if jobs == 0:
        # Host driver sizing its own fork pool — no simulation is live here.
        jobs = os.cpu_count() or 1  # detlint: allow[DET004]
    seeds = list(seeds)
    if jobs > 1 and len(seeds) > 1:
        from .pool import sweep_pooled

        outcomes, _ = sweep_pooled(world_fn, seeds, jobs=jobs, config=config,
                                   configs=configs, cap=cap,
                                   k_events=k_events, time_limit=time_limit,
                                   trace=trace, device=device, batch=batch)
        return outcomes
    outcomes, _ = _sweep_impl(world_fn, seeds, config=config,
                              configs=configs, cap=cap, k_events=k_events,
                              time_limit=time_limit, trace=trace,
                              device=device, batch=batch)
    return outcomes


def sweep_traced(world_fn, seeds, *, jobs: int = 1,
                 **kw) -> Tuple[List[Outcome], List[list]]:
    """sweep() + per-seed poll traces (testing hook)."""
    seeds = list(seeds)
    if jobs > 1 and len(seeds) > 1:
        from .pool import sweep_pooled

        return sweep_pooled(world_fn, seeds, jobs=jobs, trace=True, **kw)
    return _sweep_impl(world_fn, seeds, trace=True, **kw)


def sweep_profiled(world_fn, seeds, **kw) -> Tuple[List[Outcome], dict]:
    """sweep() + a per-phase wall-time breakdown of the lockstep loop.

    The profile dict (all times in seconds) answers "where does a round
    go": ``host_s`` (Python task bodies + root settling), ``pack_s``
    (building the padded numpy batch), ``dispatch_s`` (the jitted kernel
    step, including device sync), ``settle_s`` (send accounting, event
    dispatch, drain rounds). ``rounds``/``drain_rounds`` count kernel
    dispatches; ``events``/``sends``/``timers`` are totals across worlds.
    This is the measured artifact behind docs/bridge.md.

    Profiled sweeps additionally run the kernel with its device-resident
    observability block (``BridgeMetrics``) and report the fleet
    aggregate under ``sim_metrics`` — trajectories stay bit-identical to
    an unprofiled sweep (tests/test_obs.py).
    """
    profile: dict = {}
    outs, _ = _sweep_impl(world_fn, seeds, profile=profile, **kw)
    return outs, profile


class PackBufferCache:
    """Process-global LRU of preallocated round pack buffers.

    Round buffers are preallocated per (W, T, C, S) bucket and reused:
    fresh np.zeros for 18 arrays per round was a measured ~6% of sweep
    wall time at W=512. The cache is BOUNDED: a long recycled sweep (or
    a process re-sweeping many widths) walks many bucket shapes, and an
    unbounded dict pins every (W, T, C, S) combination it ever saw —
    least-recently-used shapes are dropped instead
    (tests/test_bridge_pool.py gates the bound).

    Buffers come back UNCLEARED: clearing is the packer's job
    (:meth:`SliceDriver.pack_into` masks-only-clears exactly the rows it
    owns), which is what lets pool workers share one (W, ...) batch
    region without any whole-array owner. Mutating a buffer after the
    kernel ``step()`` returns is safe: StepOut is materialized to numpy
    before step returns, so the device is done with the inputs.
    """

    def __init__(self, maxsize: int = 8):
        from collections import OrderedDict

        self.maxsize = maxsize
        self._bufs: "Dict[Tuple[int, int, int, int], list]" = OrderedDict()

    def __len__(self) -> int:
        return len(self._bufs)

    def get(self, W: int, T: int, C: int, S: int) -> list:
        key = (W, T, C, S)
        buf = self._bufs.get(key)
        if buf is None:
            buf = [np.zeros((W, T), np.int32), np.zeros((W, T), np.int64),
                   np.zeros((W, T), np.int64), np.zeros((W, T), np.bool_),
                   np.zeros((W, C), np.int32), np.zeros((W, C), np.bool_),
                   np.zeros((W, S), np.uint64), np.zeros((W, S), np.int64),
                   np.zeros((W, S), np.int32), np.zeros((W, S), np.int64),
                   np.zeros((W, S), np.uint64), np.zeros((W, S), np.bool_),
                   np.zeros((W, S), np.int64), np.ones((W, S), np.int64),
                   np.zeros((W, S), np.bool_), np.zeros((W, S), np.bool_),
                   np.zeros((W,), np.int64), np.zeros((W,), np.bool_)]
            self._bufs[key] = buf
            while len(self._bufs) > self.maxsize:
                self._bufs.popitem(last=False)
        else:
            self._bufs.move_to_end(key)
        return buf


_PACK_BUFFERS = PackBufferCache()


class SliceDriver:
    """Host-side driving of a contiguous slice of bridge kernel slots.

    This is the slot-sliced seam the lockstep sweep is built from: the
    serial loop (`_sweep_impl`) drives ONE slice covering all W slots
    directly against the kernel; the forked worker pool
    (`bridge/pool.py`) gives each worker its own slice — worlds,
    ``Runtime`` object graphs, and seed sub-stream live only in that
    worker — and moves the kernel interactions to the parent. Every
    per-world decision here depends only on that world's own rows, which
    is what makes the per-seed trajectory independent of how slots are
    sliced (the ``jobs=J == jobs=1 == serial`` bitwise contract,
    tests/test_bridge_pool.py).

    ``slot_lo`` is the slice's first GLOBAL kernel row; all batch/StepOut
    indexing below is global (``slot_lo + local``). ``seeds`` is the
    slice's own seed stream, recycled through its ``n_slots`` slots.
    """

    def __init__(self, world_fn, seeds, *, slot_lo: int = 0,
                 n_slots: Optional[int] = None, config=None, configs=None,
                 cap: int = 128, time_limit=None, trace: bool = False,
                 profile: Optional[dict] = None):
        self.world_fn = world_fn
        self.seeds = [int(s) for s in seeds]
        n = len(self.seeds)
        self.slot_lo = slot_lo
        self.W = n if n_slots is None else n_slots
        self.wants_seed = len(inspect.signature(world_fn).parameters) >= 1
        self.config = config
        self.configs = configs
        self.cap = cap
        self.time_limit = time_limit
        self.trace = trace
        self.profile = profile
        self.outcomes: List[Optional[Outcome]] = [None] * n
        self.traces: List[list] = [[] for _ in range(n)]
        self.slots: List[Optional[_World]] = [None] * self.W
        self.free: List[int] = list(range(self.W - 1, -1, -1))  # slot 0 first
        self.pending: set = set()       # local slots holding a live world
        self.next_pos = 0               # next seed position to admit
        self.polls_done = 0             # poll_count of retired worlds
        self._rounds: Optional[list] = None
        self._woke: List[_World] = []

    # -- admission / retirement --------------------------------------------
    @property
    def live(self) -> int:
        return len(self.pending)

    @property
    def left(self) -> int:
        return len(self.seeds) - self.next_pos

    def live_slots(self) -> List[int]:
        """GLOBAL row indices of the slots holding a live world."""
        return [self.slot_lo + s for s in sorted(self.pending)]

    def finish(self, w: _World, value=None, error=None) -> None:
        self.outcomes[w.idx] = Outcome(self.seeds[w.idx], value, error)
        w.done = True
        self.pending.discard(w.slot)
        self.free.append(w.slot)
        self.polls_done += w.rt.task.poll_count

    def run_host(self, w: _World) -> None:
        """One host burst: run all ready tasks, then settle the root."""
        ex = w.rt.task
        with context.enter_handle(w.rt.handle):
            ex.run_all_ready()
        if ex._uncaught is not None:
            exc, ex._uncaught = ex._uncaught, None
            self.finish(w, error=exc)
        elif w.root.done:
            fut = w.root.join_future
            if fut._exception is not None:
                self.finish(w, error=fut._exception)
            else:
                self.finish(w, value=fut.result())

    def spawn(self, slot: int, pos: int) -> _World:
        if self.configs is not None:
            cfg = copy.deepcopy(self.configs[pos])
        else:
            cfg = (copy.deepcopy(self.config)
                   if self.config is not None else None)
        rt = BridgeRuntime(seed=self.seeds[pos], config=cfg, cap=self.cap)
        if self.time_limit is not None:
            rt.set_time_limit(self.time_limit)
        if self.trace:
            rt.task.trace = self.traces[pos]
        with context.enter_handle(rt.handle):
            coro = (self.world_fn(self.seeds[pos]) if self.wants_seed
                    else self.world_fn())
            root = rt.task.start_root(coro)
        w = _World(pos, slot, rt, root)
        self.slots[slot] = w
        self.pending.add(slot)
        return w

    def top_up(self) -> List[Tuple[int, int]]:
        """Admit seeds into free slots (runs between rounds only — a slot
        reset mid-round would let stale kernel rows fire into the fresh
        world's seq space). Returns the (GLOBAL slot, seed) pairs whose
        kernel rows must be re-keyed (`BridgeKernel.reset_slot`/
        `reset_slots`) before the next step — the caller owns the kernel
        (directly in the serial loop; via the pool parent otherwise)."""
        blocked: List[int] = []
        resets: List[Tuple[int, int]] = []
        while self.free and self.next_pos < len(self.seeds):
            slot = self.free.pop()
            old = self.slots[slot]
            if old is not None:
                t = old.rt.time
                if t.pending_add or t.sends or t.cancels:
                    # The retiring world's final host burst recorded
                    # activity that has not been shipped yet (its stats
                    # ride the next round's batch): recycle this slot one
                    # round later.
                    blocked.append(slot)
                    continue
                resets.append((self.slot_lo + slot,
                               self.seeds[self.next_pos]))
            w = self.spawn(slot, self.next_pos)
            self.next_pos += 1
            self.run_host(w)
        self.free.extend(blocked)
        return resets

    # -- the pack seam ------------------------------------------------------
    def take_rounds(self) -> Tuple[int, int, int]:
        """Collect each slot's recorded round activity; returns the raw
        (max timers, max cancels, max sends) widths of this slice — the
        caller buckets the GLOBAL max so every packer agrees on shape."""
        rounds = []
        t_n = c_n = s_n = 0
        for w in self.slots:
            adds, cancels, sends = w.rt.time.take_round()
            rounds.append((adds, cancels, sends))
            t_n = max(t_n, len(adds))
            c_n = max(c_n, len(cancels))
            s_n = max(s_n, len(sends))
        self._rounds = rounds
        if self.profile is not None:
            self.profile["timers"] += sum(len(r[0]) for r in rounds)
            self.profile["sends"] += sum(len(r[2]) for r in rounds)
        return t_n, c_n, s_n

    def pack_into(self, bufs: list) -> None:
        """Write this slice's rows of the padded (W, ...) round batch.

        Masks-only clears, restricted to the slice's own rows: every
        value lane sits behind a mask the kernel applies (stale values
        are jnp.where'd to the dump column), and the slices of a sweep
        partition the W rows, so the batch is fully initialized with no
        per-world work outside the owning slice/worker."""
        (t_slot, t_dl, t_seq, t_mask, c_slot, c_mask,
         s_ctr, s_base, s_slot, s_seq, s_thr, s_lossall,
         s_lat_lo, s_lat_w, s_mask, s_live, clock, advance) = bufs
        lo, hi = self.slot_lo, self.slot_lo + self.W
        t_mask[lo:hi] = False
        c_mask[lo:hi] = False
        s_lat_w[lo:hi] = 1   # divisor: must stay >= 1
        s_mask[lo:hi] = False
        s_live[lo:hi] = False
        for w, (adds, cancels, sends) in zip(self.slots, self._rounds):
            i = lo + w.slot
            clock[i] = w.rt.time.elapsed_ns
            advance[i] = not w.done
            for j, (slot, (dl, sq)) in enumerate(adds.items()):
                t_slot[i, j] = slot
                t_dl[i, j] = dl
                t_seq[i, j] = sq
                t_mask[i, j] = True
            for j, slot in enumerate(cancels):
                c_slot[i, j] = slot
                c_mask[i, j] = True
            for j, s in enumerate(sends):
                s_ctr[i, j] = s.ctr
                s_base[i, j] = s.base_ns
                s_slot[i, j] = max(s.slot, 0)
                s_seq[i, j] = s.seq
                s_thr[i, j] = s.thr
                s_lossall[i, j] = s.lossall
                s_lat_lo[i, j] = s.lat_lo
                s_lat_w[i, j] = s.lat_w
                s_mask[i, j] = True
                s_live[i, j] = s.live

    # -- the settle seam ----------------------------------------------------
    def settle(self, out) -> List[int]:
        """Settle sends, dispatch popped events, detect stops for this
        slice's rows of a StepOut-shaped result (numpy arrays — the
        kernel's own StepOut or the pool's shared-memory views). Returns
        the GLOBAL rows whose worlds finished during the settle."""
        newly_done: List[int] = []
        self._woke = []
        lo = self.slot_lo
        for w, (adds, cancels, sends) in zip(self.slots, self._rounds):
            i = lo + w.slot
            for j, s in enumerate(sends):
                if out.send_ok[i, j]:
                    w.stat.msg_count += 1
                elif s.live:
                    w.rt.time.drop_send(s)
            if w.done:
                continue
            w.rt.time.elapsed_ns = int(out.clock[i])
            if out.deadlock[i]:
                self.finish(w, error=Deadlock(
                    f"deadlock detected at t={w.rt.time.elapsed_ns / 1e9:.9f}s: "
                    "all tasks are blocked and no timers are pending"))
                newly_done.append(i)
                continue
            lim = w.rt.task.time_limit_ns
            if lim is not None and w.rt.time.elapsed_ns >= lim:
                self.finish(w, error=TimeLimitExceeded(
                    f"time limit ({lim / NANOS_PER_SEC}s) exceeded"))
                newly_done.append(i)
                continue
            fired = 0
            with context.enter_handle(w.rt.handle):
                for k in range(out.event_valid.shape[1]):
                    if not out.event_valid[i, k]:
                        break
                    w.rt.time.fire(int(out.event_seq[i, k]))
                    fired += 1
            if self.profile is not None:
                self.profile["events"] += fired
            if fired or out.more_due[i]:
                self._woke.append(w)
        return newly_done

    def any_pending_more(self, more: np.ndarray) -> bool:
        """Serial-loop drain predicate: any live world of this slice with
        >K events still due (``more`` is globally indexed)."""
        return bool(self.pending
                    and np.any(more[[self.slot_lo + s
                                     for s in self.pending]]))

    def drain_assert(self, more: np.ndarray) -> None:
        # Drain rounds carry no host batch: anything a fire() callback
        # recorded would silently miss its own due cluster and fire in
        # the wrong order vs the host heap. No framework callback does
        # that today — enforce it rather than assume it.
        for w in self.slots:
            if w.done or not more[self.slot_lo + w.slot]:
                continue
            t = w.rt.time
            assert not (t.pending_add or t.sends or t.cancels), (
                "bridge drain invariant violated: a fire() callback "
                "recorded timers/sends during event dispatch")

    def fire_drain(self, ev_valid: np.ndarray, ev_seq: np.ndarray,
                   more: np.ndarray) -> None:
        """Fire one drain round's popped events for the slice's rows
        flagged in ``more`` (the PREVIOUS round's more_due — which worlds
        this drain was dispatched for)."""
        for w in self.slots:
            i = self.slot_lo + w.slot
            if w.done or not more[i]:
                continue
            with context.enter_handle(w.rt.handle):
                for k in range(ev_valid.shape[1]):
                    if not ev_valid[i, k]:
                        break
                    w.rt.time.fire(int(ev_seq[i, k]))
                    if self.profile is not None:
                        self.profile["events"] += 1

    def run_woke(self) -> None:
        """Run the host bursts of the worlds the settled round woke."""
        for w in self._woke:
            if not w.done:
                self.run_host(w)
        self._woke = []

    def poll_total(self) -> int:
        return self.polls_done + sum(
            w.rt.task.poll_count for w in self.slots
            if w is not None and not w.done)


def _sweep_impl(world_fn, seeds, *, config=None, configs=None, cap=128,
                k_events=4, time_limit=None, trace=False, device=None,
                profile=None, batch=None):
    seeds = [int(s) for s in seeds]
    n = len(seeds)
    # World recycling: W kernel slots, n seeds streamed through them. A
    # finished world's slot is re-keyed for the next seed, so batch width
    # (and host memory) stays O(W) for arbitrarily long seed lists.
    W = n if batch is None else max(1, min(int(batch), n))
    drv = SliceDriver(world_fn, seeds, n_slots=W, config=config,
                      configs=configs, cap=cap, time_limit=time_limit,
                      trace=trace, profile=profile)

    # Profiled sweeps also carry the device-resident observability block
    # (BridgeMetrics): counters accumulate inside the jitted step and are
    # pulled ONCE at the end — bit-invisible to trajectories either way.
    kernel = BridgeKernel(seeds[:W], cap=cap, k_events=k_events,
                          device=device, metrics=profile is not None)

    if profile is not None:
        from time import perf_counter

        profile.update(rounds=0, drain_rounds=0, host_s=0.0, pack_s=0.0,
                       dispatch_s=0.0, settle_s=0.0, events=0, sends=0,
                       timers=0, polls=0)

        def _clk():
            # Wall-clock profiling of the sweep driver itself (host side).
            return perf_counter()  # detlint: allow[DET001]
    else:
        def _clk():
            return 0.0

    t0 = _clk()
    for slot, seed in drv.top_up():  # no resets on the initial fill
        kernel.reset_slot(slot, seed)
    if profile is not None:
        profile["host_s"] += _clk() - t0

    while drv.live or drv.left:
        # -- build the padded round batch ---------------------------------
        t0 = _clk()
        t_n, c_n, s_n = drv.take_rounds()
        T, C, S = bucket(t_n), bucket(c_n), bucket(s_n)
        bufs = _PACK_BUFFERS.get(W, T, C, S)
        drv.pack_into(bufs)
        if profile is not None:
            profile["pack_s"] += _clk() - t0
            profile["rounds"] += 1
        t0 = _clk()
        out = kernel.step(HostBatch(*bufs))
        if profile is not None:
            profile["dispatch_s"] += _clk() - t0

        # -- settle sends, dispatch events, detect stops ------------------
        t0 = _clk()
        drv.settle(out)

        # -- drain rounds: >K events due fire before any poll runs --------
        # Pop-only kernel + dispatch-ahead (docs/perf.md "Pipelined
        # orchestration"): a drain round's only input is the
        # device-resident kernel state, so round r+1 enters the device
        # queue BEFORE round r's popped events are unpacked and fired on
        # the host. The one speculative round at chain end finds nothing
        # due and pops nothing — a semantic no-op on the lanes.
        more = out.more_due
        inflight_drain = (kernel.drain() if drv.any_pending_more(more)
                          else None)
        while inflight_drain is not None:
            drv.drain_assert(more)
            if profile is not None:
                profile["drain_rounds"] += 1
            cur = inflight_drain
            # Dispatch-ahead: queue the next round before materializing
            # this one's events (the device pops while the host fires).
            inflight_drain = kernel.drain()
            drv.fire_drain(np.asarray(cur.event_valid),
                           np.asarray(cur.event_seq), more)
            more = np.asarray(cur.more_due)
            if not drv.any_pending_more(more):
                break  # the in-flight round is the no-op tail

        if profile is not None:
            profile["settle_s"] += _clk() - t0
        t0 = _clk()
        drv.run_woke()
        # Recycle freed slots for the next seeds in the stream.
        for slot, seed in drv.top_up():
            kernel.reset_slot(slot, seed)
        if profile is not None:
            profile["host_s"] += _clk() - t0
            profile["polls"] = drv.poll_total()

    if profile is not None:
        mb = kernel.metrics()
        if mb is not None:
            # Fleet aggregate of the kernel's per-slot counters
            # (docs/observability.md).
            profile["sim_metrics"] = {k: int(v.sum()) for k, v in mb.items()}
            # Behavior-coverage sketch over the same block: the host-side
            # twin of the device sweep's ledger (obs/coverage.py). Bridge
            # counters are per SLOT and cumulative across recycled seeds
            # (bridge/kernel.py BridgeMetrics), so this is per-slot
            # coverage — one fold of the block pulled above, no extra
            # device traffic.
            from ..obs.coverage import coverage_of_counters

            profile["coverage"] = coverage_of_counters(mb)
    return [o for o in drv.outcomes], drv.traces
