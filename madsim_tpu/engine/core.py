"""The batched device engine core: world state + step function.

Design (SURVEY §7 stage 4): one *world* = one seeded simulation, all of whose
engine-level state — virtual clock, pending-event queue, RNG cursor, node
liveness/generation, link partition matrices, counters — is fixed-shape
arrays. The per-world ``step`` is a pure function (pop earliest event →
apply fault / dispatch to the actor via its handler → sample network
latency/loss for the outbox → push), ``vmap``'d over the world axis so
thousands of seeds advance per XLA dispatch. Worlds that finish (empty queue,
time limit, or bug with ``stop_on_bug``) are frozen by a select — the
step-synchronous masking that replaces the reference's one-OS-thread-per-seed
sweep (`madsim/src/sim/runtime/builder.rs:118-136`).

Semantics carried over from the reference host engine:
- message sends sample clog/loss/latency at *send* time
  (`madsim/src/sim/net/network.rs:249-257`);
- node kill bumps a generation counter so pending timers die with the node
  (the lazy-drop of queued runnables, `task.rs:211-226`), while in-flight
  messages are delivered iff the destination is alive at delivery time;
- restart re-runs the actor's init hook (`task.rs:229-240`);
- every random decision draws from the per-world counter-based Threefry
  stream, so (seed, config) ⇒ bit-exact trajectories, re-runnable anywhere.
"""
from __future__ import annotations

import copy
import dataclasses
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.blackbox import (
    BB_DROP_DEAD,
    BB_DROP_STALE,
    BB_FAULT,
    BB_MARKER,
    BB_RAISE,
    BB_TIMER,
    FAULT_NAMES,
    BlackboxRing,
)
from ..obs.metrics import NUM_FAULT_KINDS, MetricsBlock
from .lanes import (
    PACKED,
    WIDE,
    Lanes,
    deinterleave,
    join_wide,
    narrow,
    narrow_wrap,
    onehot,
    split_wide,
    take_small,
    upd,
    upd2,
    widen,
)
from .queue import (
    Event,
    EventQueue,
    FLAG_FAULT,
    FLAG_TIMER,
    GEN_MASK,
    INF_TIME,
    depth as queue_depth,
    eligible_mask,
    empty_queue,
    insert_metrics,
    next_deadline,
    pop,
    pop_indexed,
    push,
    push_many,
)
from .rng import (
    DevRng,
    _u32_to_range,
    _u32_to_unit_f32,
    make_rng,
    next_u32_vec,
    uniform_f32,
    uniform_u32,
)

# Device-engine RNG stream id (host streams occupy 0..3, see core/rng.py).
STREAM_DEVICE = 16

# Steps per block of a chunk: a chunk checks its shard for a live world
# before each block and ends at the first check that finds none
# (docs/perf.md "Chunk exit on frozen shards").
EXIT_BLOCK = 16

# Fault-injection ops (event kind when FLAG_FAULT is set). The analogs of
# Handle::kill/restart (`runtime/mod.rs:241-258`) and NetSim::clog_node /
# clog_link (`net/mod.rs:147-170`, `network.rs:159-190`).
FAULT_KILL = 0
FAULT_RESTART = 1
FAULT_CLOG_NODE = 2
FAULT_UNCLOG_NODE = 3
FAULT_CLOG_LINK = 4
FAULT_UNCLOG_LINK = 5
# Hot network-config updates (NetSim::update_config, `net/mod.rs:127-130`,
# `network.rs:74-94`): net parameters are runtime data in WorldState, so a
# schedule row can change them mid-run without recompiling.
# FAULT_SET_LATENCY: a = new min µs, b = new max µs.
# FAULT_SET_LOSS:    a = new loss rate in parts-per-million, b unused.
FAULT_SET_LATENCY = 6
FAULT_SET_LOSS = 7
# Pause/resume (Handle::pause/resume, `runtime/mod.rs:251-268`,
# `task.rs:243-261`): a paused node's deliveries and timers are BUFFERED
# (skipped by pop, untouched in the queue), then flush in (time, slot)
# order on resume. Kill/restart clear the pause, like the reference's
# fresh NodeInfo.
FAULT_PAUSE = 8
FAULT_RESUME = 9


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static (compile-time) engine parameters. Hashable: part of jit keys."""

    n_nodes: int
    queue_cap: int = 128
    payload_words: int = 8
    outbox_cap: Optional[int] = None  # default n_nodes + 1
    # Network model DEFAULTS (reference defaults: 1-10 ms latency, 0 loss;
    # `net/network.rs:74-94`). Times are int32 microseconds. These seed
    # WorldState.{lat_min,lat_max,loss} — runtime data, per world — so one
    # compiled sweep can explore a (seeds × loss × latency) grid via
    # ``init(seeds, configs=...)`` and schedules can hot-update them
    # (FAULT_SET_LATENCY / FAULT_SET_LOSS), with zero recompiles.
    latency_min_us: int = 1_000
    latency_max_us: int = 10_000
    loss_rate: float = 0.0
    t_limit_us: int = 10_000_000
    stop_on_bug: bool = True
    # Equivalence-testing knob: keep the pre-round-7 statically unrolled
    # push chain instead of the fused queue.push_many pass. The two paths
    # are bitwise identical by contract (tests/test_queue_insert.py runs
    # whole trajectories both ways); sequential exists ONLY to pin that
    # contract — it pays ~M full-queue rewrites per step.
    sequential_insert: bool = False
    # Observability: carry a per-world MetricsBlock (obs/metrics.py) in
    # WorldState.metrics and update it every step. The block is a
    # separate pytree leaf the step WRITES but never reads for any
    # simulation decision, so metrics-on trajectories are bit-identical
    # to metrics-off (tier-1, tests/test_obs.py); with False (default)
    # the field is None and the compiled step is the exact pre-metrics
    # program — the op budget in tests/test_queue_insert.py is untouched.
    metrics: bool = False
    # Flight recorder (obs/blackbox.py): carry a per-world ring buffer
    # of the last K recorded step events in WorldState.blackbox and
    # write one packed record per processed step. Same contract as
    # ``metrics``: a separate write-only pytree leaf, so blackbox-on
    # trajectories are bit-identical to blackbox-off (tier-1,
    # tests/test_obs.py) and 0 (default) leaves the field None — the
    # compiled step is the exact pre-recorder program.
    blackbox: int = 0
    # Packed lane dtypes (engine/lanes.py Lanes registry, docs/perf.md
    # "Roofline round 2"): node ids, role/decision codes, queue slot
    # indices and payload words ride i8/i16 at rest instead of i32 —
    # ~0.6x the state bytes per world, which compounds directly with
    # buffer donation into worlds-per-chip. Virtual time, RNG cursors
    # and unbounded counters stay wide. False is the reference i32
    # path, kept alive for bitwise crosscheck (the sequential_insert
    # pattern); trajectories are bit-identical between the two profiles
    # as long as no narrow lane saturates (tier-1, tests/test_obs.py).
    packed: bool = True
    # Fused Pallas step kernel (engine/pallas_step.py): run the batched
    # pop -> eligible-mask -> dispatch -> push step as ONE
    # pl.pallas_call, so the queue scatter, mask and lane updates share
    # one VMEM residency on TPU instead of round-tripping HBM between
    # XLA fusions. Off by default: CPU tier-1 compiles the existing lax
    # programs unchanged. Bitwise identical to the lax step (the kernel
    # body IS the step function, gated in tests and `make smoke`). Runs
    # interpreted only: it does not lower on a TPU yet.
    pallas: bool = False
    # World-axis block per Pallas grid step (None = whole batch in one
    # kernel invocation). Must divide the batch width: the kernel's
    # first trace raises on one that does not.
    pallas_block: Optional[int] = None
    # Force/disable interpreter-mode Pallas (None = auto: interpret
    # everywhere except on real TPU backends). Interpret mode keeps the
    # kernel runnable — and the bitwise-identity gate green — on CPU;
    # Mosaic lowering is refused with NotImplementedError until the
    # kernel lowers (pallas_step.MOSAIC_LOWERING_GAPS).
    pallas_interpret: Optional[bool] = None

    def __post_init__(self):
        if self.packed:
            if self.n_nodes > 127:
                raise ValueError(
                    f"EngineConfig(packed=True) stores node ids in int8: "
                    f"n_nodes={self.n_nodes} exceeds 127. Use "
                    f"packed=False (the int32 reference profile) for "
                    f"wider clusters.")
            if self.queue_cap > 32767:
                raise ValueError(
                    f"EngineConfig(packed=True) carries queue depths in "
                    f"int16: queue_cap={self.queue_cap} exceeds 32767. "
                    f"Use packed=False for deeper queues.")
        if self.pallas_block is not None and self.pallas_block <= 0:
            raise ValueError("pallas_block must be a positive world count")
        if self.blackbox < 0:
            raise ValueError("blackbox must be 0 (off) or a positive ring "
                             "depth K (events/world)")

    @property
    def lanes(self) -> Lanes:
        """The lane dtype registry this config compiles against."""
        return PACKED if self.packed else WIDE

    @property
    def m(self) -> int:
        return self.outbox_cap if self.outbox_cap is not None else self.n_nodes + 1


class Outbox(NamedTuple):
    """Fixed-capacity send buffer an actor returns from a handler.

    Slot fields are (M,) arrays ((M, P) for payload). Timers are delivered to
    ``dst`` after ``delay_us`` and are generation-checked; messages get
    engine-sampled latency/loss/partition treatment instead.
    """

    valid: jnp.ndarray     # (M,) bool
    is_timer: jnp.ndarray  # (M,) bool
    kind: jnp.ndarray      # (M,) int32
    dst: jnp.ndarray       # (M,) int32
    delay_us: jnp.ndarray  # (M,) int32 — timers only
    payload: jnp.ndarray   # (M, P) int32

    @staticmethod
    def empty(cfg: EngineConfig) -> "Outbox":
        m = cfg.m
        return Outbox(
            valid=jnp.zeros((m,), bool),
            is_timer=jnp.zeros((m,), bool),
            kind=jnp.zeros((m,), jnp.int32),
            dst=jnp.zeros((m,), jnp.int32),
            delay_us=jnp.zeros((m,), jnp.int32),
            payload=jnp.zeros((m, cfg.payload_words), jnp.int32),
        )


class WorldState(NamedTuple):
    """All state of one world (or, with a leading axis, of W worlds)."""

    now: jnp.ndarray          # int32 µs
    queue: EventQueue
    rng: DevRng
    alive: jnp.ndarray        # (N,) bool
    gen: jnp.ndarray          # (N,) code lane (i8 packed / i32 wide) —
                              # bumped on kill/restart, compared mod 256
    paused: jnp.ndarray       # (N,) bool — deliveries buffered while set
    clog_node: jnp.ndarray    # (N,) bool
    clog_link: jnp.ndarray    # (N, N) bool, [src, dst]
    astate: Any               # actor pytree
    active: jnp.ndarray       # bool — False ⇒ frozen
    steps: jnp.ndarray        # int32
    delivered: jnp.ndarray    # int32
    dropped: jnp.ndarray      # int32
    overflow: jnp.ndarray     # bool — event queue overflowed (diagnostic)
    qdepth: jnp.ndarray       # slot lane (i16 packed / i32 wide) — carried
                              # queue depth (== depth(queue); maintained by
                              # pop/push_many, so qmax needs no O(Q)
                              # reduction per step)
    qmax: jnp.ndarray         # slot lane — queue depth high-water mark
    bug: jnp.ndarray          # bool — invariant violation observed
    bug_time: jnp.ndarray     # int32 µs of first bug, INF_TIME if none
    # Per-world network model (runtime data — the batched sweep axis and
    # hot-update target the reference's global config cannot be,
    # `network.rs:74-94`).
    lat_min: jnp.ndarray      # int32 µs
    lat_max: jnp.ndarray      # int32 µs
    loss: jnp.ndarray         # float32 loss probability
    # Observability counters (obs/metrics.py MetricsBlock) when
    # EngineConfig.metrics, else None (an empty pytree subtree — the
    # leaf list, and therefore every compiled program and checkpoint
    # layout, is unchanged with metrics off). Write-only within the
    # step: nothing below ever reads it — the bitwise-invisibility
    # contract.
    metrics: Any = None
    # Flight-recorder ring (obs/blackbox.py BlackboxRing) when
    # EngineConfig.blackbox > 0, else None — the same empty-subtree
    # trick as ``metrics``, with the same write-only contract.
    blackbox: Any = None


def tree_select(pred, a, b):
    """Per-world select over two identical pytrees (pred is a scalar bool)."""
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def tree_select_worlds(mask, a, b):
    """Slot-wise select over two identically batched pytrees.

    ``mask`` is a (W,) bool vector over the leading world axis; it
    broadcasts over each leaf's trailing axes, so whole worlds are taken
    from ``a`` where True and from ``b`` where False. This is the
    device-side primitive behind world recycling: fresh worlds are
    selected into retired slots without the batch ever leaving the chip.
    """
    def pick(x, y):
        m = mask.reshape(mask.shape + (1,) * (x.ndim - 1))
        return jnp.where(m, x, y)

    return jax.tree.map(pick, a, b)


class DeviceEngine:
    """Compiles (actor, config) into jit-ready batched simulation functions.

    Usage::

        eng = DeviceEngine(RaftActor(rcfg), EngineConfig(n_nodes=3))
        state = eng.init(np.arange(10_000))          # one world per seed
        state = eng.run(state, max_steps=5_000)       # jitted while_loop
        out = eng.observe(state)                      # host-side dict
    """

    def __init__(self, actor, cfg: EngineConfig):
        # Packed-meta width limits (queue.pack_meta): 8-bit node ids,
        # 6-bit event kinds. num_kinds is required so the kind-width
        # guard actually covers every actor.
        if cfg.n_nodes > 256:
            raise ValueError("DeviceEngine supports at most 256 nodes/world")
        num_kinds = getattr(actor, "num_kinds", None)
        if num_kinds is None:
            raise ValueError("actor must declare num_kinds (its event-kind "
                             "count; packed event kinds are 6 bits)")
        if num_kinds > 64:
            raise ValueError("actor.num_kinds must be <= 64")
        self.actor = actor
        self.cfg = cfg
        self._set_step(self._build_step())
        # Built once: jit's own cache keys on the fault-array shape, so
        # repeated init() calls (and every sweep) reuse the compilation
        # instead of paying a fresh trace per call.
        self._init_batched = jax.jit(jax.vmap(self._init_one))
        # refill's select donates the old state (the merged batch aliases
        # it in place); the fresh batch is NOT donated — the select can
        # only alias one source, and donating both just trips XLA's
        # "donated buffer not usable" warning for the loser.
        self._refill_select = jax.jit(tree_select_worlds,
                                      donate_argnums=(2,))

    #: Whether the run loops trace the step with its fault path. Only
    #: the twin :meth:`_form` builds has False.
    fault_path = True

    def _set_step(self, step_one) -> None:
        """Install the per-world step and the run loops built on it."""
        self._step_one = step_one
        # The batched step the run loops iterate: a plain vmap of the
        # per-world step, or — with cfg.pallas — the same step fused
        # into one pl.pallas_call (engine/pallas_step.py) so every lane
        # update shares one VMEM residency. Bitwise identical by
        # construction: the kernel body IS the vmapped step.
        if self.cfg.pallas:
            from .pallas_step import make_pallas_step

            self._batched_step = make_pallas_step(step_one, self.cfg)
        else:
            self._batched_step = jax.vmap(step_one)
        self.step = jax.jit(self._batched_step)
        # The run loops DONATE their input state: XLA aliases the output
        # onto the argument buffers and updates the 200-400 MB world state
        # in place instead of double-buffering it — roughly doubling the W
        # that fits in HBM (docs/perf.md "Single-pass insert + donation").
        # Contract for callers: the state you pass in is DEAD afterwards
        # (reading it raises); rebind, as every in-repo caller does.
        self._run_steps = jax.jit(self._run_steps_impl, static_argnums=1,
                                  donate_argnums=0)
        self._run = jax.jit(self._run_impl, static_argnums=1,
                            donate_argnums=0)

    def _form(self, fault_path: bool) -> "DeviceEngine":
        """This engine, or (``fault_path=False``) its twin whose step is
        traced without the fault path, for batches in which no world
        holds a fault event.

        Only fault events write ``alive``, ``gen``, ``paused``,
        ``clog_node`` and ``clog_link``; without one every node stays
        alive and unpaused at generation 0 and no link is clogged. So
        the twin's step leaves out ``apply_fault`` and its selects, the
        pause mask of the pop, the stale and dead checks and the clog
        and generation lookups of the push, and reaches the same state
        bit for bit. Message loss stays: it is world data, not a fault.
        The twin refuses fault rows (:meth:`init`, :meth:`refill`,
        :meth:`refill_traced`), so it never holds a world its step
        would get wrong. It shares everything else with this engine;
        the sweep keys its compiled programs on ``fault_path``."""
        if fault_path:
            return self
        twin = self.__dict__.get("_fault_free")
        if twin is None:
            twin = copy.copy(self)
            twin.fault_path = False
            twin._set_step(self._build_step(fault_path=False))
            self.__dict__["_fault_free"] = twin
        return twin

    def _admit_fault_rows(self, n_rows: int) -> None:
        """Refuse ``n_rows`` fault rows a world on the fault-free twin."""
        if n_rows and not self.fault_path:
            raise ValueError(
                f"this engine's step is traced without its fault path and "
                f"cannot take fault rows ({n_rows} a world): run batches "
                f"with faults on the engine itself")

    # ------------------------------------------------------------------
    # Initialization
    # ------------------------------------------------------------------
    def init(self, seeds, faults: Optional[np.ndarray] = None,
             configs: Optional[np.ndarray] = None) -> WorldState:
        """Build W worlds from a vector of u64 seeds.

        ``faults``: optional int32 array of fault-schedule rows
        ``[time_us, op, a, b]``, shape (F, 4) (same schedule every world) or
        (W, F, 4) (per-world schedules). Rows with time < 0 are disabled —
        use that to give worlds ragged schedules under one static F.

        ``configs``: optional per-world network config, shape (3,) (every
        world) or (W, 3) (per world): columns ``[latency_min_us,
        latency_max_us, loss_rate]`` (latencies int µs, loss a float
        probability). Defaults to the EngineConfig values. This is the
        (seeds × loss × latency) sweep axis: one compiled function explores
        the whole fault-model grid because net config is world *data*, not
        a jit constant (reference analog: a fresh run per config,
        `network.rs:74-94`).
        """
        seeds = np.asarray(seeds, dtype=np.uint64)
        if seeds.ndim != 1:
            raise ValueError("seeds must be a 1-D vector (one world per seed)")
        w = seeds.shape[0]
        lo = (seeds & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        hi = (seeds >> np.uint64(32)).astype(np.uint32)
        if faults is None:
            faults = np.zeros((w, 0, 4), np.int32)
        else:
            faults = np.asarray(faults, np.int32)
            if faults.ndim == 2:
                faults = np.broadcast_to(faults, (w,) + faults.shape)
            # Validate enabled rows here, at the API boundary: the packed
            # queue stores node ids in 8 bits, so an out-of-range id would
            # otherwise alias onto a real node (a=256 would kill node 0)
            # instead of erroring.
            live = faults[..., 0] >= 0
            ops = faults[..., 1]
            a, b = faults[..., 2], faults[..., 3]
            node_op = (ops <= FAULT_UNCLOG_LINK) | (ops >= FAULT_PAUSE)
            if np.any(live & ((ops < FAULT_KILL) | (ops > FAULT_RESUME))):
                raise ValueError("fault op must be one of FAULT_KILL.."
                                 "FAULT_RESUME")
            node_params = np.stack([a, b], axis=-1)
            if np.any((live & node_op)[..., None]
                      & ((node_params < 0)
                         | (node_params >= self.cfg.n_nodes))):
                raise ValueError(
                    f"fault-row node ids must be in [0, {self.cfg.n_nodes})")
            set_lat = live & (ops == FAULT_SET_LATENCY)
            if np.any(set_lat & ((a < 0) | (b <= a))):
                raise ValueError("FAULT_SET_LATENCY needs 0 <= min < max µs")
            set_loss = live & (ops == FAULT_SET_LOSS)
            if np.any(set_loss & ((a < 0) | (a > 1_000_000))):
                raise ValueError("FAULT_SET_LOSS rate must be 0..1e6 ppm")
            # Packed payload words are int16, so each full-width net
            # param spans two words (lanes.split_wide): [a_lo, a_hi,
            # b_lo, b_hi] instead of [a, b].
            need_words = 4 if self.cfg.packed else 2
            if np.any(set_lat | set_loss) and \
                    self.cfg.payload_words < need_words:
                raise ValueError("net-config fault rows carry their params "
                                 f"in the payload: payload_words must be "
                                 f">= {need_words} (packed={self.cfg.packed})")
        self._admit_fault_rows(faults.shape[-2])

        if configs is None:
            configs = np.array([self.cfg.latency_min_us,
                                self.cfg.latency_max_us,
                                self.cfg.loss_rate], np.float64)
        configs = np.asarray(configs, np.float64)
        configs = np.broadcast_to(configs, (w, 3))
        lat_min = configs[:, 0].astype(np.int32)
        lat_max = configs[:, 1].astype(np.int32)
        loss = configs[:, 2].astype(np.float32)
        if np.any(lat_min < 0) or np.any(lat_max <= lat_min):
            raise ValueError("configs need 0 <= latency_min < latency_max µs")
        if np.any((loss < 0.0) | (loss > 1.0)):
            raise ValueError("configs loss_rate must be in [0, 1]")

        return self._init_batched(jnp.asarray(lo), jnp.asarray(hi),
                                  jnp.asarray(faults), jnp.asarray(lat_min),
                                  jnp.asarray(lat_max), jnp.asarray(loss))

    def _net_fault_payload_batch(self, rows, n_faults):
        """(F, P) int32 payload table for fault rows: net-config params
        ride the payload (src/dst are 8-bit packed and would truncate
        µs). Packed profile: each param splits across two int16-range
        words (lanes.split_wide) since the at-rest payload lane is i16."""
        cfg = self.cfg
        is_net = (rows[:, 1] == FAULT_SET_LATENCY) \
            | (rows[:, 1] == FAULT_SET_LOSS)
        a = jnp.where(is_net, rows[:, 2], 0)
        b = jnp.where(is_net, rows[:, 3], 0)
        pay = jnp.zeros((n_faults, cfg.payload_words), jnp.int32)
        if cfg.packed:
            a_lo, a_hi = split_wide(a)
            pay = pay.at[:, 0].set(a_lo)
            if cfg.payload_words >= 2:
                pay = pay.at[:, 1].set(a_hi)
            if cfg.payload_words >= 4:
                b_lo, b_hi = split_wide(b)
                pay = pay.at[:, 2].set(b_lo).at[:, 3].set(b_hi)
        else:
            pay = pay.at[:, 0].set(a)
            if cfg.payload_words >= 2:
                pay = pay.at[:, 1].set(b)
        return is_net, pay

    def _init_one(self, seed_lo, seed_hi, fault_rows, lat_min, lat_max, loss):
        cfg = self.cfg
        n_faults = fault_rows.shape[0]  # static under jit (shape-keyed cache)
        rng = make_rng(seed_lo, seed_hi, STREAM_DEVICE)
        q = empty_queue(cfg.queue_cap, cfg.payload_words,
                        payload_dtype=cfg.lanes.payload)
        astate, events, rng = self.actor.init(cfg, rng)
        overflow = jnp.asarray(False)
        if cfg.sequential_insert:
            for ev in events:
                q, ok = push(q, ev)
                overflow = overflow | ~ok
        elif events:
            q, oks, _ = push_many(
                q, jax.tree.map(lambda *xs: jnp.stack(xs), *events))
            overflow = overflow | ~jnp.all(oks)
        if n_faults and not cfg.sequential_insert:
            rows = fault_rows
            # Net-config params exceed the packed 8-bit src/dst fields, so
            # they ride the payload; node ops keep using src/dst, whose
            # 8 bits the init-time validation guards.
            is_net, pay = self._net_fault_payload_batch(rows, n_faults)
            zeros = jnp.zeros((n_faults,), jnp.int32)
            fevs = Event(time=rows[:, 0], kind=rows[:, 1],
                         flags=jnp.full((n_faults,), FLAG_FAULT, jnp.int32),
                         src=jnp.where(is_net, zeros, rows[:, 2]),
                         dst=jnp.where(is_net, zeros, rows[:, 3]),
                         gen=zeros, payload=pay)
            q, oks, _ = push_many(q, fevs, enable=rows[:, 0] >= 0)
            overflow = overflow | ~jnp.all(oks)
        elif n_faults:
            # Static unroll (sequential_insert); the payload layout is
            # shared with the batched branch above.
            is_net_all, pay_all = self._net_fault_payload_batch(
                fault_rows, n_faults)
            for f in range(n_faults):
                row = fault_rows[f]
                zero = jnp.int32(0)
                fev = Event(time=row[0], kind=row[1],
                            flags=jnp.int32(FLAG_FAULT),
                            src=jnp.where(is_net_all[f], zero, row[2]),
                            dst=jnp.where(is_net_all[f], zero, row[3]),
                            gen=jnp.int32(0), payload=pay_all[f])
                q, ok = push(q, fev, enable=row[0] >= 0)
                overflow = overflow | ~ok
        n = cfg.n_nodes
        # One O(Q) reduction at init seeds the carried depth; every step
        # after this maintains it incrementally (pop/push_many deltas).
        # The carried lane rides the (int16-capable) slot dtype; the
        # metrics block keeps the wide count.
        qd32 = queue_depth(q)
        qd = narrow(qd32, cfg.lanes.slot)
        # Metrics start from the init-time queue contents: the actor's
        # seed events and the fault rows count as enqueued.
        mb = (MetricsBlock.zeros(self.actor.num_kinds)._replace(enqueued=qd32)
              if cfg.metrics else None)
        bb = BlackboxRing.zeros(cfg.blackbox, cfg.lanes) \
            if cfg.blackbox else None
        return WorldState(
            now=jnp.int32(0),
            queue=q,
            rng=rng,
            alive=jnp.ones((n,), bool),
            # Generations compare mod 256 (queue.GEN_MASK), so the lane
            # rides the i8 code dtype with WRAP semantics.
            gen=jnp.zeros((n,), cfg.lanes.code),
            paused=jnp.zeros((n,), bool),
            clog_node=jnp.zeros((n,), bool),
            clog_link=jnp.zeros((n, n), bool),
            astate=astate,
            active=jnp.asarray(True),
            steps=jnp.int32(0),
            delivered=jnp.int32(0),
            dropped=jnp.int32(0),
            overflow=overflow,
            qdepth=qd,
            qmax=qd,
            bug=jnp.asarray(False),
            bug_time=INF_TIME,
            lat_min=lat_min,
            lat_max=lat_max,
            loss=loss,
            metrics=mb,
            blackbox=bb,
        )

    def refill(self, state: WorldState, slot_mask, new_seeds,
               faults: Optional[np.ndarray] = None,
               configs: Optional[np.ndarray] = None) -> WorldState:
        """Recycle retired batch slots: select freshly initialized worlds
        into the masked positions, on device.

        ``slot_mask`` is a (W,) bool vector over the batch; True slots
        receive the world initialized from the matching row of
        ``new_seeds`` (length W — rows outside the mask are initialized
        and immediately discarded by the select, so any placeholder seed
        works there). ``faults``/``configs`` follow :meth:`init`, plus
        one refill-specific form: a first-class PER-SLOT schedule
        override, ``(W, F, 4)`` with one fault block per refill slot —
        the shape the guided-search generator emits (search/generate.py).
        A per-slot ``faults`` may be a **device array** (``jax.Array``):
        that path skips the host-side row-value validation — no device
        sync ever happens inside the refill — under the documented
        contract that device schedules are valid by construction (the
        search mutation operators preserve validity; the seeded template
        was validated by ``init`` at sweep start). Host arrays validate
        as in ``init``.

        Worlds are position-independent, so a refilled slot's trajectory
        is bit-identical to an independent ``init``+run of that seed —
        the recycled-sweep contract (tests/test_parallel.py). When
        ``state`` is mesh-sharded, the fresh worlds are placed onto the
        same sharding first so the select is a device-side program, not
        an implicit reshard through the host.

        ``state`` (and the internal fresh batch) are **donated** into the
        select: the argument is dead after the call — rebind the result.
        """
        w = int(np.asarray(new_seeds).shape[0])
        if faults is not None and getattr(faults, "ndim", 0) == 3:
            # Validate the per-slot leading dim HERE, naming both dims:
            # a mismatched (m, F, 4) would otherwise surface as an
            # opaque vmap shape error deep inside _init_batched.
            if faults.shape[-1] != 4:
                raise ValueError(
                    f"per-slot fault schedules must be (n_slots, F, 4) "
                    f"rows of [time_us, op, a, b]; got shape "
                    f"{tuple(faults.shape)}")
            if faults.shape[0] != w:
                raise ValueError(
                    f"per-slot fault schedules carry one (F, 4) block "
                    f"per batch slot: got leading dim {faults.shape[0]} "
                    f"but the refill batch holds {w} slots")
        if isinstance(faults, jax.Array) and not isinstance(
                faults, np.ndarray):
            if faults.ndim != 3:
                raise ValueError(
                    f"a device-resident fault override must be per-slot "
                    f"(n_slots, F, 4); got {faults.ndim}-D shape "
                    f"{tuple(faults.shape)} — pass host arrays for the "
                    "shared-schedule form")
            fresh = self._init_device(new_seeds, faults, configs)
        else:
            fresh = self.init(new_seeds, faults=faults, configs=configs)
        mask = jnp.asarray(np.asarray(slot_mask, bool))
        sharding = getattr(state.now, "sharding", None)
        if isinstance(sharding, jax.sharding.NamedSharding):
            fresh, mask = jax.device_put((fresh, mask), sharding)
        return self._refill_select(mask, fresh, state)

    def _init_device(self, seeds, faults, configs=None) -> WorldState:
        """:meth:`init` for device-resident per-world fault schedules.

        Identical program (the same jitted ``_init_batched``), but the
        ``(W, F, 4)`` faults array stays on device — no value
        validation, because ``np.any`` over a ``jax.Array`` would force
        a blocking device→host sync in the middle of the sweep loop.
        Callers own the validity contract (see :meth:`refill`).
        """
        self._admit_fault_rows(faults.shape[-2])
        seeds = np.asarray(seeds, dtype=np.uint64)
        w = seeds.shape[0]
        lo = (seeds & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        hi = (seeds >> np.uint64(32)).astype(np.uint32)
        if configs is None:
            configs = np.array([self.cfg.latency_min_us,
                                self.cfg.latency_max_us,
                                self.cfg.loss_rate], np.float64)
        configs = np.broadcast_to(np.asarray(configs, np.float64), (w, 3))
        return self._init_batched(
            jnp.asarray(lo), jnp.asarray(hi),
            jnp.asarray(faults, jnp.int32),
            jnp.asarray(configs[:, 0].astype(np.int32)),
            jnp.asarray(configs[:, 1].astype(np.int32)),
            jnp.asarray(configs[:, 2].astype(np.float32)))

    # ------------------------------------------------------------------
    # The per-world step
    # ------------------------------------------------------------------
    def _build_step(self, fault_path: bool = True
                    ) -> Callable[[WorldState], WorldState]:
        """The per-world step; ``fault_path=False`` traces it without the
        fault path, for worlds that hold no fault event (:meth:`_form`)."""
        cfg = self.cfg
        actor = self.actor
        num_kinds = int(actor.num_kinds)  # kind_hist width (metrics)

        def net_params(payload):
            """Net-config fault params from an event payload — [a, b]
            full-width in the wide profile, [a_lo, a_hi, b_lo, b_hi]
            int16-range halves in the packed one (the at-rest payload
            lane is i16; _net_fault_payload_batch is the encoder).
            Short payloads return zeros: init() rejects net rows that
            would not fit, so the params are never read then."""
            if cfg.packed:
                if cfg.payload_words >= 4:
                    return (join_wide(payload[0], payload[1]),
                            join_wide(payload[2], payload[3]))
                return jnp.int32(0), jnp.int32(0)
            if cfg.payload_words >= 2:
                return payload[0], payload[1]
            return payload[0], jnp.int32(0)

        def apply_fault(ws: WorldState, ev: Event) -> Tuple[WorldState, Outbox]:
            op, a, b = ev.kind, ev.src, ev.dst
            is_kill = op == FAULT_KILL
            is_restart = op == FAULT_RESTART
            alive = upd(ws.alive, a, jnp.where(
                is_kill, False,
                jnp.where(is_restart, True, take_small(ws.alive, a))))
            # Wide read, wrapping narrow write: generations are mod-256
            # by contract (GEN_MASK), so the i8 lane wraps — never
            # saturates (lanes.narrow_wrap, not narrow).
            gen = upd(ws.gen, a, narrow_wrap(
                widen(take_small(ws.gen, a))
                + (is_kill | is_restart).astype(jnp.int32), ws.gen.dtype))
            # Pause buffers; resume releases. Kill/restart clear the pause
            # (the reference swaps in a fresh NodeInfo, `task.rs:211-240`).
            paused = upd(ws.paused, a, jnp.where(
                op == FAULT_PAUSE, True,
                jnp.where((op == FAULT_RESUME) | is_kill | is_restart,
                          False, take_small(ws.paused, a))))
            clog_node = upd(ws.clog_node, a, jnp.where(
                op == FAULT_CLOG_NODE, True,
                jnp.where(op == FAULT_UNCLOG_NODE, False,
                          take_small(ws.clog_node, a))))
            clog_link = upd2(ws.clog_link, a, b, jnp.where(
                op == FAULT_CLOG_LINK, True,
                jnp.where(op == FAULT_UNCLOG_LINK, False,
                          take_small(take_small(ws.clog_link, a), b))))
            # Hot net-config updates take effect at exactly this virtual
            # instant: sends after this event sample the new model
            # (update_config parity, `net/mod.rs:127-130`). Params arrive in
            # the payload — src/dst are 8-bit packed and would truncate µs.
            set_lat = op == FAULT_SET_LATENCY
            set_loss = op == FAULT_SET_LOSS
            pa, pb = net_params(ev.payload)
            lat_min = jnp.where(set_lat, pa, ws.lat_min)
            lat_max = jnp.where(set_lat, pb, ws.lat_max)
            loss = jnp.where(set_loss,
                             pa.astype(jnp.float32) * jnp.float32(1e-6),
                             ws.loss)
            astate_r, ob_r, rng_r = actor.on_restart(cfg, ws.astate, a, ws.now, ws.rng)
            astate = tree_select(is_restart, astate_r, ws.astate)
            rng = tree_select(is_restart, rng_r, ws.rng)
            ob = tree_select(is_restart, ob_r, Outbox.empty(cfg))
            return ws._replace(alive=alive, gen=gen, paused=paused,
                               clog_node=clog_node, clog_link=clog_link,
                               astate=astate, rng=rng, lat_min=lat_min,
                               lat_max=lat_max, loss=loss), ob

        def push_outbox(ws: WorldState, src, ob: Outbox, pre_q: EventQueue,
                        clear) -> WorldState:
            m = cfg.m
            loss = ws.loss  # per-world runtime data, not a jit constant
            # Two draws per slot regardless of validity, batched into one
            # Threefry block: the draw count per step is static, so RNG
            # counters depend only on step index — replayable and
            # backend-independent. Counters (and therefore values) are
            # bit-identical to the per-slot sequential draws.
            xs, rng = next_u32_vec(ws.rng, 2 * m)
            even, odd = deinterleave(xs)
            lat = _u32_to_range(even, ws.lat_min, ws.lat_max)      # (M,)
            u = _u32_to_unit_f32(odd)                              # (M,)
            dst = jnp.clip(ob.dst, 0, cfg.n_nodes - 1)             # (M,)
            if fault_path:
                clogged = take_small(ws.clog_node, src) \
                    | take_small(ws.clog_node, dst) \
                    | take_small(take_small(ws.clog_link, src), dst)  # (M,)
                dropped = (~ob.is_timer) & (clogged | (u < loss))
            else:
                dropped = (~ob.is_timer) & (u < loss)
            # Saturating schedule time: now + delay can wrap int32 when
            # t_limit_us or an actor delay is near 2^31. Both operands
            # are <= INF_TIME, so min-before-add cannot overflow.
            delay = jnp.maximum(jnp.where(ob.is_timer, ob.delay_us, lat), 0)
            t = ws.now + jnp.minimum(delay, INF_TIME - ws.now)
            flags = jnp.where(ob.is_timer, FLAG_TIMER, 0).astype(jnp.int32)
            if fault_path:
                gen_dst = widen(take_small(ws.gen, dst))  # wide in flight
            else:
                gen_dst = jnp.zeros((m,), jnp.int32)
            # Gated on the world's (pre-step) active flag: frozen worlds
            # write nothing into the queue, which is what lets the step's
            # tail skip the whole-state frozen-world restore select.
            enable = ob.valid & ~dropped & ws.active
            if cfg.sequential_insert:
                # The pre-fusion path, kept verbatim as the equivalence
                # reference: M statically unrolled full-queue rewrites.
                q, overflow = ws.queue, ws.overflow
                for i in range(m):  # static unroll
                    ev = Event(time=t[i], kind=ob.kind[i], flags=flags[i],
                               src=jnp.asarray(src, jnp.int32), dst=dst[i],
                               gen=gen_dst[i], payload=ob.payload[i])
                    q, ok = push(q, ev, enable=enable[i])
                    overflow = overflow | ~ok
                qd32 = queue_depth(q)
                # Inserted count via the carried-depth invariant (the
                # chain exposes no n_ins): metrics stay path-independent.
                n_ins = qd32 - widen(ws.qdepth)
                qdepth = narrow(qd32, ws.qdepth.dtype)
            else:
                # Single fused pass (queue.push_many): rank-matched M-row
                # scatter of the compacted outbox — M·(2+P) element
                # writes instead of M full-queue rewrites, bitwise
                # identical to the unrolled chain above (docs/perf.md
                # r7). This replaces the r2-era (Q, M) matching-matrix
                # design the old comment here rejected: no matrices, only
                # the (M, M) compaction index and popcount slot math.
                evs = Event(
                    time=t, kind=ob.kind, flags=flags,
                    src=jnp.broadcast_to(jnp.asarray(src, jnp.int32), (m,)),
                    dst=dst, gen=gen_dst, payload=ob.payload)
                # pre_q + clear rather than ws.queue: push_many fuses the
                # pop's clear into its own time-lane write, so every lane
                # read is a materialized state buffer (see its docstring)
                # and the pop's separate cleared lane becomes dead code.
                q, oks, n_ins = push_many(pre_q, evs, enable, clear=clear)
                overflow = ws.overflow | ~jnp.all(oks)
                # n_ins <= M by construction, so the narrowing cast into
                # the carried slot lane cannot saturate.
                qdepth = ws.qdepth + narrow(n_ins, ws.qdepth.dtype)
            qmax = jnp.maximum(ws.qmax, qdepth)
            metrics = ws.metrics
            if cfg.metrics:
                # Send-side counters (obs/metrics.py). Strictly write-only:
                # nothing above reads the block, so the metrics-on step is
                # bit-identical to metrics-off on every other leaf.
                i32 = jnp.int32
                _n_req, n_inf, n_over = insert_metrics(t, enable, n_ins)
                # dtype-pinned sums: under jax_enable_x64 a plain
                # jnp.sum(i32) widens its accumulator to i64, which would
                # make the metrics block's dtypes depend on a process
                # flag (tracelint TRC003).
                metrics = metrics._replace(
                    msgs_sent=metrics.msgs_sent + jnp.sum(
                        (ob.valid & ~ob.is_timer & ws.active), dtype=i32),
                    drop_loss=metrics.drop_loss + jnp.sum(
                        (ob.valid & dropped & ws.active), dtype=i32),
                    enqueued=metrics.enqueued + jnp.asarray(n_ins, i32),
                    drop_overflow=metrics.drop_overflow + n_over,
                    drop_inf=metrics.drop_inf + n_inf,
                )
            return ws._replace(queue=q, rng=rng, overflow=overflow,
                               qdepth=qdepth, qmax=qmax, metrics=metrics)

        def step(ws: WorldState) -> WorldState:
            # The pop is gated on ws.active too (see push_outbox): a
            # frozen world pops nothing, so every queue lane, counter and
            # actor field below is left untouched through its own masked
            # dataflow — no end-of-step whole-state restore select.
            # The named scopes (madsim/pop, fault, handle, push) tag the
            # ops' metadata for the device trace; they change no op.
            with jax.named_scope("madsim/pop"):
                if fault_path:
                    eligible = (eligible_mask(ws.queue, ws.paused,
                                              cfg.n_nodes) & ws.active)
                else:
                    eligible = ws.active
                q, ev, found, slot = pop_indexed(ws.queue, eligible)
                now = jnp.where(found, jnp.maximum(ws.now, ev.time), ws.now)
                in_time = now < jnp.int32(cfg.t_limit_us)
                ws1 = ws._replace(queue=q, now=now, steps=ws.steps + 1,
                                  qdepth=ws.qdepth
                                  - found.astype(ws.qdepth.dtype))

                dst = jnp.clip(ev.dst, 0, cfg.n_nodes - 1)
                if fault_path:
                    is_fault = (ev.flags & FLAG_FAULT) != 0
                is_timer = (ev.flags & FLAG_TIMER) != 0
                if fault_path:
                    # Generations compare modulo the packed width
                    # (queue.GEN_MASK).
                    stale = is_timer & (
                        ev.gen != (widen(take_small(ws1.gen, dst))
                                   & GEN_MASK))
                    dead = ~take_small(ws1.alive, dst)
                else:
                    # No fault event: every node alive at generation 0.
                    # Constants, not a read of the flag: reading it (and
                    # the src select it feeds) made this step ~7% slower
                    # on a v5e, though XLA's cost model priced it lower.
                    is_fault = stale = dead = np.False_
                deliver = found & in_time & ~is_fault & ~stale & ~dead
                do_fault = found & in_time & is_fault

            if fault_path:
                with jax.named_scope("madsim/fault"):
                    fault_ws, fault_ob = apply_fault(ws1, ev)
            with jax.named_scope("madsim/handle"):
                astate2, act_ob, rng2, hbug = actor.handle(
                    cfg, ws1.astate, ev, now, ws1.rng)
            act_ws = ws1._replace(astate=astate2, rng=rng2)

            ws2 = tree_select(deliver, act_ws, ws1)
            if fault_path:
                ws2 = tree_select(do_fault, fault_ws, ws2)
            ob = tree_select(deliver, act_ob, Outbox.empty(cfg))
            if fault_path:
                ob = tree_select(do_fault, fault_ob, ob)
                src = jnp.where(do_fault,
                                jnp.clip(ev.src, 0, cfg.n_nodes - 1), dst)
            else:
                src = dst
            with jax.named_scope("madsim/push"):
                ws3 = push_outbox(ws2, src, ob, ws.queue, (slot, found))

            with jax.named_scope("madsim/handle"):
                bug_now = (deliver & hbug) | actor.invariant(cfg, ws3.astate)
            bug = ws3.bug | bug_now
            bug_time = jnp.where(bug & ~ws3.bug, now, ws3.bug_time)
            active = found & in_time & ~(cfg.stop_on_bug & bug)
            ws4 = ws3._replace(
                bug=bug, bug_time=bug_time, active=active,
                delivered=ws3.delivered + deliver.astype(jnp.int32),
                dropped=ws3.dropped
                + (found & in_time & ~deliver & ~do_fault).astype(jnp.int32),
            )
            if cfg.metrics:
                # Pop-side counters (obs/metrics.py); ws3.metrics already
                # carries this step's send-side increments. Every
                # increment is gated on ``found`` (itself gated on
                # ws.active), so frozen worlds' blocks never move — no
                # restore needed in the tail below. Write-only: the
                # trajectory never reads these.
                i32 = jnp.int32
                mb = ws3.metrics
                mb = mb._replace(
                    msgs_delivered=mb.msgs_delivered
                    + (deliver & ~is_timer).astype(i32),
                    timer_fires=mb.timer_fires
                    + (deliver & is_timer).astype(i32),
                    drop_stale=mb.drop_stale
                    + (found & in_time & ~is_fault & stale).astype(i32),
                    drop_dead=mb.drop_dead
                    + (found & in_time & ~is_fault & ~stale
                       & dead).astype(i32),
                    drop_out_of_time=mb.drop_out_of_time
                    + (found & ~in_time).astype(i32),
                    vtime_us=mb.vtime_us + (now - ws.now),
                    # onehot's drop semantics cover wild kinds: an
                    # out-of-range index increments no bin.
                    fault_hist=mb.fault_hist
                    + (onehot(ev.kind, NUM_FAULT_KINDS)
                       & do_fault).astype(i32),
                    kind_hist=mb.kind_hist
                    + (onehot(ev.kind, num_kinds) & deliver).astype(i32),
                )
                ws4 = ws4._replace(metrics=mb)
            if cfg.blackbox:
                # Flight recorder (obs/blackbox.py): one packed record
                # per step trace() would record — a valid processed
                # event (found & in_time; ``found`` is already gated on
                # ws.active by the pop) or the ``invariant`` marker for
                # a raise on a step that processed no event. A frozen
                # world records nothing (found is False and its bug flag
                # cannot rise on unchanged state), so — like metrics —
                # the ring needs no restore in the tail below.
                # Write-only: the trajectory never reads these lanes.
                i32 = jnp.int32
                k = cfg.blackbox
                rb = ws3.blackbox
                valid = found & in_time
                raised = bug & ~ws3.bug
                marker = raised & ~valid
                rec = valid | marker
                # Record r lands at slot r % K; a disabled write aims at
                # slot K, which onehot's drop semantics turn into a
                # no-op (the upd-out-of-range idiom).
                cur = jnp.where(rec, jnp.remainder(rb.pos, k), i32(k))
                # Valid entries record the event's own time (trace's
                # t_us); the marker records the post-step clock.
                t_lo, t_hi = split_wide(jnp.where(marker, now, ev.time))
                fl = ((valid & is_timer).astype(i32) * BB_TIMER
                      + (valid & is_fault).astype(i32) * BB_FAULT
                      + (valid & ~is_fault & stale).astype(i32)
                      * BB_DROP_STALE
                      + (valid & ~is_fault & ~stale & dead).astype(i32)
                      * BB_DROP_DEAD
                      + raised.astype(i32) * BB_RAISE
                      + marker.astype(i32) * BB_MARKER)
                rb = rb._replace(
                    pos=rb.pos + rec.astype(i32),
                    # Step index wraps mod the slot-lane width by
                    # contract (decode reconstructs the high bits from
                    # pos) — pre-wrapped so upd's saturating narrow
                    # passes it through untouched (the gen-lane idiom).
                    step_lo=upd(rb.step_lo, cur,
                                narrow_wrap(ws.steps, rb.step_lo.dtype)),
                    t_lo=upd(rb.t_lo, cur, t_lo),
                    t_hi=upd(rb.t_hi, cur, t_hi),
                    kind=upd(rb.kind, cur, jnp.where(valid, ev.kind, 0)),
                    src=upd(rb.src, cur, jnp.where(valid, ev.src, -1)),
                    dst=upd(rb.dst, cur, jnp.where(valid, ev.dst, -1)),
                    flags=upd(rb.flags, cur, fl),
                )
                ws4 = ws4._replace(blackbox=rb)
            # Frozen worlds pass through untouched. Every lane write above
            # is already gated on ws.active (the pop found nothing, the
            # outbox was disabled, faults/delivery/bug flags all require
            # ``found``), so only the two unconditionally-advancing pieces
            # need an explicit restore: the RNG cursor (push_outbox draws
            # its static 2M block every step) and the step counter. This
            # replaces a whole-state select — ~1 op per state element per
            # step — with two scalar-sized ones (docs/perf.md r7).
            return ws4._replace(
                rng=tree_select(ws.active, ws4.rng, ws.rng),
                steps=jnp.where(ws.active, ws4.steps, ws.steps))

        return step

    # ------------------------------------------------------------------
    # Batched run loops
    # ------------------------------------------------------------------
    def _run_steps_impl(self, state: WorldState, k: int) -> WorldState:
        """``k`` masked steps, as blocks of :data:`EXIT_BLOCK` steps that
        stop at the first block boundary where no world of the batch is
        live. The step is the identity on a frozen world, so the blocks
        left out change no leaf: the result equals ``k`` steps bitwise.
        ``k <= EXIT_BLOCK`` is the plain scan. Inside ``shard_map`` the
        check sees its own shard only and adds no collective."""
        batched = self._batched_step

        def scan(s, n):
            s, _ = jax.lax.scan(lambda c, _: (batched(c), None), s, None,
                                length=n)
            return s

        if k <= EXIT_BLOCK:
            return scan(state, k)
        n_blocks, rem = divmod(k, EXIT_BLOCK)

        def cond(carry):
            s, i = carry
            return (i < n_blocks) & jnp.any(s.active)

        def body(carry):
            s, i = carry
            return scan(s, EXIT_BLOCK), i + 1

        state, _ = jax.lax.while_loop(cond, body, (state, jnp.int32(0)))
        if rem:
            state = jax.lax.cond(jnp.any(state.active),
                                 lambda s: scan(s, rem), lambda s: s, state)
        return state

    @staticmethod
    def _steps_executed(steps0, state: WorldState, k: int):
        """Steps :meth:`_run_steps_impl` executed on a batch whose per-world
        ``steps`` counters read ``steps0`` before the call: int32 scalar.

        A world's counter advances on exactly the steps that start with it
        live, and a block runs iff some world is live at its start. So with
        M the most steps any world took, the loop ran ⌈M / EXIT_BLOCK⌉
        blocks, and the remainder only after all of them (then M exceeds
        the whole blocks' steps and the count is ``k``)."""
        if k <= EXIT_BLOCK:
            return jnp.int32(k)
        most = jnp.max(state.steps - steps0, initial=0)
        blocks = (most + (EXIT_BLOCK - 1)) // EXIT_BLOCK
        return jnp.minimum(blocks * EXIT_BLOCK, k).astype(jnp.int32)

    def run_steps(self, state: WorldState, k: int) -> WorldState:
        """Advance every world by ``k`` masked steps. The device stops
        early, at a 16-step block boundary, once no world is live: the
        steps it leaves out would change nothing.

        ``state`` is **donated**: its buffers are updated in place and the
        passed-in pytree is dead after the call — rebind
        (``state = eng.run_steps(state, k)``), never reuse the argument.
        """
        return self._run_steps(state, k)

    def _superstep_impl(self, state: WorldState, stop_threshold,
                        stop_on_bug, k_chunks, *, chunk_steps: int,
                        k_max: int, reduce_sum, min_one: bool = False,
                        cov=None, cov_fold=None):
        """Up to ``k_chunks`` chunk bodies under ONE ``lax.while_loop``.

        This is the device half of the pipelined sweep orchestration
        (parallel/sweep.py): instead of one host dispatch per chunk, the
        host dispatches a *superstep* of K chunks and the early-exit
        decisions the serial loop made between chunks run ON DEVICE —
        the loop stops after the first chunk where the (reduced) active
        count drops to ``stop_threshold`` or, with ``stop_on_bug`` set,
        any world's bug flag rises. Threshold, stop flag AND ``k_chunks``
        are *traced scalars* (only the ``k_max`` history-buffer width is
        static), so ONE compiled program serves every threshold and
        superstep length the sweep cycles through — the loop bound of a
        ``lax.while_loop`` is dynamic anyway, and keying compiles on K
        would re-pay the whole step-body compile per ramp value.

        The condition is checked BEFORE the first chunk too: a superstep
        dispatched against a state that already satisfies a stop
        condition is a bitwise pass-through (zero chunks run). That
        no-op-by-construction property is what lets the sweep dispatch
        superstep k+1 before reading superstep k's scalars without ever
        advancing a world the serial loop would not have advanced.

        ``min_one`` (static) forces the FIRST chunk to run regardless of
        the entry condition — the serial loop's exact cadence right
        after a refill/shrink (it always runs one chunk before
        re-evaluating occupancy, even when the refilled count is already
        at the threshold). The sweep sets it on the first dispatch of
        each occupancy epoch; speculative dispatch-ahead supersteps keep
        ``min_one=False`` so stale ones stay pass-through no-ops.

        ``reduce_sum`` reduces a per-shard int32 scalar over the world
        axis — ``lax.psum`` inside a shard_mapped sweep, ``jnp.sum``'s
        identity under plain vmap use. Returns ``(state, any_bug,
        n_active, k_done, hist, shard_steps)`` where ``hist[j]`` is the
        active count measured after chunk ``j`` (-1 for chunks not run),
        exactly the per-chunk sequence the serial loop observed, and
        ``shard_steps`` is the steps each shard's chunks executed
        (:meth:`_steps_executed`), summed over shards: times the shard
        width, the slot-steps the device ran.

        ``cov``/``cov_fold`` (obs/coverage.py, set together or not at
        all): the retire-time coverage fold. ``cov`` is the behavior
        ledger carried through the loop; after each chunk body the fold
        callback receives ``(cov, pre_chunk_active, post_chunk_state)``
        and scatters the signatures of the worlds whose active flag fell
        during the chunk — each world folds exactly once, with no extra
        carried bookkeeping, and the fold *sequence* matches the serial
        loop's because both execute identical chunk bodies. Purely
        read-only over the simulation state (the bitwise-invisibility
        contract of ``MetricsBlock`` extends to it). With coverage on
        the return grows to ``(..., hist, cov, cov_hist, shard_steps)``
        where ``cov_hist[j]`` is the cumulative distinct-behavior count after
        chunk ``j`` (-1 beyond ``k_done``) — the novelty curve sampled
        at exactly the ``hist`` cadence.
        """
        from ..obs.coverage import distinct_count

        def measure(s):
            any_bug = reduce_sum(jnp.any(s.bug).astype(jnp.int32)) > 0
            # dtype-pinned: jnp.sum(i32) widens to i64 under x64 (TRC003).
            n_active = reduce_sum(jnp.sum(s.active, dtype=jnp.int32))
            return any_bug, n_active

        stop_threshold = jnp.asarray(stop_threshold, jnp.int32)
        stop_on_bug = jnp.asarray(stop_on_bug, bool)
        k_chunks = jnp.minimum(jnp.asarray(k_chunks, jnp.int32), k_max)
        any_bug0, n_active0 = measure(state)
        hist0 = jnp.full((k_max,), -1, jnp.int32)
        with_cov = cov_fold is not None
        # The coverage slots ride the carry ONLY when the fold is on, so
        # the coverage-off superstep remains the exact pre-coverage
        # program (None is an empty pytree: zero extra carry leaves).
        cov_hist0 = jnp.full((k_max,), -1, jnp.int32) if with_cov else None

        def cond(carry):
            _s, i, any_bug, n_active, _hist, _cov, _ch, _ran = carry
            run_more = ((n_active > stop_threshold)
                        & ~(stop_on_bug & any_bug))
            if min_one:
                run_more = (i == 0) | run_more
            return (i < k_chunks) & run_more

        def body(carry):
            s, i, _any_bug, _n_active, hist, cv, ch, ran = carry
            act0, steps0 = s.active, s.steps
            s = self._run_steps_impl(s, chunk_steps)
            ran = ran + self._steps_executed(steps0, s, chunk_steps)
            any_bug, n_active = measure(s)
            hist = jax.lax.dynamic_update_index_in_dim(hist, n_active, i, 0)
            if with_cov:
                cv = cov_fold(cv, act0, s)
                ch = jax.lax.dynamic_update_index_in_dim(
                    ch, distinct_count(cv[0]), i, 0)
            return s, i + 1, any_bug, n_active, hist, cv, ch, ran

        state, k_done, any_bug, n_active, hist, cov, cov_hist, ran = \
            jax.lax.while_loop(
                cond, body,
                (state, jnp.int32(0), any_bug0, n_active0, hist0,
                 cov, cov_hist0, jnp.int32(0)))
        shard_steps = reduce_sum(ran)
        if with_cov:
            return (state, any_bug, n_active, k_done, hist, cov, cov_hist,
                    shard_steps)
        return state, any_bug, n_active, k_done, hist, shard_steps

    def _fused_superstep_impl(self, state: WorldState, extras, stop_on_bug,
                              k_chunks, *, chunk_steps: int, k_max: int,
                              post_chunk, entry_stop):
        """:meth:`_superstep_impl` with an in-loop epoch body: the
        whole-hunt device loop.

        Where the plain superstep EXITS when occupancy crosses a
        threshold (so the host can refill/compact between dispatches),
        this variant hands each chunk boundary to ``post_chunk`` — a
        traced callback that owns the epoch machinery the serial sweep
        loop ran on host: compaction, retiring-tail harvest, coverage/
        lineage folds, guided child generation, the refill select and
        the seed-cursor advance (parallel/sweep.py builds it). The loop
        itself never stops for occupancy; it stops only when the
        callback says the *hunt* is over (cursor dry and no world
        active, or a bug under ``stop_on_bug``) or the chunk budget
        ``k_chunks`` is spent.

        ``extras`` is an opaque pytree carried through the loop — the
        sweep threads the slot→seed index, the device seed cursor, the
        per-seed observation buffers, the coverage ledger and the search
        corpus through it. ``post_chunk(s, extras, act0, any_bug,
        n_active, i)`` returns ``(s, extras, stop)``; ``entry_stop(
        extras, any_bug0, n_active0)`` evaluates the same stop predicate
        BEFORE the first chunk, preserving the plain superstep's
        pass-through property (a dispatch against a finished hunt runs
        zero chunks bitwise).

        Reductions are full-array ``jnp`` ops, not ``psum``: the fused
        program is a plain ``jit`` partitioned by GSPMD (the
        ``_compactor`` precedent — its global stable argsort cannot run
        under ``shard_map``), so a dtype-pinned integer sum over the
        whole world axis is already the global count.
        """
        def measure(s):
            any_bug = jnp.any(s.bug)
            # dtype-pinned: jnp.sum(i32) widens to i64 under x64 (TRC003).
            n_active = jnp.sum(s.active, dtype=jnp.int32)
            return any_bug, n_active

        stop_on_bug = jnp.asarray(stop_on_bug, bool)
        k_chunks = jnp.minimum(jnp.asarray(k_chunks, jnp.int32), k_max)
        any_bug0, n_active0 = measure(state)
        hist0 = jnp.full((k_max,), -1, jnp.int32)
        stop0 = entry_stop(extras, any_bug0, n_active0)

        def cond(carry):
            _s, i, stop, _ab, _na, _hist, _extras = carry
            return (i < k_chunks) & ~stop

        def body(carry):
            s, i, _stop, _ab, _na, hist, extras = carry
            act0 = s.active
            s = self._run_steps_impl(s, chunk_steps)
            any_bug, n_active = measure(s)
            hist = jax.lax.dynamic_update_index_in_dim(hist, n_active, i, 0)
            s, extras, stop = post_chunk(s, extras, act0, any_bug,
                                         n_active, i)
            return s, i + 1, stop, any_bug, n_active, hist, extras

        state, k_done, _stop, any_bug, n_active, hist, extras = \
            jax.lax.while_loop(
                cond, body,
                (state, jnp.int32(0), stop0, any_bug0, n_active0, hist0,
                 extras))
        return state, extras, any_bug, n_active, k_done, hist

    def refill_traced(self, state: WorldState, slot_mask, seeds_lo,
                      seeds_hi, faults) -> WorldState:
        """:meth:`refill` as a pure traced program — the in-loop form.

        Built for the fused superstep's epoch body: no host validation,
        no ``device_put`` (everything already rides the enclosing
        program), no donation bookkeeping — just the same
        ``_init_one``-per-world init the jitted batched init runs,
        followed by the masked world select. ``seeds_lo``/``seeds_hi``
        are the split uint32 halves of the uint64 seeds (one row per
        batch slot; rows outside the mask initialize placeholder worlds
        the select discards, exactly like :meth:`refill`), ``faults`` is
        a per-slot ``(W, F, 4)`` int32 schedule block. Latency/loss
        configs come from the engine config — the only form the sweep's
        refill path ever uses. Bitwise contract: equal inputs produce
        worlds bit-identical to :meth:`refill`'s, because both run the
        same ``vmap``'d ``_init_one`` (jit does not change values).
        """
        self._admit_fault_rows(faults.shape[-2])
        w = state.active.shape[0]
        lat_min = jnp.full((w,), int(self.cfg.latency_min_us), jnp.int32)
        lat_max = jnp.full((w,), int(self.cfg.latency_max_us), jnp.int32)
        loss = jnp.full((w,), float(self.cfg.loss_rate), jnp.float32)
        fresh = jax.vmap(self._init_one)(seeds_lo, seeds_hi, faults,
                                         lat_min, lat_max, loss)
        return tree_select_worlds(slot_mask, fresh, state)

    def _run_impl(self, state: WorldState, max_steps: int) -> WorldState:
        batched = self._batched_step

        def cond(carry):
            s, i = carry
            return jnp.any(s.active) & (i < max_steps)

        def body(carry):
            s, i = carry
            return batched(s), i + 1

        state, _ = jax.lax.while_loop(cond, body, (state, jnp.int32(0)))
        return state

    def run(self, state: WorldState, max_steps: int = 100_000) -> WorldState:
        """Step until every world is inactive (or ``max_steps``).

        ``state`` is **donated** (see :meth:`run_steps`): the argument is
        dead after the call; rebind the return value. Peak device memory
        for the run is ~1× the state plus loop temporaries, not the 2×
        double-buffer of an undonated functional update (tier-1-tested
        via ``compiled.memory_analysis()``).
        """
        return self._run(state, max_steps)

    # ------------------------------------------------------------------
    # Single-seed tracing (repro tooling)
    # ------------------------------------------------------------------
    def trace(self, seed: int, max_steps: int = 2_000,
              faults: Optional[np.ndarray] = None) -> List[Dict[str, Any]]:
        """Replay ONE seed and return its full event trace.

        The device analog of re-running a failing seed with MADSIM_LOG on:
        feed a seed from ``SweepResult.failing_seeds`` (or
        ``device_first_failing_seed``) back in and get the ordered list of
        events — virtual time, kind, src→dst, fault/timer flags, payload,
        and the step at which the bug flag first rose. Runs as one scan on
        device; decoding happens on host afterwards.
        """
        state = jax.tree.map(lambda x: x[0],
                             self.init(np.asarray([seed], np.uint64),
                                       faults=faults))

        def body(s, _):
            # Pure peek of what step will pop, under the same pause-aware
            # eligibility the step itself uses.
            _q, ev, found = pop(
                s.queue, eligible_mask(s.queue, s.paused, self.cfg.n_nodes))
            s2 = self._step_one(s)
            # Mirror the step's own gates exactly: an event popped at/past
            # t_limit_us was not processed, and a stale timer or a message
            # to a dead node was popped-and-dropped, not delivered.
            in_time = jnp.maximum(s.now, ev.time) < jnp.int32(self.cfg.t_limit_us)
            dst_c = jnp.clip(ev.dst, 0, self.cfg.n_nodes - 1)
            is_fault = (ev.flags & FLAG_FAULT) != 0
            stale = ((ev.flags & FLAG_TIMER) != 0) & \
                (ev.gen != (widen(take_small(s2.gen, dst_c)) & GEN_MASK))
            dead = ~take_small(s2.alive, dst_c)
            delivered = ~is_fault & ~stale & ~dead
            rec = (found & s.active & in_time, ev.time, ev.kind, ev.flags,
                   ev.src, ev.dst, ev.payload, delivered, s2.bug, s2.now)
            return s2, rec

        final, recs = jax.lax.scan(body, state, None, length=max_steps)
        valid, time_us, kind, flags, src, dst, payload, delivered, bug, now_us = \
            (np.asarray(r) for r in recs)
        kind_names = getattr(self.actor, "kind_names", None)
        # Shared with the blackbox ring decoder (obs/blackbox.py) so the
        # two decoders cannot drift apart — the --crosscheck contract.
        fault_names = FAULT_NAMES
        out: List[Dict[str, Any]] = []
        bug_seen = False
        for i in range(max_steps):
            raised_here = bool(bug[i]) and not bug_seen
            if not valid[i]:
                if raised_here:
                    # The invariant rose on a step that processed no event
                    # (e.g. an out-of-time or empty-queue step): record it
                    # as its own marker so the raise point is never lost.
                    out.append({"step": i, "t_us": int(now_us[i]),
                                "kind": "invariant", "timer": False,
                                "src": -1, "dst": -1, "payload": [],
                                "bug_raised": True})
                    bug_seen = True
                continue
            is_fault = bool(flags[i] & FLAG_FAULT)
            k = int(kind[i])
            if is_fault:
                name = f"fault:{fault_names.get(k, k)}"
            elif kind_names is not None and 0 <= k < len(kind_names):
                name = kind_names[k]
            else:
                name = str(k)
            entry = {
                "step": i,
                "t_us": int(time_us[i]),
                "kind": name,
                "timer": bool(flags[i] & FLAG_TIMER),
                "src": int(src[i]),
                "dst": int(dst[i]),
                "payload": payload[i].tolist(),
            }
            if not is_fault and not delivered[i]:
                # Popped but NOT handled: stale timer (node generation
                # changed) or destination dead at delivery time.
                entry["dropped"] = True
            if raised_here:
                entry["bug_raised"] = True
                bug_seen = True
            out.append(entry)
        if bool(np.asarray(final.active)):
            # max_steps hit with the world still live: mark the cut
            # explicitly instead of silently ending the list — a consumer
            # (or a human) must never mistake a truncated timeline for a
            # retired world (obs/timeline.py renders the marker).
            out.append({"step": max_steps, "t_us": int(np.asarray(final.now)),
                        "kind": "truncated", "timer": False, "src": -1,
                        "dst": -1, "payload": [], "bug_seen": bug_seen})
            if not bug_seen:
                import warnings

                warnings.warn(
                    f"trace(seed={seed}) truncated at max_steps={max_steps} "
                    "before any bug_raised event — raise max_steps if you "
                    "expected the invariant violation in this window",
                    RuntimeWarning, stacklevel=2)
        return out

    # ------------------------------------------------------------------
    # Observation
    # ------------------------------------------------------------------
    def observe_device(self, state: WorldState) -> Dict[str, jnp.ndarray]:
        """The observation dict as device values — traceable under jit.

        Same fields as :meth:`observe` with no host conversion, so jitted
        programs (e.g. the sweep's frozen-tail retirement gather,
        parallel/sweep.py) can slice observations ON DEVICE and ship only
        the rows they need across the host boundary.
        """
        out = {
            "now_us": state.now,
            "active": state.active,
            "steps": state.steps,
            "delivered": state.delivered,
            "dropped": state.dropped,
            "overflow": state.overflow,
            "qmax": state.qmax,
            "bug": state.bug,
            "bug_time_us": state.bug_time,
            # The carried lane, not a recomputed reduction — the depth
            # invariant (carried == recomputed) is a tier-1 test.
            "queue_depth": state.qdepth,
        }
        if self.cfg.metrics and state.metrics is not None:
            # One ``m_<field>`` entry per MetricsBlock counter: the sweep's
            # retirement machinery then attributes metrics per seed exactly
            # like any other observation (slot→seed index, device-side tail
            # gathers), and SweepResult.metrics reassembles the frames.
            out.update({f"m_{name}": val for name, val
                        in state.metrics._asdict().items()})
        if self.cfg.blackbox and state.blackbox is not None:
            # One ``bb_<field>`` entry per ring lane: the flight
            # recorder then rides every existing observation surface —
            # retirement tail gathers, per-seed scatters, checkpoint
            # aux arrays, fleet merges — with zero recorder-specific
            # plumbing (obs/blackbox.py decodes the rows back).
            out.update({f"bb_{name}": val for name, val
                        in state.blackbox._asdict().items()})
        out.update(self.actor.observe(self.cfg, state.astate))
        return out

    def observe(self, state: WorldState) -> Dict[str, np.ndarray]:
        """Pull engine metrics (plus the actor's) to host as numpy arrays.

        One explicit ``device_get`` of the whole dict (not per-field
        ``np.asarray``), so the pull stays a single, *explicit* transfer
        under ``jax.transfer_guard`` — the sweep's sync-discipline test
        counts every device→host crossing.
        """
        out = jax.device_get(self.observe_device(state))
        return {k: np.asarray(v) for k, v in out.items()}
