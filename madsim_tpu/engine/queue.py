"""Fixed-capacity masked event queue (per world; vmapped over the seed axis).

The device analog of the host timer wheel + NetSim delivery queue
(`madsim/src/sim/time/mod.rs:159-214`, `net/mod.rs:173-197`): every pending
future occurrence in a world — timer expiry, message delivery, fault
injection — is one slot in a flat array. ``pop`` is a masked argmin over the
time lane (a single vectorized reduction, which is exactly the shape TPUs
like); ``push`` fills the first free slot, and ``push_many`` inserts a whole
outbox of events in one fused pass (bitwise identical to chained pushes —
see its docstring). No pointer heap: priority order is recomputed per pop,
which for capacities ~64-256 is cheaper on TPU than maintaining heap
invariants with data-dependent control flow.

Storage is two lanes plus payload: the time lane (``INF_TIME`` ⇔ slot free —
there is no separate valid lane) and a *packed meta* lane holding
kind/flags/src/dst/gen in one int32. Since round 7 the per-step update is a
sparse in-place one — ``push_many`` scatters M rows and, under the run
loop's buffer donation, XLA aliases the queue in place — but the lanes are
still read wholesale every step (pop's min, the free mask), so queue
bytes/slot
remain the engine's HBM-traffic knob — packing the five meta fields and
dropping the valid lane cuts that by ~35% vs one-lane-per-field. Width
limits (asserted
at :func:`~madsim_tpu.engine.core.DeviceEngine.init` time): kind < 64,
flags < 4, src/dst < 256 nodes, and generations compare modulo 256
(``GEN_MASK``) — a node must be killed 256 times within one pending timer's
lifetime to alias, far beyond any fault schedule.

Tie-break: equal deadlines pop in *slot order*, and freed slots are reused
lowest-first, so the order is deterministic but not FIFO — the host engine
breaks ties by insertion sequence instead. Schedules are engine-specific;
determinism-per-seed is the contract (see engine/__init__ docstring).
An event scheduled exactly at ``INF_TIME`` (delay saturation) is dropped at
push time — it could never fire before any time limit anyway.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from . import lanes as _lanes
from .lanes import narrow, onehot, prefix_count, take_small, widen

INF_TIME = np.int32(2**31 - 1)

# Event flag bits.
FLAG_TIMER = 1  # gen-checked against the destination node's generation
FLAG_FAULT = 2  # engine-handled fault-injection event (kind = fault op)

# Generation comparisons wrap at this mask (8 packed bits).
GEN_MASK = 0xFF


def pack_meta(kind, flags, src, dst, gen) -> jnp.ndarray:
    """kind[0:6] | flags[6:8] | src[8:16] | dst[16:24] | gen[24:32]."""
    return ((kind & 0x3F) | ((flags & 0x3) << 6) | ((src & 0xFF) << 8)
            | ((dst & 0xFF) << 16) | ((gen & 0xFF) << 24)).astype(jnp.int32)


def unpack_meta(meta):
    """→ (kind, flags, src, dst, gen), each int32."""
    return (meta & 0x3F, (meta >> 6) & 0x3, (meta >> 8) & 0xFF,
            (meta >> 16) & 0xFF, (meta >> 24) & 0xFF)


class Event(NamedTuple):
    """One scheduled occurrence. All fields int32; payload is (P,) int32."""

    time: jnp.ndarray
    kind: jnp.ndarray
    flags: jnp.ndarray
    src: jnp.ndarray
    dst: jnp.ndarray
    gen: jnp.ndarray
    payload: jnp.ndarray

    @staticmethod
    def make(time, kind, payload_words: int, flags=0, src=0, dst=0, gen=0,
             payload=()) -> "Event":
        """Build a concrete event, zero-padding the payload to P words."""
        pad = list(payload) + [0] * (payload_words - len(payload))
        return Event(
            time=jnp.asarray(time, jnp.int32),
            kind=jnp.asarray(kind, jnp.int32),
            flags=jnp.asarray(flags, jnp.int32),
            src=jnp.asarray(src, jnp.int32),
            dst=jnp.asarray(dst, jnp.int32),
            gen=jnp.asarray(gen, jnp.int32),
            payload=jnp.asarray(pad, jnp.int32),
        )


class EventQueue(NamedTuple):
    """Struct-of-arrays event store: time/meta are (Q,), payload is (Q, P).
    A slot is free ⇔ its time is ``INF_TIME``; meta packs the five scalar
    fields (:func:`pack_meta`)."""

    time: jnp.ndarray
    meta: jnp.ndarray
    payload: jnp.ndarray


def empty_queue(capacity: int, payload_words: int,
                payload_dtype=jnp.int32) -> EventQueue:
    """``payload_dtype``: the at-rest payload lane dtype — int16 under
    the packed profile (``EngineConfig.lanes``), int32 in the reference
    path and for standalone callers. The time and meta lanes are always
    int32 (time is a wide lane; meta is already bit-packed)."""
    return EventQueue(
        time=jnp.full((capacity,), INF_TIME, jnp.int32),
        meta=jnp.zeros((capacity,), jnp.int32),
        payload=jnp.zeros((capacity, payload_words), payload_dtype),
    )


def valid_mask(q: EventQueue) -> jnp.ndarray:
    """(Q,) bool: which slots hold a pending event."""
    return q.time != INF_TIME


def depth(q: EventQueue) -> jnp.ndarray:
    """Number of pending events. dtype pinned: under jax_enable_x64 an
    unpinned integer sum accumulates as int64, which would fork the
    metrics lane dtype between init-built and refill-built worlds."""
    return jnp.sum(valid_mask(q), dtype=jnp.int32)


def push(q: EventQueue, ev: Event, enable=True) -> Tuple[EventQueue, jnp.ndarray]:
    """Insert ``ev`` into the first free slot. Returns (queue, ok).

    ``enable`` masks the push (False ⇒ no-op, ok=True) so callers can keep a
    single static code path for conditional sends. ok=False ⇒ overflow.
    An event with time == INF_TIME is dropped (ok=True): it could never
    fire, and storing it would alias the free-slot sentinel.

    Scatter-free: the slot is addressed by a one-hot mask so the whole
    insert is elementwise over the Q lanes and fuses under vmap (see
    engine/lanes.py for why this beats ``.at[slot].set`` on TPU).
    """
    enable = jnp.asarray(enable, bool) & (jnp.asarray(ev.time, jnp.int32)
                                          < INF_TIME)
    free = q.time == INF_TIME
    free_any = jnp.any(free)
    # First free slot: one-hot of the argmax over free (first True).
    mask = onehot(jnp.argmax(free), q.time.shape[0])
    do = mask & enable & free_any
    ok = ~enable | free_any
    q = EventQueue(
        time=jnp.where(do, jnp.asarray(ev.time, jnp.int32), q.time),
        meta=jnp.where(do, pack_meta(ev.kind, ev.flags, ev.src, ev.dst,
                                     ev.gen), q.meta),
        # In-flight payloads are int32; the write saturates into the
        # at-rest lane dtype (a no-op cast on the wide profile).
        payload=jnp.where(do[:, None],
                          narrow(ev.payload, q.payload.dtype)[None, :],
                          q.payload),
    )
    return q, ok


def push_many(q: EventQueue, evs: Event, enable=None,
              clear=None) -> Tuple[EventQueue, jnp.ndarray, jnp.ndarray]:
    """Insert up to M events in ONE pass over the queue lanes.
    Returns ``(queue, ok, n_inserted)``; ``ok`` is (M,) bool per event.

    ``evs`` is a batched :class:`Event` (every field carries a leading
    (M,) axis; payload is (M, P)); ``enable`` an optional (M,) bool mask.
    Semantics are **bitwise identical** to the sequential chain
    ``for i in range(M): q, ok[i] = push(q, evs[i], enable[i])`` — the
    contract the engine's trajectory-equivalence tests pin
    (tests/test_queue_insert.py, via ``EngineConfig.sequential_insert``):

    - events keep their order: the i-th *enabled* event (after the
      time < INF_TIME drop filter) lands in the i-th lowest free slot;
    - overflow matches: once the free slots run out, every remaining
      enabled event reports ok=False and writes nothing;
    - an event at INF_TIME is dropped (ok=True) and consumes no slot.

    Why one pass: each sequential ``push`` recomputes the free mask, an
    argmax and a one-hot, then rewrites all three lanes — M·Q·(2+P)
    selects per call site, the single largest int-op consumer in the
    step (docs/perf.md "Single-pass insert"). Here the assignment is
    closed-form — the i-th enabled event's cumulative-sum *rank* names
    the free slot it gets — so the insert is M row writes, not M lane
    rewrites: the free mask packs into Q/32 uint32 words, each rank's
    target slot is the word's lowest set bit (clear-lowest-bit +
    ``population_count``, a handful of scalar ops per rank), and the
    compacted events scatter into those slots. With the run loop's
    buffer donation the scatter updates the queue in place: per step the
    queue costs M·(2+P) element writes instead of Q·(2+P). (The first
    build used the issue's (Q,)-gather-driven select; measurement moved
    it to this scatter form — the batched gather materializes a (Q, 2)
    index buffer per world that dominated peak temp memory, while the
    scatter's index buffer is (M, 2). Same rank assignment either way,
    and the M-row scatter is also strictly less write traffic.)

    ``clear``: optional ``(slot, found)`` from :func:`pop_indexed` over
    THIS ``q``. When given, slot ``slot`` is treated as freed (and its
    time lane rewritten to INF unless re-filled) — i.e. the result equals
    pushing into the pop-cleared queue. The step uses this to fuse the
    pop's clear into the insert's own scatter pass, so the pop never
    rewrites the time lane at all: routing the cleared lane through a
    separate elementwise write makes CPU XLA clone the whole pop chain
    into every downstream reader of the free mask (measured ~2×
    over-pricing of the insert, docs/perf.md r7).
    """
    m = evs.time.shape[0]
    qcap = q.time.shape[0]
    t = jnp.asarray(evs.time, jnp.int32)
    en = jnp.ones((m,), bool) if enable is None else jnp.asarray(enable, bool)
    en = en & (t < INF_TIME)
    # rank[i]: how many enabled events precede i == which free slot (in
    # lowest-first order) the sequential chain would hand event i.
    rank = prefix_count(en)
    base_time = q.time
    free = base_time == INF_TIME
    scatter = _lanes.gathers_are_cheap()
    if clear is not None:
        cslot, cfound = clear
        cleared = onehot(cslot, qcap) & cfound
        free = free | cleared
        if scatter:
            base_time = base_time.at[jnp.where(cfound, cslot, qcap)].set(
                INF_TIME, mode="drop")
        else:
            base_time = jnp.where(cleared, INF_TIME, base_time)
    # Pack the free mask into uint32 words: bit s of word w ⇔ slot
    # 32w + s is free. Everything below runs on these scalars.
    words = []
    for w in range((qcap + 31) // 32):
        lanes = min(32, qcap - 32 * w)
        pow2 = jnp.asarray(np.uint32(1) << np.arange(lanes, dtype=np.uint32),
                           jnp.uint32)
        words.append(jnp.sum(jnp.where(free[32 * w:32 * w + lanes], pow2,
                                       jnp.uint32(0))))
    n_free = sum(lax.population_count(w).astype(jnp.int32) for w in words)
    # Static slices, not rank[-1]: under vmap that index is a gather.
    n_en = (lax.slice(rank, (m - 1,), (m,))
            + lax.slice(en, (m - 1,), (m,)).astype(jnp.int32))[0]
    ok = ~en | (rank < n_free)
    # Order-preserving compaction of the enabled events to the front:
    # row r of the compacted table is the event with rank r. The (M, M)
    # one-hot collapses to an M-long *index* vector and the field tables
    # are gathered rows (tiny-source gathers, lanes.take_small).
    cm = en[None, :] & (rank[None, :] == jnp.arange(m)[:, None])
    ev_idx = jnp.sum(jnp.where(cm, jnp.arange(m)[None, :], 0), axis=1)
    meta = pack_meta(evs.kind, evs.flags, evs.src, evs.dst, evs.gen)
    ct = take_small(t, ev_idx)
    cmeta = take_small(meta, ev_idx)
    cpay = take_small(evs.payload, ev_idx)
    # Target slot of rank r = lowest set bit still standing; clear it and
    # move on. Ranks past n_en aim at slot Q and are dropped.
    if m * len(words) > _UNROLL_MAX:
        slots = _rank_slots_sorted(free, m, n_en)
    else:
        slots = _rank_slots_unrolled(words, m, n_en, qcap)
    # Saturating narrow at the write boundary (packed payload lane);
    # engine-split wide params (lanes.split_wide) are in range by
    # construction, so the clip never bites them.
    cpay = narrow(cpay, q.payload.dtype)
    if scatter:
        # Slots are distinct (dropped ranks all aim at the same
        # out-of-range Q, which "drop" discards), so the scatters are
        # order-independent; XLA chains the clear scatter and this one
        # through a single buffer.
        q = EventQueue(time=base_time.at[slots].set(ct, mode="drop"),
                       meta=q.meta.at[slots].set(cmeta, mode="drop"),
                       payload=q.payload.at[slots].set(cpay, mode="drop"))
    else:
        # Where gathers are not cheap neither are scatters (the TPU):
        # one select per rank over the Q lanes, the same writes.
        time, meta, pay = base_time, q.meta, q.payload
        for r in range(m):
            hit = slots[r] == jnp.arange(qcap, dtype=jnp.int32)
            time = jnp.where(hit, ct[r], time)
            meta = jnp.where(hit, cmeta[r], meta)
            pay = jnp.where(hit[:, None], cpay[r], pay)
        q = EventQueue(time=time, meta=meta, payload=pay)
    return q, ok, jnp.minimum(n_en, n_free)


# Above this many (rank x word) steps the unrolled lowest-set-bit chain is
# replaced by one sort: the chain is a serial dependency M*Q/32 selects
# long, and at init's whole-fault-schedule batches (M in the hundreds)
# XLA CPU did not compile it in 30 minutes. The step's outbox batches
# (M <= 8, Q <= 64) stay unrolled: sorting them made phase A's warm
# sweep 19% slower on a v5e and the CPU sweeps 5-6x slower (PR 21). The
# threshold between those two ends is not tuned.
_UNROLL_MAX = 256


def _rank_slots_unrolled(words, m, n_en, qcap):
    slots = []
    for r in range(m):
        pos = jnp.int32(qcap)
        placed = jnp.asarray(False)
        nxt = []
        for wi, w in enumerate(words):
            lsb = w & (~w + jnp.uint32(1))
            p = lax.population_count(lsb - jnp.uint32(1)).astype(jnp.int32) \
                + 32 * wi
            use = ~placed & (w != 0)
            pos = jnp.where(use, p, pos)
            nxt.append(jnp.where(use, w & (w - jnp.uint32(1)), w))
            placed = placed | use
        words = nxt
        slots.append(jnp.where(r < n_en, pos, qcap))
    return jnp.stack(slots)


def _rank_slots_sorted(free, m, n_en):
    """The same assignment in closed form: rank r takes the r-th free
    slot in lowest-first order (a stable sort puts the free slots first,
    in slot order); ranks past the free count or ``n_en`` aim at Q."""
    qcap = free.shape[0]
    order = jnp.argsort((~free).astype(jnp.int32), stable=True)
    pick = jnp.concatenate([order[:m].astype(jnp.int32),
                            jnp.full((max(m - qcap, 0),), qcap, jnp.int32)])
    n_free = jnp.sum(free, dtype=jnp.int32)
    r = jnp.arange(m, dtype=jnp.int32)
    return jnp.where((r < n_en) & (r < n_free), pick, qcap)


def insert_metrics(times, enable, n_inserted):
    """Insert-path counters for the observability block
    (:mod:`madsim_tpu.obs.metrics`): given the push batch's ``times`` and
    ``enable`` mask plus the count actually inserted (``push_many``'s
    ``n_inserted``, or a carried-depth delta on the sequential path),
    return ``(n_requested, n_inf_dropped, n_overflow)`` — attempts,
    deadline-saturated drops (the INF_TIME contract above), and inserts
    refused by a full queue. Lives here, next to the INF/overflow
    semantics it mirrors, so the drop taxonomy has exactly one home.
    Pure bookkeeping: never feeds the insert itself (the bitwise-
    invisibility contract of metrics-on runs).
    """
    en = jnp.asarray(enable, bool)
    # dtype-pinned sums: a bare jnp.sum would widen to i64 under the x64
    # flag, leaking a process setting into the metrics dtypes (TRC003).
    n_req = jnp.sum(en, dtype=jnp.int32)
    n_inf = jnp.sum(en & (jnp.asarray(times, jnp.int32) >= INF_TIME),
                    dtype=jnp.int32)
    return n_req, n_inf, n_req - n_inf - jnp.asarray(n_inserted, jnp.int32)


def pop_indexed(q: EventQueue, eligible=None
                ) -> Tuple[EventQueue, Event, jnp.ndarray, jnp.ndarray]:
    """:func:`pop` that also returns the popped ``slot`` index, so the
    caller can hand ``(slot, found)`` to :func:`push_many`'s ``clear``
    and fuse the clear into the insert's single time-lane write (the
    engine step does; the returned queue is then dead code and XLA drops
    its redundant clear write)."""
    times = q.time if eligible is None else jnp.where(eligible, q.time,
                                                      INF_TIME)
    n = q.time.shape[0]
    tmin = jnp.min(times)
    found = tmin < INF_TIME
    # First slot holding the min — argmin's first-occurrence tie-break,
    # but min-priced: argmin's tuple comparator costs ~8 flops/element,
    # while "max of (n-1-slot) over the min positions" is a where + max.
    slot = (n - 1) - jnp.max(jnp.where(times == tmin,
                                       (n - 1) - jnp.arange(n), -1))
    mask = onehot(slot, n)
    kind, flags, src, dst, gen = unpack_meta(take_small(q.meta, slot))
    ev = Event(
        time=tmin, kind=kind, flags=flags, src=src, dst=dst, gen=gen,
        # Wide in flight: the popped row is widened back to int32 here
        # (lanes.widen — one (P,)-sized convert per step), so handlers
        # and apply_fault never see a narrow payload.
        payload=widen(take_small(q.payload, slot)),
    )
    q = q._replace(time=jnp.where(mask & found, INF_TIME, q.time))
    return q, ev, found, slot


def pop(q: EventQueue, eligible=None) -> Tuple[EventQueue, Event, jnp.ndarray]:
    """Remove and return the earliest valid event. Returns (queue, ev, found).

    When the queue is empty, ``found`` is False and the event contents are
    arbitrary (time INF_TIME) — callers must mask on ``found``.

    ``eligible`` (optional (Q,) bool) masks slots out of *this* pop without
    disturbing them: ineligible events stay queued at their original time.
    This is how node pause buffers deliveries on the device — events to a
    paused node are skipped until resume clears the mask, then flush in
    (time, slot) order (`task.rs:243-261` park/unpark analog). With every
    slot ineligible, ``found`` is False even for a non-empty queue.

    Scatter-free: the min slot comes from an argmin (first-occurrence
    tie-break), the clear is an elementwise select, and the meta/payload
    read-back is a single-row gather at that slot
    (:func:`~madsim_tpu.engine.lanes.take_small` — one element per world,
    priced at zero by the cost model, vs 2 ops/element over the whole
    meta+payload footprint for the old one-hot masked reduction). When
    the queue is empty the gathered row is arbitrary — covered by the
    "mask on ``found``" contract above.
    """
    q, ev, found, _slot = pop_indexed(q, eligible)
    return q, ev, found


def next_deadline(q: EventQueue) -> jnp.ndarray:
    """Earliest pending time, or INF_TIME when empty."""
    return jnp.min(q.time)


def eligible_mask(q: EventQueue, paused, n_nodes: int) -> jnp.ndarray:
    """(Q,) pop-eligibility under node pause: events to a paused node are
    buffered (skipped in place); faults always fire — the matching resume
    must be able to reach the paused node. Lives here, next to
    pack_meta/unpack_meta, so the bit layout has exactly one home.

    Reads the two needed fields straight off the packed bits (one masked
    compare for the fault flag) instead of a full :func:`unpack_meta` —
    this runs over the whole (Q,) meta lane every step."""
    is_fault_q = (q.meta & jnp.int32(FLAG_FAULT << 6)) != 0
    dst_q = (q.meta >> 16) & 0xFF  # take_small clamps to [0, n_nodes)
    del n_nodes
    return is_fault_q | ~take_small(paused, dst_q)
