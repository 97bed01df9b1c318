"""The device decision kernel of the host↔device bridge.

One jitted XLA step advances W independent simulation worlds at once:
it integrates the timers and sends the host recorded while executing task
bodies, samples every message's loss/latency from the per-world NET
Threefry stream by *counter* (bit-identical to the host engine's own
draws, see `core/rng.py` stream map), selects each world's next event,
advances its virtual clock, and pops the due events in the exact
``(deadline, seq)`` order the host timer wheel would have used
(`core/timewheel.py:135-161`).

This is SURVEY §7 stage 4 as designed: the decision kernel — next-event
selection, clock, RNG, link sampling — is data-parallel over seeds and
lives on the device; arbitrary Python task bodies stay on the host
(`madsim_tpu/bridge/runtime.py` drives them in lockstep). Reference
behavior being batched: `madsim/src/sim/time/mod.rs:45-60`
(advance_to_next_event) and `net/network.rs:249-257` (test_link), for all
W seeds per step instead of one at a time.

State layout (arrays carry a leading W axis):
- ``clock``        i64[W]        virtual ns, host-advanced between steps
- ``lane_dl``      i64[W, CAP+1] timer deadlines (INF = empty; the last
                                 column is a scatter dump for masked ops)
- ``lane_seq``     i64[W, CAP+1] creation order, the heap tie-breaker

Network config travels *per send* (loss threshold, latency bounds): each
world carries its own ``Config``, so one compiled sweep explores a
(seeds × loss × latency) grid — a batched axis the reference cannot have
(its config is one global per run, `network.rs:74-94`) — and hot
``update_config`` calls take effect at exactly the same send the host
engine would apply them.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import numpy as np

from ..core.timewheel import TIMER_MAX_NS

# Empty-lane sentinel. Deadlines clamp at TIMER_MAX_NS = 2^62-1, and the
# clock can creep slightly past a clamped deadline (advance epsilon, poll
# jitter), so the sentinel must sit far above any *reachable clock*, not
# just above any deadline — otherwise empty lanes read as due and the
# drain loop never terminates. i64 max gives 2^61 ns of headroom.
INF_NS = (1 << 63) - 1
_EPSILON_NS = 50  # core/timewheel.py ADVANCE_EPSILON_NS


class BridgeState(NamedTuple):
    clock: object     # i64[W]
    lane_dl: object   # i64[W, CAP+1]
    lane_seq: object  # i64[W, CAP+1]


class BridgeMetrics(NamedTuple):
    """Per-slot observability counters (obs/metrics.py's block, shaped
    for the bridge: i64[W] lanes accumulated ON DEVICE inside the jitted
    step — the host never pays a per-round pull for them).

    Counters are per *slot*, cumulative across recycled seeds
    (``reset_slot`` leaves them running): the fleet-aggregate frame the
    profiled sweep reports (``sweep_profiled``'s ``sim_metrics``) is
    exact either way, and zeroing on recycle would force a device
    read-back per retirement. Write-only within the step — the
    bitwise-invisibility contract of the device engine's MetricsBlock
    holds here too (metrics-on trajectories are bit-identical,
    tests/test_obs.py).
    """

    timers_set: object    # i64[W] — lane adds shipped to the device
    cancels: object       # i64[W]
    msgs_sent: object     # i64[W] — send attempts (loss drawn on device)
    msgs_lost: object     # i64[W] — sends the loss draw dropped
    events_fired: object  # i64[W] — due events popped (step + drain)
    vtime_ns: object      # i64[W] — device-observed clock advance


class StepOut(NamedTuple):
    clock: object        # i64[W] — after advance
    deadlock: object     # bool[W] — advance requested but no timers pending
    send_ok: object      # bool[W, S] — send passed the loss draw
    event_slot: object   # i32[W, K] — popped lane slots (host frees them)
    event_seq: object    # i64[W, K] — popped seqs (host dispatch key)
    event_valid: object  # bool[W, K]
    more_due: object     # bool[W] — >K events were due; drain before polls


class HostBatch(NamedTuple):
    """One lockstep round of recorded host activity, padded to bucketed
    shapes (numpy; converted at the device boundary)."""

    t_slot: np.ndarray   # i32[W, T] new-timer lane slots
    t_dl: np.ndarray     # i64[W, T] absolute deadlines
    t_seq: np.ndarray    # i64[W, T]
    t_mask: np.ndarray   # bool[W, T]
    c_slot: np.ndarray   # i32[W, C] cancelled lane slots
    c_mask: np.ndarray   # bool[W, C]
    s_ctr: np.ndarray    # u64[W, S] NET-stream counter of the loss draw
    s_base: np.ndarray   # i64[W, S] elapsed_ns at the send
    s_slot: np.ndarray   # i32[W, S] delivery lane slot (live sends)
    s_seq: np.ndarray    # i64[W, S]
    s_thr: np.ndarray    # u64[W, S] loss threshold (per-send config)
    s_lossall: np.ndarray  # bool[W, S] loss rate >= 1.0
    s_lat_lo: np.ndarray   # i64[W, S] latency lower bound (ns)
    s_lat_w: np.ndarray    # i64[W, S] latency width (ns, >= 1)
    s_mask: np.ndarray   # bool[W, S]
    s_live: np.ndarray   # bool[W, S] has a destination socket (schedule it)
    clock: np.ndarray    # i64[W]
    advance: np.ndarray  # bool[W] advance to next event (False = drain only)


def _u64_block(k0, k1, ctr):
    """threefry block ``ctr`` (u64 counter) → u64; GlobalRng.next_u64
    parity ((x1 << 32) | x0 at counter split lo/hi)."""
    import jax.numpy as jnp

    from ..ops.threefry import threefry2x32_jax

    c0 = (ctr & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    c1 = (ctr >> jnp.uint64(32)).astype(jnp.uint32)
    x0, x1 = threefry2x32_jax(k0, k1, c0, c1)
    return x0.astype(jnp.uint64) | (x1.astype(jnp.uint64) << jnp.uint64(32))


def _step(state: BridgeState, mb, net_k0, net_k1,
          t_slot, t_dl, t_seq, t_mask,
          c_slot, c_mask,
          s_ctr, s_base, s_slot, s_seq, s_thr, s_lossall,
          s_lat_lo, s_lat_w, s_mask, s_live,
          clock_in, advance, *, cap: int, k_events: int,
          metrics: bool = False):
    import jax.numpy as jnp

    W = clock_in.shape[0]
    rows = jnp.arange(W)[:, None]
    dump = jnp.int32(cap)  # the scatter dump column

    lane_dl, lane_seq = state.lane_dl, state.lane_seq

    # 1. Cancels first: a slot cancelled and reused within one host batch
    #    must end up holding the new timer (runtime.py dedups the rest).
    c_slot = jnp.where(c_mask, c_slot, dump)
    lane_dl = lane_dl.at[rows, c_slot].set(jnp.int64(INF_NS))

    # 2. New timers.
    t_slot = jnp.where(t_mask, t_slot, dump)
    lane_dl = lane_dl.at[rows, t_slot].set(t_dl)
    lane_seq = lane_seq.at[rows, t_slot].set(t_seq)

    # 3. Sends: loss draw at ctr, latency draw at ctr+1 — the counters the
    #    host's own Network.test_link would have consumed (network.py:182).
    u_loss = _u64_block(net_k0[:, None], net_k1[:, None], s_ctr)
    u_lat = _u64_block(net_k0[:, None], net_k1[:, None],
                       s_ctr + jnp.uint64(1))
    lost = (u_loss < s_thr) | s_lossall
    ok = s_mask & ~lost
    latency = s_lat_lo + (u_lat % s_lat_w.astype(jnp.uint64)).astype(jnp.int64)
    deliver = ok & s_live
    s_slot = jnp.where(deliver, s_slot, dump)
    # Same horizon clamp as the host wheel's add_timer_at: a delivery
    # scheduled past TIMER_MAX_NS must land on the same clamped instant.
    send_dl = jnp.minimum(s_base + latency, jnp.int64(TIMER_MAX_NS))
    lane_dl = lane_dl.at[rows, s_slot].set(send_dl)
    lane_seq = lane_seq.at[rows, s_slot].set(s_seq)

    # 4. Advance each world's clock to its next event
    #    (time/mod.rs:45-60: target = max(earliest + ε, now)).
    live_dl = lane_dl[:, :cap]
    min_dl = live_dl.min(axis=1)
    has_timer = min_dl < INF_NS
    do_adv = advance & has_timer
    new_clock = jnp.where(do_adv,
                          jnp.maximum(clock_in, min_dl + _EPSILON_NS),
                          clock_in)
    deadlock = advance & ~has_timer

    # 5. Pop due entries (deadline <= clock) in (deadline, seq) order —
    #    exactly the host heap's pop order. k_events iterative argmin pops
    #    (two-level: min deadline, then min seq among ties) are ~17x
    #    cheaper than a full lexicographic sort of the lanes, and due
    #    clusters are small in practice (the drain path covers the rest).
    row = jnp.arange(W)
    ev_slot, ev_seq, ev_valid = [], [], []
    for _ in range(k_events):
        live = lane_dl[:, :cap]
        m = live.min(axis=1)
        is_due = m <= new_clock
        cand = jnp.where(live == m[:, None], lane_seq[:, :cap],
                         jnp.int64(INF_NS))
        j = jnp.argmin(cand, axis=1)
        ev_slot.append(j.astype(jnp.int32))
        ev_seq.append(lane_seq[row, j])
        ev_valid.append(is_due)
        lane_dl = lane_dl.at[row, jnp.where(is_due, j, cap)].set(
            jnp.int64(INF_NS))
    event_slot = jnp.stack(ev_slot, axis=1)
    event_seq = jnp.stack(ev_seq, axis=1)
    event_valid = jnp.stack(ev_valid, axis=1)
    more_due = lane_dl[:, :cap].min(axis=1) <= new_clock

    new_state = BridgeState(clock=new_clock, lane_dl=lane_dl,
                            lane_seq=lane_seq)
    if metrics:
        # Observability accumulation (BridgeMetrics): sums of masks the
        # step already computed — write-only, so the metrics-on step's
        # StepOut is bit-identical to metrics-off.
        i64 = jnp.int64
        mb = BridgeMetrics(
            timers_set=mb.timers_set + t_mask.sum(axis=1, dtype=i64),
            cancels=mb.cancels + c_mask.sum(axis=1, dtype=i64),
            msgs_sent=mb.msgs_sent + s_mask.sum(axis=1, dtype=i64),
            msgs_lost=mb.msgs_lost + (s_mask & lost).sum(axis=1, dtype=i64),
            events_fired=mb.events_fired
            + event_valid.sum(axis=1, dtype=i64),
            vtime_ns=mb.vtime_ns + (new_clock - state.clock),
        )
    return new_state, mb, StepOut(clock=new_clock, deadlock=deadlock,
                                  send_ok=ok, event_slot=event_slot,
                                  event_seq=event_seq,
                                  event_valid=event_valid,
                                  more_due=more_due)


class DrainOut(NamedTuple):
    """Outputs of a pop-only drain round, as DEVICE arrays (lazy): the
    driver materializes them with ``np.asarray`` at use, after the next
    drain is already in the queue."""

    event_seq: object    # i64[W, K] — popped seqs (host dispatch key)
    event_valid: object  # bool[W, K]
    more_due: object     # bool[W] — still >K events due


def _drain_step(state: BridgeState, mb, *, cap: int, k_events: int,
                metrics: bool = False):
    """Pop-only kernel for drain rounds: no cancels, no timers, no sends,
    no clock advance — exactly what a zero-width ``advance=False``
    :func:`_step` round did, minus the dead scatter machinery.

    Every input is device-resident (the kernel state), which is what lets
    the sweep driver dispatch drain round r+1 BEFORE round r's popped
    events are unpacked and fired on the host (dispatch-ahead): a drain
    dispatched when nothing is due pops nothing and leaves the lanes
    semantically untouched, so the one speculative round at the end of a
    drain chain is a no-op by construction.
    """
    import jax.numpy as jnp

    W = state.clock.shape[0]
    lane_dl, lane_seq = state.lane_dl, state.lane_seq
    clock = state.clock
    row = jnp.arange(W)
    ev_seq, ev_valid = [], []
    for _ in range(k_events):
        live = lane_dl[:, :cap]
        m = live.min(axis=1)
        is_due = m <= clock
        cand = jnp.where(live == m[:, None], lane_seq[:, :cap],
                         jnp.int64(INF_NS))
        j = jnp.argmin(cand, axis=1)
        ev_seq.append(lane_seq[row, j])
        ev_valid.append(is_due)
        lane_dl = lane_dl.at[row, jnp.where(is_due, j, cap)].set(
            jnp.int64(INF_NS))
    event_seq = jnp.stack(ev_seq, axis=1)
    event_valid = jnp.stack(ev_valid, axis=1)
    more_due = lane_dl[:, :cap].min(axis=1) <= clock
    new_state = BridgeState(clock=clock, lane_dl=lane_dl, lane_seq=lane_seq)
    if metrics:
        mb = mb._replace(events_fired=mb.events_fired
                         + event_valid.sum(axis=1, dtype=jnp.int64))
    return new_state, mb, DrainOut(event_seq=event_seq,
                                   event_valid=event_valid,
                                   more_due=more_due)


# One jitted step per (cap, k_events), shared by every kernel instance:
# a fresh jax.jit object per sweep would re-trace and re-compile (~0.8 s
# on CPU XLA for this unrolled kernel) on every sweep() call in a process.
# The step is pure (all state is passed in), so sharing is sound. The
# BridgeState argument is DONATED: XLA updates the W×(CAP+1) timer lanes
# in place instead of double-buffering them per step — sound because
# ``BridgeKernel.step`` immediately rebinds ``self.state`` to the output
# and nothing else holds the previous state (``reset_slot`` only ever
# touches the current one).
_STEP_CACHE: dict = {}
_DRAIN_CACHE: dict = {}


class BridgeKernel:
    """Device-side half of the bridge: owns the batched decision state.

    The host driver calls :meth:`step` once per lockstep round with padded
    numpy batches; pad widths are bucketed (powers of two) so XLA's
    per-shape retraces stay bounded.
    """

    def __init__(self, seeds, *, cap: int = 128, k_events: int = 4,
                 device: str = None, metrics: bool = False):
        import jax
        import jax.numpy as jnp

        from ..core.rng import STREAM_NET
        from ..ops.threefry import derive_stream_np

        self._jax = jax
        self._enable_x64 = jax.enable_x64
        self.W = len(seeds)
        self.cap = cap
        self.k_events = k_events
        self.metrics_enabled = bool(metrics)
        # JAX's default device unless ``device`` names a backend
        # ("cpu", "tpu"): the lockstep protocol pays one dispatch per
        # event cluster, so a caller may pin the kernel to the host.
        self.device = (jax.local_devices(backend=device)[0] if device
                       else jax.local_devices()[0])
        seeds = np.asarray(seeds, dtype=np.uint64)
        k0 = (seeds & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        k1 = (seeds >> np.uint64(32)).astype(np.uint32)
        nk0, nk1 = derive_stream_np(k0, k1, STREAM_NET)
        with jax.default_device(self.device), self._enable_x64():
            self._net_k0 = jnp.asarray(np.atleast_1d(nk0))
            self._net_k1 = jnp.asarray(np.atleast_1d(nk1))
            self.state = BridgeState(
                clock=jnp.zeros((self.W,), jnp.int64),
                lane_dl=jnp.full((self.W, cap + 1), INF_NS, jnp.int64),
                lane_seq=jnp.zeros((self.W, cap + 1), jnp.int64),
            )
            # The per-slot observability block (device-resident; donated
            # through the step alongside the lane state).
            self._mb = (BridgeMetrics(*[jnp.zeros((self.W,), jnp.int64)
                                        for _ in BridgeMetrics._fields])
                        if self.metrics_enabled else None)
            # One jitted step; XLA re-traces per padded batch shape.
            # Process-cached so repeated sweeps reuse the compilation.
            # Metrics-on compiles its own entry (the block is an extra
            # donated argument); metrics-off is the unchanged program.
            donate = (0, 1) if self.metrics_enabled else (0,)
            key = (cap, k_events, self.metrics_enabled)
            self._fn = _STEP_CACHE.get(key)
            if self._fn is None:
                self._fn = jax.jit(
                    functools.partial(_step, cap=cap, k_events=k_events,
                                      metrics=self.metrics_enabled),
                    donate_argnums=donate)
                _STEP_CACHE[key] = self._fn
            self._drain_fn = _DRAIN_CACHE.get(key)
            if self._drain_fn is None:
                self._drain_fn = jax.jit(
                    functools.partial(_drain_step, cap=cap,
                                      k_events=k_events,
                                      metrics=self.metrics_enabled),
                    donate_argnums=donate)
                _DRAIN_CACHE[key] = self._drain_fn

    def reset_slot(self, slot: int, seed: int) -> None:
        """Recycle one world slot for a fresh seed: re-derive its NET
        stream key and clear its device rows (clock zero, all timer lanes
        empty). After the reset the slot is indistinguishable from row
        ``slot`` of a freshly built kernel keyed on ``seed``, so a world
        spawned into it keeps the bit-identical per-seed contract — this
        is what lets bounded-width sweeps (``sweep(batch=...)``) stream
        seeds through a fixed batch instead of sizing W to the seed list.
        """
        from ..core.rng import STREAM_NET
        from ..ops.threefry import derive_stream_np, seed_to_key

        import jax.numpy as jnp

        nk0, nk1 = derive_stream_np(*seed_to_key(int(seed)), STREAM_NET)
        with self._jax.default_device(self.device), self._enable_x64():
            self._net_k0 = self._net_k0.at[slot].set(jnp.uint32(nk0))
            self._net_k1 = self._net_k1.at[slot].set(jnp.uint32(nk1))
            st = self.state
            self.state = BridgeState(
                clock=st.clock.at[slot].set(0),
                lane_dl=st.lane_dl.at[slot].set(jnp.int64(INF_NS)),
                lane_seq=st.lane_seq.at[slot].set(0),
            )

    def reset_slots(self, pairs) -> None:
        """Batched :meth:`reset_slot`: re-key ALL of a round's recycled
        slots in one device write per lane array instead of one dispatch
        chain per slot — the pool parent's refill path
        (`bridge/pool.py`), where a wide recycled sweep can retire many
        slots per round. Bit-identical to sequential ``reset_slot``
        calls: the slots are distinct, and each row gets exactly the
        values a fresh kernel keyed on its seed would hold."""
        if not pairs:
            return
        if len(pairs) == 1:
            self.reset_slot(*pairs[0])
            return
        from ..core.rng import STREAM_NET
        from ..ops.threefry import derive_stream_np

        import jax.numpy as jnp

        slots = np.asarray([int(s) for s, _ in pairs], np.int32)
        seeds = np.asarray([int(x) for _, x in pairs], np.uint64)
        k0 = (seeds & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        k1 = (seeds >> np.uint64(32)).astype(np.uint32)
        nk0, nk1 = derive_stream_np(k0, k1, STREAM_NET)
        with self._jax.default_device(self.device), self._enable_x64():
            self._net_k0 = self._net_k0.at[slots].set(jnp.asarray(nk0))
            self._net_k1 = self._net_k1.at[slots].set(jnp.asarray(nk1))
            st = self.state
            self.state = BridgeState(
                clock=st.clock.at[slots].set(0),
                lane_dl=st.lane_dl.at[slots].set(jnp.int64(INF_NS)),
                lane_seq=st.lane_seq.at[slots].set(0),
            )

    def drain(self) -> DrainOut:
        """Dispatch one pop-only drain round and return LAZY device
        outputs (materialize with ``np.asarray`` at use). The round's
        only input is the device-resident kernel state, so the driver can
        enqueue drain r+1 before unpacking round r's events — and a
        speculatively dispatched round that finds nothing due is a
        semantic no-op on the lanes."""
        with self._jax.default_device(self.device), self._enable_x64():
            state, mb, out = self._drain_fn(self.state, self._mb)
            self.state = state
            self._mb = mb
            return out

    def step(self, batch: HostBatch, out: Optional[StepOut] = None
             ) -> StepOut:
        """One lockstep round. ``batch`` arrays may be backed by ANY
        buffer — the pool parent hands shared-memory views straight in
        (the H2D copy reads them in place). ``out``, when given, is a
        StepOut of caller-owned destination arrays (``None`` fields
        skipped): the results are scattered into them after
        materialization — the shared-memory egress seam of
        `bridge/pool.py`, whose workers read their slice rows without
        any per-world parent work."""
        import jax.numpy as jnp

        with self._jax.default_device(self.device), self._enable_x64():
            state, mb, res = self._fn(
                self.state, self._mb, self._net_k0, self._net_k1,
                jnp.asarray(batch.t_slot), jnp.asarray(batch.t_dl),
                jnp.asarray(batch.t_seq), jnp.asarray(batch.t_mask),
                jnp.asarray(batch.c_slot), jnp.asarray(batch.c_mask),
                jnp.asarray(batch.s_ctr), jnp.asarray(batch.s_base),
                jnp.asarray(batch.s_slot), jnp.asarray(batch.s_seq),
                jnp.asarray(batch.s_thr), jnp.asarray(batch.s_lossall),
                jnp.asarray(batch.s_lat_lo), jnp.asarray(batch.s_lat_w),
                jnp.asarray(batch.s_mask), jnp.asarray(batch.s_live),
                jnp.asarray(batch.clock), jnp.asarray(batch.advance))
            self.state = state
            self._mb = mb
            res = StepOut(*[np.asarray(x) for x in res])
            if out is not None:
                for dst, src in zip(out, res):
                    if dst is not None:
                        np.copyto(dst, src)
            return res

    def metrics(self):
        """Host copy of the per-slot :class:`BridgeMetrics` block (dict of
        i64[W] numpy arrays), or ``None`` when metrics are off. One
        explicit pull — call at sweep end, not per round."""
        if self._mb is None:
            return None
        vals = self._jax.device_get(self._mb)
        return {k: np.asarray(v) for k, v in vals._asdict().items()}


def bucket(n: int, minimum: int = 4) -> int:
    """Round a per-step count up to a power of two so jit shapes repeat."""
    b = minimum
    while b < n:
        b <<= 1
    return b
