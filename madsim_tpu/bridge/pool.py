# tracelint: hot-loop
"""Forked worker pool: parallel Python task bodies behind the device kernel.

docs/bridge.md pins the bridge's Amdahl ceiling: the device decision
kernel is ~5-15% of a lockstep round, the rest is the single serial
CPython interpreter running task bodies plus the per-world pack loop
(224 ms of a 295 ms round at W=4096). Per-slot state is independent by
construction and the kernel already batches W slots, so the serial
fraction is embarrassingly parallel — this module cracks it:

- ``sweep_pooled(world_fn, seeds, jobs=J)`` shards the W kernel slots
  across J forked workers. Each worker owns a CONTIGUOUS slot slice —
  its ``Runtime`` object graphs live only in that worker (the W=4096
  cache collapse fix) — and drives it with the same
  :class:`~madsim_tpu.bridge.runtime.SliceDriver` seam the serial loop
  uses, so bit-identity is structural, not re-implemented.
- Workers are forked, not spawned: the parent has already imported this
  package (and holds the ``world_fn`` closure), so per-worker warmup is
  ONE fork, not an interpreter boot — and ``world_fn``/``configs`` need
  no pickling. Workers never touch jax; the device kernel lives only in
  the parent (forking a jax-live parent is safe exactly because the
  children never re-enter the inherited XLA state).
- Each worker packs its slice DIRECTLY into a shared-memory (W, ...)
  batch region (one ``multiprocessing.shared_memory`` segment per
  (T, C, S) bucket, masks-only clears preserved), so the parent does
  zero per-world Python work: it barriers the round, hands the shared
  batch to the jitted kernel step, scatters the StepOut into a shared
  output region, and the workers settle their own rows. Drain rounds
  keep PR 4's dispatch-ahead overlap: drain r+1 is in the device queue
  while the workers fire drain r's events.

Determinism is the contract and the test: per-seed traces, send
accounting, and mixed-outcome attribution are bit-identical to
``jobs=1`` and to the serial bridge for every J and every W%J remainder
(tests/test_bridge_pool.py, tools/bridge_pool_demo.py), exactly as
``bridge.sweep(batch=N)`` gates batching. Worker death mid-round raises
a pointed :class:`BridgePoolError` naming the worker, its slot range,
and the round — no hangs, no partial batches, and every shared-memory
segment is unlinked on the way out.

Sync discipline (DET008/DET009): the parent round loop's only blocking
device->host reads are the kernel step/drain materializations, routed
through the sanctioned :func:`_fetch` seam below so the static pass and
the counted-fetch tests see one auditable site.
"""
from __future__ import annotations

import os
import pickle
import warnings
from collections import OrderedDict
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

from .kernel import BridgeKernel, HostBatch, StepOut, bucket
from .runtime import Outcome, SliceDriver


def _fetch(x) -> np.ndarray:
    """THE sanctioned blocking device->host seam of the pool round loop
    (the `_fetch` discipline of docs/perf.md "Pipelined orchestration"):
    drain outputs are dispatched ahead and materialized here, after the
    next drain is already in the device queue. Tests monkeypatch this to
    count syncs."""
    return np.asarray(x)


class BridgePoolError(RuntimeError):
    """A pool worker died (or errored) mid-sweep.

    Carries ``worker`` (index), ``slots`` (the worker's (lo, hi) global
    slot range, half-open), and ``round_no`` so the failure names exactly
    which slice of which lockstep round was lost. The parent kills the
    remaining workers and unlinks every shared-memory segment before
    raising — no hangs, no partial batches, no orphaned segments.
    """

    def __init__(self, message: str, *, worker: Optional[int] = None,
                 slots: Optional[Tuple[int, int]] = None,
                 round_no: Optional[int] = None):
        super().__init__(message)
        self.worker = worker
        self.slots = slots
        self.round_no = round_no


# ---------------------------------------------------------------------------
# Shared-memory layout
# ---------------------------------------------------------------------------

# One segment per (T, C, S) bucket holds the whole 18-array HostBatch,
# field order matching bridge/kernel.py HostBatch. Axis letters map to
# the padded widths: t/c/s -> T/C/S columns, w -> the flat [W] lanes.
_BATCH_SPECS = (
    ("t_slot", "t", np.int32), ("t_dl", "t", np.int64),
    ("t_seq", "t", np.int64), ("t_mask", "t", np.bool_),
    ("c_slot", "c", np.int32), ("c_mask", "c", np.bool_),
    ("s_ctr", "s", np.uint64), ("s_base", "s", np.int64),
    ("s_slot", "s", np.int32), ("s_seq", "s", np.int64),
    ("s_thr", "s", np.uint64), ("s_lossall", "s", np.bool_),
    ("s_lat_lo", "s", np.int64), ("s_lat_w", "s", np.int64),
    ("s_mask", "s", np.bool_), ("s_live", "s", np.bool_),
    ("clock", "w", np.int64), ("advance", "w", np.bool_),
)


class PoolOut(NamedTuple):
    """The shared step/drain output region (one segment per S bucket).

    ``drain_fire`` is the drain-round fire mask: the PREVIOUS round's
    more_due — which worlds this drain was dispatched for — written by
    the parent before each drain broadcast (StepOut's own ``more_due``
    is the post-pop flag the settle phase reads for woke detection)."""

    clock: np.ndarray        # i64[W]
    deadlock: np.ndarray     # bool[W]
    send_ok: np.ndarray      # bool[W, S]
    event_seq: np.ndarray    # i64[W, K]
    event_valid: np.ndarray  # bool[W, K]
    more_due: np.ndarray     # bool[W]
    drain_fire: np.ndarray   # bool[W]


def _carve(buf, specs) -> Tuple[list, int]:
    """Carve 8-byte-aligned numpy views out of one flat buffer."""
    views, off = [], 0
    for shape, dt in specs:
        off = (off + 7) & ~7
        a = np.ndarray(shape, dt, buffer=buf, offset=off)
        views.append(a)
        off += a.nbytes
    return views, off


def _batch_shapes(W: int, T: int, C: int, S: int) -> list:
    dims = {"t": T, "c": C, "s": S}
    return [((W,) if ax == "w" else (W, dims[ax]), dt)
            for _name, ax, dt in _BATCH_SPECS]


def _out_shapes(W: int, S: int, K: int) -> list:
    return [((W,), np.int64), ((W,), np.bool_), ((W, S), np.bool_),
            ((W, K), np.int64), ((W, K), np.bool_), ((W,), np.bool_),
            ((W,), np.bool_)]


def _nbytes(specs) -> int:
    off = 0
    for shape, dt in specs:
        off = (off + 7) & ~7
        off += int(np.prod(shape)) * np.dtype(dt).itemsize
    return max(off, 1)


def _attach(name: str):
    """Worker-side attach to a parent-owned segment.

    CPython 3.10's ``SharedMemory(name=...)`` registers even pure
    attachments with the resource tracker as if they were owned. That is
    benign here BECAUSE the workers are forked: they share the parent's
    tracker process, whose per-name cache is a set — the worker's
    register dedupes against the parent's, and the parent's unlink
    unregisters once for everyone. (Unregistering here instead would
    strip the parent's entry and make its own unlink warn.)"""
    from multiprocessing import shared_memory

    return shared_memory.SharedMemory(name=name)


_SHM_PREFIX = "msbp"
_POOL_SEQ = [0]  # per-process pool counter (unique segment names)


class _SegmentStore:
    """Parent-owned named shared-memory segments: batch regions per
    (T, C, S) bucket and output regions per S bucket, LRU-bounded like
    the serial pack-buffer cache — evicted segments are closed and
    unlinked immediately (workers' live attachments keep the mapping
    valid; names are never reused)."""

    def __init__(self, W: int, k_events: int, maxsize: int = 8):
        self.W = W
        self.K = k_events
        self.maxsize = maxsize
        _POOL_SEQ[0] += 1
        self._uid = f"{_SHM_PREFIX}-{os.getpid()}-{_POOL_SEQ[0]}"
        self._seq = 0
        self._batch: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._out: "OrderedDict[int, tuple]" = OrderedDict()

    def _create(self, specs):
        from multiprocessing import shared_memory

        self._seq += 1
        name = f"{self._uid}-{self._seq}"
        shm = shared_memory.SharedMemory(create=True, size=_nbytes(specs),
                                         name=name)
        views, _ = _carve(shm.buf, specs)
        return name, shm, views

    @staticmethod
    def _evict(cache, maxsize):
        while len(cache) > maxsize:
            _key, (_name, shm, _views) = cache.popitem(last=False)
            shm.close()
            shm.unlink()

    def batch(self, T: int, C: int, S: int) -> Tuple[str, list]:
        key = (T, C, S)
        ent = self._batch.get(key)
        if ent is None:
            ent = self._create(_batch_shapes(self.W, T, C, S))
            self._batch[key] = ent
            self._evict(self._batch, self.maxsize)
        else:
            self._batch.move_to_end(key)
        return ent[0], ent[2]

    def out(self, S: int) -> Tuple[str, PoolOut]:
        ent = self._out.get(S)
        if ent is None:
            name, shm, views = self._create(_out_shapes(self.W, S, self.K))
            ent = (name, shm, PoolOut(*views))
            self._out[S] = ent
            self._evict(self._out, self.maxsize)
        else:
            self._out.move_to_end(S)
        return ent[0], ent[2]

    def close(self) -> None:
        """Unlink everything (idempotent) — the no-orphaned-segments
        contract of BridgePoolError holds through this."""
        for cache in (self._batch, self._out):
            for _name, shm, _views in cache.values():
                try:
                    shm.close()
                    shm.unlink()
                except FileNotFoundError:  # pragma: no cover — already gone
                    pass
            cache.clear()


# ---------------------------------------------------------------------------
# Worker side
# ---------------------------------------------------------------------------


class _WorkerSegs:
    """Worker-side attachment cache (name -> (shm, views)), LRU-bounded;
    names are parent-unique so a cached view can never alias a stale
    segment."""

    def __init__(self, maxsize: int = 16):
        self.maxsize = maxsize
        self._segs: "OrderedDict[str, tuple]" = OrderedDict()

    def get(self, name: str, make_views):
        ent = self._segs.get(name)
        if ent is None:
            shm = _attach(name)
            ent = (shm, make_views(shm.buf))
            self._segs[name] = ent
            while len(self._segs) > self.maxsize:
                _n, (old, _v) = self._segs.popitem(last=False)
                old.close()
        else:
            self._segs.move_to_end(name)
        return ent[1]


def _picklable(outs: List[Outcome]) -> List[Outcome]:
    """Outcomes cross the pipe pickled; errors that cannot pickle are
    re-wrapped as RuntimeError with the original repr (same contract as
    the pre-pool forked shards)."""
    safe = []
    for o in outs:
        try:
            pickle.dumps(o)
            safe.append(o)
        except Exception:
            safe.append(Outcome(o.seed, None,
                                RuntimeError(f"unpicklable outcome: {o!r}")))
    return safe


def _worker_main(conn, idx: int, slot_lo: int, n_slots: int, seeds,
                 world_fn, k_events: int, kw: dict) -> None:
    """One forked worker: drive slots [slot_lo, slot_lo+n_slots) with a
    SliceDriver, barriered by the parent's round messages. Never touches
    jax — the decision kernel lives only in the parent."""
    try:
        drv = SliceDriver(world_fn, seeds, slot_lo=slot_lo, n_slots=n_slots,
                          **kw)
        segs = _WorkerSegs()
        W = None  # learned from the first pack (global batch width)

        def ready():
            resets = drv.top_up()
            t_n, c_n, s_n = drv.take_rounds()
            conn.send(("ready", (t_n, c_n, s_n), resets, drv.live_slots(),
                       drv.left))

        ready()
        while True:
            msg = conn.recv()
            tag = msg[0]
            if tag == "pack":
                _tag, W, T, C, S, name = msg
                views = segs.get(
                    name, lambda b: _carve(b, _batch_shapes(W, T, C, S))[0])
                drv.pack_into(views)
                conn.send(("packed",))
            elif tag == "settle":
                _tag, S, name = msg
                out = segs.get(
                    name,
                    lambda b: PoolOut(*_carve(
                        b, _out_shapes(W, S, k_events))[0]))
                drv.settle(out)
                conn.send(("settled", drv.live_slots()))
            elif tag == "drain":
                _tag, S, name = msg
                out = segs.get(
                    name,
                    lambda b: PoolOut(*_carve(
                        b, _out_shapes(W, S, k_events))[0]))
                drv.drain_assert(out.drain_fire)
                drv.fire_drain(out.event_valid, out.event_seq,
                               out.drain_fire)
                conn.send(("drained",))
            elif tag == "settle_host":
                # Merged fast path: the parent proved no drain round can
                # fire (no live world had >K events due), so settle,
                # woke host bursts, and admission collapse into ONE
                # barrier — the common round costs two round trips, not
                # three.
                _tag, S, name = msg
                out = segs.get(
                    name,
                    lambda b: PoolOut(*_carve(
                        b, _out_shapes(W, S, k_events))[0]))
                drv.settle(out)
                drv.run_woke()
                ready()
            elif tag == "host":
                drv.run_woke()
                ready()
            elif tag == "finish":
                conn.send(("outcomes", _picklable(drv.outcomes),
                           drv.traces))
                conn.close()
                return
            else:  # pragma: no cover — parent protocol bug
                raise RuntimeError(f"unknown pool message {tag!r}")
    except (EOFError, OSError, BrokenPipeError):  # parent gone
        os._exit(1)
    except BaseException as exc:  # noqa: BLE001 — report, then die loudly
        import traceback

        try:
            conn.send(("error", f"{type(exc).__name__}: {exc}",
                       traceback.format_exc()))
        except Exception:
            pass
        os._exit(1)


# ---------------------------------------------------------------------------
# Parent side
# ---------------------------------------------------------------------------


class _Worker(NamedTuple):
    idx: int
    proc: object          # multiprocessing.Process (fork context)
    conn: object          # parent end of the duplex pipe
    slot_lo: int
    n_slots: int
    pos_lo: int
    pos_hi: int


def _shard_plan(n: int, W: int, J: int) -> List[Tuple[int, int, int, int]]:
    """(slot_lo, n_slots, pos_lo, pos_hi) per worker: contiguous slot
    slices (first W%J workers take the extra slot) and proportional
    contiguous seed shards. ``pos = (n * slot_off) // W`` keeps every
    shard's seed count >= its slot count (n >= W), so every slot spawns
    a world on the initial fill, exactly like the serial loop."""
    base, extra = divmod(W, J)
    plan, off = [], 0
    for j in range(J):
        w_j = base + (1 if j < extra else 0)
        plan.append((off, w_j, (n * off) // W, (n * (off + w_j)) // W))
        off += w_j
    return plan


def _fork_worker(ctx, idx, slot_lo, n_slots, seeds, world_fn, k_events, kw):
    parent_conn, child_conn = ctx.Pipe()
    with warnings.catch_warnings():
        # jax warns on ANY os.fork() in a process with live XLA threads;
        # the hazard is a child re-entering inherited XLA state, which
        # pool workers never do (they run pure-Python task bodies).
        warnings.filterwarnings("ignore", message=".*os\\.fork\\(\\).*",
                                category=RuntimeWarning)
        p = ctx.Process(target=_worker_main,
                        args=(child_conn, idx, slot_lo, n_slots, seeds,
                              world_fn, k_events, kw),
                        daemon=True)
        p.start()
    child_conn.close()
    return parent_conn, p


def sweep_pooled(world_fn, seeds, *, jobs: int, config=None, configs=None,
                 cap: int = 128, k_events: int = 4, time_limit=None,
                 trace: bool = False, device: Optional[str] = None,
                 batch: Optional[int] = None,
                 stats: Optional[dict] = None
                 ) -> Tuple[List[Outcome], List[list]]:
    """One lockstep sweep, task bodies sharded across ``jobs`` forked
    workers behind ONE shared device decision kernel.

    Returns ``(outcomes, traces)`` exactly like the serial
    ``_sweep_impl`` — and bit-identically to it, per seed, for every
    ``jobs``/``batch`` split. ``stats`` (optional dict) receives the
    parent-observed per-phase wall windows
    (``host_s``/``pack_s``/``dispatch_s``/``settle_s``/``parent_s``/
    ``rounds``/``drain_rounds``/``resets``).
    """
    import multiprocessing as mp

    seeds = [int(s) for s in seeds]
    n = len(seeds)
    if n == 0:
        return [], []
    W = n if batch is None else max(1, min(int(batch), n))
    J = max(1, min(int(jobs), W))
    plan = _shard_plan(n, W, J)
    kw = dict(cap=cap, time_limit=time_limit, trace=trace, config=config)

    if stats is not None:
        from time import perf_counter

        stats.update(rounds=0, drain_rounds=0, resets=0, host_s=0.0,
                     pack_s=0.0, dispatch_s=0.0, settle_s=0.0,
                     parent_s=0.0, workers=J, w=W)

        def _clk():
            # Wall-clock phase windows of the pool driver (stats only).
            return perf_counter()  # detlint: allow[DET001]
    else:
        def _clk():
            return 0.0

    # Fork FIRST (fork-server discipline: modules + world_fn are already
    # in this image, so each worker costs one fork), then build the
    # kernel — the children never re-enter the parent's jax state. The
    # resource tracker must be live BEFORE the fork: children then share
    # it, their attach-registrations dedupe against the parent's (set
    # semantics), and the parent's unlink unregisters once for everyone —
    # a child-spawned tracker would instead warn about "leaked" segments
    # it never owned.
    from multiprocessing import resource_tracker

    resource_tracker.ensure_running()
    ctx = mp.get_context("fork")
    workers: List[_Worker] = []
    for idx, (slot_lo, n_slots, pos_lo, pos_hi) in enumerate(plan):
        wkw = dict(kw)
        wkw["configs"] = (configs[pos_lo:pos_hi]
                          if configs is not None else None)
        conn, p = _fork_worker(ctx, idx, slot_lo, n_slots,
                               seeds[pos_lo:pos_hi], world_fn, k_events,
                               wkw)
        workers.append(_Worker(idx, p, conn, slot_lo, n_slots,
                               pos_lo, pos_hi))

    # Kernel slot keys = each worker's initial fill, in slot order (the
    # SliceDriver free list admits its first n_slots seeds into local
    # slots 0..n_slots-1).
    kernel_seeds = []
    for w in workers:
        kernel_seeds.extend(seeds[w.pos_lo:w.pos_lo + w.n_slots])
    kernel = BridgeKernel(kernel_seeds, cap=cap, k_events=k_events,
                          device=device)
    segs = _SegmentStore(W, k_events)
    live = np.zeros(W, np.bool_)
    round_no = 0

    def fail(w: _Worker, phase: str, remote: Optional[tuple] = None):
        if remote is not None:
            raise BridgePoolError(
                f"bridge pool worker {w.idx} (slots {w.slot_lo}.."
                f"{w.slot_lo + w.n_slots - 1}) failed during round "
                f"{round_no} ({phase}): {remote[0]}\n{remote[1]}",
                worker=w.idx, slots=(w.slot_lo, w.slot_lo + w.n_slots),
                round_no=round_no)
        w.proc.join(timeout=1.0)  # reap, so the exitcode names the signal
        raise BridgePoolError(
            f"bridge pool worker {w.idx} (slots {w.slot_lo}.."
            f"{w.slot_lo + w.n_slots - 1}) died during round {round_no} "
            f"({phase} phase, exitcode {w.proc.exitcode})",
            worker=w.idx, slots=(w.slot_lo, w.slot_lo + w.n_slots),
            round_no=round_no)

    def gather(expect: str, phase: str) -> dict:
        """Collect one ``expect`` message per worker; a worker dying (or
        reporting an error) raises the pointed BridgePoolError instead of
        hanging the barrier."""
        from multiprocessing.connection import wait as conn_wait

        got: dict = {}
        remaining = {w.conn: w for w in workers}
        while remaining:
            ready = conn_wait(list(remaining), timeout=0.25)
            if not ready:
                for conn, w in list(remaining.items()):
                    if not w.proc.is_alive():
                        fail(w, phase)
                continue
            for conn in ready:
                w = remaining[conn]
                try:
                    msg = conn.recv()
                except (EOFError, OSError):
                    fail(w, phase)
                if msg[0] == "error":
                    fail(w, phase, remote=(msg[1], msg[2]))
                got[w.idx] = msg[1:]
                del remaining[conn]
        return got

    def broadcast(msg) -> None:
        for w in workers:
            try:
                w.conn.send(msg)
            except (OSError, BrokenPipeError):
                fail(w, msg[0])

    def apply_live(w: _Worker, live_rows: List[int]) -> None:
        live[w.slot_lo:w.slot_lo + w.n_slots] = False
        if live_rows:
            live[live_rows] = True

    try:
        t0 = _clk()
        ready = gather("ready", "host")
        if stats is not None:
            stats["host_s"] += _clk() - t0
        while True:
            t0 = _clk()
            t_n = c_n = s_n = left = 0
            resets: List[Tuple[int, int]] = []
            for w in workers:
                counts, rs, live_rows, w_left = ready[w.idx]
                t_n, c_n, s_n = (max(t_n, counts[0]), max(c_n, counts[1]),
                                 max(s_n, counts[2]))
                resets.extend(rs)
                apply_live(w, live_rows)
                left += w_left
            if not live.any() and left == 0:
                break
            # Re-key recycled slots before the step that ships the fresh
            # worlds' first recorded activity — the same dispatch point
            # the serial loop resets at, one batched device write.
            kernel.reset_slots(resets)
            T, C, S = bucket(t_n), bucket(c_n), bucket(s_n)
            name, views = segs.batch(T, C, S)
            oname, out_views = segs.out(S)
            if stats is not None:
                stats["parent_s"] += _clk() - t0
                stats["rounds"] += 1
                stats["resets"] += len(resets)
            t0 = _clk()
            broadcast(("pack", W, T, C, S, name))
            gather("packed", "pack")
            if stats is not None:
                stats["pack_s"] += _clk() - t0
            t0 = _clk()
            # The whole (W, ...) round batch goes to the device straight
            # from shared memory; the StepOut scatters straight back
            # (kernel.step(out=...) — the shared-memory egress seam).
            out = kernel.step(
                HostBatch(*views),
                out=StepOut(clock=out_views.clock,
                            deadlock=out_views.deadlock,
                            send_ok=out_views.send_ok, event_slot=None,
                            event_seq=out_views.event_seq,
                            event_valid=out_views.event_valid,
                            more_due=out_views.more_due))
            if stats is not None:
                stats["dispatch_s"] += _clk() - t0
            more = out.more_due
            if not bool((live & more).any()):
                # No drain round can fire (live only shrinks during a
                # settle, so the pre-settle mask is a safe upper bound):
                # settle + woke host bursts + admission collapse into one
                # barrier.
                t0 = _clk()
                broadcast(("settle_host", S, oname))
                round_no += 1
                ready = gather("ready", "settle_host")
                if stats is not None:
                    stats["host_s"] += _clk() - t0
                continue
            t0 = _clk()
            broadcast(("settle", S, oname))
            settled = gather("settled", "settle")
            for w in workers:
                apply_live(w, settled[w.idx][0])
            # Drain chain: pop-only kernel, dispatch-ahead — drain r+1
            # enters the device queue before the workers fire round r's
            # events; the speculative tail round pops nothing.
            more = more.copy()
            inflight = kernel.drain() if bool((live & more).any()) else None
            while inflight is not None:
                if stats is not None:
                    stats["drain_rounds"] += 1
                cur = inflight
                inflight = kernel.drain()
                out_views.drain_fire[:] = more
                out_views.event_seq[:] = _fetch(cur.event_seq)
                out_views.event_valid[:] = _fetch(cur.event_valid)
                more = _fetch(cur.more_due)
                broadcast(("drain", S, oname))
                gather("drained", "drain")
                if not bool((live & more).any()):
                    break  # the in-flight round is the no-op tail
            if stats is not None:
                stats["settle_s"] += _clk() - t0
            t0 = _clk()
            broadcast(("host",))
            round_no += 1
            ready = gather("ready", "host")
            if stats is not None:
                stats["host_s"] += _clk() - t0

        broadcast(("finish",))
        finals = gather("outcomes", "finish")
        outcomes: List[Optional[Outcome]] = [None] * n
        traces: List[list] = [[] for _ in range(n)]
        for w in workers:
            outs, trs = finals[w.idx]
            outcomes[w.pos_lo:w.pos_hi] = outs
            traces[w.pos_lo:w.pos_hi] = trs
        for w in workers:
            w.proc.join(timeout=10.0)
        return outcomes, traces
    finally:
        for w in workers:
            if w.proc.is_alive():
                w.proc.terminate()
        for w in workers:
            w.proc.join(timeout=5.0)
            w.conn.close()
        segs.close()
