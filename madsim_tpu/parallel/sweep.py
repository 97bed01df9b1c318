"""Sharded multi-seed sweeps: the TPU replacement for MADSIM_TEST_JOBS.

``sweep`` is the device-engine counterpart of the host test driver's seed
loop (`madsim/src/sim/runtime/builder.rs:110-148` / madsim_tpu.testing):
initialize one world per seed, shard the world axis over the mesh, advance
all worlds in fixed-step chunks, and after each chunk reduce two tiny scalars
over ICI — "any bug found?" and "how many worlds still active?" — so the host
loop makes progress/early-exit decisions without ever pulling per-world state
off device. Failing seeds (the repro banner of `runtime/mod.rs:192-199`)
are gathered once, at the end.

The loop is a slot-occupancy model (docs/perf.md "World recycling"): the
batch is a fixed set of world slots, compaction is an on-device stable
partition (no host pull of per-world state), and with ``recycle=True``
retired slots are refilled with fresh seeds from a host-side cursor so
the mesh stays full for open-ended hunts. Per-chunk occupancy telemetry
(``n_active_history`` / ``world_utilization``) rides every result.

Orchestration is *pipelined and superstepped* by default (docs/perf.md
"Pipelined orchestration"): up to ``superstep_max`` chunks fold into one
jitted ``lax.while_loop`` dispatch whose early-exit decisions (all
retired / occupancy at the recycle threshold / bug under
``stop_on_first_bug``) run ON DEVICE, and the host issues superstep k+1
before reading superstep k's scalars, so the device queue stays non-empty
while the host decides. A superstep dispatched past a stop/recycle point
is a bitwise pass-through (its entry condition is already false), which is
what makes one-dispatch-stale decisions exact rather than approximate:
results are bit-identical to the serial per-chunk loop (``pipeline=False``,
kept as the equivalence reference and tier-1-tested against).
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from jax import shard_map

from ..engine.core import DeviceEngine, EngineConfig, WorldState
from ..obs import observatory as _obsy
from .mesh import (
    scalar_spec,
    seed_mesh,
    shard_worlds,
    world_sharding,
    world_spec,
)

# Every device→host pull the sweep loop makes goes through this hook, so
# the tier-1 sync-discipline test (tests/test_sweep_pipeline.py) can count
# host-boundary crossings per superstep by monkeypatching it, and the
# static twin (detlint DET008/DET009, docs/detlint.md) can treat any other
# blocking read in this module as a finding. Semantics: jax.device_get of
# an arbitrary pytree.
_fetch = jax.device_get  # detlint: allow[DET008] reason=the ONE sanctioned pull hook; runtime tests count calls through this exact name

# The ``loop_stats`` seconds keys, each fed by one phase's host spans
# (docs/observability.md "Loop spans and profiler capture").
_LOOP_SECONDS = ("prepare_s", "identity_s", "init_s", "upload_s",
                 "dispatch_s", "device_wait_s", "host_decision_s",
                 "retire_wait_s", "assemble_s")

# The fault fingerprint of a sweep without faults, as a checkpoint stores it.
_NO_FAULTS_SHA256 = hashlib.sha256(b"none").hexdigest()


def _cov_reducers(mesh: Mesh):
    """Mesh reductions for the coverage ledger: psum for bucket counts,
    pmin for first-seen seed ids (obs/coverage.py fold_retired)."""
    axes = tuple(mesh.axis_names)
    return (lambda x: jax.lax.psum(x, axes),
            lambda x: jax.lax.pmin(x, axes))


def sharded_engine(eng: DeviceEngine, mesh: Mesh, chunk_steps: int = 512,
                   donate: bool = False,
                   coverage: Optional[int] = None,
                   fault_path: bool = True):
    """Compile a chunk runner: state → (state, any_bug, n_active,
    shard_steps).

    The body is `shard_map`'d so each device advances only its world shard
    (no resharding possible); the scalar outputs are psum/any reductions
    over ALL mesh axes — ICI within a host, DCN across hosts on a 2-D
    ``multihost_mesh`` — the only cross-chip communication in a sweep.
    ``shard_steps`` is the steps each shard executed before its worlds
    all froze (``DeviceEngine._steps_executed``), summed over shards.

    ``donate=True`` donates the input state: XLA updates the sharded
    batch in place instead of double-buffering it, which roughly doubles
    the W that fits in HBM — but the caller's reference is DEAD after
    each call. The sweep enables this exactly when no checkpoint writer
    is attached: the async checkpointer reads the pre-chunk state from a
    background thread, which donation would invalidate.

    ``coverage`` (bucket count, or None): the retire-time behavior fold
    (obs/coverage.py). The runner signature widens to
    ``(state, hits, first_seen, idx, n_real) → (state, any_bug,
    n_active, hits, first_seen, distinct, shard_steps)``: after the
    chunk body, the worlds whose active flag fell during the chunk
    scatter their behavior signatures into the replicated K-bucket
    ledger (psum/pmin over the mesh — the only additions; the chunk body
    itself is untouched, so trajectories stay bitwise identical and with
    ``coverage=None`` this compiles the exact pre-coverage program).

    ``fault_path=False`` traces the step without its fault path
    (``DeviceEngine._form``): only for batches that hold no fault event.

    Runners are cached per (mesh, chunk_steps, donate, coverage,
    fault_path) on the engine, so repeated sweeps reuse the compiled
    program instead of paying a fresh XLA compile for an identical
    closure.
    """
    cache = eng.__dict__.setdefault("_sharded_runner_cache", {})
    key = (mesh, chunk_steps, donate, coverage, fault_path)
    if key in cache:
        return cache[key]
    form = eng._form(fault_path)
    spec = world_spec(mesh)
    axes = tuple(mesh.axis_names)
    sp = scalar_spec()

    def run(state: WorldState):
        steps0 = state.steps
        state = form._run_steps_impl(state, chunk_steps)
        any_bug = jax.lax.psum(
            jnp.any(state.bug).astype(jnp.int32), axes) > 0
        n_active = jax.lax.psum(
            jnp.sum(state.active, dtype=jnp.int32), axes)
        shard_steps = jax.lax.psum(
            eng._steps_executed(steps0, state, chunk_steps), axes)
        return state, any_bug, n_active, shard_steps

    if coverage is None:
        chunk = run
        in_specs, out_specs = (spec,), (spec, sp, sp, sp)
    else:
        from ..obs.coverage import distinct_count, fold_retired

        rsum, rmin = _cov_reducers(mesh)

        def chunk(state: WorldState, hits, first, idx, n_real):
            act0 = state.active
            state, any_bug, n_active, shard_steps = run(state)
            mask = act0 & ~state.active & (idx >= 0) & (idx < n_real)
            hits, first = fold_retired(hits, first, state.metrics, mask,
                                       idx, rsum, rmin)
            return state, any_bug, n_active, hits, first, \
                distinct_count(hits), shard_steps

        in_specs = (spec, sp, sp, spec, sp)
        out_specs = (spec, sp, sp, sp, sp, sp, sp)

    mapped = shard_map(chunk, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    runner = jax.jit(mapped, donate_argnums=(0,) if donate else ())
    cache[key] = runner
    return runner


def sharded_superstep(eng: DeviceEngine, mesh: Mesh, chunk_steps: int,
                      k_max: int, donate: bool = False,
                      min_one: bool = False,
                      coverage: Optional[int] = None,
                      fault_path: bool = True):
    """Compile a superstep runner:
    ``(state, stop_threshold, stop_on_bug, k_chunks) → (state, any_bug,
    n_active, k_done, hist, shard_steps)`` (``shard_steps`` as in
    :func:`sharded_engine`, over every chunk the superstep ran).

    The superstep folds up to ``k_chunks`` chunk bodies into ONE jitted
    dispatch (`DeviceEngine._superstep_impl`): a ``lax.while_loop`` whose
    condition re-checks the psum'd occupancy/bug scalars after every
    chunk, so the early exits the serial loop made from the host run on
    device and the host pays one dispatch + one scalar read per K chunks.
    ``stop_threshold`` / ``stop_on_bug`` / ``k_chunks`` are traced
    scalars — ONE compiled program per (mesh, chunk_steps, k_max,
    donate, min_one) serves every threshold and superstep length the
    adaptive schedule cycles through; only the (k_max,)-shaped history
    buffer is compile-time static.

    ``hist[j]`` is the post-chunk active count for each chunk actually
    run (-1 beyond ``k_done``) — the same per-chunk sequence the serial
    loop's ``n_active_history`` records. ``min_one`` forces the first
    chunk regardless of the entry condition (the serial loop's cadence
    right after a refill/shrink — see ``_superstep_impl``). Donation
    follows :func:`sharded_engine` (on exactly when no checkpoint writer
    holds state references between dispatches).

    ``coverage`` (bucket count, or None) threads the retire-time
    behavior ledger (obs/coverage.py) through the on-device chunk loop:
    the runner widens to ``(state, hits, first_seen, idx, n_real,
    stop_threshold, stop_on_bug, k_chunks) → (state, any_bug, n_active,
    k_done, hist, hits, first_seen, cov_hist, shard_steps)``, where
    ``cov_hist[j]`` is the cumulative distinct-behavior count after chunk ``j`` — the
    novelty curve at exactly the ``hist`` cadence, riding the SAME
    scalar fetch (zero extra device→host syncs). A pass-through
    superstep (entry condition already false) folds nothing, which is
    what keeps the ledger — like everything else — bitwise identical
    between the dispatch-ahead and serial loops.

    ``fault_path`` as in :func:`sharded_engine`, and part of the key.
    """
    cache = eng.__dict__.setdefault("_sharded_superstep_cache", {})
    key = (mesh, chunk_steps, k_max, donate, min_one, coverage, fault_path)
    if key in cache:
        return cache[key]
    form = eng._form(fault_path)
    spec = world_spec(mesh)
    axes = tuple(mesh.axis_names)
    sp = scalar_spec()
    rsum = lambda x: jax.lax.psum(x, axes)  # noqa: E731

    if coverage is None:
        def sstep(state: WorldState, stop_threshold, stop_on_bug, k_chunks):
            return form._superstep_impl(
                state, stop_threshold, stop_on_bug, k_chunks,
                chunk_steps=chunk_steps, k_max=k_max,
                reduce_sum=rsum, min_one=min_one)

        in_specs = (spec, sp, sp, sp)
        out_specs = (spec, sp, sp, sp, sp, sp)
    else:
        from ..obs.coverage import fold_retired

        _, rmin = _cov_reducers(mesh)

        def sstep(state: WorldState, hits, first, idx, n_real,
                  stop_threshold, stop_on_bug, k_chunks):
            def fold(cov, act0, s):
                h, f = cov
                mask = act0 & ~s.active & (idx >= 0) & (idx < n_real)
                return fold_retired(h, f, s.metrics, mask, idx, rsum, rmin)

            (state, any_bug, n_active, k_done, hist, (hits, first), ch,
             ran) = form._superstep_impl(
                state, stop_threshold, stop_on_bug, k_chunks,
                chunk_steps=chunk_steps, k_max=k_max,
                reduce_sum=rsum, min_one=min_one,
                cov=(hits, first), cov_fold=fold)
            return (state, any_bug, n_active, k_done, hist, hits, first, ch,
                    ran)

        in_specs = (spec, sp, sp, spec, sp, sp, sp, sp)
        out_specs = (spec, sp, sp, sp, sp, sp, sp, sp, sp)

    mapped = shard_map(sstep, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    runner = jax.jit(mapped, donate_argnums=(0,) if donate else ())
    cache[key] = runner
    return runner


def _cov_endfolder(eng: DeviceEngine, mesh: Mesh):
    """Compile (and cache per engine) the boundary coverage fold.

    One shard_mapped program folding the worlds whose ``active`` flag
    equals ``fold_active`` into the ledger: the sweep runs it with
    ``fold_active=False`` on resume (worlds that retired before the
    checkpoint carry frozen histograms but will never transition
    active→inactive in THIS call) and with ``fold_active=True`` at sweep
    end (worlds still live at exit — a truncated behavior is a behavior
    too). Because ``hits``/``first_seen`` are fold-order invariant
    (counts and minima), a resumed sweep's final ledger is bit-identical
    to an unbroken run's (tests/test_obs.py). Shapes key jit's own
    retrace cache, so one entry serves every batch width.
    """
    cache = eng.__dict__.setdefault("_cov_endfolder_cache", {})
    if mesh in cache:
        return cache[mesh]
    from ..obs.coverage import fold_retired

    spec = world_spec(mesh)
    sp = scalar_spec()
    rsum, rmin = _cov_reducers(mesh)

    def fold_end(state, hits, first, idx, n_real, fold_active):
        mask = (state.active == fold_active) & (idx >= 0) & (idx < n_real)
        return fold_retired(hits, first, state.metrics, mask, idx,
                            rsum, rmin)

    in_specs = (spec, sp, sp, spec, sp, sp)
    out_specs = (sp, sp)
    mapped = shard_map(fold_end, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    fn = jax.jit(mapped)
    cache[mesh] = fn
    return fn


class TriageContext(NamedTuple):
    """What :meth:`SweepResult.minimize` / ``triage.triage`` need to
    re-execute worlds from this sweep: the engine (compiled programs and
    all), the ORIGINAL fault schedule argument, and the mesh. Attached
    to every locally-run SweepResult; absent (None) on results
    reconstructed from checkpoints or merged across a fleet — those
    must re-run the sweep to minimize.

    Guided sweeps (``search=``) attach the MATERIALIZED per-seed
    ``(n, F, 4)`` schedules here instead of the template argument — each
    world ran a generated child schedule, and this is what lets every
    find pipe unchanged through ``triage.triage`` → ddmin → minimized
    bundles (docs/search.md)."""

    engine: Any                 # the DeviceEngine the sweep ran
    faults: Optional[Any]       # the faults= argument (or, under
                                # search=, the materialized per-seed
                                # schedules)
    mesh: Any                   # the mesh the sweep ran on


class _Flight(NamedTuple):
    """One dispatched-but-unread superstep: its scalar futures plus the
    host-side facts (plan, width, epoch) needed to interpret them."""

    any_bug: Any
    n_active: Any
    k_done: Any
    hist: Any
    shard_steps: Any      # steps its chunks executed, summed over shards
    planned: int          # chunks this dispatch may run (its K)
    w: int                # batch width at dispatch time
    epoch: int            # occupancy epoch at dispatch time
    out_state: Any        # output state ref — kept ONLY for the writer
    cov_hist: Any = None  # per-chunk novelty-curve lane (coverage on)
    # Ledger refs paired with out_state (writer + coverage only): the
    # loop's cov_hits/cov_first globals advance with dispatch-ahead, so
    # a checkpoint must snapshot the refs matching the state it writes —
    # else a resume would restore a ledger one superstep AHEAD of the
    # state and double-fold the replayed chunk's retirees.
    out_cov: Any = None


class _AsyncCheckpointer:
    """Background checkpoint writer: overlaps the device→host pull and the
    npz write with the next chunk's device work (VERDICT r4 item 7 — the
    synchronous save used to block the chunk loop for its full duration).

    Latest-wins coalescing: if the writer is still busy when the next
    snapshot arrives, the queued-but-unstarted one is replaced — for
    preemption survival only the newest durable state matters, and write
    cadence must not backpressure the sweep. Reading completed jax arrays
    from this thread is safe: whenever a writer is attached the sweep
    compiles its chunk runner WITHOUT input donation (donation would hand
    XLA the submitted buffers mid-read — see ``sharded_engine``), and
    the on-disk write stays atomic (engine/checkpoint.py tmp+rename).
    """

    def __init__(self, eng, path, extra_meta):
        import threading

        self._eng = eng
        self._path = path
        self._meta = extra_meta
        self._cond = threading.Condition()
        self._pending = None
        self._busy = False
        self._stop = False
        self._error: Optional[BaseException] = None
        # detlint: allow[DET003] — host-side checkpoint writer beside the device sweep
        self._thread = threading.Thread(
            target=self._run, name="madsim-checkpointer", daemon=True)
        self._thread.start()

    def submit(self, state, aux=None) -> None:
        """Queue a snapshot. ``aux`` (recycled sweeps) is a dict of
        sweep-level values saved beside the state: device arrays (the
        slot→seed index, the coverage ledger) are pulled by the writer
        thread, lists of host arrays (retired observation batches) are
        concatenated there — the loop thread never blocks on either."""
        with self._cond:
            if self._error is not None:
                err, self._error = self._error, None
                raise err
            self._pending = (state, aux)
            self._cond.notify_all()

    def _run(self) -> None:
        import jax as _jax

        from ..engine import checkpoint as ckpt

        while True:
            with self._cond:
                while self._pending is None and not self._stop:
                    self._cond.wait()
                if self._pending is None:
                    return
                (state, aux), self._pending = self._pending, None
                self._busy = True
            try:
                # Pull to host FIRST and drop the device reference: holding
                # the device pytree through the disk write would pin up to
                # a full extra state of HBM while the sweep runs ahead.
                # detlint: allow[DET008] reason=checkpoint writer THREAD; blocks itself, never the dispatch loop
                host_state, host_aux = _jax.device_get((state, aux))
                state = aux = None
                extra_arrays = None
                if host_aux is not None:
                    extra_arrays = {
                        k: (np.concatenate([np.asarray(a) for a in v],
                                           axis=0)
                            if isinstance(v, list) else np.asarray(v))
                        for k, v in host_aux.items()}
                ckpt.save(self._eng, host_state, self._path,
                          extra_meta=self._meta,
                          extra_arrays=extra_arrays)
                exc = None
            except BaseException as e:  # noqa: BLE001 — surfaced at submit/flush
                exc = e
            with self._cond:
                self._busy = False
                if exc is not None:
                    self._error = exc
                self._cond.notify_all()

    def flush_and_close(self, suppress_errors: bool = False) -> None:
        """Wait until every submitted snapshot is durable, then stop.

        ``suppress_errors`` logs a deferred writer failure instead of
        raising — for finally blocks where an in-flight exception must not
        be masked by a checkpoint-write error."""
        with self._cond:
            while self._pending is not None or self._busy:
                self._cond.wait()
            self._stop = True
            self._cond.notify_all()
        self._thread.join()
        if self._error is not None:
            if suppress_errors:
                import logging

                logging.getLogger("madsim_tpu.sweep").warning(
                    "checkpoint write failed during sweep teardown: %r",
                    self._error)
            else:
                raise self._error


@dataclasses.dataclass
class SweepResult:
    """Outcome of a sharded seed sweep."""

    seeds: np.ndarray            # the (unpadded) seed vector
    bug: np.ndarray              # per-seed bug flag
    observations: Dict[str, np.ndarray]  # engine + actor metrics, per seed
    steps_run: int               # executed chunks * chunk_steps
    n_devices: int
    # Occupancy telemetry (docs/perf.md "world recycling"): the active
    # world count after each chunk, and the fraction of executed
    # slot-steps that advanced a live world — useful/(sum over chunks of
    # shard_width * the steps each shard ran before its worlds all froze;
    # the fused path counts batch_width*chunk_steps). Frozen worlds riding
    # masked in the batch are the difference; 1.0 means the mesh never
    # ran a frozen slot.
    n_active_history: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int64))
    world_utilization: float = 0.0
    # The chunk index each ``n_active_history`` entry was MEASURED at
    # (0-based count of executed chunks, aligned entrywise). Under the
    # pipelined loop the host reads a measurement only after dispatching
    # the next superstep, so the decision taken at dispatch d is based on
    # the entry measured at some chunk < d — up to one superstep behind.
    # The measurement sequence itself is per-chunk and identical to the
    # serial loop's; entries are strictly increasing (tier-1-tested).
    # The fused loop records the chunk index INSIDE the device program
    # (a lane of the mega-dispatch history alongside the occupancy
    # counts), so a K-chunk dispatch lands K correctly-indexed entries
    # — no skew relative to the serial sequence even though the host
    # only reads once per mega-dispatch (docs/perf.md "Whole-hunt
    # residency", measurement-skew caveat).
    n_active_chunks: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int64))
    # Orchestration telemetry (docs/perf.md "Pipelined orchestration" /
    # "Whole-hunt residency"): dispatch counts, superstep fan-in, and
    # the host/device wall split of the chunk loop, also carried by the
    # observe= stream's summary record. Keys: pipelined,
    # fused, chunks, dispatches, chunks_per_dispatch,
    # dispatches_per_seed, seeds_per_dispatch, epochs_on_device,
    # dispatch_depth, device_wait_s, host_decision_s, dispatch_s,
    # retire_wait_s, scalar_fetches, retire_fetches, loop_wall_s,
    # superstep_max, chunk_steps, slot_steps_skipped.
    loop_stats: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # Fault-schedule fingerprint (sha256 over the padded rows, or of
    # b"none"): rides the result so repro banners and bundles can assert
    # the replay used the same schedule — a seed alone does not pin the
    # trajectory when schedules vary per run.
    faults_sha256: Optional[str] = None
    # Behavior-coverage ledger (obs/coverage.py SweepCoverage), present
    # when the engine ran ``EngineConfig(metrics=True)``: per-bucket hit
    # counts, lowest-seed-per-bucket attribution, and the per-chunk
    # ``novelty_curve`` (cumulative distinct behaviors, aligned
    # entrywise with ``n_active_history``/``n_active_chunks``).
    coverage: Optional[Any] = None
    # Guided-search report (search/__init__.py SearchReport), present
    # when the sweep ran ``search=SearchConfig(...)``: final corpus
    # contents, insert/generation counters, and the materialized
    # per-seed ``(n, F, 4)`` schedules each world actually ran (also
    # wired into ``triage_ctx.faults`` so triage needs no special
    # casing).
    search: Optional[Any] = None
    # Triage context (triage/): the engine/schedule/mesh refs
    # :meth:`minimize` and ``triage.triage`` re-execute worlds with.
    # None on reconstructed results (fleet merges, checkpoint loads).
    triage_ctx: Optional[TriageContext] = dataclasses.field(
        default=None, repr=False)

    @property
    def failing_seeds(self) -> List[int]:
        return [int(s) for s in self.seeds[self.bug]]

    def minimize(self, seed: Optional[int] = None, **kw):
        """Minimize a failing seed's fault schedule (triage/minimize.py).

        ``seed`` defaults to the first failing seed; ``kw`` forwards to
        :func:`madsim_tpu.triage.minimize` (``pipeline``, ``weaken``,
        ``tighten``, ``chunk_steps``, ``max_steps``, ...). Re-uses this
        sweep's engine — and its compiled programs — so the candidate
        sweeps pay no fresh actor compile. Returns a
        :class:`~madsim_tpu.triage.MinimizeResult` whose ``schedule``
        is the smallest still-failing row set, 1-minimal and
        deterministic (docs/triage.md)."""
        from ..triage import TriageError
        from ..triage import minimize as _minimize

        if self.triage_ctx is None:
            raise TriageError(
                "this SweepResult carries no triage context (merged or "
                "reconstructed result): re-run the sweep locally, or "
                "call triage.minimize(actor, cfg, seed, faults) with "
                "the original inputs")
        if seed is None:
            if not self.failing_seeds:
                raise TriageError("no failing seeds to minimize")
            seed = self.failing_seeds[0]
        rows = np.flatnonzero(np.asarray(self.seeds) == np.uint64(seed))
        if rows.size == 0:
            raise TriageError(f"seed {seed} was not part of this sweep")
        faults = self.triage_ctx.faults
        if faults is not None:
            faults = np.asarray(faults, np.int32)
            if faults.ndim == 3:  # per-world schedules: this seed's rows
                faults = faults[int(rows[0])]
        eng = self.triage_ctx.engine
        return _minimize(eng.actor, eng.cfg, int(seed), faults,
                         engine=eng, mesh=self.triage_ctx.mesh, **kw)

    @property
    def metrics(self) -> Optional[Dict[str, Any]]:
        """Simulation metrics frames (docs/observability.md), or ``None``
        when the sweep ran metrics-off: ``{"per_seed": {field: (n, ...)
        array}, "aggregate": {field: int | [int]}}``. Per-seed rows are
        attributed through the same slot→seed machinery as every other
        observation, so they survive recycling/compaction; the aggregate
        is the fleet sum."""
        from ..obs.metrics import aggregate_metrics, metrics_from_observations

        per_seed = metrics_from_observations(self.observations)
        if per_seed is None:
            return None
        return {"per_seed": per_seed, "aggregate": aggregate_metrics(per_seed)}

    def blackbox(self, seed: Optional[int] = None) -> List[Dict[str, Any]]:
        """Decode one seed's flight-recorder ring (obs/blackbox.py) into
        trace-shaped event records — the last K step events of that
        world, oldest first, with the ``invariant`` raise in place.

        ``seed`` defaults to the first failing seed. Raises
        ``ValueError`` on a blackbox-off sweep (run with
        ``EngineConfig(blackbox=K)``) or an unknown seed. Render with
        ``obs.timeline.ring_to_chrome`` or crosscheck against a fresh
        ``trace()`` via ``obs.blackbox.ring_matches_trace`` (the
        ``obs replay --crosscheck`` CLI leg)."""
        from ..obs.blackbox import decode_ring, rings_from_observations

        rings = rings_from_observations(self.observations)
        if rings is None:
            raise ValueError(
                "this sweep ran blackbox-off: enable the flight recorder "
                "with EngineConfig(blackbox=K) (docs/observability.md)")
        if seed is None:
            if not self.failing_seeds:
                raise ValueError("no failing seeds — pass an explicit "
                                 "seed= to decode a passing world's ring")
            seed = self.failing_seeds[0]
        rows = np.flatnonzero(np.asarray(self.seeds) == np.uint64(seed))
        if rows.size == 0:
            raise ValueError(f"seed {seed} was not part of this sweep")
        row = int(rows[0])
        actor = getattr(getattr(self.triage_ctx, "engine", None),
                        "actor", None)
        return decode_ring({k: v[row] for k, v in rings.items()},
                           kind_names=getattr(actor, "kind_names", None))

    def summary(self) -> str:
        """One human paragraph of what the sweep did — seeds, bugs,
        utilization, coverage, top drop causes — so operators read prose
        instead of grepping a dataclass repr (examples/device_sweep.py
        and the repro banner both print it)."""
        n = len(self.seeds)
        n_bug = len(self.failing_seeds)
        parts = [f"swept {n} seed{'s' if n != 1 else ''} on "
                 f"{self.n_devices} device(s) in {self.steps_run} issued "
                 f"steps: {n_bug} failing"]
        if self.n_active_history.size:
            parts.append(f"world utilization "
                         f"{self.world_utilization:.0%} over "
                         f"{self.n_active_history.size} chunks")
        if self.coverage is not None:
            cov = self.coverage
            curve = cov.novelty_curve
            tail = (f" (novelty {int(curve[0])}→{int(curve[-1])} "
                    f"across the run)" if curve.size else "")
            parts.append(f"{cov.distinct_behaviors} distinct behaviors "
                         f"in {cov.n_buckets} buckets{tail}")
        if self.search is not None:
            # Guided hunts summarize their evolution too (obs/lineage.py):
            # corpus fill, insert pressure, generations, top operator.
            s = self.search
            line = (f"guided search: corpus {s.corpus_size}/"
                    f"{s.corpus_capacity}, {s.inserted} inserted over "
                    f"{s.generations} generations")
            if getattr(s, "operator_stats", None):
                from ..obs.lineage import top_operator

                top = top_operator(s.operator_stats)
                if top:
                    line += f", top operator {top}"
            parts.append(line)
        m = self.metrics
        if m is not None:
            agg = m["aggregate"]
            drops = sorted(((k, v) for k, v in agg.items()
                            if k.startswith("drop_") and isinstance(v, int)
                            and v > 0), key=lambda kv: -kv[1])
            if drops:
                parts.append("top drop causes: " + ", ".join(
                    f"{k[5:]}={v}" for k, v in drops[:3]))
        from ..obs.blackbox import ring_depth

        k_ring = ring_depth(self.observations)
        parts.append(f"black box: last {k_ring} events/world recorded"
                     if k_ring is not None else "black box: off")
        return "; ".join(parts) + "."

    def repro_banner(self) -> Optional[str]:
        """The failing-seed reproduction hint (`runtime/mod.rs:192-199`),
        prefixed with the human :meth:`summary` paragraph."""
        if not self.failing_seeds:
            return None
        banner = self.summary() + "\n"
        banner += ("note: run with environment variable "
                  f"MADSIM_TEST_SEED={self.failing_seeds[0]} to reproduce "
                  f"this failure ({len(self.failing_seeds)} failing seeds "
                  "total)")
        if self.faults_sha256 is not None:
            banner += (f"\nnote: fault-schedule sha256: "
                       f"{self.faults_sha256[:16]} (replay must use the "
                       "same schedule)")
        from ..obs.blackbox import ring_depth

        k_ring = ring_depth(self.observations)
        banner += ("\nnote: flight recorder "
                   + (f"K={k_ring} (SweepResult.blackbox(seed) decodes "
                      "the failing world's last events)" if k_ring
                      else "off (enable with EngineConfig(blackbox=K))"))
        return banner


@_obsy.spanned("madsim:sweep")
def sweep(actor: Any, cfg: EngineConfig, seeds, faults: Optional[np.ndarray] = None,
          mesh: Optional[Mesh] = None, chunk_steps: int = 512,
          max_steps: int = 1_000_000, stop_on_first_bug: bool = False,
          engine: Optional[DeviceEngine] = None,
          checkpoint_path: Optional[str] = None,
          checkpoint_every_chunks: int = 0,
          resume: bool = False,
          compact: bool = False,
          recycle: bool = False,
          batch_worlds: Optional[int] = None,
          pipeline: bool = True,
          superstep_max: int = 16,
          fused: bool = False,
          observe: Any = None,
          profile_dir: Optional[str] = None,
          profile_window: Tuple[int, int] = (0, 4),
          coverage_buckets: Optional[int] = None,
          search: Optional[Any] = None,
          search_corpus: Optional[Any] = None,
          search_gen0: int = 0,
          search_lin_base: int = 0) -> SweepResult:
    """Run one simulation per seed, sharded over the mesh, to completion.

    The loop is a slot-occupancy model: the device batch is a fixed set of
    world *slots*, each holding a live world, a finished one awaiting
    retirement, or (after retirement) a recycled world for a fresh seed.
    Per chunk the host learns exactly two scalars — "any bug?" and "how
    many slots are active?" — and every occupancy decision (shrink,
    retire, refill) runs as an on-device program keyed off that count.

    ``pipeline`` (default True): dispatch-ahead, superstepped
    orchestration (docs/perf.md "Pipelined orchestration"). Up to
    ``superstep_max`` chunks fold into one jitted dispatch whose early
    exits (all retired, occupancy at the recycle/compact threshold, bug
    under ``stop_on_first_bug``) run on device, and the host issues the
    next superstep BEFORE reading the previous one's scalars, so XLA's
    async dispatch keeps the device queue non-empty while the host
    decides. K adapts to the observed retirement rate: it doubles
    (capped at ``superstep_max``) while supersteps run to plan and
    settles to the chunks a cut-short superstep actually ran — all
    inputs are sim outputs, so the dispatch schedule is deterministic
    per (seeds, config), and K rides as a traced scalar so the schedule
    never recompiles. A superstep dispatched past a stop/threshold point runs
    ZERO chunks (its entry condition is false), so one-dispatch-stale
    occupancy reads never advance, retire, or refill a world the serial
    loop would not have: results — including retirement attribution —
    are bitwise identical to ``pipeline=False`` (the serial per-chunk
    reference loop, tier-1-tested for every actor family). Decisions are
    additionally epoch-guarded: after a refill/shrink, occupancy reads
    from supersteps dispatched before it are ignored (they ran zero
    chunks), so a stale trigger can never re-fire on the slots it just
    refilled.

    ``fused`` (opt-in; docs/perf.md "Whole-hunt residency"): the
    whole-hunt fused program. The occupancy loop itself — compaction,
    retiring-tail harvest into per-seed device buffers, the coverage
    fold, guided generation, refill and the seed cursor — moves inside
    ONE ``lax.while_loop`` dispatch (:func:`_fused_hunt`), so the host
    issues O(1) mega-dispatches per batch instead of one dispatch per
    refill epoch. Mid-hunt host reads stay the sanctioned ``_fetch``
    scalar batch (one per mega-dispatch); the retired observations are
    pulled ONCE at the end. Results are bitwise identical to the
    serial/pipelined loops (ids, observations, ``m_*`` metrics,
    coverage ledger, lineage lanes, SearchReport — tier-1,
    tests/test_fused.py); only ``world_utilization`` may differ, since
    the fused tail skips the dry-cursor shrink (contract surfaces are
    shrink-invariant — the shrink exists to save flops, which the fused
    loop saves by not leaving the device instead). ``fused=True``
    refuses ``checkpoint_path`` and ``compact`` (see the ValueErrors
    below for the reasoning) and subsumes ``pipeline``.

    Preemption survival: with ``checkpoint_path`` set, the (padded) world
    state is written every ``checkpoint_every_chunks`` chunks (and at the
    end); with ``resume=True`` an existing checkpoint is loaded instead of
    re-initializing, and the sweep continues bit-exactly where it stopped —
    resumed trajectories equal an unbroken run's (the state carries every
    RNG cursor and queue). ``max_steps`` counts steps issued by THIS call.
    Under pipelining the snapshot cadence is superstep-granular (K caps at
    ``checkpoint_every_chunks``), and the submitted state is always a
    COMPLETED superstep output the writer can read while later supersteps
    run — donation stays disabled whenever a writer is attached, exactly
    as in the serial loop.

    Donation caveat: without checkpointing, the chunk runner DONATES its
    input state (XLA steps the batch in place — roughly double the W per
    HBM; a donated state is dead after the call). Checkpointing turns
    donation off, because the async writer still reads the submitted
    pre-chunk state while the next chunk runs — so a checkpointed sweep
    keeps the old double-buffered peak. Budget W accordingly when
    enabling ``checkpoint_path``.

    ``compact``: straggler compaction (docs/perf.md "the straggler
    tail"). A chunked batch runs until its SLOWEST world finishes, so
    once most worlds are done the chip mostly advances frozen state.
    When the active count drops below half the batch, the sweep gathers
    the active worlds to the front — a stable active-first ``argsort``
    computed INSIDE a jitted, mesh-resident program, so no per-world
    state (not even ``state.active``) crosses to the host and no reshard
    round trip follows — retires the frozen tail (its observations are
    sliced out ON DEVICE and pulled alone, never the full batch), and
    continues on a power-of-two-smaller batch. Worlds' trajectories are
    position-independent, so results are bitwise identical to the
    uncompacted run (tested). Disabled automatically when checkpointing
    (a shrunken state cannot resume into the full-shape contract).

    ``recycle`` + ``batch_worlds``: world recycling / seed streaming
    (docs/perf.md "world recycling"). Instead of only shrinking, retired
    slots are REFILLED with freshly initialized worlds for the next
    seeds from a host-side cursor: the sweep holds ``batch_worlds``
    slots (rounded to the mesh) and streams the full seed list through
    them, keeping utilization near 100% while any seeds remain; once the
    cursor is dry it falls back to shrink compaction for the tail. Each
    refilled world is bit-identical to an independent run of its seed
    (tested). This is the shape for open-ended hunts —
    ``stop_on_first_bug`` sweeps over huge seed spaces on a bounded
    memory footprint. On an early stop, seeds never admitted report
    zeroed observations (``bug=False``).

    Recycled sweeps CAN checkpoint (the hunt config a long-running fleet
    actually uses): the checkpoint carries, beside the world state, the
    device-resident slot→seed index, the refill cursor, the retired
    observations recorded so far, and (metrics on) the coverage ledger
    — everything a resume needs to re-attribute recycled slots. Resume
    requires the same ``batch_worlds`` (the padded-seed hash already
    pins seeds/faults; the slot width is checked explicitly — a
    shrunk-compacted state cannot resume into the full-shape contract
    and raises ``ValueError``). While a writer is attached the dry-
    cursor shrink fallback stays OFF (the tail runs at the full batch
    width) so every snapshot written is resumable. A resumed recycled
    sweep's per-seed observations, bug flags, and coverage ledger equal
    an unbroken run's exactly; refill *timing* after the resume point
    may differ by one chunk, so occupancy histories are telemetry, not
    part of the contract.

    Occupancy telemetry rides the result: ``SweepResult.n_active_history``
    (per-chunk active counts, with ``n_active_chunks`` recording the
    chunk index each entry was measured at), ``world_utilization``
    (live-world steps / issued slot-steps, mesh padding included), and
    ``loop_stats`` (the dispatch-count / host-stall breakdown of the
    orchestration loop).

    Observatory knobs (docs/observability.md "The sweep observatory"):

    ``observe``: a live telemetry sink — a callable receiving one dict
    per host read of the loop's scalars (per chunk on the serial path,
    per superstep when pipelined), or a file path for a JSONL stream
    (``python -m madsim_tpu.obs watch <file>`` tails/summarizes it).
    Records are built ONLY from values the loop already fetched plus
    host counters — zero extra device syncs (counted-``_fetch`` tested)
    — and cover seeds/s, occupancy, utilization, coverage growth,
    dispatch depth, and ETA.

    ``profile_dir`` + ``profile_window``: wrap a window of the loop's
    dispatches (by dispatch index, ``[start, stop)``) in
    ``jax.profiler`` trace capture, so a device timeline lands in
    ``profile_dir`` next to the virtual-time timelines of
    obs/timeline.py. Purely host-side observation: trajectories and the
    dispatch schedule are unchanged. The loop's host spans
    (``madsim:*``) show in any ``jax.profiler`` capture.

    ``coverage_buckets``: bucket count of the behavior-coverage ledger
    (obs/coverage.py; default ``DEFAULT_BUCKETS`` when the engine runs
    ``EngineConfig(metrics=True)``). The ledger folds each retiring
    world's metrics histograms into a device-resident K-bucket sketch —
    psum'd across the mesh inside the chunk/superstep programs, zero
    host pulls mid-loop — and lands on ``SweepResult.coverage`` with the
    per-chunk ``novelty_curve``. Requires metrics; passing an explicit
    value with a metrics-off engine raises ``ValueError``.

    ``search``: a :class:`~madsim_tpu.search.SearchConfig` — coverage-
    guided fault-schedule evolution (docs/search.md, the closed fuzzer
    loop of ROADMAP item 2). Requires ``recycle=True`` (the feedback
    edge IS the refill), ``EngineConfig(metrics=True)`` (novelty hashes
    the MetricsBlock), and a non-empty ``faults`` template (the fault
    vocabulary the operators perturb within). At every refill boundary
    one extra jitted program (search/generate.py, registry
    ``search.generate``) harvests the retiring slots' behavior
    signatures into a device-resident parent corpus and generates
    mutated/crossed-over children, which the refill installs via the
    per-slot device schedule path of ``DeviceEngine.refill`` — zero new
    mid-loop host pulls (corpus telemetry rides the retire pulls the
    loop already pays; tier-1-counted). The whole guided run is a pure
    function of (seeds, config, SearchConfig.seed): bitwise identical
    across re-runs and across ``pipeline=True/False``, and checkpoint→
    resume restores the corpus and per-slot schedules bit-exactly.
    Results gain ``SweepResult.search`` (final corpus + the
    materialized per-seed schedules), and ``triage_ctx.faults`` becomes
    that per-seed array, so ``triage.triage``/``minimize`` work on
    guided finds unchanged.

    ``search_corpus``: a host corpus snapshot
    (:class:`~madsim_tpu.search.corpus.HostCorpus`-shaped: ``sched``
    ``(K, F, 4)``, ``sig``/``score``/``filled`` ``(K,)``) that SEEDS the
    device corpus instead of the template-only ``corpus_init`` — the
    fleet's cross-range corpus exchange (fleet/exchange.py) passes the
    merged previous-epoch corpus here so a leased range continues the
    fleet's search instead of restarting from the template. One
    host→device transfer at sweep start; zero mid-loop syncs added.
    Seeding with the template-initialized corpus is bitwise identical
    to ``search_corpus=None`` (tested). A checkpoint resume overrides
    it (the snapshot's corpus wins — it already embeds the seed).

    ``search_gen0``: starting value of the corpus generation counter
    (default 0). The mutation lanes key children by ``(SearchConfig.
    seed, slot seed id, generation)``, so two sweeps over the same
    corpus at the same generations draw the SAME mutations; the
    exchange offsets each epoch's ranges by a fixed stride
    (fleet/exchange.py ``GEN_STRIDE``) so a seeded epoch explores fresh
    mutation streams instead of redrawing its parents' — deterministic
    per range, chaos-invariant. ``SweepResult.search.generations``
    still reports the generations THIS sweep ran (the offset is
    subtracted).

    ``search_lin_base``: base of the lineage entry-id space
    (obs/lineage.py; default 0). A world at seed position ``i`` whose
    schedule survives into the corpus is recorded under entry id
    ``search_lin_base + i + 1`` — a fleet range passes its ``lo`` so
    entry ids are globally unique across ranges and the merged report
    resolves cross-range ancestry with plain arithmetic. Pure
    accounting: it shifts ids only, never a corpus decision or a child
    byte.
    """
    import os

    from ..engine import checkpoint as ckpt

    tr = _obsy.LoopTracer(_LOOP_SECONDS)
    eng = engine if engine is not None else DeviceEngine(actor, cfg)
    mesh = mesh if mesh is not None else seed_mesh()
    n_dev = mesh.devices.size
    seeds = np.asarray(seeds, np.uint64)
    n = seeds.shape[0]

    if superstep_max < 1:
        raise ValueError("superstep_max must be >= 1")

    if fused and checkpoint_path is not None:
        raise ValueError(
            "fused=True cannot checkpoint: the whole-hunt program "
            "retires and refills worlds inside one device dispatch, so "
            "no host-visible boundary exists mid-hunt where state, "
            "cursor, and retired observations are simultaneously "
            "consistent for a snapshot — run the pipelined path "
            "(fused=False) when checkpoint_path is set")
    if fused and compact:
        raise ValueError(
            "fused=True has no shrink path: compact=True saves flops "
            "by narrowing a mostly-frozen batch, but the fused loop "
            "already avoids the host round trips that made the "
            "straggler tail expensive, and every result surface is "
            "shrink-invariant — drop compact (or run fused=False)")

    # Behavior-coverage ledger (obs/coverage.py): on exactly when the
    # engine carries the MetricsBlock — signatures are hashes of it.
    from ..obs.coverage import (
        DEFAULT_BUCKETS,
        coverage_from_device,
        ledger_zeros,
    )
    cov_on = bool(eng.cfg.metrics)
    if coverage_buckets is not None and not cov_on:
        raise ValueError(
            "coverage_buckets requires EngineConfig(metrics=True): the "
            "behavior ledger hashes the MetricsBlock histograms of "
            "retiring worlds")
    cov_k = int(coverage_buckets) if coverage_buckets else DEFAULT_BUCKETS
    if cov_on and cov_k < 1:
        raise ValueError("coverage_buckets must be >= 1")

    # Guided schedule search (search/, docs/search.md): validated here,
    # wired in at the refill boundaries below.
    search_on = search is not None
    if search_on:
        if not recycle:
            raise ValueError(
                "search= needs recycle=True (and batch_worlds): guided "
                "children stream into recycled refill slots — a "
                "non-recycled sweep has no refill edge to feed")
        if not cov_on:
            raise ValueError(
                "search= requires EngineConfig(metrics=True): the "
                "novelty signal hashes the MetricsBlock histograms of "
                "retiring worlds (obs/coverage.py)")
        if faults is None:
            raise ValueError(
                "search= needs a fault-schedule template (faults=): the "
                "mutation operators perturb within the template's fault "
                "vocabulary — an empty schedule has nothing to evolve")
    if search_corpus is not None and not search_on:
        raise ValueError(
            "search_corpus= seeds the guided-search parent corpus and "
            "needs search=SearchConfig(...) — a plain sweep has no "
            "corpus to seed")
    if search_gen0 and not search_on:
        raise ValueError("search_gen0= offsets the guided mutation "
                         "streams and needs search=SearchConfig(...)")
    if search_gen0 < 0:
        raise ValueError("search_gen0 must be >= 0")
    if search_lin_base and not search_on:
        raise ValueError("search_lin_base= offsets the lineage entry-id "
                         "space and needs search=SearchConfig(...)")
    if search_lin_base < 0:
        raise ValueError("search_lin_base must be >= 0")
    lineage_on = bool(search_on and getattr(search, "lineage", False))

    with tr.span("madsim:prepare", "prepare_s"):
        # Batch width: a multiple of the mesh. Plain sweeps hold every seed at
        # once; recycled sweeps hold batch_worlds slots and stream the rest.
        full_w = n + ((-n) % n_dev)
        if recycle and batch_worlds is not None:
            w0 = min(max(1, int(batch_worlds)), max(n, 1))
            w0 += (-w0) % n_dev
            w0 = min(w0, full_w)
        else:
            w0 = full_w
        # Pad the seed-id space to the batch width (padded worlds are real
        # simulations of dummy seeds; their results are sliced off below).
        n_ids = max(n, w0)
        seeds_p = (np.concatenate([seeds, seeds[:1].repeat(n_ids - n)])
                   if n_ids > n else seeds)

        faults_p = faults
        per_world_faults = False
        if faults is not None:
            faults_p = np.asarray(faults, np.int32)
            if faults_p.ndim == 2:
                if faults_p.shape[-1] != 4:
                    raise ValueError(
                        f"shared fault schedule must be (F, 4) rows of "
                        f"[time_us, op, a, b]; got shape {faults_p.shape}")
            elif faults_p.ndim == 3:
                # Validate the leading dim EXPLICITLY against len(seeds):
                # without this, a mismatched (m, F, 4) would silently gather
                # via ``faults_p[ids]`` below — wrong-world schedules (m > n)
                # or an IndexError deep in a refill (m < n) instead of a
                # boundary error naming both dims.
                if faults_p.shape[-1] != 4:
                    raise ValueError(
                        f"per-world fault schedules must be (n_seeds, F, 4) "
                        f"rows of [time_us, op, a, b]; got shape "
                        f"{faults_p.shape}")
                if faults_p.shape[0] != n:
                    raise ValueError(
                        f"per-world fault schedules carry one (F, 4) block "
                        f"per seed: got leading dim {faults_p.shape[0]} but "
                        f"len(seeds)={n}")
                per_world_faults = True
                if n_ids > n:
                    faults_p = np.concatenate(
                        [faults_p, faults_p[:1].repeat(n_ids - n, axis=0)],
                        axis=0)
            else:
                raise ValueError(
                    f"faults must be (F, 4) or (n_seeds, F, 4); got "
                    f"{faults_p.ndim}-D shape {faults_p.shape}")

        # The step runs its fault path only where a world can hold a
        # fault event: fault rows at init or at a refill (guided
        # children are fault rows). ``form`` inits and refills, and
        # refuses fault rows without it.
        fault_path = search_on or (faults_p is not None
                                   and faults_p.shape[-2] > 0)
        form = eng._form(fault_path)

        def batch_faults(ids: np.ndarray):
            """Fault rows for the worlds holding the given seed ids."""
            if faults_p is None:
                return None
            return faults_p[ids] if per_world_faults else faults_p

        # The fault fingerprint rides every result that has faults (repro
        # banners, bundles, the fleet merge check).
        faults_sha256 = (hashlib.sha256(
            np.ascontiguousarray(faults_p).tobytes()).hexdigest()
            if faults_p is not None else _NO_FAULTS_SHA256)
        # World identity travels with the checkpoint: resuming under different
        # seeds OR fault schedules would silently attribute results (repro
        # banners!) to inputs that never produced them. Only a checkpoint
        # reads the seeds' hash, so only a checkpointed sweep pays for it.
        seeds_meta = None
        if checkpoint_path:
            with tr.span("madsim:identity", "identity_s"):
                seeds_meta = {
                    "seeds_sha256": hashlib.sha256(
                        seeds_p.tobytes()).hexdigest(),
                    "faults_sha256": faults_sha256,
                }

    resumed = False
    resume_aux: Dict[str, np.ndarray] = {}
    with tr.span("madsim:init", "init_s"):
        if resume and checkpoint_path and os.path.exists(checkpoint_path):
            state, resume_aux = ckpt.load(
                eng, checkpoint_path, expect_extra=seeds_meta, with_aux=True)
            w_file = int(np.asarray(state.now).shape[0])
            if recycle:
                # Recycled checkpoints carry the sweep-level aux (cursor,
                # slot→seed index, retired observations) — without it the
                # file is a plain full-batch snapshot this mode cannot
                # re-attribute.
                if "cursor" not in resume_aux:
                    raise ckpt.CheckpointError(
                        f"checkpoint {checkpoint_path!r} was written by a "
                        "non-recycled sweep (no slot->seed aux): resume it "
                        "with recycle=False, or delete it to start the "
                        "recycled hunt fresh")
                if w_file != w0:
                    raise ValueError(
                        f"cannot resume recycled sweep: checkpoint holds "
                        f"{w_file} world slots but batch_worlds implies {w0} "
                        "— a shrunk-compacted or differently-batched state "
                        "cannot resume into the full-shape contract; rerun "
                        "with the original batch_worlds")
            elif "cursor" in resume_aux:
                raise ckpt.CheckpointError(
                    f"checkpoint {checkpoint_path!r} was written by a "
                    "recycled sweep: pass recycle=True (and the original "
                    "batch_worlds) to resume it")
            elif w_file != seeds_p.shape[0]:
                raise ckpt.CheckpointError(
                    f"checkpoint holds {w_file} worlds, "
                    f"sweep expects {seeds_p.shape[0]} (seeds + mesh padding)")
            state = shard_worlds(state, mesh)
            resumed = True
        else:
            state = shard_worlds(form.init(
                seeds_p[:w0], faults=batch_faults(np.arange(w0))), mesh)

    writer = (_AsyncCheckpointer(eng, checkpoint_path, seeds_meta)
              if checkpoint_path else None)
    # Donate the chunk state unless a checkpoint writer holds references
    # to it between chunks (the writer reads the submitted pytree from a
    # background thread; donating would hand XLA its buffers mid-read).
    donate = writer is None
    compact = compact and writer is None  # shrunken state cannot resume
    steps = 0
    chunks = 0                         # executed chunk bodies
    c_max = -(-max_steps // chunk_steps)  # serial loop's chunk budget
    # Chunk counter at the last writer submission — a counter, not an
    # object ref: a pytree ref here would pin a full extra device state
    # between checkpoints. Compact stays disabled under a writer; a
    # recycled refill CAN change state without running a chunk, but every
    # snapshot is self-consistent (state+idx+cursor+retired captured
    # together), and a post-submit refill with no subsequent chunk simply
    # re-derives deterministically on resume — so chunk-count identity
    # remains a sound skip condition for the final submit.
    submitted_chunks = -1
    with tr.span("madsim:upload", "upload_s"):
        w_cur = w0                         # current batch width (slot count)
        cursor = w0                        # next seed id the stream admits
        # Slot→seed-id map, DEVICE-resident: compaction permutes it with the
        # state in the same on-device program, so the host never needs the
        # permutation (or state.active) to keep attribution straight. -1
        # marks a dead slot (retired world still riding in the batch).
        idx = shard_worlds(jnp.arange(w_cur, dtype=jnp.int32), mesh)
        reordered = False                  # batch rows still == seed order?
        retired: Dict[str, list] = {}      # field → retired obs batches
        retired_rows: List[np.ndarray] = []
        # -- guided-search state (search/, docs/search.md) --------------------
        # slot_sched: the (W, F, 4) schedule each slot is CURRENTLY running,
        # device-resident and permuted/refilled in lockstep with the state —
        # the attribution that makes generated children replayable. corpus:
        # the mesh-replicated parent pool (search/corpus.py).
        slot_sched = corpus = None
        retired_sched: List[np.ndarray] = []
        # -- lineage lanes + operator outcome table (obs/lineage.py) ----------
        # slot_lin: per-slot provenance (parent entry ids, applied-operator
        # bitmask, ancestry depth), permuted/split/refilled in lockstep with
        # slot_sched; op_tab: the per-operator produced/novel/survived/bug
        # counters, accumulated inside the searcher program.
        slot_lin = op_tab = None
        retired_lin: List[tuple] = []
        search_host = {"corpus_size": 1, "inserted": 0, "gen": 0,
                       "refill_novel": 0, "refill_inserted": 0}
        if search_on:
            from ..search.corpus import CorpusState, corpus_init
            from ..search.generate import searcher as _searcher
            from ..triage.shrink import normalize as _normalize_sched

            f_rows = int(faults_p.shape[-2])
            base0 = (faults_p[:w0] if per_world_faults
                     else np.broadcast_to(faults_p, (w0,) + faults_p.shape))
            slot_sched = shard_worlds(
                jnp.asarray(np.ascontiguousarray(base0), jnp.int32), mesh)
            if lineage_on:
                from ..obs.lineage import lanes_origin, table_zeros

                # The initial batch runs the template itself: generation-0
                # lanes (no parents, no operators, depth 0).
                slot_lin = shard_worlds(lanes_origin(w0), mesh)
                op_tab = jax.device_put(table_zeros(),
                                        NamedSharding(mesh, scalar_spec()))
            if search_corpus is not None:
                # Exchange seeding (fleet/exchange.py): start from a merged
                # host corpus instead of the template-only init. The per-
                # sweep gen/inserted counters still start at zero — they
                # count THIS sweep's refills/inserts.
                sc_sched = np.asarray(search_corpus.sched, np.int32)
                k = int(search.corpus)
                if sc_sched.shape != (k, f_rows, 4):
                    raise ValueError(
                        f"search_corpus.sched must be (K, F, 4) = "
                        f"({k}, {f_rows}, 4) for SearchConfig.corpus={k} and "
                        f"the {f_rows}-row template; got {sc_sched.shape}")
                for name in ("sig", "score", "filled", "entry", "depth"):
                    shp = np.asarray(getattr(search_corpus, name)).shape
                    if shp != (k,):
                        raise ValueError(
                            f"search_corpus.{name} must be ({k},) for "
                            f"SearchConfig.corpus={k}; got {shp}")
                # gen starts at the epoch stream offset (fleet/exchange.py):
                # generation is the third key of the mutation lanes, so the
                # shift moves this sweep onto a fresh splitmix64 stream
                # family instead of redrawing the seed corpus's parents'.
                corpus = jax.device_put(CorpusState(
                    sched=jnp.asarray(sc_sched),
                    sig=jnp.asarray(np.asarray(search_corpus.sig, np.uint32)),
                    score=jnp.asarray(np.asarray(search_corpus.score,
                                                 np.int32)),
                    filled=jnp.asarray(np.asarray(search_corpus.filled, bool)),
                    gen=jnp.int32(search_gen0), inserted=jnp.int32(0),
                    entry=jnp.asarray(np.asarray(search_corpus.entry,
                                                 np.int32)),
                    depth=jnp.asarray(np.asarray(search_corpus.depth,
                                                 np.int32)),
                ), NamedSharding(mesh, scalar_spec()))
            else:
                # Corpus seeded with the (normalized) template: parents
                # always exist, so generation-1 children mutate the original
                # schedule.
                template = _normalize_sched(
                    faults_p[0] if per_world_faults else faults_p)
                c0 = corpus_init(int(search.corpus), template)
                if search_gen0:
                    c0 = c0._replace(gen=jnp.int32(search_gen0))
                corpus = jax.device_put(
                    c0, NamedSharding(mesh, scalar_spec()))
        if resumed and recycle:
            # Rehydrate the sweep-level bookkeeping the checkpoint carried:
            # the slot→seed index (device-resident again), the refill
            # cursor, and the observations of every world retired before the
            # snapshot. With these restored, the continuation re-attributes
            # recycled slots exactly as the unbroken run would have.
            cursor = int(np.asarray(resume_aux["cursor"]))
            idx = shard_worlds(
                jnp.asarray(np.asarray(resume_aux["idx"], np.int32)), mesh)
            reordered = True
            if "ret_rows" in resume_aux:
                retired_rows.append(np.asarray(resume_aux["ret_rows"]))
                for key in resume_aux:
                    if key.startswith("ret_") and key != "ret_rows":
                        retired[key[4:]] = [np.asarray(resume_aux[key])]
            if search_on != ("srch_sched" in resume_aux):
                raise ckpt.CheckpointError(
                    f"checkpoint {checkpoint_path!r} was written by a "
                    f"{'guided' if 'srch_sched' in resume_aux else 'plain'} "
                    f"sweep but this resume is "
                    f"{'guided (search=...)' if search_on else 'plain'}: "
                    "the per-slot schedules and search corpus cannot be "
                    "reconciled — resume with the original search setting")
            if search_on:
                # Restore the search state bit-exactly: the per-slot
                # schedules, the parent corpus (incl. its generation and
                # insert counters), and the retired-schedule attribution.
                from ..search.corpus import CorpusState

                if lineage_on != ("srch_lin_p1" in resume_aux):
                    raise ckpt.CheckpointError(
                        f"checkpoint {checkpoint_path!r} was written with "
                        f"lineage "
                        f"{'on' if 'srch_lin_p1' in resume_aux else 'off'} "
                        f"but this resume runs SearchConfig(lineage="
                        f"{lineage_on}): the provenance lanes cannot be "
                        "reconciled — resume with the original lineage "
                        "setting")
                slot_sched = shard_worlds(jnp.asarray(
                    np.asarray(resume_aux["srch_sched"], np.int32)), mesh)
                corpus = jax.device_put(CorpusState(
                    sched=jnp.asarray(np.asarray(resume_aux["srch_c_sched"],
                                                 np.int32)),
                    sig=jnp.asarray(np.asarray(resume_aux["srch_c_sig"],
                                               np.uint32)),
                    score=jnp.asarray(np.asarray(resume_aux["srch_c_score"],
                                                 np.int32)),
                    filled=jnp.asarray(np.asarray(resume_aux["srch_c_filled"],
                                                  bool)),
                    gen=jnp.asarray(np.asarray(resume_aux["srch_c_gen"],
                                               np.int32).reshape(())),
                    inserted=jnp.asarray(np.asarray(
                        resume_aux["srch_c_inserted"], np.int32).reshape(())),
                    entry=jnp.asarray(np.asarray(resume_aux["srch_c_entry"],
                                                 np.int32)),
                    depth=jnp.asarray(np.asarray(resume_aux["srch_c_depth"],
                                                 np.int32)),
                ), NamedSharding(mesh, scalar_spec()))
                if "srch_ret" in resume_aux:
                    retired_sched.append(
                        np.asarray(resume_aux["srch_ret"], np.int32))
                if lineage_on:
                    # Lineage lanes + operator table ride the same aux
                    # channel — a resumed hunt's ancestry and outcome
                    # accounting equal an unbroken run's bit for bit.
                    from ..obs.lineage import LineageLanes, OperatorTable

                    slot_lin = shard_worlds(LineageLanes(
                        p1=jnp.asarray(np.asarray(resume_aux["srch_lin_p1"],
                                                  np.int32)),
                        p2=jnp.asarray(np.asarray(resume_aux["srch_lin_p2"],
                                                  np.int32)),
                        ops=jnp.asarray(np.asarray(resume_aux["srch_lin_ops"],
                                                   np.int8)),
                        depth=jnp.asarray(np.asarray(
                            resume_aux["srch_lin_depth"], np.int32)),
                    ), mesh)
                    op_tab = jax.device_put(OperatorTable(
                        produced=jnp.asarray(np.asarray(
                            resume_aux["srch_op_produced"], np.int32)),
                        novel=jnp.asarray(np.asarray(
                            resume_aux["srch_op_novel"], np.int32)),
                        survived=jnp.asarray(np.asarray(
                            resume_aux["srch_op_survived"], np.int32)),
                    ), NamedSharding(mesh, scalar_spec()))
                    if "srch_ret_lin_p1" in resume_aux:
                        retired_lin.append(tuple(
                            np.asarray(resume_aux[f"srch_ret_lin_{k}"])
                            for k in ("p1", "p2", "ops", "depth")))
        n_active_hist: List[int] = []
        n_active_chunk: List[int] = []     # chunk index each entry measured at
        # Slot-steps the chunks executed (the fused path counts them as
        # planned), and those the chunk exit left out: every chunk plans
        # width * chunk_steps.
        issued_slot_steps = 0
        skipped_slot_steps = 0
        live_world_steps = 0               # steps that advanced a live world
        # Counts the spans do not keep; the rest are tr.entered/tr.stats.
        perf = {"retire_fetches": 0, "dispatch_depth": 0}
        t_loop0 = tr.clock()

        # -- observatory hooks (docs/observability.md) ------------------------
        # Telemetry emitter + profiler window are host-side observation only:
        # every record is built from scalars the loop already fetched, so the
        # sync discipline (one _fetch per superstep) is unchanged.
        emit_telemetry, close_telemetry = _obsy.make_observer(observe)
        prof = _obsy.ProfilerWindow(profile_dir, profile_window)
        novelty_hist: List[int] = []       # cumulative distinct, per chunk
        cov_hits = cov_first = n_real_dev = None
        if cov_on:
            cov_hits, cov_first = jax.device_put(
                ledger_zeros(cov_k), NamedSharding(mesh, scalar_spec()))
            n_real_dev = jnp.int32(n)
            if resumed and "cov_hits" in resume_aux:
                # Recycled checkpoints persist the ledger itself (retired-
                # and-refilled slots no longer carry their histograms, so a
                # pre-pass could not rebuild it): restore and continue.
                # Folds trigger on active FALLING within a chunk, so worlds
                # already inactive in the snapshot never re-fold.
                cov_hits, cov_first = jax.device_put(
                    tuple(jnp.asarray(np.asarray(resume_aux[k], np.int32))
                          for k in ("cov_hits", "cov_first")),
                    NamedSharding(mesh, scalar_spec()))
            elif resumed:
                # Resume pre-pass: worlds that retired before the checkpoint
                # carry frozen histograms but will never transition
                # active→inactive in THIS call — fold them up front. The
                # ledger is fold-order invariant (counts + minima), so the
                # final hits/first_seen equal an unbroken run's bit for bit.
                cov_hits, cov_first = _cov_endfolder(eng, mesh)(
                    state, cov_hits, cov_first, idx, n_real_dev,
                    jnp.asarray(False))

    def emit_point(n_act: int, bug_seen: bool, depth: int) -> None:
        """One live-telemetry record per host read of the loop scalars
        (host data only — never a device pull)."""
        if emit_telemetry is None:
            return
        elapsed = tr.clock() - t_loop0
        done = int(min(max(cursor - n_act, 0), n))
        rate = done / elapsed if elapsed > 0 else 0.0
        remaining = n - done
        rec = {
            "schema": "madsim.sweep.telemetry/1",
            "elapsed_s": round(elapsed, 6),
            "chunks": int(chunks),
            "steps": int(steps),
            "batch_worlds": int(w_cur),
            "n_active": int(n_act),
            "occupancy": round(n_act / w_cur, 4) if w_cur else 0.0,
            "seeds_total": int(n),
            "seeds_admitted": int(min(cursor, n)),
            "seeds_done": done,
            "seeds_per_s": round(rate, 2),
            # Running lower bound: retired-tail attribution lands at the
            # next retirement pull, so mid-loop utilization trails the
            # final SweepResult.world_utilization slightly.
            "world_utilization": (round(
                live_world_steps / issued_slot_steps, 4)
                if issued_slot_steps else 0.0),
            "dispatch_depth": int(depth),
            "bug_seen": bool(bug_seen),
            "eta_s": (round(remaining / rate, 3) if rate > 0
                      and remaining > 0 else
                      (0.0 if remaining == 0 else None)),
        }
        if cov_on:
            rec["coverage_distinct"] = (int(novelty_hist[-1])
                                        if novelty_hist else 0)
            rec["coverage_buckets"] = cov_k
        if search_on:
            # Host mirrors of the corpus scalars, refreshed by the
            # retire pulls (never an extra device sync).
            rec["search_corpus"] = search_host["corpus_size"]
            rec["search_inserted"] = search_host["inserted"]
        emit_telemetry(rec)

    def retire(obs_slice: Dict[str, np.ndarray], rows: np.ndarray,
               sched_slice: Optional[np.ndarray] = None,
               lin_slice: Optional[tuple] = None) -> None:
        """Record final observations for rows leaving the batch (dead
        slots — already retired earlier — are filtered out by idx).
        ``sched_slice`` (guided sweeps) carries the retiring rows'
        materialized fault schedules; ``lin_slice`` (lineage on) their
        provenance lanes — both filtered identically."""
        nonlocal live_world_steps
        keep = rows >= 0
        if not keep.all():
            rows = rows[keep]
            obs_slice = {k: np.asarray(v)[keep] for k, v in obs_slice.items()}
            if sched_slice is not None:
                sched_slice = np.asarray(sched_slice)[keep]
            if lin_slice is not None:
                lin_slice = tuple(np.asarray(a)[keep] for a in lin_slice)
        if rows.size == 0:
            return
        live_world_steps += int(np.asarray(obs_slice["steps"]).sum())
        retired_rows.append(rows)
        for k, v in obs_slice.items():
            retired.setdefault(k, []).append(np.asarray(v))
        if sched_slice is not None:
            retired_sched.append(np.asarray(sched_slice, np.int32))
        if lin_slice is not None:
            retired_lin.append(tuple(np.asarray(a) for a in lin_slice))

    def emit_search_point(op_h) -> None:
        """One ``madsim.search.telemetry/1`` record per guided refill —
        built ONLY from the values the retire pull already fetched
        (zero extra device syncs, like every other telemetry record).
        ``op_h`` is the pulled OperatorTable (or None, lineage off)."""
        if emit_telemetry is None or not search_on:
            return
        from ..obs.lineage import OP_NAMES
        from ..obs.lineage import (
            SEARCH_TELEMETRY_SCHEMA as _SEARCH_SCHEMA,
        )

        rec = {
            "schema": _SEARCH_SCHEMA,
            "event": "refill",
            "elapsed_s": round(tr.clock() - t_loop0, 6),
            "generation": search_host["gen"],
            "corpus_size": search_host["corpus_size"],
            "corpus_inserted": search_host["inserted"],
            "refill_novel": search_host["refill_novel"],
            "refill_inserted": search_host["refill_inserted"],
        }
        if "epochs_on_device" in search_host:
            # Fused hunt: refills run ON DEVICE, so this record is the
            # per-MEGA-DISPATCH rollup of the last device refill, not a
            # per-refill sample. The label lets `obs watch` render the
            # collapsed cadence explicitly (docs/observability.md).
            rec["epochs_on_device"] = search_host["epochs_on_device"]
        if op_h is not None:
            for row, vals in zip(("produced", "novel", "survived"), op_h):
                arr = np.asarray(vals)
                for i, name in enumerate(OP_NAMES):
                    rec[f"op_{row}_{name}"] = int(arr[i])
        emit_telemetry(rec)

    def fetch_retire(handles) -> None:
        """Materialize a deferred on-device retirement slice and record
        it. The pull covers ONLY the (bucketed) frozen-tail rows — the
        full per-world observation arrays never cross to the host. On a
        guided sweep the same single ``_fetch`` additionally carries the
        tail's schedule rows, its lineage lanes, the corpus telemetry
        scalars, and the operator outcome table — the "corpus syncs
        ride the existing cadence" half of the zero-new-syncs contract
        (tests/test_search.py counts this)."""
        obs_t, idx_t, tail_len, sched_t, stats_t, lin_t, op_t = handles
        with tr.span("madsim:pull", "retire_wait_s"):
            obs_h, idx_h, sched_h, stats_h, lin_h, op_h = _fetch(
                (obs_t, idx_t, sched_t, stats_t, lin_t, op_t))
        perf["retire_fetches"] += 1
        if stats_h is not None:
            search_host["corpus_size"] = int(stats_h[0])
            search_host["inserted"] = int(stats_h[1])
            if len(stats_h) > 2:           # lineage-on stats vector
                search_host["gen"] = int(stats_h[2])
                search_host["refill_novel"] = int(stats_h[3])
                search_host["refill_inserted"] = int(stats_h[4])
            emit_search_point(op_h)
        retire({k: np.asarray(v)[:tail_len] for k, v in obs_h.items()},
               np.asarray(idx_h)[:tail_len],
               (np.asarray(sched_h)[:tail_len]
                if sched_h is not None else None),
               (tuple(np.asarray(a)[:tail_len] for a in lin_h)
                if lin_h is not None else None))

    def do_refill(n_act: int):
        """World recycling: stable active-first partition on device,
        retire the frozen tail, refill it with the next seeds from the
        cursor. Only the n_active scalar (already on host) shapes the
        refill mask; the tail observations are sliced on device and
        returned as un-fetched handles so the pull can overlap later
        dispatches.

        Guided sweeps (``search=``) widen this boundary, still with zero
        host pulls: the per-slot schedule array compacts alongside the
        state, the retiring tail's schedules join the deferred handles,
        and ONE extra jitted dispatch (search/generate.py) harvests the
        tail into the corpus and generates the children the refill
        installs through ``DeviceEngine.refill``'s device-schedule
        path."""
        nonlocal state, idx, cursor, reordered, slot_sched, corpus, \
            slot_lin, op_tab
        if search_on and lineage_on:
            # The lineage lanes permute/split with the state in the SAME
            # compaction dispatch (the varargs sched group), so
            # provenance attribution travels with the worlds for free.
            (state, idx, slot_sched, l_p1, l_p2, l_ops, l_dep) = \
                _compactor(eng, mesh, w_cur, w_cur, with_sched=True)(
                    state, idx, slot_sched, *slot_lin)
            slot_lin = type(slot_lin)(l_p1, l_p2, l_ops, l_dep)
        elif search_on:
            state, idx, slot_sched = _compactor(
                eng, mesh, w_cur, w_cur, with_sched=True)(
                    state, idx, slot_sched)
        else:
            state, idx = _compactor(eng, mesh, w_cur, w_cur)(state, idx)
        reordered = True
        tail_len = w_cur - n_act
        rows = min(_pow2_at_least(tail_len), _pow2_at_least(w_cur))
        obs_t, idx_t = _tail_observer(eng, mesh, w_cur, rows)(
            state, idx, jnp.int32(n_act))
        take = min(tail_len, n_ids - cursor)
        repl = np.full(w_cur, -1, np.int32)
        repl[n_act:n_act + take] = np.arange(
            cursor, cursor + take, dtype=np.int32)
        cursor += take
        mask = np.zeros(w_cur, bool)
        mask[n_act:n_act + take] = True
        fill_ids = np.maximum(repl, 0)
        sched_t = stats_t = lin_t = op_t = None
        if search_on:
            new_ids = shard_worlds(
                jnp.asarray(fill_ids.astype(np.int32)), mesh)
            if lineage_on:
                # One tail gather covers the schedules AND the lanes
                # (same bucketed program, a wider pytree); it reads the
                # PRE-refill lanes — the retiring worlds' provenance —
                # before the children overwrite them below.
                sched_t, lt1, lt2, lto, ltd = _sched_tail(
                    eng, mesh, w_cur, rows)(
                        (slot_sched,) + tuple(slot_lin), jnp.int32(n_act))
                lin_t = (lt1, lt2, lto, ltd)
                fill_dev = shard_worlds(jnp.asarray(mask), mesh)
                children, child_lin, corpus, op_tab, stats_t = _searcher(
                    eng, mesh, search, w_cur, f_rows)(
                        state, slot_sched, idx, corpus, jnp.int32(n_act),
                        new_ids, fill_dev, slot_lin, op_tab,
                        jnp.int32(search_lin_base))
                op_t = op_tab
                slot_lin = type(slot_lin)(*(
                    jnp.where(jnp.asarray(mask), c, s)
                    for c, s in zip(child_lin, slot_lin)))
            else:
                sched_t = _sched_tail(eng, mesh, w_cur, rows)(
                    slot_sched, jnp.int32(n_act))
                children, corpus, stats_t = _searcher(
                    eng, mesh, search, w_cur, f_rows)(
                        state, slot_sched, idx, corpus, jnp.int32(n_act),
                        new_ids)
            state = shard_worlds(
                form.refill(state, mask, seeds_p[fill_ids],
                            faults=children), mesh)
            slot_sched = jnp.where(
                jnp.asarray(mask)[:, None, None], children, slot_sched)
        else:
            state = shard_worlds(
                form.refill(state, mask, seeds_p[fill_ids],
                            faults=batch_faults(fill_ids)), mesh)
        idx = jnp.where(jnp.asarray(np.arange(w_cur) >= n_act),
                        jnp.asarray(repl), idx)
        return obs_t, idx_t, tail_len, sched_t, stats_t, lin_t, op_t

    def do_shrink(new_w: int):
        """Shrink compaction, fully on device: permutation, split, and
        the live batch's mesh placement all happen inside one jitted
        program (out_shardings = the world sharding). Returns the frozen
        tail's observation handles, un-fetched. Guided sweeps split the
        per-slot schedule array with the state so the frozen tail keeps
        its schedule attribution."""
        nonlocal state, idx, reordered, w_cur, slot_sched, slot_lin
        flin = None
        if search_on and lineage_on:
            ((state, idx, slot_sched, l1, l2, lo_, ld),
             (frozen, fidx, fsched, f1, f2, fo, fd)) = \
                _compactor(eng, mesh, w_cur, new_w, with_sched=True)(
                    state, idx, slot_sched, *slot_lin)
            slot_lin = type(slot_lin)(l1, l2, lo_, ld)
            flin = (f1, f2, fo, fd)
        elif search_on:
            (state, idx, slot_sched), (frozen, fidx, fsched) = \
                _compactor(eng, mesh, w_cur, new_w, with_sched=True)(
                    state, idx, slot_sched)
        else:
            fsched = None
            (state, idx), (frozen, fidx) = \
                _compactor(eng, mesh, w_cur, new_w)(state, idx)
        reordered = True
        tail_len = w_cur - new_w
        w_cur = new_w
        obs_t, idx_t = _observer(eng)(frozen, fidx)
        return obs_t, idx_t, tail_len, fsched, None, flin, None

    def ckpt_aux(cov_pair):
        """Sweep-level aux for a recycled checkpoint, captured at submit
        time — the one point where host cursor/idx/retired are
        consistent with the submitted state (pending retires drained;
        pipelined submits additionally gated on epoch match). Device
        values (idx, ledger) ride as refs the writer thread pulls;
        retired observations as lists it concatenates — the loop thread
        never blocks here."""
        if not recycle:
            return None
        aux: Dict[str, Any] = {"cursor": np.int64(cursor), "idx": idx}
        if cov_pair is not None:
            aux["cov_hits"], aux["cov_first"] = cov_pair
        if retired_rows:
            aux["ret_rows"] = list(retired_rows)
            for k, v in retired.items():
                aux[f"ret_{k}"] = list(v)
        if search_on:
            # Search state rides the same aux channel: per-slot
            # schedules + the whole corpus (device refs the writer
            # thread pulls; consistent with the submitted state because
            # submits are epoch-gated and search state only changes at
            # epoch bumps), plus the retired-schedule attribution.
            aux["srch_sched"] = slot_sched
            aux["srch_c_sched"] = corpus.sched
            aux["srch_c_sig"] = corpus.sig
            aux["srch_c_score"] = corpus.score
            aux["srch_c_filled"] = corpus.filled
            aux["srch_c_gen"] = corpus.gen
            aux["srch_c_inserted"] = corpus.inserted
            aux["srch_c_entry"] = corpus.entry
            aux["srch_c_depth"] = corpus.depth
            if retired_sched:
                aux["srch_ret"] = list(retired_sched)
            if lineage_on:
                # Provenance lanes + outcome table (obs/lineage.py):
                # same epoch-gated consistency argument as slot_sched.
                for k, v in zip(("p1", "p2", "ops", "depth"), slot_lin):
                    aux[f"srch_lin_{k}"] = v
                for k, v in zip(("produced", "novel", "survived"),
                                op_tab):
                    aux[f"srch_op_{k}"] = v
                if retired_lin:
                    for i, k in enumerate(("p1", "p2", "ops", "depth")):
                        aux[f"srch_ret_lin_{k}"] = [t[i]
                                                    for t in retired_lin]
        return aux

    fused_epochs = 0                   # device refill epochs (fused path)
    fused_setup_hit = False            # setup program came from the cache
    fused_k_bucket = 0                 # chunk window per mega-dispatch
    fused_bufs = fused_sched_buf = fused_lin_buf = None
    try:
        if fused:
            # -- whole-hunt fused orchestration (docs/perf.md
            # "Whole-hunt residency"): the occupancy loop lives inside
            # ONE device program; the host's job shrinks to issuing
            # mega-dispatches and mirroring telemetry scalars. ---------
            with tr.span("madsim:upload", "upload_s"):
                rep_sh = NamedSharding(mesh, scalar_spec())
                n_ids_b = _pow2_at_least(n_ids)
                fused_k_bucket = _pow2_at_least(max(min(c_max, _FUSED_K_CAP),
                                                    1))
                # Replicated seed/fault tables the in-loop refill gathers
                # from, bucketed to a power of two: every seed count in a
                # bucket reuses ONE compiled program (the PR 3 zero-
                # recompile contract extended to fused). Rows past n_ids
                # are never gathered (the traced cursor clamps at the real
                # count), so zero/repeat padding is inert. The seed words
                # go up as they are and split into lo/hi on the device,
                # in the cached setup program that also zeroes the
                # per-seed buffers (retiring rows land there INSIDE the
                # loop, live rows at each mega-dispatch boundary, and the
                # host pulls the whole thing ONCE at the end).
                setup, fused_setup_hit = _fused_setup(
                    eng, mesh, state, w=w_cur, n_ids_b=n_ids_b,
                    f_rows=(f_rows if search_on else 0),
                    lineage_on=lineage_on)
                (lo, hi, fused_bufs, fused_sched_buf, fused_lin_buf,
                 cursor_dev, epochs_dev) = setup(
                    _seed_words(seeds_p, n_ids_b).reshape(-1),
                    np.int32(cursor))
                tabs = {"lo": lo, "hi": hi}
                if search_on:
                    fault_mode = "search"
                elif faults_p is None:
                    fault_mode = "none"
                elif per_world_faults:
                    fault_mode = "per_world"
                    ftab = faults_p
                    if n_ids_b > n_ids:
                        ftab = np.concatenate(
                            [ftab, ftab[:1].repeat(n_ids_b - n_ids, axis=0)],
                            axis=0)
                    tabs["faults"] = jax.device_put(
                        np.asarray(ftab, np.int32), rep_sh)
                else:
                    fault_mode = "shared"
                    tabs["faults"] = jax.device_put(
                        np.asarray(faults_p, np.int32), rep_sh)
            runner = _fused_hunt(
                eng, mesh, search, w=w_cur, n_ids_b=n_ids_b,
                f_rows=(f_rows if search_on else 0),
                chunk_steps=chunk_steps, k_bucket=fused_k_bucket,
                cov_k=(cov_k if cov_on else None),
                lineage_on=lineage_on, fault_mode=fault_mode,
                recycle=recycle, fault_path=fault_path)
            stop = False
            first = True
            # "first" forces one dispatch even when max_steps <= 0: a
            # zero-chunk pass still parks the live (init) observations
            # in the buffers, mirroring the serial loop's final
            # observe() of an unstepped batch.
            while first or (chunks < c_max and not stop):
                first = False
                k = max(0, min(fused_k_bucket, c_max - chunks))
                prof.before_dispatch()
                srch_in = ()
                if search_on:
                    srch_in = (slot_sched, corpus, fused_sched_buf)
                    if lineage_on:
                        srch_in += (slot_lin, op_tab, fused_lin_buf)
                with tr.span("madsim:fused_hunt", "dispatch_s"):
                    (state, idx, cursor_dev, epochs_dev, fused_bufs,
                     cov_pair, srch_out, any_bug, n_active, k_done,
                     hist, cov_h, stats_t) = runner(
                        state, idx, cursor_dev, epochs_dev, fused_bufs,
                        ((cov_hits, cov_first) if cov_on else ()),
                        srch_in, tabs, jnp.int32(n_ids), jnp.int32(n),
                        jnp.int32(search_lin_base),
                        jnp.asarray(bool(stop_on_first_bug)),
                        jnp.int32(k))
                if cov_on:
                    cov_hits, cov_first = cov_pair
                if search_on:
                    slot_sched, corpus, fused_sched_buf = srch_out[:3]
                    if lineage_on:
                        slot_lin, op_tab, fused_lin_buf = srch_out[3:]
                # ONE scalar batch per mega-dispatch — the sanctioned
                # mid-hunt read (occupancy telemetry, novelty lane,
                # cursor/epoch mirrors, stop_on_first_bug).
                with tr.span("madsim:wait", "device_wait_s"):
                    (bug_h, n_act_h, k_done_h, hist_h, cur_h, ep_h, cov_np,
                     stats_h) = _fetch(
                        (any_bug, n_active, k_done, hist, cursor_dev,
                         epochs_dev, cov_h if cov_on else None,
                         stats_t if search_on else None))
                prof.after_read()
                with tr.span("madsim:decide", "host_decision_s"):
                    k_done = int(k_done_h)
                    n_act = int(n_act_h)
                    hist_np = np.asarray(hist_h)
                    cov_arr = np.asarray(cov_np) if cov_on else None
                    for j in range(k_done):
                        n_active_hist.append(int(hist_np[j]))
                        n_active_chunk.append(chunks + j)
                        if cov_on:
                            novelty_hist.append(int(cov_arr[j]))
                    chunks += k_done
                    steps = chunks * chunk_steps
                    issued_slot_steps += w_cur * chunk_steps * k_done
                    cursor = int(cur_h)
                    if search_on and int(ep_h) > fused_epochs:
                        # Host mirrors of the corpus telemetry, refreshed
                        # from the LAST device refill's stats — once per
                        # mega-dispatch rather than once per refill (the
                        # per-refill cadence lives on device now; see
                        # docs/observability.md). The operator table is NOT
                        # pulled mid-hunt — its record rows fold at the end.
                        search_host["corpus_size"] = int(stats_h[0])
                        search_host["inserted"] = int(stats_h[1])
                        if lineage_on:
                            search_host["gen"] = int(stats_h[2])
                            search_host["refill_novel"] = int(stats_h[3])
                            search_host["refill_inserted"] = int(stats_h[4])
                        search_host["epochs_on_device"] = int(ep_h)
                        emit_search_point(None)
                    if int(ep_h) > 0:
                        reordered = True
                    fused_epochs = int(ep_h)
                    more_seeds = cursor < n_ids
                    if (n_act == 0 and not more_seeds) or \
                            (stop_on_first_bug and bool(bug_h)):
                        stop = True
                    elif k_done < k:
                        # The device loop exits early only on its stop
                        # predicate; a short count means the predicate
                        # fired on-device — mirror it (the scalars above
                        # necessarily agree, but int rounding of a pulled
                        # bool keeps this branch as the belt to their
                        # suspenders).
                        stop = True
                emit_point(n_act, bool(bug_h), 0)
        elif pipeline:
            # -- pipelined, superstepped orchestration ---------------------
            k_cur = 1                  # adaptive superstep size (chunks)
            epoch = 0                  # bumps on every refill/shrink
            epoch_fresh = True         # next dispatch is its epoch's first
            ckpt_mark = 0              # checkpoint cadence periods covered
            inflight: Optional[_Flight] = None
            pending_retires: list = []
            stop = False

            def threshold() -> int:
                """The on-device early-exit occupancy for the NEXT
                dispatch: the serial loop's trigger boundary (half the
                batch) whenever a refill or shrink could actually fire,
                else 0 (run until all retired). Under a checkpoint
                writer the dry-cursor shrink fallback is disabled (a
                shrunken snapshot could not resume), so the tail runs
                to all-retired at full width."""
                if recycle and cursor < n_ids:
                    return w_cur // 2
                if ((compact or (recycle and writer is None))
                        and w_cur % 2 == 0
                        and (w_cur // 2) % n_dev == 0):
                    return w_cur // 2
                return 0

            def dispatch(reserve: int = 0) -> None:
                """Issue one superstep on the CURRENT state (enqueue
                only — never blocks on device results). ``reserve`` is
                the planned chunk count of a superstep already in the
                device queue but not yet read: those chunks may still
                execute, so the budget must treat them as spent or a
                binding ``max_steps`` overruns the serial loop's
                ``c_max`` chunk ceiling."""
                nonlocal state, inflight, epoch_fresh, cov_hits, cov_first
                budget = c_max - chunks - reserve
                k = max(1, min(k_cur, budget, superstep_max))
                if writer is not None and checkpoint_every_chunks:
                    k = min(k, checkpoint_every_chunks)
                # The first dispatch of each occupancy epoch mirrors the
                # serial cadence exactly: one chunk runs before occupancy
                # is re-evaluated, even if a refill landed at/below the
                # threshold. Speculative dispatches keep min_one=False
                # so a stale one stays a pass-through no-op. K itself is
                # a traced scalar of the (per min_one variant) single
                # compiled runner, not a compile key.
                if epoch_fresh:
                    k = 1
                runner = sharded_superstep(
                    eng, mesh, chunk_steps, superstep_max, donate,
                    min_one=epoch_fresh,
                    coverage=cov_k if cov_on else None,
                    fault_path=fault_path)
                epoch_fresh = False
                prof.before_dispatch()
                with tr.span("madsim:superstep", "dispatch_s"):
                    if cov_on:
                        (state, any_bug, n_active, k_done, hist, cov_hits,
                         cov_first, cov_h, shard_steps) = runner(
                            state, cov_hits, cov_first, idx, n_real_dev,
                            jnp.int32(threshold()),
                            jnp.asarray(bool(stop_on_first_bug)),
                            jnp.int32(k))
                    else:
                        cov_h = None
                        (state, any_bug, n_active, k_done, hist,
                         shard_steps) = runner(
                            state, jnp.int32(threshold()),
                            jnp.asarray(bool(stop_on_first_bug)),
                            jnp.int32(k))
                inflight = _Flight(
                    any_bug, n_active, k_done, hist, shard_steps, k, w_cur,
                    epoch, state if writer is not None else None, cov_h,
                    ((cov_hits, cov_first)
                     if writer is not None and cov_on else None))

            # max_steps <= 0 means a zero-chunk budget: the serial loop
            # never enters its body, so the pipelined loop must not
            # force a min_one first chunk either.
            if c_max > 0:
                dispatch()
            while inflight is not None:
                prev, inflight = inflight, None
                # Dispatch-ahead: superstep k+1 enters the device queue
                # BEFORE superstep k's scalars are read, so the device
                # never idles on host decision latency. If k's scalars
                # turn out to demand a stop/refill, k+1 is a bitwise
                # no-op (its entry condition is already false).
                if not stop and chunks + prev.planned < c_max:
                    dispatch(reserve=prev.planned)
                with tr.span("madsim:wait", "device_wait_s"):
                    # The executed-step count and (coverage on) the novelty
                    # lane ride the SAME scalar batch — one _fetch per
                    # superstep either way (tier-1-counted).
                    bug_h, n_act_h, k_done_h, hist_h, ran_h, cov_h = _fetch(
                        (prev.any_bug, prev.n_active, prev.k_done,
                         prev.hist, prev.shard_steps, prev.cov_hist))
                prof.after_read()
                perf["dispatch_depth"] = max(
                    perf["dispatch_depth"], 1 if inflight is not None else 0)
                # Retirement pulls deferred from earlier refills/shrinks:
                # drain them here, where the loop blocks anyway.
                while pending_retires:
                    fetch_retire(pending_retires.pop(0))
                with tr.span("madsim:decide", "host_decision_s"):
                    k_done = int(k_done_h)
                    n_act = int(n_act_h)
                    hist_np = np.asarray(hist_h)
                    cov_np = np.asarray(cov_h) if cov_on else None
                    for j in range(k_done):
                        n_active_hist.append(int(hist_np[j]))
                        n_active_chunk.append(chunks + j)
                        if cov_on:
                            novelty_hist.append(int(cov_np[j]))
                    chunks += k_done
                    steps = chunks * chunk_steps
                    ran = prev.w // n_dev * int(ran_h)
                    issued_slot_steps += ran
                    skipped_slot_steps += prev.w * chunk_steps * k_done - ran
                    if prev.epoch == epoch:
                        # Superstep sizing adapts to the observed retirement
                        # rate: double while supersteps run to plan (slow
                        # start), and after an early exit settle on the
                        # chunks it actually ran — the measured
                        # chunks-per-decision of this workload. Deterministic
                        # — every input is a sim output; and since K is a
                        # traced scalar, the schedule costs no recompiles.
                        if k_done == prev.planned:
                            k_cur = min(k_cur * 2, superstep_max)
                        else:
                            k_cur = max(k_done, 1)
                    if writer is not None and checkpoint_every_chunks and \
                            prev.epoch == epoch and \
                            chunks // checkpoint_every_chunks > ckpt_mark:
                        # Async: the pull + write overlap later supersteps'
                        # device work; the submitted state is a COMPLETED
                        # superstep output (donation is off with a writer).
                        # Epoch-gated: a stale pass-through superstep's state
                        # predates the refill the host idx/cursor already
                        # reflect — submitting it would tear the snapshot
                        # (the current epoch's next superstep submits soon).
                        writer.submit(prev.out_state, ckpt_aux(prev.out_cov))
                        submitted_chunks = chunks
                        ckpt_mark = chunks // checkpoint_every_chunks
                    if prev.epoch == epoch and not stop:
                        more_seeds = cursor < n_ids
                        if n_act == 0 and not more_seeds:
                            stop = True
                        elif stop_on_first_bug and bool(bug_h):
                            stop = True
                        elif recycle and more_seeds and n_act <= w_cur // 2:
                            pending_retires.append(do_refill(n_act))
                            epoch += 1
                            epoch_fresh = True
                        else:
                            new_w = _compact_bucket(n_act, w_cur, n_dev)
                            # Dry-cursor shrink only without a writer: every
                            # snapshot written must stay full-shape-resumable.
                            if (compact or (recycle and not more_seeds
                                            and writer is None)) \
                                    and new_w < w_cur:
                                pending_retires.append(do_shrink(new_w))
                                epoch += 1
                                epoch_fresh = True
                emit_point(n_act, bool(bug_h),
                           1 if inflight is not None else 0)
                if stop:
                    break
                if inflight is None and chunks < c_max:
                    dispatch()
            while pending_retires:
                fetch_retire(pending_retires.pop(0))
        else:
            # -- serial per-chunk reference loop ---------------------------
            runner = sharded_engine(eng, mesh, chunk_steps, donate=donate,
                                    coverage=cov_k if cov_on else None,
                                    fault_path=fault_path)
            while steps < max_steps:
                prof.before_dispatch()
                with tr.span("madsim:chunk", "dispatch_s"):
                    if cov_on:
                        (state, any_bug, n_active, cov_hits, cov_first,
                         distinct, shard_steps) = runner(
                            state, cov_hits, cov_first, idx, n_real_dev)
                    else:
                        distinct = None
                        state, any_bug, n_active, shard_steps = runner(state)
                steps += chunk_steps
                chunks += 1
                if writer is not None and checkpoint_every_chunks and \
                        chunks % checkpoint_every_chunks == 0:
                    # Async: the pull + write overlap the next chunk's
                    # device work; the loop never blocks on the filesystem.
                    writer.submit(state, ckpt_aux(
                        (cov_hits, cov_first) if cov_on else None))
                    submitted_chunks = chunks
                with tr.span("madsim:wait", "device_wait_s"):
                    n_act_h, bug_h, ran_h, dist_h = _fetch(
                        (n_active, any_bug, shard_steps, distinct))
                prof.after_read()
                n_act = int(n_act_h)
                ran = w_cur // n_dev * int(ran_h)
                issued_slot_steps += ran
                skipped_slot_steps += w_cur * chunk_steps - ran
                if cov_on:
                    novelty_hist.append(int(dist_h))
                emit_point(n_act, bool(bug_h), 0)
                handles = None
                with tr.span("madsim:decide", "host_decision_s"):
                    n_active_hist.append(n_act)
                    n_active_chunk.append(chunks - 1)
                    more_seeds = cursor < n_ids
                    stop = (n_act == 0 and not more_seeds) or \
                        (stop_on_first_bug and bool(bug_h))
                    if not stop and recycle and more_seeds \
                            and n_act <= w_cur // 2:
                        handles = do_refill(n_act)
                    elif not stop:
                        new_w = _compact_bucket(n_act, w_cur, n_dev)
                        if (compact or (recycle and not more_seeds
                                        and writer is None)) \
                                and new_w < w_cur:
                            handles = do_shrink(new_w)
                if stop:
                    break
                if handles is not None:
                    fetch_retire(handles)
        if writer is not None and submitted_chunks != chunks:
            # The final state is always durable.
            writer.submit(state, ckpt_aux(
                (cov_hits, cov_first) if cov_on else None))
        if writer is not None:
            writer.flush_and_close()
            writer = None
    finally:
        prof.close()  # idempotent; stops a capture left open by an error
        if writer is not None:  # exception path: don't mask it
            writer.flush_and_close(suppress_errors=True)

    if cov_on:
        # End-of-sweep fold: worlds still live at exit (max_steps /
        # stop_on_first_bug truncation) contribute their partial-behavior
        # signatures, so distinct_behaviors accounts every admitted seed
        # exactly once. Identical between loops: both exit on the same
        # state (tier-1 bitwise contract).
        cov_hits, cov_first = _cov_endfolder(eng, mesh)(
            state, cov_hits, cov_first, idx, n_real_dev, jnp.asarray(True))

    sched_live_h = corpus_h = lin_live_h = op_tab_h = None
    sched_per_seed = lin_per_seed = None
    if fused:
        # Fused final read: retired AND live observations already sit in
        # the per-seed device buffers (retiring rows landed inside the
        # loop, live rows at the last mega-dispatch boundary), so the
        # whole result crosses in ONE pull — the "pulled once at the
        # end" half of the fused contract. Everything below is host
        # slicing of bucket padding.
        with tr.span("madsim:pull", "retire_wait_s"):
            (bufs_h, cov_pack_h, sched_b_h, corpus_h, lin_b_h,
             op_tab_h) = _fetch(
                (fused_bufs, (cov_hits, cov_first) if cov_on else None,
                 fused_sched_buf, corpus, fused_lin_buf, op_tab))
        perf["retire_fetches"] += 1
    else:
        with tr.span("madsim:pull", "retire_wait_s"):
            obs_live = eng.observe(state)
            if cov_on and search_on:
                # Search state rides the final ledger pull — still ONE
                # _fetch.
                (idx_h, cov_hits_h, cov_first_h, sched_live_h, corpus_h,
                 lin_live_h, op_tab_h) = _fetch(
                    (idx, cov_hits, cov_first, slot_sched, corpus,
                     slot_lin, op_tab))
            elif cov_on:
                # The ledger rides the final slot-index pull — still ONE
                # _fetch.
                idx_h, cov_hits_h, cov_first_h = _fetch(
                    (idx, cov_hits, cov_first))
            else:
                idx_h = _fetch(idx)
    with tr.span("madsim:assemble", "assemble_s"):
        if fused:
            if cov_on:
                cov_hits_h, cov_first_h = (np.asarray(x) for x in cov_pack_h)
            obs = {k: np.asarray(v)[:n_ids] for k, v in bufs_h.items()}
            live_world_steps += int(np.asarray(obs["steps"]).sum())
            if search_on:
                sched_per_seed = np.asarray(sched_b_h, np.int32)[:n_ids]
            if lineage_on:
                lin_per_seed = tuple(np.asarray(a, np.int32)[:n_ids]
                                     for a in lin_b_h)
        else:
            live_keep = idx_h >= 0
            live_world_steps += int(
                np.asarray(obs_live["steps"])[live_keep].sum())
            # Scatter whenever the live batch does not cover the full id
            # space in seed order — after any reorder/retirement, OR when a
            # recycled sweep exited (stop_on_first_bug / max_steps) before
            # its first refill, so only the first w0 < n_ids seeds were
            # ever admitted.
            if reordered or retired_rows or w0 < n_ids:
                rows = np.concatenate(retired_rows + [idx_h[live_keep]])
                obs = {}
                for k, v_live in obs_live.items():
                    v_live = np.asarray(v_live)[live_keep]
                    merged = np.concatenate(retired.get(k, []) + [v_live],
                                            axis=0)
                    # Zeros, not empty: an early stop (stop_on_first_bug)
                    # can leave streamed seeds never admitted — they report
                    # zeroed observations (bug=False) rather than garbage.
                    out = np.zeros((n_ids,) + merged.shape[1:], merged.dtype)
                    out[rows] = merged
                    obs[k] = out
                if search_on:
                    merged_s = np.concatenate(
                        retired_sched + [sched_live_h[live_keep]], axis=0)
                    sched_out = np.full((n_ids,) + merged_s.shape[1:], -1,
                                        np.int32)
                    sched_out[:, :, 1:] = 0  # canonical DISABLED_ROW padding
                    sched_out[rows] = merged_s
                    sched_per_seed = sched_out
                if lin_live_h is not None:
                    # Per-seed lineage lanes scatter exactly like the
                    # schedules; never-admitted seeds read as generation 0
                    # (-1 parents, no operators, depth 0).
                    lanes_out = []
                    for i, dflt in enumerate((-1, -1, 0, 0)):
                        merged_l = np.concatenate(
                            [t[i] for t in retired_lin]
                            + [lin_live_h[i][live_keep]], axis=0)
                        out = np.full((n_ids,), dflt, np.int32)
                        out[rows] = np.asarray(merged_l, np.int32)
                        lanes_out.append(out)
                    lin_per_seed = tuple(lanes_out)
            else:
                obs = obs_live
                if search_on:
                    sched_per_seed = sched_live_h
                if lin_live_h is not None:
                    lin_per_seed = tuple(np.asarray(a, np.int32)
                                         for a in lin_live_h)
        obs = {k: v[:n] for k, v in obs.items()}
        if sched_per_seed is not None:
            sched_per_seed = sched_per_seed[:n]
        if lin_per_seed is not None:
            lin_per_seed = tuple(a[:n] for a in lin_per_seed)
        util = (live_world_steps / issued_slot_steps if issued_slot_steps
                else 0.0)
        n_disp = tr.entered["madsim:fused_hunt" if fused else
                            "madsim:superstep" if pipeline else "madsim:chunk"]
        loop_stats = {
            "pipelined": bool(pipeline) and not fused,
            "fused": bool(fused),
            "superstep_max": (int(fused_k_bucket) if fused
                              else int(superstep_max) if pipeline else 1),
            "chunk_steps": int(chunk_steps),
            "chunks": int(chunks),
            # Slot-steps the chunks' exit on all-frozen shards left out
            # (docs/perf.md "Chunk exit on frozen shards"); 0 when fused.
            "slot_steps_skipped": int(skipped_slot_steps),
            "dispatches": n_disp,
            "chunks_per_dispatch": round(chunks / max(n_disp, 1), 3),
            "dispatches_per_seed": round(n_disp / max(n, 1), 6),
            # The fused headline (and its reciprocal): how many seeds one
            # host dispatch retires end to end. epochs_on_device counts the
            # refill epochs that ran INSIDE fused mega-dispatches (0 on the
            # host-orchestrated paths, where every epoch is its own
            # dispatch).
            "seeds_per_dispatch": round(n / max(n_disp, 1), 3),
            "epochs_on_device": int(fused_epochs),
            # Fused setups served by the engine's cached setup program (no
            # eval_shape, no trace), and hashes of the seed array
            # (madsim:identity, checkpointed sweeps only).
            "fused_setup_cache_hits": int(fused_setup_hit),
            "identity_hashes": tr.entered["madsim:identity"],
            # 1 when the step ran its fault path, 0 when no world could
            # hold a fault event (DeviceEngine._form).
            "fault_path": int(fault_path),
            "dispatch_depth": int(perf["dispatch_depth"]),
            "scalar_fetches": tr.entered["madsim:wait"],
            "retire_fetches": int(perf["retire_fetches"]),
            "loop_wall_s": round(tr.clock() - t_loop0, 6),
        }
        coverage = (coverage_from_device(cov_k, cov_hits_h, cov_first_h,
                                         novelty_hist) if cov_on else None)
        search_report = None
        triage_faults = faults
        if search_on:
            from ..search import SearchReport

            lineage_rep = op_stats = None
            if lin_per_seed is not None:
                from ..obs.lineage import (
                    N_OPS,
                    SearchLineage,
                    host_credit,
                    operator_stats,
                )

                lineage_rep = SearchLineage(
                    parent1=lin_per_seed[0], parent2=lin_per_seed[1],
                    ops=lin_per_seed[2], depth=lin_per_seed[3],
                    entry_base=int(search_lin_base))
                # Bug credit folds HOST-side over the per-seed lanes: a find
                # that halted the sweep (or sat live at exit) never crossed
                # a harvest edge, so only this fold counts every find
                # exactly once (obs/lineage.py OperatorTable).
                op_bug = host_credit(np.zeros(N_OPS, np.int32),
                                     lineage_rep.ops,
                                     np.asarray(obs["bug"], bool))
                op_stats = operator_stats(*(tuple(op_tab_h) + (op_bug,)))
            c_filled = np.asarray(corpus_h.filled, bool)
            search_report = SearchReport(
                # Generations THIS sweep ran: the epoch stream offset
                # (search_gen0) is a key-space shift, not work done here.
                generations=int(np.asarray(corpus_h.gen)) - int(search_gen0),
                inserted=int(np.asarray(corpus_h.inserted)),
                corpus_size=int(c_filled.sum()),
                corpus_capacity=int(c_filled.shape[0]),
                corpus_sched=np.asarray(corpus_h.sched, np.int32),
                corpus_sig=np.asarray(corpus_h.sig, np.uint32),
                corpus_score=np.asarray(corpus_h.score, np.int32),
                corpus_filled=c_filled,
                schedules=sched_per_seed,
                corpus_entry=np.asarray(corpus_h.entry, np.int32),
                corpus_depth=np.asarray(corpus_h.depth, np.int32),
                lineage=lineage_rep,
                operator_stats=op_stats,
            )
            # Triage sees the MATERIALIZED per-seed schedules: a guided
            # find's minimize/triage path re-executes the child schedule
            # the world actually ran, not the template.
            triage_faults = sched_per_seed
        result = SweepResult(seeds=seeds, bug=obs["bug"], observations=obs,
                             steps_run=steps, n_devices=n_dev,
                             n_active_history=np.asarray(n_active_hist,
                                                         np.int64),
                             world_utilization=util,
                             n_active_chunks=np.asarray(n_active_chunk,
                                                        np.int64),
                             loop_stats=loop_stats,
                             faults_sha256=(faults_sha256
                                            if faults is not None else None),
                             coverage=coverage,
                             search=search_report,
                             triage_ctx=TriageContext(engine=eng,
                                                      faults=triage_faults,
                                                      mesh=mesh))
    loop_stats.update(tr.seconds())
    if emit_telemetry is not None:
        final = {
            # /2: seeds_per_dispatch + epochs_on_device surfaced top-
            # level (additive — docs/observability.md "Schema history").
            "schema": "madsim.sweep.telemetry/2",
            "event": "summary",
            "elapsed_s": loop_stats["loop_wall_s"],
            "seeds_total": int(n),
            "failing_seeds": len(result.failing_seeds),
            "world_utilization": round(util, 4),
            # Dispatch economics, surfaced TOP-LEVEL (schema /2 —
            # docs/observability.md): the Prometheus renderer exports
            # only top-level numerics, and these two are the fused
            # path's headline gauges. Duplicated from loop_stats, where
            # the full breakdown still lives.
            "seeds_per_dispatch": loop_stats["seeds_per_dispatch"],
            "epochs_on_device": loop_stats["epochs_on_device"],
            "loop_stats": loop_stats,
        }
        if coverage is not None:
            final["coverage"] = coverage.to_json()
        if search_report is not None:
            final["search"] = search_report.to_json()
            if search_report.lineage is not None and result.failing_seeds:
                # The finds' full derivations ride the summary record
                # (capped — a hunt's first few finds, not the seed
                # space), so `python -m madsim_tpu.obs lineage
                # <stream>` can render ancestry without the SweepResult.
                from ..obs.lineage import lineage_block

                rows = np.flatnonzero(np.asarray(result.bug))[:8]
                final["search"]["finds"] = [
                    lineage_block(search_report.lineage, int(r),
                                  seeds=np.asarray(result.seeds))
                    for r in rows]
        emit_telemetry(final)
    if close_telemetry is not None:
        close_telemetry()
    return result


def _compact_bucket(n_active: int, w_cur: int, n_dev: int) -> int:
    """Largest power-of-two shrink of ``w_cur`` that still holds every
    active world and stays a multiple of the mesh; ``w_cur`` when no
    halving is possible (compaction triggers only below half-occupancy)."""
    w = w_cur
    # w//2 % n_dev == 0 already implies the w//2 >= n_dev floor (any
    # positive value below n_dev fails the modulus test).
    while w % 2 == 0 and w // 2 >= max(n_active, 1) and w // 2 % n_dev == 0:
        w //= 2
    return w


def _pow2_at_least(n: int) -> int:
    """Smallest power of two >= n (>= 1): bucketed retirement-gather
    widths, so the tail observer compiles at most log2(W) programs."""
    b = 1
    while b < n:
        b <<= 1
    return b


def _seed_words(seeds, n_b: int) -> np.ndarray:
    """The u64 seeds as an ``(n_b, 2)`` table of little-endian u32 words,
    ``[:, 0]`` the low word and ``[:, 1]`` the high one, zero rows past
    ``len(seeds)``: the ``& 0xFFFFFFFF`` / ``>> 32`` split without a pass
    over the seeds. A view of the seed array (no copy) when it is
    contiguous native u64 on a little-endian host and ``n_b`` adds no
    rows."""
    words = np.ascontiguousarray(seeds, dtype="<u8").view("<u4")
    words = words.reshape(-1, 2)
    if words.shape[0] == n_b:
        return words
    table = np.zeros((n_b, 2), "<u4")
    table[:words.shape[0]] = words
    return table


def _fused_setup(eng: DeviceEngine, mesh: Mesh, state, *, w: int,
                 n_ids_b: int, f_rows: int, lineage_on: bool):
    """Compile (and cache per engine) the fused hunt's setup program;
    returns ``(setup, cache_hit)``.

    ``setup(words, cursor)`` splits the :func:`_seed_words` table, flat
    (a ``(n, 2)`` array would pad its minor axis to the TPU's 128 lanes),
    into the refill's ``lo``/``hi`` seed tables and builds every zeroed
    per-seed buffer (observations, and with search the schedules and
    lineage lanes; one trailing dump row each) and the cursor/epoch
    scalars, all mesh-replicated, in one dispatch. The buffer shapes
    come from one ``eval_shape`` of ``eng.observe_device`` per engine
    and geometry, so a later hunt in the same bucket traces nothing.
    """
    cache = eng.__dict__.setdefault("_fused_setup_cache", {})
    key = (mesh, w, n_ids_b, f_rows, lineage_on)
    if key in cache:
        return cache[key], True

    from ..obs.lineage import lanes_buffer

    obs_shapes = jax.eval_shape(eng.observe_device, state)

    def setup(words, cursor):
        bufs = {k: jnp.zeros((n_ids_b + 1,) + tuple(sh.shape[1:]), sh.dtype)
                for k, sh in obs_shapes.items()}
        sched_buf = lin_buf = None
        if f_rows:
            # Canonical disabled-row padding: time -1, op/a/b 0.
            sched_buf = jnp.zeros((n_ids_b + 1, f_rows, 4),
                                  jnp.int32).at[:, :, 0].set(-1)
        if lineage_on:
            lin_buf = lanes_buffer(n_ids_b)
        lo = jax.lax.slice(words, (0,), (2 * n_ids_b,), (2,))
        hi = jax.lax.slice(words, (1,), (2 * n_ids_b,), (2,))
        return (lo, hi, bufs, sched_buf, lin_buf,
                jnp.asarray(cursor, jnp.int32), jnp.int32(0))

    rep = NamedSharding(mesh, scalar_spec())
    fn = jax.jit(setup, in_shardings=rep, out_shardings=rep)
    cache[key] = fn
    return fn, False


@jax.jit
def _permute_worlds(state, perm):
    """Reorder the world axis of a whole state pytree on device."""
    return jax.tree.map(lambda x: x[perm], state)


def _compactor(eng: DeviceEngine, mesh: Mesh, w: int, new_w: int,
               with_sched: bool = False):
    """Compile (and cache per engine) the on-device compaction program.

    The program computes the stable active-first permutation of a
    width-``w`` batch with ``jnp.argsort`` ON DEVICE, applies it to the
    state and the slot→seed index vector via :func:`_permute_worlds`, and
    (for ``new_w < w``) splits off the frozen tail. ``out_shardings``
    pins every output to the mesh's world sharding, so compaction needs
    no host pull of ``state.active``, no host-built permutation, and no
    ``device_put`` reshard afterwards — the host contributes only the
    ``n_active`` scalar the chunk runner already returned. Shrink widths
    are power-of-two buckets, so at most log2(W) programs compile.

    ``with_sched`` (guided sweeps, search/): the program additionally
    permutes/splits the per-slot ``(W, F, 4)`` schedule array in the
    same dispatch, so schedule attribution travels with the worlds.
    A distinct cache key — ``search=None`` sweeps compile the exact
    pre-search program (tier-1, tests/test_search.py).

    Deliberately NOT donated: the permutation is a gather, whose output
    XLA can never alias onto its input (an in-place permute would read
    clobbered rows), so donating here frees nothing and trips the
    "donated buffer not usable" warning on every leaf. Compaction
    transiently holds two batches; the chunk runner — where the state
    lives 99% of the time — is the donated path.
    """
    cache = eng.__dict__.setdefault("_compactor_cache", {})
    key = (mesh, w, new_w, with_sched)
    if key in cache:
        return cache[key]

    def compacted(state, idx, *sched):
        order = jnp.argsort((~state.active).astype(jnp.int32), stable=True)
        group = (state, idx) + sched
        group = _permute_worlds(group, order)
        if new_w == w:
            return group
        live = jax.tree.map(lambda x: x[:new_w], group)
        frozen = jax.tree.map(lambda x: x[new_w:], group)
        return live, frozen

    fn = jax.jit(compacted, out_shardings=world_sharding(mesh))
    cache[key] = fn
    return fn


def _sched_tail(eng: DeviceEngine, mesh: Mesh, w: int, rows: int):
    """Compile (and cache per engine) the frozen-tail schedule gather —
    the :func:`_tail_observer` twin for the guided sweep's per-slot
    ``(W, F, 4)`` schedule array, sharing its bucketed-``rows`` compile
    bound and its clamp-and-slice contract. Accepts any pytree of
    ``(W, ...)`` arrays: with lineage on the sweep passes ``(sched,
    *LineageLanes)`` so the provenance lanes ride the SAME gather
    dispatch as the schedules."""
    cache = eng.__dict__.setdefault("_sched_tail_cache", {})
    key = (mesh, w, rows)
    if key in cache:
        return cache[key]

    def tail(group, start):
        take = jnp.clip(start + jnp.arange(rows, dtype=jnp.int32), 0, w - 1)
        return jax.tree.map(lambda x: jnp.take(x, take, axis=0), group)

    fn = jax.jit(tail)
    cache[key] = fn
    return fn


def _tail_observer(eng: DeviceEngine, mesh: Mesh, w: int, rows: int):
    """Compile (and cache per engine) the frozen-tail retirement gather.

    One jitted program slices ``rows`` observation rows starting at a
    dynamic ``start`` out of a width-``w`` batch — gathering INSIDE the
    device program via ``DeviceEngine.observe_device`` — so retirement
    pulls only the (bucketed) frozen-tail rows across the host boundary
    instead of the full per-world observation arrays. ``rows`` is a
    power-of-two bucket (bounded compiles); indices past the batch clamp
    to the last row and the caller slices the pull to the true tail
    length. The slot→seed index vector rides the same gather so
    attribution needs no second pull.
    """
    cache = eng.__dict__.setdefault("_tail_observer_cache", {})
    key = (mesh, w, rows)
    if key in cache:
        return cache[key]

    def tail(state, idx, start):
        take = jnp.clip(start + jnp.arange(rows, dtype=jnp.int32), 0, w - 1)
        obs = {k: jnp.take(v, take, axis=0)
               for k, v in eng.observe_device(state).items()}
        return obs, jnp.take(idx, take, axis=0)

    fn = jax.jit(tail)
    cache[key] = fn
    return fn


def _observer(eng: DeviceEngine):
    """Cached jit of ``observe_device`` for an already-split frozen batch
    (the shrink-compaction tail): builds the observation dict on device
    so the host pull covers exactly the retiring rows."""
    fn = eng.__dict__.get("_observer_fn")
    if fn is None:
        fn = jax.jit(lambda s, i: (eng.observe_device(s), i))
        eng.__dict__["_observer_fn"] = fn
    return fn


# Ceiling on the fused program's static per-dispatch chunk window (the
# hist-buffer width): every realistic hunt fits one mega-dispatch, and a
# ludicrous max_steps re-dispatches instead of compiling a huge history
# buffer. 4096 i32 entries = 16 KiB per lane — noise next to the state.
_FUSED_K_CAP = 4096


def _fused_hunt(eng: DeviceEngine, mesh: Mesh, scfg, *, w: int,
                n_ids_b: int, f_rows: int, chunk_steps: int,
                k_bucket: int, cov_k: Optional[int], lineage_on: bool,
                fault_mode: str, recycle: bool, fault_path: bool = True):
    """Compile (and cache per engine) the whole-hunt fused program.

    One plain-``jit`` dispatch runs the ENTIRE occupancy loop the serial
    sweep ran on host: chunk bodies under
    ``DeviceEngine._fused_superstep_impl``, and — inside the same
    ``lax.while_loop``, behind a ``lax.cond`` epoch trigger — the stable
    active-first compaction (the ``_compactor`` permutation), the
    retiring-tail scatter into per-seed observation buffers, the
    coverage fold, the guided harvest+generate
    (``search.generate_body``, the SAME callable the ``searcher``
    program jits), the in-loop refill (``DeviceEngine.refill_traced``)
    and the device-resident seed-cursor advance. Like ``_compactor``
    this is a plain ``jax.jit`` with mesh-pinned ``out_shardings`` (the
    global stable argsort cannot live under ``shard_map``); GSPMD
    partitions the loop body, and integer full-axis reductions equal
    the shard_mapped psums bitwise.

    Bit-exactness contract (tier-1: tests/test_fused.py): chunk bodies,
    the permutation, the harvest mask/order, the mutation streams and
    the refill init are all the exact programs/callables of the serial
    path evaluated on equal values, so ids, observations, m_* metrics,
    the coverage ledger, lineage lanes and the SearchReport are bitwise
    identical to ``fused=False``. The ONLY deliberate divergence is the
    dry-cursor shrink: contract surfaces are shrink-invariant, so the
    fused tail just runs at full width (``world_utilization`` is
    telemetry and may differ — docs/perf.md "Whole-hunt residency").

    Static geometry: ``w`` slots, ``n_ids_b`` power-of-two-bucketed
    seed-id space (+1 dump row on every per-seed buffer), ``k_bucket``
    history width per mega-dispatch. The real ``n_ids``/``n`` ride as
    traced scalars, so every seed count in a bucket reuses ONE compiled
    program (the PR 3 zero-recompile contract extended to fused).
    ``fault_mode``: ``search`` (children), ``per_world`` (gather the
    replicated table), ``shared`` (broadcast the template) or ``none``.
    The phases' ops carry ``jax.named_scope`` names (``madsim/compact``
    and kin, docs/observability.md) for the device trace.
    ``fault_path`` as in :func:`sharded_engine`.
    """
    cache = eng.__dict__.setdefault("_fused_hunt_cache", {})
    key = (mesh, w, n_ids_b, f_rows, chunk_steps, k_bucket, cov_k,
           scfg, lineage_on, fault_mode, recycle, fault_path)
    if key in cache:
        return cache[key]
    form = eng._form(fault_path)

    from ..obs.coverage import distinct_count, fold_retired_local

    search_on = scfg is not None
    cov_on = cov_k is not None
    if search_on:
        from ..search.generate import generate_body, generate_body_lineage

        gen_fn = (generate_body_lineage(eng.cfg, scfg, w) if lineage_on
                  else generate_body(eng.cfg, scfg, w))

    rep = NamedSharding(mesh, scalar_spec())
    ws = world_sharding(mesh)
    dump = jnp.int32(n_ids_b)         # trailing dump row of every buffer
    rows_r = jnp.arange(w, dtype=jnp.int32)

    def refill_epoch(s, ex, n_act, tabs, n_ids_real, lin_base):
        # (1) Stable active-first compaction — the _compactor program's
        # exact permutation, applied to the state, the slot→seed index
        # and (guided) the schedule/lane arrays in lockstep.
        with jax.named_scope("madsim/compact"):
            order = jnp.argsort((~s.active).astype(jnp.int32), stable=True)
            perm = (s, ex["idx"])
            if search_on:
                perm = perm + (ex["sched"],)
            if lineage_on:
                perm = perm + (ex["lin"],)
            perm = jax.tree.map(lambda x: x[order], perm)
            s, idx = perm[0], perm[1]
            sched = perm[2] if search_on else None
            lin = perm[3] if lineage_on else None
        # (2) Retiring-tail harvest: scatter the frozen rows' final
        # observations by slot→seed idx into the per-seed buffers (the
        # serial loop's retire() attribution, kept on device). Dead
        # slots (idx < 0, dry-cursor leftovers already harvested) land
        # on the dump row.
        with jax.named_scope("madsim/harvest"):
            tail = (rows_r >= n_act) & (idx >= 0)
            tgt = jnp.where(tail, idx, dump)
            obs = eng.observe_device(s)
            ex = dict(ex, idx=idx)
            ex["bufs"] = {k: ex["bufs"][k].at[tgt].set(obs[k])
                          for k in ex["bufs"]}
        # (3) Admit the next seeds from the device-resident cursor —
        # the same take/repl/mask arithmetic do_refill ran on host.
        with jax.named_scope("madsim/generate"):
            take = jnp.minimum(jnp.int32(w) - n_act,
                               n_ids_real - ex["cursor"])
            fill = (rows_r >= n_act) & (rows_r < n_act + take)
            repl = jnp.where(fill, ex["cursor"] + rows_r - n_act,
                             jnp.int32(-1))
            fill_ids = jnp.maximum(repl, 0)
            if search_on:
                # Park the retiring schedules (and provenance lanes) BEFORE
                # the children overwrite them — the pre-refill read order of
                # the serial _sched_tail gather.
                ex["sched_buf"] = ex["sched_buf"].at[tgt].set(sched)
                if lineage_on:
                    ex["lin_buf"] = jax.tree.map(
                        lambda b, v: b.at[tgt].set(v), ex["lin_buf"], lin)
                    (children, child_lin, ex["corpus"], ex["op_tab"],
                     ex["stats"]) = gen_fn(
                        s, sched, idx, ex["corpus"], n_act, fill_ids, fill,
                        lin, ex["op_tab"], lin_base)
                    ex["lin"] = jax.tree.map(
                        lambda c, o: jnp.where(fill, c, o), child_lin, lin)
                else:
                    children, ex["corpus"], ex["stats"] = gen_fn(
                        s, sched, idx, ex["corpus"], n_act, fill_ids)
                f_new = children
                ex["sched"] = jnp.where(fill[:, None, None], children, sched)
            elif fault_mode == "per_world":
                f_new = tabs["faults"][fill_ids]
            elif fault_mode == "shared":
                f_new = jnp.broadcast_to(tabs["faults"],
                                         (w,) + tabs["faults"].shape)
            else:
                f_new = jnp.zeros((w, 0, 4), jnp.int32)
        # (4) Re-key the refilled slots: the traced twin of
        # DeviceEngine.refill (same vmapped _init_one, same select).
        with jax.named_scope("madsim/rekey"):
            s = form.refill_traced(s, fill, tabs["lo"][fill_ids],
                                   tabs["hi"][fill_ids], f_new)
            ex["idx"] = jnp.where(rows_r >= n_act, repl, idx)
            ex["cursor"] = ex["cursor"] + take
            ex["epochs"] = ex["epochs"] + jnp.int32(1)
        return s, ex

    def run(state, idx, cursor, epochs, bufs, cov, srch, tabs,
            n_ids_real, n_real, lin_base, stop_on_bug, k_chunks):
        n_ids_real = jnp.asarray(n_ids_real, jnp.int32)
        n_real = jnp.asarray(n_real, jnp.int32)
        lin_base = jnp.asarray(lin_base, jnp.int32)
        stop_on_bug = jnp.asarray(stop_on_bug, bool)

        ex = {"idx": idx, "cursor": jnp.asarray(cursor, jnp.int32),
              "epochs": jnp.asarray(epochs, jnp.int32), "bufs": bufs}
        if cov_on:
            ex["cov"] = cov
            ex["cov_hist"] = jnp.full((k_bucket,), -1, jnp.int32)
        if search_on:
            ex["sched"], ex["corpus"], ex["sched_buf"] = srch[:3]
            if lineage_on:
                ex["lin"], ex["op_tab"], ex["lin_buf"] = srch[3:]
            ex["stats"] = tuple(jnp.int32(0)
                                for _ in range(5 if lineage_on else 2))

        def more_seeds(cursor):
            if not recycle:
                return jnp.asarray(False)
            return cursor < n_ids_real

        def entry_stop(ex, any_bug0, n_active0):
            # The pass-through property: a dispatch against a finished
            # hunt runs zero chunks, like the plain superstep's.
            return ((stop_on_bug & any_bug0)
                    | ((n_active0 == 0) & ~more_seeds(ex["cursor"])))

        def post_chunk(s, ex, act0, any_bug, n_active, i):
            if cov_on:
                with jax.named_scope("madsim/coverage_fold"):
                    hits, first = ex["cov"]
                    fmask = (act0 & ~s.active & (ex["idx"] >= 0)
                             & (ex["idx"] < n_real))
                    hits, first = fold_retired_local(hits, first, s.metrics,
                                                     fmask, ex["idx"])
                    ex = dict(ex, cov=(hits, first))
                    ex["cov_hist"] = jax.lax.dynamic_update_index_in_dim(
                        ex["cov_hist"], distinct_count(hits), i, 0)
            # The serial loop's exact decision order: hunt-over checks
            # first (a bug under stop_on_bug, or nothing active with a
            # dry cursor), THEN the refill trigger — a stop never
            # refills, a refill always runs one chunk before the next
            # evaluation (the body re-enters through the chunk).
            more = more_seeds(ex["cursor"])
            stop = ((n_active == 0) & ~more) | (stop_on_bug & any_bug)
            if recycle:
                trigger = ((~stop) & more
                           & (n_active <= jnp.int32(w // 2)))
                s, ex = jax.lax.cond(
                    trigger,
                    lambda op: refill_epoch(op[0], op[1], n_active, tabs,
                                            n_ids_real, lin_base),
                    lambda op: op,
                    (s, ex))
            return s, ex, stop

        state, ex, any_bug, n_active, k_done, hist = \
            form._fused_superstep_impl(
                state, ex, stop_on_bug, k_chunks,
                chunk_steps=chunk_steps, k_max=k_bucket,
                post_chunk=post_chunk, entry_stop=entry_stop)

        # End-of-dispatch: park the LIVE slots' rows (never-retired and
        # dry-tail worlds alike) so the host's single end-of-hunt pull
        # is one buffer slice. Later dispatches overwrite with newer
        # values; retire-time scatters of refilled slots already moved
        # their idx, so no double attribution is possible.
        with jax.named_scope("madsim/park_live"):
            live_tgt = jnp.where(ex["idx"] >= 0, ex["idx"], dump)
            obs = eng.observe_device(state)
            bufs = {k: ex["bufs"][k].at[live_tgt].set(obs[k])
                    for k in ex["bufs"]}
            cov_out = ex["cov"] if cov_on else ()
            ch_out = ex["cov_hist"] if cov_on else ()
            srch_out = ()
            stats_out = ex["stats"] if search_on else ()
            if search_on:
                sched_buf = ex["sched_buf"].at[live_tgt].set(ex["sched"])
                srch_out = (ex["sched"], ex["corpus"], sched_buf)
                if lineage_on:
                    lin_buf = jax.tree.map(
                        lambda b, v: b.at[live_tgt].set(v), ex["lin_buf"],
                        ex["lin"])
                    srch_out = srch_out + (ex["lin"], ex["op_tab"], lin_buf)
        return (state, ex["idx"], ex["cursor"], ex["epochs"], bufs,
                cov_out, srch_out, any_bug, n_active, k_done, hist,
                ch_out, stats_out)

    cov_sh = (rep, rep) if cov_on else ()
    srch_sh = ()
    stats_sh = ()
    if search_on:
        srch_sh = (ws, rep, rep)
        stats_sh = (rep,) * (5 if lineage_on else 2)
        if lineage_on:
            srch_sh = srch_sh + (ws, rep, rep)
    out_sh = (ws, ws, rep, rep, rep, cov_sh, srch_sh,
              rep, rep, rep, rep, (rep if cov_on else ()), stats_sh)
    fn = jax.jit(run, out_shardings=out_sh)
    cache[key] = fn
    return fn


class SweepSession:
    """A persistent sweep session: the fleet's answer to O(fresh-sweep)
    lease turnaround (docs/fleet.md "Fabric cost model").

    ``sweep()`` pays a per-call host tax — seed/fault padding, fault
    hashing, batch ``init``, compile-cache lookups, telemetry plumbing —
    that a fleet worker used to repeat for EVERY leased range. A session
    pins the (engine, mesh, chunk/superstep geometry) once and streams
    successive seed ranges through it:

    * :meth:`run` is a drop-in ``sweep()`` with the session's engine,
      mesh, and loop geometry pre-bound — checkpoint/resume, ``search=``
      corpus seeding, and every other sweep mode stay per-lease.
    * :meth:`run_group` takes SEVERAL ranges at once and advances them
      as ONE standing device batch (the widths the engine is actually
      efficient at), then splits per-range ``SweepResult``s that are
      bit-identical to one fresh ``sweep()`` per range. Worlds are
      position-independent and every range installs at chunk 0, so a
      grouped world's trajectory equals its solo counterpart's bit for
      bit; chunks past a range's retirement are on-device pass-throughs
      on inactive worlds. The standing slots are RECYCLED between
      groups: the next group's worlds enter through ``DeviceEngine.
      refill`` (all-slots mask, donating the dead batch in place)
      rather than a fresh double-buffered ``init``.

    Sync discipline matches the solo pipelined loop exactly: dispatch-
    ahead supersteps, ONE ``_fetch`` per superstep, and (coverage on)
    one final ledger pull covering every range — counted by the tier-1
    seam tests (tests/test_fleet.py) against the non-session path.

    NOT thread-safe; one session per worker.
    """

    #: sweep() kwargs run_group understands. A lease whose sweep kwargs
    #: leave this set (checkpointing, search, recycle, ...) must run
    #: solo through :meth:`run` — the worker enforces this split.
    GROUPABLE_KW = frozenset(
        {"chunk_steps", "max_steps", "superstep_max", "coverage_buckets"})

    def __init__(self, actor: Any = None, cfg: Optional[EngineConfig] = None,
                 *, engine: Optional[DeviceEngine] = None,
                 mesh: Optional[Mesh] = None, chunk_steps: int = 512,
                 max_steps: int = 1_000_000, superstep_max: int = 16,
                 coverage_buckets: Optional[int] = None):
        if engine is None:
            if cfg is None:
                raise ValueError(
                    "SweepSession needs engine=DeviceEngine(...) or "
                    "(actor, cfg) to build one")
            engine = DeviceEngine(actor, cfg)
        if superstep_max < 1:
            raise ValueError("superstep_max must be >= 1")
        self.engine = engine
        self.mesh = mesh if mesh is not None else seed_mesh()
        self.chunk_steps = int(chunk_steps)
        self.max_steps = int(max_steps)
        self.superstep_max = int(superstep_max)
        self.coverage_buckets = coverage_buckets
        #: Ranges served without paying a fresh per-lease sweep setup
        #: (``loop_stats["fleet"]["session_reuse_hits"]`` sums them).
        self.reuse_hits = 0
        self._runs = 0
        self._k_warm = 1          # adaptive-K carry across groups
        self._slot_state = None   # standing batch between groups
        self._slot_w = 0

    # -- solo path --------------------------------------------------------

    def run(self, seeds, faults: Optional[np.ndarray] = None,
            **kw) -> SweepResult:
        """One leased range through the full ``sweep()`` — session
        engine/mesh/geometry pre-bound, every per-lease mode
        (checkpoint/resume, ``search=``, recycling) available."""
        kw.setdefault("chunk_steps", self.chunk_steps)
        kw.setdefault("max_steps", self.max_steps)
        kw.setdefault("superstep_max", self.superstep_max)
        if self.coverage_buckets is not None:
            kw.setdefault("coverage_buckets", self.coverage_buckets)
        # A solo run does not leave the standing batch in a known state.
        self._slot_state = None
        first = self._runs == 0
        self._runs += 1
        if not first:
            self.reuse_hits += 1
        return sweep(None, self.engine.cfg, seeds, faults=faults,
                     engine=self.engine, mesh=self.mesh, **kw)

    # -- grouped path -----------------------------------------------------

    def _part_sha256(self, faults: Optional[np.ndarray]) -> Optional[str]:
        """Replicate the solo sweep's ``faults_sha256`` for one range:
        sha256 over the PADDED int32 rows (3-D schedules pad to the
        mesh-rounded id space with repeats of row 0, exactly as
        ``sweep()`` pads), so a grouped result's fingerprint equals its
        solo counterpart's byte for byte."""
        if faults is None:
            return None
        fp = np.asarray(faults, np.int32)
        if fp.ndim == 3:
            n_i = fp.shape[0]
            pad = (-n_i) % self.mesh.devices.size
            if pad:
                fp = np.concatenate([fp, fp[:1].repeat(pad, axis=0)], axis=0)
        return hashlib.sha256(
            np.ascontiguousarray(fp).tobytes()).hexdigest()

    @_obsy.spanned("madsim:sweep")
    def run_group(self, parts: List[Dict[str, Any]],
                  observe: Any = None) -> List[SweepResult]:
        """Advance several seed ranges as one standing device batch;
        return one ``SweepResult`` per range, bit-identical to a fresh
        per-range ``sweep()`` (tier-1 contract, tests/test_fleet.py).

        ``parts``: ``[{"seeds": (n_i,) uint64, "faults": None | (F, 4)
        shared template | (n_i, F, 4) per-world}, ...]``. All parts must
        agree on the faults *form* (the worker groups only leases that
        slice one fleet-level schedule). ``observe``: the solo sweep's
        live-telemetry sink — one record per superstep scalar read,
        schema ``madsim.sweep.telemetry/1`` — which is what lets the
        fleet worker's heartbeat (and therefore every chaos preemption
        point) ride the grouped loop at the same cadence.
        """
        from ..obs.coverage import (
            DEFAULT_BUCKETS,
            coverage_from_device,
            ledger_zeros,
        )

        if not parts:
            raise ValueError("run_group needs at least one range")
        tr = _obsy.LoopTracer(_LOOP_SECONDS)
        eng, mesh = self.engine, self.mesh
        n_dev = mesh.devices.size
        chunk_steps, superstep_max = self.chunk_steps, self.superstep_max
        cov_on = bool(eng.cfg.metrics)
        cov_k = (int(self.coverage_buckets) if self.coverage_buckets
                 else DEFAULT_BUCKETS)

        # -- combine ranges into one batch --------------------------------
        with tr.span("madsim:prepare", "prepare_s"):
            seeds_list: List[np.ndarray] = []
            faults_list: List[Optional[np.ndarray]] = []
            for p in parts:
                s = np.asarray(p["seeds"], np.uint64)
                if s.shape[0] == 0:
                    raise ValueError("run_group ranges must be non-empty")
                f = p.get("faults")
                if f is not None:
                    f = np.asarray(f, np.int32)
                    if f.ndim not in (2, 3) or f.shape[-1] != 4:
                        raise ValueError(
                            f"range fault schedules must be (F, 4) or "
                            f"(n_i, F, 4); got shape {f.shape}")
                    if f.ndim == 3 and f.shape[0] != s.shape[0]:
                        raise ValueError(
                            f"per-world schedules carry one (F, 4) block "
                            f"per seed: got leading dim {f.shape[0]} for "
                            f"{s.shape[0]} seeds")
                seeds_list.append(s)
                faults_list.append(f)
            forms = {(None if f is None else f.ndim) for f in faults_list}
            if len(forms) > 1:
                raise ValueError(
                    "run_group ranges must agree on the faults form "
                    "(all None, all shared (F, 4), or all per-world)")
            form = forms.pop()

            n_list = [int(s.shape[0]) for s in seeds_list]
            offs = np.concatenate([[0], np.cumsum(n_list)]).astype(int)
            n_tot = int(offs[-1])
            w = n_tot + ((-n_tot) % n_dev)
            seeds_c = np.concatenate(seeds_list)
            if w > n_tot:  # mesh padding: dummy worlds, sliced off below
                seeds_c = np.concatenate(
                    [seeds_c, seeds_c[:1].repeat(w - n_tot)])
            if form is None:
                faults_init = None
            elif form == 2:
                faults_init = faults_list[0]
                for f in faults_list[1:]:
                    if not np.array_equal(f, faults_init):
                        raise ValueError(
                            "shared (F, 4) templates must be identical "
                            "across grouped ranges")
            else:
                faults_init = np.concatenate(faults_list, axis=0)
                if w > n_tot:
                    faults_init = np.concatenate(
                        [faults_init,
                         faults_init[:1].repeat(w - n_tot, axis=0)], axis=0)

        # -- install: recycle the standing slots, else fresh init ---------
        # Every slot is installed anew, so the group's own fault rows
        # decide the step's form, as in sweep().
        fault_path = faults_init is not None and faults_init.shape[-2] > 0
        form = eng._form(fault_path)
        with tr.span("madsim:init", "init_s"):
            reused = self._slot_state is not None and self._slot_w == w
            if reused:
                prev_state, self._slot_state = self._slot_state, None
                state = shard_worlds(
                    form.refill(prev_state, np.ones(w, bool), seeds_c,
                                faults=faults_init), mesh)
            else:
                self._slot_state = None
                state = shard_worlds(
                    form.init(seeds_c, faults=faults_init), mesh)
        first = self._runs == 0
        self._runs += 1
        self.reuse_hits += len(parts) - (1 if first else 0)

        emit_telemetry, close_telemetry = _obsy.make_observer(observe)
        t_loop0 = tr.clock()
        dispatch_depth = 0

        # -- pipelined dispatch-ahead loop (the solo loop, minus the
        # refill/shrink/search edges grouped mode never takes) ------------
        c_max = -(-self.max_steps // chunk_steps)
        chunks = 0
        issued = 0                     # slot-steps the chunks executed
        k_cur = max(1, min(self._k_warm, superstep_max))
        epoch_fresh = True
        inflight: Optional[_Flight] = None
        stop = False
        n_act = n_tot

        def dispatch(reserve: int = 0) -> None:
            nonlocal state, inflight, epoch_fresh
            k = max(1, min(k_cur, c_max - chunks - reserve, superstep_max))
            if epoch_fresh:
                k = 1
            runner = sharded_superstep(
                eng, mesh, chunk_steps, superstep_max, donate=True,
                min_one=epoch_fresh, coverage=None, fault_path=fault_path)
            epoch_fresh = False
            with tr.span("madsim:superstep", "dispatch_s"):
                state, any_bug, n_active, k_done, hist, shard_steps = runner(
                    state, jnp.int32(0), jnp.asarray(False), jnp.int32(k))
            inflight = _Flight(any_bug, n_active, k_done, hist, shard_steps,
                               k, w, 0, None)

        try:
            if c_max > 0:
                dispatch()
            while inflight is not None:
                prev, inflight = inflight, None
                if not stop and chunks + prev.planned < c_max:
                    dispatch(reserve=prev.planned)
                with tr.span("madsim:wait", "device_wait_s"):
                    bug_h, n_act_h, k_done_h, _hist_h, ran_h = _fetch(
                        (prev.any_bug, prev.n_active, prev.k_done,
                         prev.hist, prev.shard_steps))
                dispatch_depth = max(dispatch_depth,
                                     1 if inflight is not None else 0)
                with tr.span("madsim:decide", "host_decision_s"):
                    k_done = int(k_done_h)
                    n_act = int(n_act_h)
                    chunks += k_done
                    issued += w // n_dev * int(ran_h)
                    if k_done == prev.planned:
                        k_cur = min(k_cur * 2, superstep_max)
                    else:
                        k_cur = max(k_done, 1)
                    if not stop and n_act == 0:
                        stop = True
                if emit_telemetry is not None:
                    elapsed = tr.clock() - t_loop0
                    done = max(n_tot - n_act, 0)
                    emit_telemetry({
                        "schema": "madsim.sweep.telemetry/1",
                        "elapsed_s": round(elapsed, 6),
                        "chunks": int(chunks),
                        "steps": int(chunks * chunk_steps),
                        "batch_worlds": int(w),
                        "n_active": int(n_act),
                        "occupancy": round(n_act / w, 4) if w else 0.0,
                        "seeds_total": int(n_tot),
                        "seeds_done": int(done),
                        "bug_seen": bool(bug_h),
                        "session_group": len(parts),
                        "dispatch_depth": 1 if inflight is not None else 0,
                    })
                if stop:
                    break
                if inflight is None and chunks < c_max:
                    dispatch()
        except BaseException:
            # A kill/preemption mid-group leaves donated buffers in an
            # unknown state: drop the standing batch, never resume it.
            self._slot_state = None
            self._slot_w = 0
            if close_telemetry is not None:
                close_telemetry()
            raise

        # -- per-range extraction -----------------------------------------
        # One eng.observe pull (its own single device_get, exactly the
        # solo end-of-sweep read) + (coverage on) ONE _fetch batching
        # every range's end-folded ledger.
        ledgers_h = None
        if cov_on:
            folder = _cov_endfolder(eng, mesh)
            sharding = NamedSharding(mesh, scalar_spec())
            ledgers = []
            for i, n_i in enumerate(n_list):
                idx_np = np.full(w, -1, np.int32)
                idx_np[offs[i]:offs[i + 1]] = np.arange(n_i, dtype=np.int32)
                idx_r = shard_worlds(jnp.asarray(idx_np), mesh)
                hits, first = jax.device_put(ledger_zeros(cov_k), sharding)
                n_real = jnp.int32(n_i)
                # Two boundary folds per range: worlds that retired
                # during the group (frozen histograms — the resume
                # pre-pass precedent), then worlds still live at exit.
                # hits/first_seen are fold-order invariant, so the pair
                # equals the solo sweep's mid-loop + end folds exactly.
                hits, first = folder(state, hits, first, idx_r, n_real,
                                     jnp.asarray(False))
                hits, first = folder(state, hits, first, idx_r, n_real,
                                     jnp.asarray(True))
                ledgers.append((hits, first))
        with tr.span("madsim:pull", "retire_wait_s"):
            if cov_on:
                ledgers_h = _fetch(ledgers)
            obs_all = eng.observe(state)

        with tr.span("madsim:assemble", "assemble_s"):
            self._slot_state = state
            self._slot_w = w
            self._k_warm = k_cur

            steps = chunks * chunk_steps
            live_steps = int(np.asarray(obs_all["steps"])[:n_tot].sum())
            util = live_steps / issued if issued else 0.0
            loop_stats_base = {
                "pipelined": True,
                "session": True,
                "session_group": len(parts),
                "session_reused_slots": bool(reused),
                "superstep_max": int(superstep_max),
                "chunk_steps": int(chunk_steps),
                "chunks": int(chunks),
                "slot_steps_skipped": int(w * chunk_steps * chunks - issued),
                "dispatches": tr.entered["madsim:superstep"],
                "chunks_per_dispatch": round(
                    chunks / max(tr.entered["madsim:superstep"], 1), 3),
                "dispatch_depth": dispatch_depth,
                "scalar_fetches": tr.entered["madsim:wait"],
                "fault_path": int(fault_path),
                "loop_wall_s": round(tr.clock() - t_loop0, 6),
            }

            results: List[SweepResult] = []
            for i, (s, f) in enumerate(zip(seeds_list, faults_list)):
                lo, hi = int(offs[i]), int(offs[i + 1])
                obs = {k: np.asarray(v)[lo:hi] for k, v in obs_all.items()}
                coverage = None
                if cov_on:
                    hits_h, first_h = ledgers_h[i]
                    coverage = coverage_from_device(
                        cov_k, np.asarray(hits_h), np.asarray(first_h), [])
                results.append(SweepResult(
                    seeds=s, bug=obs["bug"], observations=obs,
                    steps_run=steps, n_devices=n_dev,
                    world_utilization=util,
                    loop_stats=dict(loop_stats_base),
                    faults_sha256=self._part_sha256(f),
                    coverage=coverage,
                    triage_ctx=TriageContext(eng, f, mesh)))
        for res in results:
            res.loop_stats.update(tr.seconds())
        if close_telemetry is not None:
            close_telemetry()
        return results
