"""tracelint — pass 3: program-level static analysis of the compiled sweep.

detlint's AST passes see Python source; since PR 3-7 the determinism and
performance contracts moved INTO compiled programs — the superstep loop,
donated step buffers, the coverage fold, the bridge kernel — where an AST
walk cannot follow. This pass traces the repo's hot-path entry points to
their jaxprs (and, for the budget/donation gates, compiles them fresh)
and enforces four rule families, the same shape as compiler-level
sanitizer passes in a training stack (DrJAX's MapReduce-primitive
discipline, SCALE-Sim's cost-model validation — PAPERS.md):

- **TRC001** — no host callbacks (``pure_callback``/``io_callback``/
  ``debug_callback``/``debug_print``) inside jitted sim programs: a callback re-enters
  the host mid-program, breaking both determinism (host state) and the
  dispatch-ahead pipeline (implicit sync).
- **TRC002** — no backend-variant or nondeterministic primitives:
  unstable sorts, float scatter-accumulation onto possibly-duplicate
  indices, approximate/stateful kernels.
- **TRC003** — no numerics that change under the x64 flag: each engine
  program is traced twice (plain and under ``enable_x64``) and must keep
  identical output dtypes and stay float64-free — otherwise a process
  that flips ``jax_enable_x64`` silently changes trajectories.
- **TRC004** — declared donation actually lands: JAX drops donation
  SILENTLY when an output cannot alias its input, which would quietly
  re-double-buffer the state PR 3 paid to alias (the 1.195x-of-state
  peak gate). Checked against the per-program ``alias_fraction`` floor
  recorded in the budget ledger, compiled FRESH (cache-deserialized
  executables lose alias statistics — :mod:`.budgets`).

Plus the **budget ledger** (``analysis/budgets.json``): per-program
``cost_analysis`` flops/bytes and ``memory_analysis`` temp/peak, diffed
against checked-in ceilings (BUD001/BUD002) so a hot program regressing
its op budget fails ``make lint`` before a bench round ever runs.

Entry points: ``python -m madsim_tpu.analysis trace`` (the ``make
tracelint`` / ``make lint`` gate), ``tools/update_budgets.py`` to
regenerate the ledger. Findings use the pseudo-path ``trace/<program>``
so allowlist prefixes and ``--format=github`` output compose unchanged.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

from . import budgets as _budgets
from .pragmas import Finding
from .rules import RULES

# -- rule tables -------------------------------------------------------------

# TRC001: primitives that re-enter the host from inside a program.
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback",
    # jax.debug.print traces to its own primitive, not debug_callback
    "debug_print",
})

# TRC002: outright-forbidden primitives (stateful/approximate kernels whose
# results are backend- or scheduling-dependent).
NONDET_PRIMS = frozenset({
    "rng_uniform",        # the old stateful lax RNG — backend-defined
    "rng_bit_generator",  # platform-keyed algorithm selection
    "approx_top_k",       # approximate by construction
})

# TRC002: scatter accumulation combiners that are order-sensitive in
# floating point (float add/mul are not associative; duplicate indices
# then make the result depend on reduction order, which backends choose).
SCATTER_ACCUM_PRIMS = frozenset({"scatter-add", "scatter-mul"})

# TRC005: narrow-lane dtypes (the packed profile of engine/lanes.py) and
# the wide integer dtypes an unannotated promotion would leak them into.
NARROW_INT_DTYPES = frozenset({"int8", "int16", "uint8", "uint16"})
WIDE_INT_DTYPES = frozenset({"int32", "int64", "uint32", "uint64"})
# The one sanctioned widening site: lanes.widen() (and the helpers in
# the same module — take_small's index cast, onehot's compare operand).
# Path-qualified: a bare "lanes.py" would also match e.g.
# tests/test_packed_lanes.py in the source summary.
SANCTIONED_WIDEN_FILE = "engine/lanes.py"


# -- jaxpr walking -----------------------------------------------------------

def _sub_jaxprs(value: Any) -> Iterator[Any]:
    vals = value if isinstance(value, (tuple, list)) else [value]
    for v in vals:
        if hasattr(v, "eqns"):               # open Jaxpr
            yield v
        elif hasattr(v, "jaxpr") and hasattr(getattr(v, "jaxpr"), "eqns"):
            yield v.jaxpr                    # ClosedJaxpr


def iter_eqns(jaxpr) -> Iterator[Any]:
    """Every equation in ``jaxpr`` and (recursively) every sub-jaxpr a
    param carries — while/scan/cond bodies, pjit calls, shard_map, custom
    derivative closures."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def _where(eqn) -> str:
    """Best-effort source attribution for an equation."""
    try:
        from jax._src import source_info_util

        s = source_info_util.summarize(eqn.source_info)
        return f" at {s}" if s else ""
    except Exception:  # pragma: no cover — jax internals drift
        return ""


def _aval_dtypes(jaxpr, acc: set) -> None:
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "dtype"):
                acc.add(str(aval.dtype))
        for p in eqn.params.values():
            for sub in _sub_jaxprs(p):
                _aval_dtypes(sub, acc)


# -- the program registry ----------------------------------------------------

@dataclasses.dataclass
class Built:
    """A traceable/lowerable hot-path program instance.

    ``fn``/``args`` is the jitted entry used for ``lower().compile()``
    (donation declarations live there); ``trace_fn``/``trace_args``
    override it for ``make_jaxpr`` when the jit carries static argnums
    (``make_jaxpr`` traces every argument, so a static int would arrive
    as a tracer and fail to hash)."""

    fn: Callable                  # the jitted callable
    args: Tuple[Any, ...]         # small concrete example args
    ctx: Callable[[], Any] = contextlib.nullcontext  # trace/lower context
    trace_fn: Optional[Callable] = None
    trace_args: Optional[Tuple[Any, ...]] = None

    @property
    def for_trace(self) -> Tuple[Callable, Tuple[Any, ...]]:
        return (self.trace_fn or self.fn,
                self.args if self.trace_args is None else self.trace_args)


@dataclasses.dataclass
class TraceProgram:
    name: str
    title: str                    # one human line for --list-programs
    build: Callable[[], Built]
    x64: str = "off"              # "off": dual-trace diff; "required": bridge
    budget: bool = False          # compile fresh: TRC004 + ledger metrics
    donates: bool = False         # program declares input donation
    unit_div: Optional[int] = None  # world count for flops_per_world
    packed: bool = False          # TRC005 narrow-dtype discipline applies


_ENGINE_CACHE: Dict[str, Any] = {}


def _bug_engine(metrics: bool = False, blackbox: int = 0):
    """The canonical raft bug config every budget in the repo is pinned
    to (tests/test_queue_insert.py)."""
    key = f"eng_m{int(metrics)}_b{blackbox}"
    if key not in _ENGINE_CACHE:
        from ..engine import (DeviceEngine, EngineConfig, RaftActor,
                              RaftDeviceConfig)

        cfg = EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                           t_limit_us=2_000_000, stop_on_bug=False,
                           metrics=metrics, blackbox=blackbox)
        _ENGINE_CACHE[key] = DeviceEngine(
            RaftActor(RaftDeviceConfig(n=3, buggy_double_vote=True)), cfg)
    return _ENGINE_CACHE[key]


def _mesh():
    if "mesh" not in _ENGINE_CACHE:
        from ..parallel.mesh import seed_mesh

        _ENGINE_CACHE["mesh"] = seed_mesh()
    return _ENGINE_CACHE["mesh"]


# Pinned shapes: every ledger number is "at this shape" — small enough to
# trace in seconds, large enough that per-world figures are meaningful.
RUN_WORLDS = 256          # matches the historical tier-1 op-budget shape
RUN_MAX_STEPS = 4_000
SWEEP_WORLDS = 64
SWEEP_CHUNK_STEPS = 16
SWEEP_K_MAX = 4


def _build_engine_run() -> Built:
    import numpy as np

    eng = _bug_engine()
    state = eng.init(np.arange(RUN_WORLDS))
    return Built(fn=eng._run, args=(state, RUN_MAX_STEPS),
                 trace_fn=lambda s: eng._run_impl(s, RUN_MAX_STEPS),
                 trace_args=(state,))


# Flight-recorder ring depth the budget is pinned at — the depth the
# docs recommend (docs/observability.md "The flight recorder").
BLACKBOX_K = 64


def _build_engine_run_blackbox() -> Built:
    import numpy as np

    eng = _bug_engine(blackbox=BLACKBOX_K)
    state = eng.init(np.arange(RUN_WORLDS))
    return Built(fn=eng._run, args=(state, RUN_MAX_STEPS),
                 trace_fn=lambda s: eng._run_impl(s, RUN_MAX_STEPS),
                 trace_args=(state,))


def _build_engine_run_snapshot() -> Built:
    """The Raft step with log compaction at the lab 2D deployment's shapes
    (benchmark/configs/raft3snap.json: 3 servers, a 32-entry window, a
    snapshot every 10 applied entries, the client stream's second timer
    row, 10% loss)."""
    import numpy as np

    if "snap_eng" not in _ENGINE_CACHE:
        from ..engine import (DeviceEngine, EngineConfig, RaftActor,
                              RaftDeviceConfig)

        _ENGINE_CACHE["snap_eng"] = DeviceEngine(
            RaftActor(RaftDeviceConfig(
                n=3, log_cap=32, n_proposals=250, propose_start_us=500_000,
                propose_interval_us=10_000, snapshot_interval=10)),
            EngineConfig(n_nodes=3, outbox_cap=5, queue_cap=32,
                         loss_rate=0.1, t_limit_us=3_000_000))
    eng = _ENGINE_CACHE["snap_eng"]
    state = eng.init(np.arange(RUN_WORLDS))
    return Built(fn=eng._run, args=(state, RUN_MAX_STEPS),
                 trace_fn=lambda s: eng._run_impl(s, RUN_MAX_STEPS),
                 trace_args=(state,))


# Pallas kernel shape: smaller than RUN_WORLDS — the interpret-mode
# kernel is traced/compiled per check and the contract (one fused
# kernel, full donation, narrow lanes) is width-invariant.
PALLAS_WORLDS = 64


def _build_pallas_step() -> Built:
    import dataclasses as _dc

    import jax
    import numpy as np

    if "pallas_eng" not in _ENGINE_CACHE:
        from ..engine import DeviceEngine

        eng0 = _bug_engine()
        _ENGINE_CACHE["pallas_eng"] = DeviceEngine(
            eng0.actor, _dc.replace(eng0.cfg, pallas=True))
    eng = _ENGINE_CACHE["pallas_eng"]
    state = eng.init(np.arange(PALLAS_WORLDS))
    # One batched kernel invocation, donated like the run loop: the
    # jitted wrapper is what the ledger prices (alias_fraction must
    # show the input_output_aliases landing at the XLA level too).
    if "pallas_step_jit" not in _ENGINE_CACHE:
        _ENGINE_CACHE["pallas_step_jit"] = jax.jit(
            eng._batched_step, donate_argnums=0)
    return Built(fn=_ENGINE_CACHE["pallas_step_jit"], args=(state,),
                 trace_fn=eng._batched_step)


def _build_push_many() -> Built:
    import jax
    import jax.numpy as jnp

    from ..engine.queue import Event, empty_queue, push_many

    q = empty_queue(64, 2)
    m = 4
    evs = Event(time=jnp.zeros((m,), jnp.int32),
                kind=jnp.zeros((m,), jnp.int32),
                flags=jnp.zeros((m,), jnp.int32),
                src=jnp.zeros((m,), jnp.int32),
                dst=jnp.zeros((m,), jnp.int32),
                gen=jnp.zeros((m,), jnp.int32),
                payload=jnp.zeros((m, 2), jnp.int32))
    return Built(fn=jax.jit(push_many), args=(q, evs))


def _superstep_args(eng, mesh):
    import jax.numpy as jnp
    import numpy as np

    from ..parallel.mesh import shard_worlds

    state = shard_worlds(eng.init(np.arange(SWEEP_WORLDS)), mesh)
    return state, (jnp.int32(0), jnp.asarray(False),
                   jnp.int32(SWEEP_K_MAX))


def _build_superstep(min_one: bool) -> Built:
    def build():
        from ..parallel.sweep import sharded_superstep

        eng, mesh = _bug_engine(), _mesh()
        runner = sharded_superstep(eng, mesh, SWEEP_CHUNK_STEPS,
                                   SWEEP_K_MAX, donate=True,
                                   min_one=min_one)
        state, scalars = _superstep_args(eng, mesh)
        return Built(fn=runner, args=(state,) + scalars)
    return build


def _build_superstep_coverage() -> Built:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from ..obs.coverage import ledger_zeros
    from ..parallel.mesh import scalar_spec, shard_worlds
    from ..parallel.sweep import sharded_superstep

    eng, mesh = _bug_engine(metrics=True), _mesh()
    cov_k = 64
    runner = sharded_superstep(eng, mesh, SWEEP_CHUNK_STEPS, SWEEP_K_MAX,
                               donate=True, min_one=False, coverage=cov_k)
    state = shard_worlds(eng.init(np.arange(SWEEP_WORLDS)), mesh)
    hits, first = jax.device_put(ledger_zeros(cov_k),
                                 NamedSharding(mesh, scalar_spec()))
    idx = shard_worlds(jnp.arange(SWEEP_WORLDS, dtype=jnp.int32), mesh)
    return Built(fn=runner, args=(
        state, hits, first, idx, jnp.int32(SWEEP_WORLDS), jnp.int32(0),
        jnp.asarray(False), jnp.int32(SWEEP_K_MAX)))


def _build_endfold() -> Built:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from ..obs.coverage import ledger_zeros
    from ..parallel.mesh import scalar_spec, shard_worlds
    from ..parallel.sweep import _cov_endfolder

    eng, mesh = _bug_engine(metrics=True), _mesh()
    state = shard_worlds(eng.init(np.arange(SWEEP_WORLDS)), mesh)
    hits, first = jax.device_put(ledger_zeros(64),
                                 NamedSharding(mesh, scalar_spec()))
    idx = shard_worlds(jnp.arange(SWEEP_WORLDS, dtype=jnp.int32), mesh)
    return Built(fn=_cov_endfolder(eng, mesh), args=(
        state, hits, first, idx, jnp.int32(SWEEP_WORLDS),
        jnp.asarray(False)))


def _build_compactor() -> Built:
    import jax.numpy as jnp
    import numpy as np

    from ..parallel.mesh import shard_worlds
    from ..parallel.sweep import _compactor

    eng, mesh = _bug_engine(), _mesh()
    state = shard_worlds(eng.init(np.arange(SWEEP_WORLDS)), mesh)
    idx = shard_worlds(jnp.arange(SWEEP_WORLDS, dtype=jnp.int32), mesh)
    return Built(fn=_compactor(eng, mesh, SWEEP_WORLDS, SWEEP_WORLDS),
                 args=(state, idx))


def _build_refill_select() -> Built:
    import jax.numpy as jnp
    import numpy as np

    eng = _bug_engine()
    mask = jnp.zeros((SWEEP_WORLDS,), bool)
    fresh = eng.init(np.arange(SWEEP_WORLDS))
    state = eng.init(np.arange(SWEEP_WORLDS))
    return Built(fn=eng._refill_select, args=(mask, fresh, state))


# Guided-search generator shape (search/generate.py): the harvest +
# mutate program one guided refill dispatches — the "search superstep"
# of the closed fuzzer loop (docs/search.md), at the canonical family
# hunt shape.
SEARCH_WORLDS = 32
SEARCH_ROWS = 6


def _search_fixture():
    """Shared state of the guided-search builders: engine, mesh, the
    canonical template, and the per-slot arrays at the hunt shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from ..parallel.mesh import scalar_spec, shard_worlds
    from ..search.corpus import corpus_init

    if "search_eng" not in _ENGINE_CACHE:
        from ..engine import DeviceEngine
        from ..search.family import (GuidedPairActor, GuidedPairConfig,
                                     engine_config)

        acfg = GuidedPairConfig(n=12)
        _ENGINE_CACHE["search_eng"] = DeviceEngine(
            GuidedPairActor(acfg), engine_config(acfg))
    from ..search.family import family_schedule, hunt_search_config
    from ..search.family import GuidedPairConfig as _GPC

    eng, mesh = _ENGINE_CACHE["search_eng"], _mesh()
    scfg = hunt_search_config(True)
    tmpl = family_schedule(SEARCH_ROWS, _GPC(n=12))
    w = SEARCH_WORLDS
    state = shard_worlds(eng.init(np.arange(w), faults=tmpl), mesh)
    sched = shard_worlds(jnp.asarray(
        np.broadcast_to(tmpl, (w,) + tmpl.shape).copy()), mesh)
    idx = shard_worlds(jnp.arange(w, dtype=jnp.int32), mesh)
    corpus = jax.device_put(corpus_init(int(scfg.corpus), tmpl),
                            NamedSharding(mesh, scalar_spec()))
    return eng, mesh, scfg, w, state, sched, idx, corpus


def _search_lineage_args(mesh, w):
    """The lineage-side searcher inputs (obs/lineage.py lanes + outcome
    table) at the hunt shape."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from ..obs.lineage import lanes_origin, table_zeros
    from ..parallel.mesh import scalar_spec, shard_worlds

    lin = shard_worlds(lanes_origin(w), mesh)
    op_tab = jax.device_put(table_zeros(),
                            NamedSharding(mesh, scalar_spec()))
    fill = shard_worlds(jnp.asarray(
        jnp.arange(w, dtype=jnp.int32) >= w // 2), mesh)
    return lin, op_tab, fill


def _build_search_generate() -> Built:
    import jax.numpy as jnp

    from ..search.generate import searcher

    eng, mesh, scfg, w, state, sched, idx, corpus = _search_fixture()
    runner = searcher(eng, mesh, scfg, w, SEARCH_ROWS)
    from ..parallel.mesh import shard_worlds

    ids = shard_worlds(jnp.arange(w, dtype=jnp.int32), mesh)
    lin, op_tab, fill = _search_lineage_args(mesh, w)
    return Built(fn=runner, args=(state, sched, idx, corpus,
                                  jnp.int32(w // 2), ids, fill, lin,
                                  op_tab, jnp.int32(0)))


def _build_fused_hunt() -> Built:
    """The whole-hunt fused program (parallel/sweep.py _fused_hunt) at
    its widest shape — guided + lineage + coverage — so the ledger
    budgets the full in-loop epoch body: chunk loop, stable compaction,
    retiring-tail scatter, coverage fold, harvest+generate, refill, and
    the device seed cursor, all inside ONE dispatch."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from ..obs.coverage import ledger_zeros
    from ..obs.lineage import lanes_buffer
    from ..parallel.mesh import scalar_spec
    from ..parallel.sweep import _fused_hunt

    eng, mesh, scfg, w, state, sched, idx, corpus = _search_fixture()
    lin, op_tab, _fill = _search_lineage_args(mesh, w)
    del _fill
    rep = NamedSharding(mesh, scalar_spec())
    cov_k = 64
    runner = _fused_hunt(eng, mesh, scfg, w=w, n_ids_b=w,
                         f_rows=SEARCH_ROWS,
                         chunk_steps=SWEEP_CHUNK_STEPS,
                         k_bucket=SWEEP_K_MAX, cov_k=cov_k,
                         lineage_on=True, fault_mode="search",
                         recycle=True)
    hits, first = jax.device_put(ledger_zeros(cov_k), rep)
    obs_shapes = jax.eval_shape(eng.observe_device, state)
    bufs = jax.device_put(
        {k: jnp.zeros((w + 1,) + tuple(s.shape[1:]), s.dtype)
         for k, s in obs_shapes.items()}, rep)
    sb = np.full((w + 1, SEARCH_ROWS, 4), -1, np.int32)
    sb[:, :, 1:] = 0
    sched_buf = jax.device_put(jnp.asarray(sb), rep)
    lin_buf = jax.device_put(lanes_buffer(w), rep)
    seeds = np.arange(w, dtype=np.uint64)
    tabs = jax.device_put(
        {"lo": jnp.asarray((seeds & np.uint64(0xFFFFFFFF))
                           .astype(np.uint32)),
         "hi": jnp.asarray((seeds >> np.uint64(32)).astype(np.uint32))},
        rep)
    cursor = jax.device_put(jnp.int32(w), rep)
    epochs = jax.device_put(jnp.int32(0), rep)
    return Built(fn=runner, args=(
        state, idx, cursor, epochs, bufs, (hits, first),
        (sched, corpus, sched_buf, lin, op_tab, lin_buf), tabs,
        jnp.int32(w), jnp.int32(w), jnp.int32(0), jnp.asarray(False),
        jnp.int32(SWEEP_K_MAX)))


def _build_compactor_sched() -> Built:
    """The guided with_sched compactor: state + slot index + per-slot
    schedules + lineage lanes permuted in ONE dispatch (the widened
    PR 13 shape the guided sweep dispatches at every refill)."""
    import jax.numpy as jnp

    from ..parallel.mesh import shard_worlds
    from ..parallel.sweep import _compactor

    eng, mesh, _scfg, w, state, sched, idx, _corpus = _search_fixture()
    lin, _op_tab, _fill = _search_lineage_args(mesh, w)
    del _op_tab, _fill
    return Built(fn=_compactor(eng, mesh, w, w, with_sched=True),
                 args=(state, idx, sched) + tuple(lin))


# Triage candidate-eval shape (triage/minimize.py): one batch of
# candidate schedules of the known-minimal synthetic bug, evaluated by
# the superstep runner compiled for the pair_restart engine — a
# DISTINCT compiled program from sweep.superstep (different actor step),
# and the hot path every minimization round dispatches.
TRIAGE_CANDS = 32
TRIAGE_ROWS = 16


def _build_triage_candidate_eval() -> Built:
    import jax.numpy as jnp
    import numpy as np

    from ..engine import DeviceEngine
    from ..parallel.mesh import shard_worlds
    from ..parallel.sweep import sharded_superstep
    from ..triage.synthetic import (PairRestartActor, PairRestartConfig,
                                    engine_config, pair_schedule)

    if "triage_eng" not in _ENGINE_CACHE:
        acfg = PairRestartConfig()
        _ENGINE_CACHE["triage_eng"] = DeviceEngine(
            PairRestartActor(acfg), engine_config(acfg))
    eng, mesh = _ENGINE_CACHE["triage_eng"], _mesh()
    runner = sharded_superstep(eng, mesh, SWEEP_CHUNK_STEPS, SWEEP_K_MAX,
                               donate=True, min_one=False)
    cands = np.broadcast_to(
        pair_schedule(n_rows=TRIAGE_ROWS, need=(2, 11)),
        (TRIAGE_CANDS, TRIAGE_ROWS, 4))
    state = shard_worlds(
        eng.init(np.full(TRIAGE_CANDS, 7, np.uint64), faults=cands), mesh)
    return Built(fn=runner, args=(state, jnp.int32(0), jnp.asarray(False),
                                  jnp.int32(SWEEP_K_MAX)))


# Compiled-actor (actorc) run shapes: the whole point of registering
# these is TRC005 — the compiler CLAIMS its widen-on-read /
# narrow-on-write boundaries are placed by construction, and the
# narrow-discipline scan over a compiled family's full run program is
# what holds it to that. Small widths: the contract is width-invariant.
ACTORC_WORLDS = 64
ACTORC_MAX_STEPS = 4_000


def _build_actorc_run(family: str) -> Callable[[], Built]:
    def build() -> Built:
        import numpy as np

        key = f"actorc_{family}"
        if key not in _ENGINE_CACHE:
            from ..engine import DeviceEngine

            if family == "paxos":
                from ..actorc.families.paxos import (PaxosActor,
                                                     PaxosConfig,
                                                     engine_config)

                acfg = PaxosConfig()
                _ENGINE_CACHE[key] = DeviceEngine(PaxosActor(acfg),
                                                  engine_config(acfg))
            elif family == "pb":
                from ..engine import EngineConfig, PBActor, PBDeviceConfig

                _ENGINE_CACHE[key] = DeviceEngine(
                    PBActor(PBDeviceConfig()),
                    EngineConfig(n_nodes=3, outbox_cap=4, queue_cap=64,
                                 t_limit_us=2_000_000))
            else:  # tpc — the migrated hand-written family
                from ..engine import EngineConfig, TPCActor, TPCDeviceConfig

                _ENGINE_CACHE[key] = DeviceEngine(
                    TPCActor(TPCDeviceConfig(n=4, n_txns=4)),
                    EngineConfig(n_nodes=4, outbox_cap=5, queue_cap=64,
                                 t_limit_us=2_000_000, stop_on_bug=False))
        eng = _ENGINE_CACHE[key]
        state = eng.init(np.arange(ACTORC_WORLDS))
        return Built(fn=eng._run, args=(state, ACTORC_MAX_STEPS),
                     trace_fn=lambda s: eng._run_impl(s, ACTORC_MAX_STEPS),
                     trace_args=(state,))
    return build


BRIDGE_SLOTS = 8
BRIDGE_CAP = 16
BRIDGE_K_EVENTS = 2
BRIDGE_PAD = 4


def _bridge_kernel():
    if "bridge" not in _ENGINE_CACHE:
        import numpy as np

        from ..bridge.kernel import BridgeKernel

        _ENGINE_CACHE["bridge"] = BridgeKernel(
            np.arange(1, BRIDGE_SLOTS + 1), cap=BRIDGE_CAP,
            k_events=BRIDGE_K_EVENTS)
    return _ENGINE_CACHE["bridge"]


def _bridge_batch_args(bk):
    """A zero HostBatch at the kernel's bucketed pad shapes, with the
    exact dtypes bridge/runtime.py feeds the jitted step."""
    import jax.numpy as jnp
    import numpy as np

    from ..bridge.kernel import HostBatch

    W, P = bk.W, BRIDGE_PAD
    batch = HostBatch(
        t_slot=np.zeros((W, P), np.int32), t_dl=np.zeros((W, P), np.int64),
        t_seq=np.zeros((W, P), np.int64), t_mask=np.zeros((W, P), bool),
        c_slot=np.zeros((W, P), np.int32), c_mask=np.zeros((W, P), bool),
        s_ctr=np.zeros((W, P), np.uint64), s_base=np.zeros((W, P), np.int64),
        s_slot=np.zeros((W, P), np.int32), s_seq=np.zeros((W, P), np.int64),
        s_thr=np.zeros((W, P), np.uint64),
        s_lossall=np.zeros((W, P), bool),
        s_lat_lo=np.zeros((W, P), np.int64),
        s_lat_w=np.ones((W, P), np.int64),
        s_mask=np.zeros((W, P), bool), s_live=np.zeros((W, P), bool),
        clock=np.zeros((W,), np.int64), advance=np.zeros((W,), bool))
    return tuple(jnp.asarray(x) for x in batch)


def _bridge_ctx():
    bk = _bridge_kernel()

    @contextlib.contextmanager
    def ctx():
        with bk._jax.default_device(bk.device), bk._enable_x64():
            yield
    return ctx


def _build_bridge_step() -> Built:
    bk = _bridge_kernel()
    ctx = _bridge_ctx()
    with ctx():
        args = (bk.state, bk._mb, bk._net_k0, bk._net_k1) \
            + _bridge_batch_args(bk)
    return Built(fn=bk._fn, args=args, ctx=ctx)


def _build_bridge_drain() -> Built:
    bk = _bridge_kernel()
    return Built(fn=bk._drain_fn, args=(bk.state, bk._mb),
                 ctx=_bridge_ctx())


def registry() -> Dict[str, TraceProgram]:
    """Every hot-path program the sweep actually dispatches, by name.
    Builders are lazy (nothing imports jax until a check runs)."""
    progs = [
        TraceProgram(
            "engine.run", "DeviceEngine.run while-loop (donated step "
            f"path, raft bug config, W={RUN_WORLDS})",
            _build_engine_run, budget=True, donates=True,
            unit_div=RUN_WORLDS, packed=True),
        TraceProgram(
            "engine.run_blackbox", "DeviceEngine.run with the flight "
            f"recorder aboard (EngineConfig(blackbox={BLACKBOX_K}), "
            f"raft bug config, W={RUN_WORLDS}) — the per-step ring "
            "writes must hold the packed narrow-lane discipline and "
            "the K=64 state_bytes_per_world ceiling",
            _build_engine_run_blackbox, budget=True, donates=True,
            unit_div=RUN_WORLDS, packed=True),
        TraceProgram(
            "engine.run_snapshot", "DeviceEngine.run while-loop of the "
            "Raft step with log compaction (lab 2D deployment: "
            "InstallSnapshot, the client stream's timer row, digests; "
            f"W={RUN_WORLDS})", _build_engine_run_snapshot, budget=True,
            donates=True, unit_div=RUN_WORLDS, packed=True),
        TraceProgram(
            "engine.pallas_step", "fused Pallas step kernel "
            f"(interpret mode, raft bug config, W={PALLAS_WORLDS}, "
            "docs/perf.md Roofline round 2)", _build_pallas_step,
            budget=True, donates=True, unit_div=PALLAS_WORLDS,
            packed=True),
        TraceProgram(
            "engine.push_many", "single-pass outbox insert (queue "
            "scatter core of the step)", _build_push_many),
        TraceProgram(
            "engine.refill_select", "recycle-slot select (donated old "
            "batch)", _build_refill_select, budget=True, donates=True),
        TraceProgram(
            "sweep.superstep", "pipelined superstep runner "
            f"(W={SWEEP_WORLDS}, chunk_steps={SWEEP_CHUNK_STEPS}, "
            f"k_max={SWEEP_K_MAX})", _build_superstep(False),
            budget=True, donates=True),
        TraceProgram(
            "sweep.superstep_min_one", "superstep min_one variant (epoch-"
            "first dispatch cadence)", _build_superstep(True),
            budget=True, donates=True),
        TraceProgram(
            "sweep.superstep_coverage", "superstep with the retire-time "
            "coverage fold (metrics on)", _build_superstep_coverage),
        TraceProgram(
            "sweep.coverage_endfold", "boundary coverage fold (resume "
            "pre-pass / end-of-sweep)", _build_endfold),
        TraceProgram(
            "sweep.compactor", "on-device stable active-first compaction "
            "(deliberately undonated: gather outputs cannot alias)",
            _build_compactor, budget=True, donates=False),
        TraceProgram(
            "triage.candidate_eval", "batched ddmin candidate sweep "
            f"(C={TRIAGE_CANDS} candidate schedules x F={TRIAGE_ROWS} "
            "rows over the pair_restart engine, docs/triage.md)",
            _build_triage_candidate_eval, budget=True, donates=True),
        TraceProgram(
            "search.generate", "guided-search harvest + mutate program "
            f"(W={SEARCH_WORLDS} slots x F={SEARCH_ROWS} rows over the "
            "guided_pair family engine, docs/search.md; lineage lanes + "
            "operator outcome table aboard (obs/lineage.py); "
            "deliberately undonated: it only reads the state the refill "
            "then donates)", _build_search_generate, budget=True,
            donates=False, packed=True),
        TraceProgram(
            "sweep.compactor_sched", "guided compaction: state + "
            "per-slot schedules + lineage lanes permuted in one "
            "dispatch (undonated like sweep.compactor — gathers cannot "
            "alias)", _build_compactor_sched, budget=True,
            donates=False),
        TraceProgram(
            "sweep.fused_hunt", "whole-hunt fused program: the "
            "occupancy loop — compaction, retiring-tail harvest, "
            "coverage fold, guided generate, refill, seed cursor — in "
            f"ONE dispatch (W={SEARCH_WORLDS}, "
            f"chunk_steps={SWEEP_CHUNK_STEPS}, k={SWEEP_K_MAX}, guided "
            "pair family, lineage on; undonated v1 — per-seed buffers "
            "and loop state round-trip by value, docs/perf.md "
            "Whole-hunt residency)", _build_fused_hunt, budget=True,
            donates=False, packed=True),
        TraceProgram(
            "actorc.tpc_run", "compiled two-phase-commit run loop "
            f"(actorc spec, W={ACTORC_WORLDS}; TRC005 holds the "
            "compiler to its by-construction widen/narrow claim, "
            "docs/actorc.md)", _build_actorc_run("tpc"), budget=True,
            donates=True, unit_div=ACTORC_WORLDS, packed=True),
        TraceProgram(
            "actorc.pb_run", "compiled primary-backup run loop "
            f"(actorc spec, W={ACTORC_WORLDS}; closes the BUD002 gap — "
            "every shipped actorc family step program is in the "
            "budget ledger)", _build_actorc_run("pb"), budget=True,
            donates=True, unit_div=ACTORC_WORLDS, packed=True),
        TraceProgram(
            "actorc.paxos_run", "compiled multi-decree Paxos run loop "
            f"(DSL-only family, W={ACTORC_WORLDS})",
            _build_actorc_run("paxos"), budget=True, donates=True,
            unit_div=ACTORC_WORLDS, packed=True),
        TraceProgram(
            "bridge.step", "bridge decision-kernel lockstep round "
            f"(W={BRIDGE_SLOTS}, cap={BRIDGE_CAP})", _build_bridge_step,
            x64="required", budget=True, donates=True),
        TraceProgram(
            "bridge.drain", "bridge pop-only drain round",
            _build_bridge_drain, x64="required", budget=True,
            donates=True),
    ]
    return {p.name: p for p in progs}


# -- rule checks -------------------------------------------------------------

def _x64_ctx():
    import jax

    return jax.enable_x64


def _finding(program: str, rule: str, msg: str) -> Finding:
    r = RULES[rule]
    return Finding(f"trace/{program}", 0, rule,
                   f"{r.title}: {msg} — {r.suggestion}")


def check_jaxpr_rules(name: str, jaxpr) -> List[Finding]:
    """TRC001/TRC002 over one traced program."""
    findings: List[Finding] = []
    for eqn in iter_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim in CALLBACK_PRIMS:
            cb = eqn.params.get("callback")
            what = f" ({cb!r})" if cb is not None else ""
            findings.append(_finding(
                name, "TRC001",
                f"`{prim}` primitive{what}{_where(eqn)}"))
        elif prim in NONDET_PRIMS:
            findings.append(_finding(
                name, "TRC002", f"`{prim}` primitive{_where(eqn)}"))
        elif prim == "sort" and eqn.params.get("is_stable") is False:
            # Equal keys then land in backend-chosen order.
            findings.append(_finding(
                name, "TRC002",
                f"unstable `sort` (is_stable=False){_where(eqn)}"))
        elif prim in SCATTER_ACCUM_PRIMS \
                and not eqn.params.get("unique_indices", False):
            import numpy as _np

            dt = getattr(eqn.outvars[0].aval, "dtype", None)
            if dt is not None and _np.issubdtype(dt, _np.floating):
                findings.append(_finding(
                    name, "TRC002",
                    f"float `{prim}` without unique_indices (reduction "
                    f"order is backend-chosen){_where(eqn)}"))
    return findings


def check_narrow_discipline(name: str, jaxpr) -> List[Finding]:
    """TRC005 over one traced *packed* program: every
    ``convert_element_type`` that widens a narrow integer lane
    (i8/i16 -> i32/i64) must originate in engine/lanes.py — the
    ``widen()`` helper and the module's own index casts are the
    sanctioned sites. Anything else is an implicit promotion: a narrow
    lane leaking wide through mixed-dtype arithmetic, exactly the
    regression the packed profile exists to prevent. (The dual-trace
    machinery that backs TRC003 exposes every equation's operand and
    result dtypes; this walk reuses it.)"""
    findings: List[Finding] = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = getattr(eqn.invars[0].aval, "dtype", None)
        dst = eqn.params.get("new_dtype")
        if src is None or dst is None:
            continue
        if str(src) not in NARROW_INT_DTYPES \
                or str(dst) not in WIDE_INT_DTYPES:
            continue
        where = _where(eqn)
        if not where:
            # No source attribution (e.g. a synthesized const cast):
            # nothing actionable to report, and no narrow lane of ours
            # lacks a source line.
            continue
        if SANCTIONED_WIDEN_FILE in where:
            continue
        findings.append(_finding(
            name, "TRC005", f"{src} -> {dst} widening{where}"))
    return findings


def check_x64_invariance(name: str, prog: TraceProgram,
                         built: Built) -> List[Finding]:
    """TRC003: trace twice — plain and under ``enable_x64`` — and demand
    identical output dtypes plus a float64-free x64 trace. (int64 index
    arithmetic under x64 is exact and tolerated; float64 intermediates
    round differently than the f32 they silently replace.)"""
    import jax

    findings: List[Finding] = []
    tfn, targs = built.for_trace
    with built.ctx():
        base = jax.make_jaxpr(tfn)(*targs)
    try:
        with built.ctx(), _x64_ctx()():
            wide = jax.make_jaxpr(tfn)(*targs)
    except Exception as exc:  # the program cannot even trace under x64
        return [_finding(name, "TRC003",
                         f"fails to trace under jax_enable_x64: "
                         f"{type(exc).__name__}: {exc}")]
    b_out = [str(v.aval.dtype) for v in base.jaxpr.outvars]
    w_out = [str(v.aval.dtype) for v in wide.jaxpr.outvars]
    if b_out != w_out:
        diff = [(a, b) for a, b in zip(b_out, w_out) if a != b][:4]
        findings.append(_finding(
            name, "TRC003",
            f"output dtypes change with the x64 flag: {diff} "
            f"({sum(a != b for a, b in zip(b_out, w_out))} outputs)"))
    acc: set = set()
    _aval_dtypes(wide.jaxpr, acc)
    bad = sorted(d for d in acc if d in ("float64", "complex128"))
    if bad:
        findings.append(_finding(
            name, "TRC003",
            f"{'/'.join(bad)} intermediates appear under jax_enable_x64 "
            "(an unpinned float dtype — f32 math silently widens)"))
    return findings


def check_trace_rules(name: str, prog: TraceProgram,
                      built: Optional[Built] = None) -> List[Finding]:
    """The trace-only rule families (no XLA compile): TRC001/002 on the
    program's jaxpr, TRC003 via the dual trace for non-x64 programs."""
    import jax

    built = built or prog.build()
    findings: List[Finding] = []
    tfn, targs = built.for_trace
    if prog.x64 == "required":
        with built.ctx(), _x64_ctx()():
            jaxpr = jax.make_jaxpr(tfn)(*targs)
        findings.extend(check_jaxpr_rules(name, jaxpr.jaxpr))
        acc: set = set()
        _aval_dtypes(jaxpr.jaxpr, acc)
        if "complex128" in acc:
            findings.append(_finding(
                name, "TRC003", "complex128 intermediates in an x64 "
                "program"))
    else:
        with built.ctx():
            jaxpr = jax.make_jaxpr(tfn)(*targs)
        findings.extend(check_jaxpr_rules(name, jaxpr.jaxpr))
        if prog.packed:
            findings.extend(check_narrow_discipline(name, jaxpr.jaxpr))
        findings.extend(check_x64_invariance(name, prog, built))
    return findings


def measure_program(name: str, prog: TraceProgram,
                    built: Optional[Built] = None) -> Dict[str, Any]:
    """Fresh-compile one budget program and extract its ledger metrics
    (:func:`budgets.measure_compiled`)."""
    built = built or prog.build()
    with built.ctx():
        lowered = built.fn.lower(*built.args)
        comp = _budgets.compile_fresh(lowered)
        return _budgets.measure_compiled(comp, unit_div=prog.unit_div)


# -- the pass entry ----------------------------------------------------------

def run_trace(programs: Optional[List[str]] = None,
              budget_check: bool = True,
              ledger_path: Optional[str] = None,
              ) -> Tuple[List[Finding], Dict[str, Dict[str, Any]]]:
    """Run tracelint over the registered programs.

    Returns ``(findings, measurements)``. Trace rules (TRC001-003) run on
    every selected program; with ``budget_check`` the budget programs are
    additionally compiled fresh and diffed against the ledger
    (TRC004/BUD001/BUD002). Measurements are returned either way (empty
    without ``budget_check``) so ``tools/update_budgets.py`` can reuse
    this exact code path for regeneration.
    """
    regs = registry()
    if programs:
        unknown = [p for p in programs if p not in regs]
        if unknown:
            raise KeyError(f"unknown program(s): {unknown}; known: "
                           f"{sorted(regs)}")
        regs = {k: v for k, v in regs.items() if k in programs}
    findings: List[Finding] = []
    measured: Dict[str, Dict[str, Any]] = {}
    for name, prog in regs.items():
        try:
            built = prog.build()
        except Exception as exc:
            findings.append(_finding(
                name, "BUD002",
                f"program failed to build: {type(exc).__name__}: {exc}"))
            continue
        findings.extend(check_trace_rules(name, prog, built))
        if budget_check and prog.budget:
            measured[name] = measure_program(name, prog, built)
    if budget_check:
        ledger = _budgets.load_ledger(ledger_path)
        regs_all = registry() if programs else regs
        findings.extend(_budgets.diff_ledger(
            measured, ledger,
            registered=sorted(regs_all) if not programs else None,
            donates={k: v.donates for k, v in regs.items()}))
    findings.sort(key=lambda f: (f.path, f.rule))
    return findings, measured
