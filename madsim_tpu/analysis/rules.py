"""detlint rule catalog.

Every rule names a class of *determinism escape*: a call that reaches the
host OS (clock, entropy, scheduler, NIC) without going through the sim's
interception layer, so the same seed can produce different trajectories.
The catalog is the static twin of the dynamic interception table in
:mod:`madsim_tpu.shims.aio` (``install()``'s patch list) — anything that
table patches at runtime, this table flags at lint time, because code paths
the sweep never executes are exactly where escapes hide (the ahead-of-time
argument of PRISM-style modeling vs observed-run sampling, PAPERS.md).

``PAR`` rules belong to pass 2 (sim/real API parity); ``DET9xx`` codes are
lint-hygiene errors (stale pragmas, stale allowlist lines), so an
allow-comment can never silently rot into a blanket waiver.

``TRC``/``BUD`` rules belong to pass 3 (tracelint, :mod:`.tracelint`):
they fire on *compiled programs* — the traced jaxprs and XLA executables
of the hot-path entry points — not on source lines, because the
determinism and performance contracts of the superstep loop, donated
buffers, and the coverage fold live below the Python AST.

``SPC`` rules belong to pass 4 (speclint, :mod:`.speclint`): they fire
on *protocol state machines* — the ``actorc.spec`` declarations —
before the compiler lowers them to packed lanes. Where passes 1–3
police how code executes, pass 4 polices what the protocol *says*:
unreachable kinds, unhandled deliveries, unarmed timers, counters whose
static bound escapes their packed dtype, transitions leaning on DSL
features the lowering flattens (multi-send payloads, multi-timer arms,
>1 RNG draw), and volatile state read with no restart reconstruction.
SPC900 is the pass's own hygiene code (a stale ``lint_allow`` entry),
mirroring DET900/DET901.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple


class Rule(NamedTuple):
    code: str
    title: str
    suggestion: str


RULES: Dict[str, Rule] = {r.code: r for r in [
    Rule("DET001", "wall-clock read escapes virtual time",
         "use madsim_tpu.time (system_time/monotonic/sleep) — virtual, seeded"),
    Rule("DET002", "ambient entropy escapes the seeded RNG",
         "use madsim_tpu.rand.thread_rng() (per-world, derived from the seed)"),
    Rule("DET003", "real concurrency inside the single-threaded simulation",
         "use madsim_tpu.task.spawn / spawn_blocking (deterministic tasks)"),
    Rule("DET004", "host introspection used for sizing",
         "use madsim_tpu.task.available_parallelism() (the node's cores)"),
    Rule("DET005", "raw socket bypasses the simulated network",
         "use madsim_tpu.net (Endpoint/TcpStream) or the eventloop shim"),
    Rule("DET006", "id()/hash()-keyed ordering depends on allocation history",
         "sort by a stable field (node id, tag, name), never object identity"),
    Rule("DET007", "device profiler / wall-clock capture inside sim code",
         "profile from the observatory layer (madsim_tpu.obs.observatory "
         "ProfilerWindow / sweep(profile_dir=...)) — step code must stay "
         "free of host-time observation"),
    Rule("DET008", "blocking device sync in an orchestration hot-loop module",
         "route every device->host pull through the counted `_fetch` hook "
         "(parallel/sweep.py) so the sync-discipline tests stay honest; a "
         "deliberate site needs `detlint: allow[DET008] reason=...`"),
    Rule("DET009", "device value converted to host without going through "
         "`_fetch`",
         "fetch first (`x_h = _fetch(x)`), then convert the host copy — "
         "int()/np.asarray() on a device array is a hidden blocking sync"),
    Rule("DET900", "stale pragma: allow[...] names a rule with no finding",
         "delete the pragma (or the code that made it necessary came back)"),
    Rule("DET901", "stale allowlist entry: its path[:rule] matches no finding",
         "delete the detlint-allow.txt line — the tree it excused is clean "
         "now (or was renamed out from under it)"),
    Rule("TRC001", "host callback primitive inside a jitted sim program",
         "pure_callback/io_callback/debug_callback/debug_print re-enter the "
         "host mid-program: remove it (debug prints belong in obs/, not the "
         "step)"),
    Rule("TRC002", "backend-variant or nondeterministic primitive",
         "unstable sorts, float scatter-accumulation onto duplicate "
         "indices, approximate/stateful kernels vary across backends — "
         "use a stable, exact formulation"),
    Rule("TRC003", "numerics that change under the x64 flag",
         "pin every dtype explicitly (jnp.int32/float32) so the program "
         "is bit-identical whether or not jax_enable_x64 is set"),
    Rule("TRC004", "declared buffer donation was dropped by XLA",
         "restructure so the output can alias the donated input (XLA "
         "drops donation SILENTLY; peak memory then double-buffers)"),
    Rule("TRC005", "unannotated narrow-to-wide dtype conversion in a "
         "packed program",
         "an i8/i16 lane widened outside engine/lanes.py — an implicit "
         "promotion is leaking a narrow lane wide; read it through "
         "lanes.widen() (and write back via the saturating lanes.narrow "
         "path) so every width change is a stated decision"),
    Rule("BUD001", "program exceeds its checked-in cost budget",
         "if intentional, re-measure and regenerate analysis/budgets.json "
         "via tools/update_budgets.py --reason '...' in the same PR"),
    Rule("BUD002", "budget ledger out of sync with the program registry",
         "run tools/update_budgets.py to add/remove the program entry"),
    Rule("PAR001", "sim/real API parity drift",
         "mirror the signature in both trees — the same program must compile "
         "against either backend"),
    Rule("PAR002", "public sim API without a real-backend dispatch",
         "branch on core.backend.is_real() (directly or via a helper) so the "
         "function works outside the simulation too"),
    Rule("SPC001", "spec fails validation or abstract evaluation",
         "fix the declaration/handler the message names — the spec cannot "
         "lower until its own model is well-formed"),
    Rule("SPC010", "unreachable message kind",
         "seed it from init, emit it from a reachable transition, or delete "
         "the dead kind (and its handler)"),
    Rule("SPC011", "message kind delivered but not handled",
         "add a handler, or declare the drop deliberate via ignore=(...) on "
         "the spec — implicit drops are how real protocol bugs hide"),
    Rule("SPC012", "transition with no effects (dead no-op handler)",
         "implement it, delete it, or declare the kind in terminal=(...) if "
         "absorbing is the point"),
    Rule("SPC013", "spec declaration hygiene (ignore/terminal misuse)",
         "ignore/terminal must name declared kinds, an ignored kind cannot "
         "also be handled, and a terminal kind's handler must not emit"),
    Rule("SPC020", "timer handled but never armed on any path",
         "arm it from a transition, the on_restart hook or an init event — "
         "or delete the dead timer"),
    Rule("SPC021", "multiple timer arms without provably-disjoint conditions",
         "the lowering's single merged timer row is last-write-wins; make "
         "the arm conditions disjoint (when=cond / when=~cond) or split the "
         "transition"),
    Rule("SPC030", "written value can exceed the packed lane dtype",
         "the static bound escapes the rail lane_dtype() chose from the "
         "declared range — widen the declared range (costs a wider lane), "
         "clip the expression, or tighten the inputs"),
    Rule("SPC031", "emitted payload word can escape its declared range",
         "the receiver's arg() read assumes the declared word range; widen "
         "the Word declaration or narrow the sent expression"),
    Rule("SPC040", "multiple sends without provably-disjoint conditions",
         "the single merged message row broadcasts ONE payload per step — "
         "per-destination payloads/concurrent sends are a known DSL gap; "
         "make the send conditions disjoint or split across kinds"),
    Rule("SPC041", "more than one RNG draw in a single transition",
         "the static-draw-shape rule allows one draw per event; combine "
         "draws into one mapped value or move a draw to another kind"),
    Rule("SPC050", "volatile lane read with no on_restart reconstruction",
         "a post-restart read sees the reset value; mark the lane durable, "
         "or add an on_restart hook that rebuilds it"),
    Rule("SPC900", "stale lint_allow entry: its code suppressed nothing",
         "delete the code from the spec's lint_allow tuple (or the defect "
         "it excused came back)"),
]}


# -- pass-1 call tables ------------------------------------------------------
# Fully-qualified call name (after import-alias resolution) -> rule code.

_RANDOM_GLOBALS = (
    "random", "randint", "randrange", "choice", "choices", "shuffle",
    "uniform", "getrandbits", "sample", "randbytes", "gauss", "betavariate",
    "expovariate", "normalvariate", "triangular", "vonmisesvariate", "seed",
)

EXACT_CALLS: Dict[str, str] = {
    # DET001 — wall clock
    "time.time": "DET001",
    "time.time_ns": "DET001",
    "time.monotonic": "DET001",
    "time.monotonic_ns": "DET001",
    "time.perf_counter": "DET001",
    "time.perf_counter_ns": "DET001",
    "time.process_time": "DET001",
    "time.thread_time": "DET001",
    "time.thread_time_ns": "DET001",
    "time.sleep": "DET001",
    "datetime.datetime.now": "DET001",
    "datetime.datetime.utcnow": "DET001",
    "datetime.datetime.today": "DET001",
    "datetime.date.today": "DET001",
    # DET002 — ambient entropy
    "os.urandom": "DET002",
    "os.getrandom": "DET002",
    "uuid.uuid1": "DET002",
    "uuid.uuid4": "DET002",
    "random.SystemRandom": "DET002",
    # DET003 — real concurrency
    "threading.Thread": "DET003",
    "threading.Timer": "DET003",
    "concurrent.futures.ThreadPoolExecutor": "DET003",
    "concurrent.futures.ProcessPoolExecutor": "DET003",
    "multiprocessing.Process": "DET003",
    "multiprocessing.Pool": "DET003",
    # DET004 — host introspection used for sizing
    "os.cpu_count": "DET004",
    "os.process_cpu_count": "DET004",
    "os.sched_getaffinity": "DET004",
    "multiprocessing.cpu_count": "DET004",
    # DET005 — raw sockets
    "socket.socket": "DET005",
    "socket.create_connection": "DET005",
    "socket.socketpair": "DET005",
    "socket.create_server": "DET005",
}
EXACT_CALLS.update({f"random.{fn}": "DET002" for fn in _RANDOM_GLOBALS})

# Dotted-prefix matches (any call under the module escapes).
PREFIX_CALLS: Dict[str, str] = {
    "secrets.": "DET002",
    # DET007 — jax.profiler trace capture (and its wall-clock timeline)
    # started from simulation/engine code: the capture observes HOST
    # time and scheduling, so any code path that branches on it (or a
    # trace accidentally left running across a step) is a sim-visible
    # nondeterminism escape. The observatory's host-side emitter
    # (obs/observatory.py) is the sanctioned site, pragma'd per line.
    "jax.profiler.": "DET007",
}

# Clock-DEFAULT calls (DET001, decode-path extension for obs/ timeline
# code): these read the wall clock only when the time operand is omitted
# — with an explicit seconds/struct_time argument they are pure
# converters a timeline renderer may legitimately use on *virtual*
# timestamps. Value = (rule, max positional args at which the call still
# defaults to "now"): ``time.ctime()`` escapes, ``time.ctime(t_us)`` is
# clean; ``time.strftime(fmt)`` escapes, ``strftime(fmt, tm)`` is clean.
# Motivated by obs/timeline.py: exported timelines must be byte-stable
# across replays, so every timestamp comes from virtual time.
CLOCK_DEFAULT_CALLS: Dict[str, Tuple[str, int]] = {
    "time.ctime": ("DET001", 0),
    "time.asctime": ("DET001", 0),
    "time.localtime": ("DET001", 0),
    "time.gmtime": ("DET001", 0),
    "time.strftime": ("DET001", 1),
}

# Attribute-name matches on an unresolvable receiver: `loop` in
# `loop.run_in_executor(...)` has no static type, but the method name alone
# identifies the escape (real threads behind the event loop).
ATTR_CALLS: Dict[str, str] = {
    "run_in_executor": "DET003",
}

# Attribute calls that escape only on an *event-loop* receiver: the bare
# method name is too common to flag everywhere (`self.time()` is the shim
# loop's own virtual clock), but `loop.time()` on an asyncio loop handle
# reads the host monotonic clock. Keyed by method name; the value's
# receiver set is matched against a bare-name receiver (exact name, or a
# `_`-suffix match like `event_loop`).
LOOP_ATTR_CALLS: Dict[str, Tuple[str, Tuple[str, ...]]] = {
    "time": ("DET001", ("loop",)),
}


# -- sync-discipline tables (DET008/DET009) ----------------------------------
# The orchestration hot loops live by a counted-fetch contract (docs/perf.md
# "Pipelined orchestration"): the ONLY device->host pull per superstep is the
# `_fetch` hook, which the tier-1 sync tests monkeypatch and count. These
# modules get the extra pass; everywhere else a blocking read is just slow,
# here it silently breaks the dispatch-ahead pipeline.
HOT_LOOP_MODULES = frozenset({
    "madsim_tpu/parallel/sweep.py",
    "madsim_tpu/fleet/worker.py",
    # The fabric scheduler drives every worker quantum (ISSUE 17: the
    # per-round loop is now the fleet's only serial section) — a stray
    # device pull here would stall every worker's pipeline at once.
    "madsim_tpu/fleet/fabric.py",
    "madsim_tpu/obs/observatory.py",
    "madsim_tpu/bridge/pool.py",
})

# First-line marker opting any other file into the hot-loop pass (fixtures,
# user orchestration code): `# tracelint: hot-loop`.
HOT_LOOP_MARKER = "tracelint: hot-loop"

# Fully-qualified jax APIs that ARE a blocking sync (or hand one out).
SYNC_CALLS = frozenset({
    "jax.device_get",
    "jax.block_until_ready",
    "jax.effects_barrier",
})

# Method names that force materialization on an arbitrary receiver.
SYNC_METHODS = frozenset({"item", "block_until_ready"})

# Host-conversion callables: np.asarray(x)/np.array(x)/float(x)/... block
# when x is a device array. Flagged (DET008) when applied directly to a
# fresh jnp./jax. call result, or (DET009) to a name the module-order taint
# scan marked device-resident and never `_fetch`ed.
CONVERT_NP = frozenset({"asarray", "array", "copy"})
CONVERT_BUILTINS = frozenset({"float", "int", "bool"})

# The sanctioned pull hook: assignments FROM it mark their targets as host
# values, and calls THROUGH it are never findings.
FETCH_NAMES = frozenset({"_fetch"})

# Callees whose results are device-resident (taint sources for DET009);
# `jnp.`-rooted calls are device-typed by construction, the rest are the
# repo's device-placement helpers.
DEVICE_CALL_HEADS = frozenset({"jnp"})
DEVICE_CALLS = frozenset({
    "jax.device_put",
    "shard_worlds",
})
