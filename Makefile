# CI harness (reference analog: .github/workflows/ci.yml:66-125 + Makefile).
# `make check` is the snapshot gate: every target must pass before a commit
# that touches runtime behavior ships. Nonzero exit on any failure.

PY ?= python
# Tests and the determinism sweep run on a virtual 8-device CPU mesh so they
# pass anywhere (tests/conftest.py pins this too; exporting here covers the
# non-pytest entry points).
CPU_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
# The persistent XLA compilation cache needs nothing here: every entry
# point follows madsim_tpu/parallel/compile_cache.py at package import
# ($JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache).

.PHONY: check lint detlint tracelint speclint speclint-demo test smoke \
        dryrun determinism dualmode native clean replay-demo \
        chaos chaos-full triage-demo fuzz-demo actorc-demo \
        bridge-pool-demo

check: lint test smoke dryrun determinism
	@echo "ALL CHECKS PASSED"

# The static gate, four passes in three legs (docs/detlint.md):
#  - detlint: AST passes — nondeterminism escapes (DET*), sim/real API
#    parity (PAR*), hot-loop sync discipline (DET008/DET009).
#  - tracelint: program-level pass — jaxpr rules over the compiled
#    hot-path programs (TRC*), donation contracts, and the checked-in
#    cost-budget ledger analysis/budgets.json (BUD*). Budget programs
#    compile FRESH (the persistent cache strips cost/alias stats), so
#    this leg costs real compile time — that is the point: an op-budget
#    regression fails `make lint` before any chip run.
#  - speclint: protocol-level pass (docs/speclint.md) — the shipped
#    actorc family specs verified BEFORE lowering: reachability,
#    exhaustiveness, timer discipline, lane-capacity proofs, RNG/effect
#    budgets, durability flow (SPC*).
# Zero findings required; intentional sites are covered by
# detlint-allow.txt and inline `detlint: allow[RULE]` pragmas.
lint: detlint tracelint speclint

detlint:
	$(PY) -m madsim_tpu.analysis madsim_tpu tools

tracelint:
	$(CPU_ENV) $(PY) tools/update_budgets.py --check

speclint:
	$(CPU_ENV) $(PY) -m madsim_tpu.analysis spec

# Pass 4's protocol card for the Paxos family — the kinds x handlers
# matrix, timer graph and lane budget table, rendered byte-stably (CI
# runs it twice and diffs: the static profile must not wobble).
speclint-demo:
	$(CPU_ENV) $(PY) -m madsim_tpu.analysis spec --card paxos

test:
	$(PY) -m pytest tests/ -x -q

# The sim/real matrix on its own (also part of `test`): the same worlds
# executed inside a seeded simulation AND over real asyncio + TCP.
dualmode:
	$(PY) -m pytest tests/test_dualmode.py -q

# The Pallas step in interpret mode (tools/pallas_smoke.py). Speed is
# measured by benchmark/run.py (BENCHMARK.json), the chip is checked
# against the CPU by chip_smoke.py, and the search, fleet and coverage
# gates are tier-1 tests (tests/test_search.py, test_exchange.py,
# test_fleet.py, test_obs.py, test_fused.py).
smoke:
	$(CPU_ENV) $(PY) tools/pallas_smoke.py

# Fleet chaos matrix (docs/fleet.md): worker kills, lease expiries +
# re-issues, duplicated completions, SIGTERM preemptions, torn
# checkpoints — asserting the merged SweepResult stays bitwise identical
# to a crash-free fleet AND a single-host sweep, for raft/pb/tpc on the
# CPU mesh. CI runs this after smoke; `make test` covers the same
# contract via tests/test_fleet.py. chaos-full adds the multiprocess
# leg (real worker processes + SIGKILL; slower — each worker re-imports
# JAX).
chaos:
	$(CPU_ENV) $(PY) tools/chaos_matrix.py

chaos-full:
	$(CPU_ENV) $(PY) tools/chaos_matrix.py --process

# End-to-end failure-triage workflow (docs/triage.md): inject the
# known-minimal synthetic bug, hunt it with one pipelined sweep, dedupe
# the failures into classes, batch-ddmin one representative per class
# (must converge to EXACTLY the two load-bearing schedule rows), and
# replay the minimized bundle through `python -m madsim_tpu.obs replay`
# in a fresh process — nonzero exit unless the recorded failure
# reproduces from the minimized schedule. CI runs this after chaos.
triage-demo:
	$(CPU_ENV) $(PY) tools/triage_demo.py

# The closed fuzzer loop end to end (docs/search.md; ROADMAP item 2):
# inject the pair-restart family (bug reachable ONLY through schedule
# mutation), run the coverage-guided hunt vs the matched random-mutation
# baseline — guided must reach the bug in strictly fewer seeds — then
# triage the find to a verified 1-minimal bundle and replay it in a
# fresh process; plus the seeded raft double-vote leg, where guided must
# out-hunt random (failing seeds at the same budget). Nonzero exit on
# any miss. CI runs this after triage-demo.
fuzz-demo:
	$(CPU_ENV) $(PY) tools/fuzz_demo.py

# The actor compiler end to end (docs/actorc.md; ROADMAP item 3):
# build the multi-decree Paxos spec, compile it, crosscheck the device
# actor against its generated host twin per event (bitwise), run the
# guided hunt over the forgetful-acceptor consistency violation —
# guided must reach the bug in strictly fewer seeds than the matched
# random baseline — then triage the find to a verified 1-minimal
# bundle and replay it through `python -m madsim_tpu.obs replay` in a
# fresh process. Nonzero exit on any miss. CI runs this after
# fuzz-demo.
actorc-demo:
	$(CPU_ENV) $(PY) tools/actorc_demo.py

# The bridge worker pool end to end (docs/bridge.md "Parallel task
# bodies"; ROADMAP item 4): a mixed-outcome suite (values, raises,
# deadlocks, lossy-RPC send accounting) swept serial, pooled jobs=1,
# and pooled jobs=2 (uneven W%J split) must be BITWISE identical on
# traces + outcomes, with and without batch recycling; then SIGKILL a
# worker mid-round and assert the pointed BridgePoolError (worker /
# slot range / round) with every shared-memory segment unlinked.
# Nonzero exit on any miss. CI runs this after actorc-demo.
bridge-pool-demo:
	$(CPU_ENV) $(PY) tools/bridge_pool_demo.py

# End-to-end repro-bundle workflow (docs/observability.md): sweep a known
# buggy config, write a repro bundle for a failing seed, replay it through
# `python -m madsim_tpu.obs replay`, and validate the exported Chrome
# trace ends at the invariant raise.
replay-demo:
	$(CPU_ENV) $(PY) tools/replay_demo.py

dryrun:
	$(PY) -c "from __graft_entry__ import dryrun_multichip, entry; \
	          dryrun_multichip(8); print('dryrun_multichip(8) ok'); \
	          import jax; fn, args = entry(); \
	          jax.jit(fn).lower(*args).compile(); print('entry() compiles')"

determinism:
	$(CPU_ENV) MADSIM_TEST_NUM=8 MADSIM_TEST_SEED=0 \
	MADSIM_TEST_CHECK_DETERMINISM=1 $(PY) tools/determinism_sweep.py

native:
	$(PY) -c "from madsim_tpu import native; \
	          assert native.available(), 'native core failed to build'; \
	          print('native core built:', native._SO)"

clean:
	rm -f madsim_tpu/native/_core.so
