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
        dryrun determinism dualmode native clean replay-demo bench-diff \
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
#    regression fails `make lint` before a bench round ever runs.
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

smoke:
	$(PY) bench.py --smoke > /tmp/bench_smoke.json
	@tail -1 /tmp/bench_smoke.json | $(PY) -c "import json,sys; \
	d=json.load(sys.stdin); assert d['value'], d; \
	bad={k: v for k, v in d['configs'].items() if isinstance(v, dict) \
	     and ({'error', 'dev_error', 'host_error'} & set(v))}; \
	assert not bad, f'configs failed: {bad}'; \
	print('smoke ok:', d['value'], d['unit'])"
	@$(PY) -c "import json; d=json.load(open('bench_results.json')); \
	missing={'metric','value','unit','vs_baseline','configs'}-set(d); \
	assert not missing, f'bench_results.json missing {missing}'; \
	xc=[d['configs'][k].get('xla_cost') for k in \
	    ('time_to_first_bug','madraft_5node')]; \
	need={'flops_per_step','flops_per_world_step','peak_bytes_est', \
	      'argument_size_bytes','aliased_bytes', \
	      'state_bytes_per_world','packed'}; \
	assert all(isinstance(x,dict) and need<=set(x) for x in xc), \
	    f'xla_cost records missing/incomplete: {xc}'; \
	sl=[d['configs'][k].get('sweep_loop') for k in \
	    ('time_to_first_bug','madraft_5node')]; \
	sneed={'device_wait_s','host_decision_s','dispatch_depth', \
	       'dispatches_per_seed','seeds_per_dispatch','epochs_on_device', \
	       'chunks','dispatches','chunks_per_dispatch','loop_wall_s'}; \
	assert all(isinstance(x,dict) and sneed<=set(x) for x in sl), \
	    f'sweep_loop records missing/incomplete: {sl}'; \
	sm=[d['configs'][k].get('sim_metrics') for k in \
	    ('time_to_first_bug','madraft_5node')]; \
	mneed={'msgs_sent','msgs_delivered','timer_fires','kind_hist', \
	       'fault_hist','enqueued','vtime_us'}; \
	assert all(isinstance(x,dict) and mneed<=set(x) for x in sm), \
	    f'sim_metrics records missing/incomplete: {sm}'; \
	cv=[d['configs'][k].get('coverage') for k in \
	    ('time_to_first_bug','madraft_5node')]; \
	assert all(isinstance(x,dict) and x.get('distinct_behaviors',0)>1 \
	           for x in cv), f'coverage records missing/flat: {cv}'; \
	bb=d['configs']['time_to_first_bug'].get('blackbox'); \
	bneed={'k','seeds_per_sec','seeds_per_sec_off','seeds_per_sec_ratio', \
	       'state_bytes_per_world','state_bytes_per_world_off', \
	       'state_bytes_per_world_delta','flops_per_world_step', \
	       'flops_per_world_step_off','flops_per_world_step_delta'}; \
	assert isinstance(bb,dict) and bneed<=set(bb), \
	    f'blackbox record missing/incomplete: {bb}'; \
	gh=d['configs'].get('guided_hunt'); \
	assert isinstance(gh,dict) and {'pair','raft'}<=set(gh), \
	    f'guided_hunt record missing/incomplete: {gh}'; \
	p=gh['pair']; \
	assert p.get('guided_seeds_to_bug') and \
	    (p.get('random_seeds_to_bug') is None or \
	     p['guided_seeds_to_bug']<p['random_seeds_to_bug']), \
	    f'guided search did not beat random on the pair family: {p}'; \
	px=gh.get('paxos'); \
	assert isinstance(px,dict) and px.get('guided_seeds_to_bug') and \
	    (px.get('random_seeds_to_bug') is None or \
	     px['guided_seeds_to_bug']<px['random_seeds_to_bug']), \
	    f'guided did not beat random on the actorc Paxos family: {px}'; \
	assert px.get('guided_lineage_depth',0)>=1, \
	    f'paxos find has no ancestry depth: {px.get(\"guided_lineage_depth\")}'; \
	rneed={'guided_bugs_found','random_bugs_found', \
	       'guided_novelty_area','random_novelty_area'}; \
	assert rneed<=set(gh['raft']), f'guided_hunt raft leg: {gh[\"raft\"]}'; \
	bp=d['configs']['bridge_sweep'].get('pool'); \
	bneed={'bridge_vs_host','pool_overhead_frac','seeds_per_sec', \
	       'host_ms_per_round','pack_ms_per_round','dispatch_ms_per_round', \
	       'settle_ms_per_round','parent_ms_per_round'}; \
	assert isinstance(bp,dict) and {'j1_w64','j2_w64'}<=set(bp) and \
	    all(bneed<=set(v) for v in bp.values()), \
	    f'bridge pool record missing/incomplete: {bp}'; \
	dsp={'seeds_per_dispatch','epochs_on_device'}; \
	assert dsp<=set(p.get('sweep_loop',{})), \
	    f'guided_hunt pair sweep_loop missing {dsp}: {p.get(\"sweep_loop\")}'; \
	slf=d['configs']['time_to_first_bug'].get('sweep_loop_fused'); \
	assert isinstance(slf,dict) and slf.get('fused') and \
	    dsp<=set(slf), f'fused sweep_loop record missing/incomplete: {slf}'; \
	ls=p.get('guided_operator_stats'); \
	assert isinstance(ls,dict) and {'splice','node_rotate'}<=set(ls) \
	    and all({'produced','novel','survived','bug'}<=set(v) \
	            for v in ls.values()), \
	    f'guided_hunt operator_stats missing/incomplete: {ls}'; \
	assert p.get('guided_lineage_depth',0)>=1, \
	    f'guided find has no ancestry depth: {p.get(\"guided_lineage_depth\")}'; \
	gf=d['configs'].get('guided_fleet'); \
	fneed={'exchanged_seeds_to_bug','independent_seeds_to_bug', \
	       'exchanged_bugs_found','independent_bugs_found', \
	       'exchange_overhead_frac','epochs_merged','publishes', \
	       'lineage_depth','operator_stats'}; \
	assert isinstance(gf,dict) and fneed<=set(gf), \
	    f'guided_fleet record missing/incomplete: {gf}'; \
	assert gf.get('exchanged_seeds_to_bug') and \
	    gf['exchanged_bugs_found']>=gf['independent_bugs_found'], \
	    f'exchanged fleet did not hold the cross-range gate: {gf}'; \
	fs=d['configs'].get('fleet_sweep'); \
	fsneed={'fabric_overhead_frac','acquire_ms','sweep_ms','merge_ms', \
	        'rpcs_per_lease','control_rpcs_per_lease', \
	        'session_reuse_hits','leases_prefetched','grouped_leases'}; \
	assert isinstance(fs,dict) and fsneed<=set(fs), \
	    f'fleet_sweep cost-model record missing/incomplete: {fs}'; \
	assert fs['session_reuse_hits']>=1 and fs['leases_prefetched']>=1, \
	    f'fleet fabric disciplines inactive: {fs}'; \
	from madsim_tpu.fleet import MAX_CONTROL_RPCS_PER_LEASE as M; \
	assert fs['control_rpcs_per_lease']<=M, \
	    f'control plane over budget ({M}/lease): {fs}'; \
	print('bench_results.json ok:', d['metric'])"
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

# Regression table between two bench rounds (tools/bench_diff.py):
# compares seeds/s, utilization, xla_cost flops/bytes, sweep_loop stalls
# and coverage. Default (--auto) diffs the newest BENCH_r*.json round
# against bench_results.json when present, else the two newest rounds.
# CI runs it after smoke whenever a previous round artifact exists.
bench-diff:
	$(PY) tools/bench_diff.py --auto

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
	rm -f madsim_tpu/native/_core.so /tmp/bench_smoke.json
