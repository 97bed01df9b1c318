#!/usr/bin/env python
"""End-to-end closed-fuzzer-loop demo: inject → guided hunt → triage.

The `make fuzz-demo` target (docs/search.md "The guided workflow") — the
acceptance gate of ROADMAP item 2. Exits nonzero on any miss.

1. INJECT: the pair-restart family (search/family.py) — the invariant
   needs two specific node restarts; the template restarts only filler
   nodes, so NO fixed-schedule sweep can ever reach the bug: only the
   search's mutation operators can.
2. HUNT: coverage-guided `sweep(recycle=True, search=...)` vs the
   MATCHED random-mutation baseline (same operators, rates and budget,
   no feedback) — guided must reach the bug in strictly fewer seeds.
3. TRIAGE: the find pipes unchanged through `triage.triage` — the
   materialized child schedule ddmins to a verified 1-minimal bundle
   (exactly the two target restarts), which must replay through
   `python -m madsim_tpu.obs replay` in a fresh process.
4. RAFT: the seeded double-vote hunt (search/hunts.py raft_hunt):
   guided must find strictly more failing seeds than random at the
   same budget (first-bug ties are expected — generation-1 children
   are shared by construction).
"""
import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BUDGET = 512

# The pinned search numbers (ROADMAP item 2; docs/search.md): the
# guided hunts are bitwise-deterministic, so these exact values hold
# until MUTATION/GENERATION code changes — at which point the PR 11
# retune-and-re-pin rule applies: retune search/family.py + hunts.py,
# re-measure with `make fuzz-demo`, and re-pin here, in
# tests/test_search.py AND in the ROADMAP recap. A drift WITHOUT a mutation-code change means search
# semantics regressed silently — that is what this gate exists to catch
# (PR 12 satellite: exchange/fleet work must not move these).
PIN_PAIR_GUIDED = 73    # guided seeds-to-bug, pair family
PIN_PAIR_RANDOM = 409   # random seeds-to-bug, pair family
PIN_RAFT_GUIDED = 6     # guided failing seeds at budget, seeded raft
PIN_RAFT_RANDOM = 3     # random failing seeds at budget, seeded raft


def main() -> int:
    import numpy as np

    from madsim_tpu.engine import DeviceEngine
    from madsim_tpu.parallel.sweep import sweep
    from madsim_tpu.search.family import GuidedPairConfig, HUNT_NODES
    from madsim_tpu.search.hunts import pair_hunt, raft_hunt
    from madsim_tpu.triage import triage

    def run(hunt, guided, stop):
        eng = engines.setdefault(hunt.name,
                                 DeviceEngine(hunt.actor, hunt.cfg))
        return sweep(None, hunt.cfg, np.arange(BUDGET), engine=eng,
                     faults=hunt.template, stop_on_first_bug=stop,
                     search=hunt.search(guided), **hunt.sweep_kw)

    engines = {}

    # -- 1+2: the pair family, guided vs random ------------------------
    pair = pair_hunt()
    g = run(pair, guided=True, stop=True)
    r = run(pair, guided=False, stop=True)
    g_seeds = (g.failing_seeds[0] + 1) if g.failing_seeds else None
    r_seeds = (r.failing_seeds[0] + 1) if r.failing_seeds else None
    print(f"fuzz-demo: pair family @ {BUDGET} seeds: guided found the "
          f"bug at seed {g_seeds}, random at "
          f"{r_seeds if r_seeds else f'>{BUDGET} (not found)'}",
          file=sys.stderr)
    if g_seeds is None:
        print("fuzz-demo: guided search missed the pair bug in budget",
              file=sys.stderr)
        return 1
    if r_seeds is not None and g_seeds >= r_seeds:
        print(f"fuzz-demo: guided ({g_seeds}) did not beat random "
              f"({r_seeds}) on the pair family", file=sys.stderr)
        return 1
    if (g_seeds, r_seeds) != (PIN_PAIR_GUIDED, PIN_PAIR_RANDOM):
        print(f"fuzz-demo: pair seeds-to-bug drifted off the pinned "
              f"numbers: got guided={g_seeds} random={r_seeds}, pinned "
              f"{PIN_PAIR_GUIDED}/{PIN_PAIR_RANDOM}. If mutation/"
              f"generation code changed deliberately, retune and re-pin "
              f"(see the constants above); otherwise search semantics "
              f"regressed.", file=sys.stderr)
        return 1

    # -- 2b: the find's lineage (obs/lineage.py, docs/search.md
    # "Reading the lineage"): the ancestry chain must reach a
    # generation-0 template parent and name at least one mutation
    # operator — the pair bug is UNREACHABLE without mutation, so an
    # operator-free chain means provenance accounting broke.
    from madsim_tpu.obs.lineage import render_operator_table, render_tree

    chain = g.search.ancestry(g.failing_seeds[0], seeds=g.seeds)
    print("fuzz-demo: find derivation:\n"
          + render_tree(chain), file=sys.stderr)
    print(render_operator_table(g.search.operator_stats), file=sys.stderr)
    if chain[-1].get("kind") != "template":
        print(f"fuzz-demo: ancestry chain does not terminate at the "
              f"generation-0 template: {chain[-1]}", file=sys.stderr)
        return 1
    chain_ops = {op for node in chain for op in node.get("ops", [])}
    if not chain_ops:
        print("fuzz-demo: the find's ancestry names NO mutation "
              "operators — the pair bug cannot be reached without "
              "mutation, so the lineage lanes are broken",
              file=sys.stderr)
        return 1
    bug_ops = {name for name, row in g.search.operator_stats.items()
               if row["bug"] > 0}
    if not bug_ops:
        print("fuzz-demo: operator outcome table credits no operator "
              "with the find (bug row all zero)", file=sys.stderr)
        return 1

    # -- 3: triage the guided find to a 1-minimal replayable bundle ----
    with tempfile.TemporaryDirectory() as td:
        report = triage(g, out_dir=td, chunk_steps=32, max_steps=20_000)
        print(report.summary(), file=sys.stderr)
        if len(report.classes) != 1:
            print(f"fuzz-demo: expected ONE failure class, got "
                  f"{len(report.classes)}", file=sys.stderr)
            return 1
        key = report.classes[0].key
        mr = report.minimized[key]
        acfg = GuidedPairConfig(n=HUNT_NODES)
        targets = sorted(int(x) for x in mr.schedule[:, 2])
        if mr.final_rows != 2 or not mr.one_minimal or \
                targets != [acfg.node_a, acfg.node_b]:
            print(f"fuzz-demo: minimizer returned {mr.final_rows} rows "
                  f"targeting {targets} (want 2 rows, targets "
                  f"{[acfg.node_a, acfg.node_b]}, 1-minimal); "
                  f"{mr.summary()}", file=sys.stderr)
            return 1
        bundle_path = report.bundles[key]
        with open(bundle_path, encoding="utf-8") as f:
            bundle = json.load(f)
        block = bundle.get("minimization") or {}
        if block.get("final_rows") != 2:
            print(f"fuzz-demo: bundle minimization block off: {block}",
                  file=sys.stderr)
            return 1
        lin_block = bundle.get("lineage") or {}
        if lin_block.get("schema") != "madsim.search.lineage/1" or \
                not lin_block.get("operators_applied") or \
                (lin_block.get("chain") or [{}])[-1].get("kind") \
                != "template":
            print(f"fuzz-demo: bundle lineage block missing/incomplete: "
                  f"{ {k: lin_block.get(k) for k in ('schema', 'operators_applied')} }",
                  file=sys.stderr)
            return 1
        trace_path = os.path.join(td, "trace.json")
        proc = subprocess.run(
            [sys.executable, "-m", "madsim_tpu.obs", "replay",
             "--bundle", bundle_path, "--out", trace_path],
            env={**os.environ}, capture_output=True, text=True)
        sys.stderr.write(proc.stderr)
        if proc.returncode != 0:
            print(f"fuzz-demo: CLI replay of the minimized bundle "
                  f"failed rc={proc.returncode}", file=sys.stderr)
            return 1
        print(f"fuzz-demo: guided find minimized "
              f"{block['original_rows']} -> {block['final_rows']} rows "
              f"in {block['rounds']} rounds and replayed", file=sys.stderr)

    # -- 4: the seeded raft double-vote, bugs-at-budget ----------------
    raft = raft_hunt()
    gr = run(raft, guided=True, stop=False)
    rr = run(raft, guided=False, stop=False)
    g_bugs, r_bugs = len(gr.failing_seeds), len(rr.failing_seeds)
    print(f"fuzz-demo: seeded raft double-vote @ {BUDGET} seeds: "
          f"guided found {g_bugs} failing seeds, random {r_bugs}",
          file=sys.stderr)
    if g_bugs <= r_bugs:
        print("fuzz-demo: guided search did not out-hunt random on the "
              "seeded raft bug", file=sys.stderr)
        return 1
    if (g_bugs, r_bugs) != (PIN_RAFT_GUIDED, PIN_RAFT_RANDOM):
        print(f"fuzz-demo: raft bugs-at-budget drifted off the pinned "
              f"numbers: got guided={g_bugs} random={r_bugs}, pinned "
              f"{PIN_RAFT_GUIDED}/{PIN_RAFT_RANDOM} — retune and re-pin "
              f"if mutation code changed, else investigate the "
              f"regression.", file=sys.stderr)
        return 1

    print(f"fuzz-demo ok: pair bug at seed {g_seeds} guided vs "
          f"{r_seeds if r_seeds else f'>{BUDGET}'} random "
          f"(>= {((r_seeds or BUDGET + 1) / g_seeds):.1f}x fewer seeds), "
          f"1-minimal bundle replayed; raft {g_bugs} vs {r_bugs} "
          f"failing seeds at the same budget")
    return 0


if __name__ == "__main__":
    sys.exit(main())
