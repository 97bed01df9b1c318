"""tracelint tests: golden fixtures for every TRC rule and the hot-loop
sync discipline (DET008/DET009), the donation-drop mutation, the budget
ledger gates, and the tier-1 self-scan of the registered hot-path
programs.

Compile discipline: only the donation-mutation and budget-gate tests pay
fresh XLA compiles (the persistent cache must be bypassed for honest
alias/cost statistics — see analysis/budgets.py); everything else is
trace-only (make_jaxpr), which costs seconds.
"""
import importlib.util
import json
import os
import warnings

import numpy as np
import pytest

from madsim_tpu.analysis import Allowlist, run_lint, scan_source
from madsim_tpu.analysis import budgets as B
from madsim_tpu.analysis import tracelint as TL
from madsim_tpu.analysis.cli import main as detlint_main
from madsim_tpu.analysis.cli import main_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "tracelint")


def _load_fixture_module():
    spec = importlib.util.spec_from_file_location(
        "tracelint_bad_programs", os.path.join(FIXTURES, "bad_programs.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bad():
    return _load_fixture_module()


def _trace_rules(fn, *args):
    import jax

    jaxpr = jax.make_jaxpr(fn)(*args)
    return TL.check_jaxpr_rules("fixture", jaxpr.jaxpr)


def _rules(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------------------------
# Golden fixtures: each TRC rule fires on its planted violation
# ---------------------------------------------------------------------------

def test_trc001_host_callbacks_fire(bad):
    import jax.numpy as jnp

    fs = _trace_rules(bad.leaky_callback, jnp.int32(1))
    assert _rules(fs) == ["TRC001", "TRC001"], fs
    assert any("pure_callback" in f.message for f in fs)
    assert any("debug_print" in f.message for f in fs)


def test_trc001_recurses_into_scan_bodies(bad):
    import jax.numpy as jnp

    fs = _trace_rules(bad.callback_in_scan, jnp.int32(0))
    assert _rules(fs) == ["TRC001"], fs


def test_trc002_unstable_sort_fires(bad):
    import jax.numpy as jnp

    fs = _trace_rules(bad.unstable_sort, jnp.arange(8, dtype=jnp.int32))
    assert _rules(fs) == ["TRC002"], fs
    assert "is_stable" in fs[0].message


def test_trc002_float_scatter_accum_fires_int_stays_clean(bad):
    import jax.numpy as jnp

    idx = jnp.zeros((4,), jnp.int32)  # every row hits index 0: duplicates
    fs = _trace_rules(bad.float_scatter_accum,
                      jnp.zeros((8,), jnp.float32), idx,
                      jnp.ones((4,), jnp.float32))
    assert _rules(fs) == ["TRC002"], fs
    fs = _trace_rules(bad.int_scatter_accum,
                      jnp.zeros((8,), jnp.int32), idx,
                      jnp.ones((4,), jnp.int32))
    assert fs == [], fs


def _x64_findings(fn, *args):
    built = TL.Built(fn=fn, args=args)
    prog = TL.TraceProgram("fixture", "fixture", lambda: built)
    return TL.check_x64_invariance("fixture", prog, built)


def test_trc003_unpinned_sum_changes_output_dtype(bad):
    import jax.numpy as jnp

    fs = _x64_findings(bad.x64_leaky_sum, jnp.ones((8,), bool))
    assert "TRC003" in _rules(fs), fs
    assert any("output dtypes change" in f.message for f in fs)


def test_trc003_f64_intermediate_flagged(bad):
    import jax.numpy as jnp

    with warnings.catch_warnings():
        # Without x64 the f64 cast truncates with a UserWarning — that
        # silent truncation is exactly what the rule exists to expose.
        warnings.simplefilter("ignore")
        fs = _x64_findings(bad.f64_intermediate, jnp.ones((4,), jnp.float32))
    assert any(f.rule == "TRC003" and "float64" in f.message
               for f in fs), fs


def test_clean_program_has_no_findings(bad):
    import jax.numpy as jnp

    x = jnp.arange(8, dtype=jnp.int32)
    assert _trace_rules(bad.clean_program, x) == []
    assert _x64_findings(bad.clean_program, x) == []


# ---------------------------------------------------------------------------
# DET008/DET009 — hot-loop sync discipline (AST pass)
# ---------------------------------------------------------------------------

def test_hot_sync_fixture_golden_counts():
    src = open(os.path.join(FIXTURES, "hot_sync.py")).read()
    fs = scan_source(src, "hot_sync.py")  # marker auto-enables the pass
    counts = {}
    for f in fs:
        counts[f.rule] = counts.get(f.rule, 0) + 1
    assert counts == {"DET008": 3, "DET009": 1}, \
        "\n".join(f.render() for f in fs)


def test_hot_pass_off_for_unmarked_modules():
    src = ("import jax\n"
           "x = jax.device_get(1)\n")
    assert scan_source(src, "cold_module.py") == []
    assert [f.rule for f in scan_source(src, "cold.py", hot=True)] \
        == ["DET008"]


def test_repo_hot_modules_are_in_the_pass_and_clean():
    """The three orchestration modules run the sync pass by path and are
    clean modulo their reason= pragmas — i.e. the counted-fetch contract
    the runtime tests enforce dynamically holds statically too."""
    from madsim_tpu.analysis.rules import HOT_LOOP_MODULES

    assert "madsim_tpu/parallel/sweep.py" in HOT_LOOP_MODULES
    # The bridge pool's parent round loop lives by the same counted-fetch
    # contract (bridge/pool.py `_fetch` seam; PR 15) — keep it in the
    # pass by path, and marker-opted-in at its first line too.
    assert "madsim_tpu/bridge/pool.py" in HOT_LOOP_MODULES
    from madsim_tpu.analysis.escape import is_hot_loop_module

    src = open(os.path.join(REPO, "madsim_tpu/bridge/pool.py")).read()
    assert is_hot_loop_module("anywhere/pool.py", src)  # marker opt-in
    for rel in sorted(HOT_LOOP_MODULES):
        src = open(os.path.join(REPO, rel)).read()
        fs = scan_source(src, rel)
        assert fs == [], "\n".join(f.render() for f in fs)


def test_det008_pragma_requires_reason():
    src = ("# tracelint: hot-loop\n"
           "import jax\n"
           "_fetch = jax.device_get  # detlint: allow[DET008]\n")
    (f,) = scan_source(src, "hot.py")
    assert f.rule == "DET900" and "reason=" in f.message
    src = src.replace("allow[DET008]", "allow[DET008] reason=test hook")
    assert scan_source(src, "hot.py") == []


def test_taint_clears_through_fetch():
    src = ("# tracelint: hot-loop\n"
           "import jax.numpy as jnp\n"
           "def f(_fetch, x):\n"
           "    y = jnp.sum(x)\n"
           "    y = _fetch(y)\n"
           "    return int(y)\n")
    assert scan_source(src, "hot.py") == []


# ---------------------------------------------------------------------------
# DET901 — stale allowlist entries
# ---------------------------------------------------------------------------

def test_stale_allowlist_entry_flagged(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "clean.py").write_text("x = 1\n")
    (pkg / "dirty.py").write_text("import time\nt = time.time()\n")
    allow = Allowlist.parse("pkg/dirty.py:DET001\n"
                            "pkg/ghost.py:DET002\n"        # stale
                            "elsewhere/unscanned.py\n")    # not covered
    fs = run_lint(str(tmp_path), ["pkg"], allow)
    assert [f.rule for f in fs] == ["DET901"]
    assert "ghost.py" in fs[0].message and fs[0].line == 2


def test_repo_allowlist_has_no_stale_entries():
    allow = Allowlist.load(os.path.join(REPO, "detlint-allow.txt"))
    fs = run_lint(REPO, ["madsim_tpu", "tools"], allow)
    assert [f for f in fs if f.rule == "DET901"] == [], \
        "\n".join(f.render() for f in fs)


# ---------------------------------------------------------------------------
# TRC004 — the donation-drop mutation is caught
# ---------------------------------------------------------------------------

def _scratch_ledger(alias_min):
    return {"schema": B.LEDGER_SCHEMA, "justification": "test",
            "programs": {"engine.scratch": {
                "alias_fraction": {"measured": 1.0, "min": alias_min}}}}


def test_donation_drop_mutation_is_caught():
    """A scratch copy of the run entry point with its donation
    declaration broken (plain jit, no donate_argnums) must trip TRC004
    against the recorded alias floor; the intact entry point must not.
    Both compile FRESH — a cache-deserialized executable reads alias 0
    and would flag the healthy program too."""
    import jax

    eng = TL._bug_engine()
    state = eng.init(np.arange(8))
    intact = B.measure_compiled(
        B.compile_fresh(eng._run.lower(state, 50)))
    broken_fn = jax.jit(eng._run_impl, static_argnums=1)  # donation dropped
    broken = B.measure_compiled(
        B.compile_fresh(broken_fn.lower(state, 50)))

    ledger = _scratch_ledger(alias_min=0.995)
    ok = B.diff_ledger({"engine.scratch": intact}, ledger,
                       donates={"engine.scratch": True})
    assert ok == [], ok
    bad = B.diff_ledger({"engine.scratch": broken}, ledger,
                        donates={"engine.scratch": True})
    assert [f.rule for f in bad] == ["TRC004"], bad
    assert broken["alias_fraction"] < 0.01  # the drop really is total
    assert intact["alias_fraction"] > 0.999


# ---------------------------------------------------------------------------
# The budget ledger gates
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_run_measured():
    """ONE fresh compile of the ledger's engine.run program, shared by
    the budget-gate tests below (fresh compiles are the expensive part
    of this file)."""
    prog = TL.registry()["engine.run"]
    return TL.measure_program("engine.run", prog)


def test_ledger_passes_on_current_program(engine_run_measured):
    ledger = B.load_ledger()
    fs = B.diff_ledger({"engine.run": engine_run_measured}, ledger,
                       donates={"engine.run": True})
    assert fs == [], "\n".join(f.render() for f in fs)


def test_tampered_ledger_fails_budget_gate(engine_run_measured):
    """`make lint` must fail when a hot program's flops exceed the
    ledger: tighten the checked-in budget below the fresh measurement
    and the diff must report BUD001 (same code path the CLI gates on)."""
    ledger = json.loads(json.dumps(B.load_ledger()))  # deep copy
    entry = ledger["programs"]["engine.run"]
    entry["flops_per_world"]["budget"] = \
        engine_run_measured["flops_per_world"] * 0.5
    entry["temp_bytes"]["budget"] = 1
    fs = B.diff_ledger({"engine.run": engine_run_measured}, ledger,
                       donates={"engine.run": True})
    assert sorted(f.rule for f in fs) == ["BUD001", "BUD001"], fs
    assert all("budget" in f.message for f in fs)


def test_ledger_and_registry_agree():
    """BUD002 structure contract: the checked-in ledger covers exactly
    the budget-tracked programs (so `trace` can never silently skip a
    hot program), and drift in either direction is a finding."""
    ledger = B.load_ledger()
    reg = TL.registry()
    budget_progs = {k for k, p in reg.items() if p.budget}
    assert set(ledger["programs"]) == budget_progs
    # A measured program missing from the ledger:
    fs = B.diff_ledger({"new.prog": {"flops": 1.0}},
                       {"schema": B.LEDGER_SCHEMA, "programs": {}})
    assert [f.rule for f in fs] == ["BUD002"]
    # A ledger entry no registered program backs:
    fs = B.diff_ledger({}, ledger, registered=["engine.run"])
    assert fs and all(f.rule == "BUD002" for f in fs)


def test_budget_ratchet_and_rebase():
    """Regeneration keeps a still-fitting ceiling (no churn on
    improvement) and re-bases with headroom only when exceeded."""
    prev = {"flops": {"measured": 100.0, "budget": 120.0}}
    kept = B.make_entry({"flops": 90.0, "alias_fraction": 1.0},
                        "n", prev)
    assert kept["flops"]["budget"] == 120.0
    moved = B.make_entry({"flops": 200.0, "alias_fraction": 1.0},
                         "n", prev)
    assert moved["flops"]["budget"] == float(int(200.0 * B.HEADROOM + 1))


# ---------------------------------------------------------------------------
# The tier-1 self-scan: the repo's own programs are clean
# ---------------------------------------------------------------------------

def test_self_scan_trace_rules_clean():
    """Every registered hot-path program — engine run/push_many, both
    superstep variants, the coverage folds, compactor, refill select,
    bridge step/drain — passes TRC001-003 with zero findings. Trace-only
    (no XLA compiles): the budget/donation leg runs in `make tracelint`
    where its fresh-compile cost belongs."""
    findings, measured = TL.run_trace(budget_check=False)
    assert findings == [], "\n".join(f.render() for f in findings)
    assert measured == {}


def test_registry_covers_the_hot_paths():
    names = set(TL.registry())
    for required in ("engine.run", "engine.run_snapshot",
                     "engine.pallas_step", "engine.push_many",
                     "engine.refill_select", "sweep.superstep",
                     "sweep.superstep_min_one", "sweep.superstep_coverage",
                     "sweep.coverage_endfold", "sweep.compactor",
                     "bridge.step", "bridge.drain"):
        assert required in names, f"{required} missing from the registry"


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------

def test_trace_cli_list_programs(capsys):
    assert main_trace(["--list-programs"]) == 0
    out = capsys.readouterr().out
    assert "engine.run" in out and "bridge.step" in out
    assert "[budget,donates]" in out


def test_trace_cli_unknown_program_is_usage_error(capsys):
    assert main_trace(["--programs", "no.such.prog", "--no-budgets"]) == 2


def test_trace_cli_single_program_json(capsys):
    rc = main_trace(["--programs", "engine.push_many", "--no-budgets",
                     "--json"])
    assert rc == 0
    assert json.loads(capsys.readouterr().out) == []


def test_github_format_annotations(capsys):
    rc = detlint_main(["--root", os.path.join(REPO, "tests", "fixtures",
                                              "detlint"),
                       "--no-parity", "--format=github", "bad_socket.py"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "::error file=bad_socket.py,line=" in out
    assert "title=DET005" in out
