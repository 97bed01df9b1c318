"""The checked-in cost-budget ledger (``analysis/budgets.json``).

PR 3 pinned ONE number — flops per world-step of the engine run loop — as
a tier-1 constant in ``tests/test_queue_insert.py``. This module
generalizes that into a ledger covering every registered hot-path program
(:mod:`.tracelint`): per program, XLA's own ``cost_analysis()`` flops and
bytes, ``memory_analysis()`` temp/peak sizes, and the donation
``alias_fraction``, each paired with an explicit budget ceiling. The
tracelint gate re-measures and diffs on every ``make lint``, so an op- or
peak-regression in a hot program fails CI *before* a bench round ever
runs — the SCALE-Sim-style "validate the cost model per change" loop
(PAPERS.md), applied to the simulator itself.

Budgets RATCHET: ``tools/update_budgets.py`` keeps an existing ceiling
whenever the fresh measurement still fits (no churn when code merely
improves) and requires a ``--reason`` line to raise one, recorded in the
ledger's ``justification`` field.

Fresh-compile caveat (docs/detlint.md): executables deserialized from the
persistent compilation cache LOSE their cost/memory statistics
(``alias_size_in_bytes`` reads 0), so every measurement here compiles
fresh via :func:`compile_fresh`, exactly like the tier-1 budget tests.
"""
from __future__ import annotations

import json
import math
import os
from typing import Any, Dict, List, Optional, Tuple

from .pragmas import Finding
from .rules import RULES

DEFAULT_LEDGER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "budgets.json")
LEDGER_SCHEMA = "madsim.tracelint.budgets/1"

# Headroom factor applied when a budget must be (re)established: wide
# enough to absorb XLA version noise, tight enough that a real op-count
# regression (the fusion-cloning failure mode of docs/perf.md r7) trips.
HEADROOM = 1.15

# Relative tolerance on the donation fraction: replicated scalar args
# shift the per-device ratio by O(bytes_scalar / bytes_state).
ALIAS_TOL = 0.005


def compile_fresh(lowered):
    """Compile BYPASSING the persistent compilation cache: an executable
    deserialized from the cache loses parts of its cost/memory statistics
    (``alias_size_in_bytes`` reads 0), which would let the budget gates
    silently pass-or-fail on cache state instead of on the program. The
    cache singleton initializes once per process and then ignores config
    updates, so it must be reset around the config flip (and reset back
    after, so later compiles re-attach to the directory cache)."""
    import jax

    from jax.experimental.compilation_cache.compilation_cache import \
        reset_cache as reset

    prev = jax.config.jax_compilation_cache_dir
    reset()
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        return lowered.compile()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        reset()


def measure_compiled(comp, unit_div: Optional[int] = None) -> Dict[str, Any]:
    """Extract the ledger metrics from a (freshly) compiled executable.

    All sizes are per-device (XLA reports the per-shard module); ratios
    — ``alias_fraction``, ``peak_over_arg`` — are therefore
    shard-invariant and the ones the gates compare. ``unit_div`` divides
    flops into a per-world figure for programs with a world axis.
    """
    ca = comp.cost_analysis()
    ma = comp.memory_analysis()
    arg = int(ma.argument_size_in_bytes)
    out_b = int(ma.output_size_in_bytes)
    temp = int(ma.temp_size_in_bytes)
    alias = int(ma.alias_size_in_bytes)
    peak = arg + out_b + temp - alias
    m: Dict[str, Any] = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "arg_bytes": arg,
        "out_bytes": out_b,
        "temp_bytes": temp,
        "alias_bytes": alias,
        "alias_fraction": round(alias / arg, 4) if arg else 0.0,
        "peak_over_arg": round(peak / arg, 4) if arg else 0.0,
    }
    if unit_div:
        m["flops_per_world"] = round(m["flops"] / unit_div, 2)
        # The packed-lane regression surface (docs/perf.md "Roofline
        # round 2"): bytes of world state per world, straight from
        # XLA's argument accounting. A lane silently widening back to
        # i32 shows up here before any bench round runs.
        m["state_bytes_per_world"] = round(arg / unit_div, 2)
    return m


# Metrics gated as ceilings (measured must stay <= budget) and the one
# gated as a floor (donation must keep landing).
CEILING_METRICS = ("flops", "flops_per_world", "state_bytes_per_world",
                   "bytes_accessed", "temp_bytes", "peak_over_arg")
FLOOR_METRICS = ("alias_fraction",)


def load_ledger(path: Optional[str] = None) -> Dict[str, Any]:
    path = path or DEFAULT_LEDGER
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    if doc.get("schema") != LEDGER_SCHEMA:
        raise ValueError(f"{path}: not a {LEDGER_SCHEMA} ledger "
                         f"(schema={doc.get('schema')!r})")
    return doc


def budget_for(ledger: Dict[str, Any], program: str,
               metric: str) -> Optional[float]:
    """One budget ceiling (or ``alias_fraction`` floor) from the ledger;
    None when absent. The tier-1 budget tests read through this, so the
    ledger is the single source of truth for every gate."""
    entry = ledger.get("programs", {}).get(program, {})
    field = entry.get(metric)
    if not isinstance(field, dict):
        return None
    key = "min" if metric in FLOOR_METRICS else "budget"
    return field.get(key)


def diff_ledger(measured: Dict[str, Dict[str, Any]],
                ledger: Dict[str, Any],
                registered: Optional[List[str]] = None,
                donates: Optional[Dict[str, bool]] = None) -> List[Finding]:
    """Compare fresh measurements against the checked-in ledger.

    - ``BUD001`` — a ceiling metric exceeds its budget.
    - ``TRC004`` — ``alias_fraction`` fell below its recorded floor on a
      program that declares donation (XLA dropped the aliasing).
    - ``BUD002`` — the ledger and the program registry drifted apart
      (measured/registered program missing from the ledger, or a ledger
      entry no registered program backs).
    """
    findings: List[Finding] = []
    programs = ledger.get("programs", {})
    donates = donates or {}

    def _f(program: str, rule: str, msg: str) -> None:
        r = RULES[rule]
        findings.append(Finding(f"trace/{program}", 0, rule,
                                f"{r.title}: {msg} — {r.suggestion}"))

    for name, m in sorted(measured.items()):
        entry = programs.get(name)
        if entry is None:
            _f(name, "BUD002", "program has no ledger entry in "
               "analysis/budgets.json")
            continue
        for metric in CEILING_METRICS:
            budget = budget_for(ledger, name, metric)
            if budget is None or metric not in m:
                continue
            if float(m[metric]) > float(budget):
                _f(name, "BUD001",
                   f"{metric} measured {m[metric]} > budget {budget} "
                   f"(ledger measured {entry[metric].get('measured')})")
        floor = budget_for(ledger, name, "alias_fraction")
        if floor is not None and donates.get(name, True):
            if float(m.get("alias_fraction", 0.0)) < float(floor) - ALIAS_TOL:
                _f(name, "TRC004",
                   f"alias_fraction measured {m.get('alias_fraction')} < "
                   f"recorded floor {floor}: a declared donation stopped "
                   "landing (peak memory now double-buffers)")
    if registered is not None:
        for name in sorted(programs):
            if name not in registered:
                _f(name, "BUD002",
                   "ledger entry names a program the registry no longer "
                   "registers")
    return findings


def make_entry(m: Dict[str, Any], note: str,
               prev: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """One ledger entry from a measurement, ratcheting existing budgets:
    a ceiling survives regeneration while the fresh measurement fits
    under it; otherwise it re-bases to ``measured * HEADROOM``."""
    prev = prev or {}
    entry: Dict[str, Any] = {"note": note}
    for metric in CEILING_METRICS:
        if metric not in m:
            continue
        val = float(m[metric])
        old = prev.get(metric, {}).get("budget") if isinstance(
            prev.get(metric), dict) else None
        if old is not None and val <= float(old):
            budget = float(old)
        elif metric == "peak_over_arg":
            budget = round(val * 1.05 + 1e-9, 3)
        elif metric == "state_bytes_per_world":
            # Arg bytes are a pure function of shapes/dtypes — no XLA
            # version noise — so the ceiling sits tight: one narrow
            # lane regressing to i32 must trip it.
            budget = float(math.ceil(val * 1.02))
        else:
            budget = float(math.ceil(val * HEADROOM))
        entry[metric] = {"measured": val, "budget": budget}
    af = float(m.get("alias_fraction", 0.0))
    old_min = prev.get("alias_fraction", {}).get("min") if isinstance(
        prev.get("alias_fraction"), dict) else None
    # The floor ratchets UP as well: if donation improved, keep the win.
    floor = round(max(float(old_min or 0.0), af - ALIAS_TOL), 4)
    entry["alias_fraction"] = {"measured": af, "min": floor}
    for k in ("arg_bytes", "out_bytes", "temp_bytes", "alias_bytes"):
        if k in m and k not in entry:
            entry[k] = m[k]
    return entry


def write_ledger(entries: Dict[str, Dict[str, Any]], reason: str,
                 path: Optional[str] = None) -> str:
    path = path or DEFAULT_LEDGER
    doc = {"schema": LEDGER_SCHEMA, "justification": reason,
           "programs": {k: entries[k] for k in sorted(entries)}}
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2, sort_keys=False)
        f.write("\n")
    os.replace(tmp, path)
    return path
