"""How ``correct`` is decided: the window's own answers against the plain
reference, and what every finished unit must show.

Each number compared has its limit; ``correct`` is true when none
exceeds it. The numbers are:

- ``rows_mismatched``: sampled rows (drawn from the seed, with each
  unit's longest-running worlds and some failing ones) whose observation
  row differs, in any field, from the reference run of the same seed
  and schedule to the same step count. Exact: limit 0.
- ``rows_live`` / ``rows_unrun`` (traffic that must finish every
  world): worlds still live, or never run, after their sweep returned.
- ``units_short`` / ``units_without_find`` (hunts): hunts that admitted
  fewer seeds than their batch holds, or that reported no failing seed.
"""
from __future__ import annotations

LIMITS = {"rows_mismatched": 0, "rows_live": 0, "rows_unrun": 0,
          "units_short": 0, "units_without_find": 0}


def reference_rows(ref, engine: dict, raft: dict, rows: list,
                   control=None) -> list:
    """The reference's row for each sampled row: the same seed and
    schedule, run until it finishes or reaches the sampled row's step
    count (a hunt stops with worlds still live)."""
    return [ref.reference_row(r["seed"], engine, raft, r["faults"] or (),
                              steps=r["row"]["steps"], control=control)
            for r in rows]


def mismatched(rows: list, ref_rows: list) -> list:
    """The sampled rows that differ from the reference, with each
    differing field as (program, reference)."""
    bad = []
    for r, want in zip(rows, ref_rows):
        diff = {f: (r["row"][f], int(v)) for f, v in want.items()
                if r["row"][f] != int(v)}
        if diff:
            bad.append({"seed": r["seed"], "diff": diff})
    return bad


def numbers(traffic: dict, units: list, n_mismatched: int) -> dict:
    """Every number compared, with its limit."""
    out = {"rows_mismatched": n_mismatched}
    exp = traffic["expect"]
    if exp.get("all_retired"):
        out["rows_live"] = sum(u.live for u in units)
        out["rows_unrun"] = sum(u.unrun for u in units)
    if "admitted_at_least" in exp:
        out["units_short"] = sum(u.admitted < exp["admitted_at_least"]
                                 for u in units)
    if exp.get("finds_per_unit"):
        out["units_without_find"] = sum(u.failing == 0 for u in units)
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in out.items()}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
