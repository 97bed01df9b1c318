"""Orchestration (parallel/sweep.py): the mean over the run's sweeps of
``SweepResult.world_utilization``, the share of issued slot-steps that
advanced a live world. A count the program makes; it does not depend on
the clock. Every sweep of a cell has the same width."""


def read(ctx):
    if not ctx.units:
        return None
    return sum(u.utilization for u in ctx.units) / len(ctx.units)
