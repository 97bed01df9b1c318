"""Orchestration (parallel/sweep.py, the fused hunt): the mean over the
run's hunts of ``loop_stats["dispatches"]``, the device programs the
host issued per hunt. A count the program makes."""


def read(ctx):
    if not ctx.units:
        return None
    return sum(u.dispatches for u in ctx.units) / len(ctx.units)
