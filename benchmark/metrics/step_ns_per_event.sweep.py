"""Step program (engine/core.py, engine/queue.py, engine/raft_actor.py):
device nanoseconds of the superstep programs per simulated event.

The numerator is the device time, from the trace, of the programs named
``jit_sstep(...)`` (the shard-mapped superstep of parallel/sweep.py,
which runs the chunks of steps), summed over devices. The denominator is
the events the traced sweeps simulated: the sum of their per-seed
``steps``, which counts the steps each world took while live, so it does
not depend on how the step is implemented."""
MODULE = r"^jit_sstep\("


def read(ctx):
    if ctx.trace is None:
        return None
    ns, n = ctx.trace.module_ns(MODULE)
    events = sum(u.events for u in ctx.traced_units)
    if n == 0 or events == 0:
        return None
    return ns / events
