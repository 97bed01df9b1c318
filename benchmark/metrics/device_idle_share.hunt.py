"""Device (TPU v5e): 1 less the share of the hunts' own time in which
the device ran a program, averaged over the devices; in the hunt cell.

The time is the union of the ``bench:sweep`` host spans, each from a
hunt's ``sweep()`` call until its failing seeds are on the host: the
interval ``hunt_s_p95`` times. The harness's own work between hunts
(the next seed range, recording the finished hunt) is left out."""
SPAN = "bench:sweep"


def read(ctx):
    return None if ctx.trace is None else ctx.trace.idle_share(within=SPAN)
