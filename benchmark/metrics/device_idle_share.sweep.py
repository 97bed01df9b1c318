"""Device (TPU v5e): 1 less the union of the device's busy intervals
(its executed programs) over the traced window, averaged over the
devices; in the sweep cells."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.idle_share()
