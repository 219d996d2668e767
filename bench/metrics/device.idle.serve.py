"""device.idle.serve: 1 - (union of the device's busy intervals / traced
window), averaged over the devices, in percent."""

from bench import trace_reduce as tr


def read(ctx):
    return 100.0 * tr.idle_share(ctx.trace, ctx.window_ns)
