"""accept.ms: device time of the central accept kernel in the traced
window, per selection, averaged over the devices (the central accept runs
replicated on each)."""

from bench import trace_reduce as tr


def read(ctx):
    planes = tr.device_planes(ctx.trace)
    ns = sum(tr.op_time_ns(p, ctx.config["kernel_ops"]["accept"],
                           ctx.window_ns) for p in planes) / len(planes)
    if not ns or not ctx.selections:
        return None
    return ns / 1e6 / ctx.selections
