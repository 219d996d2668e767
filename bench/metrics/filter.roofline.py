"""filter.roofline: the least time of the filter rounds' algorithmic work
at the chip's published peaks, over the device time of the filter's
marginals kernel in the traced window, in percent.

The work is counted from the algorithm's shapes, not from the calls made:
per selection, each device's filter round reads each of its n / chips
corpus rows once (n_local * d * itemsize bytes), and the exemplar objective
does its distance matmul once (2 * n_local * r * d FLOP).  A filter that
streams the rows once per threshold lane spends J times that."""

from bench import trace_reduce as tr


def read(ctx):
    cfg, peaks = ctx.config, ctx.peaks
    planes = tr.device_planes(ctx.trace)
    kernel_ns = sum(tr.op_time_ns(p, cfg["kernel_ops"]["marginals"],
                                  ctx.window_ns) for p in planes) / len(planes)
    if not kernel_ns or not ctx.selections:
        return None
    n_local = cfg["n"] // ctx.chips
    itemsize = 2 if cfg["precision"] == "bf16" else 4
    bytes_ = n_local * cfg["d"] * itemsize
    flops = (2.0 * n_local * cfg["reference_size"] * cfg["d"]
             if cfg["oracle"] == "exemplar" else 0.0)
    least, bound = tr.least_time_s(flops, bytes_, peaks.flops, peaks.hbm_bw)
    pct = tr.roofline_pct(ctx.selections * least, kernel_ns / 1e9)
    ctx.log(f"filter.roofline: least {least * 1e3:.4f} ms a selection "
            f"({bound}-bound), marginals kernel "
            f"{kernel_ns / 1e6 / ctx.selections:.4f} ms a selection")
    return pct
