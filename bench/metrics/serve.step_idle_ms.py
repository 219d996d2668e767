"""serve.step_idle_ms: device idle time inside the program's ``serve.step``
spans that start in the traced window, per step, averaged over the
devices; its split by the innermost child span over each idle piece
(serve.admit, serve.batch, serve.dispatch, select.budget_check,
serve.wait, serve.retire, or the step itself) goes to the log."""

from bench import trace_scopes as ts


def read(ctx):
    steps, split = ts.step_idle_split(ctx.trace, ctx.window_ns)
    if not steps:
        return None
    ctx.log(f"serve.step_idle_ms: {sum(split.values()) / 1e6 / steps:.6f} "
            f"ms of device idle per step over {steps} steps; by span (ms "
            f"per step): " + ", ".join(
                f"{n} {v / 1e6 / steps:.6f}" for n, v in sorted(
                    split.items(), key=lambda t: -t[1])))
    return sum(split.values()) / 1e6 / steps
