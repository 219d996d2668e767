"""serve.dispatch_ms: mean duration of the program's ``serve.dispatch``
span (``SelectionService.select_batch`` to its return, with the budget
check's readback), over the steps that start in the traced window."""

from bench import trace_scopes as ts


def read(ctx):
    return ts.mean_span_ms(ctx.trace, ctx.window_ns, "serve.dispatch")
