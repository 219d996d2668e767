"""serve.retire_ms: mean duration of the program's ``serve.retire`` span
(per-slot readbacks, the rows and the service's accounting), over the
steps that start in the traced window."""

from bench import trace_scopes as ts


def read(ctx):
    return ts.mean_span_ms(ctx.trace, ctx.window_ns, "serve.retire")
