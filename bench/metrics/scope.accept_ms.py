"""scope.accept_ms: device time under the program's ``accept`` scope (the
central ThresholdGreedy phases and their accept kernels) in the traced
window, per selection, averaged over the devices (bench/trace_scopes.py)."""

from bench import trace_scopes as ts


def read(ctx):
    return ts.scope_ms_per_selection(ctx, "accept")
