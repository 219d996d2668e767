"""scope.gather_ms: device time under the program's ``gather`` scope (the
all-gathers of the sample and of the survivor stack, block by block, with
the slicing of each block) in the traced window, per selection, averaged
over the devices (bench/trace_scopes.py)."""

from bench import trace_scopes as ts


def read(ctx):
    return ts.scope_ms_per_selection(ctx, "gather")
