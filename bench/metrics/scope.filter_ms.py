"""scope.filter_ms: device time under the program's ``filter`` scope (the
local survivor filter: exclusion mask, marginals kernel, threshold) in the
traced window, per selection, averaged over the devices; each instant of
busy time goes to the innermost op running then (bench/trace_scopes.py)."""

from bench import trace_scopes as ts


def read(ctx):
    return ts.scope_ms_per_selection(ctx, "filter")
