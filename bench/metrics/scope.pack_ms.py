"""scope.pack_ms: device time under the program's ``pack`` scope
(``pack_by_mask``: the composite-key top_k and the row gather that turn a
mask into a packed message) in the traced window, per selection, averaged
over the devices (bench/trace_scopes.py)."""

from bench import trace_scopes as ts


def read(ctx):
    return ts.scope_ms_per_selection(ctx, "pack")
