"""compiles.batch: programs new to the process in the traced window: JAX's
lowering of a jit cache miss (``lower_sharding_computation``), which it
follows with a compile (``backend_compile_and_load``) or a load from the
persistent compile cache, which leaves no event of its own on the v5e.
Each lowering and compile is logged with the span it fell in."""

from bench import trace_scopes as ts


def read(ctx):
    return ts.compile_count(ctx)
