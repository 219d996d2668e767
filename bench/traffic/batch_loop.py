"""Closed loop of batch selections: back-to-back ``DistributedSelector.select``
calls over one corpus, the i-th with the key ``fold_in(run key, i)``.  Each
call is waited for before the next is made, as a batch job's caller does.

Mix parameters: none besides ``driver``.
"""

from __future__ import annotations

import time

import numpy as np

from bench import check, system


def setup(ctx) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.selector import DistributedSelector

    cfg = ctx.config
    kx, ctx.key_sel, kw = jax.random.split(ctx.key, 3)
    mesh = system.mesh_for(ctx.chips)
    X = system.corpus(kx, cfg, NamedSharding(mesh, P("data")), ctx.seed)
    ctx.X, ctx.ref = X, system.reference_rows(X, cfg)
    ctx.rows_of = system.host_rows(X, cfg["k"])
    ctx.selector = DistributedSelector(
        system.selector_spec(cfg), mesh, n_total=cfg["n"], feat_dim=cfg["d"],
        reference=ctx.ref)
    ctx.select = ctx.selector.select
    # the window's one shape, compiled (or read from the cache) and run
    # twice: the selector's running counters compile their small sums at
    # the second call
    for i in range(2):
        jax.block_until_ready(ctx.select(X, key=jax.random.fold_in(kw, i)))


def window(ctx) -> None:
    import jax
    results, t0 = [], time.monotonic()
    while True:
        with ctx.spans("select"):
            res = ctx.select(ctx.X, key=jax.random.fold_in(ctx.key_sel,
                                                           len(results)))
        with ctx.spans("readback"):
            jax.block_until_ready(res)
        results.append(res)
        if time.monotonic() - t0 >= ctx.seconds:
            break
    ctx.elapsed_s = time.monotonic() - t0
    ctx.results = results
    ctx.selections = len(results)
    ctx.attempted = len(results)
    ctx.rows_done = len(results) * ctx.config["n"]
    ctx.log(f"window: {len(results)} selections in {ctx.elapsed_s:.3f} s")


def answers(ctx):
    """Every selection of the window, read back; none can be missing in a
    closed loop."""
    import jax
    out = []
    for res in jax.device_get(ctx.results):
        out.append(check.Answer(budget=ctx.config["k"],
                                ids=np.asarray(res.sol_ids),
                                size=int(res.sol_size),
                                value=float(res.value),
                                dropped=int(res.n_dropped)))
    ctx.log(f"runtime events {ctx.selector.runtime_events()}")
    ctx.log("round log: " + ctx.selector.round_log.summary().replace(
        "\n", " | "))
    del ctx.selector, ctx.select, ctx.results
    return out, 0
