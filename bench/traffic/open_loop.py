"""Open loop of selection requests to the selection service: arrivals on a
fixed schedule, whatever the service does, into ``ServeLoop`` over a
``SelectionService`` with the configuration's ``slots``.

Mix parameters:

* ``rate_per_s``: offered load, requests per second;
* ``budget_min``, ``budget_max``: each request's budget k, uniform on the
  integers between them;
* ``deadline_ms``: each request's deadline, or null for none.

Every seed gets the same multiset of inter-arrival gaps and budgets, drawn
once from a fixed generator, in an order of its own: the seed changes the
order of the work, not its amount.  A request is timed from its due time
to the return of the ``run_step`` that answered it, so a stall counts
against every request due during it.  Requests due in the window that are
still queued when it closes are served after it, for at most
``DRAIN_S``; one that gets no answer counts as missing.
"""

from __future__ import annotations

import math
import time

import numpy as np

from bench import check, system

#: the fixed generator of the gaps and budgets every seed shares
SCHEDULE_SEED = 20181003
#: how long requests due in the window are served after it closes
DRAIN_S = 60.0


def schedule(mix: dict, seconds: float, seed: int):
    """(due offsets in seconds, budgets) of the requests due in a window of
    ``seconds``."""
    base = np.random.default_rng(SCHEDULE_SEED)
    count = int(round(mix["rate_per_s"] * seconds))
    gaps = base.exponential(1.0 / mix["rate_per_s"], count)
    gaps *= seconds / gaps.sum() * count / (count + 1)
    budgets = base.integers(mix["budget_min"], mix["budget_max"] + 1, count)
    mine = np.random.default_rng(seed)
    return np.cumsum(mine.permutation(gaps)), mine.permutation(budgets)


def setup(ctx) -> None:
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.launch.select_serve import Request, SelectionService, ServeLoop

    cfg = ctx.config
    kx, ctx.key_serve, kw = jax.random.split(ctx.key, 3)
    X = system.corpus(kx, cfg, SingleDeviceSharding(ctx.devices[0]),
                      ctx.seed)
    ctx.X, ctx.ref = X, system.reference_rows(X, cfg)
    host = np.asarray(X)
    ctx.rows_of = lambda ids: host[np.asarray(ids, np.int64)].astype(
        np.float64)
    svc = SelectionService(system.selector_spec(cfg),
                           system.mesh_for(ctx.chips), host,
                           reference=ctx.ref)
    ctx.svc = svc
    # every step's result, so that each answer's ids can be judged
    ctx.step_results = []
    ctx.served = svc.select_batch

    def select_batch(queries, key):
        res = ctx.served(queries, key)
        ctx.step_results.append(res)
        return res
    svc.select_batch = select_batch
    ctx.Request, ctx.ServeLoop = Request, ServeLoop
    # the window's step (Q slots), compiled and run, once with each number
    # of requests admitted: the service's own accounting after a step
    # compiles a small program for each of those numbers
    warm = ServeLoop(svc, cfg["slots"], kw)
    ids = iter(range(cfg["slots"] * (cfg["slots"] + 1)))
    for admitted in range(cfg["slots"], 0, -1):
        for _ in range(admitted):
            warm.submit(Request(id=next(ids), k=cfg["k"]))
        warm.run_step()
    ctx.step_results.clear()
    ctx.due, ctx.budgets = schedule(ctx.traffic, ctx.seconds, ctx.seed)


def window(ctx) -> None:
    loop = ctx.ServeLoop(ctx.svc, ctx.config["slots"], ctx.key_serve)
    deadline = ctx.traffic.get("deadline_ms")
    n = len(ctx.due)
    t0 = time.monotonic()
    due = t0 + ctx.due
    done_at = np.full(n, math.inf)
    admitted_at = np.full(n, math.nan)
    ctx.step_spans, ctx.step_rows = [], []
    i = 0
    while True:
        now = time.monotonic()
        if i < n and due[i] <= now:
            with ctx.spans("submit"):
                while i < n and due[i] <= now:
                    loop.submit(ctx.Request(id=i, k=int(ctx.budgets[i]),
                                            deadline_ms=deadline),
                                now=float(due[i]))
                    i += 1
        if len(loop.queue):
            ts = time.monotonic()
            with ctx.spans("run_step"):
                rows = loop.run_step()
            te = time.monotonic()
            if rows:
                ctx.step_spans.append((ts, te))
                ctx.step_rows.append(rows)
            for row in rows:
                done_at[row["id"]] = te
                admitted_at[row["id"]] = ts
        elif i < n:
            with ctx.spans("wait_arrival"):
                time.sleep(max(0.0, min(due[i] - time.monotonic(), 0.05)))
        else:
            break
        if time.monotonic() > t0 + ctx.seconds + DRAIN_S:
            break
    t_end = time.monotonic()
    ctx.elapsed_s = t_end - t0
    ctx.latencies_s = done_at - due
    ctx.queue_waits_s = admitted_at - due
    ctx.shed = loop.shed
    ctx.attempted = n
    ctx.selections = int(np.isfinite(done_at).sum())
    steps = np.array([e - s for s, e in ctx.step_spans])
    ctx.log(f"window: {n} requests due in {ctx.seconds} s, "
            f"{ctx.selections} answered in {len(steps)} steps "
            f"(median {np.median(steps) * 1e3 if len(steps) else 0:.3f} ms, "
            f"{ctx.selections / max(len(steps), 1):.2f} requests a step), "
            f"{len(loop.shed)} shed; the last answer came "
            f"{ctx.elapsed_s - ctx.seconds:.3f} s after the window closed")


def answers(ctx):
    """Every answered request, read back with the ids of its slot; a request
    due in the window that got no answer is missing."""
    import jax
    results = jax.device_get(ctx.step_results)
    out = []
    for res, rows in zip(results, ctx.step_rows):
        for slot, row in enumerate(rows):
            out.append(check.Answer(budget=row["k"],
                                    ids=np.asarray(res.sol_ids[slot]),
                                    size=row["size"], value=row["value"],
                                    dropped=row["dropped"]))
    missing = ctx.attempted - len(out)
    ctx.log(f"service: {ctx.svc.summary()}")
    del ctx.svc, ctx.step_results
    return out, missing
