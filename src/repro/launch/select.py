"""Standalone distributed-selection launcher (the paper's algorithm as a
service): select k of n embedded documents on the current device mesh.

    PYTHONPATH=src python -m repro.launch.select --n 8192 --k 64 \
        --oracle feature_coverage --algorithm two_round [--t 3]

The embeddings here are synthetic; in the framework the same entry point is
fed by the data pipeline (repro.data.selection) with model embeddings.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

# CLI choices derive from the central registries — registering a new
# oracle/engine/constraint makes it launchable with no CLI edit
from repro.core.constraints import CONSTRAINT_NAMES
from repro.core.faults import chaos_plan, fault_summary
from repro.core.grids import SCHEDULE_KINDS
from repro.core.precision import PRECISION_NAMES
from repro.core.selector import (ALGORITHMS, ORACLE_NAMES,
                                 DistributedSelector, SelectorSpec)
from repro.core.threshold import ENGINES
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh_for


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=8192)
    ap.add_argument("--k", type=int, default=64)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--oracle", default="feature_coverage",
                    choices=list(ORACLE_NAMES))
    ap.add_argument("--algorithm", default="two_round",
                    choices=list(ALGORITHMS))
    ap.add_argument("--engine", default="dense", choices=list(ENGINES),
                    help="ThresholdGreedy engine for the central phases")
    ap.add_argument("--chunk", type=int, default=128,
                    help="lazy/fused-engine chunk size")
    ap.add_argument("--use-kernel", action="store_true",
                    help="route oracle marginals/accepts through the "
                         "Pallas kernels (interpreted on the CPU backend)")
    ap.add_argument("--precision", default="f32",
                    choices=list(PRECISION_NAMES),
                    help="storage/compute precision policy (accumulators "
                         "stay f32); bf16 halves feature bytes at rest "
                         "and on the wire")
    ap.add_argument("--constraint", default="cardinality",
                    choices=list(CONSTRAINT_NAMES),
                    help="feasibility constraint on the selection; the "
                         "launcher draws synthetic per-element costs / "
                         "part labels to exercise it")
    ap.add_argument("--budget", type=float, default=None,
                    help="knapsack cost budget (default: k * mean cost / 2)")
    ap.add_argument("--n-parts", type=int, default=8,
                    help="partition_matroid: number of parts (capacities "
                         "split k evenly)")
    ap.add_argument("--mi-noise", type=float, default=1.0,
                    help="mutual_information sensor noise variance")
    ap.add_argument("--t", type=int, default=3)
    ap.add_argument("--epochs", type=int, default=None,
                    help="multi_epoch threshold levels (2 rounds each); "
                         "default derives ceil(1/eps) from --eps")
    ap.add_argument("--eps", type=float, default=0.15,
                    help="approximation slack: grid resolution, and the "
                         "multi_epoch shortfall below 1-1/e")
    ap.add_argument("--schedule", default="paper",
                    choices=list(SCHEDULE_KINDS),
                    help="multi_epoch descending-threshold schedule family")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="chaos injection: per-epoch shard-loss rate (with "
                         "message drop/corrupt/straggler at rate/2, /4, /4)"
                         "; faults are recorded in the round log and the "
                         "result reports degraded + guarantee haircut")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the deterministic fault schedule")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    mesh = make_mesh_for(len(jax.devices()), model_parallel=1)
    key = jax.random.PRNGKey(args.seed)
    kd, kr, ks = jax.random.split(key, 3)
    emb = jax.random.uniform(kd, (args.n, args.d)) ** 2

    reference = None
    if args.oracle in ("facility_location", "exemplar"):
        reference = jax.random.uniform(kr, (256, args.d))
    total = jnp.sum(emb, axis=0) \
        if args.oracle in ("graph_cut", "saturated_coverage") else None

    # synthetic per-element constraint data (the framework feeds real
    # costs/labels through the same DistributedSelector arguments)
    element_costs = parts = part_caps = budget = None
    if args.constraint == "knapsack":
        kc, _ = jax.random.split(kr)
        element_costs = jax.random.uniform(kc, (args.n,), minval=0.5,
                                           maxval=2.0)
        budget = (args.budget if args.budget is not None
                  else args.k * 1.25 / 2.0)
    elif args.constraint == "partition_matroid":
        kc, _ = jax.random.split(kr)
        parts = jax.random.randint(kc, (args.n,), 0, args.n_parts)
        cap = max(1, args.k // args.n_parts)
        part_caps = jnp.full((args.n_parts,), cap, jnp.int32)

    faults = chaos_plan(args.fault_rate, seed=args.fault_seed)
    spec = SelectorSpec(k=args.k, oracle=args.oracle,
                        algorithm=args.algorithm, t=args.t,
                        eps=args.eps, epochs=args.epochs,
                        schedule_kind=args.schedule,
                        engine=args.engine, chunk=args.chunk,
                        use_kernel=args.use_kernel,
                        precision=args.precision,
                        constraint=args.constraint,
                        knapsack_budget=budget,
                        mi_noise=args.mi_noise,
                        faults=faults)
    sel = DistributedSelector(spec, mesh, n_total=args.n, feat_dim=args.d,
                              reference=reference, total=total,
                              element_costs=element_costs, parts=parts,
                              part_caps=part_caps)
    with mesh:
        emb = jax.device_put(emb, sel.data_sharding())
        t0 = time.time()
        if args.algorithm in ("two_round", "multi_epoch"):
            # the OPT-free drivers: multi_epoch is E descending-threshold
            # epochs of the same grid engine (E=1 == two_round)
            res = sel.select(emb, key=ks)
        else:
            # the paper's unknown-OPT handling for Alg. 5: an initial round
            # gives v = max singleton (OPT in [v, k*v]); try O(log k / eps)
            # geometric estimates *in parallel* (here: a loop over the same
            # jitted fn — on hardware the copies share the 2t rounds) and
            # keep the best solution (the paper's extra final round).
            v = sel.opt_upper_bound(emb) / spec.k  # max singleton
            import math
            n_est = max(4, int(math.ceil(math.log(args.k) / 0.25)) + 1)
            best = None
            for j in range(n_est):
                est = float(v) * (1.25 ** (j + 1))
                r = sel.select(emb, jnp.asarray(est, jnp.float32),
                               jax.random.fold_in(ks, j))
                if best is None or float(r.value) > float(best.value):
                    best = r
            res = best
        jax.block_until_ready(res.value)
        dt = time.time() - t0

    print(f"[select] n={args.n} k={args.k} oracle={args.oracle} "
          f"algo={args.algorithm} machines={sel.cfg.n_machines} "
          f"precision={args.precision} constraint={args.constraint}")
    print(sel.round_log.summary())
    print(f"[select] f(S)={float(res.value):.4f} |S|={int(res.sol_size)} "
          f"dropped={int(res.n_dropped)} wall={dt * 1e3:.0f}ms")
    if faults is not None:
        realized, frac = fault_summary(sel.round_log)
        ev = sel.round_log.fault_events()
        print(f"[select] chaos rate={args.fault_rate:g} "
              f"seed={args.fault_seed}: degraded={int(res.degraded)} "
              f"haircut={float(res.haircut):.3f} events={ev}")
        # a realized fault must be REPORTED degraded — silent degradation
        # is the failure mode this subsystem exists to prevent
        assert int(res.degraded) == int(realized), \
            "fault records and the result's degraded flag disagree"
        if realized:
            assert abs(float(res.haircut) - frac) < 1e-6


if __name__ == "__main__":
    main()
