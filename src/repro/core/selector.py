"""DistributedSelector — the framework-facing API for the paper's technique.

The data pipeline (repro.data.selection) and the examples talk to this class,
not to mapreduce.py directly.  It owns: oracle construction from a spec,
MRConfig derivation from the mesh, algorithm choice, and jit caching.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import constraints as constraints_mod
from repro.core import faults as faults_mod
from repro.core import functions as F
from repro.core import mapreduce as mr
from repro.core import precision as precision_mod

#: every algorithm DistributedSelector can run — CLIs and serving configs
#: derive their choices from this tuple, not hand-copied literals.
ALGORITHMS = ("two_round", "multi_epoch", "multi_threshold",
              "two_round_known_opt")

#: the subset that needs no OPT estimate / guess loop — what a serving
#: loop can run unattended on every request.
OPT_FREE_ALGORITHMS = ("two_round", "multi_epoch")


@dataclasses.dataclass(frozen=True)
class SelectorSpec:
    k: int
    oracle: str = "feature_coverage"   # see ORACLE_NAMES for the full zoo
    algorithm: str = "two_round"       # see ALGORITHMS
    t: int = 1                         # thresholds for multi_threshold
    eps: float = 0.15
    epochs: Optional[int] = None       # multi_epoch levels; None derives
    #                                    ceil(1/eps) (the 1-1/e-eps setting)
    schedule_kind: str = "paper"       # epoch schedule family, see
    #                                    grids.SCHEDULE_KINDS
    accept: str = "first"
    engine: str = "dense"              # ThresholdGreedy engine:
    #                                    "dense" | "lazy" | "fused"
    chunk: int = 128                   # lazy/fused-engine chunk size
    reference_size: int = 256          # facility location / exemplar clients
    use_kernel: bool = False
    graph_cut_lam: float = 0.5         # GraphCut redundancy penalty, <= 1/2
    logdet_alpha: float = 1.0          # LogDetDiversity kernel scale
    saturated_alpha: float = 0.25      # SaturatedCoverage saturation frac
    oracle_tp: bool = False            # shard the feature dim over "model"
    #                                    (TPOracle — the central phase's
    #                                    elementwise work / tp per device)
    precision: str = "f32"             # storage/compute policy ("f32" |
    #                                    "bf16"); accumulators stay f32 —
    #                                    see repro.core.precision
    constraint: str = "cardinality"    # feasibility constraint, see
    #                                    constraints.CONSTRAINT_NAMES; the
    #                                    per-element data (costs / part
    #                                    labels) is a DistributedSelector
    #                                    constructor argument — it belongs
    #                                    to the corpus, not the spec
    knapsack_budget: Optional[float] = None   # constraint="knapsack" budget
    mi_noise: float = 1.0              # MutualInformationGaussian sensor
    #                                    noise variance sigma^2
    faults: Optional[faults_mod.FaultPlan] = None
    #                                    deterministic chaos schedule
    #                                    injected at the round boundaries
    #                                    (core/faults.py); None is the
    #                                    untouched production fast path

    def __post_init__(self):
        precision_mod.validate(self.precision, where="SelectorSpec")
        constraints_mod.validate_constraint_name(self.constraint,
                                                 where="SelectorSpec")
        if self.algorithm not in ALGORITHMS:
            raise ValueError(f"SelectorSpec: unknown algorithm "
                             f"{self.algorithm!r}; choose from {ALGORITHMS}")
        if self.faults is not None and not isinstance(
                self.faults, faults_mod.FaultPlan):
            raise TypeError(
                "SelectorSpec: faults must be a repro.core.faults.FaultPlan "
                f"(or None), got {type(self.faults).__name__}")

    @property
    def precision_policy(self):
        return precision_mod.resolve(self.precision)


#: every oracle make_oracle can build — benchmarks and the conformance
#: harness sweep this list, so registering an oracle here opts it into the
#: ratio / throughput / property-test coverage.
ORACLE_NAMES = ("feature_coverage", "facility_location", "weighted_coverage",
                "saturated_coverage", "graph_cut", "log_det", "exemplar",
                "mutual_information")


def make_oracle(spec: SelectorSpec, feat_dim: int, reference=None,
                total=None):
    """Build the spec's oracle.  ``reference`` is the replicated client set
    for facility_location / exemplar; ``total`` is the ground-set feature
    sum for graph_cut (a dataset statistic, computed once up front)."""
    if spec.oracle == "feature_coverage":
        return F.FeatureCoverage(feat_dim=feat_dim,
                                 use_kernel=spec.use_kernel)
    if spec.oracle == "facility_location":
        assert reference is not None, "facility_location needs a reference set"
        return F.FacilityLocation(feat_dim=feat_dim, reference=reference,
                                  use_kernel=spec.use_kernel)
    if spec.oracle == "weighted_coverage":
        return F.WeightedCoverage(feat_dim=feat_dim,
                                  use_kernel=spec.use_kernel)
    if spec.oracle == "saturated_coverage":
        assert total is not None, \
            "saturated_coverage needs the ground-set feature sum (total)"
        return F.SaturatedCoverage(feat_dim=feat_dim, total=total,
                                   alpha=spec.saturated_alpha,
                                   use_kernel=spec.use_kernel)
    if spec.oracle == "graph_cut":
        assert total is not None, \
            "graph_cut needs the ground-set feature sum (total)"
        return F.GraphCut(feat_dim=feat_dim, total=total,
                          lam=spec.graph_cut_lam, use_kernel=spec.use_kernel)
    if spec.oracle == "log_det":
        return F.LogDetDiversity(feat_dim=feat_dim, k_max=spec.k,
                                 alpha=spec.logdet_alpha,
                                 use_kernel=spec.use_kernel)
    if spec.oracle == "exemplar":
        assert reference is not None, "exemplar needs a reference set"
        return F.ExemplarClustering(feat_dim=feat_dim, reference=reference,
                                    use_kernel=spec.use_kernel)
    if spec.oracle == "mutual_information":
        return F.MutualInformationGaussian(feat_dim=feat_dim, k_max=spec.k,
                                           noise=spec.mi_noise,
                                           use_kernel=spec.use_kernel)
    raise ValueError(f"unknown oracle {spec.oracle!r}; "
                     f"registered: {ORACLE_NAMES}")


class DistributedSelector:
    """Runs the paper's MapReduce selection on a device mesh.

    ``select(embeddings, opt_estimate, key)``: embeddings (n, d) sharded over
    the machine axes; returns SelectionResult (replicated).  On a 1-device
    mesh this degenerates gracefully (m=1: the algorithm is sequential
    threshold greedy — still correct, zero communication).
    """

    def __init__(self, spec: SelectorSpec, mesh: Mesh, n_total: int,
                 feat_dim: int, axes=("data",), reference=None, total=None,
                 element_costs=None, parts=None, part_caps=None):
        self.spec = spec
        self.mesh = mesh
        # Stash the oracle's corpus-level statistics: opt_upper_bound (and
        # anything else that rebuilds a full-width oracle outside shard_map)
        # must thread these through make_oracle again, or the rebuild
        # asserts/mis-builds for facility_location / exemplar / graph_cut.
        # The reference set is a feature plane — it rides at storage
        # precision; ``total`` is an accumulator statistic and stays f32.
        if reference is not None:
            reference = spec.precision_policy.cast_storage(
                jnp.asarray(reference))
        self.reference = reference
        self.total = total
        self.axes = tuple(a for a in axes if a in mesh.shape)
        m = 1
        for a in self.axes:
            m *= mesh.shape[a]
        # the constraint object marries the spec's knob (name, budget) to
        # the corpus's per-element data (costs / part labels) — built here
        # because only the selector sees both
        self.constraint = constraints_mod.make_constraint(
            spec.constraint, n_total, costs=element_costs,
            budget=spec.knapsack_budget, parts=parts, capacities=part_caps)
        self.cfg = mr.MRConfig(k=spec.k, n_total=n_total, n_machines=m,
                               eps=spec.eps, accept=spec.accept,
                               engine=spec.engine, chunk=spec.chunk,
                               epochs=spec.epochs,
                               schedule_kind=spec.schedule_kind,
                               precision=spec.precision,
                               constraint=self.constraint,
                               faults=spec.faults)
        self.cfg.require_even_shards(where="DistributedSelector data sharding")
        tp = mesh.shape.get("model", 1)
        self.tp = (spec.oracle_tp and tp > 1 and feat_dim % tp == 0 and
                   spec.oracle in ("feature_coverage", "weighted_coverage"))
        if self.tp:
            base = make_oracle(spec, feat_dim // tp, reference)
            self.oracle = F.TPOracle(base=base, axis="model")
            ax0 = self.axes if len(self.axes) > 1 else self.axes[0]
            self._data_spec = P(ax0, "model")
        else:
            self.oracle = make_oracle(spec, feat_dim, reference, total)
            self._data_spec = P(self.axes if len(self.axes) > 1
                                else self.axes[0])
        if spec.algorithm == "multi_epoch":
            # the (1-1/e-eps) driver: OPT-free like two_round, of which it
            # is the E-epoch generalization (E=1 IS two_round, bit-for-bit)
            self._run, self.round_log = mr.multi_epoch_mesh(
                self.oracle, self.cfg, mesh, self.axes,
                data_spec=self._data_spec)
            self._needs_opt = False
        elif spec.algorithm == "multi_threshold":
            self._run, self.round_log = mr.multi_threshold_mesh(
                self.oracle, self.cfg, spec.t, mesh, self.axes,
                data_spec=self._data_spec)
            self._needs_opt = True
        elif spec.algorithm == "two_round_known_opt":
            self._run, self.round_log = mr.two_round_known_opt_mesh(
                self.oracle, self.cfg, mesh, self.axes,
                data_spec=self._data_spec)
            self._needs_opt = True
        else:  # "two_round" = Theorem 8, OPT-free (the production default)
            self._run, self.round_log = mr.two_round_mesh(
                self.oracle, self.cfg, mesh, self.axes,
                data_spec=self._data_spec)
            self._needs_opt = False
        self._jitted = None
        self._batch_run = None
        self._batch_round_log = None
        self._batch_logs = {}      # Q -> RoundLog (events accumulate)

    def data_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self._data_spec)

    def select(self, embeddings, opt_estimate=None, key=None
               ) -> mr.SelectionResult:
        n = embeddings.shape[0]
        ids = jnp.arange(n, dtype=jnp.int32)
        if self._jitted is None:
            self._jitted = jax.jit(self._run)
        if self._needs_opt:
            assert opt_estimate is not None, \
                f"{self.spec.algorithm} needs an OPT estimate"
            res = self._jitted(embeddings, ids, opt_estimate, key)
        else:
            res = self._jitted(embeddings, ids, key)
        # Degenerate-sample / overflow events surface in the round log's
        # runtime counters (lazy device scalars — no sync here), so serving
        # dashboards reading round_log.summary() see them, not only callers
        # that inspect the raw SelectionResult.
        self.round_log.note("tau_fallback", res.tau_fallback)
        self.round_log.note("n_dropped", res.n_dropped)
        self.round_log.note("degraded_selects", res.degraded)
        return res

    def select_batch(self, embeddings, queries: mr.QueryBatch, key=None
                     ) -> mr.SelectionResult:
        """Answer Q selection queries against one corpus in ONE mesh
        program: the sample round is shared, the two all_gathers carry
        every query, and the central phases vmap over per-query budgets
        (queries.k <= spec.k) and oracle hyper-parameters.  Returns a
        SelectionResult whose fields carry a leading (Q,) axis.

        Only the OPT-free epoch drivers batch (the known-OPT variants
        would need a per-query opt estimate round of their own); the batch
        path always runs the 1-epoch (two_round) pipeline.  The compiled
        program specializes on Q — a serving loop should pin its slot
        count and mask unused slots with k=0."""
        assert self.spec.algorithm in ("two_round", "multi_epoch"), \
            "select_batch requires an OPT-free algorithm " \
            "(two_round or multi_epoch)"
        with jax.profiler.TraceAnnotation("select.budget_check"):
            k_max = int(jnp.max(queries.k))
        assert k_max <= self.spec.k, \
            (f"select_batch: per-query budget {k_max} exceeds the slot "
             f"buffer capacity spec.k={self.spec.k}; the engine would "
             f"silently truncate — build the selector with a larger k")
        n = embeddings.shape[0]
        ids = jnp.arange(n, dtype=jnp.int32)
        if self._batch_run is None:
            run, round_log = mr.two_round_batch_mesh(
                self.oracle, self.cfg, self.mesh, self.axes,
                data_spec=self._data_spec)
            self._batch_run = jax.jit(run)
            self._batch_round_log = round_log
        # one RoundLog per slot width, REUSED across calls so the runtime
        # event counters accumulate over every select_batch this selector
        # serves (note()'s contract) instead of resetting per step
        Q = queries.n_queries
        if Q not in self._batch_logs:
            self._batch_logs[Q] = self._batch_round_log(Q)
        self.round_log_batch = self._batch_logs[Q]
        res = self._batch_run(embeddings, ids, queries, key)
        if self.spec.use_kernel and F.consumes_query_params(self.oracle):
            # the per-query knob (graph_cut lam / log_det alpha) is traced
            # on the query axis and the kernels bake it in at compile time,
            # so this path ran the jnp oracle: say so, never silently
            self.round_log_batch.note("kernel_bypassed", 1)
        self.round_log_batch.note("tau_fallback", jnp.sum(res.tau_fallback))
        self.round_log_batch.note("n_dropped", jnp.sum(res.n_dropped))
        self.round_log_batch.note("degraded_selects", res.degraded)
        return res

    def runtime_events(self) -> dict:
        """Realized runtime counters (tau_fallback, n_dropped,
        degraded_selects, ...) summed across every select()/select_batch()
        this selector served — the single-query round log plus every
        slot-width batch log — merged with the fault-injection records
        (``fault_*`` keys, from RoundLog.fault_events()), with the
        coverage filter's and central accept's route counts of the process
        (``marginals_*``, kernels.coverage_marginals.lane_stats;
        ``accept_*``, kernels.coverage_accept.lane_stats) and with the survivor
        gather of the last mesh program built (``gather_lane_blocks``,
        ``gather_block_lanes``, ``gather_bytes_in``;
        rounds.gather_stats).  This is the one
        place the lazy device scalars are forced to ints, so serving
        stats/SLO dashboards read one dict instead of reaching into per-Q
        RoundLogs."""
        out: dict = {}
        seen_faults = set()
        for log in (self.round_log, *self._batch_logs.values()):
            for name, v in log.events.items():
                out[name] = out.get(name, 0) + int(v)
            # every batch-width log shares ONE fault record list (the
            # driver's) — aggregate each distinct list once, not per width
            if id(log.faults) in seen_faults:
                continue
            seen_faults.add(id(log.faults))
            for name, v in log.fault_events().items():
                key = f"fault_{name}"
                if name == "min_eff_machines":
                    out[key] = min(out.get(key, v), v)
                else:
                    out[key] = out.get(key, 0) + v
        # the coverage filter's and accept's routes in each program built
        # so far in this process (trace-time counts, not per select)
        from repro.kernels import coverage_accept, coverage_marginals
        for prefix, module in (("marginals", coverage_marginals),
                               ("accept", coverage_accept)):
            lanes = module.lane_stats()
            if lanes["fused_calls"] or lanes["per_lane_calls"]:
                for name, v in lanes.items():
                    out[f"{prefix}_{name}"] = v
        # the grid survivor gather of the last mesh program built
        # (trace-time): blocks, lanes a block, bytes one device receives
        # per selection (rounds.gather_stats)
        from repro.core.rounds import gather_stats
        gathers = gather_stats()
        if gathers["lane_blocks"]:
            for name, v in gathers.items():
                out[f"gather_{name}"] = v
        return out

    def opt_upper_bound(self, embeddings) -> jax.Array:
        """k * (max singleton value) >= OPT >= max singleton — the standard
        first-round estimate (paper §2.2: 'an extra initial round').
        Runs outside shard_map, so always on a full-width oracle: a TPOracle
        would psum over a mesh axis that doesn't exist here, so rebuild the
        unsharded base oracle at the embeddings' full feature width (with
        the stashed reference/total — the rebuild must carry the corpus
        statistics or it asserts for facility_location/exemplar/graph_cut)."""
        if isinstance(self.oracle, F.TPOracle):
            oracle = make_oracle(self.spec, embeddings.shape[-1],
                                 self.reference, self.total)
        else:
            oracle = self.oracle
        st0 = oracle.init_state()
        singles = oracle.marginals(st0, oracle.prep(st0, embeddings))
        return jnp.max(singles) * self.spec.k
