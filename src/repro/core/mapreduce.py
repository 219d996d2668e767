"""The paper's MapReduce algorithms (Algorithms 3–7, Theorem 8, and the
multi-epoch (1 - 1/e - eps) driver), on JAX.

Every driver here is an instantiation of the epoch engine in
``repro.core.rounds``: a descending threshold schedule executed on a
round-primitives backend (``SimRounds`` — machines as a vmap axis, the
executable MRC model; ``MeshRounds`` — machines as device-mesh axes under
shard_map, the production path).  One epoch = one threshold level = two
MapReduce rounds (sample gather + survivor gather):

* ``two_round_known_opt_{sim,mesh}`` — Algorithm 4: 1 epoch at OPT/2k.
* ``multi_threshold_{sim,mesh}``     — Algorithm 5: t epochs at the
  known-OPT schedule alpha_l = (1 - 1/(t+1))^l OPT/k.
* ``two_round_{sim,mesh}``           — Theorem 8: 1 epoch vmapped over the
  unknown-OPT tau grid (Alg. 6) with the sparse top-singleton path
  (Alg. 7) riding the same two rounds; best of all lanes.
* ``multi_epoch_{sim,mesh}``         — the (1 - 1/e - eps) result: E =
  ceil(1/eps) epochs of the same grid drivers, carrying the solution
  across epochs; epochs/schedule kind from MRConfig or per call.
* ``two_round_batch_{sim,mesh}``     — Theorem 8 for Q queries sharing one
  corpus partition and one sample round (the query axis).

Static-shape discipline: every MRC message becomes a fixed-capacity packed
buffer (`threshold.pack_by_mask`) with a validity mask + overflow counter.
Capacities default to the paper's whp bounds (Lemma 2 / Lemma 6) with a
safety factor; overflows are *reported*, so a capacity bust is an observable
event rather than silent corruption.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import constraints as constraints_mod
from repro.core import faults as faults_mod
from repro.core import grids, rounds
from repro.core import precision as precision_mod
from repro.core.functions import bind_query, consumes_query_params
from repro.core.rounds import (MeshRounds, RoundLog, SimRounds, buffer_bytes,
                               run_epochs)
from repro.core.threshold import DEFAULT_CHUNK, validate_engine


class SelectionResult(NamedTuple):
    sol_ids: jax.Array        # (k,) int32 global element ids, -1 padded
    sol_size: jax.Array       # () int32
    value: jax.Array          # () f(S)
    n_dropped: jax.Array      # () int32 — total buffer overflow (0 whp)
    tau_fallback: jax.Array = 0   # () int32 — # of threshold grids that hit
    #                               the degenerate-sample (+inf) guard; > 0
    #                               means the unknown-OPT estimate had no
    #                               signal and the affected path selected
    #                               nothing instead of everything
    degraded: jax.Array = 0       # () int32 — 1 when fault injection (or a
    #                               real outage routed through FaultyRounds)
    #                               degraded this run; the fault records are
    #                               in the driver's RoundLog
    haircut: jax.Array = 1.0      # () f32 — estimated multiplicative
    #                               guarantee factor under the recorded
    #                               faults: worst per-round survivor
    #                               fraction (faults.fault_summary)


class QueryBatch(NamedTuple):
    """Q selection queries against one shared corpus (the query axis).

    The paper's algorithms consume only oracle state + a threshold, so a
    query is (budget, oracle hyper-parameters); Q of them share one corpus
    partition, one sample round and one gather round.  All leaves carry a
    leading (Q,) axis; hyper-parameters that don't apply to the active
    oracle are ignored (see functions.bind_query)."""
    k: jax.Array               # (Q,) int32 per-query budget, <= MRConfig.k
    graph_cut_lam: jax.Array   # (Q,) f32 GraphCut redundancy penalty
    logdet_alpha: jax.Array    # (Q,) f32 LogDetDiversity kernel scale

    @property
    def n_queries(self) -> int:
        return self.k.shape[0]


def make_query_batch(ks, graph_cut_lam=None, logdet_alpha=None,
                     default_lam: float = 0.5,
                     default_alpha: float = 1.0) -> QueryBatch:
    """Build a QueryBatch from per-query budgets, filling hyper-parameter
    lanes with the given defaults when not supplied."""
    ks = jnp.asarray(ks, jnp.int32)
    Q = ks.shape[0]
    lam = (jnp.full((Q,), default_lam, jnp.float32)
           if graph_cut_lam is None
           else jnp.asarray(graph_cut_lam, jnp.float32))
    alpha = (jnp.full((Q,), default_alpha, jnp.float32)
             if logdet_alpha is None
             else jnp.asarray(logdet_alpha, jnp.float32))
    return QueryBatch(ks, lam, alpha)


@dataclasses.dataclass(frozen=True)
class MRConfig:
    """Capacities & knobs. Defaults follow the paper's memory bounds."""
    k: int
    n_total: int
    n_machines: int
    eps: float = 0.15
    sample_cap: Optional[int] = None      # per machine
    survivor_cap: Optional[int] = None    # per machine
    top_cap: Optional[int] = None         # per machine, Algorithm 7
    n_grid: Optional[int] = None          # unknown-OPT threshold grid size
    accept: str = "first"                 # "first" = Algorithm-1-faithful
    engine: str = "dense"                 # ThresholdGreedy engine:
    #                                       "dense" | "lazy" | "fused"
    chunk: int = DEFAULT_CHUNK            # lazy/fused-engine chunk size
    epochs: Optional[int] = None          # multi-epoch threshold levels;
    #                                       None derives ceil(1/eps)
    schedule_kind: str = "paper"          # grids.SCHEDULE_KINDS
    precision: str = "f32"                # dtype policy name; "f32" is the
    #                                       bit-compat default, "bf16" stores
    #                                       features half-width (f32 accum)
    constraint: Optional[constraints_mod.Constraint] = None
    #                                       feasibility constraint threaded
    #                                       through every epoch driver; None
    #                                       is plain k-cardinality (the
    #                                       pre-constraint fast path)
    faults: Optional[faults_mod.FaultPlan] = None
    #                                       deterministic chaos schedule
    #                                       (core/faults.py); None is the
    #                                       untouched production fast path

    def __post_init__(self):
        # trace-time knob validation with the config as the call site —
        # a typo'd engine fails here, not deep inside a vmapped driver
        validate_engine(self.engine, self.accept, where="MRConfig")
        grids.validate_schedule_kind(self.schedule_kind, where="MRConfig")
        precision_mod.validate(self.precision, where="MRConfig")
        if self.constraint is not None and not isinstance(
                self.constraint, constraints_mod.Constraint):
            raise TypeError(
                "MRConfig: constraint must be a repro.core.constraints."
                f"Constraint (or None), got {type(self.constraint).__name__}"
                "; build one with constraints.make_constraint(...)")
        if self.faults is not None and not isinstance(
                self.faults, faults_mod.FaultPlan):
            raise TypeError(
                "MRConfig: faults must be a repro.core.faults.FaultPlan "
                f"(or None), got {type(self.faults).__name__}")

    @property
    def constraint_planes(self) -> int:
        """Width of the constraint's attribute plane — the extra f32
        columns the round backends append to every packed message (and
        the Lemma-2/6 byte accounting must therefore count)."""
        return constraints_mod.n_planes_of(self.constraint)

    @property
    def precision_policy(self) -> precision_mod.Precision:
        """The resolved Precision policy: storage dtype for feature planes
        and gather messages (the Lemma-2/6 wire width), f32 accumulators."""
        return precision_mod.resolve(self.precision)

    @property
    def filter_chunk(self) -> Optional[int]:
        """Tile size for threshold_filter's streaming sweep: the chunked
        engines bound the filter's transient aux the same way they bound
        the greedy rescore; the dense engine keeps the one-shot call."""
        return self.chunk if self.engine in ("lazy", "fused") else None

    @property
    def sample_p(self) -> float:
        return min(1.0, 4.0 * math.sqrt(self.k / self.n_total))

    @property
    def n_local(self) -> int:
        # Ceil: when n_total isn't a multiple of n_machines the largest
        # shard has ceil(n/m) elements, and the expected-sample/survivor
        # caps must be sized from that, not the floored undercount.
        return -(-self.n_total // self.n_machines)

    def n_epochs(self, epochs=None) -> int:
        """Resolve the multi-epoch level count: explicit argument, then
        the config's ``epochs``, then the eps -> ceil(1/eps) derivation."""
        return grids.epochs_for_eps(
            self.eps, epochs if epochs is not None else self.epochs)

    def require_even_shards(self, where: str = "sim reshape") -> None:
        """The sim drivers' (m, n/m, d) reshape and the mesh data sharding
        both need exact divisibility — fail loudly, not with a shape error
        (or worse, a silently truncated ground set)."""
        if self.n_total % self.n_machines:
            raise ValueError(
                f"{where}: n_total={self.n_total} is not divisible by "
                f"n_machines={self.n_machines}; pad the ground set with "
                f"invalid (id=-1) rows to a multiple of n_machines")

    def caps(self) -> Tuple[int, int, int]:
        n_loc = self.n_local
        exp_sample = self.sample_p * n_loc
        s_cap = self.sample_cap or min(n_loc, int(3 * exp_sample) + 16)
        exp_surv = math.sqrt(self.n_total * self.k) / self.n_machines
        f_cap = self.survivor_cap or min(n_loc, int(4 * exp_surv) + self.k + 16)
        t_cap = self.top_cap or min(n_loc, 2 * self.k + 16)
        return s_cap, f_cap, t_cap

    def grid_size(self) -> int:
        # one tau_j within (1+eps) of OPT/2k needs ~log_{1+eps}(k) points;
        # under a fault plan the sampled v estimate can sag by the loss
        # fraction, so the derived grid gets statically padded (an explicit
        # n_grid is respected as-is)
        J = grids.grid_size(self.k, self.eps, self.n_grid)
        if self.n_grid is None and self.faults is not None:
            J += self.faults.grid_pad(self.eps)
        return J


# Thin aliases: the drivers' central/local pieces live in repro.core.rounds
# now; these keep historical call sites and white-box tests stable.
def _empty_solution(oracle, k, constraint=None):
    return rounds.empty_solution(oracle, k, constraint)


def _greedy(oracle, st, sol, size, feats, ids, valid, tau, k, cfg: MRConfig,
            k_dyn=None, constraint=None, cstate=None):
    st, sol, size, cst = rounds.greedy_step(
        oracle, (st, sol, size, () if cstate is None else cstate),
        (feats, ids, valid), tau, k, cfg, k_dyn=k_dyn, constraint=constraint)
    return (st, sol, size) if constraint is None else (st, sol, size, cst)


_local_sample = rounds.local_sample
_local_filter = rounds.local_filter
_local_top = rounds.local_top
_max_singleton = grids.max_singleton


def _tau_grid(oracle, cfg, s_feats, s_ids, s_valid, k=None):
    """Threshold guesses tau_j = (v/2k)(1+eps)^j from the sampled max
    singleton v (the 'dense' estimate; v in [OPT/2k, OPT] whp), with the
    degenerate-sample +inf guard — see grids.tau_grid_from_v.

    ``k`` optionally overrides cfg.k (a traced per-query budget in the
    batched multi-query path).
    Returns (taus (J,), degenerate () int32)."""
    # gathered messages carry the constraint plane — singleton estimates
    # want the base features only
    base, _ = rounds.split_plane(s_feats, cfg.constraint_planes)
    v = _max_singleton(oracle, base, s_valid)
    return _tau_grid_from_v(cfg, v, cfg.k if k is None else k)


def _tau_grid_from_v(cfg, v, k):
    """Scale the sampled max singleton v into the (J,) threshold grid for
    budget ``k`` (traced-friendly), applying the degenerate guard."""
    return grids.tau_grid_from_v(v, k, cfg.eps, cfg.grid_size())


# ---------------------------------------------------------------------------
# substrate-independent driver bodies (sim and mesh share these)
# ---------------------------------------------------------------------------

def _known_opt_select(oracle, rr, cfg: MRConfig, schedule,
                      epoch_keys) -> SelectionResult:
    """Known-OPT epoch driver: run the scalar schedule, report the carried
    solution (Algorithms 4 and 5)."""
    (st, sol, size, _cst), drops = run_epochs(oracle, rr, schedule,
                                              epoch_keys, cfg,
                                              constraint=rr.constraint)
    return SelectionResult(sol, size, oracle.value(st),
                           rr.finalize_drops(drops), jnp.zeros((), jnp.int32))


def _epoch_select(oracle, rr, cfg: MRConfig, epoch_keys, epochs: int,
                  kind: str, with_sparse: bool = True) -> SelectionResult:
    """Unknown-OPT epoch driver: derive the tau grid from epoch 1's sample,
    run every guess's descending schedule as a vmapped engine lane, ride
    the Algorithm-7 sparse path through the same rounds (its guesses sweep
    the same schedule centrally over the top-singleton pool), and keep the
    best lane.  At epochs=1 this IS Theorem 8, bit-for-bit."""
    k = cfg.k
    s_cap, f_cap, t_cap = cfg.caps()

    S1, sdrop1 = rr.sample(epoch_keys[0], cfg.sample_p, s_cap)
    taus, fb_d = _tau_grid(oracle, cfg, *S1)
    sched = grids.epoch_schedule(taus, epochs, cfg.eps, kind)
    (st_j, sol_j, size_j, _cst), drops = run_epochs(
        oracle, rr, sched, epoch_keys, cfg, first_sample=(S1, sdrop1),
        constraint=rr.constraint)
    dval = jax.vmap(oracle.value)(st_j)

    if with_sparse:
        Ltop, _tdrop = rr.tops(oracle, t_cap)
        taus_s, fb_s = _tau_grid(oracle, cfg, *Ltop)
        sched_s = grids.epoch_schedule(taus_s, epochs, cfg.eps, kind)
        ssol, ssize, sval = rounds.sparse_sweep(oracle, Ltop, sched_s, cfg,
                                                constraint=rr.constraint)
        sols = jnp.concatenate([sol_j, ssol], axis=0)
        sizes = jnp.concatenate([size_j, ssize], axis=0)
        vals = jnp.concatenate([dval, sval], axis=0)
        fb = fb_d + fb_s
    else:
        sols, sizes, vals, fb = sol_j, size_j, dval, fb_d
    best = jnp.argmax(vals)
    return SelectionResult(sols[best], sizes[best], vals[best],
                           rr.finalize_drops(drops), fb)


def _epoch_keys_split(key, epochs: int):
    """Per-epoch sample keys for the unknown-OPT drivers: one epoch uses
    the key itself (preserving two_round's bit-exact sampling), more split
    it E ways."""
    return [key] if epochs == 1 else list(jax.random.split(key, epochs))


# ---------------------------------------------------------------------------
# sim drivers — machines as a vmap axis (executable MRC model)
# ---------------------------------------------------------------------------

def two_round_known_opt_sim(oracle, feats_mk, ids_mk, valid_mk, opt,
                            cfg: MRConfig, key
                            ) -> Tuple[SelectionResult, RoundLog]:
    """Algorithm 4: 2 rounds, 1/2-approx, OPT known — the 1-epoch scalar
    instantiation at tau = OPT/2k."""
    m = feats_mk.shape[0]
    rr = SimRounds(oracle, feats_mk, ids_mk, valid_mk,
                   precision=cfg.precision_policy, constraint=cfg.constraint)
    log = rounds.epoch_round_log(cfg, m, rr.feat_dim, 1)
    rr = faults_mod.with_faults(rr, cfg.faults, log, m, cfg.n_total)
    res = _known_opt_select(oracle, rr, cfg, [opt / (2.0 * cfg.k)], [key])
    return faults_mod.apply_fault_flags(res, log), log


def multi_threshold_sim(oracle, feats_mk, ids_mk, valid_mk, opt, t: int,
                        cfg: MRConfig, key, schedule=None
                        ) -> Tuple[SelectionResult, RoundLog]:
    """Algorithm 5: 2t rounds, 1 - (1 - 1/(t+1))^t approx, OPT known —
    t epochs at the schedule alpha_l = (1 - 1/(t+1))^l OPT/k.

    ``schedule`` optionally overrides the thresholds (absolute values,
    descending) — used by the Theorem-4 adversarial benchmark, which needs
    control over the boundary between element values and thresholds."""
    m = feats_mk.shape[0]
    rr = SimRounds(oracle, feats_mk, ids_mk, valid_mk,
                   precision=cfg.precision_policy, constraint=cfg.constraint)
    sched = (list(schedule) if schedule is not None
             else grids.alg5_schedule(opt, cfg.k, t))
    log = rounds.epoch_round_log(cfg, m, rr.feat_dim, t, level_suffix=True)
    rr = faults_mod.with_faults(rr, cfg.faults, log, m, cfg.n_total)
    res = _known_opt_select(oracle, rr, cfg, sched,
                            rounds.chain_keys(key, t))
    return faults_mod.apply_fault_flags(res, log), log


def dense_two_round_sim(oracle, feats_mk, ids_mk, valid_mk, cfg: MRConfig,
                        key) -> Tuple[SelectionResult, RoundLog]:
    """Algorithm 6: 2 rounds, (1/2 - eps)-approx for 'dense' inputs.
    One grid epoch: the Algorithm-4 pipeline for every tau_j in the grid
    (a vmapped engine lane — the paper's '1/eps log k parallel copies')."""
    m = feats_mk.shape[0]
    rr = SimRounds(oracle, feats_mk, ids_mk, valid_mk,
                   precision=cfg.precision_policy, constraint=cfg.constraint)
    log = rounds.epoch_round_log(cfg, m, rr.feat_dim, 1, with_grid=True)
    rr = faults_mod.with_faults(rr, cfg.faults, log, m, cfg.n_total)
    res = _epoch_select(oracle, rr, cfg, [key], 1, cfg.schedule_kind,
                        with_sparse=False)
    return faults_mod.apply_fault_flags(res, log), log


def sparse_two_round_sim(oracle, feats_mk, ids_mk, valid_mk, cfg: MRConfig,
                         key) -> Tuple[SelectionResult, RoundLog]:
    """Algorithm 7: 2 rounds, (1/2 - eps)-approx for 'sparse' inputs.
    Each machine ships its O(k) largest singletons to the central machine,
    which tries the threshold grid sequentially."""
    m = feats_mk.shape[0]
    _, _, t_cap = cfg.caps()
    rr = SimRounds(oracle, feats_mk, ids_mk, valid_mk,
                   precision=cfg.precision_policy, constraint=cfg.constraint)
    log = RoundLog()
    rounds.log_gather(log, "gather-top-singletons", t_cap, m, rr.feat_dim,
                      f"top {t_cap}/machine",
                      itemsize=cfg.precision_policy.storage_itemsize)
    rr = faults_mod.with_faults(rr, cfg.faults, log, m, cfg.n_total)
    L, tdrop = rr.tops(oracle, t_cap)
    taus, tau_fb = _tau_grid(oracle, cfg, *L)
    sol_j, size_j, val_j = rounds.sparse_sweep(oracle, L, [taus], cfg,
                                               constraint=rr.constraint)
    log.add("broadcast-result", buffer_bytes(cfg.k, 0), buffer_bytes(cfg.k, 0),
            "central solution out")
    best = jnp.argmax(val_j)
    res = SelectionResult(sol_j[best], size_j[best], val_j[best], tdrop,
                          tau_fb)
    return faults_mod.apply_fault_flags(res, log), log


def multi_epoch_sim(oracle, feats_mk, ids_mk, valid_mk, cfg: MRConfig, key,
                    epochs: Optional[int] = None,
                    schedule_kind: Optional[str] = None, opt=None
                    ) -> Tuple[SelectionResult, RoundLog]:
    """The paper's multi-epoch driver: E epochs (2E rounds) of descending
    thresholds, value >= (1 - (1 - 1/(E+1))^E) OPT >= (1 - 1/e - eps) OPT
    for E = ceil(1/eps) (derived from cfg.eps when ``epochs`` is None).

    OPT unknown by default: every tau-grid guess runs its own schedule as
    a vmapped engine lane, the Algorithm-7 sparse path rides the same
    rounds, best lane wins — so ``epochs=1`` IS two_round_sim, bit-for-bit.
    With ``opt`` given, runs the exact Algorithm-5 schedule instead (one
    sequential lane, the tight guarantee with no grid slack)."""
    E = cfg.n_epochs(epochs)
    kind = schedule_kind or cfg.schedule_kind
    m = feats_mk.shape[0]
    rr = SimRounds(oracle, feats_mk, ids_mk, valid_mk,
                   precision=cfg.precision_policy, constraint=cfg.constraint)
    if opt is not None:
        sched = (grids.alg5_schedule(opt, cfg.k, E) if kind == "paper"
                 else grids.epoch_schedule(opt / (2.0 * cfg.k), E, cfg.eps,
                                           kind))
        log = rounds.epoch_round_log(cfg, m, rr.feat_dim, E)
        rr = faults_mod.with_faults(rr, cfg.faults, log, m, cfg.n_total)
        # chained keys = multi_threshold_sim's derivation, so the known-OPT
        # paper-schedule instantiation IS Algorithm 5 bit-for-bit
        res = _known_opt_select(oracle, rr, cfg, sched,
                                rounds.chain_keys(key, E))
        return faults_mod.apply_fault_flags(res, log), log
    kd, _ks = jax.random.split(key)
    log = rounds.epoch_round_log(cfg, m, rr.feat_dim, E, with_grid=True,
                                 with_top=True)
    rr = faults_mod.with_faults(rr, cfg.faults, log, m, cfg.n_total)
    res = _epoch_select(oracle, rr, cfg, _epoch_keys_split(kd, E), E, kind)
    return faults_mod.apply_fault_flags(res, log), log


def two_round_sim(oracle, feats_mk, ids_mk, valid_mk, cfg: MRConfig,
                  key) -> Tuple[SelectionResult, RoundLog]:
    """Theorem 8: Algorithms 6 and 7 in parallel (same two rounds), best of
    the two solutions.  This is the paper's headline (1/2 - eps) result with
    no knowledge of OPT and no dataset duplication — and exactly the
    1-epoch instantiation of multi_epoch_sim."""
    return multi_epoch_sim(oracle, feats_mk, ids_mk, valid_mk, cfg, key,
                           epochs=1)


def two_round_batch_sim(oracle, feats_mk, ids_mk, valid_mk, qb: QueryBatch,
                        cfg: MRConfig, key
                        ) -> Tuple[SelectionResult, RoundLog]:
    """Theorem 8 for Q queries over ONE corpus partition (the query axis).

    PartitionAndSample is oblivious to which query it serves, so the
    Bernoulli sample round is drawn ONCE (same key derivation as
    two_round_sim: a Q=1 batch with k=cfg.k and default hyper-parameters
    reproduces two_round_sim's selection exactly) and shared by every
    query; everything downstream — threshold grid, central greedy,
    survivor filter, sparse top-singleton path — is vmapped over the
    (Q,) query axis with per-query budget ``qb.k`` (carried as a dynamic
    bound through the fixed cfg.k-shaped buffers) and per-query oracle
    hyper-parameters (functions.bind_query).

    Returns a SelectionResult whose every field carries a leading (Q,)
    axis, and a RoundLog with shared-vs-per-query bytes broken out.
    """
    _require_unconstrained(cfg, "two_round_batch_sim")
    m, _, d = feats_mk.shape
    K = cfg.k
    s_cap, f_cap, t_cap = cfg.caps()
    J = cfg.grid_size()
    Q = qb.n_queries
    shared_stats = not consumes_query_params(oracle)
    log = _batch_round_log(cfg, m, d, Q, shared_stats)
    rr = SimRounds(oracle, feats_mk, ids_mk, valid_mk,
                   precision=cfg.precision_policy)
    rr = faults_mod.with_faults(rr, cfg.faults, log, m, cfg.n_total)

    # shared round 1a: one Bernoulli sample serves all Q queries
    kd, _ks = jax.random.split(key)
    S, sdrop = rr.sample(kd, cfg.sample_p, s_cap)

    # Query-invariant statistics are hoisted OUT of the per-query vmap when
    # the oracle consumes no per-query hyper-parameters: the max-singleton
    # estimates and the top-singleton message depend only on the oracle +
    # corpus, so Q queries pay for them once (per-query budgets only
    # rescale the threshold grid, which is O(J) arithmetic).  The per-lane
    # math is bit-identical either way.
    if shared_stats:
        v_dense = _max_singleton(oracle, S[0], S[2])
        L_shared, _ = rr.tops(oracle, t_cap)
        v_sparse = _max_singleton(oracle, L_shared[0], L_shared[2])

    def one_query(kq, lam, alpha):
        orc = bind_query(oracle, lam, alpha)
        taus, fb_d, carry = _query_grid_a(
            orc, cfg, S, K, kq, v_dense if shared_stats else None)
        R, rdrop = rr.filter_grid(orc, *carry, taus, f_cap, kq,
                                  cfg.filter_chunk)
        if shared_stats:
            L, v_s = L_shared, v_sparse
        else:
            L, _ = rr.tops(orc, t_cap)
            v_s = None
        sol, size, val, fb_s = _query_grid_b(orc, cfg, K, kq, taus, carry,
                                             R, L, v_s)
        return sol, size, val, rdrop, fb_d + fb_s

    sols, sizes, vals, rdrops, fbs = jax.vmap(one_query)(
        qb.k, qb.graph_cut_lam, qb.logdet_alpha)
    res = SelectionResult(sols, sizes, vals, sdrop + rdrops, fbs)
    return faults_mod.apply_fault_flags(res, log), log


# ---------------------------------------------------------------------------
# per-query central phases (shared by the sim and mesh batch drivers)
# ---------------------------------------------------------------------------

def _require_unconstrained(cfg: MRConfig, where: str) -> None:
    """The query-batched drivers share one sample/gather round across Q
    queries but would need Q independent feasibility states woven through
    the shared buffers — not wired up yet; fail loudly at trace time."""
    if cfg.constraint is not None:
        raise NotImplementedError(
            f"{where}: constrained selection is not supported on the "
            "query-batched path; run the single-query drivers per query")


def _batch_round_log(cfg, m, feat_dim, n_queries: int,
                     shared_stats: bool) -> RoundLog:
    s_cap, f_cap, t_cap = cfg.caps()
    J = cfg.grid_size()
    Q = n_queries
    isz = cfg.precision_policy.storage_itemsize
    n_tops = 1 if shared_stats else Q
    log = RoundLog()
    log.add("gather-sample||top[Q]",
            buffer_bytes(s_cap, feat_dim, isz)
            + n_tops * buffer_bytes(t_cap, feat_dim, isz),
            buffer_bytes(m * s_cap, feat_dim, isz)
            + n_tops * buffer_bytes(m * t_cap, feat_dim, isz),
            f"Q={Q}: shared sample {buffer_bytes(m * s_cap, feat_dim, isz)}B "
            f"+ {'shared' if n_tops == 1 else 'per-query'} top "
            f"{buffer_bytes(m * t_cap, feat_dim, isz)}B")
    log.add("gather-survivors[QxJ]",
            Q * J * buffer_bytes(f_cap, feat_dim, isz),
            Q * J * buffer_bytes(m * f_cap, feat_dim, isz),
            f"per-query {J * buffer_bytes(m * f_cap, feat_dim, isz)}B "
            f"grid J={J}")
    return log


def _query_grid_a(orc, cfg, S, K, kq, v_dense=None):
    """One query's dense phase 1: the tau grid (from the shared max-
    singleton estimate when available) and the per-tau empty-start greedy
    over the shared sample."""
    if v_dense is not None:
        taus, fb_d = _tau_grid_from_v(cfg, v_dense, kq)
    else:
        taus, fb_d = _tau_grid(orc, cfg, *S, k=kq)
    carry = rounds.grid_phase1(orc, S, taus, K, cfg, k_dyn=kq)
    return taus, fb_d, carry


def _query_grid_b(orc, cfg, K, kq, taus, carry, R, L, v_sparse=None):
    """One query's phase 2 + sparse path + best-of: complete every grid
    lane on its gathered survivors (``R``; None where the lanes were
    completed already, in lane blocks), sweep the sparse grid over the
    top-singleton pool, keep the best lane."""
    st_j, sol_j, size_j = carry[:3]

    def p2(st, sol, size, f, i, v, tau):
        st, sol, size, _ = rounds.greedy_step(orc, (st, sol, size, ()),
                                              (f, i, v), tau, K, cfg,
                                              k_dyn=kq)
        return sol, size, orc.value(st)

    if R is None:
        dsol, dsize, dval = sol_j, size_j, jax.vmap(orc.value)(st_j)
    else:
        dsol, dsize, dval = jax.vmap(p2)(st_j, sol_j, size_j, *R, taus)
    if v_sparse is not None:
        taus_s, fb_s = _tau_grid_from_v(cfg, v_sparse, kq)
    else:
        taus_s, fb_s = _tau_grid(orc, cfg, *L, k=kq)
    ssol, ssize, sval = rounds.sparse_sweep(orc, L, [taus_s], cfg, k_dyn=kq)
    sols = jnp.concatenate([dsol, ssol], axis=0)
    sizes = jnp.concatenate([dsize, ssize], axis=0)
    vals = jnp.concatenate([dval, sval], axis=0)
    best = jnp.argmax(vals)
    return sols[best], sizes[best], vals[best], fb_s


# ---------------------------------------------------------------------------
# mesh drivers — machines as mesh axes (the production path)
# ---------------------------------------------------------------------------

def _machine_axes_size(mesh: Mesh, axes) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def _mesh_setup(mesh: Mesh, axes, data_spec):
    m = _machine_axes_size(mesh, axes)
    gather_axes = axes if len(axes) > 1 else axes[0]
    data_spec = data_spec or P(axes if len(axes) > 1 else axes[0])
    ids_spec = P(data_spec[0])
    return m, gather_axes, data_spec, ids_spec


def two_round_known_opt_mesh(oracle, cfg: MRConfig, mesh: Mesh,
                             axes=("data",), data_spec=None):
    """Algorithm 4 on a device mesh.  Returns a jit-able fn
    (feats_global, ids_global, opt, key) -> SelectionResult, plus a
    RoundLog.  feats_global: (n, d) sharded over `axes` on dim 0.  The two
    all_gathers inside the shard_map body *are* the two MapReduce rounds."""
    m, gather_axes, data_spec, ids_spec = _mesh_setup(mesh, axes, data_spec)
    # Message rows carry the oracle's feature width (for TPOracle that is
    # the per-device shard width — exactly what each machine sends) plus
    # the constraint's attribute plane.
    log = rounds.epoch_round_log(
        cfg, m, oracle.feat_dim + cfg.constraint_planes, 1)

    def body(feats, ids, opt, key):
        rr = MeshRounds(oracle, feats, ids, ids >= 0, gather_axes,
                        precision=cfg.precision_policy,
                        constraint=cfg.constraint)
        rr = faults_mod.with_faults(rr, cfg.faults, log, m, cfg.n_total)
        return _known_opt_select(oracle, rr, cfg, [opt / (2.0 * cfg.k)],
                                 [key])

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(data_spec, ids_spec, P(), P()),
                       out_specs=P(),
                       check_vma=False)

    def run(feats_global, ids_global, opt, key):
        out = fn(feats_global, ids_global, jnp.asarray(opt, jnp.float32), key)
        return faults_mod.apply_fault_flags(SelectionResult(*out), log)

    return run, log


def multi_threshold_mesh(oracle, cfg: MRConfig, t: int, mesh: Mesh,
                         axes=("data",), data_spec=None):
    """Algorithm 5 on a device mesh: t epochs (2t all_gather phases) in one
    program at the known-OPT schedule."""
    m, gather_axes, data_spec, ids_spec = _mesh_setup(mesh, axes, data_spec)
    log = rounds.epoch_round_log(
        cfg, m, oracle.feat_dim + cfg.constraint_planes, t,
        level_suffix=True)

    def body(feats, ids, opt, key):
        rr = MeshRounds(oracle, feats, ids, ids >= 0, gather_axes,
                        precision=cfg.precision_policy,
                        constraint=cfg.constraint)
        rr = faults_mod.with_faults(rr, cfg.faults, log, m, cfg.n_total)
        return _known_opt_select(oracle, rr, cfg,
                                 grids.alg5_schedule(opt, cfg.k, t),
                                 rounds.chain_keys(key, t))

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(data_spec, ids_spec, P(), P()),
                       out_specs=P(),
                       check_vma=False)

    def run(feats_global, ids_global, opt, key):
        out = fn(feats_global, ids_global, jnp.asarray(opt, jnp.float32), key)
        return faults_mod.apply_fault_flags(SelectionResult(*out), log)

    return run, log


def multi_epoch_mesh(oracle, cfg: MRConfig, mesh: Mesh, axes=("data",),
                     data_spec=None, epochs: Optional[int] = None,
                     schedule_kind: Optional[str] = None,
                     _block_lanes=None):
    """The multi-epoch (1 - 1/e - eps) driver on a device mesh: E epochs
    of the unknown-OPT grid engine (2E all_gather phases), sparse path
    riding the same rounds.  ``epochs=1`` reproduces two_round_mesh
    bit-for-bit.  Returns a jit-able (feats_global, ids_global, key) ->
    SelectionResult plus the RoundLog."""
    E = cfg.n_epochs(epochs)
    kind = schedule_kind or cfg.schedule_kind
    m, gather_axes, data_spec, ids_spec = _mesh_setup(mesh, axes, data_spec)
    log = rounds.epoch_round_log(
        cfg, m, oracle.feat_dim + cfg.constraint_planes, E, with_grid=True,
        with_top=True)

    def body(feats, ids, key):
        rr = MeshRounds(oracle, feats, ids, ids >= 0, gather_axes,
                        precision=cfg.precision_policy,
                        constraint=cfg.constraint, _block_lanes=_block_lanes)
        rr = faults_mod.with_faults(rr, cfg.faults, log, m, cfg.n_total)
        return _epoch_select(oracle, rr, cfg, _epoch_keys_split(key, E), E,
                             kind)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(data_spec, ids_spec, P()),
                       out_specs=P(),
                       check_vma=False)

    def run(feats_global, ids_global, key):
        out = fn(feats_global, ids_global, key)
        return faults_mod.apply_fault_flags(SelectionResult(*out), log)

    return run, log


def two_round_mesh(oracle, cfg: MRConfig, mesh: Mesh,
                   axes=("data",), data_spec=None, _block_lanes=None):
    """Theorem 8 on a device mesh: the dense grid (Alg. 6) and sparse
    top-singletons path (Alg. 7) share the same two all_gather rounds; the
    best solution over all thresholds/paths wins.  OPT is NOT an input —
    this is the paper's headline no-duplication 2-round (1/2-eps) result,
    the production default of DistributedSelector, and exactly the 1-epoch
    instantiation of multi_epoch_mesh."""
    return multi_epoch_mesh(oracle, cfg, mesh, axes, data_spec=data_spec,
                            epochs=1, _block_lanes=_block_lanes)


def two_round_batch_mesh(oracle, cfg: MRConfig, mesh: Mesh,
                         axes=("data",), data_spec=None, _block_lanes=None):
    """Theorem 8 for Q queries on a device mesh — the query axis on the
    production substrate.

    Same two all_gather rounds as two_round_mesh, but each round's message
    carries every query: round 1 gathers the SHARED Bernoulli sample (drawn
    once, query-oblivious) plus the per-query top-singleton buffers stacked
    on a leading (Q,) axis; round 2 gathers the (Q, J, cap) survivor
    buffers, in blocks of ceil(Q*J/m) lanes one after another
    (``rounds.gather_accept``; one block on one machine, or under a fault
    plan).  The central phases vmap over queries with
    per-query dynamic budgets and bind_query'd oracle hyper-parameters.
    Amortization: Q concurrent selection requests cost ONE partition, ONE
    sample round, ONE gather round — not Q compiled calls serialized on
    the pod.

    Returns a jit-able (feats_global, ids_global, qb: QueryBatch, key) ->
    SelectionResult (every field with a leading (Q,) axis), plus a
    RoundLog parameterized by ``n_queries``.  The jitted fn specializes on
    Q (a shape), so a service should pin its slot count.
    """
    _require_unconstrained(cfg, "two_round_batch_mesh")
    m, gather_axes, data_spec, ids_spec = _mesh_setup(mesh, axes, data_spec)
    K = cfg.k
    s_cap, f_cap, t_cap = cfg.caps()
    feat_dim = oracle.feat_dim
    shared_stats = not consumes_query_params(oracle)

    # fault records live in one driver-held log (the per-Q round logs a
    # service builds below share its list, so selector/service stats see
    # the same records)
    fault_log = RoundLog()

    def round_log(n_queries: int) -> RoundLog:
        blog = _batch_round_log(cfg, m, feat_dim, n_queries, shared_stats)
        blog.faults = fault_log.faults
        return blog

    def body(feats, ids, qk, qlam, qalpha, key):
        valid = ids >= 0
        # cast once at the shard boundary: the per-query tops/filter below
        # read `feats` directly, so they must see the same storage plane
        # the round backend gathers (identity under the default policy)
        feats = cfg.precision_policy.cast_storage(feats)
        rr = MeshRounds(oracle, feats, ids, valid, gather_axes,
                        precision=cfg.precision_policy,
                        _block_lanes=_block_lanes)
        rr = faults_mod.with_faults(rr, cfg.faults, fault_log, m,
                                    cfg.n_total)

        # ---- round 1: shared sample + per-query tops, one gather --------
        # (same key derivation as two_round_mesh, so a Q=1 batch with
        # k=cfg.k and default hyper-parameters reproduces it exactly)
        S, sdrop = rr.sample(key, cfg.sample_p, s_cap)
        if shared_stats:
            # query-invariant oracle: ONE top-singleton message + ONE max-
            # singleton estimate serve the whole batch (budgets only
            # rescale the grid); the round-1 gather shrinks accordingly
            (Ltf, Lti, Ltv), _ = rr.tops(oracle, t_cap)
            v_dense = _max_singleton(oracle, S[0], S[2])
            v_sparse = _max_singleton(oracle, Ltf, Ltv)
            top_axis = None
        else:
            tf, ti, tv, _ = jax.vmap(
                lambda lam, alpha: rounds.local_top(
                    bind_query(oracle, lam, alpha), feats, ids, valid, t_cap)
            )(qlam, qalpha)
            rounds.count_gathered((tf, ti, tv), gather_axes)
            Ltf = rounds.gather_packed(tf, gather_axes, lead=1)  # (Q, m*t_cap, d)
            Lti = rounds.gather_packed(ti, gather_axes, lead=1)
            Ltv = rounds.gather_packed(tv, gather_axes, lead=1)
            (Ltf, Lti, Ltv), _ = faults_mod.degrade_gathered(
                rr, (Ltf, Lti, Ltv), jnp.zeros((), jnp.int32))
            top_axis = 0

        # ---- central phase 1 + local survivor filter, per query ---------
        def phase_a(kq, lam, alpha):
            orc = bind_query(oracle, lam, alpha)
            taus, fb_d, (st_j, sol_j, size_j, _cst) = _query_grid_a(
                orc, cfg, S, K, kq, v_dense if shared_stats else None)
            rf, ri, rv, rdrop = jax.vmap(
                lambda st, sol, size, tau: rounds.local_filter(
                    orc, st, sol, feats, ids, valid, tau, f_cap, size, kq,
                    cfg.filter_chunk)
            )(st_j, sol_j, size_j, taus)
            return taus, fb_d, st_j, sol_j, size_j, rf, ri, rv, \
                jnp.sum(rdrop)

        (taus_q, fb_d_q, st_q, sol_q, size_q, rf, ri, rv,
         rdrop_q) = jax.vmap(phase_a)(qk, qlam, qalpha)

        # ---- round 2: the (Q, J, cap) survivor stack, in lane blocks ---
        Q, J = taus_q.shape
        block = Q * J if cfg.faults is not None else rr.lane_block(Q * J)
        if block == Q * J:
            # (Q, J, m*f_cap, d) in one gather; phase 2 runs in phase_b
            Rf, Ri, Rv = rounds.gather_lanes((rf, ri, rv), gather_axes,
                                             lead=2)
            (Rf, Ri, Rv), _ = faults_mod.degrade_gathered(
                rr, (Rf, Ri, Rv), jnp.zeros((), jnp.int32))
        else:
            def lane_accept(c, cand, a):
                kq, lam, alpha, tau = a
                st, sol, size, _ = rounds.greedy_step(
                    bind_query(oracle, lam, alpha), (*c, ()), cand, tau, K,
                    cfg, k_dyn=kq)
                return st, sol, size

            def lanes(x):
                return x.reshape((Q * J,) + x.shape[2:])

            st, sol, size = rounds.gather_accept(
                tuple(map(lanes, (rf, ri, rv))),
                tuple(map(lanes, (st_q, sol_q, size_q))),
                (jnp.repeat(qk, J), jnp.repeat(qlam, J),
                 jnp.repeat(qalpha, J), lanes(taus_q)),
                lane_accept, gather_axes, block)
            st_q, sol_q, size_q = (x.reshape((Q, J) + x.shape[1:])
                                   for x in (st, sol, size))
            Rf = Ri = Rv = None

        # ---- central phase 2 + sparse path, per query -------------------
        def phase_b(kq, lam, alpha, taus, st_j, sol_j, size_j, f_j, i_j, v_j,
                    ltf, lti, ltv):
            orc = bind_query(oracle, lam, alpha)
            return _query_grid_b(orc, cfg, K, kq, taus,
                                 (st_j, sol_j, size_j),
                                 None if f_j is None else (f_j, i_j, v_j),
                                 (ltf, lti, ltv),
                                 v_sparse if shared_stats else None)

        sol_b, size_b, val_b, fb_s_q = jax.vmap(
            phase_b,
            in_axes=(0,) * 10 + (top_axis,) * 3)(
            qk, qlam, qalpha, taus_q, st_q, sol_q, size_q, Rf, Ri, Rv,
            Ltf, Lti, Ltv)
        drops = rr.finalize_drops(sdrop + rdrop_q)
        return SelectionResult(sol_b, size_b, val_b, drops,
                               fb_d_q + fb_s_q)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(data_spec, ids_spec, P(), P(), P(), P()),
                       out_specs=P(),
                       check_vma=False)

    def run(feats_global, ids_global, qb: QueryBatch, key):
        out = fn(feats_global, ids_global, qb.k, qb.graph_cut_lam,
                 qb.logdet_alpha, key)
        return faults_mod.apply_fault_flags(SelectionResult(*out), fault_log)

    return run, round_log
