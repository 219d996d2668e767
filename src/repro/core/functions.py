"""Monotone submodular objective oracles, in a batched/JAX-friendly form.

The paper assumes every machine has oracle access to ``f``.  To make that real
on a TPU pod, each oracle here is *state-based*: the current solution ``S`` is
summarized by a compact ``state`` pytree such that

  * ``marginals(state, aux)`` scores a whole block of candidates at once
    (vectorized / MXU-friendly — this is the hot loop ThresholdGreedy runs), and
  * ``state`` is O(d)-sized and replicable, so the paper's "send the partial
    greedy solution G to every machine" is a broadcast of ``state`` + the id
    list, never a re-evaluation of f from scratch.

Every element is represented by a dense *feature row*; a candidate block is a
``(C, feat_dim)`` array.  ``prep`` turns a candidate block into per-candidate
``aux`` (e.g. similarity rows for facility location), computed once per
ThresholdGreedy call and reused across its iterations.

Oracles implemented:

  FeatureCoverage    f(S) = sum_f w_f * sqrt(sum_{e in S} x_{e,f})
                     (concave-over-modular coverage; the workhorse for
                     distributed data selection — state is a (d,) vector)
  FacilityLocation   f(S) = sum_{v in R} max_{e in S} <x_v, x_e>
                     over a replicated reference/client set R
                     (the Pallas kernel target; state is the cover vector)
  WeightedCoverage   classic weighted max-coverage (the paper's canonical
                     application, cf. Assadi–Khanna / McGregor–Vu)
  SaturatedCoverage  f(S) = sum_f w_f * min(sum_{e in S} x_{e,f},
                     alpha * total_f) — per-feature coverage truncated at
                     a fraction of the dataset total (Krause's SATURATE
                     family); state is the O(d) accumulator
  GraphCut           f(S) = sum_{u in V, v in S} w(u,v) - lam sum_{u,v in S}
                     w(u,v) with w(u,v) = <x_u, x_v>, x >= 0 — the cut
                     objective of the GreeDi/core-set evaluations, in O(d)
                     state: f(S) = <t, s> - lam ||s||^2 for s = sum_S x_v
  LogDetDiversity    f(S) = log det(I + alpha K_S) (DPP-style diversity);
                     state is the O(k*d) whitened basis U = L^{-1} X_S of
                     an incremental Cholesky, so marginals are one matmul
  ExemplarClustering k-medoid loss reduction over a reference set R:
                     f(S) = L({e0}) - L(S + e0), L(S) = sum_{v in R}
                     min_{e in S} ||v - x_e||^2 (phantom exemplar at 0);
                     state is R's current min-distance vector
  MutualInformationGaussian  sensor-placement mutual information
                     f(S) = 0.5 log det(I + X_S X_S^T / noise^2) — the
                     Gaussian information gain, sharing log_det's O(k*d)
                     whitened state and Pallas kernels (0.5 gain scale)
  AdversarialThreshold  the hard instance of Theorem 4, in closed form
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.precision import MXU, accum32

Array = jax.Array


class SubmodularOracle:
    """Protocol (duck-typed) for batched submodular oracles.

    Precision contract: feature rows (``cand_feats``, ``aux_row`` where prep
    is the identity, and replicated reference sets) may arrive in the
    Precision policy's *storage* dtype — f32 or bf16.  Every oracle lifts
    them onto the f32 *accumulate* plane at its math boundary (``accum32``
    casts, or ``preferred_element_type=f32`` on MXU matmuls), so gains,
    state pytrees, and values are ALWAYS f32 regardless of storage.  The
    casts are identities for f32 input — the default policy is bit-compat.

    feat_dim:     width of an element's feature row.
    init_state(): state pytree for S = {}.
    prep(state, cand_feats):      per-candidate aux, computed once per block.
    marginals(state, aux):        (C,) marginal gains f_S(e) for the block.
    chunk_marginals(state, cand_feats): (B,) gains straight from features —
                                  the lazy engine's streaming path; never
                                  materializes a full-block aux.
    chunk_accept(state, cand_feats, eligible, tau, budget):
                                  the fused engine's path — run the whole
                                  Algorithm-1 accept loop over the (B, d)
                                  chunk, returning (mask (B,) bool,
                                  new_state, gains (B,) f32); the default
                                  is a lax.scan over rows (correct for
                                  every oracle), kerneled oracles override
                                  it with a single Pallas sweep.
    add(state, aux_row):          state for S + {e}, from e's aux row.
    value(state):                 f(S).
    """

    feat_dim: int

    def init_state(self):  # pragma: no cover - interface
        raise NotImplementedError

    def prep(self, state, cand_feats):
        return cand_feats

    def chunk_marginals(self, state, cand_feats):
        return self.marginals(state, self.prep(state, cand_feats))

    def chunk_accept(self, state, cand_feats, eligible, tau, budget,
                     cost=None, cost_budget=None):
        """Sequential threshold-accept sweep over one chunk (the paper's
        Algorithm-1 inner loop restricted to these B rows): row i's gain
        is its fresh marginal against the state *after* every earlier
        accepted row, it is accepted when eligible & gain >= tau &
        accepts-so-far < budget, and accepted rows update the state.

        Returns (mask (B,) bool, new_state, gains (B,) f32).  The gains
        are fresh marginals at scan time — valid stale upper bounds for
        the lazy buffer by submodularity.  This reference implementation
        is a lax.scan over rows with a conditional state swap per row —
        correct for every oracle (including pytree states like log-det's
        incremental Cholesky); the state-decomposable oracles override it
        with fused Pallas kernels that keep the state in VMEM scratch.

        Knapsack-constrained sweeps (core/constraints.py) pass ``cost``
        (B,) f32 per-row costs and ``cost_budget`` () f32 remaining
        budget: the accept rule becomes gain >= tau * cost_i (cost-ratio
        thresholding) with spend tracked in the carry, so intra-chunk
        budget exhaustion is exact.  ``cost=None`` is the unconstrained
        sweep, computation-for-computation identical to before.
        """
        aux = self.prep(state, cand_feats)

        if cost is None:
            def step(carry, xs):
                st, n_acc = carry
                ok, aux_row = xs
                gain = self.marginals(
                    st, jax.tree.map(lambda a: a[None], aux_row))[0]
                acc = ok & (gain >= tau) & (n_acc < budget)
                new_st = self.add(st, aux_row)
                st = jax.tree.map(
                    lambda new, old: jnp.where(acc, new, old), new_st, st)
                return (st, n_acc + acc.astype(jnp.int32)), (acc, gain)

            (st, _), (mask, gains) = jax.lax.scan(
                step, (state, jnp.zeros((), jnp.int32)), (eligible, aux))
            return mask, st, gains

        def step(carry, xs):
            st, n_acc, spent = carry
            ok, aux_row, ci = xs
            gain = self.marginals(
                st, jax.tree.map(lambda a: a[None], aux_row))[0]
            acc = ok & (gain >= tau * ci) & (n_acc < budget) & \
                (spent + ci <= cost_budget)
            new_st = self.add(st, aux_row)
            st = jax.tree.map(
                lambda new, old: jnp.where(acc, new, old), new_st, st)
            return (st, n_acc + acc.astype(jnp.int32),
                    spent + jnp.where(acc, ci, jnp.float32(0.0))), (acc, gain)

        (st, _, _), (mask, gains) = jax.lax.scan(
            step, (state, jnp.zeros((), jnp.int32),
                   jnp.zeros((), jnp.float32)), (eligible, aux, cost))
        return mask, st, gains

    def marginals(self, state, aux):  # pragma: no cover - interface
        raise NotImplementedError

    def add(self, state, aux_row):  # pragma: no cover - interface
        raise NotImplementedError

    def value(self, state):  # pragma: no cover - interface
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class FeatureCoverage(SubmodularOracle):
    """f(S) = sum_f w_f sqrt(sum_{e in S} x_{e,f}),  x >= 0.

    Concave-over-modular => monotone submodular.  The state is the modular
    accumulator ``agg`` — O(d), trivially broadcastable, so the MapReduce
    "ship G to everyone" is a d-float message.
    """

    feat_dim: int
    weights: Any = None  # optional (d,) nonneg weights
    use_kernel: bool = False  # route marginals through the Pallas kernel

    def init_state(self):
        return jnp.zeros((self.feat_dim,), jnp.float32)

    def marginals(self, state, aux):
        if self.use_kernel:
            from repro.kernels import ops

            return ops.coverage_marginals(aux, state, self.weights)
        new = jnp.sqrt(state[None, :] + aux) - jnp.sqrt(state[None, :])
        if self.weights is not None:
            new = new * self.weights[None, :]
        return jnp.sum(new, axis=-1)

    def chunk_accept(self, state, cand_feats, eligible, tau, budget,
                     cost=None, cost_budget=None):
        if self.use_kernel:
            from repro.kernels import ops

            return ops.coverage_accept(cand_feats, state, self.weights,
                                       eligible, tau, budget, cost=cost,
                                       cost_budget=cost_budget)
        return super().chunk_accept(state, cand_feats, eligible, tau, budget,
                                    cost=cost, cost_budget=cost_budget)

    def add(self, state, aux_row):
        return state + aux_row

    def value(self, state):
        v = jnp.sqrt(state)
        if self.weights is not None:
            v = v * self.weights
        return jnp.sum(v)


@dataclasses.dataclass(frozen=True)
class FacilityLocation(SubmodularOracle):
    """f(S) = sum_{v in R} max(0, max_{e in S} <x_v, x_e>).

    ``reference`` is a replicated client set (r, d) — standard practice for
    distributed facility location (clients are a fixed subsample).  ``prep``
    computes the (C, r) similarity block once; iterating ThresholdGreedy then
    touches only (C, r) data.  The prep matmul + rectified reduction is the
    compute hot spot and has a Pallas kernel (repro.kernels.facility_marginals);
    set ``use_kernel=True`` to route through it.
    """

    feat_dim: int
    reference: Any = None  # (r, d)
    use_kernel: bool = False

    def init_state(self):
        r = self.reference.shape[0]
        return jnp.zeros((r,), jnp.float32)

    def prep(self, state, cand_feats):
        # (C, r) similarities; nonneg similarities keep f monotone.  The
        # matmul accepts storage-dtype (bf16) tiles but accumulates f32 —
        # the native MXU mixed-precision contract, a no-op for f32 input.
        sims = jnp.matmul(cand_feats, self.reference.T,
                          preferred_element_type=jnp.float32,
                          precision=MXU)
        return jnp.maximum(sims, 0.0)

    def marginals(self, state, aux):
        if self.use_kernel:
            from repro.kernels import ops

            return ops.rectified_residual_sum(aux, state)
        return jnp.sum(jnp.maximum(aux - state[None, :], 0.0), axis=-1)

    def chunk_marginals(self, state, cand_feats):
        # The lazy engine's hot path: a (B, d) tile against the cover vector.
        # The fused kernel keeps the (B, r) similarity block in VMEM, so the
        # full (C, r) aux of `prep` never exists in HBM.
        if self.use_kernel:
            from repro.kernels import ops

            return ops.facility_marginals(cand_feats, self.reference, state)
        return self.marginals(state, self.prep(state, cand_feats))

    def chunk_accept(self, state, cand_feats, eligible, tau, budget,
                     cost=None, cost_budget=None):
        # The fused engine's hot path: matmul + rectified residual +
        # the whole accept loop in one kernel, (B, r) similarities and the
        # cover vector both living in VMEM scratch.
        if self.use_kernel:
            from repro.kernels import ops

            return ops.facility_accept(cand_feats, self.reference, state,
                                       eligible, tau, budget, cost=cost,
                                       cost_budget=cost_budget)
        return super().chunk_accept(state, cand_feats, eligible, tau, budget,
                                    cost=cost, cost_budget=cost_budget)

    def add(self, state, aux_row):
        return jnp.maximum(state, aux_row)

    def value(self, state):
        return jnp.sum(state)


@dataclasses.dataclass(frozen=True)
class WeightedCoverage(SubmodularOracle):
    """Weighted max-coverage: element e covers universe items u with inc[e,u]=1.

    feature row = incidence row over the universe.  state = remaining
    (uncovered) weight per universe item.  The marginal is the remaining
    weight the row picks up — a single (C, U) x (U,) contraction, fused by
    repro.kernels.weighted_coverage_marginals when ``use_kernel``.
    """

    feat_dim: int  # universe size
    weights: Any = None  # (U,) item weights; default all-ones
    use_kernel: bool = False

    def _w(self):
        if self.weights is None:
            return jnp.ones((self.feat_dim,), jnp.float32)
        return self.weights

    def init_state(self):
        return self._w()  # remaining weight

    def marginals(self, state, aux):
        if self.use_kernel:
            from repro.kernels import ops

            return ops.weighted_coverage_marginals(aux, state)
        return jnp.sum(state[None, :] * aux, axis=-1)

    def chunk_accept(self, state, cand_feats, eligible, tau, budget,
                     cost=None, cost_budget=None):
        if self.use_kernel:
            from repro.kernels import ops

            return ops.weighted_coverage_accept(cand_feats, state, eligible,
                                                tau, budget, cost=cost,
                                                cost_budget=cost_budget)
        return super().chunk_accept(state, cand_feats, eligible, tau, budget,
                                    cost=cost, cost_budget=cost_budget)

    def add(self, state, aux_row):
        return state * (1.0 - aux_row)

    def value(self, state):
        return jnp.sum(self._w()) - jnp.sum(state)


@dataclasses.dataclass(frozen=True)
class SaturatedCoverage(SubmodularOracle):
    """f(S) = sum_f w_f * min(sum_{e in S} x_{e,f}, alpha * total_f),
    x >= 0 — coverage that saturates at a fraction ``alpha`` of the
    dataset's per-feature total (the ROADMAP's saturated-coverage
    candidate; cf. Krause–Guestrin SATURATE).  min(·, cap) is concave
    nondecreasing, so the composition with the modular accumulator is
    monotone submodular.

    Like GraphCut's ``total``, ``total`` here is a corpus-level statistic
    (the ground-set feature sum) computed once up front and cached by the
    serving layer; the state stays the O(d) accumulator, so the MapReduce
    "ship G to everyone" is still a d-float message.
    """

    feat_dim: int
    total: Any = None      # (d,) = sum of all element features
    alpha: float = 0.25    # saturation fraction of the per-feature total
    weights: Any = None    # optional (d,) nonneg weights
    use_kernel: bool = False

    def _cap(self):
        return self.alpha * self.total

    def init_state(self):
        return jnp.zeros((self.feat_dim,), jnp.float32)

    def marginals(self, state, aux):
        if self.use_kernel:
            from repro.kernels import ops

            return ops.saturated_coverage_marginals(aux, state, self._cap(),
                                                    self.weights)
        cap = self._cap()[None, :]
        new = jnp.minimum(state[None, :] + aux, cap) \
            - jnp.minimum(state[None, :], cap)
        if self.weights is not None:
            new = new * self.weights[None, :]
        return jnp.sum(new, axis=-1)

    def chunk_accept(self, state, cand_feats, eligible, tau, budget,
                     cost=None, cost_budget=None):
        if self.use_kernel:
            from repro.kernels import ops

            return ops.saturated_coverage_accept(cand_feats, state,
                                                 self._cap(), self.weights,
                                                 eligible, tau, budget,
                                                 cost=cost,
                                                 cost_budget=cost_budget)
        return super().chunk_accept(state, cand_feats, eligible, tau, budget,
                                    cost=cost, cost_budget=cost_budget)

    def add(self, state, aux_row):
        return state + aux_row

    def value(self, state):
        v = jnp.minimum(state, self._cap())
        if self.weights is not None:
            v = v * self.weights
        return jnp.sum(v)


@dataclasses.dataclass(frozen=True)
class GraphCut(SubmodularOracle):
    """Monotone graph-cut objective over the similarity graph
    w(u, v) = <x_u, x_v> with nonnegative features:

        f(S) = sum_{u in V, v in S} w(u,v) - lam * sum_{u, v in S} w(u,v)
             = <t, s> - lam * ||s||^2

    for s = sum_{v in S} x_v and the dataset constant t = sum_{u in V} x_u.
    The double sums collapse into inner products, so the state is the O(d)
    accumulator ``s`` — the MapReduce "ship G to everyone" stays a d-float
    message, and no machine ever needs the n x n similarity matrix.

    lam in [0, 1/2] keeps f monotone on subsets of V (marginal of e given
    S subseteq V \\ {e} is >= (1 - 2 lam) <t, x_e> + lam ||x_e||^2 >= 0);
    any lam >= 0 keeps it submodular (marginals shrink as s grows).
    ``total`` must be the feature sum of the *same* ground set the driver
    selects from.

    ``lam`` may be a traced () scalar (the batched multi-query path carries
    per-query lam as state); the Pallas kernel bakes lam in at compile time,
    so a non-static lam routes through the jnp path.
    """

    feat_dim: int
    total: Any = None   # (d,) = sum of all element features
    lam: Any = 0.5
    use_kernel: bool = False

    def init_state(self):
        return jnp.zeros((self.feat_dim,), jnp.float32)

    def marginals(self, state, aux):
        if self.use_kernel and isinstance(self.lam, (int, float)):
            from repro.kernels import ops

            return ops.graph_cut_marginals(aux, self.total, state, self.lam)
        aux = accum32(aux)
        lin = jnp.matmul(aux, self.total - 2.0 * self.lam * state,
                         precision=MXU)
        return lin - self.lam * jnp.sum(aux * aux, axis=-1)

    def chunk_accept(self, state, cand_feats, eligible, tau, budget,
                     cost=None, cost_budget=None):
        # like marginals, the accept kernel bakes lam in at compile time —
        # a traced (per-query) lam routes through the scan reference
        if self.use_kernel and isinstance(self.lam, (int, float)):
            from repro.kernels import ops

            return ops.graph_cut_accept(cand_feats, self.total, state,
                                        eligible, tau, budget, self.lam,
                                        cost=cost, cost_budget=cost_budget)
        return super().chunk_accept(state, cand_feats, eligible, tau, budget,
                                    cost=cost, cost_budget=cost_budget)

    def add(self, state, aux_row):
        return state + aux_row

    def value(self, state):
        return jnp.dot(state, self.total, precision=MXU) \
            - self.lam * jnp.sum(state * state)


LOGDET_EPS = 1e-12  # Schur-complement clamp (exact math keeps it >= 1)


@dataclasses.dataclass(frozen=True)
class LogDetDiversity(SubmodularOracle):
    """DPP-style diversity:  f(S) = log det(I + alpha * X_S X_S^T).

    Monotone submodular for any features (the marginal is
    log(1 + alpha x^T (I + alpha X_S^T X_S)^{-1} x) >= 0 and shrinking).

    State is an O(k*d) *incremental Cholesky in whitened form*: with
    B = I + alpha X_S X_S^T = L L^T, keep U = L^{-1} X_S (plus the scalar
    log det and |S|).  Then for a candidate e:

        v   = alpha * U x_e               (the Cholesky border L^{-1} b_e)
        d^2 = 1 + alpha ||x_e||^2 - ||v||^2   (Schur complement, >= 1)
        f(S+e) - f(S) = log d^2

    so ``marginals`` is one (C, d) x (d, k) matmul + row norms (the Pallas
    kernel target), and ``add`` is a rank-1 Gram–Schmidt append:
    U <- [U; (x_e - v^T U) / d],  log det += log d^2.  No k x k solve ever
    runs in the hot loop, and the state stays a fixed-shape pytree.

    ``k_max`` must be >= the cardinality budget the engines run with
    (``make_oracle`` sets it to SelectorSpec.k); a speculative ``add`` at
    |S| = k_max is an out-of-bounds scatter, which JAX drops — harmless,
    because the engines never accept past k.

    ``alpha`` may be a traced () scalar (per-query alpha in the batched
    multi-query path); the Pallas kernel bakes alpha in at compile time, so
    a non-static alpha routes through the jnp path.
    """

    feat_dim: int
    k_max: int = 1
    alpha: Any = 1.0
    use_kernel: bool = False

    def init_state(self):
        return (jnp.zeros((self.k_max, self.feat_dim), jnp.float32),  # U
                jnp.zeros((), jnp.float32),                           # logdet
                jnp.zeros((), jnp.int32))                             # |S|

    def marginals(self, state, aux):
        U, _, _ = state
        if self.use_kernel and isinstance(self.alpha, (int, float)):
            from repro.kernels import ops

            return ops.logdet_marginals(aux, U, self.alpha)
        aux = accum32(aux)
        proj = jnp.matmul(aux, U.T, precision=MXU)
        resid = 1.0 + self.alpha * jnp.sum(aux * aux, axis=-1) \
            - (self.alpha ** 2) * jnp.sum(proj * proj, axis=-1)
        return jnp.log(jnp.maximum(resid, LOGDET_EPS))

    def chunk_accept(self, state, cand_feats, eligible, tau, budget,
                     cost=None, cost_budget=None):
        # Fused sweep: marginal + rank-1 Gram–Schmidt append per accepted
        # row, the (k_max, d) whitened basis living in VMEM scratch.  Like
        # marginals, alpha bakes in at compile time — a traced (per-query)
        # alpha routes through the scan reference.
        if self.use_kernel and isinstance(self.alpha, (int, float)):
            from repro.kernels import ops

            U, logdet, size = state
            mask, U, logdet, size, gains = ops.logdet_accept(
                cand_feats, U, logdet, size, eligible, tau, budget,
                alpha=self.alpha, cost=cost, cost_budget=cost_budget)
            return mask, (U, logdet, size), gains
        return super().chunk_accept(state, cand_feats, eligible, tau, budget,
                                    cost=cost, cost_budget=cost_budget)

    def add(self, state, aux_row):
        U, logdet, size = state
        aux_row = accum32(aux_row)
        v = self.alpha * jnp.matmul(U, aux_row, precision=MXU)
        d2 = jnp.maximum(
            1.0 + self.alpha * jnp.sum(aux_row * aux_row) - jnp.sum(v * v),
            LOGDET_EPS)
        u_new = (aux_row - jnp.matmul(v, U, precision=MXU)) / jnp.sqrt(d2)
        return (U.at[size].set(u_new), logdet + jnp.log(d2), size + 1)

    def value(self, state):
        return state[1]


@dataclasses.dataclass(frozen=True)
class MutualInformationGaussian(SubmodularOracle):
    """Sensor-placement mutual information under the Gaussian-process
    model with i.i.d. observation noise:

        f(S) = I(y_S; g) = 0.5 * log det(I + sigma^{-2} X_S X_S^T)

    for sensors with feature rows x_e (the GP covariance factor,
    K = X X^T) and noise variance sigma^2 = ``noise``^2.  This is the
    classic Krause–Guestrin objective in its information-gain form —
    monotone submodular for any features, and exactly the log-det
    geometry at alpha = 1/noise^2 scaled by 1/2.

    The state is therefore the SAME O(k*d) whitened incremental Cholesky
    as :class:`LogDetDiversity` (U = L^{-1} X_S, the running MI scalar,
    |S|), and the fused kernels are shared: ``ops.logdet_marginals`` /
    ``ops.logdet_accept`` take a compile-time ``scale`` that the MI
    oracle sets to 0.5 (LogDetDiversity's scale=1.0 path is untouched —
    the scaling is a python-level branch, so its lowering is
    bit-identical to before this oracle existed).

    ``noise`` is a corpus-level sensor property, not a per-query knob, so
    MI is deliberately NOT in ``consumes_query_params``.
    """

    feat_dim: int
    k_max: int = 1
    noise: float = 1.0
    use_kernel: bool = False

    @property
    def alpha(self):
        return 1.0 / (self.noise * self.noise)

    def init_state(self):
        return (jnp.zeros((self.k_max, self.feat_dim), jnp.float32),  # U
                jnp.zeros((), jnp.float32),                           # MI
                jnp.zeros((), jnp.int32))                             # |S|

    def marginals(self, state, aux):
        U, _, _ = state
        if self.use_kernel:
            from repro.kernels import ops

            return ops.logdet_marginals(aux, U, self.alpha, scale=0.5)
        aux = accum32(aux)
        proj = jnp.matmul(aux, U.T, precision=MXU)
        resid = 1.0 + self.alpha * jnp.sum(aux * aux, axis=-1) \
            - (self.alpha ** 2) * jnp.sum(proj * proj, axis=-1)
        return 0.5 * jnp.log(jnp.maximum(resid, LOGDET_EPS))

    def chunk_accept(self, state, cand_feats, eligible, tau, budget,
                     cost=None, cost_budget=None):
        if self.use_kernel:
            from repro.kernels import ops

            U, mi, size = state
            mask, U, mi, size, gains = ops.logdet_accept(
                cand_feats, U, mi, size, eligible, tau, budget,
                alpha=self.alpha, scale=0.5, cost=cost,
                cost_budget=cost_budget)
            return mask, (U, mi, size), gains
        return super().chunk_accept(state, cand_feats, eligible, tau, budget,
                                    cost=cost, cost_budget=cost_budget)

    def add(self, state, aux_row):
        U, mi, size = state
        aux_row = accum32(aux_row)
        v = self.alpha * jnp.matmul(U, aux_row, precision=MXU)
        d2 = jnp.maximum(
            1.0 + self.alpha * jnp.sum(aux_row * aux_row) - jnp.sum(v * v),
            LOGDET_EPS)
        u_new = (aux_row - jnp.matmul(v, U, precision=MXU)) / jnp.sqrt(d2)
        return (U.at[size].set(u_new), mi + 0.5 * jnp.log(d2), size + 1)

    def value(self, state):
        return state[1]


@dataclasses.dataclass(frozen=True)
class ExemplarClustering(SubmodularOracle):
    """k-medoid loss reduction over a replicated reference set R (r, d):

        f(S) = L({e0}) - L(S + {e0}),
        L(S) = sum_{v in R} min_{e in S} ||v - x_e||^2

    with the phantom exemplar e0 at the origin (standard in the
    distributed exemplar-clustering evaluations).  The state is R's
    current min squared-distance vector m (r,), initialized to
    m0 = ||v||^2; marginals are sum_j max(m_j - d2(e, j), 0) — the same
    shape as facility location with distances instead of similarities, so
    the same fused-kernel treatment applies (``use_kernel=True`` streams
    (chunk, d) tiles through repro.kernels.exemplar_marginals and never
    materializes the (C, r) distance block).
    """

    feat_dim: int
    reference: Any = None   # (r, d)
    use_kernel: bool = False

    def _m0(self):
        ref = self.reference.astype(jnp.float32)
        return jnp.sum(ref * ref, axis=-1)

    def init_state(self):
        return self._m0()

    def prep(self, state, cand_feats):
        # (C, r) squared distances, clamped at 0 against float cancellation;
        # bf16 tiles in, f32 accumulate (matmul via preferred_element_type,
        # the row norms on the accumulate plane)
        sims = jnp.matmul(cand_feats, self.reference.T,
                          preferred_element_type=jnp.float32,
                          precision=MXU)
        sq = jnp.sum(jnp.square(accum32(cand_feats)), axis=-1, keepdims=True)
        return jnp.maximum(self._m0()[None, :] - 2.0 * sims + sq, 0.0)

    def marginals(self, state, aux):
        return jnp.sum(jnp.maximum(state[None, :] - aux, 0.0), axis=-1)

    def chunk_marginals(self, state, cand_feats):
        # The lazy engine's hot path: a (B, d) tile against the min-distance
        # vector, fused so the (C, r) distance block never exists in HBM.
        if self.use_kernel:
            from repro.kernels import ops

            return ops.exemplar_marginals(cand_feats, self.reference, state)
        return self.marginals(state, self.prep(state, cand_feats))

    def chunk_accept(self, state, cand_feats, eligible, tau, budget,
                     cost=None, cost_budget=None):
        # The fused engine's hot path: distance block + the whole accept
        # loop in one kernel, the (B, r) distances and the min-distance
        # vector living in VMEM scratch (same shape as facility_accept,
        # with min-update instead of max).
        if self.use_kernel:
            from repro.kernels import ops

            return ops.exemplar_accept(cand_feats, self.reference, state,
                                       eligible, tau, budget, cost=cost,
                                       cost_budget=cost_budget)
        return super().chunk_accept(state, cand_feats, eligible, tau, budget,
                                    cost=cost, cost_budget=cost_budget)

    def add(self, state, aux_row):
        return jnp.minimum(state, aux_row)

    def value(self, state):
        return jnp.sum(self._m0() - state)


@dataclasses.dataclass(frozen=True)
class AdversarialThreshold(SubmodularOracle):
    """The Theorem-4 hard instance, as a closed-form oracle.

    f(S' u O') = sum_{i in S'} v_i + (1 - sum_{i in S'} v_i / (k v*)) |O'| v*.

    feature row = (value v_i, is_opt flag).  state = (sum of S'-values, |O'|).
    Used to verify the thresholding upper bound 1 - (t/(t+1))^t is *achieved*
    (i.e. our implementation is exactly as good as the theory allows, no
    better, no worse).
    """

    feat_dim: int  # = 2
    k: int = 1
    vstar: float = 1.0

    def init_state(self):
        return (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))

    def marginals(self, state, aux):
        sum_s, n_o = state
        v, is_opt = aux[:, 0], aux[:, 1]
        gain_s = v * (1.0 - n_o / self.k)
        gain_o = (1.0 - sum_s / (self.k * self.vstar)) * self.vstar
        return jnp.where(is_opt > 0.5, gain_o, gain_s)

    def add(self, state, aux_row):
        sum_s, n_o = state
        v, is_opt = aux_row[0], aux_row[1]
        return (sum_s + jnp.where(is_opt > 0.5, 0.0, v),
                n_o + jnp.where(is_opt > 0.5, 1.0, 0.0))

    def value(self, state):
        sum_s, n_o = state
        return sum_s + (1.0 - sum_s / (self.k * self.vstar)) * n_o * self.vstar


@dataclasses.dataclass(frozen=True)
class TPOracle(SubmodularOracle):
    """Tensor parallelism for the oracle: the wrapped oracle sees a SHARD
    of the feature dimension (FeatureCoverage/WeightedCoverage: a d/tp
    feature slice; FacilityLocation: an r/tp client slice) and marginal /
    value sums are completed with a psum over ``axis``.

    This is the DESIGN.md §2 'model axis splits the embedding dimension of
    marginal evaluations' optimization: inside the MapReduce drivers the
    central ThresholdGreedy phase runs replicated across the model axis, so
    without this the model axis is idle — with it, every marginals pass
    does 1/tp of the elementwise work and one (C,)-sized psum.

    chunk_accept is inherited from the generic scan: prep/marginals/add
    all delegate through the psum'd wrappers, so every shard sees the
    full (psummed) gain before the accept decision and applies only its
    local slice of the update — accept sequences stay replicated."""

    base: Any = None
    axis: str = "model"

    @property
    def feat_dim(self):  # local shard width
        return self.base.feat_dim

    def init_state(self):
        return self.base.init_state()

    def prep(self, state, cand_feats):
        return self.base.prep(state, cand_feats)

    def marginals(self, state, aux):
        return jax.lax.psum(self.base.marginals(state, aux), self.axis)

    def chunk_marginals(self, state, cand_feats):
        return jax.lax.psum(self.base.chunk_marginals(state, cand_feats),
                            self.axis)

    def add(self, state, aux_row):
        return self.base.add(state, aux_row)

    def value(self, state):
        return jax.lax.psum(self.base.value(state), self.axis)


def consumes_query_params(oracle) -> bool:
    """True when bind_query can actually rebind something on this oracle —
    i.e. per-query hyper-parameters change its marginals.  The batched
    drivers use the negation to share query-invariant work (singleton
    evaluations, top-singleton messages) across the whole batch."""
    if isinstance(oracle, TPOracle):
        return consumes_query_params(oracle.base)
    return isinstance(oracle, (GraphCut, LogDetDiversity))


def bind_query(oracle, graph_cut_lam=None, logdet_alpha=None):
    """Rebind per-query oracle hyper-parameters for the batched multi-query
    path: the paper's algorithms only consume oracle state + a threshold, so
    a query is fully described by (k, tau, hyper-params) and Q queries can
    share one corpus partition.  ``graph_cut_lam`` / ``logdet_alpha`` are ()
    scalars (typically traced, one lane of a vmapped (Q,) axis); oracles
    without that knob pass through unchanged.  TPOracle rebinds its base so
    the model-axis sharding wraps the query-specific oracle."""
    if isinstance(oracle, TPOracle):
        return dataclasses.replace(
            oracle, base=bind_query(oracle.base, graph_cut_lam, logdet_alpha))
    if isinstance(oracle, GraphCut) and graph_cut_lam is not None:
        return dataclasses.replace(oracle, lam=graph_cut_lam)
    if isinstance(oracle, LogDetDiversity) and logdet_alpha is not None:
        return dataclasses.replace(oracle, alpha=logdet_alpha)
    return oracle


def make_adversarial_instance(k: int, thresholds, vstar: float = 1.0,
                              margin: float = 2e-3):
    """Element features for the Theorem-4 instance against a given threshold
    schedule alpha_1 >= ... >= alpha_t (normalized so OPT = k * vstar).

    n_l = (alpha_{l-1}/alpha_l - 1) k elements of value alpha_l, plus the k
    optimal elements of value vstar.

    The proof lets the adversary break marginal ties against the algorithm;
    with floating point and a `>= tau` accept rule, exact ties go *for* the
    algorithm instead.  ``margin`` realizes the adversary's tie-breaking:
    decoy values are alpha_l (1 + margin) while the intended run thresholds
    are alpha_l (1 + margin/2) (see ``adversarial_schedule``), so decoys
    qualify and optimal elements' marginals land strictly below threshold
    exactly as in the proof.

    Returns (features (n, 2), opt_value).
    """
    import numpy as np

    alphas = [vstar] + list(thresholds)
    rows = []
    for lo, hi in zip(alphas[1:], alphas[:-1]):
        n_l = int(round((hi / lo - 1.0) * k))
        rows += [[lo * (1.0 + margin), 0.0]] * n_l
    rows += [[vstar, 1.0]] * k
    feats = np.asarray(rows, np.float32)
    return jnp.asarray(feats), float(k * vstar)


def adversarial_schedule(thresholds, margin: float = 2e-3):
    """Run thresholds matching ``make_adversarial_instance``'s margin."""
    return [a * (1.0 + margin / 2.0) for a in thresholds]
