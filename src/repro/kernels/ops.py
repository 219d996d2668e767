"""Public jit'd entry points for the Pallas kernels.

Backend dispatch, decided by ``jax.default_backend()`` at trace time:

* ``tpu`` — the kernels compile natively with Mosaic (a compiled step holds
  one ``tpu_custom_call`` per kernel launch site);
* ``cpu`` — the test backend (``JAX_PLATFORMS=cpu``): the kernels run under
  ``interpret=True``, which executes the kernel body with identical
  semantics — that is how the shape/dtype sweep tests validate them
  against ref.py;
* anything else raises: there is no lowering for it, and a silent
  interpret fallback would hide that the device never ran a kernel.

A program compiled ahead of time for a described TPU from a CPU host still
sees the CPU backend here, so such a compile calls the kernel modules with
``interpret=False`` directly (tests/test_tpu_compile.py).
"""

from __future__ import annotations

import jax

from repro.kernels import coverage_accept as _ca
from repro.kernels import coverage_marginals as _cm
from repro.kernels import exemplar_accept as _ea
from repro.kernels import exemplar_marginals as _em
from repro.kernels import facility_accept as _fa
from repro.kernels import facility_marginals as _fm
from repro.kernels import graph_cut_accept as _ga
from repro.kernels import graph_cut_marginals as _gc
from repro.kernels import logdet_accept as _la
from repro.kernels import logdet_marginals as _ld
from repro.kernels import saturated_coverage_accept as _sa
from repro.kernels import saturated_coverage_marginals as _sc
from repro.kernels import weighted_coverage_accept as _wa
from repro.kernels import weighted_coverage_marginals as _wc


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels have no lowering for backend {backend!r}: they "
        "compile on 'tpu' and are interpreted on 'cpu' only")


def facility_marginals(cand, ref, state, *, block_c=None, block_r=None):
    """Fused (C,d)x(r,d)->(C,) facility-location marginals."""
    kw = {}
    if block_c:
        kw["block_c"] = block_c
    if block_r:
        kw["block_r"] = block_r
    return _fm.facility_marginals(cand, ref, state,
                                  interpret=_interpret(), **kw)


def rectified_residual_sum(aux, state, *, block_c=None, block_r=None):
    """Unfused (C,r)->(C,) rectified residual reduction."""
    kw = {}
    if block_c:
        kw["block_c"] = block_c
    if block_r:
        kw["block_r"] = block_r
    return _fm.rectified_residual_sum(aux, state,
                                      interpret=_interpret(), **kw)


def coverage_marginals(x, state, weights=None, *, block_c=None, block_f=None):
    """Fused (C,d),(d,)->(C,) FeatureCoverage marginals.  Under a vmap over
    ``state`` alone the block is read once for every lane
    (``coverage_marginals.routed``)."""
    return _cm.routed(block_c or _cm.DEFAULT_BC, block_f or _cm.DEFAULT_BF,
                      _interpret())(x, state, weights)


def saturated_coverage_marginals(x, state, cap, weights=None, *,
                                 block_c=None, block_f=None):
    """Fused (C,d),(d,),(d,)->(C,) SaturatedCoverage marginals."""
    kw = {}
    if block_c:
        kw["block_c"] = block_c
    if block_f:
        kw["block_f"] = block_f
    return _sc.saturated_coverage_marginals(x, state, cap, weights,
                                            interpret=_interpret(), **kw)


def weighted_coverage_marginals(x, state, *, block_c=None, block_u=None):
    """Fused (C,U),(U,)->(C,) WeightedCoverage marginals."""
    kw = {}
    if block_c:
        kw["block_c"] = block_c
    if block_u:
        kw["block_u"] = block_u
    return _wc.weighted_coverage_marginals(x, state,
                                           interpret=_interpret(), **kw)


def graph_cut_marginals(x, total, state, lam=0.5, *, block_c=None,
                        block_f=None):
    """Fused (C,d),(d,),(d,)->(C,) GraphCut marginals."""
    kw = {}
    if block_c:
        kw["block_c"] = block_c
    if block_f:
        kw["block_f"] = block_f
    return _gc.graph_cut_marginals(x, total, state, lam,
                                   interpret=_interpret(), **kw)


def logdet_marginals(x, U, alpha=1.0, *, block_c=None, scale=1.0):
    """Fused (C,d),(k,d)->(C,) log-det diversity marginals (``scale=0.5``
    is the mutual-information oracle)."""
    kw = {}
    if block_c:
        kw["block_c"] = block_c
    return _ld.logdet_marginals(x, U, alpha, interpret=_interpret(),
                                scale=scale, **kw)


def coverage_accept(x, state, weights, eligible, tau, budget,
                    cost=None, cost_budget=None):
    """Fused FeatureCoverage chunk-accept sweep: one kernel runs the
    ThresholdGreedy inner loop over the (B, d) tile.  Returns
    (mask (B,) bool, state (d,), gains (B,)).  ``cost``/``cost_budget``
    switch to knapsack cost-ratio accepts (all accept entries).  Under a
    vmap over the lanes (the threshold guesses; weights shared, no cost)
    eight lanes sweep at once (``coverage_accept.routed``)."""
    return _ca.routed(_interpret())(x, state, weights, eligible, tau,
                                    budget, cost, cost_budget)


def weighted_coverage_accept(x, state, eligible, tau, budget,
                             cost=None, cost_budget=None):
    """Fused WeightedCoverage chunk-accept sweep."""
    return _wa.weighted_coverage_accept(x, state, eligible, tau, budget,
                                        interpret=_interpret(), cost=cost,
                                        cost_budget=cost_budget)


def saturated_coverage_accept(x, state, cap, weights, eligible, tau,
                              budget, cost=None, cost_budget=None):
    """Fused SaturatedCoverage chunk-accept sweep."""
    return _sa.saturated_coverage_accept(x, state, cap, weights, eligible,
                                         tau, budget,
                                         interpret=_interpret(), cost=cost,
                                         cost_budget=cost_budget)


def graph_cut_accept(x, total, state, eligible, tau, budget, lam=0.5,
                     cost=None, cost_budget=None):
    """Fused GraphCut chunk-accept sweep (lam baked at compile time)."""
    return _ga.graph_cut_accept(x, total, state, eligible, tau, budget,
                                lam, interpret=_interpret(), cost=cost,
                                cost_budget=cost_budget)


def facility_accept(cand, ref, state, eligible, tau, budget,
                    cost=None, cost_budget=None):
    """Fused facility-location chunk-accept sweep: matmul + rectified
    residual + accept loop in one kernel; the (B, r) similarity block
    never leaves VMEM."""
    return _fa.facility_accept(cand, ref, state, eligible, tau, budget,
                               interpret=_interpret(), cost=cost,
                               cost_budget=cost_budget)


def exemplar_accept(cand, ref, state, eligible, tau, budget,
                    cost=None, cost_budget=None):
    """Fused exemplar-clustering chunk-accept sweep: matmul + distance
    expansion + accept loop in one kernel; the (B, r) squared-distance
    block never leaves VMEM."""
    return _ea.exemplar_accept(cand, ref, state, eligible, tau, budget,
                               interpret=_interpret(), cost=cost,
                               cost_budget=cost_budget)


def logdet_accept(x, U, logdet, size, eligible, tau, budget, alpha=1.0,
                  scale=1.0, cost=None, cost_budget=None):
    """Fused log-det (scale=1) / mutual-information (scale=0.5)
    chunk-accept sweep: Schur-complement gains + rank-1 Gram-Schmidt
    appends against the whitened basis held in VMEM scratch.  Returns
    (mask (B,) bool, U (k,d), logdet (), size (), gains (B,))."""
    return _la.logdet_accept(x, U, logdet, size, eligible, tau, budget,
                             alpha, scale=scale, interpret=_interpret(),
                             cost=cost, cost_budget=cost_budget)


def exemplar_marginals(cand, ref, state, *, block_c=None, block_r=None):
    """Fused (C,d)x(r,d)->(C,) exemplar-clustering marginals."""
    kw = {}
    if block_c:
        kw["block_c"] = block_c
    if block_r:
        kw["block_r"] = block_r
    return _em.exemplar_marginals(cand, ref, state,
                                  interpret=_interpret(), **kw)
