"""The query axis: batched multi-query selection (threshold_greedy_batch,
two_round_batch_sim/mesh, DistributedSelector.select_batch) — per-query
budgets, per-query oracle hyper-parameters, exact parity with the
single-query path — plus regression tests for the satellite bugfixes
(rand_greedi branch consistency, opt_upper_bound reference/total rebuild,
the degenerate-sample _tau_grid guard)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DistributedSelector, FeatureCoverage, GraphCut,
                        LogDetDiversity, MRConfig, ORACLE_NAMES,
                        SelectorSpec, WeightedCoverage, make_query_batch,
                        threshold_greedy, threshold_greedy_batch,
                        two_round_batch_sim, two_round_sim)
from repro.core import functions as F
from repro.core import mapreduce as mr
from repro.core.distributed_baselines import rand_greedi
from repro.core.sequential import greedy
from repro.launch.mesh import make_mesh_for

jax.config.update("jax_platform_name", "cpu")

ZOO = ["feature_coverage", "facility_location", "weighted_coverage",
       "graph_cut", "log_det", "exemplar"]


def _setup(name, seed=0, n=256, d=10, k=10):
    rng = np.random.default_rng(seed)
    if name == "weighted_coverage":
        feats = jnp.asarray((rng.random((n, d)) < 0.2).astype(np.float32))
        oracle = WeightedCoverage(feat_dim=d)
    elif name == "facility_location":
        feats = jnp.asarray(rng.random((n, d)).astype(np.float32))
        ref = jnp.asarray(rng.random((24, d)).astype(np.float32))
        oracle = F.FacilityLocation(feat_dim=d, reference=ref)
    elif name == "graph_cut":
        feats = jnp.asarray((rng.random((n, d)).astype(np.float32)) ** 2)
        oracle = GraphCut(feat_dim=d, total=jnp.sum(feats, axis=0), lam=0.5)
    elif name == "log_det":
        feats = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
        oracle = LogDetDiversity(feat_dim=d, k_max=32, alpha=1.0)
    elif name == "exemplar":
        feats = jnp.asarray(rng.random((n, d)).astype(np.float32))
        ref = jnp.asarray(rng.random((24, d)).astype(np.float32))
        oracle = F.ExemplarClustering(feat_dim=d, reference=ref)
    else:
        feats = jnp.asarray((rng.random((n, d)).astype(np.float32)) ** 2)
        oracle = FeatureCoverage(feat_dim=d)
    st0 = oracle.init_state()
    singles = oracle.marginals(st0, oracle.prep(st0, feats))
    tau = float(jnp.max(singles)) / (2 * k)
    ids = jnp.arange(n, dtype=jnp.int32)
    valid = jnp.ones((n,), bool)
    return oracle, feats, ids, valid, tau


def _sim_instance(seed=0, n=256, d=10, m=8):
    rng = np.random.default_rng(seed)
    X = jnp.asarray((rng.random((n, d)).astype(np.float32)) ** 2)
    return (X, X.reshape(m, n // m, d),
            jnp.arange(n, dtype=jnp.int32).reshape(m, n // m),
            jnp.ones((m, n // m), bool))


# ---------------------------------------------------------------------------
# the engine layer: threshold_greedy_batch + dynamic budgets
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ZOO)
@pytest.mark.parametrize("engine", ["dense", "lazy"])
def test_batch_engine_matches_per_query_runs(name, engine):
    """Q vmapped queries over one candidate block == Q separate
    threshold_greedy calls with the same (tau, budget)."""
    K, Q = 8, 4
    oracle, feats, ids, valid, tau = _setup(name)
    taus = jnp.asarray([tau, 2.0 * tau, 0.5 * tau, tau], jnp.float32)
    kdyn = jnp.asarray([K, K, K // 2, 3], jnp.int32)

    def empty(_):
        return (oracle.init_state(), jnp.full((K,), -1, jnp.int32),
                jnp.zeros((), jnp.int32))

    states, sols, sizes = jax.vmap(empty)(jnp.arange(Q))
    bst, bsol, bsize = threshold_greedy_batch(
        oracle, states, sols, sizes, feats, ids, valid, taus, K,
        k_dyn=kdyn, engine=engine)
    for q in range(Q):
        st, sol, size = threshold_greedy(
            oracle, oracle.init_state(), jnp.full((K,), -1, jnp.int32),
            jnp.zeros((), jnp.int32), feats, ids, valid, taus[q], K,
            engine=engine, k_dyn=kdyn[q])
        np.testing.assert_array_equal(np.asarray(bsol[q]), np.asarray(sol))
        assert int(bsize[q]) == int(size) <= int(kdyn[q])


def test_dynamic_budget_is_prefix_of_full_run():
    """accept='first' with budget q accepts exactly the first q elements of
    the budget-K accept sequence — the property the batched drivers rely on
    for per-query budgets through shared fixed-shape buffers."""
    K = 10
    oracle, feats, ids, valid, tau = _setup("feature_coverage", seed=5)
    _, full, _ = threshold_greedy(
        oracle, oracle.init_state(), jnp.full((K,), -1, jnp.int32),
        jnp.zeros((), jnp.int32), feats, ids, valid, tau, K)
    for q in (0, 1, 4, 7):
        _, sol, size = threshold_greedy(
            oracle, oracle.init_state(), jnp.full((K,), -1, jnp.int32),
            jnp.zeros((), jnp.int32), feats, ids, valid, tau, K, k_dyn=q)
        assert int(size) == q
        np.testing.assert_array_equal(np.asarray(sol[:q]),
                                      np.asarray(full[:q]))


def test_bind_query_rebinding_and_kernel_gate():
    """bind_query rebinds only the matching oracle's knob; a traced
    hyper-parameter routes GraphCut/LogDet marginals through the jnp path
    (the Pallas kernel bakes the knob in at compile time)."""
    gc = GraphCut(feat_dim=4, total=jnp.ones((4,)), lam=0.5, use_kernel=True)
    ld = LogDetDiversity(feat_dim=4, k_max=4, alpha=1.0, use_kernel=True)
    fc = FeatureCoverage(feat_dim=4)
    assert F.consumes_query_params(gc) and F.consumes_query_params(ld)
    assert not F.consumes_query_params(fc)
    assert F.bind_query(fc, 0.1, 0.1) is fc
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (6, 4)))

    def gains(lam):
        orc = F.bind_query(gc, lam, None)
        return orc.marginals(orc.init_state(), orc.prep(orc.init_state(), x))

    g_traced = jax.jit(gains)(jnp.float32(0.5))     # traced lam: jnp path
    g_static = gains(0.5)                           # static lam: kernel path
    np.testing.assert_allclose(np.asarray(g_traced), np.asarray(g_static),
                               rtol=1e-5, atol=1e-5)
    jax.jit(lambda a: F.bind_query(ld, None, a).marginals(
        ld.init_state(), x))(jnp.float32(0.7))      # must not raise


# ---------------------------------------------------------------------------
# the driver layer: two_round_batch_sim / mesh / select_batch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ZOO)
def test_batch_sim_q1_matches_single_query_driver(name):
    """A Q=1 batch with k=cfg.k and default hyper-parameters reproduces
    two_round_sim exactly — the batched path is a strict generalization."""
    oracle, feats, ids, valid, _ = _setup(name, seed=2, n=256)
    m, k = 8, 8
    fm = feats.reshape(m, -1, feats.shape[-1])
    im = ids.reshape(m, -1)
    vm = valid.reshape(m, -1)
    cfg = MRConfig(k=k, n_total=feats.shape[0], n_machines=m)
    key = jax.random.PRNGKey(11)
    res1, log1 = two_round_sim(oracle, fm, im, vm, cfg, key)
    resb, logb = two_round_batch_sim(oracle, fm, im, vm,
                                     make_query_batch([k]), cfg, key)
    np.testing.assert_array_equal(np.asarray(res1.sol_ids),
                                  np.asarray(resb.sol_ids[0]))
    assert int(res1.sol_size) == int(resb.sol_size[0])
    np.testing.assert_allclose(float(res1.value), float(resb.value[0]),
                               rtol=1e-6)
    assert logb.n_rounds == 2


@pytest.mark.parametrize("engine", ["dense", "lazy"])
def test_batch_sim_lanes_match_q1_lanes(engine):
    """Every lane of a heterogeneous Q=5 batch equals the corresponding
    Q=1 call (same shared sample key): batching changes nothing per query."""
    X, fm, im, vm = _sim_instance(seed=3)
    oracle = FeatureCoverage(feat_dim=X.shape[1])
    K = 8
    cfg = MRConfig(k=K, n_total=X.shape[0], n_machines=fm.shape[0],
                   engine=engine)
    key = jax.random.PRNGKey(4)
    qb = make_query_batch([K, K // 2, 3, K, 1])
    resb, _ = two_round_batch_sim(oracle, fm, im, vm, qb, cfg, key)
    for q in range(5):
        qb1 = make_query_batch([int(qb.k[q])])
        r1, _ = two_round_batch_sim(oracle, fm, im, vm, qb1, cfg, key)
        np.testing.assert_array_equal(np.asarray(resb.sol_ids[q]),
                                      np.asarray(r1.sol_ids[0]))
        assert int(resb.sol_size[q]) <= int(qb.k[q])
    # identical specs -> identical lanes
    np.testing.assert_array_equal(np.asarray(resb.sol_ids[0]),
                                  np.asarray(resb.sol_ids[3]))


def test_batch_sim_per_query_hyperparams_match_static_oracles():
    """A lane with graph_cut_lam=0.25 equals two_round_sim run on a
    GraphCut oracle with lam statically 0.25 — per-query hyper-parameters
    are the real thing, not an approximation."""
    rng = np.random.default_rng(7)
    n, d, m, k = 256, 8, 8, 8
    X = jnp.asarray((rng.random((n, d)).astype(np.float32)) ** 2)
    fm = X.reshape(m, n // m, d)
    im = jnp.arange(n, dtype=jnp.int32).reshape(m, n // m)
    vm = jnp.ones((m, n // m), bool)
    total = jnp.sum(X, axis=0)
    cfg = MRConfig(k=k, n_total=n, n_machines=m)
    key = jax.random.PRNGKey(9)
    qb = make_query_batch([k, k], graph_cut_lam=[0.5, 0.25])
    resb, _ = two_round_batch_sim(GraphCut(feat_dim=d, total=total, lam=0.5),
                                  fm, im, vm, qb, cfg, key)
    for q, lam in enumerate((0.5, 0.25)):
        r1, _ = two_round_sim(GraphCut(feat_dim=d, total=total, lam=lam),
                              fm, im, vm, cfg, key)
        np.testing.assert_array_equal(np.asarray(resb.sol_ids[q]),
                                      np.asarray(r1.sol_ids))
        np.testing.assert_allclose(float(resb.value[q]), float(r1.value),
                                   rtol=1e-6)


def test_batch_sim_per_query_guarantee():
    """Each lane keeps the Theorem-8 guarantee for ITS OWN budget:
    value_q >= (1/2 - eps) * greedy_value(k_q)."""
    X, fm, im, vm = _sim_instance(seed=6, n=512)
    oracle = FeatureCoverage(feat_dim=X.shape[1])
    K = 8
    cfg = MRConfig(k=K, n_total=X.shape[0], n_machines=fm.shape[0], eps=0.1)
    qb = make_query_batch([K, K // 2, K // 4])
    resb, _ = two_round_batch_sim(oracle, fm, im, vm, qb, cfg,
                                  jax.random.PRNGKey(12))
    for q in range(3):
        kq = int(qb.k[q])
        _, _, gval = greedy(oracle, X, jnp.ones(X.shape[0], bool), kq)
        assert float(resb.value[q]) >= (0.5 - cfg.eps) * float(gval), \
            f"lane {q} (k={kq}) below guarantee"
        assert int(resb.n_dropped[q]) == 0
        assert int(resb.tau_fallback[q]) == 0


def test_select_batch_mesh_matches_select():
    """DistributedSelector.select_batch on the mesh substrate: lane 0
    (k=spec.k, default hyper-parameters) equals select() verbatim, budgets
    are honored, and the Q-parameterized RoundLog still shows 2 rounds."""
    n, d, k = 256, 8, 8
    rng = np.random.default_rng(13)
    X = jnp.asarray((rng.random((n, d)).astype(np.float32)) ** 2)
    mesh = make_mesh_for(len(jax.devices()), model_parallel=1)
    spec = SelectorSpec(k=k, oracle="feature_coverage", algorithm="two_round")
    sel = DistributedSelector(spec, mesh, n_total=n, feat_dim=d)
    key = jax.random.PRNGKey(14)
    res1 = sel.select(X, key=key)
    resb = sel.select_batch(X, make_query_batch([k, k // 2, 1]), key=key)
    np.testing.assert_array_equal(np.asarray(res1.sol_ids),
                                  np.asarray(resb.sol_ids[0]))
    assert [int(s) for s in resb.sol_size] == [k, k // 2, 1]
    assert sel.round_log_batch.n_rounds == 2
    assert int(jnp.sum(resb.n_dropped)) == 0


@pytest.mark.parametrize("oracle,bypassed", [("feature_coverage", False),
                                              ("graph_cut", True)])
def test_select_batch_records_kernel_bypass(oracle, bypassed):
    """use_kernel=True with a per-query knob on the query axis (graph_cut
    lam) runs the jnp oracle — the batch round log says so; an oracle with
    no per-query knob keeps its kernels and records nothing."""
    n, d, k = 128, 8, 4
    rng = np.random.default_rng(21)
    X = jnp.asarray((rng.random((n, d)).astype(np.float32)) ** 2)
    mesh = make_mesh_for(len(jax.devices()), model_parallel=1)
    spec = SelectorSpec(k=k, oracle=oracle, use_kernel=True)
    sel = DistributedSelector(spec, mesh, n_total=n, feat_dim=d,
                              total=X.sum(0))
    sel.select_batch(X, make_query_batch([k, 2]), key=jax.random.PRNGKey(3))
    assert ("kernel_bypassed" in sel.runtime_events()) == bypassed
    assert ("kernel_bypassed" in sel.round_log_batch.summary()) == bypassed


def test_batch_sim_and_mesh_round_logs_agree():
    """Sim and mesh batched drivers claim identical per-round bytes for the
    same machine count (the DESIGN.md §1 record-for-record invariant,
    extended to the query axis)."""
    n, d, K, Q = 256, 8, 8, 4
    X, fm, im, vm = _sim_instance(seed=1, n=n, d=d, m=1)
    oracle = FeatureCoverage(feat_dim=d)
    cfg = MRConfig(k=K, n_total=n, n_machines=1)
    _, sim_log = two_round_batch_sim(oracle, fm, im, vm,
                                     make_query_batch([K] * Q), cfg,
                                     jax.random.PRNGKey(0))
    mesh = make_mesh_for(1, model_parallel=1)
    _, round_log = mr.two_round_batch_mesh(oracle, cfg, mesh)
    mesh_log = round_log(Q)
    assert mesh_log.n_rounds == sim_log.n_rounds == 2
    for s_rec, m_rec in zip(sim_log.records, mesh_log.records):
        assert s_rec.name == m_rec.name
        assert s_rec.bytes_per_machine == m_rec.bytes_per_machine
        assert s_rec.bytes_total == m_rec.bytes_total


# ---------------------------------------------------------------------------
# satellite bugfix regressions
# ---------------------------------------------------------------------------

def test_rand_greedi_local_win_is_consistent():
    """Instance where the best LOCAL machine beats the central greedy
    (the classic myopia trap: a big overlapping element baits the central
    run), so rand_greedi must return the local branch — and its ids, size
    and value must all describe the same solution."""
    # universe u1..u6, unit weights.  Machine 0 holds the optimal pair
    # x={u1,u2,u3}, y={u4,u5,u6} (local value 6).  Machine 1 holds the
    # bait z={u1,u2,u4,u5} (singleton 4) and w={u6}.  Central greedy on
    # the union picks z first, then recovers only 1 more unit: value 5.
    d = 6
    x = [1, 1, 1, 0, 0, 0]
    y = [0, 0, 0, 1, 1, 1]
    z = [1, 1, 0, 1, 1, 0]
    w = [0, 0, 0, 0, 0, 1]
    feats_mk = jnp.asarray([[x, y], [z, w]], jnp.float32)   # (m=2, 2, d)
    ids_mk = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    valid_mk = jnp.ones((2, 2), bool)
    oracle = WeightedCoverage(feat_dim=d)
    res, _ = rand_greedi(oracle, feats_mk, ids_mk, valid_mk, k=2)
    # the local branch won:
    np.testing.assert_array_equal(np.sort(np.asarray(res.sol_ids)), [0, 1])
    np.testing.assert_allclose(float(res.value), 6.0, rtol=1e-6)
    # ids/size/value mutual consistency (the bug kept central's size):
    assert int(res.sol_size) == int(jnp.sum(res.sol_ids >= 0)) == 2
    sel = np.asarray(res.sol_ids)
    sel = sel[sel >= 0]
    st = oracle.init_state()
    allf = feats_mk.reshape(4, d)
    for e in sel:
        st = oracle.add(st, allf[e])
    np.testing.assert_allclose(float(oracle.value(st)), float(res.value),
                               rtol=1e-6)


@pytest.mark.parametrize("name", ORACLE_NAMES)
def test_opt_upper_bound_every_oracle_with_tp_rebuild(name):
    """opt_upper_bound must work for EVERY registered oracle, including
    through the TPOracle branch that rebuilds a full-width oracle — the
    bug dropped reference/total there, asserting for facility_location,
    exemplar and graph_cut."""
    n, d, k = 128, 8, 4
    rng = np.random.default_rng(17)
    X = jnp.asarray((rng.random((n, d)).astype(np.float32)) ** 2)
    ref = jnp.asarray(rng.random((16, d)).astype(np.float32)) \
        if name in ("facility_location", "exemplar") else None
    total = jnp.sum(X, axis=0) \
        if name in ("graph_cut", "saturated_coverage") else None
    mesh = make_mesh_for(len(jax.devices()), model_parallel=1)
    spec = SelectorSpec(k=k, oracle=name, algorithm="two_round")
    sel = DistributedSelector(spec, mesh, n_total=n, feat_dim=d,
                              reference=ref, total=total)
    ub = float(sel.opt_upper_bound(X))
    # force the rebuild branch: wrap in TPOracle (psum over a size-1 axis
    # would fail outside shard_map, so the rebuild path must fire) and
    # check the stashed reference/total produce the same bound
    sel.oracle = F.TPOracle(base=sel.oracle, axis="model")
    ub_rebuilt = float(sel.opt_upper_bound(X))
    assert np.isfinite(ub) and ub > 0
    np.testing.assert_allclose(ub_rebuilt, ub, rtol=1e-5)


def test_tau_grid_degenerate_sample_guard():
    """An empty/all-masked sample must NOT produce an all-zero threshold
    grid (which would accept every candidate); the grid falls back to +inf
    and the event is reported."""
    oracle = FeatureCoverage(feat_dim=4)
    cfg = MRConfig(k=4, n_total=64, n_machines=4)
    feats = jnp.ones((8, 4), jnp.float32)
    ids = jnp.arange(8, dtype=jnp.int32)
    taus, deg = mr._tau_grid(oracle, cfg, feats, ids, jnp.zeros((8,), bool))
    assert int(deg) == 1
    assert bool(jnp.all(jnp.isinf(taus)))
    # non-degenerate sample: finite grid, no flag
    taus2, deg2 = mr._tau_grid(oracle, cfg, feats, ids, jnp.ones((8,), bool))
    assert int(deg2) == 0
    assert bool(jnp.all(jnp.isfinite(taus2))) and bool(jnp.all(taus2 > 0))


def test_two_round_sim_all_masked_reports_fallback():
    """End-to-end: a fully masked corpus selects NOTHING (previously the
    zero grid admitted arbitrary elements) and raises tau_fallback."""
    X, fm, im, _ = _sim_instance(seed=19, n=128)
    oracle = FeatureCoverage(feat_dim=X.shape[1])
    cfg = MRConfig(k=4, n_total=X.shape[0], n_machines=fm.shape[0])
    vm0 = jnp.zeros(im.shape, bool)
    res, _ = two_round_sim(oracle, fm, im, vm0, cfg, jax.random.PRNGKey(0))
    assert int(res.sol_size) == 0
    assert int(res.tau_fallback) >= 1
    assert bool(jnp.all(res.sol_ids == -1))
    # healthy corpus: no fallback
    res2, _ = two_round_sim(oracle, fm, im, jnp.ones(im.shape, bool), cfg,
                            jax.random.PRNGKey(0))
    assert int(res2.tau_fallback) == 0 and int(res2.sol_size) == 4
