"""Pallas kernel validation: shape/dtype sweeps against the ref.py oracles
(interpret=True on CPU), plus hypothesis property tests on the kernel's
algebraic invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.facility_marginals import (facility_marginals,
                                              rectified_residual_sum)

jax.config.update("jax_enable_x64", False)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


SHAPES_FM = [
    # (C, r, d) — exact tile multiples, ragged, tiny, tall, wide
    (256, 512, 64), (256, 512, 128), (100, 300, 96), (8, 128, 16),
    (1, 1, 1), (513, 257, 33), (1024, 128, 256), (37, 1024, 8),
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("C,r,d", SHAPES_FM)
@pytest.mark.parametrize("dtype", DTYPES)
def test_facility_marginals_matches_ref(C, r, d, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(C * 7 + r), 3)
    cand = _rand(k1, (C, d), dtype)
    refs = _rand(k2, (r, d), dtype)
    state = jnp.abs(_rand(k3, (r,), jnp.float32))
    got = facility_marginals(cand, refs, state, interpret=True)
    want = ref.facility_marginals(cand, refs, state)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * d)


@pytest.mark.parametrize("C,r", [(256, 512), (100, 300), (1, 1), (513, 129),
                                 (8, 2048)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_rectified_residual_sum_matches_ref(C, r, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(C + r))
    aux = jnp.abs(_rand(k1, (C, r), dtype))
    state = jnp.abs(_rand(k2, (r,), jnp.float32))
    got = rectified_residual_sum(aux, state, interpret=True)
    want = ref.rectified_residual_sum(aux.astype(jnp.float32), state)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * r)


@pytest.mark.parametrize("block_c,block_r", [(8, 128), (64, 128), (256, 512),
                                             (16, 256)])
def test_block_shape_invariance(block_c, block_r):
    """Output must not depend on the tiling."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    cand = _rand(k1, (200, 48), jnp.float32)
    refs = _rand(k2, (333, 48), jnp.float32)
    state = jnp.abs(_rand(k3, (333,), jnp.float32))
    base = ref.facility_marginals(cand, refs, state)
    got = facility_marginals(cand, refs, state, block_c=block_c,
                             block_r=block_r, interpret=True)
    np.testing.assert_allclose(got, base, rtol=1e-5, atol=1e-4)


def test_kernels_refuse_unknown_backend(monkeypatch):
    """The interpret decision is explicit: compiled on tpu, interpreted on
    cpu, and any other backend raises instead of interpreting in silence."""
    from repro.kernels import ops

    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no lowering for backend 'gpu'"):
        ops._interpret()
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False


def test_ops_dispatch_cpu_interpret():
    """ops.* entry points run (interpret) on CPU and match ref."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    cand = _rand(k1, (64, 32), jnp.float32)
    refs = _rand(k2, (96, 32), jnp.float32)
    state = jnp.abs(_rand(k3, (96,), jnp.float32))
    np.testing.assert_allclose(ops.facility_marginals(cand, refs, state),
                               ref.facility_marginals(cand, refs, state),
                               rtol=1e-5, atol=1e-4)
    aux = jnp.maximum(cand @ refs.T, 0.0)
    np.testing.assert_allclose(ops.rectified_residual_sum(aux, state),
                               ref.rectified_residual_sum(aux, state),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# property tests: kernel output obeys the submodular-marginal invariants
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(2, 40), st.integers(2, 60), st.integers(1, 16),
       st.integers(0, 2 ** 31 - 1))
def test_marginals_nonneg_and_monotone_in_state(C, r, d, seed):
    """gains >= 0 always; pointwise-larger state => pointwise-smaller gains
    (diminishing returns as the cover grows)."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    cand = jax.random.normal(k1, (C, d))
    refs = jax.random.normal(k2, (r, d))
    st0 = jnp.abs(jax.random.normal(k3, (r,)))
    bump = jnp.abs(jax.random.normal(k4, (r,)))
    g0 = facility_marginals(cand, refs, st0, interpret=True)
    g1 = facility_marginals(cand, refs, st0 + bump, interpret=True)
    assert bool(jnp.all(g0 >= 0)) and bool(jnp.all(g1 <= g0 + 1e-5))


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 30), st.integers(1, 50), st.integers(1, 12),
       st.integers(0, 2 ** 31 - 1))
def test_zero_state_reduces_to_sum_of_sims(C, r, d, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    cand = jax.random.normal(k1, (C, d))
    refs = jax.random.normal(k2, (r, d))
    got = facility_marginals(cand, refs, jnp.zeros((r,)), interpret=True)
    want = jnp.sum(jnp.maximum(cand @ refs.T, 0.0), axis=-1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_oracle_kernel_path_consistency():
    """FacilityLocation(use_kernel=True) equals the pure-jnp oracle path."""
    from repro.core.functions import FacilityLocation

    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    refs = jax.random.normal(k1, (64, 24))
    f_jnp = FacilityLocation(feat_dim=24, reference=refs, use_kernel=False)
    f_krn = FacilityLocation(feat_dim=24, reference=refs, use_kernel=True)
    cand = jax.random.normal(k2, (40, 24))
    st0 = f_jnp.init_state()
    aux = f_jnp.prep(st0, cand)
    np.testing.assert_allclose(f_krn.marginals(st0, aux),
                               f_jnp.marginals(st0, aux),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# coverage_marginals kernel
# ---------------------------------------------------------------------------

from repro.kernels.coverage_marginals import coverage_marginals  # noqa: E402

SHAPES_CM = [
    (256, 512), (100, 96), (8, 128), (1, 1), (513, 257), (1024, 64),
]


@pytest.mark.parametrize("C,d", SHAPES_CM)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("weighted", [False, True])
def test_coverage_marginals_matches_ref(C, d, dtype, weighted):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(C * 13 + d), 3)
    x = jnp.abs(_rand(k1, (C, d), dtype))          # coverage needs x >= 0
    state = jnp.abs(_rand(k2, (d,), jnp.float32))
    w = jnp.abs(_rand(k3, (d,), jnp.float32)) if weighted else None
    got = coverage_marginals(x, state, w, interpret=True)
    want = ref.coverage_marginals(x, state, w)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 200), st.integers(1, 160), st.integers(0, 2 ** 31))
def test_coverage_marginals_property(C, d, seed):
    """Property: marginals are nonnegative (monotone f) and DECREASE as the
    state grows (submodularity), and the kernel agrees with ref."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jnp.abs(jax.random.normal(k1, (C, d)))
    st0 = jnp.abs(jax.random.normal(k2, (d,)))
    st1 = st0 + jnp.abs(jax.random.normal(k3, (d,)))   # larger state
    g0 = coverage_marginals(x, st0, interpret=True)
    g1 = coverage_marginals(x, st1, interpret=True)
    assert np.all(np.asarray(g0) >= -1e-6)
    assert np.all(np.asarray(g1) <= np.asarray(g0) + 1e-5)  # submodular
    np.testing.assert_allclose(np.asarray(g0),
                               np.asarray(ref.coverage_marginals(x, st0)),
                               rtol=1e-4, atol=1e-4)


def test_feature_coverage_oracle_kernel_route():
    """FeatureCoverage(use_kernel=True) == plain oracle end-to-end."""
    from repro.core import FeatureCoverage
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.random((64, 32)).astype(np.float32))
    st0 = jnp.asarray(rng.random(32).astype(np.float32))
    plain = FeatureCoverage(feat_dim=32)
    fused = FeatureCoverage(feat_dim=32, use_kernel=True)
    np.testing.assert_allclose(
        np.asarray(plain.marginals(st0, X)),
        np.asarray(fused.marginals(st0, X)), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# many-lane coverage marginals: one pass over x for a stack of states
# ---------------------------------------------------------------------------

from repro.kernels import coverage_marginals as cm  # noqa: E402


def _lanes_case(C, d, L, dtype, weighted):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(C * 7 + d + L), 3)
    x = jnp.abs(_rand(k1, (C, d), dtype))
    states = jnp.abs(_rand(k2, (L, d), jnp.float32)) * 3.0
    w = jnp.abs(_rand(k3, (d,), jnp.float32)) if weighted else None
    return x, states, w


def _one_lane_stack(x, states, w):
    """The one-lane kernel called once per state (a lax.map, no vmap)."""
    return jax.lax.map(
        lambda s: coverage_marginals(x, s, w, interpret=True), states)


@pytest.mark.parametrize("C,d", SHAPES_CM)
@pytest.mark.parametrize("L", [1, 5, 37, 296])
def test_coverage_marginals_lanes_bit_exact(C, d, L):
    """Each lane of the many-lane kernel equals the one-lane kernel bit for
    bit, for f32 and bf16 x, weighted and not."""
    for dtype in DTYPES:
        for weighted in (False, True):
            x, states, w = _lanes_case(C, d, L, dtype, weighted)
            got = cm.coverage_marginals_lanes(x, states, w, interpret=True)
            assert got.shape == (L, C)
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(_one_lane_stack(
                                              x, states, w)))


def _stats_delta(fn):
    before = cm.lane_stats()
    out = fn()
    after = cm.lane_stats()
    return out, {k: after[k] - before[k] for k in after}


@pytest.mark.parametrize("batched", ["state", "x", "weights"])
def test_coverage_marginals_vmap_routing(batched):
    """A vmap over the state alone takes the many-lane kernel; one that
    batches x or the weights keeps Pallas's per-lane batching.  Both give
    the one-lane kernel's numbers."""
    L, C, d = 6, 100, 96
    x, states, w = _lanes_case(C, d, L, jnp.float32, True)
    xs = jnp.stack([x * (1 + i) for i in range(L)])
    ws = jnp.stack([w * (1 + i) for i in range(L)])
    fn, args, want = {
        "state": (lambda s: ops.coverage_marginals(x, s, w), (states,),
                  _one_lane_stack(x, states, w)),
        "x": (lambda xx, s: ops.coverage_marginals(xx, s, w), (xs, states),
              jnp.stack([coverage_marginals(xs[i], states[i], w,
                                            interpret=True)
                         for i in range(L)])),
        "weights": (lambda s, ww: ops.coverage_marginals(x, s, ww),
                    (states, ws),
                    jnp.stack([coverage_marginals(x, states[i], ws[i],
                                                  interpret=True)
                               for i in range(L)])),
    }[batched]
    got, delta = _stats_delta(lambda: jax.jit(jax.vmap(fn))(*args))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if batched == "state":
        assert delta == {"fused_calls": 1, "fused_lanes": L,
                         "per_lane_calls": 0}
    else:
        assert delta == {"fused_calls": 0, "fused_lanes": 0,
                         "per_lane_calls": 1}


@pytest.mark.parametrize("outer,inner,x_outer", [
    (3, 4, False),      # queries x lanes: both fold into 12 lanes
    (2, 5, True),       # machines x lanes: lanes fused, machines a grid axis
    (1, 5, True),       # one machine: an axis of size 1 is no axis
])
def test_coverage_marginals_nested_vmaps(outer, inner, x_outer):
    """Nested vmaps match the un-kerneled FeatureCoverage, and the
    state-only axes fold into one many-lane call."""
    from repro.core import FeatureCoverage
    C, d = 130, 40
    kx, ks = jax.random.split(jax.random.PRNGKey(outer * 10 + inner))
    xm = jnp.abs(_rand(kx, (outer, C, d), jnp.float32))
    S = jnp.abs(_rand(ks, (outer, inner, d), jnp.float32))
    plain = FeatureCoverage(feat_dim=d)
    fused = FeatureCoverage(feat_dim=d, use_kernel=True)

    def run(orc):
        lanes = lambda x, s: jax.vmap(lambda st: orc.marginals(st, x))(s)
        if x_outer:                                  # (machines, lanes)
            return jax.jit(jax.vmap(lanes))(xm, S)
        return jax.jit(jax.vmap(lambda s: lanes(xm[0], s)))(S)

    got, delta = _stats_delta(lambda: run(fused))
    np.testing.assert_allclose(np.asarray(got), np.asarray(run(plain)),
                               rtol=1e-5, atol=1e-5)
    lanes = inner if x_outer else outer * inner
    assert delta == {"fused_calls": 1, "fused_lanes": lanes,
                     "per_lane_calls": 0}


def _grouped_corpus(seed, n=512, d=48, groups=8):
    """Contiguous row groups, each bright on its own band of features."""
    rng = np.random.default_rng(seed)
    band = d // groups
    X = rng.uniform(0.0, 0.05, (n, d)).astype(np.float32)
    for g in range(groups):
        rows = slice(g * n // groups, (g + 1) * n // groups)
        X[rows, g * band:(g + 1) * band] += rng.uniform(
            0.2, 0.66) * rng.uniform(0.75, 1.25, (n // groups, band))
    return jnp.asarray(X)


def _per_lane_route(x, state, weights=None, **_):
    """The one-lane kernel with no vmap rule: Pallas batches every lane."""
    return coverage_marginals(x, state, weights, interpret=True)


@pytest.mark.parametrize("driver", ["two_round", "two_round_batch",
                                    "selector", "selector_batch"])
def test_coverage_lane_fusion_end_to_end(driver, monkeypatch):
    """Selections with use_kernel=True return the same ids and values with
    the many-lane filter as with every lane read apart."""
    from repro.core import (DistributedSelector, FeatureCoverage, MRConfig,
                            SelectorSpec, make_query_batch, two_round_sim,
                            two_round_batch_sim)
    from repro.launch.mesh import make_mesh_for
    n, d, k, m = 512, 48, 8, 2
    X = _grouped_corpus(3, n, d)
    key = jax.random.PRNGKey(5)
    qb = make_query_batch([k, k // 2])
    oracle = FeatureCoverage(feat_dim=d, use_kernel=True)
    cfg = MRConfig(k=k, n_total=n, n_machines=m, engine="fused")
    shards = (X.reshape(m, n // m, d),
              jnp.arange(n, dtype=jnp.int32).reshape(m, n // m),
              jnp.ones((m, n // m), bool))

    def select():
        if driver == "two_round":
            return jax.jit(lambda *a: two_round_sim(
                oracle, *a, cfg, key)[0])(*shards)
        if driver == "two_round_batch":
            return jax.jit(lambda *a: two_round_batch_sim(
                oracle, *a, qb, cfg, key)[0])(*shards)
        mesh = make_mesh_for(len(jax.devices()), model_parallel=1)
        spec = SelectorSpec(k=k, oracle="feature_coverage", engine="fused",
                            use_kernel=True)
        sel = DistributedSelector(spec, mesh, n_total=n, feat_dim=d)
        sels.append(sel)
        if driver == "selector":
            return sel.select(X, key=key)
        return sel.select_batch(X, qb, key=key)

    sels = []
    fused, delta = _stats_delta(select)
    assert delta["fused_calls"] >= 1
    if sels:
        ev = sels[0].runtime_events()
        assert ev["marginals_fused_lanes"] >= delta["fused_lanes"] > 0
    monkeypatch.setattr(ops, "coverage_marginals", _per_lane_route)
    apart, delta = _stats_delta(select)
    assert delta["fused_calls"] == 0
    np.testing.assert_array_equal(np.asarray(fused.sol_ids),
                                  np.asarray(apart.sol_ids))
    np.testing.assert_array_equal(np.asarray(fused.value),
                                  np.asarray(apart.value))


# ---------------------------------------------------------------------------
# saturated_coverage_marginals kernel
# ---------------------------------------------------------------------------

from repro.kernels.saturated_coverage_marginals import (  # noqa: E402
    saturated_coverage_marginals)


@pytest.mark.parametrize("C,d", SHAPES_CM)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("weighted", [False, True])
def test_saturated_coverage_marginals_matches_ref(C, d, dtype, weighted):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(C * 19 + d), 4)
    x = jnp.abs(_rand(k1, (C, d), dtype))          # coverage needs x >= 0
    state = jnp.abs(_rand(k2, (d,), jnp.float32))
    cap = jnp.abs(_rand(k3, (d,), jnp.float32)) * 2.0
    w = jnp.abs(_rand(k4, (d,), jnp.float32)) if weighted else None
    got = saturated_coverage_marginals(x, state, cap, w, interpret=True)
    want = ref.saturated_coverage_marginals(x, state, cap, w)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * d)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 200), st.integers(1, 160), st.integers(0, 2 ** 31))
def test_saturated_coverage_marginals_property(C, d, seed):
    """Nonneg gains, bounded by the unsaturated (linear) gain; a larger
    state gives pointwise-smaller gains (diminishing returns); kernel ==
    ref."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jnp.abs(jax.random.normal(k1, (C, d)))
    st0 = jnp.abs(jax.random.normal(k2, (d,)))
    cap = jnp.abs(jax.random.normal(k3, (d,))) * 2.0
    g0 = saturated_coverage_marginals(x, st0, cap, interpret=True)
    g1 = saturated_coverage_marginals(
        x, st0 + jnp.abs(jax.random.normal(k4, (d,))), cap, interpret=True)
    assert np.all(np.asarray(g0) >= -1e-6)
    assert np.all(np.asarray(g0) <= np.asarray(jnp.sum(x, axis=-1)) + 1e-4)
    assert np.all(np.asarray(g1) <= np.asarray(g0) + 1e-5)  # submodular
    np.testing.assert_allclose(
        np.asarray(g0),
        np.asarray(ref.saturated_coverage_marginals(x, st0, cap)),
        rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# weighted_coverage_marginals kernel
# ---------------------------------------------------------------------------

from repro.kernels.weighted_coverage_marginals import (  # noqa: E402
    weighted_coverage_marginals)


@pytest.mark.parametrize("C,U", SHAPES_CM)
@pytest.mark.parametrize("dtype", DTYPES)
def test_weighted_coverage_marginals_matches_ref(C, U, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(C * 17 + U))
    x = (jax.random.uniform(k1, (C, U)) < 0.3).astype(dtype)  # incidence rows
    state = jnp.abs(_rand(k2, (U,), jnp.float32))
    got = weighted_coverage_marginals(x, state, interpret=True)
    want = ref.weighted_coverage_marginals(x, state)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * U)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 200), st.integers(1, 160), st.integers(0, 2 ** 31))
def test_weighted_coverage_marginals_property(C, U, seed):
    """Nonneg gains; pointwise-smaller remaining weight => smaller gains
    (diminishing returns as the cover grows); kernel == ref."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = (jax.random.uniform(k1, (C, U)) < 0.4).astype(jnp.float32)
    st0 = jnp.abs(jax.random.normal(k2, (U,)))
    g0 = weighted_coverage_marginals(x, st0, interpret=True)
    g1 = weighted_coverage_marginals(x, st0 * 0.5, interpret=True)
    assert np.all(np.asarray(g0) >= -1e-6)
    assert np.all(np.asarray(g1) <= np.asarray(g0) + 1e-5)
    np.testing.assert_allclose(
        np.asarray(g0), np.asarray(ref.weighted_coverage_marginals(x, st0)),
        rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# oracle-zoo kernels: graph_cut / logdet / exemplar vs ref.py
# ---------------------------------------------------------------------------

from repro.kernels.exemplar_marginals import exemplar_marginals  # noqa: E402
from repro.kernels.graph_cut_marginals import graph_cut_marginals  # noqa: E402
from repro.kernels.logdet_marginals import logdet_marginals  # noqa: E402


@pytest.mark.parametrize("C,d", SHAPES_CM)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("lam", [0.0, 0.5])
def test_graph_cut_marginals_matches_ref(C, d, dtype, lam):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(C * 3 + d), 3)
    x = jnp.abs(_rand(k1, (C, d), dtype))            # cut weights need x >= 0
    total = jnp.abs(_rand(k2, (d,), jnp.float32)) * C
    state = jnp.abs(_rand(k3, (d,), jnp.float32))
    got = graph_cut_marginals(x, total, state, lam, interpret=True)
    want = ref.graph_cut_marginals(x.astype(jnp.float32), total, state, lam)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * C)


@pytest.mark.parametrize("C,k,d", [(256, 8, 64), (100, 3, 96), (8, 1, 16),
                                   (1, 1, 1), (513, 33, 40), (64, 0, 12)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_logdet_marginals_matches_ref(C, k, d, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(C * 5 + d))
    x = _rand(k1, (C, d), dtype)
    # a realistic U: orthonormal-ish rows with zero tail (|S| < k_max)
    U = _rand(k2, (k, d), jnp.float32) * 0.3
    if k > 1:
        U = U.at[-1].set(0.0)
    got = logdet_marginals(x, U, alpha=0.7, interpret=True)
    want = ref.logdet_marginals(x.astype(jnp.float32), U, alpha=0.7)
    # log() amplifies the matmul's reduction-order noise near cancellation
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("C,r,d", SHAPES_FM)
@pytest.mark.parametrize("dtype", DTYPES)
def test_exemplar_marginals_matches_ref(C, r, d, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(C * 11 + r), 3)
    cand = _rand(k1, (C, d), dtype)
    refs = _rand(k2, (r, d), dtype)
    state = jnp.abs(_rand(k3, (r,), jnp.float32)) * d
    got = exemplar_marginals(cand, refs, state, interpret=True)
    want = ref.exemplar_marginals(cand, refs, state)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * max(d, r))


@pytest.mark.parametrize("block_c,block_r", [(8, 128), (64, 128), (16, 256)])
def test_zoo_kernels_block_shape_invariance(block_c, block_r):
    """Tiling must not change any zoo kernel's output."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(1), 4)
    cand = _rand(k1, (200, 48), jnp.float32)
    refs = _rand(k2, (333, 48), jnp.float32)
    state_r = jnp.abs(_rand(k3, (333,), jnp.float32)) * 48
    np.testing.assert_allclose(
        exemplar_marginals(cand, refs, state_r, block_c=block_c,
                           block_r=block_r, interpret=True),
        ref.exemplar_marginals(cand, refs, state_r), rtol=1e-5, atol=1e-3)
    x = jnp.abs(cand)
    total = jnp.abs(_rand(k4, (48,), jnp.float32)) * 200
    state_d = jnp.abs(_rand(k3, (48,), jnp.float32))
    np.testing.assert_allclose(
        graph_cut_marginals(x, total, state_d, 0.5, block_c=block_c,
                            block_f=block_r, interpret=True),
        ref.graph_cut_marginals(x, total, state_d, 0.5),
        rtol=1e-5, atol=1e-3)
    U = _rand(k4, (16, 48), jnp.float32) * 0.3
    np.testing.assert_allclose(
        logdet_marginals(cand, U, block_c=block_c, interpret=True),
        ref.logdet_marginals(cand, U), rtol=1e-5, atol=1e-4)
    inc = (jnp.abs(cand) < 0.4).astype(jnp.float32)
    np.testing.assert_allclose(
        weighted_coverage_marginals(inc, state_d, block_c=block_c,
                                    block_u=block_r, interpret=True),
        ref.weighted_coverage_marginals(inc, state_d), rtol=1e-5, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 40), st.integers(1, 12), st.integers(0, 2 ** 31 - 1))
def test_zoo_kernel_submodular_invariants(C, d, seed):
    """Kernel outputs obey diminishing returns: a pointwise-larger state
    (bigger cut accumulator / smaller residual basis span is excluded here;
    graph_cut and exemplar shrink pointwise as their states grow)."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jnp.abs(jax.random.normal(k1, (C, d)))
    total = jnp.sum(x, axis=0)
    s0 = jnp.abs(jax.random.normal(k2, (d,)))
    bump = jnp.abs(jax.random.normal(k3, (d,)))
    g0 = graph_cut_marginals(x, total, s0, 0.5, interpret=True)
    g1 = graph_cut_marginals(x, total, s0 + bump, 0.5, interpret=True)
    assert bool(jnp.all(g1 <= g0 + 1e-5))
    refs = jnp.abs(jax.random.normal(k4, (max(2, C // 2), d)))
    m0 = jnp.sum(refs * refs, axis=-1)
    e0 = exemplar_marginals(x, refs, m0, interpret=True)
    e1 = exemplar_marginals(x, refs, m0 * 0.5, interpret=True)  # cover shrank
    assert bool(jnp.all(e0 >= -1e-6)) and bool(jnp.all(e1 <= e0 + 1e-5))


from oracle_contract import KERNELED, REGISTRY  # noqa: E402


@pytest.mark.parametrize("name", KERNELED)
def test_oracle_kernel_routes_match_plain(name):
    """Every kernel-capable registered oracle: use_kernel=True equals the
    pure-jnp path on a non-trivial state.  Parametrized over the shared
    registry's KERNELED list, so a new kerneled oracle is swept by adding
    it there — no per-oracle copy."""
    import dataclasses

    rng = np.random.default_rng(23)
    plain, X = REGISTRY[name](rng, 40, 24)
    fused = dataclasses.replace(plain, use_kernel=True)
    st_ = plain.init_state()
    aux = plain.prep(st_, X)
    for i in (3, 11):   # route through a non-trivial state too
        st_ = plain.add(st_, jax.tree.map(lambda a: a[i], aux))
    np.testing.assert_allclose(
        np.asarray(fused.chunk_marginals(st_, X)),
        np.asarray(plain.marginals(st_, plain.prep(st_, X))),
        rtol=1e-5, atol=1e-4, err_msg=name)


# ---------------------------------------------------------------------------
# fused chunk-accept kernels: the whole accept loop inside one pallas_call
# ---------------------------------------------------------------------------

SHAPES_ACC = [
    # (B, d) — tile multiples, ragged, tiny, wide
    (32, 128), (13, 20), (1, 1), (64, 300), (129, 64), (8, 1024),
]


def _accept_case(seed, B, d, dtype, nonneg=True):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = _rand(k1, (B, d), dtype)
    if nonneg:
        x = jnp.abs(x)
    state = jnp.abs(_rand(k2, (d,), jnp.float32))
    elig = jax.random.uniform(k3, (B,)) < 0.8
    return x, state, elig


def _assert_accept_matches(got, want, d, dtype, name):
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]),
                                  err_msg=f"{name}: accept masks differ")
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=tol, atol=tol * d, err_msg=name)
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                               rtol=tol, atol=tol * d, err_msg=name)


@pytest.mark.parametrize("B,d", SHAPES_ACC)
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_coverage_accept_matches_ref(B, d, dtype):
    from repro.kernels.coverage_accept import coverage_accept

    x, state, elig = _accept_case(B * 31 + d, B, d, dtype)
    w = jnp.abs(_rand(jax.random.PRNGKey(d), (d,), jnp.float32))
    # tau from the gain scale so accepts/rejects both occur
    tau = float(jnp.median(ref.coverage_marginals(x, state, w)))
    budget = max(1, B // 3)
    got = coverage_accept(x, state, w, elig, tau, budget, interpret=True)
    want = ref.coverage_accept(x, state, w, elig, tau, budget)
    _assert_accept_matches(got, want, d, dtype, "coverage_accept")


@pytest.mark.parametrize("B,d", SHAPES_ACC)
def test_weighted_coverage_accept_matches_ref(B, d):
    from repro.kernels.weighted_coverage_accept import \
        weighted_coverage_accept

    rng = np.random.default_rng(B * 7 + d)
    x = jnp.asarray((rng.random((B, d)) < 0.3).astype(np.float32))
    state = jnp.abs(_rand(jax.random.PRNGKey(d), (d,), jnp.float32))
    elig = jnp.asarray(rng.random(B) < 0.8)
    tau = float(jnp.median(ref.weighted_coverage_marginals(x, state)))
    budget = max(1, B // 2)
    got = weighted_coverage_accept(x, state, elig, tau, budget,
                                   interpret=True)
    want = ref.weighted_coverage_accept(x, state, elig, tau, budget)
    _assert_accept_matches(got, want, d, jnp.float32,
                           "weighted_coverage_accept")


@pytest.mark.parametrize("B,d", SHAPES_ACC)
def test_saturated_coverage_accept_matches_ref(B, d):
    from repro.kernels.saturated_coverage_accept import \
        saturated_coverage_accept

    x, state, elig = _accept_case(B * 13 + d, B, d, jnp.float32)
    cap = jnp.abs(_rand(jax.random.PRNGKey(B), (d,), jnp.float32)) * 2.0
    w = jnp.abs(_rand(jax.random.PRNGKey(d + 1), (d,), jnp.float32))
    tau = float(jnp.median(
        ref.saturated_coverage_marginals(x, state, cap, w)))
    budget = max(1, B // 3)
    got = saturated_coverage_accept(x, state, cap, w, elig, tau, budget,
                                    interpret=True)
    want = ref.saturated_coverage_accept(x, state, cap, w, elig, tau,
                                         budget)
    _assert_accept_matches(got, want, d, jnp.float32,
                           "saturated_coverage_accept")


@pytest.mark.parametrize("B,d", SHAPES_ACC)
def test_graph_cut_accept_matches_ref(B, d):
    from repro.kernels.graph_cut_accept import graph_cut_accept

    x, state, elig = _accept_case(B * 17 + d, B, d, jnp.float32)
    total = jnp.sum(x, axis=0) + state
    tau = float(jnp.median(ref.graph_cut_marginals(x, total, state, 0.5)))
    budget = max(1, B // 3)
    got = graph_cut_accept(x, total, state, elig, tau, budget, 0.5,
                           interpret=True)
    want = ref.graph_cut_accept(x, total, state, elig, tau, budget, 0.5)
    _assert_accept_matches(got, want, d, jnp.float32, "graph_cut_accept")


@pytest.mark.parametrize("B,r,d", [(32, 128, 64), (13, 20, 8), (1, 1, 1),
                                   (64, 300, 16), (100, 257, 33)])
def test_facility_accept_matches_ref(B, r, d):
    from repro.kernels.facility_accept import facility_accept

    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(B * 3 + r), 4)
    cand = _rand(k1, (B, d), jnp.float32)
    refs = _rand(k2, (r, d), jnp.float32)
    state = jnp.abs(_rand(k3, (r,), jnp.float32)) * 0.1
    elig = jax.random.uniform(k4, (B,)) < 0.8
    tau = float(jnp.median(ref.facility_marginals(cand, refs, state)))
    budget = max(1, B // 3)
    got = facility_accept(cand, refs, state, elig, tau, budget,
                          interpret=True)
    want = ref.facility_accept(cand, refs, state, elig, tau, budget)
    _assert_accept_matches(got, want, d, jnp.float32, "facility_accept")


@pytest.mark.parametrize("B,r,d", [(32, 128, 64), (13, 20, 8), (1, 1, 1),
                                   (64, 300, 16), (100, 257, 33)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_exemplar_accept_matches_ref(B, r, d, dtype):
    from repro.kernels.exemplar_accept import exemplar_accept

    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(B * 5 + r), 4)
    cand = _rand(k1, (B, d), dtype)
    refs = _rand(k2, (r, d), dtype)
    state = jnp.abs(_rand(k3, (r,), jnp.float32)) * d
    elig = jax.random.uniform(k4, (B,)) < 0.8
    tau = float(jnp.median(ref.exemplar_marginals(cand, refs, state)))
    budget = max(1, B // 3)
    got = exemplar_accept(cand, refs, state, elig, tau, budget,
                          interpret=True)
    want = ref.exemplar_accept(cand, refs, state, elig, tau, budget)
    if dtype == jnp.bfloat16:
        # bf16 tiles: masks can legitimately flip on near-tau rows; check
        # the invariants (budget/eligibility) and the state/gain bands
        mask = np.asarray(got[0])
        assert mask.sum() <= budget
        assert not np.any(mask & ~np.asarray(elig))
        tol = 5e-2
        np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                                   rtol=tol, atol=tol * max(d, r),
                                   err_msg="exemplar_accept gains")
    else:
        _assert_accept_matches(got, want, max(d, r), dtype,
                               "exemplar_accept")


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 30), st.integers(1, 40), st.integers(1, 12),
       st.integers(0, 2 ** 16), st.integers(0, 6), st.floats(0.0, 2.0))
def test_exemplar_accept_property(B, r, d, seed, budget, tau_scale):
    """Property: budget/eligibility always respected; kernel == scan ref
    over random shapes, budgets and thresholds (incl. budget 0); state
    only shrinks (min-distance updates)."""
    from repro.kernels.exemplar_accept import exemplar_accept

    rng = np.random.default_rng(seed)
    cand = jnp.asarray(rng.standard_normal((B, d)).astype(np.float32))
    refs = jnp.asarray(rng.standard_normal((r, d)).astype(np.float32))
    state = jnp.asarray(rng.random(r).astype(np.float32)) * d
    elig = jnp.asarray(rng.random(B) < 0.7)
    tau = tau_scale * float(
        jnp.max(ref.exemplar_marginals(cand, refs, state))) / 2.0
    got = exemplar_accept(cand, refs, state, elig, tau, budget,
                          interpret=True)
    want = ref.exemplar_accept(cand, refs, state, elig, tau, budget)
    _assert_accept_matches(got, want, max(d, r), jnp.float32,
                           "exemplar_accept")
    mask = np.asarray(got[0])
    assert mask.sum() <= budget
    assert not np.any(mask & ~np.asarray(elig))
    assert np.all(np.asarray(got[1]) <= np.asarray(state) + 1e-6)


def test_exemplar_oracle_kernel_accept_route():
    """ExemplarClustering(use_kernel=True).chunk_accept == the plain path."""
    from repro.core.functions import ExemplarClustering

    rng = np.random.default_rng(29)
    X = jnp.asarray(rng.standard_normal((40, 24)).astype(np.float32))
    refs = jnp.asarray(rng.standard_normal((16, 24)).astype(np.float32))
    plain = ExemplarClustering(feat_dim=24, reference=refs)
    fused = ExemplarClustering(feat_dim=24, reference=refs, use_kernel=True)
    st0 = plain.init_state()
    tau = float(jnp.median(plain.chunk_marginals(st0, X)))
    elig = jnp.asarray(rng.random(40) < 0.8)
    got = fused.chunk_accept(st0, X, elig, tau, 6)
    want = plain.chunk_accept(st0, X, elig, tau, 6)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=1e-5, atol=1e-4)


def test_accept_budget_and_eligibility_respected():
    """No kernel accepts an ineligible row or exceeds the budget, and the
    emitted gains are the accept-time fresh marginals (valid stale upper
    bounds): replaying the mask sequentially reproduces them."""
    from repro.kernels.coverage_accept import coverage_accept

    rng = np.random.default_rng(5)
    B, d = 40, 12
    x = jnp.asarray(rng.random((B, d)).astype(np.float32)) ** 2
    state = jnp.zeros((d,), jnp.float32)
    elig = jnp.asarray(rng.random(B) < 0.5)
    tau = 0.1
    budget = 4
    mask, st_out, gains = coverage_accept(x, state, None, elig, tau,
                                          budget, interpret=True)
    mask = np.asarray(mask)
    assert mask.sum() <= budget
    assert not np.any(mask & ~np.asarray(elig))
    # replay: accepted rows' gains computed against the running state
    st_ = state
    for i in range(B):
        g = float(jnp.sum(jnp.sqrt(st_ + x[i]) - jnp.sqrt(st_)))
        np.testing.assert_allclose(g, float(gains[i]), rtol=1e-5)
        if mask[i]:
            assert g >= tau
            st_ = st_ + x[i]
    np.testing.assert_allclose(np.asarray(st_out), np.asarray(st_),
                               rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 40), st.integers(1, 16), st.integers(0, 2 ** 16),
       st.integers(0, 8), st.floats(0.0, 2.0))
def test_accept_scan_vs_kernel_property(B, d, seed, budget, tau_scale):
    """Property: the coverage accept kernel agrees with the scan reference
    over random shapes, budgets and thresholds (incl. budget 0)."""
    from repro.kernels.coverage_accept import coverage_accept

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.random((B, d)).astype(np.float32)) ** 2
    state = jnp.asarray(rng.random((d,)).astype(np.float32))
    elig = jnp.asarray(rng.random(B) < 0.7)
    tau = tau_scale * float(
        jnp.max(ref.coverage_marginals(x, state, None))) / 2.0
    got = coverage_accept(x, state, None, elig, tau, budget,
                          interpret=True)
    want = ref.coverage_accept(x, state, None, elig, tau, budget)
    _assert_accept_matches(got, want, d, jnp.float32, "coverage_accept")


# ---------------------------------------------------------------------------
# logdet_accept kernel (log-det scale=1 / mutual-information scale=0.5)
# ---------------------------------------------------------------------------

from repro.kernels.logdet_accept import logdet_accept  # noqa: E402


def _logdet_accept_case(seed, B, k, d):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = _rand(k1, (B, d), jnp.float32)
    U = _rand(k2, (k, d), jnp.float32) * 0.3
    if k > 1:
        U = U.at[-1].set(0.0)               # room left in the basis
    elig = jax.random.uniform(k3, (B,)) < 0.8
    return x, U, elig


def _assert_logdet_accept_matches(got, want, name, tol=2e-4):
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]),
                                  err_msg=f"{name}: accept masks differ")
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=tol, atol=tol, err_msg=name)


@pytest.mark.parametrize("B,k,d", [(32, 8, 64), (13, 3, 20), (1, 1, 1),
                                   (64, 16, 300), (129, 33, 40)])
@pytest.mark.parametrize("scale", [1.0, 0.5])
def test_logdet_accept_matches_ref(B, k, d, scale):
    x, U, elig = _logdet_accept_case(B * 7 + k, B, k, d)
    tau = float(jnp.median(ref.logdet_marginals(x, U, alpha=0.8))) * scale
    budget = max(1, min(B, k) // 2)
    got = logdet_accept(x, U, 0.3, 1, elig, tau, budget, alpha=0.8,
                        scale=scale, interpret=True)
    want = ref.logdet_accept(x, U, 0.3, 1, elig, tau, budget, alpha=0.8,
                             scale=scale)
    _assert_logdet_accept_matches(got, want, f"logdet_accept scale={scale}")


@pytest.mark.parametrize("B,k,d", [(32, 8, 64), (13, 3, 20), (64, 16, 48)])
def test_logdet_accept_with_cost_matches_ref(B, k, d):
    """The knapsack variant: per-row costs + a cost budget gate accepts
    alongside tau and the cardinality budget."""
    x, U, elig = _logdet_accept_case(B * 11 + k, B, k, d)
    cost = jnp.abs(_rand(jax.random.PRNGKey(B + d), (B,), jnp.float32)) + 0.1
    tau = float(jnp.median(ref.logdet_marginals(x, U, alpha=0.8)))
    budget = max(1, min(B, k) // 2)
    cost_budget = float(jnp.sum(cost)) / 4.0
    got = logdet_accept(x, U, 0.0, 1, elig, tau, budget, alpha=0.8,
                        cost=cost, cost_budget=cost_budget, interpret=True)
    want = ref.logdet_accept(x, U, 0.0, 1, elig, tau, budget, alpha=0.8,
                             cost=cost, cost_budget=cost_budget)
    _assert_logdet_accept_matches(got, want, "logdet_accept+cost")
    # spent cost of the accepted rows never exceeds the cost budget
    mask = np.asarray(got[0])
    assert float(np.sum(np.asarray(cost)[mask])) <= cost_budget + 1e-5


def test_mutual_information_oracle_kernel_accept_route():
    """MutualInformationGaussian(use_kernel=True).chunk_accept == the plain
    scan path (the kernel shares logdet_accept at compile-time scale=0.5)."""
    from repro.core.functions import MutualInformationGaussian

    rng = np.random.default_rng(31)
    X = jnp.asarray(rng.standard_normal((40, 24)).astype(np.float32))
    plain = MutualInformationGaussian(feat_dim=24, k_max=8, noise=0.7)
    fused = MutualInformationGaussian(feat_dim=24, k_max=8, noise=0.7,
                                      use_kernel=True)
    st0 = plain.init_state()
    tau = float(jnp.median(plain.chunk_marginals(st0, X)))
    elig = jnp.asarray(rng.random(40) < 0.8)
    got = fused.chunk_accept(st0, X, elig, tau, 6)
    want = plain.chunk_accept(st0, X, elig, tau, 6)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-4)
