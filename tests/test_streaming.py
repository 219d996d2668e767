"""Streaming subsystem tests: sieve guarantees across the oracle zoo,
replay determinism, distributed sieve-and-merge parity with the MapReduce
drivers, and the out-of-core ingestion / warm-start path."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (MRConfig, SelectionResult, make_oracle, two_round_sim)
from repro.core.selector import SelectorSpec
from repro.core.sequential import greedy
from repro.launch.mesh import make_mesh_for
from repro.streaming import (HostCorpus, SieveSpec, StreamingSelector,
                             sieve_and_merge_mesh, sieve_and_merge_sim,
                             sieve_finish, sieve_run)

jax.config.update("jax_platform_name", "cpu")

ZOO = ["feature_coverage", "weighted_coverage", "saturated_coverage",
       "facility_location", "graph_cut", "log_det", "exemplar"]


def _instance(name, seed=0, n=256, d=8, k=8):
    """(oracle, X) through the registry path (make_oracle)."""
    rng = np.random.default_rng(seed)
    reference = total = None
    if name == "log_det":
        X = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    elif name == "weighted_coverage":
        X = jnp.asarray((rng.random((n, d)) < 0.3).astype(np.float32))
    else:
        X = jnp.asarray((rng.random((n, d)).astype(np.float32)) ** 2)
    if name in ("graph_cut", "saturated_coverage"):
        total = jnp.sum(X, axis=0)
    if name in ("facility_location", "exemplar"):
        reference = jnp.asarray(rng.random((max(4, n // 4), d))
                                .astype(np.float32))
    spec = SelectorSpec(k=k, oracle=name)
    return make_oracle(spec, d, reference=reference, total=total), X


def _streamed(X, n):
    return jnp.arange(n, dtype=jnp.int32), jnp.ones((n,), bool)


# ---------------------------------------------------------------------------
# single-pass sieve: guarantee + determinism
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ZOO)
def test_sieve_guarantee_vs_greedy(name):
    """One pass, never revisiting an element, must keep
    f(S) >= (1/2 - eps) OPT >= (1/2 - eps) greedy (sieve theory: the lane
    covering OPT from above never misses a qualifying element)."""
    n, d, k = 256, 8, 8
    oracle, X = _instance(name, seed=1, n=n, d=d, k=k)
    ids, valid = _streamed(X, n)
    _, _, gval = greedy(oracle, X, valid, k)
    spec = SieveSpec(k=k, eps=0.1)
    res, _ = sieve_run(oracle, spec, X, ids, valid, chunk_elems=64)
    assert int(res.sol_size) > 0
    assert float(res.value) >= (0.5 - spec.eps) * float(gval) - 1e-5, \
        f"{name}: sieve {float(res.value):.4f} < (1/2-eps) greedy " \
        f"{float(gval):.4f}"
    # every reported id is a real element, no duplicates
    sel = np.asarray(res.sol_ids)[: int(res.sol_size)]
    assert len(set(sel.tolist())) == len(sel)
    assert (sel >= 0).all() and (sel < n).all()


@pytest.mark.parametrize("name", ZOO)
def test_sieve_replay_determinism(name):
    """Replaying the same chunk sequence is bit-identical: same lane
    exponents, same solutions, same value bits (no RNG anywhere)."""
    n, d, k = 192, 6, 6
    oracle, X = _instance(name, seed=2, n=n, d=d, k=k)
    ids, valid = _streamed(X, n)
    spec = SieveSpec(k=k, eps=0.12)
    res_a, st_a = sieve_run(oracle, spec, X, ids, valid, chunk_elems=48)
    res_b, st_b = sieve_run(oracle, spec, X, ids, valid, chunk_elems=48)
    np.testing.assert_array_equal(np.asarray(res_a.sol_ids),
                                  np.asarray(res_b.sol_ids))
    assert np.asarray(res_a.value).tobytes() == \
        np.asarray(res_b.value).tobytes()
    np.testing.assert_array_equal(np.asarray(st_a.exps),
                                  np.asarray(st_b.exps))
    np.testing.assert_array_equal(np.asarray(st_a.sol_ids),
                                  np.asarray(st_b.sol_ids))
    for a, b in zip(jax.tree.leaves(st_a.oracle_states),
                    jax.tree.leaves(st_b.oracle_states)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_sieve_reseeds_as_v_grows():
    """An adversarially increasing stream (each element's singleton dwarfs
    everything before it) must slide the lane window and still end with a
    valid solution — the lazy max-singleton tracker at work."""
    n, d, k = 64, 4, 4
    base = np.ones((n, d), np.float32)
    scale = (2.0 ** np.arange(n, dtype=np.float32) / 8.0)[:, None]
    X = jnp.asarray(base * scale)
    from repro.core import FeatureCoverage
    oracle = FeatureCoverage(feat_dim=d)
    ids, valid = _streamed(X, n)
    spec = SieveSpec(k=k, eps=0.1)
    res, st = sieve_run(oracle, spec, X, ids, valid, chunk_elems=8)
    assert int(res.sol_size) == k
    # the window tracked the stream max: the largest element must be in
    # range of the final grid (its exponent window covers v_max)
    assert float(st.v_max) > 0
    _, _, gval = greedy(oracle, X, valid, k)
    assert float(res.value) >= (0.5 - spec.eps) * float(gval) - 1e-5


# ---------------------------------------------------------------------------
# distributed sieve-and-merge
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["feature_coverage", "saturated_coverage",
                                  "graph_cut", "facility_location"])
def test_distributed_sieve_vs_two_round_band(name):
    """Sieve-and-merge (one gather round, one pass per shard) lands in the
    same value band as the paper's two-round driver and keeps the
    (1/2 - eps)-of-greedy floor."""
    n, d, k, m = 512, 8, 8, 8
    oracle, X = _instance(name, seed=3, n=n, d=d, k=k)
    ids, valid = _streamed(X, n)
    fm = X.reshape(m, n // m, d)
    im = ids.reshape(m, n // m)
    vm = valid.reshape(m, n // m)
    _, _, gval = greedy(oracle, X, valid, k)
    spec = SieveSpec(k=k, eps=0.1)
    cfg = MRConfig(k=k, n_total=n, n_machines=m)
    res2, _ = two_round_sim(oracle, fm, im, vm, cfg, jax.random.PRNGKey(0))
    resd, log = sieve_and_merge_sim(oracle, fm, im, vm, spec,
                                    chunk_elems=32)
    assert log.n_rounds == 1
    assert int(resd.n_dropped) == 0       # default pool cap is lossless
    ratio = float(resd.value) / float(res2.value)
    assert ratio >= 0.9, \
        f"{name}: sieve-and-merge/two_round {ratio:.4f} below parity band"
    assert float(resd.value) >= (0.5 - spec.eps) * float(gval) - 1e-5


def test_distributed_sieve_mesh_matches_sim_band():
    """The shard_map driver runs end-to-end on the (1-device) mesh and
    lands within the sim band; its RoundLog matches the sim's accounting
    structure (same record name / per-machine bytes formula)."""
    n, d, k = 256, 8, 8
    oracle, X = _instance("feature_coverage", seed=4, n=n, d=d, k=k)
    ids, valid = _streamed(X, n)
    spec = SieveSpec(k=k, eps=0.1)
    mesh = make_mesh_for(len(jax.devices()), model_parallel=1)
    m = mesh.shape["data"] * mesh.shape.get("pod", 1)
    run, log_mesh = sieve_and_merge_mesh(oracle, spec, mesh,
                                         chunk_elems=64)
    with mesh:
        res_mesh = jax.jit(run)(X, ids)
    fm = X.reshape(m, n // m, d)
    res_sim, log_sim = sieve_and_merge_sim(
        oracle, fm, ids.reshape(m, n // m), valid.reshape(m, n // m),
        spec, chunk_elems=64)
    assert log_mesh.n_rounds == log_sim.n_rounds == 1
    assert log_mesh.records[0].name == log_sim.records[0].name
    assert log_mesh.records[0].bytes_per_machine == \
        log_sim.records[0].bytes_per_machine
    # m=1 mesh sieves the whole corpus in one stream; same band as sim
    assert float(res_mesh.value) > 0
    assert abs(float(res_mesh.value) - float(res_sim.value)) \
        / float(res_sim.value) < 0.15


def test_distributed_sieve_pool_cap_overflow_reported():
    """A too-small survivor cap must be *reported* (n_dropped > 0), never
    silent — the same static-shape message discipline as mapreduce."""
    n, d, k, m = 256, 6, 6, 4
    oracle, X = _instance("feature_coverage", seed=5, n=n, d=d, k=k)
    ids, valid = _streamed(X, n)
    fm = X.reshape(m, n // m, d)
    im = ids.reshape(m, n // m)
    vm = valid.reshape(m, n // m)
    spec = SieveSpec(k=k, eps=0.1)
    res, _ = sieve_and_merge_sim(oracle, fm, im, vm, spec, chunk_elems=32,
                                 pool_cap=k)   # k << lanes*k survivors
    assert int(res.n_dropped) > 0
    assert int(res.sol_size) > 0              # still answers


# ---------------------------------------------------------------------------
# out-of-core ingestion / warm start
# ---------------------------------------------------------------------------

def test_host_corpus_chunking():
    hc = HostCorpus(feat_dim=4, chunk_elems=8)
    hc.append(np.ones((5, 4), np.float32))
    hc.append(2 * np.ones((13, 4), np.float32))
    assert hc.n_total == 18
    full = list(hc.chunks(0, full_only=True))
    assert len(full) == 2 and all(v.all() for _, _, v in full)
    everything = list(hc.chunks(0))
    assert len(everything) == 3
    f, i, v = everything[-1]
    assert f.shape == (8, 4) and int(v.sum()) == 2 and i[-1] == -1
    # row content round-trips across the part boundaries
    np.testing.assert_array_equal(hc._rows(3, 7),
                                  np.concatenate([np.ones((2, 4)),
                                                  2 * np.ones((2, 4))]))


def test_host_corpus_many_small_appends():
    """A long-lived service ingests many SMALL batches: chunk assembly
    must touch only the parts overlapping the requested range (the
    searchsorted offset index), not scan every part ever appended — the
    old linear scan made assembly O(#appends), i.e. quadratic overall."""
    d, P = 4, 600
    rng = np.random.default_rng(0)
    parts = [rng.random((int(rng.integers(1, 5)), d)).astype(np.float32)
             for _ in range(P)]
    hc = HostCorpus(feat_dim=d, chunk_elems=16)
    for p in parts:
        hc.append(p)
    ref = np.concatenate(parts)
    assert hc.n_total == ref.shape[0]
    # correctness: arbitrary ranges reassemble exactly
    for a, b in [(0, 7), (3, 64), (100, 101), (ref.shape[0] - 9,
                                               ref.shape[0])]:
        np.testing.assert_array_equal(hc._rows(a, b), ref[a:b])
    # chunk iteration reassembles the whole corpus in order
    got = np.concatenate([f[v] for f, _, v in hc.chunks(0)])
    np.testing.assert_array_equal(got, ref)
    # the index narrows the work: a 16-row window among 600 parts touches
    # a handful of parts, not all of them (parts are 1-4 rows each)
    i0, i1 = hc._part_range(128, 144)
    assert i1 - i0 <= 17                # not ~600
    assert int(hc._starts[i0]) <= 128
    assert int(hc._starts[i1 - 1]) + parts[i1 - 1].shape[0] >= 144


def test_host_corpus_prune_and_base():
    """prune() releases fully consumed parts (one-pass discipline) while
    keeping global ids stable; a base-offset corpus (the checkpoint
    restore path) serves the same chunks as the original tail."""
    d = 4
    hc = HostCorpus(feat_dim=d, chunk_elems=8)
    blocks = [np.full((6, d), i, np.float32) for i in range(5)]
    for b in blocks:
        hc.append(b)
    ref = np.concatenate(blocks)
    dropped = hc.prune(14)          # parts 0-1 end at 12 <= 14; part 2
    assert dropped == 2             # straddles nothing (12 < 14 < 18): kept
    assert hc.base == 12 and hc.n_total == 30
    np.testing.assert_array_equal(hc._rows(14, 26), ref[14:26])
    with pytest.raises(AssertionError, match="pruned"):
        hc._rows(5, 10)
    # a restored corpus built from only the tail at base=n_streamed
    tail = hc._rows(14, 30)
    rc = HostCorpus(feat_dim=d, chunk_elems=8, base=14)
    rc.append(tail)
    assert rc.n_total == 30
    for (f1, i1_, v1), (f2, i2_, v2) in zip(hc.chunks(14), rc.chunks(14)):
        np.testing.assert_array_equal(f1, f2)
        np.testing.assert_array_equal(i1_, i2_)
        np.testing.assert_array_equal(v1, v2)


def test_streaming_selector_prunes_consumed_parts():
    """The one-pass contract lets the selector drop streamed host parts:
    memory holds O(unstreamed tail), and the selection is unaffected."""
    n, d, k, B = 512, 8, 8, 64
    oracle, X = _instance("feature_coverage", seed=12, n=n, d=d, k=k)
    X_host = np.asarray(X)
    spec = SieveSpec(k=k, eps=0.1)

    pruner = StreamingSelector(oracle, spec, d, chunk_elems=B)
    keeper = StreamingSelector(oracle, spec, d, chunk_elems=B,
                               retain_streamed=True)
    for sel in (pruner, keeper):
        for at in range(0, n, 32):          # many small ingests
            sel.ingest(X_host[at: at + 32])
    held = sum(p.shape[0] for p in pruner.corpus._parts)
    assert held <= B                        # only the unstreamed tail
    assert sum(p.shape[0] for p in keeper.corpus._parts) == n
    r1, r2 = pruner.select(), keeper.select()
    np.testing.assert_array_equal(np.asarray(r1.sol_ids),
                                  np.asarray(r2.sol_ids))
    assert np.asarray(r1.value).tobytes() == np.asarray(r2.value).tobytes()


@pytest.mark.parametrize("name", ["feature_coverage", "graph_cut"])
def test_ingest_incremental_matches_one_shot(name):
    """Chunk-aligned incremental ingest is bit-identical to ingesting the
    whole corpus at once (warm-start correctness: the live state IS the
    state of the full replay)."""
    n, d, k, B = 256, 8, 8, 64
    oracle, X = _instance(name, seed=6, n=n, d=d, k=k)
    X_host = np.asarray(X)
    spec = SieveSpec(k=k, eps=0.1)

    one = StreamingSelector(oracle, spec, d, chunk_elems=B)
    one.ingest(X_host)
    res_one = one.select()

    inc = StreamingSelector(oracle, spec, d, chunk_elems=B)
    inc.ingest(X_host[:B])              # exactly one chunk
    inc.ingest(X_host[B: B + 2 * B])    # two more
    r_mid = inc.select()                # a warm read mid-stream...
    assert int(r_mid.sol_size) > 0
    inc.ingest(X_host[3 * B:])          # ...must not perturb the stream
    res_inc = inc.select()

    np.testing.assert_array_equal(np.asarray(res_one.sol_ids),
                                  np.asarray(res_inc.sol_ids))
    assert np.asarray(res_one.value).tobytes() == \
        np.asarray(res_inc.value).tobytes()


def test_out_of_core_value_band_and_budget():
    """Host corpus 8x the device chunk: the one-pass out-of-core selection
    stays within the two-round value band, and per-request budgets
    (select(budget)) come from the same compiled program."""
    n, d, k, m = 1024, 8, 16, 8
    oracle, X = _instance("feature_coverage", seed=7, n=n, d=d, k=k)
    X_host = np.asarray(X)
    ids, valid = _streamed(X, n)
    spec = SieveSpec(k=k, eps=0.1)
    sel = StreamingSelector(oracle, spec, d, chunk_elems=n // 8)
    sel.ingest(X_host)
    res = sel.select()
    cfg = MRConfig(k=k, n_total=n, n_machines=m)
    res2, _ = two_round_sim(oracle, X.reshape(m, n // m, d),
                            ids.reshape(m, n // m),
                            valid.reshape(m, n // m), cfg,
                            jax.random.PRNGKey(0))
    assert float(res.value) >= 0.9 * float(res2.value)
    # smaller per-request budget: a valid (and no larger) selection
    res_small = sel.select(budget=k // 2)
    assert int(res_small.sol_size) <= k // 2
    assert 0 < float(res_small.value) <= float(res.value) + 1e-6
    # an over-capacity budget must fail loudly, not silently truncate
    with pytest.raises(ValueError, match="budget"):
        sel.select(budget=2 * k)
    # ingesting after a select keeps working (tail flush advanced the
    # stream; new docs continue from there)
    sel.ingest(X_host[:64])
    res3 = sel.select()
    assert isinstance(res3, SelectionResult)
    assert int(res3.sol_size) > 0


def test_select_serve_service_ingest_warm():
    """The serving facade: SelectionService.ingest() admits documents
    between steps and select_warm() answers from the live sieve;
    tau_fallback events aggregate into the service stats."""
    from repro.launch.select_serve import SelectionService
    from repro.core.mapreduce import make_query_batch

    n, d, k = 256, 8, 8
    rng = np.random.default_rng(8)
    emb = (rng.random((n, d)).astype(np.float32)) ** 2
    mesh = make_mesh_for(len(jax.devices()), model_parallel=1)
    spec = SelectorSpec(k=k, oracle="feature_coverage",
                        algorithm="two_round")
    svc = SelectionService(spec, mesh, emb, stream_chunk=64)

    qb = make_query_batch([k, k // 2])
    res = svc.select_batch(qb, key=jax.random.PRNGKey(0))
    svc.account([{"dropped": int(res.n_dropped[q]),
                  "tau_fallback": int(res.tau_fallback[q])}
                 for q in range(2)])
    assert svc.stats["served"] == 2

    info = svc.ingest((rng.random((64, d)).astype(np.float32)) ** 2)
    assert info["n_total"] == n + 64
    warm = svc.select_warm()
    assert int(warm.sol_size) > 0 and float(warm.value) > 0
    assert svc.stats["warm_selects"] == 1
    assert "tau_fallback" in svc.summary()
    # the batch round log carries the runtime event counters (satellite:
    # degenerate-sample events visible in serving, not only the result)
    assert "tau_fallback" in svc.selector.round_log_batch.summary()
    # ...and they ACCUMULATE across steps at the same slot width instead
    # of resetting each select_batch call
    log1 = svc.selector.round_log_batch
    svc.select_batch(qb, key=jax.random.PRNGKey(2))
    assert svc.selector.round_log_batch is log1


def test_selector_round_log_notes_runtime_events():
    """DistributedSelector.select threads tau_fallback/n_dropped into its
    RoundLog as runtime events."""
    from repro.core.selector import DistributedSelector

    n, d, k = 128, 6, 4
    rng = np.random.default_rng(9)
    X = jnp.asarray((rng.random((n, d)).astype(np.float32)) ** 2)
    mesh = make_mesh_for(len(jax.devices()), model_parallel=1)
    sel = DistributedSelector(SelectorSpec(k=k), mesh, n_total=n, feat_dim=d)
    sel.select(X, key=jax.random.PRNGKey(0))
    sel.select(X, key=jax.random.PRNGKey(1))
    s = sel.round_log.summary()
    assert "events:" in s and "tau_fallback=0" in s and "n_dropped=0" in s


# ---------------------------------------------------------------------------
# fused engine through the sieve's per-lane update
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["feature_coverage", "facility_location",
                                  "graph_cut"])
def test_sieve_fused_engine_matches_dense(name):
    """SieveSpec(engine="fused") — the per-lane Algorithm-1 accept over
    each chunk runs through oracle.chunk_accept (vmapped over lanes) and
    must reproduce the dense sieve bit-for-bit, plain and kernel paths."""
    import dataclasses

    n, d, k = 256, 8, 8
    oracle, X = _instance(name, seed=6, n=n, d=d, k=k)
    ids, valid = _streamed(X, n)
    out = {}
    for engine in ("dense", "fused"):
        spec = SieveSpec(k=k, engine=engine, chunk=32)
        res, state = sieve_run(oracle, spec, X, ids, valid, chunk_elems=64)
        out[engine] = (res, state)
    np.testing.assert_array_equal(np.asarray(out["dense"][0].sol_ids),
                                  np.asarray(out["fused"][0].sol_ids))
    np.testing.assert_array_equal(np.asarray(out["dense"][1].sol_ids),
                                  np.asarray(out["fused"][1].sol_ids))
    np.testing.assert_allclose(float(out["dense"][0].value),
                               float(out["fused"][0].value), rtol=1e-6)

    try:
        krn = dataclasses.replace(oracle, use_kernel=True)
    except TypeError:
        return
    spec = SieveSpec(k=k, engine="fused", chunk=32)
    res_k, _ = sieve_run(krn, spec, X, ids, valid, chunk_elems=64)
    np.testing.assert_array_equal(np.asarray(out["dense"][0].sol_ids),
                                  np.asarray(res_k.sol_ids))
