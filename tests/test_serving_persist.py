"""Serving-hardening tests: deadline-aware admission (EDF + shed
reporting), sieve/selection-state checkpoint/restore bit-identity, the
service's init-corpus release, and the per-path stats split."""

import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import Checkpointer
from repro.core.mapreduce import make_query_batch
from repro.core.selector import SelectorSpec, make_oracle
from repro.launch.mesh import make_mesh_for
from repro.launch.select_serve import (AdmissionQueue, Request,
                                       SelectionService, ServeLoop,
                                       synth_docs, synth_requests)
from repro.streaming import (SieveSpec, StreamingSelector, restore_selector,
                             selector_template, snapshot_selector)

jax.config.update("jax_platform_name", "cpu")


def _corpus(n, d, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.random((n, d)).astype(np.float32)) ** 2


def _mesh():
    return make_mesh_for(len(jax.devices()), model_parallel=1)


# ---------------------------------------------------------------------------
# deadline-aware admission
# ---------------------------------------------------------------------------

def test_admission_earliest_deadline_first():
    q = AdmissionQueue()
    q.submit(Request(id=0, k=4, deadline_ms=None), now=0.0)   # best-effort
    q.submit(Request(id=1, k=4, deadline_ms=900.0), now=0.0)
    q.submit(Request(id=2, k=4, deadline_ms=200.0), now=0.0)
    q.submit(Request(id=3, k=4, deadline_ms=500.0), now=0.0)
    admitted, shed = q.admit(3, now=0.0, est_step_s=None)
    assert [r.id for r in admitted] == [2, 3, 1] and not shed
    # the best-effort request waits behind every deadlined one
    admitted, shed = q.admit(3, now=0.0, est_step_s=None)
    assert [r.id for r in admitted] == [0] and not shed


def test_admission_sheds_unmeetable_deadlines():
    q = AdmissionQueue()
    q.submit(Request(id=0, k=4, deadline_ms=50.0), now=0.0)    # unmeetable
    q.submit(Request(id=1, k=4, deadline_ms=5000.0), now=0.0)  # fine
    q.submit(Request(id=2, k=4), now=0.0)                      # best-effort
    admitted, shed = q.admit(4, now=1.0, est_step_s=0.5)
    assert [r.id for r in shed] == [0]          # 1.0 + 0.5 > 0.05
    assert [r.id for r in admitted] == [1, 2]   # shed frees the slot
    # without an estimate, only already-expired deadlines shed
    q.submit(Request(id=3, k=4, deadline_ms=0.0), now=0.0)
    q.submit(Request(id=4, k=4, deadline_ms=1e7), now=0.0)
    admitted, shed = q.admit(4, now=1.0, est_step_s=None)
    assert [r.id for r in shed] == [3] and [r.id for r in admitted] == [4]


def test_serve_loop_deadline_shed_regression():
    """End-to-end: expired-deadline requests are shed AND reported (row +
    service counter), served+shed accounts for every submission, and
    served requests record latencies."""
    n, d, k, Q = 256, 8, 8, 4
    svc = SelectionService(SelectorSpec(k=k), _mesh(), _corpus(n, d, 1))
    loop = ServeLoop(svc, Q, jax.random.PRNGKey(0))
    for rid in range(Q):
        loop.submit(Request(id=rid, k=k))
    loop.submit(Request(id=99, k=k, deadline_ms=0.0))   # expired on arrival
    with svc.mesh:
        while len(loop.queue):
            loop.run_step()
    assert len(loop.done) == Q and len(loop.shed) == 1
    assert loop.shed[0]["id"] == 99 and "deadline" in loop.shed[0]["reason"]
    assert svc.stats["shed"] == 1 and svc.stats["served"] == Q
    assert all(r["latency_s"] > 0 for r in loop.done)
    assert all(r["size"] <= r["k"] for r in loop.done)


def test_synth_requests_carry_deadlines():
    reqs = synth_requests(8, 16, "graph_cut", seed=0, deadline_ms=400.0)
    assert all(200.0 <= r.deadline_ms <= 600.0 for r in reqs)
    assert all(r.lam is not None for r in reqs)
    assert all(r.deadline_ms is None
               for r in synth_requests(4, 16, "graph_cut", seed=0))


# ---------------------------------------------------------------------------
# ingest freshness (regression: the same block re-ingested every step)
# ---------------------------------------------------------------------------

def test_synth_docs_fresh_per_step():
    """The ingest key folds by step: every cadence step streams NEW rows,
    and successive service ingests append distinct ids."""
    key = jax.random.PRNGKey(3)
    d0, d1 = synth_docs(key, 1, 32, 8), synth_docs(key, 2, 32, 8)
    assert not np.array_equal(d0, d1)
    # same step -> same docs (the stream is a pure function of the key)
    np.testing.assert_array_equal(d0, synth_docs(key, 1, 32, 8))

    svc = SelectionService(SelectorSpec(k=4), _mesh(), _corpus(128, 8, 2),
                           stream_chunk=32)
    i1 = svc.ingest(d0)
    i2 = svc.ingest(d1)
    assert i1["first_id"] == 128 and i2["first_id"] == 160  # distinct ids
    assert i2["n_total"] == 128 + 64


# ---------------------------------------------------------------------------
# checkpoint/restore bit-identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["feature_coverage", "graph_cut"])
def test_selector_snapshot_restore_bit_identity(name):
    """ingest A -> snapshot -> ingest B -> select vs restore -> ingest B
    -> select: bit-identical ids and value, through a disk round-trip."""
    n, d, k, B = 256, 8, 8, 64
    X = _corpus(n, d, 4)
    a, b = X[:144], X[144:]
    total = jnp.sum(jnp.asarray(X[:96]), axis=0)  # pinned a-priori stat
    oracle = make_oracle(SelectorSpec(k=k, oracle=name), d, total=total)
    spec = SieveSpec(k=k, eps=0.1)

    one = StreamingSelector(oracle, spec, d, chunk_elems=B)
    one.ingest(a)                       # 144 rows: 2 full chunks + tail 16
    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp)
        ck.save(1, snapshot_selector(one))
        one.ingest(b)
        res_one = one.select()

        two = StreamingSelector(oracle, spec, d, chunk_elems=B)
        snap, step = ck.restore(selector_template(two))
        assert step == 1
        restore_selector(two, snap)
        assert two.n_streamed == 128 and two.n_total == 144
        two.ingest(b)
        res_two = two.select()

    np.testing.assert_array_equal(np.asarray(res_one.sol_ids),
                                  np.asarray(res_two.sol_ids))
    assert np.asarray(res_one.value).tobytes() == \
        np.asarray(res_two.value).tobytes()
    # the live states themselves are bit-identical, not just this answer
    for x, y in zip(jax.tree.leaves(one.state), jax.tree.leaves(two.state)):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


def test_restore_selector_rejects_mismatches():
    d, k, B = 8, 8, 64
    oracle = make_oracle(SelectorSpec(k=k), d)
    sel = StreamingSelector(oracle, SieveSpec(k=k), d, chunk_elems=B)
    sel.ingest(_corpus(100, d, 5))
    snap = snapshot_selector(sel)
    # wrong chunk size: chunk boundaries are part of the replay
    other = StreamingSelector(oracle, SieveSpec(k=k), d, chunk_elems=32)
    with pytest.raises(ValueError, match="chunk_elems"):
        restore_selector(other, snap)
    # wrong spec (different k -> different lane/buffer shapes)
    small = StreamingSelector(make_oracle(SelectorSpec(k=4), d),
                              SieveSpec(k=4), d, chunk_elems=B)
    with pytest.raises(ValueError, match="mismatch"):
        restore_selector(small, snap)


def test_service_checkpoint_restore_bit_identity():
    """The service-level kill/restore: warm answers and stats continue
    from the checkpoint as if never interrupted."""
    n, d, k = 256, 8, 8
    emb = _corpus(n, d, 6)
    docs_a, docs_b = _corpus(96, d, 7), _corpus(80, d, 8)
    spec = SelectorSpec(k=k, oracle="feature_coverage")
    mesh = _mesh()

    svc = SelectionService(spec, mesh, emb, stream_chunk=64)
    svc.ingest(docs_a)
    with tempfile.TemporaryDirectory() as tmp:
        svc.save(Checkpointer(tmp), step=3)
        svc.ingest(docs_b)
        res_full = svc.select_warm()

        svc2 = SelectionService(spec, mesh, emb, stream_chunk=64)
        step = svc2.restore(Checkpointer(tmp))
        assert step == 3
        # restored, not re-ingested: the stream cursor picked up mid-way
        assert svc2.stream.n_total == n + 96
        assert svc2.stats["ingested"] == n + 96
        svc2.ingest(docs_b)
        res_rest = svc2.select_warm()

    np.testing.assert_array_equal(np.asarray(res_full.sol_ids),
                                  np.asarray(res_rest.sol_ids))
    assert np.asarray(res_full.value).tobytes() == \
        np.asarray(res_rest.value).tobytes()


def test_service_save_is_read_only():
    """Checkpointing mid-stream must not perturb the stream: a service
    that saves between ingests answers identically to one that never
    saved."""
    n, d, k = 192, 8, 8
    emb = _corpus(n, d, 9)
    docs = _corpus(70, d, 10)
    spec = SelectorSpec(k=k)
    mesh = _mesh()

    plain = SelectionService(spec, mesh, emb, stream_chunk=64)
    plain.ingest(docs)
    res_plain = plain.select_warm()

    saver = SelectionService(spec, mesh, emb, stream_chunk=64)
    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp)
        saver.save(ck, step=1)
        saver.ingest(docs)
        saver.save(ck, step=2)
        res_saver = saver.select_warm()
    np.testing.assert_array_equal(np.asarray(res_plain.sol_ids),
                                  np.asarray(res_saver.sol_ids))


# ---------------------------------------------------------------------------
# service memory + stats hygiene
# ---------------------------------------------------------------------------

def test_service_releases_init_corpus_after_both_paths():
    n, d, k = 128, 8, 4
    svc = SelectionService(SelectorSpec(k=k), _mesh(), _corpus(n, d, 11),
                           stream_chunk=64)
    assert svc._init_corpus is not None
    svc.materialize()                    # batch path consumed it...
    assert svc._init_corpus is not None  # ...but the sieve still needs it
    svc.ingest(_corpus(64, d, 12))       # online path consumed it too
    assert svc._init_corpus is None      # host pin released
    # both paths still serve after the release
    qb = make_query_batch([k])
    res = svc.select_batch(qb, key=jax.random.PRNGKey(0))
    assert int(res.sol_size[0]) > 0
    assert int(svc.select_warm().sol_size) > 0


def test_service_restore_releases_init_corpus():
    n, d, k = 128, 8, 4
    spec = SelectorSpec(k=k)
    mesh = _mesh()
    emb = _corpus(n, d, 13)
    svc = SelectionService(spec, mesh, emb, stream_chunk=64)
    with tempfile.TemporaryDirectory() as tmp:
        svc.save(Checkpointer(tmp), step=1)
        svc2 = SelectionService(spec, mesh, emb, stream_chunk=64)
        svc2.materialize()
        svc2.restore(Checkpointer(tmp))
    assert svc2._init_corpus is None     # checkpoint replaced the stream


def test_service_stats_split_batch_vs_warm():
    """tau_fallback is split by serve path, so summary() no longer
    conflates a degenerate batched sample with a degenerate sieve pool."""
    n, d, k = 128, 8, 4
    svc = SelectionService(SelectorSpec(k=k), _mesh(), _corpus(n, d, 14),
                           stream_chunk=64)
    res = svc.select_batch(make_query_batch([k, k // 2]),
                           key=jax.random.PRNGKey(0))
    svc.account([{"dropped": int(res.n_dropped[q]),
                  "tau_fallback": int(res.tau_fallback[q])}
                 for q in range(2)])
    svc.select_warm()
    assert set(svc.stats) >= {"tau_fallback_batch", "tau_fallback_warm",
                              "shed", "deadline_miss"}
    s = svc.summary()
    assert "tau_fallback_batch=" in s and "tau_fallback_warm=" in s
    assert "shed=" in s
    # the selector-side aggregate view realizes the same counters
    ev = svc.selector.runtime_events()
    assert ev.get("tau_fallback", 0) == svc.stats["tau_fallback_batch"]


# ---------------------------------------------------------------------------
# retrying serving paths + corrupted-checkpoint rejection (DESIGN.md §9)
# ---------------------------------------------------------------------------

def test_checkpointer_retries_transient_write_failures(monkeypatch):
    """A save that fails transiently is retried with backoff (counted in
    n_retries) and succeeds; the checkpoint on disk restores cleanly."""
    state = {"a": np.arange(6, dtype=np.float32)}
    fails = {"left": 2}
    real_savez = np.savez

    def flaky_savez(path, **kw):
        if fails["left"]:
            fails["left"] -= 1
            raise OSError("disk hiccup")
        real_savez(path, **kw)

    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp, retry_attempts=3, retry_backoff_s=0.0)
        monkeypatch.setattr(np, "savez", flaky_savez)
        ck.save(1, state)
        monkeypatch.setattr(np, "savez", real_savez)
        assert ck.n_retries == 2
        got, step = ck.restore({"a": np.zeros(6, np.float32)})
        assert step == 1
        np.testing.assert_array_equal(np.asarray(got["a"]), state["a"])


def test_checkpointer_async_exhaustion_raises_from_wait(monkeypatch):
    """Retries exhausted on the async path: the worker stashes the error
    and wait() re-raises it with the attempt count — never silent."""
    def always_fail(path, **kw):
        raise OSError("disk gone")

    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp, retry_attempts=3, retry_backoff_s=0.0)
        monkeypatch.setattr(np, "savez", always_fail)
        ck.async_save(1, {"a": np.zeros(3, np.float32)})
        with pytest.raises(RuntimeError, match="3 attempts"):
            ck.wait()
        assert ck.n_retries == 2          # 2 retried + 1 final failure
        # the failed save left no half-written checkpoint behind
        assert ck.latest_step() is None


def test_bit_flipped_checkpoint_raises_corrupt_error():
    """A single flipped byte in arrays.npz must surface as
    CheckpointCorruptError on restore, not a raw zip/unpickling traceback
    or silently damaged state."""
    import glob
    import os

    from repro.streaming import CheckpointCorruptError

    state = {"a": np.arange(512, dtype=np.float32)}
    tmpl = {"a": np.zeros(512, np.float32)}
    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp)
        ck.save(1, state)
        [npz] = glob.glob(os.path.join(tmp, "step_1", "arrays.npz"))
        blob = bytearray(open(npz, "rb").read())
        blob[len(blob) // 2] ^= 0xFF      # flip one payload byte
        open(npz, "wb").write(bytes(blob))
        with pytest.raises(CheckpointCorruptError):
            Checkpointer(tmp).restore(tmpl)


def test_truncated_checkpoint_raises_corrupt_error():
    import os

    from repro.checkpoint.checkpointer import CheckpointCorruptError

    state = {"a": np.arange(512, dtype=np.float32),
             "b": np.ones(64, np.float32)}
    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp)
        ck.save(1, state)
        npz = os.path.join(tmp, "step_1", "arrays.npz")
        blob = open(npz, "rb").read()
        open(npz, "wb").write(blob[: len(blob) // 2])   # truncate
        with pytest.raises(CheckpointCorruptError):
            Checkpointer(tmp).restore(
                {"a": np.zeros(512, np.float32),
                 "b": np.zeros(64, np.float32)})


def test_service_ingest_retry_is_idempotent(monkeypatch):
    """The ingest path retries absorb() (cursor-driven, idempotent) but
    appends exactly once: after two injected _update failures the final
    state is bit-identical to a never-failed run, and the retries are
    counted in the service stats."""
    n, d, k = 128, 8, 4
    emb = _corpus(n, d, 20)
    docs = _corpus(96, d, 21)
    spec = SelectorSpec(k=k)
    mesh = _mesh()

    plain = SelectionService(spec, mesh, emb, stream_chunk=32)
    plain.ingest(docs)
    res_plain = plain.select_warm()

    flaky = SelectionService(spec, mesh, emb, stream_chunk=32,
                             retry_backoff_s=0.0)
    flaky._ensure_stream()
    real_update = flaky.stream._update
    fails = {"left": 2}

    def flaky_update(st, f, i, v):
        if fails["left"]:
            fails["left"] -= 1
            raise RuntimeError("transient device error")
        return real_update(st, f, i, v)

    monkeypatch.setattr(flaky.stream, "_update", flaky_update)
    info = flaky.ingest(docs)
    assert flaky.stats["ingest_retries"] == 2
    assert flaky.stats["ingest_failures"] == 0
    assert info["n_total"] == n + 96
    res_flaky = flaky.select_warm()

    np.testing.assert_array_equal(np.asarray(res_plain.sol_ids),
                                  np.asarray(res_flaky.sol_ids))
    assert np.asarray(res_plain.value).tobytes() == \
        np.asarray(res_flaky.value).tobytes()
    # no row was double-streamed: the cursors agree
    assert flaky.stream.n_streamed == plain.stream.n_streamed


def test_service_ingest_retry_exhaustion_reports(monkeypatch):
    n, d, k = 128, 8, 4
    svc = SelectionService(SelectorSpec(k=k), _mesh(), _corpus(n, d, 22),
                           stream_chunk=32, retry_attempts=2,
                           retry_backoff_s=0.0)
    svc._ensure_stream()

    def always_fail(st, f, i, v):
        raise RuntimeError("device gone")

    monkeypatch.setattr(svc.stream, "_update", always_fail)
    with pytest.raises(RuntimeError, match="device gone"):
        svc.ingest(_corpus(64, d, 23))
    assert svc.stats["ingest_retries"] == 1
    assert svc.stats["ingest_failures"] == 1
    assert "ingest=1(+1 failed)" in svc.summary()


def test_service_ingest_does_not_retry_jax_errors(monkeypatch):
    """A JAX/XLA error (a refused compile, an exhausted device) fails the
    same way on every attempt: it is counted as a failure at once, with no
    retry and no backoff."""
    n, d, k = 128, 8, 4
    svc = SelectionService(SelectorSpec(k=k), _mesh(), _corpus(n, d, 24),
                           stream_chunk=32, retry_attempts=3,
                           retry_backoff_s=0.0)
    svc._ensure_stream()

    def refused(st, f, i, v):
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of HBM")

    monkeypatch.setattr(svc.stream, "_update", refused)
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE"):
        svc.ingest(_corpus(64, d, 25))
    assert svc.stats["ingest_retries"] == 0
    assert svc.stats["ingest_failures"] == 1


def test_select_serve_exits_nonzero_when_ingest_fails_for_good(monkeypatch,
                                                              capsys):
    """The serve loop keeps serving past an ingest whose retries ran out,
    then exits non-zero: a run that lost documents never reports success."""
    from repro.launch import select_serve

    def gone(self, docs):
        raise RuntimeError("host corpus unreachable")

    monkeypatch.setattr(select_serve.SelectionService, "ingest", gone)
    # keep this process's compile cache setting as the session has it
    monkeypatch.setattr(select_serve, "enable_compile_cache", lambda: None)
    monkeypatch.setattr("sys.argv", [
        "select_serve", "--n", "128", "--k", "4", "--d", "8", "--slots", "2",
        "--requests", "4", "--ingest-docs", "16", "--ingest-every", "1"])
    with pytest.raises(SystemExit) as exit_info:
        select_serve.main()
    assert exit_info.value.code == 1
    out = capsys.readouterr()
    assert "INGEST FAILED" in out.out
    assert "served=4" in out.out
    assert "FAILED for good: ingest @ step 1" in out.err
