"""The program's own measurement: the named scopes on the round primitives
(each device op carries its layer in its HLO ``op_name``), the profiler
spans inside ``ServeLoop.run_step``, and the service stats counted from
the retired rows."""

import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MRConfig
from repro.core import functions as F
from repro.core import mapreduce as mr
from repro.core.mapreduce import make_query_batch
from repro.core.selector import SelectorSpec
from repro.launch.mesh import make_mesh_for
from repro.launch.select_serve import Request, SelectionService, ServeLoop

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import trace_reduce  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

SCOPES = ("sample", "tops", "filter", "pack", "gather", "accept")
STEP_CHILDREN = ("serve.admit", "serve.batch", "serve.dispatch",
                 "serve.wait", "serve.retire")
LOC = re.compile(r'loc\("([^"]*)"')


def _corpus(n, d, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.random((n, d)).astype(np.float32)) ** 2


def _lowered(algo):
    n, d, k = 256, 8, 8
    oracle = F.FeatureCoverage(feat_dim=d)
    cfg = MRConfig(k=k, n_total=n, n_machines=1, engine="fused")
    mesh = make_mesh_for(1, model_parallel=1)
    X = jnp.asarray(_corpus(n, d))
    ids = jnp.arange(n, dtype=jnp.int32)
    key = jax.random.PRNGKey(0)
    if algo == "two_round_mesh":
        run, _ = mr.two_round_mesh(oracle, cfg, mesh)
        lowered = jax.jit(run).lower(X, ids, key)
    else:
        run, _ = mr.two_round_batch_mesh(oracle, cfg, mesh)
        lowered = jax.jit(run).lower(X, ids, make_query_batch([k, 3]), key)
    return lowered.as_text(debug_info=True)


@pytest.fixture(scope="module")
def op_names():
    """Every op_name path of both mesh algorithms' lowered programs."""
    return {drv: set(LOC.findall(_lowered(drv)))
            for drv in ("two_round_mesh", "two_round_batch_mesh")}


@pytest.mark.parametrize("scope", SCOPES)
@pytest.mark.parametrize("algo", ["two_round_mesh",
                                    "two_round_batch_mesh"])
def test_round_primitives_name_their_scope_in_the_lowered_program(
        op_names, algo, scope):
    # a scope is a path component before the op's own (primitive) name;
    # a transformation wraps it, as in vmap(vmap(filter))
    comp = re.compile(r"(?:^|/)(?:\w+\()*" + scope + r"\)*/")
    assert any(comp.search(name) for name in op_names[algo]), \
        f"no op of {algo} lies under the {scope!r} scope"


def _serve_loop(n=128, d=8, k=4, slots=4):
    svc = SelectionService(SelectorSpec(k=k), make_mesh_for(1, 1),
                           _corpus(n, d, 3))
    return ServeLoop(svc, slots, jax.random.PRNGKey(0))


def test_serve_step_spans_nest_in_order(tmp_path):
    loop = _serve_loop()
    loop.submit(Request(id=0, k=4))
    loop.run_step()                      # compiles outside the trace
    for i in range(6):
        loop.submit(Request(id=1 + i, k=1 + i % 4))
    jax.profiler.start_trace(str(tmp_path))
    try:
        steps = 0
        while len(loop.queue):
            loop.run_step()
            steps += 1
    finally:
        jax.profiler.stop_trace()
    assert steps == 2
    trace = trace_reduce.load_xplane(str(tmp_path))
    spans = trace_reduce.host_spans(
        trace, ("serve.step", "select.budget_check") + STEP_CHILDREN)
    outer = [s for s in spans if s[0] == "serve.step"]
    assert len(outer) == steps
    for _, s0, e0 in outer:
        inner = [s for s in spans if s[0] != "serve.step"
                 and s0 <= s[1] and s[2] <= e0]
        children = [s for s in inner if s[0] in STEP_CHILDREN]
        assert [c[0] for c in children] == list(STEP_CHILDREN)
        assert all(a[2] <= b[1] for a, b in zip(children, children[1:]))
        _, d0, d1 = children[2]
        checks = [s for s in inner if s[0] == "select.budget_check"]
        assert len(checks) == 1 and d0 <= checks[0][1] <= checks[0][2] <= d1


def test_service_stats_are_the_sum_of_the_retired_rows():
    loop = _serve_loop()
    svc = loop.svc
    for i in range(7):
        loop.submit(Request(id=i, k=1 + i % 4))
    rows = []
    while len(loop.queue):
        rows += loop.run_step()
    assert rows == loop.done and len(rows) == 7
    assert svc.stats["served"] == len(rows)
    assert svc.stats["n_dropped"] == sum(r["dropped"] for r in rows)
    assert svc.stats["tau_fallback_batch"] == sum(r["tau_fallback"]
                                                  for r in rows)
    assert svc.stats["deadline_miss"] == sum(r["deadline_miss"]
                                             for r in rows)


def test_latency_and_step_estimate_cover_the_retire_readbacks():
    loop = _serve_loop()
    loop.submit(Request(id=0, k=4))
    loop.run_step()                      # compiles; kept out of the EWMA
    served = loop.svc.select_batch
    pause = 0.05

    class SlowRead:
        """A result field whose every element read takes ``pause``."""

        def __init__(self, a):
            self.a = a

        def __getitem__(self, i):
            time.sleep(pause)
            return self.a[i]

    def slow_batch(queries, key):
        res = served(queries, key)
        return res._replace(sol_size=SlowRead(res.sol_size))

    loop.svc.select_batch = slow_batch
    for i in range(2):
        loop.submit(Request(id=1 + i, k=2))
    rows = loop.run_step()
    # two slots read back, each read of the size pausing: both requests
    # and the step estimate end after them
    assert all(r["latency_s"] >= 2 * pause for r in rows)
    assert loop.est_step_s >= 2 * pause
