"""bench/trace_scopes.py and the readers of this layer's metrics on a small
trace.

``trace_scoped_small.json`` is cut from one serving step of a v5e trace of
``coverage_serve``: the step's program spans with the benchmark's
``run_step`` around them, and 17 of the step's device ops with the op_name
each carried, times shifted so that the step starts at 1,000 ns.  Every
expected number is worked out below from the events' durations.
"""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench import trace_scopes as ts  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
#: the serving step's span
STEP = (1000.0, 1000.0 + 315134734.0)


@pytest.fixture(scope="module")
def scoped():
    with open(os.path.join(HERE, "trace_scoped_small.json")) as f:
        return json.load(f)


@pytest.fixture
def plain(scoped):
    """The harness's plain structure of the same trace: device events
    without their op_name."""
    return {"planes": [
        {"name": p["name"], "lines": [
            {"name": line["name"], "events": [e[:3] for e in line["events"]]}
            for line in p["lines"]]}
        for p in scoped["planes"]]}


def dur(scoped, name):
    return next(e[2] for p in scoped["planes"] for line in p["lines"]
                for e in line["events"] if e[0] == name)


def ctx_for(plain, selections=1):
    logged = []
    return types.SimpleNamespace(
        trace=plain, window_ns=STEP, selections=selections,
        log=logged.append, logged=logged, cell={"name": "coverage_serve"})


def read(metric, ctx):
    return harness.load_module(os.path.join(
        ROOT, "bench", "metrics", f"{metric}.py")).read(ctx)


@pytest.mark.parametrize("op_name, scope", [
    ("jit(run)/sample/jit(pack_by_mask)/pack/top_k", "pack"),
    ("jit(run)/vmap(vmap(filter))/filter/while/body/squeeze", "filter"),
    ("jit(run)/vmap(vmap(filter))/filter/jit(pack_by_mask)/pack/sort",
     "pack"),
    ("jit(run)/vmap(vmap(accept))/accept/while", "accept"),
    ("jit(run)/tops/jit(coverage_marginals)/pallas_call", "tops"),
    # the last component is the op itself, never a scope
    ("jit(run)/vmap(accept)/accept/while/body/gather", "accept"),
    ("jit(run)/gather/all_gather", "gather"),
    ("jit(run)/jit(coverage_marginals)/pallas_call", None),
    ("jit(dynamic_slice)/dynamic_slice", None),
    ("", None),
])
def test_scope_is_the_innermost_named_scope(op_name, scope):
    assert ts.scope_of(op_name) == scope


def test_time_by_scope_partitions_the_busy_time(scoped, plain):
    by_scope, by_op = ts.scope_time_ns(ts.tr.device_planes(scoped)[0], STEP)
    d = lambda name: dur(scoped, name)  # noqa: E731
    # pack: the sample's reduce, top_k sort and row gather, and the tops'
    # sort; tops: its marginals kernel
    assert by_scope["pack"] == (d("convert_reduce_fusion.1") + d("sort")
                                + d("fusion.9") + d("sort.2"))
    assert by_scope["tops"] == d("coverage_marginals.7")
    # the two loops carry no op_name; their body ops do, so each loop
    # keeps only its self time, under no scope
    assert by_scope["filter"] == (d("dynamic-slice_bitcast_fusion.2")
                                  + d("copy.346"))
    assert by_scope["accept"] == d("and_reduce_fusion.17") + d(
        "not_and_fusion.13")
    loops_self = (d("while.287") - d("dynamic-slice_bitcast_fusion.2")
                  - d("copy.346") + d("while.313")
                  - d("and_reduce_fusion.17") - d("not_and_fusion.13"))
    assert by_scope[None] == (d("copy.1") + d("reduce_max.7") + d("iota.1")
                              + d("coverage_marginals.6") + loops_self
                              + 2 * d("dynamic_slice.1"))
    assert sum(by_scope.values()) == ts.tr.busy_ns(
        ts.tr.device_planes(plain)[0], STEP) == 42769450.0
    assert by_op[("while.287", None)] == 30104856.0
    assert by_op[("dynamic_slice.1", None)] == 2 * 317.0


def test_scope_readers(scoped, plain, monkeypatch):
    monkeypatch.setattr(ts, "load_scoped", lambda trace_dir: scoped)
    ctx = ctx_for(plain, selections=2)
    assert read("scope.pack_ms", ctx) == pytest.approx(171168.0 / 1e6 / 2)
    assert read("scope.filter_ms", ctx) == pytest.approx(6739.0 / 1e6 / 2)
    assert read("scope.accept_ms", ctx) == pytest.approx(1610.0 / 1e6 / 2)
    # the share under no scope and the top ops, each with its scope
    assert "99.373% of 0.042769 s busy device time is under no scope" in \
        ctx.logged[0]
    assert ctx.logged[1].startswith(
        "scopes: top ops (s, scope): while.287 0.030105 none, "
        "while.313 0.012306 none, fusion.9 0.000158 pack")


def test_scope_readers_find_nothing_in_a_program_without_scopes(
        scoped, plain, monkeypatch):
    bare = {"planes": [{"name": p["name"], "lines": [
        {"name": line["name"], "events": [e[:3] + [""]
                                          for e in line["events"]]}
        for line in p["lines"]]} for p in scoped["planes"]]}
    monkeypatch.setattr(ts, "load_scoped", lambda trace_dir: bare)
    ctx = ctx_for(plain)
    for m in ("scope.filter_ms", "scope.pack_ms", "scope.accept_ms"):
        assert read(m, ctx) is None


def test_step_idle_split_by_innermost_span(plain):
    steps, split = ts.step_idle_split(plain, STEP)
    assert steps == 1
    assert split == {
        "serve.admit": 15970.0,                     # no device op
        "serve.batch": 1607729.0 - 540.0,           # copy.1
        # dispatch outside its budget check: reduce_max.7 and the first
        # ops of the step's program (pack, sample and tops kernels)
        "serve.dispatch": (3986018.0 - 783619.0) - (446.0 + 905.0 + 6313.0
                                                    + 157638.0 + 88723.0
                                                    + 88725.0 + 6312.0),
        "select.budget_check": 783619.0 - 257.0,    # iota.1
        "serve.wait": 268214467.0 - 30111595.0 - 12307362.0,  # two loops
        "serve.retire": 41282229.0 - 2 * 317.0,     # two dynamic slices
        # the step's own time between its children
        "serve.step": 315134734.0 - (15970.0 + 1607729.0 + 3986018.0
                                     + 268214467.0 + 41282229.0),
    }
    assert sum(split.values()) == 315134734.0 - 42769450.0


def test_serving_step_readers(plain):
    ctx = ctx_for(plain)
    assert read("serve.step_idle_ms", ctx) == pytest.approx(
        (315134734.0 - 42769450.0) / 1e6)
    assert ctx.logged[0].startswith(
        "serve.step_idle_ms: 272.365284 ms of device idle per step over 1 "
        "steps; by span (ms per step): serve.wait 225.795510, "
        "serve.retire 41.281595, serve.dispatch 2.853337")
    assert read("serve.retire_ms", ctx) == pytest.approx(41.282229)
    assert read("serve.dispatch_ms", ctx) == pytest.approx(3.986018)


def test_serving_step_readers_find_nothing_without_the_spans(plain):
    host = plain["planes"][0]["lines"][0]
    host["events"] = [e for e in host["events"] if e[0] == "run_step"]
    ctx = ctx_for(plain)
    for m in ("serve.step_idle_ms", "serve.retire_ms", "serve.dispatch_ms"):
        assert read(m, ctx) is None


@pytest.mark.parametrize("metric", ["compiles.batch", "compiles.serve"])
def test_programs_lowered_in_the_window_with_their_span(plain, metric):
    host = plain["planes"][0]["lines"][0]["events"]
    # a program lowered and compiled inside the budget check, one lowered
    # inside the retire and loaded from the compile cache (no compile
    # event), and one lowered before the window, which is not counted
    host += [["lower_sharding_computation", 3047959.0 + 100.0, 800.0],
             ["backend_compile_and_load", 3047959.0 + 1000.0, 5000.0],
             ["lower_sharding_computation", 273850545.0 + 100.0, 800.0],
             ["lower_sharding_computation", 10.0, 800.0]]
    ctx = ctx_for(plain)
    assert read(metric, ctx) == 2
    assert [m.split(": ", 1)[1].split(" ")[0] for m in ctx.logged] == [
        "lower_sharding_computation", "backend_compile_and_load",
        "lower_sharding_computation"]
    assert [m.rsplit(" in ", 1)[1] for m in ctx.logged] == [
        "select.budget_check", "select.budget_check", "serve.retire"]
    host[:] = [e for e in host if e[0] not in (
        "lower_sharding_computation", "backend_compile_and_load")]
    assert read(metric, ctx) == 0


def _msg(*fields):
    """A protobuf message of (field number, int or bytes or str) fields."""
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += _varint(num << 3) + _varint(value)
        else:
            data = value.encode() if isinstance(value, str) else value
            out += _varint(num << 3 | 2) + _varint(len(data)) + data
    return out


def _varint(n):
    out = b""
    while True:
        out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def test_op_names_from_the_event_metadata(tmp_path):
    # XSpace.planes[]: name, event_metadata {id: XEventMetadata(name,
    # stats)}, stat_metadata {id: XStatMetadata(name)}; a program id is a
    # uint64 stat (field 3), the op_name a string stat (field 5)
    stat_md = [_msg((1, i), (2, _msg((1, i), (2, name))))
               for i, name in ((1, "program_id"), (2, "tf_op"),
                               (3, "flops"))]

    def event_md(i, text, program, op):
        stats = [_msg((1, 1), (3, program)), _msg((1, 3), (4, 7))]
        if op is not None:
            stats.append(_msg((1, 2), (5, op)))
        return _msg((1, i), (2, _msg((1, i), (2, text),
                                     *[(5, s) for s in stats])))

    device = _msg(
        (1, 3), (2, "/device:TPU:0"),
        (4, event_md(1, "%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p)",
                     2 ** 63 + 5, "jit(run)/vmap(filter)/filter/"
                                  "jit(pack_by_mask)/pack/sort:")),
        (4, event_md(2, "%while.3 = (s32[]) while((s32[]) %t)",
                     2 ** 63 + 5, None)),
        *[(5, s) for s in stat_md])
    host = _msg((1, 4), (2, "/host:CPU"),
                (4, event_md(1, "%fusion.1 = f32[]", 9, "jit(f)/add:")),
                *[(5, s) for s in stat_md])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, device), (1, host)))
    assert ts.op_names_by_program(str(path)) == {"/device:TPU:0": {
        (2 ** 63 + 5, "fusion.12"):
            "jit(run)/vmap(filter)/filter/jit(pack_by_mask)/pack/sort"}}


def test_a_program_new_in_the_window_counts_once(tmp_path):
    """JAX's own events on a real (CPU) trace: a fresh jit is lowered and
    compiled inside the window; a second call of it is neither."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
    x = jnp.ones(7)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("window"):
            f(x).block_until_ready()
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    trace = ts.tr.load_xplane(str(tmp_path))
    (win,) = ts.tr.host_spans(trace, ["window"])
    logged = []
    ctx = types.SimpleNamespace(trace=trace, window_ns=win[1:],
                                log=logged.append)
    assert ts.compile_count(ctx) == 1
    assert any("backend_compile_and_load" in m and m.endswith("in window")
               for m in logged)
