"""The readers of the survivor gather layer, ``scope.gather_ms`` and
``gather.roofline``, on a small scoped trace of two devices.

The fixture is laid out as the four-chip batch cell's program runs: the
sample all-gather, then a loop over the lane blocks whose body slices a
block, all-gathers it and runs the block's accept kernel, with the filter
and the accept around it.  Every expected number is worked out below from
the events' durations.
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
from bench.peaks import PEAKS, V5E  # noqa: E402

WINDOW = (0.0, 1e9)
BODY = "jit(run)/while/body"
#: (name, start ns, duration ns, op_name) of one device's ops
OPS = [
    ["coverage_marginals.9", 1e6, 80e6, "jit(run)/vmap(filter)/filter/"
                                        "pallas_call"],
    ["all-gather.31", 90e6, 9e6, "jit(run)/gather/all_gather"],
    ["coverage_accept.1", 100e6, 40e6, "jit(run)/vmap(accept)/accept/"
                                       "pallas_call"],
    # the lane blocks' loop carries no op_name; its body ops do
    ["while.12", 150e6, 400e6, ""],
    ["constant_dynamic-slice_fusion.20", 150e6, 2e6,
     BODY + "/gather/dynamic_slice"],
    ["all-gather.34", 152e6, 100e6, BODY + "/gather/gather/all_gather"],
    ["coverage_accept", 252e6, 60e6, BODY + "/vmap(accept)/accept/"
                                     "pallas_call"],
]
#: the second device's gather takes 10 ms longer
SLOW = 10e6


def cell_config():
    with open(os.path.join(ROOT, "bench", "configs",
                           "tinyimg_coverage_x4.json")) as f:
        return json.load(f)


@pytest.fixture
def scoped():
    second = []
    for name, s, d, op in OPS:
        if name == "all-gather.34":
            d += SLOW
        elif s > 152e6 and name != "while.12":
            s += SLOW
        second.append([name, s, d, op])
    return {"planes": [
        {"name": f"/device:TPU:{i}",
         "lines": [{"name": "XLA Ops", "events": events}]}
        for i, events in enumerate((OPS, second))]}


def ctx_for(selections=1, chips=4):
    logged = []
    return types.SimpleNamespace(
        window_ns=WINDOW, selections=selections, chips=chips,
        config=cell_config(), peaks=PEAKS[V5E], log=logged.append,
        logged=logged, cell={"name": "coverage_two_round_x4"})


def read(metric, ctx):
    return harness.load_module(os.path.join(
        ROOT, "bench", "metrics", f"{metric}.py")).read(ctx)


def reader(metric):
    return harness.load_module(os.path.join(
        ROOT, "bench", "metrics", f"{metric}.py"))


def test_gather_bytes_at_the_cell_shapes():
    # n = 1,048,576, k = 64, eps = 0.15 over 4 machines: s_cap = 3 * 8,192
    # + 16, f_cap = 4 * 2,048 + 64 + 16, J = 37 lanes; a row is 3,072 f32
    # features, an int32 id and a bool
    cfg = cell_config()
    rows = 4 * 24592 + 37 * 4 * 8272
    assert reader("gather.roofline").bytes_in(cfg, 4) == \
        3 / 4 * rows * (3072 * 4 + 5) == 12194262624.0
    # one machine receives nothing; bf16 features are half as wide
    assert reader("gather.roofline").bytes_in(cfg, 1) == 0
    assert reader("gather.roofline").bytes_in(
        dict(cfg, precision="bf16"), 4) == 3 / 4 * rows * (3072 * 2 + 5)


def test_gather_readers(scoped, monkeypatch):
    monkeypatch.setattr(ts, "load_scoped", lambda trace_dir: scoped)
    ctx = ctx_for()
    # the sample gather, the block's slice and its all-gather, averaged
    # over the two devices: 116 ms in the one selection
    gather_ns = 9e6 + 2e6 + 100e6 + SLOW / 2
    assert read("scope.gather_ms", ctx) == pytest.approx(gather_ns / 1e6)
    least_s = 12194262624.0 / (4 * 50e9)
    pct = read("gather.roofline", ctx)
    assert pct == pytest.approx(100 * least_s / (gather_ns / 1e9))
    assert 0 < pct <= 100
    assert ctx.logged[-1] == ("gather.roofline: least 60.9713 ms a "
                              "selection (bytes-bound), under the gather "
                              "scope 116.0000 ms")


def test_gather_roofline_over_100_is_an_error(scoped, monkeypatch):
    # the same trace read as two selections: 58 ms a selection, less than
    # the least time, so the time leaves part of the work out
    monkeypatch.setattr(ts, "load_scoped", lambda trace_dir: scoped)
    with pytest.raises(ValueError, match="over 100%"):
        read("gather.roofline", ctx_for(selections=2))


def test_gather_readers_find_nothing_without_a_gather(scoped, monkeypatch):
    for p in scoped["planes"]:
        for line in p["lines"]:
            line["events"] = [e for e in line["events"]
                              if ts.scope_of(e[3]) != "gather"]
    monkeypatch.setattr(ts, "load_scoped", lambda trace_dir: scoped)
    for m in ("scope.gather_ms", "gather.roofline"):
        assert read(m, ctx_for()) is None


def test_gather_roofline_reads_nothing_on_one_chip(scoped, monkeypatch):
    monkeypatch.setattr(ts, "load_scoped", lambda trace_dir: scoped)
    assert read("gather.roofline", ctx_for(chips=1)) is None
