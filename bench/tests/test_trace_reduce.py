"""bench/trace_reduce.py and bench/peaks.py on small traces.

``trace_small.json`` is written out by hand: two devices, a host plane
with the benchmark's spans, and op names in the form the TPU trace gives
them, with every expected number worked out below.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import peaks  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
WINDOW = (1000.0, 11000.0)


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "trace_small.json")) as f:
        return json.load(f)


def test_device_planes_in_device_order(trace):
    assert [p["name"] for p in tr.device_planes(trace)] == [
        "/device:TPU:0", "/device:TPU:1"]


def test_op_name_is_the_instruction_name():
    assert tr.op_name("%coverage_accept.2 = (s32[8,37,1,128]{3,2,1,0}, "
                      "f32[8]) custom-call(%get-tuple-element.5)") == \
        "coverage_accept.2"
    assert tr.op_name("fusion.3") == "fusion.3"


def test_busy_union_and_idle_share(trace):
    d0, d1 = tr.device_planes(trace)
    # device 0: [1000, 4000) u [3000, 5000) u [6000, 7000), inside the loop
    # [1000, 5000) -> 5000 busy
    assert tr.busy_ns(d0, WINDOW) == 5000.0
    # device 1: [500, 2000) clipped to [1000, 2000), [8000, 12000) clipped
    # to [8000, 11000) -> 4000 busy
    assert tr.busy_ns(d1, WINDOW) == 4000.0
    assert tr.idle_share(trace, WINDOW) == pytest.approx(
        ((1 - 0.5) + (1 - 0.4)) / 2)


def test_kernel_time_by_name(trace):
    d0, _ = tr.device_planes(trace)
    assert tr.op_time_ns(d0, ["coverage_marginals*"], WINDOW) == 3000.0
    assert tr.op_time_ns(d0, ["coverage_accept*"], WINDOW) == 1000.0
    assert tr.op_time_ns(d0, ["*nothing*"], WINDOW) == 0.0


def test_all_gather_time_and_exposed_part(trace):
    d0, _ = tr.device_planes(trace)
    pats = ["all-gather*"]
    # all-gather-start [3000, 5000): [3000, 4000) overlaps the marginals
    # kernel, [4000, 5000) runs alone
    assert tr.op_time_ns(d0, pats, WINDOW) == 2000.0
    assert tr.exposed_ns(d0, pats, WINDOW) == 1000.0


def test_host_spans_and_breakdown(trace):
    spans = tr.host_spans(trace, ["window", "select"])
    assert [s[0] for s in spans] == ["window", "select", "select"]
    b = tr.breakdown(trace, WINDOW, ["window", "select", "readback"])
    # fusion.3: 4000 ns on device 1, averaged over the two devices; the
    # loop that holds device 0's kernels is not an op of its own
    assert b["device_ops"][0] == ["fusion.3", pytest.approx(2e-6)]
    assert "while.7" not in [n for n, _ in b["device_ops"]]
    # device 0 idles [5000, 6000) inside the first select's readback and
    # [7000, 11000) in the window alone
    assert b["idle_gaps"] == [["window", 4e-6], ["readback", 1e-6]]


def test_least_time_and_roofline():
    # coverage filter of one v5e chip: 262,144 rows x 3,072 f32 read once
    p = peaks.peaks_for(peaks.V5E)
    least, bound = tr.least_time_s(0.0, 262144 * 3072 * 4, p.flops,
                                   p.hbm_bw)
    assert bound == "bytes"
    assert least == pytest.approx(3.9331e-3, rel=1e-4)
    # exemplar filter: 2 n r d FLOP at n = 32,768, r = 1,024
    least, bound = tr.least_time_s(2.0 * 32768 * 1024 * 3072,
                                   32768 * 3072 * 4, p.flops, p.hbm_bw)
    assert bound == "flops"
    assert least == pytest.approx(1.0465e-3, rel=1e-3)
    assert tr.roofline_pct(1.0, 4.0) == 25.0


def test_roofline_over_100_is_an_error():
    with pytest.raises(ValueError, match="over 100%"):
        tr.roofline_pct(1.1, 1.0)


def test_peaks_unknown_kind_raises():
    assert peaks.peaks_for("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("cpu")
