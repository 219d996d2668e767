"""Profiler trace -> numbers.

A trace is first read into a plain structure (``load_xplane``), so that the
arithmetic below can be checked on a small recorded trace:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

Device planes are ``/device:TPU:<i>``; their ``XLA Ops`` line holds one event
per operation run on the device (fusions, Pallas kernels, collectives),
named by its HLO text (``%coverage_marginals.9 = f32[...] custom-call(...)``)
and kept here under the instruction's own name (``coverage_marginals.9``).
A ``while`` (or ``conditional``, ``call``) event spans the ops of its body.
The benchmark's own host spans (``jax.profiler.TraceAnnotation``) are
events on the ``/host:CPU`` plane.  Times are in nanoseconds on one clock.
"""

from __future__ import annotations

import fnmatch
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

_DEVICE = re.compile(r"^/device:[A-Z]+:(\d+)$")
_CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"

Interval = Tuple[float, float]


def load_xplane(trace_dir: str) -> dict:
    """Read the newest ``*.xplane.pb`` under ``trace_dir`` into the plain
    structure, keeping device op lines and host events only."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    planes = []
    for plane in pd.planes:
        keep = _DEVICE.match(plane.name) or plane.name == HOST_PLANE
        if not keep:
            continue
        lines = []
        for line in plane.lines:
            if _DEVICE.match(plane.name) and line.name != OPS_LINE:
                continue
            lines.append({"name": line.name, "events": [
                [op_name(e.name), float(e.start_ns), float(e.duration_ns)]
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def op_name(text: str) -> str:
    """``coverage_accept.2`` of ``%coverage_accept.2 = (s32[...]) ...``."""
    return text.split(" = ", 1)[0].lstrip("%")


def device_planes(trace: dict) -> List[dict]:
    """Device planes in device order."""
    devs = [(int(_DEVICE.match(p["name"]).group(1)), p)
            for p in trace["planes"] if _DEVICE.match(p["name"])]
    return [p for _, p in sorted(devs, key=lambda t: t[0])]


def op_events(plane: dict, window: Optional[Interval] = None
              ) -> List[Tuple[str, float, float]]:
    """(name, start, end) of the device ops of ``plane``, clipped to
    ``window``; ops wholly outside it are left out."""
    out = []
    for line in plane["lines"]:
        if line["name"] != OPS_LINE:
            continue
        for name, start, dur in line["events"]:
            s, e = start, start + dur
            if window is not None:
                s, e = max(s, window[0]), min(e, window[1])
                if e <= s:
                    continue
            out.append((name, s, e))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def busy_ns(plane: dict, window: Interval) -> float:
    """Time in ``window`` in which some op ran on this device."""
    return length(union((s, e) for _, s, e in op_events(plane, window)))


def idle_share(trace: dict, window: Interval) -> float:
    """1 - busy / window, averaged over the device planes (a fraction)."""
    planes = device_planes(trace)
    if not planes:
        raise ValueError("no device plane in the trace")
    w = window[1] - window[0]
    return sum(1.0 - busy_ns(p, window) / w for p in planes) / len(planes)


def op_time_ns(plane: dict, patterns: Iterable[str],
               window: Optional[Interval] = None) -> float:
    """Summed device time of the ops whose name matches one of the
    ``fnmatch`` patterns."""
    pats = tuple(patterns)
    return sum(e - s for name, s, e in op_events(plane, window)
               if any(fnmatch.fnmatchcase(name, p) for p in pats))


def exposed_ns(plane: dict, patterns: Iterable[str],
               window: Optional[Interval] = None) -> float:
    """The part of the matching ops' time during which no other op runs on
    the device (e.g. a collective not overlapped by compute); a loop that
    holds the op is not another op."""
    pats = tuple(patterns)
    ev = op_events(plane, window)
    hit = union((s, e) for n, s, e in ev
                if any(fnmatch.fnmatchcase(n, p) for p in pats))
    other = union((s, e) for n, s, e in ev
                  if not any(fnmatch.fnmatchcase(n, p) for p in pats)
                  and not _CONTAINER.match(n))
    covered, i, j = 0.0, 0, 0
    while i < len(hit) and j < len(other):
        s, e = max(hit[i][0], other[j][0]), min(hit[i][1], other[j][1])
        covered += max(0.0, e - s)
        if hit[i][1] < other[j][1]:
            i += 1
        else:
            j += 1
    return length(hit) - covered


def host_spans(trace: dict, names: Iterable[str]) -> List[Tuple[str, float,
                                                               float]]:
    """(name, start, end) of the host events named in ``names``."""
    want = set(names)
    out = []
    for p in trace["planes"]:
        if p["name"] != HOST_PLANE:
            continue
        for line in p["lines"]:
            for name, start, dur in line["events"]:
                if name in want:
                    out.append((name, start, start + dur))
    return sorted(out, key=lambda t: t[1])


def roofline_pct(least_s: float, measured_s: float) -> float:
    """Share of the roofline in percent: the least time the work could take
    at the chip's peaks over the time it took.  Above 100% the work was
    counted too high or the time left part of it out: an error, not a
    number."""
    if measured_s <= 0:
        raise ValueError("no measured time to compare the least time with")
    pct = 100.0 * least_s / measured_s
    if pct > 100.0:
        raise ValueError(f"roofline share {pct:.1f}% is over 100%: the work "
                         f"is counted too high or the time is incomplete")
    return pct


def least_time_s(flops: float, bytes_: float, peak_flops: float,
                 peak_bw: float) -> Tuple[float, str]:
    """(least seconds, which bound sets it: "flops" or "bytes")."""
    tf, tb = flops / peak_flops, bytes_ / peak_bw
    return (tf, "flops") if tf >= tb else (tb, "bytes")


def breakdown(trace: dict, window: Interval, span_names: Iterable[str],
              top: int = 10) -> dict:
    """The device ops that took most time (seconds, averaged over the
    devices; loops and calls, which hold other ops, left out) and the
    longest idle gaps of device 0, each named by the innermost host span
    that covers the gap's midpoint."""
    planes = device_planes(trace)
    totals: Dict[str, float] = {}
    for p in planes:
        for name, s, e in op_events(p, window):
            if not _CONTAINER.match(name):
                totals[name] = totals.get(name, 0.0) + (e - s)
    ops = sorted(totals.items(), key=lambda t: -t[1])[:top]
    device_ops = [[n, t / len(planes) / 1e9] for n, t in ops]
    busy = union((s, e) for _, s, e in op_events(planes[0], window))
    gaps, t = [], window[0]
    for s, e in busy + [(window[1], window[1])]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    spans = host_spans(trace, span_names)
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [(se - ss, n) for n, ss, se in spans if ss <= mid <= se]
        named.append([min(cover)[1] if cover else "no_span", (e - s) / 1e9])
    named.sort(key=lambda t: -t[1])
    return {"device_ops": device_ops, "idle_gaps": named[:top]}
