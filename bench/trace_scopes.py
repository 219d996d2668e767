"""Device time by the program's named scopes, and the program's own spans
inside the serving step.

The program traces each round primitive under a ``jax.named_scope``
(``core/rounds.py``, ``core/threshold.py``): ``sample``, ``tops``,
``filter``, ``pack``, ``gather``, ``accept``.  XLA keeps the scope path in
each instruction's ``op_name`` metadata (``jit(run)/vmap(filter)/filter/
jit(pack_by_mask)/pack/sort``), and the TPU trace's ``XLA Ops`` events
carry it as a stat of their metadata (``OP_NAME_STAT``).  ``trace_reduce``'s plain
structure drops event stats, so ``load_scoped`` reads the newest
``.xplane.pb`` again and keeps each device op's op_name as a fourth field:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns,
                                        op_name], ...]}]}]}

An op belongs to the innermost of the six scopes on its op_name path; the
last component is the op's own primitive (``gather``, ``sort``) and names
no scope.  Each op counts its self time, its duration less that of the
ops it holds (a loop holds its body's), so the time by scope partitions
the busy device time.

The serving step's spans (``serve.step`` and its children, and
``select.budget_check`` inside ``serve.dispatch``) and JAX's own events
for a new program (``LOWER_EVENT``, then ``COMPILE_EVENT`` or a load from
the compile cache) are host events, read from the harness's plain trace.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from bench import harness
from bench import trace_reduce as tr

SCOPES = ("sample", "tops", "filter", "pack", "gather", "accept")
#: the program's spans: a serving step, its children in the order a step
#: opens them, and the budget check inside ``serve.dispatch``
PROGRAM_SPANS = ("serve.step", "serve.admit", "serve.batch",
                 "serve.dispatch", "serve.wait", "serve.retire",
                 "select.budget_check")
#: the stat of a TPU ``XLA Ops`` event's metadata that holds the op_name
OP_NAME_STAT = "tf_op"
#: JAX's host event for a program new to the process (a jit cache miss),
#: which JAX then compiles or loads from the persistent compile cache
LOWER_EVENT = "lower_sharding_computation"
#: JAX's host event for a compile; a cache load leaves no event of its own
#: on the v5e
COMPILE_EVENT = "backend_compile_and_load"

_UNWRAP = re.compile(r"^(?:[\w.]+\()*([^()]*)\)*$")
#: an ``XLA Modules`` event: ``jit_run(18379618128849442235)``
_PROGRAM = re.compile(r"^.*\((\d+)\)$")

Interval = Tuple[float, float]


def scope_of(op_name: str) -> Optional[str]:
    """The innermost of ``SCOPES`` on an op_name path, or None.  A scope
    under a transformation reads ``vmap(vmap(filter))``."""
    parts = op_name.split("/")[:-1]
    for part in reversed(parts):
        name = _UNWRAP.match(part).group(1)
        if name in SCOPES:
            return name
    return None


def _fields(buf: memoryview, start: int = 0, end: Optional[int] = None):
    """(field number, value) of one protobuf message in ``buf[start:end]``:
    an int for a varint or fixed field, a (start, end) slice for a
    length-delimited one."""
    end = len(buf) if end is None else end
    i = start
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            value, i = int.from_bytes(buf[i:i + width], "little"), i + width
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    out, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _text(buf: memoryview, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def op_names_by_program(path: str) -> Dict[str, Dict[Tuple[int, str], str]]:
    """{device plane: {(program id, instruction): op_name}} from the event
    metadata of an ``.xplane.pb`` (``XSpace.planes[].event_metadata``, whose
    stats hold ``program_id`` and ``tf_op``); the profiler's Python reader
    leaves metadata stats out, so this walks the protobuf wire format."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for field, plane in _fields(buf):
        if field != 1:                          # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for pf, value in _fields(buf, *plane):
            if pf == 2:                         # XPlane.name
                name = _text(buf, value)
            elif pf == 4:                       # event_metadata map entry
                metas.append(value)
            elif pf == 5:                       # stat_metadata map entry
                for ef, ev in _fields(buf, *value):
                    if ef == 2:
                        sid, sname = None, ""
                        for sf, sv in _fields(buf, *ev):
                            if sf == 1:
                                sid = sv
                            elif sf == 2:
                                sname = _text(buf, sv)
                        stat_names[sid] = sname
        if not tr._DEVICE.match(name):
            continue
        want = {k for k, v in stat_names.items()
                if v in (OP_NAME_STAT, "program_id")}
        table = {}
        for entry in metas:
            for ef, ev in _fields(buf, *entry):
                if ef != 2:                     # the XEventMetadata
                    continue
                ev_name, stats = "", {}
                for mf, mv in _fields(buf, *ev):
                    if mf == 2:
                        ev_name = _text(buf, mv)
                    elif mf == 5:               # XEventMetadata.stats
                        sid, val = None, None
                        for sf, sv in _fields(buf, *mv):
                            if sf == 1:
                                sid = sv
                            elif sf in (3, 4):
                                val = sv
                            elif sf == 5:
                                val = _text(buf, sv)
                        if sid in want:
                            stats[stat_names[sid]] = val
                op = stats.get(OP_NAME_STAT)
                if op is not None and "program_id" in stats:
                    key = (int(stats["program_id"]), tr.op_name(ev_name))
                    table[key] = op.rpartition(":")[0] or op
        out[name] = table
    return out


def load_scoped(trace_dir: str) -> dict:
    """The device planes of the newest ``*.xplane.pb`` under ``trace_dir``,
    each ``XLA Ops`` event with its op_name ("" where it carries none),
    found by the program (``XLA Modules`` line) that the op ran in."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    tables = op_names_by_program(paths[-1])
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name not in tables:
            continue
        table = tables[plane.name]
        lines = {line.name: line for line in plane.lines}
        # the programs run one after another: (start, end, program id)
        runs = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       int(_PROGRAM.match(e.name).group(1)))
                      for e in (lines["XLA Modules"].events
                                if "XLA Modules" in lines else [])
                      if _PROGRAM.match(e.name))
        starts = [r[0] for r in runs]
        events, short = [], {}
        for e in (lines[tr.OPS_LINE].events if tr.OPS_LINE in lines
                  else []):
            start = e.start_ns
            j = bisect.bisect_right(starts, start) - 1
            program = runs[j][2] if j >= 0 and start < runs[j][1] else None
            name = short.get(e.name)
            if name is None:
                name = short[e.name] = tr.op_name(e.name)
            events.append([name, start, e.duration_ns,
                           table.get((program, name), "")])
        planes.append({"name": plane.name, "lines": [
            {"name": tr.OPS_LINE, "events": events}]})
    return {"planes": planes}


def _events(plane: dict, window: Interval):
    """(name, start, end, op_name) of the plane's ops, clipped to
    ``window``."""
    for line in plane["lines"]:
        if line["name"] != tr.OPS_LINE:
            continue
        for name, start, dur, op in line["events"]:
            s, e = max(start, window[0]), min(start + dur, window[1])
            if e > s:
                yield name, s, e, op


def scope_time_ns(plane: dict, window: Interval
                  ) -> Tuple[Dict[Optional[str], float],
                             Dict[Tuple[str, Optional[str]], float]]:
    """(busy ns by scope, None for none; busy ns by (op, scope)) of one
    device in ``window``.  Ops nest (a loop holds its body's ops), and each
    op is given its self time: its duration less its children's, so that
    every instant goes to the innermost op running then."""
    ev = sorted(_events(plane, window), key=lambda t: (t[1], -t[2]))
    self_ns = [e - s for _, s, e, _ in ev]
    stack: List[int] = []
    for i, (_, s, e, _) in enumerate(ev):
        while stack and ev[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= min(e, ev[stack[-1]][2]) - s
        stack.append(i)
    by_scope: Dict[Optional[str], float] = {}
    by_op: Dict[Tuple[str, Optional[str]], float] = {}
    scopes: Dict[str, Optional[str]] = {}
    for (name, _, _, op), t in zip(ev, self_ns):
        if op not in scopes:
            scopes[op] = scope_of(op)
        scope = scopes[op]
        by_scope[scope] = by_scope.get(scope, 0.0) + t
        by_op[(name, scope)] = by_op.get((name, scope), 0.0) + t
    return by_scope, by_op


def scope_times(ctx) -> Tuple[Dict[Optional[str], float],
                              Dict[Tuple[str, Optional[str]], float]]:
    """``scope_time_ns`` in the window of the traced run's newest trace,
    averaged over the devices, read once per run; the first read logs the unscoped share of the busy time
    and the ten ops that held the device longest, each with its scope."""
    if getattr(ctx, "scope_times", None) is None:
        planes = tr.device_planes(load_scoped(os.path.join(
            harness.OUT_DIR, "trace", ctx.cell["name"])))
        by_scope: Dict[Optional[str], float] = {}
        by_op: Dict[Tuple[str, Optional[str]], float] = {}
        for p in planes:
            s, o = scope_time_ns(p, ctx.window_ns)
            for k, v in s.items():
                by_scope[k] = by_scope.get(k, 0.0) + v / len(planes)
            for k, v in o.items():
                by_op[k] = by_op.get(k, 0.0) + v / len(planes)
        ctx.scope_times = (by_scope, by_op)
        busy = sum(by_scope.values())
        if busy:
            ctx.log(f"scopes: {100 * by_scope.get(None, 0.0) / busy:.3f}% "
                    f"of {busy / 1e9:.6f} s busy device time is under no "
                    f"scope; by scope (s): " + ", ".join(
                        f"{k or 'none'} {v / 1e9:.6f}" for k, v in sorted(
                            by_scope.items(), key=lambda t: -t[1])))
            top = sorted(by_op.items(), key=lambda t: -t[1])[:10]
            ctx.log("scopes: top ops (s, scope): " + ", ".join(
                f"{n} {v / 1e9:.6f} {s or 'none'}" for (n, s), v in top))
    return ctx.scope_times


def scope_ms_per_selection(ctx, scope: str) -> Optional[float]:
    """Device time under ``scope`` in the window, per selection, in ms;
    None where no op carries the scope (a program without scopes)."""
    by_scope, _ = scope_times(ctx)
    ns = by_scope.get(scope, 0.0)
    if not ns or not ctx.selections:
        return None
    return ns / 1e6 / ctx.selections


def _busy_before(merged: List[Interval], starts: List[float],
                 before: List[float], t: float) -> float:
    """Busy time up to ``t`` of the merged intervals, given their
    ``starts`` and the busy time ``before`` each."""
    i = bisect.bisect_right(starts, t) - 1
    if i < 0:
        return 0.0
    s, e = merged[i]
    return before[i] + min(t, e) - s


def step_idle_split(trace: dict, window: Interval
                    ) -> Tuple[int, Dict[str, float]]:
    """(steps, device idle ns inside the ``serve.step`` spans that start in
    ``window``, by the innermost program span over it), averaged over the
    devices; idle in a step outside its children goes to ``serve.step``."""
    spans = tr.host_spans(trace, PROGRAM_SPANS)
    steps = [sp for sp in spans if sp[0] == "serve.step"
             and window[0] <= sp[1] < window[1]]
    if not steps:
        return 0, {}
    hull = (steps[0][1], max(e for _, _, e in steps))
    planes = []
    for p in tr.device_planes(trace):
        merged = tr.union((s, e) for _, s, e in tr.op_events(p, hull))
        before = [0.0]
        for s, e in merged[:-1]:
            before.append(before[-1] + e - s)
        planes.append((merged, [s for s, _ in merged], before))
    split: Dict[str, float] = {}
    for step in steps:
        nodes = [step] + [sp for sp in spans if sp[0] != "serve.step"
                          and step[1] <= sp[1] and sp[2] <= step[2]]
        for i, (name, s, e) in enumerate(nodes):
            idle = e - s - sum(
                _busy_before(*p, e) - _busy_before(*p, s)
                for p in planes) / len(planes)
            split[name] = split.get(name, 0.0) + idle
            # a span's idle leaves its parent, the innermost span over it
            outer = [(pe - ps, j) for j, (_, ps, pe) in enumerate(nodes)
                     if j != i and ps <= s and e <= pe]
            if outer:
                parent = nodes[min(outer)[1]][0]
                split[parent] = split.get(parent, 0.0) - idle
    return len(steps), split


def mean_span_ms(trace: dict, window: Interval, name: str
                 ) -> Optional[float]:
    """Mean duration in ms of the ``name`` spans that start in
    ``window``, or None where there is none."""
    durs = [e - s for n, s, e in tr.host_spans(trace, [name])
            if window[0] <= s < window[1]]
    return sum(durs) / len(durs) / 1e6 if durs else None


def compile_count(ctx) -> int:
    """Programs lowered in the window, each compiled or loaded from the
    compile cache; every lowering and compile is logged with the innermost
    program or benchmark span over its start."""
    spans = tr.host_spans(ctx.trace, PROGRAM_SPANS + harness.SPANS)
    count = 0
    for name, s, _ in tr.host_spans(ctx.trace, (LOWER_EVENT, COMPILE_EVENT)):
        if not ctx.window_ns[0] <= s < ctx.window_ns[1]:
            continue
        cover = [(se - ss, n) for n, ss, se in spans if ss <= s <= se]
        ctx.log(f"compiles: {name} at {(s - ctx.window_ns[0]) / 1e9:.6f} s "
                f"into the window, in {min(cover)[1] if cover else 'no_span'}")
        count += name == LOWER_EVENT
    return count
