"""The round-primitives layer: backend-parameterized MapReduce building
blocks, the epoch engine, and round/communication accounting.

Every driver in ``mapreduce.py`` is some composition of the same five
moves — Bernoulli-sample locally, filter locally at a threshold, ship the
top-O(k) singletons, gather the packed messages, accept centrally with
``threshold_greedy`` — repeated per threshold level.  This module defines
those moves ONCE, behind two interchangeable backends:

* ``SimRounds``  — the m machines are a leading vmap axis on one device
  (the executable MRC model used by tests/benchmarks);
* ``MeshRounds`` — the m machines are mesh axes inside a ``shard_map``
  body; a gather is a ``lax.all_gather`` and overflow counts finalize
  with a ``lax.psum``.

``run_epochs`` executes a descending threshold schedule tau_0 > tau_1 > ...
on either backend, carrying the partial solution across epochs: each epoch
is one (sample -> central accept -> filter -> gather -> central accept)
level, i.e. two MapReduce rounds.  Algorithm 4 is the 1-epoch scalar
instantiation, Algorithm 5 is the t-epoch known-OPT schedule, Algorithm 6
is 1 epoch vmapped over the unknown-OPT tau grid, and the paper's
(1 - 1/e - eps) multi-epoch driver is E = ceil(1/eps) epochs over the
same grid.

On the mesh a grid epoch's survivor gather and the accept after it run
over the threshold lanes in blocks of ceil(J/m), one block after another
(``gather_accept``), so that a device holds one block of the gathered
survivor stack, not all J lanes of every machine's buffer.

Each primitive, here and in ``core/threshold.py``, traces under a
``jax.named_scope`` named for its move (``sample``, ``tops``, ``filter``,
``pack``, ``gather``, ``accept``), so every device op of every algorithm
carries its move in its HLO ``op_name``.

The paper's complexity measure is the number of synchronous communication
rounds (and the per-machine message volume).  On a TPU pod a "round" is a
collective phase; the drivers construct a RoundLog from their *static*
buffer shapes, so the claimed "2 rounds" / "2t rounds" and the
Lemma-2/Lemma-6 memory bounds are checkable quantities, not comments.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.core.constraints import n_planes_of, split_plane, append_plane
from repro.core.threshold import (exclude_ids, pack_by_mask, threshold_filter,
                                  threshold_greedy)


@dataclasses.dataclass(frozen=True)
class RoundRecord:
    name: str
    bytes_per_machine: int   # outgoing message bound per machine
    bytes_total: int         # total gathered volume (central-machine memory)
    detail: str = ""


@dataclasses.dataclass(frozen=True)
class FaultRecord:
    """One realized fault, recorded beside the Lemma-2/6 byte accounting.

    ``eff_machines``/``eff_n`` are the *degraded* effective machine count
    and ground-set size after this fault landed — what the guarantee
    haircut is computed from (see faults.fault_summary)."""
    kind: str                  # faults.FAULT_KINDS
    epoch: int                 # epoch the fault landed in
    round_index: int           # gather index within the driver's trace
    machines: tuple            # affected machine indices
    n_machines: int            # configured M
    eff_machines: int          # survivors after this fault
    eff_n: int                 # degraded effective ground-set size
    detail: str = ""


@dataclasses.dataclass
class RoundLog:
    records: List[RoundRecord] = dataclasses.field(default_factory=list)
    #: runtime event counters (tau_fallback, n_dropped, ...) noted by the
    #: selector after each run — unlike ``records`` these are observed, not
    #: static.  Values may be (device) scalars; they are only coerced to
    #: int when summarized, so noting them never forces a sync.
    events: Dict[str, object] = dataclasses.field(default_factory=dict)
    #: realized fault-injection records (faults.FaultyRounds) — static per
    #: (plan, config) like ``records``, rebuilt from scratch on retrace
    faults: List[FaultRecord] = dataclasses.field(default_factory=list)

    def add(self, name: str, bytes_per_machine: int, bytes_total: int,
            detail: str = "") -> None:
        self.records.append(
            RoundRecord(name, int(bytes_per_machine), int(bytes_total), detail))

    def note(self, name: str, count) -> None:
        """Accumulate a runtime counter (e.g. tau_fallback events across the
        selects served by this driver).  Lazy: ``count`` may be a traced-out
        device scalar; it is summed symbolically and realized in summary()."""
        prev = self.events.get(name)
        self.events[name] = count if prev is None else prev + count

    def fault(self, rec: FaultRecord) -> None:
        self.faults.append(rec)

    def fault_events(self) -> Dict[str, int]:
        """Aggregate the fault records into flat counters, mirroring
        ``runtime_events()`` on the selectors so service stats expose shard
        losses/drops/corruptions/stragglers uniformly: per-kind affected-
        machine counts, the number of distinct faulted gathers, and the
        worst-round survivor count."""
        out: Dict[str, int] = {}
        for rec in self.faults:
            key = f"{rec.kind}_machines"
            out[key] = out.get(key, 0) + len(rec.machines)
        if self.faults:
            out["faulted_rounds"] = len(
                {(rec.epoch, rec.round_index) for rec in self.faults})
            out["min_eff_machines"] = min(
                rec.eff_machines for rec in self.faults)
        return out

    @property
    def n_rounds(self) -> int:
        return len(self.records)

    @property
    def total_bytes(self) -> int:
        return sum(r.bytes_total for r in self.records)

    @property
    def max_central_bytes(self) -> int:
        return max((r.bytes_total for r in self.records), default=0)

    def summary(self) -> str:
        lines = [f"rounds={self.n_rounds} total_gathered={self.total_bytes}B"]
        for i, r in enumerate(self.records, 1):
            lines.append(
                f"  round {i}: {r.name:24s} per-machine<={r.bytes_per_machine}B "
                f"gathered={r.bytes_total}B {r.detail}")
        if self.events:
            counts = " ".join(f"{k}={int(v)}"
                              for k, v in sorted(self.events.items()))
            lines.append(f"  events: {counts}")
        for rec in self.faults:
            lines.append(
                f"  FAULT [{rec.kind}] epoch={rec.epoch} "
                f"gather={rec.round_index} machines={list(rec.machines)} "
                f"eff=(M={rec.eff_machines}/{rec.n_machines}, "
                f"n~{rec.eff_n}) {rec.detail}")
        return "\n".join(lines)


def buffer_bytes(cap: int, feat_dim: int, itemsize: int = 4) -> int:
    """Bytes of one packed message buffer: features + ids + validity.
    ``itemsize`` is the feature element width on the wire — callers derive
    it from the precision policy's storage dtype (2 for bf16, 4 for f32);
    the Lemma-2/6 bounds are byte bounds, so the reported numbers must
    track what the gather actually ships, not assume float32."""
    return cap * (feat_dim * itemsize + 4 + 1)


def log_gather(log: RoundLog, name: str, cap: int, m: int, feat_dim: int,
               detail: str = "", itemsize: int = 4) -> None:
    """Record one gather round of an m-machine packed message of ``cap``
    rows — the per-machine/total byte-accounting idiom every driver (and
    the streaming sieve) repeats."""
    log.add(name, buffer_bytes(cap, feat_dim, itemsize),
            buffer_bytes(m * cap, feat_dim, itemsize), detail)


def epoch_round_log(cfg, m: int, feat_dim: int, epochs: int,
                    with_grid: bool = False, with_top: bool = False,
                    level_suffix=None) -> RoundLog:
    """The static RoundLog of an epoch-engine driver: 2 records per epoch
    (sample gather, survivor gather), identical for both backends by
    construction.  ``with_grid`` multiplies the survivor round by the
    unknown-OPT tau-grid width; ``with_top`` rides the Algorithm-7
    top-singleton message along with the first sample gather (the sparse
    path shares the same rounds).  ``level_suffix`` forces/suppresses the
    per-level ``-l{e}`` name suffix (default: only when epochs > 1)."""
    s_cap, f_cap, t_cap = cfg.caps()
    J = cfg.grid_size() if with_grid else 1
    isz = cfg.precision_policy.storage_itemsize
    levels = (epochs > 1) if level_suffix is None else level_suffix
    log = RoundLog()
    for e in range(1, epochs + 1):
        sfx = f"-l{e}" if levels else ""
        if with_top and e == 1:
            log_gather(log, f"gather-sample||top{sfx}", s_cap + t_cap, m,
                       feat_dim, "dense || sparse round 1", itemsize=isz)
        else:
            log_gather(log, f"gather-sample{sfx}", s_cap, m, feat_dim,
                       itemsize=isz)
        if with_grid:
            log.add(f"gather-survivors[grid]{sfx}",
                    J * buffer_bytes(f_cap, feat_dim, isz),
                    J * buffer_bytes(m * f_cap, feat_dim, isz),
                    f"grid J={J}")
        else:
            log_gather(log, f"gather-survivors{sfx}", f_cap, m, feat_dim,
                       itemsize=isz)
    return log


# ---------------------------------------------------------------------------
# local round halves (what one machine computes before a gather)
# ---------------------------------------------------------------------------

@jax.named_scope("sample")
def local_sample(oracle, key, feats, ids, valid, p, cap):
    """Algorithm 3 local half: Bernoulli(p) sample, packed."""
    mask = (jax.random.uniform(key, ids.shape) < p) & valid
    return pack_by_mask(feats, ids, mask, cap)


@jax.named_scope("filter")
def local_filter(oracle, st, sol, feats, ids, valid, tau, cap, size=None,
                 k=None, chunk=None, constraint=None, cstate=None):
    """Algorithm 2 local half: survivors of ThresholdFilter, packed.
    ``chunk`` (from MRConfig.filter_chunk) tiles the marginal sweep so the
    filter never materializes a full-block prep aux.

    Under a constraint, ``feats`` rows are AUGMENTED (plane columns last):
    the oracle filter runs on the base features, rows infeasible under the
    carried ``cstate`` are dropped (sound: feasibility is monotone), the
    threshold is cost-ratio scaled per row, and the packed survivors keep
    their plane columns — the plane rides the gather.

    Lemma 2's escape hatch: if the partial greedy solution already has k
    elements, the algorithm is done and the machines send *nothing* to the
    central machine ("In that case, we are done and do not send anything").
    Without this, low thresholds in the unknown-OPT grid overflow their
    whp-sized survivor buffers."""
    v = exclude_ids(ids, valid, sol)
    base, plane = split_plane(feats, n_planes_of(constraint))
    if plane is not None:
        v = v & constraint.eligible(cstate, plane)
        tau = constraint.row_tau(tau, plane)
    mask = threshold_filter(oracle, st, base, v, tau, chunk=chunk)
    if size is not None and k is not None:
        mask = mask & (size < k)
    return pack_by_mask(feats, ids, mask, cap)


@jax.named_scope("tops")
def local_top(oracle, feats, ids, valid, cap, constraint=None):
    """Algorithm 7 local half: top-`cap` elements by singleton value
    (computed on the base features when ``feats`` carries a constraint
    plane; the packed rows stay augmented).

    Truncation to the O(k) largest is the algorithm's *intended* behaviour
    ("send the O(k) largest elements on each machine"), not a buffer
    overflow — so n_dropped is reported as 0 here.  The sparse-path
    guarantee (Lemma 7) rests on the balls-and-bins argument that all
    globally-large elements survive this cut whp."""
    st0 = oracle.init_state()
    base, _ = split_plane(feats, n_planes_of(constraint))
    gains = oracle.marginals(st0, oracle.prep(st0, base))
    f, i, v, _ = pack_by_mask(feats, ids, valid, cap, priority=gains)
    return f, i, v, jnp.zeros((), jnp.int32)


#: The gathers of the last mesh selection program built, counted while it
#: traces (each ``MeshRounds`` starts a new count): ``lane_blocks`` blocks
#: of ``block_lanes`` lanes per grid survivor gather (``gather_accept``),
#: and ``bytes_in``, what one device receives from the other machines per
#: selection over all of the program's gathers.
_GATHER = {"lane_blocks": 0, "block_lanes": 0, "bytes_in": 0}


def gather_stats() -> dict:
    """The trace-time gather counts of the last mesh program built."""
    return dict(_GATHER)


def count_gathered(xs, gather_axes, times: int = 1) -> None:
    """Count what one device receives when each of ``xs`` is all-gathered
    ``times`` times: the other machines' buffers."""
    m = jax.lax.axis_size(gather_axes)
    _GATHER["bytes_in"] += times * (m - 1) * sum(
        math.prod(x.shape) * jnp.dtype(x.dtype).itemsize for x in xs)


def block_lanes(lanes: int, m: int) -> int:
    """Lanes per block of a grid survivor gather over m machines,
    ceil(lanes / m): one block of the gathered stack is about what one
    machine's own survivor buffers take."""
    return -(-lanes // m)


@jax.named_scope("gather")
def gather_packed(x, gather_axes, lead: int = 0):
    """all_gather a packed message buffer inside a shard_map body,
    concatenating the per-machine buffers on the capacity axis.  ``lead``
    leading batch axes (e.g. a threshold-grid axis, or (query, grid) in
    the batched driver) are kept in place — the whole stack moves in one
    collective, concatenated on the capacity axis itself, so no second,
    transposed copy of the gathered stack is ever made."""
    return jax.lax.all_gather(x, gather_axes, axis=lead, tiled=True)


def gather_accept(local, carry, lane_args, accept, gather_axes, block):
    """The survivor round's gather and central accept over the lane axis
    in blocks of ``block`` lanes, one block after another, so that no
    device ever holds more than one block of the gathered stack.

    ``local`` is this machine's packed triple, each (L, cap, ...) for L
    lanes; ``carry`` and ``lane_args`` are pytrees with a leading (L,)
    axis; ``accept(carry, cands, args)`` is one lane's accept and returns
    that lane's carry.  The blocks are the steps of a ``lax.scan``, so each
    block's all-gather waits for the block before it.  They are equal: the
    last one starts at L - block and overlaps the one before it, and an
    overlapped lane is accepted again from the same carry and candidates,
    so it is written twice with the same values.  Each lane sees the same
    candidates in the same order as in one gather of the whole stack, so
    the answers are the same bit for bit."""
    L = local[1].shape[0]
    n_blocks = -(-L // block)
    _GATHER.update(lane_blocks=n_blocks, block_lanes=block)
    count_gathered([jax.ShapeDtypeStruct((block,) + x.shape[1:], x.dtype)
                    for x in local], gather_axes, times=n_blocks)

    def take(tree, start):
        return jax.tree.map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, start, block), tree)

    def one_block(out, start):
        with jax.named_scope("gather"):
            cands = tuple(gather_packed(x, gather_axes, lead=1)
                          for x in take(local, start))
        new = jax.vmap(accept)(take(carry, start), cands,
                               take(lane_args, start))
        out = jax.tree.map(
            lambda o, n: jax.lax.dynamic_update_slice_in_dim(o, n, start, 0),
            out, new)
        return out, None

    starts = jnp.minimum(jnp.arange(n_blocks, dtype=jnp.int32) * block,
                         L - block)
    out, _ = jax.lax.scan(one_block, carry, starts)
    return out


def gather_lanes(local, gather_axes, lead: int = 1):
    """All-gather a packed triple whose ``lead`` leading axes are lanes,
    the whole stack in one block."""
    _GATHER.update(lane_blocks=1, block_lanes=math.prod(
        local[1].shape[:lead]))
    count_gathered(local, gather_axes)
    return tuple(gather_packed(x, gather_axes, lead=lead) for x in local)


def filter_accept_gathered(rr, oracle, carry, taus, cap, k, chunk, accept):
    """A grid epoch's survivor round in one block: ``rr.filter_grid``'s
    whole gathered (J, m*cap) stack, then every lane's accept on it."""
    R, rdrop = rr.filter_grid(oracle, *carry, taus, cap, k, chunk)
    return jax.vmap(accept)(carry, R, taus), rdrop


# ---------------------------------------------------------------------------
# backends: the same round primitives on the sim and mesh substrates
# ---------------------------------------------------------------------------

class SimRounds:
    """Round primitives with the m machines as a leading vmap axis.

    Holds the (m, n/m, ...) sharded ground set; every primitive returns the
    *gathered* message triple (feats, ids, valid) with the machine axis
    flattened into the capacity axis — exactly what the central machine
    sees — plus the summed overflow count."""

    def __init__(self, oracle, feats_mk, ids_mk, valid_mk, precision=None,
                 constraint=None):
        self.oracle = oracle
        if precision is not None:
            feats_mk = precision.cast_storage(feats_mk)
        # the constraint's attribute plane rides the sharded feature block
        # (at storage dtype) — every pack/gather ships it for free, and
        # feat_dim / the byte accounting below reflect the augmented width
        feats_mk = append_plane(feats_mk, constraint, ids_mk)
        self.constraint = constraint
        self.feats_mk, self.ids_mk, self.valid_mk = feats_mk, ids_mk, valid_mk
        self.m, self.n_local, self.feat_dim = feats_mk.shape

    def begin_epoch(self, e: int) -> None:
        """Epoch-boundary hook (run_epochs announces each level): a no-op
        on the bare substrates, where faults.FaultyRounds realizes its
        per-epoch shard-loss mask."""

    def sample(self, key, p, cap):
        m, d = self.m, self.feat_dim
        keys = jax.random.split(key, m)
        sf, si, sv, sdrop = jax.vmap(
            lambda ky, f, i, v: local_sample(self.oracle, ky, f, i, v, p, cap)
        )(keys, self.feats_mk, self.ids_mk, self.valid_mk)
        return ((sf.reshape(m * cap, d), si.reshape(-1), sv.reshape(-1)),
                jnp.sum(sdrop))

    def tops(self, oracle, cap):
        m, d = self.m, self.feat_dim
        tf, ti, tv, tdrop = jax.vmap(
            lambda f, i, v: local_top(oracle, f, i, v, cap,
                                      constraint=self.constraint)
        )(self.feats_mk, self.ids_mk, self.valid_mk)
        return ((tf.reshape(m * cap, d), ti.reshape(-1), tv.reshape(-1)),
                jnp.sum(tdrop))

    def filter(self, oracle, st, sol, size, cstate, tau, cap, k, chunk):
        m, d = self.m, self.feat_dim
        rf, ri, rv, rdrop = jax.vmap(
            lambda f, i, v: local_filter(oracle, st, sol, f, i, v, tau, cap,
                                         size, k, chunk,
                                         constraint=self.constraint,
                                         cstate=cstate)
        )(self.feats_mk, self.ids_mk, self.valid_mk)
        return ((rf.reshape(m * cap, d), ri.reshape(-1), rv.reshape(-1)),
                jnp.sum(rdrop))

    def filter_grid(self, oracle, st_j, sol_j, size_j, cstate_j, taus, cap,
                    k, chunk):
        """Per-tau survivor filter for a (J,)-stacked grid of partial
        solutions; machines outer, taus inner, then transposed so each
        grid lane sees its own (m*cap,) gathered message."""
        m, d = self.m, self.feat_dim
        J = taus.shape[0]

        def local_all(f, i, v):
            return jax.vmap(
                lambda st, sol, size, cst, tau: local_filter(
                    oracle, st, sol, f, i, v, tau, cap, size, k, chunk,
                    constraint=self.constraint, cstate=cst)
            )(st_j, sol_j, size_j, cstate_j, taus)

        rf, ri, rv, rdrop = jax.vmap(local_all)(self.feats_mk, self.ids_mk,
                                                self.valid_mk)
        # (m, J, cap, d) -> (J, m*cap, d)
        rf = rf.transpose(1, 0, 2, 3).reshape(J, m * cap, d)
        ri = ri.transpose(1, 0, 2).reshape(J, m * cap)
        rv = rv.transpose(1, 0, 2).reshape(J, m * cap)
        return (rf, ri, rv), jnp.sum(rdrop)

    def filter_accept_grid(self, oracle, carry, taus, cap, k, chunk, accept):
        """filter_grid, then ``accept`` on every lane: one block."""
        return filter_accept_gathered(self, oracle, carry, taus, cap, k,
                                      chunk, accept)

    def finalize_drops(self, drops):
        return drops


class MeshRounds:
    """Round primitives inside a shard_map body: this device IS one
    machine, a gather is a lax.all_gather over the mesh axes, and overflow
    counts stay machine-local until ``finalize_drops`` psums them once."""

    def __init__(self, oracle, feats, ids, valid, gather_axes,
                 precision=None, constraint=None, _block_lanes=None):
        self.oracle = oracle
        if precision is not None:
            feats = precision.cast_storage(feats)
        feats = append_plane(feats, constraint, ids)
        self.constraint = constraint
        self.feats, self.ids, self.valid = feats, ids, valid
        self.gather_axes = gather_axes
        self.machine_index = jax.lax.axis_index(gather_axes)
        self.m = jax.lax.axis_size(gather_axes)
        # lanes per block of the grid survivor gather; None derives
        # block_lanes(J, m) (tests set it to compare blockings)
        self._block_lanes = _block_lanes
        _GATHER.update(lane_blocks=0, block_lanes=0, bytes_in=0)

    def lane_block(self, lanes: int) -> int:
        return min(lanes, self._block_lanes or block_lanes(lanes, self.m))

    def begin_epoch(self, e: int) -> None:
        """Epoch-boundary hook — see SimRounds.begin_epoch."""

    def _gather3(self, f, i, v, lead: int = 0):
        count_gathered((f, i, v), self.gather_axes)
        return tuple(gather_packed(x, self.gather_axes, lead=lead)
                     for x in (f, i, v))

    def sample(self, key, p, cap):
        ky = jax.random.fold_in(key, self.machine_index)
        sf, si, sv, sdrop = local_sample(self.oracle, ky, self.feats,
                                         self.ids, self.valid, p, cap)
        return self._gather3(sf, si, sv), sdrop

    def tops(self, oracle, cap):
        tf, ti, tv, tdrop = local_top(oracle, self.feats, self.ids,
                                      self.valid, cap,
                                      constraint=self.constraint)
        return self._gather3(tf, ti, tv), tdrop

    def filter(self, oracle, st, sol, size, cstate, tau, cap, k, chunk):
        rf, ri, rv, rdrop = local_filter(oracle, st, sol, self.feats,
                                         self.ids, self.valid, tau, cap,
                                         size, k, chunk,
                                         constraint=self.constraint,
                                         cstate=cstate)
        return self._gather3(rf, ri, rv), rdrop

    def _filter_lanes(self, oracle, st_j, sol_j, size_j, cstate_j, taus,
                      cap, k, chunk):
        return jax.vmap(
            lambda st, sol, size, cst, tau: local_filter(
                oracle, st, sol, self.feats, self.ids, self.valid, tau, cap,
                size, k, chunk, constraint=self.constraint, cstate=cst)
        )(st_j, sol_j, size_j, cstate_j, taus)

    def filter_grid(self, oracle, st_j, sol_j, size_j, cstate_j, taus, cap,
                    k, chunk):
        rf, ri, rv, rdrop = self._filter_lanes(oracle, st_j, sol_j, size_j,
                                               cstate_j, taus, cap, k, chunk)
        return self._gather3(rf, ri, rv, lead=1), jnp.sum(rdrop)

    def filter_accept_grid(self, oracle, carry, taus, cap, k, chunk, accept):
        """The grid survivor round: every lane's filter in one call, then
        the gather and ``accept`` in blocks of ``lane_block(J)`` lanes
        (``gather_accept``); one block is filter_grid's whole stack."""
        J = taus.shape[0]
        block = self.lane_block(J)
        if block == J:
            _GATHER.update(lane_blocks=1, block_lanes=J)
            return filter_accept_gathered(self, oracle, carry, taus, cap, k,
                                          chunk, accept)
        rf, ri, rv, rdrop = self._filter_lanes(oracle, *carry, taus, cap, k,
                                               chunk)
        carry = gather_accept((rf, ri, rv), carry, taus, accept,
                              self.gather_axes, block)
        return carry, jnp.sum(rdrop)

    def finalize_drops(self, drops):
        return jax.lax.psum(drops, self.gather_axes)


# ---------------------------------------------------------------------------
# central-phase pieces and the epoch engine
# ---------------------------------------------------------------------------

def empty_solution(oracle, k, constraint=None):
    """The empty carry: (oracle state, sol ids, size, constraint state).
    The trailing cstate is ``()`` when unconstrained — an empty pytree, so
    vmapping / scanning the carry adds zero leaves and the unconstrained
    drivers trace exactly as before."""
    return (oracle.init_state(),
            jnp.full((k,), -1, jnp.int32),
            jnp.zeros((), jnp.int32),
            () if constraint is None else constraint.init_state())


@jax.named_scope("accept")
def greedy_step(oracle, carry, cands, tau, k, cfg, k_dyn=None,
                constraint=None):
    """One central accept: extend the carried (state, sol, size, cstate)
    with the gathered candidate triple at threshold tau via
    ThresholdGreedy (engine/accept/chunk from cfg), excluding
    already-selected ids.  Augmented candidate rows are split into base
    features + constraint plane in front of the engine."""
    st, sol, size, cstate = carry
    feats, ids, valid = cands
    valid = exclude_ids(ids, valid & (ids >= 0), sol)
    base, plane = split_plane(feats, n_planes_of(constraint))
    if constraint is None:
        st, sol, size = threshold_greedy(
            oracle, st, sol, size, base, ids, valid, tau, k,
            accept=cfg.accept, engine=cfg.engine, chunk=cfg.chunk,
            k_dyn=k_dyn)
        return st, sol, size, cstate
    return threshold_greedy(
        oracle, st, sol, size, base, ids, valid, tau, k,
        accept=cfg.accept, engine=cfg.engine, chunk=cfg.chunk, k_dyn=k_dyn,
        constraint=constraint, cstate=cstate, cplane=plane)


@jax.named_scope("accept")
def grid_phase1(oracle, S, taus, k, cfg, k_dyn=None, constraint=None):
    """First central accept of a grid epoch: an independent empty-start
    greedy per threshold guess (the paper's parallel tau copies)."""
    def p1(tau):
        return greedy_step(oracle, empty_solution(oracle, k, constraint), S,
                           tau, k, cfg, k_dyn, constraint)
    return jax.vmap(p1)(taus)


@jax.named_scope("accept")
def sparse_sweep(oracle, L, schedule, cfg, k_dyn=None, constraint=None):
    """Algorithm 7's central half, generalized to a schedule: each guess
    lane runs its full descending threshold sequence over the gathered
    top-singleton pool — purely central, no extra rounds.  ``schedule`` is
    a list of per-level (G,) threshold columns.  Returns per-lane
    (sol (G, k), size (G,), value (G,))."""
    k = cfg.k

    def per_guess(*taus):
        carry = empty_solution(oracle, k, constraint)
        for tau in taus:
            carry = greedy_step(oracle, carry, L, tau, k, cfg, k_dyn,
                                constraint)
        st, sol, size, _ = carry
        return sol, size, oracle.value(st)

    return jax.vmap(per_guess)(*schedule)


def chain_keys(key, n: int):
    """The historical multi-threshold key chain: split once per level and
    use the second half, preserving the drivers' bit-exact sampling."""
    ks = []
    for _ in range(n):
        key, k2 = jax.random.split(key)
        ks.append(k2)
    return ks


def run_epochs(oracle, rounds, schedule, epoch_keys, cfg, k_dyn=None,
               first_sample=None, constraint=None):
    """The epoch engine: execute a descending threshold schedule on a
    round-primitives backend, carrying the partial solution across epochs.

    Each epoch (= 2 MapReduce rounds) at level threshold tau_e:
      sample -> central accept at tau_e -> local filter at tau_e
             -> gather survivors -> central accept at tau_e.

    ``schedule`` is a list of per-epoch thresholds, each either a scalar
    (one sequential solution — Algorithms 4/5) or a (G,) column of guesses
    (G vmapped lanes sharing every epoch's sample — Algorithm 6 and the
    unknown-OPT multi-epoch driver; the grid axis leads the carry).
    ``first_sample`` optionally injects epoch 1's already-gathered sample
    (the unknown-OPT drivers derive the tau grid from it before the first
    accept).  ``constraint`` threads the feasibility contract through every
    central accept and local filter; its O(1)/O(P) state rides the carry
    across epochs (per grid lane when vmapped).  Returns
    ((state, sol, size, cstate), drops); drops are summed but NOT
    finalized — callers pass them through rounds.finalize_drops once.
    """
    k = cfg.k
    s_cap, f_cap, _ = cfg.caps()
    keff = k if k_dyn is None else k_dyn
    grid = jnp.ndim(schedule[0]) == 1
    carry = None
    drops = jnp.zeros((), jnp.int32)
    for e, taus in enumerate(schedule):
        # announce the epoch boundary so a fault-injecting wrapper can
        # realize its per-epoch shard-loss mask (no-op on bare substrates;
        # idempotent when the unknown-OPT drivers pre-drew epoch 1's sample)
        rounds.begin_epoch(e)
        if e == 0 and first_sample is not None:
            S, sdrop = first_sample
        else:
            S, sdrop = rounds.sample(epoch_keys[e], cfg.sample_p, s_cap)
        if grid:
            if carry is None:
                carry = grid_phase1(oracle, S, taus, k, cfg, k_dyn,
                                    constraint)
            else:
                carry = jax.vmap(
                    lambda c, t: greedy_step(oracle, c, S, t, k, cfg, k_dyn,
                                             constraint)
                )(carry, taus)
            carry, rdrop = rounds.filter_accept_grid(
                oracle, carry, taus, f_cap, keff, cfg.filter_chunk,
                lambda c, cand, t: greedy_step(oracle, c, cand, t, k, cfg,
                                               k_dyn, constraint))
        else:
            if carry is None:
                carry = empty_solution(oracle, k, constraint)
            carry = greedy_step(oracle, carry, S, taus, k, cfg, k_dyn,
                                constraint)
            R, rdrop = rounds.filter(oracle, *carry, taus, f_cap, keff,
                                     cfg.filter_chunk)
            carry = greedy_step(oracle, carry, R, taus, k, cfg, k_dyn,
                                constraint)
        drops = drops + sdrop + rdrop
    return carry, drops
