"""Algorithm 1 (ThresholdGreedy) and Algorithm 2 (ThresholdFilter).

Paper-faithful semantics with TPU-shaped execution:

* The paper streams elements one at a time and accepts any element whose
  marginal is >= tau.  Sequential rank-1 oracle calls are hostile to a
  vector machine, so the engines here score candidates in batches and then
  accept per ``accept``:

    - ``"first"`` (default, Algorithm-1-faithful): the earliest element in
      the fixed stream order whose fresh marginal is >= tau.  Because
      marginals are recomputed against the current solution before an
      accept, the accepted sequence is exactly what the paper's sequential
      loop would accept.
    - ``"best"``: argmax above tau (beyond-paper; never worse — see
      EXPERIMENTS.md §Perf).

  Either rule preserves the two facts the proofs use: every accepted marginal
  is >= tau, and on exit (with |G| < k) no candidate has marginal >= tau.

* Three interchangeable engines (DESIGN.md §3):

    - ``engine="dense"``: every iteration rescores the *whole* candidate
      block with one batched ``marginals`` call — O(|G| * C) oracle rows.
    - ``engine="lazy"``: a stale-gains buffer upper-bounds every candidate's
      marginal (submodularity: marginals only shrink as G grows), and each
      iteration rescores only one fixed-size ``chunk`` of candidates whose
      stale gain still clears tau.  Rows with stale gain < tau can never be
      accepted and are never touched again.  For ``accept="first"`` the
      accepted sequence is *identical* to the dense engine's; oracle work
      drops to ~O(|G| * chunk).  The lazy engine never materializes the
      full prep aux — candidates stream through ``oracle.chunk_marginals``
      in (chunk, d) tiles (FacilityLocation routes them through the fused
      Pallas kernel, so the (C, r) similarity block never exists in HBM).
    - ``engine="fused"`` (accept="first" only): the whole accept loop moves
      on-device — each iteration hands one contiguous ``chunk`` at the scan
      frontier to ``oracle.chunk_accept``, which sweeps its rows *inside
      one kernel* (state in VMEM scratch for the kerneled oracles, a
      lax.scan reference otherwise), accepting every qualifying row in
      stream order.  The outer while_loop advances one CHUNK per trip
      instead of one accept: the per-accept kernel launch, the tree-wide
      jnp.where over the oracle state, and the O(C) frontier scan are all
      paid once per chunk.  Accepted sequences are bit-identical to the
      dense engine's (the sweep is exactly Algorithm 1's sequential loop).

* Everything is fixed-shape: candidate blocks carry a validity mask, the
  solution is a fixed (k,) id buffer with a size counter.  Every engine is
  a ``lax.while_loop`` bounded by k accepts (the fused engine additionally
  by the chunk count).

All functions are pure and jit/shard_map friendly; determinism across
machines (the paper needs G_0 identical everywhere) is inherited from
replicated inputs + deterministic reductions.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

NEG = -jnp.inf

DEFAULT_CHUNK = 128

ENGINES = ("dense", "lazy", "fused")
ACCEPTS = ("first", "best")


def validate_engine(engine: str, accept: str = "first",
                    where: str = "threshold_greedy") -> None:
    """Shared trace-time validation of the (engine, accept) knobs.

    Every consumer — threshold_greedy, threshold_greedy_batch, MRConfig,
    the streaming SieveSpec — funnels through here, so a typo'd knob fails
    immediately with the call-site name instead of surfacing as a
    mysterious shape/tracer error deep inside a vmapped driver (or, worse,
    only on the one code path that happened to dispatch on it)."""
    if engine not in ENGINES:
        raise ValueError(f"{where}: unknown engine {engine!r}; "
                         f"choose from {ENGINES}")
    if accept not in ACCEPTS:
        raise ValueError(f"{where}: unknown accept {accept!r}; "
                         f"choose from {ACCEPTS}")
    if engine == "fused" and accept != "first":
        raise ValueError(
            f"{where}: engine='fused' sweeps chunks in stream order — a "
            f"forward pass — so it only implements accept='first' "
            f"(Algorithm-1-faithful); use engine='lazy' for accept='best'")


class GreedyStats(NamedTuple):
    """Oracle-work accounting for one threshold_greedy call (all int32).

    n_evals counts candidate *rows* pushed through a marginals evaluation —
    the paper's oracle-call measure, batched.  n_iters counts loop trips.
    """
    n_evals: jax.Array
    n_iters: jax.Array


class GreedyState(NamedTuple):
    oracle_state: object
    sol_ids: jax.Array      # (k,) int32, -1 padded
    sol_size: jax.Array     # () int32
    taken: jax.Array        # (C,) bool — candidates already taken this call
    done: jax.Array         # () bool
    n_evals: jax.Array      # () int32 — marginal rows evaluated so far
    n_iters: jax.Array      # () int32
    cstate: object = ()     # constraint feasibility state (() when none)


class LazyState(NamedTuple):
    oracle_state: object
    sol_ids: jax.Array      # (k,) int32, -1 padded
    sol_size: jax.Array     # () int32
    g_stale: jax.Array      # (C,) f32 — upper bounds on fresh marginals
    taken: jax.Array        # (C,) bool
    done: jax.Array         # () bool
    n_evals: jax.Array      # () int32
    n_iters: jax.Array      # () int32
    cstate: object = ()     # constraint feasibility state (() when none)


def _feasible(constraint, cstate, cplane, C):
    """(C,) feasibility under the current constraint state; all-true when
    unconstrained.  Sound to exclude from lazy/fused hot sets because
    constraint feasibility is monotone (see core/constraints.py)."""
    if constraint is None or cplane is None:   # plane-less: never binding
        return jnp.ones((C,), bool)
    return constraint.eligible(cstate, cplane)


def _row_tau(constraint, tau, cplane):
    """Per-row accept threshold — ``tau`` itself when unconstrained (or
    when the constraint does no cost-ratio scaling)."""
    if constraint is None or cplane is None:
        return tau
    return constraint.row_tau(tau, cplane)


def _tau_at(tau_row, idxs):
    """Index a per-row threshold that may be a scalar broadcast."""
    return tau_row[idxs] if jnp.ndim(tau_row) else tau_row


def _cstate_accept(constraint, cstate, cplane, idx, accept_now):
    """Conditionally account candidate ``idx`` into the feasibility state."""
    if constraint is None or cplane is None:
        return cstate
    new = constraint.add(cstate, cplane[idx])
    return jax.tree.map(lambda a, b: jnp.where(accept_now, a, b),
                        new, cstate)


def _apply_accept(st, accept_now, new_state, cand_id, idx, k):
    """Shared accept bookkeeping: conditionally swap in the post-add oracle
    state, append cand_id to the solution buffer, and mark idx taken."""
    oracle_state = jax.tree.map(
        lambda new, old: jnp.where(accept_now, new, old),
        new_state, st.oracle_state)
    sol_ids = jnp.where(
        accept_now,
        st.sol_ids.at[jnp.minimum(st.sol_size, k - 1)].set(cand_id),
        st.sol_ids)
    sol_size = st.sol_size + jnp.where(accept_now, 1, 0)
    taken = st.taken.at[idx].set(st.taken[idx] | accept_now)
    return oracle_state, sol_ids, sol_size, taken


@jax.named_scope("accept")
def threshold_greedy(oracle, oracle_state, sol_ids, sol_size, cand_feats,
                     cand_ids, cand_valid, tau, k: int, accept: str = "first",
                     engine: str = "dense", chunk: int = DEFAULT_CHUNK,
                     with_stats: bool = False, k_dyn=None, constraint=None,
                     cstate=None, cplane=None):
    """Algorithm 1.  Extends (sol_ids, sol_size, oracle_state) greedily with
    candidates whose marginal w.r.t. the current solution is >= tau, until
    |G| = k or no candidate qualifies.

    cand_feats: (C, feat_dim); cand_ids: (C,) int32; cand_valid: (C,) bool.
    engine: "dense" rescores all C candidates per iteration; "lazy" keeps
    stale upper bounds and rescores `chunk`-sized slices on demand (same
    accepted sequence for accept="first"; same invariants for both accepts);
    "fused" runs the accept loop itself inside ``oracle.chunk_accept`` and
    advances one chunk per iteration (accept="first" only; same accepted
    sequence).  ``k`` is the static solution-buffer capacity; ``k_dyn``
    (optional, a traced () int32 <= k) is the effective cardinality budget
    — the batched multi-query path carries per-query budgets through one
    fixed-shape program this way.

    Constrained selection (core/constraints.py): pass ``constraint``
    together with its feasibility state ``cstate`` and the candidates'
    (C, n_planes) attribute plane ``cplane``; every engine then consults
    feasibility before accepting and applies the constraint's per-row
    threshold rule (cost-ratio for knapsack).  The return value grows the
    updated cstate: (oracle_state, sol_ids, sol_size, cstate[, stats]).

    Unconstrained returns (oracle_state, sol_ids, sol_size), plus a
    GreedyStats when ``with_stats``.
    """
    validate_engine(engine, accept, where="threshold_greedy")
    fn = {"dense": _threshold_greedy_dense,
          "lazy": _threshold_greedy_lazy,
          "fused": _threshold_greedy_fused}[engine]
    k_eff = k if k_dyn is None else jnp.minimum(
        jnp.asarray(k_dyn, jnp.int32), k)
    if constraint is not None and cstate is None:
        cstate = constraint.init_state()
    out_state, out_sol, out_size, out_cstate, stats = fn(
        oracle, oracle_state, sol_ids, sol_size, cand_feats, cand_ids,
        cand_valid, tau, k, k_eff, accept, chunk, constraint,
        () if cstate is None else cstate, cplane)
    out = (out_state, out_sol, out_size)
    if constraint is not None:
        out = out + (out_cstate,)
    if with_stats:
        return out + (stats,)
    return out


@jax.named_scope("accept")
def threshold_greedy_batch(oracle, oracle_states, sol_ids, sol_sizes,
                           cand_feats, cand_ids, cand_valid, taus, k: int,
                           k_dyn=None, bind=None, bind_params=None,
                           accept: str = "first", engine: str = "dense",
                           chunk: int = DEFAULT_CHUNK,
                           with_stats: bool = False, constraint=None,
                           cstates=None, cplane=None):
    """Q independent ThresholdGreedy queries over ONE shared candidate block.

    The paper's algorithms consume only (oracle state, threshold) — they are
    oblivious to which query they serve — so Q queries vmap over per-query
    state while the (C, d) candidate block stays a broadcast operand: one
    compiled program, one pass over the corpus shard, Q answers.

    oracle_states / sol_ids / sol_sizes / taus carry a leading (Q,) axis;
    cand_feats / cand_ids / cand_valid do not.  ``k`` is the shared buffer
    capacity, ``k_dyn`` (Q,) int32 the per-query budgets (<= k).  Per-query
    oracle hyper-parameters ride in ``bind_params`` (a pytree with leading
    (Q,) leaves); ``bind(oracle, params_q)`` rebuilds the oracle with one
    query's slice (see functions.bind_query).  Constrained selection adds
    per-query feasibility states ``cstates`` (leading (Q,) leaves) over
    the shared candidate plane ``cplane``.
    Returns (oracle_states, sol_ids, sol_sizes[, cstates][, GreedyStats])
    batched on Q.
    """
    validate_engine(engine, accept, where="threshold_greedy_batch")
    Q = taus.shape[0]
    if k_dyn is None:
        k_dyn = jnp.full((Q,), k, jnp.int32)
    if constraint is not None and cstates is None:
        cstates = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (Q,) + a.shape),
            constraint.init_state())

    def one(state, sol, size, tau, kq, prm, cst):
        orc = oracle if bind is None else bind(oracle, prm)
        out = threshold_greedy(orc, state, sol, size, cand_feats, cand_ids,
                               cand_valid, tau, k, accept=accept,
                               engine=engine, chunk=chunk, k_dyn=kq,
                               with_stats=True, constraint=constraint,
                               cstate=cst, cplane=cplane)
        if constraint is None:
            return out[:3] + ((),) + out[3:]
        return out

    out_state, out_sol, out_size, out_cst, stats = jax.vmap(one)(
        oracle_states, sol_ids, sol_sizes, taus, k_dyn, bind_params,
        cstates if constraint is not None else ())
    out = (out_state, out_sol, out_size)
    if constraint is not None:
        out = out + (out_cst,)
    if with_stats:
        return out + (stats,)
    return out


def _threshold_greedy_dense(oracle, oracle_state, sol_ids, sol_size,
                            cand_feats, cand_ids, cand_valid, tau, k, k_eff,
                            accept, chunk, constraint=None, cstate=(),
                            cplane=None):
    """Batched engine: one full-block marginals call per accept."""
    aux = oracle.prep(oracle_state, cand_feats)
    C = cand_feats.shape[0]
    order = jnp.arange(C, dtype=jnp.int32)
    tau_row = _row_tau(constraint, tau, cplane)

    def pick(gains, eligible):
        ok = eligible & (gains >= tau_row)
        if accept == "first":
            key = jnp.where(ok, order, C)
            idx = jnp.argmin(key)
        else:
            key = jnp.where(ok, gains, NEG)
            idx = jnp.argmax(key)
        return idx, jnp.any(ok)

    def body(st: GreedyState) -> GreedyState:
        gains = oracle.marginals(st.oracle_state, aux)
        eligible = cand_valid & ~st.taken
        if constraint is not None and cplane is not None:
            eligible = eligible & constraint.eligible(st.cstate, cplane)
        idx, any_ok = pick(gains, eligible)
        accept_now = any_ok & (st.sol_size < k_eff)
        aux_row = jax.tree.map(lambda a: a[idx], aux)
        new_state = oracle.add(st.oracle_state, aux_row)
        oracle_state, sol_ids, sol_size, taken = _apply_accept(
            st, accept_now, new_state, cand_ids[idx], idx, k)
        cstate = _cstate_accept(constraint, st.cstate, cplane, idx,
                                accept_now)
        return GreedyState(oracle_state, sol_ids, sol_size, taken,
                           done=~accept_now, n_evals=st.n_evals + C,
                           n_iters=st.n_iters + 1, cstate=cstate)

    def cond(st: GreedyState):
        return (~st.done) & (st.sol_size < k_eff)

    init = GreedyState(oracle_state, sol_ids, sol_size,
                       taken=jnp.zeros((C,), bool),
                       done=jnp.asarray(False),
                       n_evals=jnp.zeros((), jnp.int32),
                       n_iters=jnp.zeros((), jnp.int32), cstate=cstate)
    out = jax.lax.while_loop(cond, body, init)
    return (out.oracle_state, out.sol_ids, out.sol_size, out.cstate,
            GreedyStats(out.n_evals, out.n_iters))


def _threshold_greedy_lazy(oracle, oracle_state, sol_ids, sol_size,
                           cand_feats, cand_ids, cand_valid, tau, k, k_eff,
                           accept, chunk, constraint=None, cstate=(),
                           cplane=None):
    """Lazy engine: stale-gain upper bounds + chunked on-demand rescoring.

    Invariant: ``g_stale[i] >= fresh_marginal(i)`` at all times.  It starts
    at +inf (trivially valid, maximally lazy) and each rescore tightens it
    to the exact marginal under the then-current solution; submodularity
    guarantees the bound stays valid as the solution grows.  Hence:

      * a candidate with ``g_stale < tau`` can never be accepted (fresh <=
        stale < tau) — it is excluded without an oracle call;
      * exiting when no hot (stale >= tau) candidate remains certifies the
        paper's exit condition: no candidate has fresh marginal >= tau.

    accept="first": ThresholdGreedy with a fixed tau is a single forward
    pass (the paper's own streaming loop): once a candidate's fresh gain is
    seen below tau it can never qualify again, so the scan never moves
    backwards.  Each iteration slices the contiguous chunk starting at the
    first hot candidate, rescores it, and accepts the earliest whose fresh
    gain clears tau.  Every candidate earlier in the stream either was cold
    or was just rescored below tau, so the accepted element is exactly the
    one the dense engine picks — at O(chunk) oracle rows + an O(C) vector
    scan per iteration (no sort, no gather).

    accept="best": each iteration gathers the `chunk` candidates with the
    largest stale bounds and accepts the freshest-best only if it also
    beats every stale bound outside the chunk (the classic lazy-greedy
    certificate), so the accepted element is a true fresh argmax.

    Constrained runs fold monotone feasibility into the hot set (an
    infeasible row can never become feasible again, so excluding it is
    as permanent as a cold stale bound) and compare fresh gains against
    the constraint's per-row threshold.
    """
    C = cand_feats.shape[0]
    B = max(1, min(chunk, C))
    order = jnp.arange(C, dtype=jnp.int32)
    tau_row = _row_tau(constraint, tau, cplane)

    def body(st: LazyState) -> LazyState:
        eligible = cand_valid & ~st.taken & \
            _feasible(constraint, st.cstate, cplane, C)
        hot = eligible & (st.g_stale >= tau_row)
        if accept == "first":
            # contiguous chunk at the scan frontier (first hot index);
            # dynamic_slice clamps near the right edge, which only re-reads
            # rows already proven cold (fresh <= stale < tau, can't match).
            c = jnp.argmax(hot).astype(jnp.int32)
            feats_chunk = jax.lax.dynamic_slice_in_dim(cand_feats, c, B)
            g_chunk = oracle.chunk_marginals(st.oracle_state, feats_chunk)
            base = jnp.minimum(c, C - B)
            idxs = base + jnp.arange(B, dtype=jnp.int32)
            # fresh gains are valid upper bounds for every row going forward
            g_stale = jax.lax.dynamic_update_slice_in_dim(st.g_stale,
                                                          g_chunk, c, axis=0)
            ok = eligible[idxs] & (g_chunk >= _tau_at(tau_row, idxs))
            j = jnp.argmax(ok)                    # earliest qualifying
            found = jnp.any(ok)
        else:
            key = jnp.where(hot, st.g_stale, NEG)
            _, idxs = jax.lax.top_k(key, B)       # B hottest stale bounds
            chunk_ok = hot[idxs]
            feats_chunk = cand_feats[idxs]
            g_chunk = oracle.chunk_marginals(st.oracle_state, feats_chunk)
            g_stale = st.g_stale.at[idxs].set(
                jnp.where(chunk_ok, g_chunk, st.g_stale[idxs]))
            jkey = jnp.where(chunk_ok, g_chunk, NEG)
            j = jnp.argmax(jkey)
            best_fresh = jkey[j]
            tau_j = _tau_at(tau_row, idxs)
            tau_j = tau_j[j] if jnp.ndim(tau_j) else tau_j
            # certificate: the best fresh gain in the chunk dominates every
            # stale bound outside it, hence every fresh gain outside it
            max_rest = jnp.max(key.at[idxs].set(NEG))
            found = chunk_ok[j] & (best_fresh >= tau_j) & \
                (best_fresh >= max_rest)
        idx = idxs[j]
        accept_now = found & (st.sol_size < k_eff)

        # Fetch the accepted row by GLOBAL index from the original array —
        # identical to feats_chunk[j] in both branches (idx = base + j /
        # idxs[j] by construction), but avoids a gather-of-dynamic-slice,
        # which XLA:CPU has been observed to mis-lower inside while_loop
        # (the add consumed a row from the previous iteration's chunk when
        # the scan frontier crossed C - B, leaving stale bounds hot and
        # accepting elements whose fresh marginal was below tau).
        aux_row = jax.tree.map(
            lambda a: a[0], oracle.prep(st.oracle_state,
                                        cand_feats[idx][None]))
        new_state = oracle.add(st.oracle_state, aux_row)
        oracle_state, sol_ids, sol_size, taken = _apply_accept(
            st, accept_now, new_state, cand_ids[idx], idx, k)
        cstate = _cstate_accept(constraint, st.cstate, cplane, idx,
                                accept_now)

        hot_left = cand_valid & ~taken & \
            _feasible(constraint, cstate, cplane, C) & (g_stale >= tau_row)
        return LazyState(oracle_state, sol_ids, sol_size, g_stale, taken,
                         done=~jnp.any(hot_left), n_evals=st.n_evals + B,
                         n_iters=st.n_iters + 1, cstate=cstate)

    def cond(st: LazyState):
        return (~st.done) & (st.sol_size < k_eff)

    init = LazyState(oracle_state, sol_ids, sol_size,
                     g_stale=jnp.full((C,), jnp.inf, jnp.float32),
                     taken=jnp.zeros((C,), bool),
                     done=~jnp.any(cand_valid),
                     n_evals=jnp.zeros((), jnp.int32),
                     n_iters=jnp.zeros((), jnp.int32), cstate=cstate)
    out = jax.lax.while_loop(cond, body, init)
    return (out.oracle_state, out.sol_ids, out.sol_size, out.cstate,
            GreedyStats(out.n_evals, out.n_iters))


def constrained_chunk_accept(oracle, constraint, oracle_state, cstate,
                             feats_chunk, plane_chunk, eligible, tau,
                             budget):
    """Reference constrained accept sweep: Algorithm 1's sequential loop
    over one chunk with a per-row ``admit`` consult, as a lax.scan.

    The fused engine routes through here when the constraint's state
    cannot ride the Pallas kernels' scalar cost carry (fused_mode ==
    "scan", e.g. the partition matroid's per-part count vector — two
    same-part rows in one chunk must see each other's count update).
    Still one while-trip per chunk; only the sweep itself leaves the
    kernel.  Returns (mask (B,) bool, oracle_state, cstate, gains (B,)).
    """
    aux = oracle.prep(oracle_state, feats_chunk)
    B = eligible.shape[0]
    tau_vec = jnp.broadcast_to(_row_tau(constraint, tau, plane_chunk), (B,))

    def step(carry, xs):
        st, cst, n_acc = carry
        ok, aux_row, prow, tr = xs
        gain = oracle.marginals(
            st, jax.tree.map(lambda a: a[None], aux_row))[0]
        feas = constraint.eligible(cst, prow[None])[0]
        acc = ok & feas & (gain >= tr) & (n_acc < budget)
        new_st = oracle.add(st, aux_row)
        st = jax.tree.map(lambda a, b: jnp.where(acc, a, b), new_st, st)
        new_cst = constraint.add(cst, prow)
        cst = jax.tree.map(lambda a, b: jnp.where(acc, a, b), new_cst, cst)
        return (st, cst, n_acc + acc.astype(jnp.int32)), (acc, gain)

    (oracle_state, cstate, _), (mask, gains) = jax.lax.scan(
        step, (oracle_state, cstate, jnp.zeros((), jnp.int32)),
        (eligible, aux, plane_chunk, tau_vec))
    return mask, oracle_state, cstate, gains


def _threshold_greedy_fused(oracle, oracle_state, sol_ids, sol_size,
                            cand_feats, cand_ids, cand_valid, tau, k, k_eff,
                            accept, chunk, constraint=None, cstate=(),
                            cplane=None):
    """Fused engine: the accept loop runs inside ``oracle.chunk_accept``.

    Same stale-gains invariant and scan frontier as the lazy engine
    (accept="first" is a single forward pass), but each while_loop trip
    hands the whole contiguous chunk at the frontier to the oracle's
    chunk_accept sweep, which accepts EVERY qualifying row in stream order
    against the live state — state updates happen in the kernel's VMEM
    scratch (or a lax.scan carry for the reference path), not as one
    tree-wide jnp.where over HBM per accept.  The loop advances one chunk
    per trip instead of one accept, so n_iters drops from ~|G| to
    ~(span of the accept region)/chunk.

    The emitted per-row gains are fresh marginals at scan time — valid
    stale upper bounds forever (submodularity), so the frontier logic is
    unchanged: after a sweep every non-accepted chunk row is provably cold
    (its recorded gain < tau), except rows cut off by the budget, which
    the exit condition (sol_size == k_eff) retires anyway.

    Bit-identity with dense (accept="first"): dense accepts are strictly
    increasing in stream index at fixed tau (a row once seen below tau can
    never re-qualify), and the sweep IS that sequential loop restricted to
    the chunk, so both engines accept the same sequence.
    """
    C = cand_feats.shape[0]
    B = max(1, min(chunk, C))
    arange_b = jnp.arange(B, dtype=jnp.int32)
    tau_row = _row_tau(constraint, tau, cplane)
    fused_mode = "none" if constraint is None else constraint.fused_mode

    def body(st: LazyState) -> LazyState:
        eligible = cand_valid & ~st.taken & \
            _feasible(constraint, st.cstate, cplane, C)
        hot = eligible & (st.g_stale >= tau_row)
        # contiguous chunk at the scan frontier; the dynamic_slice clamp
        # near the right edge only re-reads rows already proven cold or
        # taken (ineligible), which the sweep can never re-accept
        c = jnp.argmax(hot).astype(jnp.int32)
        feats_chunk = jax.lax.dynamic_slice_in_dim(cand_feats, c, B)
        base = jnp.minimum(c, C - B)
        idxs = base + arange_b
        budget = k_eff - st.sol_size
        if fused_mode == "none":
            mask, oracle_state, g_chunk = oracle.chunk_accept(
                st.oracle_state, feats_chunk, eligible[idxs], tau, budget)
            cstate = st.cstate
        elif fused_mode == "cost":
            # per-row costs + remaining budget ride into the sweep kernel;
            # the kernel's carry tracks intra-chunk spend so multi-accept
            # stays on-device (see kernels/_accept_common.py)
            plane_chunk = jax.lax.dynamic_slice_in_dim(cplane, base, B)
            cost_chunk = constraint.fused_cost(plane_chunk)
            mask, oracle_state, g_chunk = oracle.chunk_accept(
                st.oracle_state, feats_chunk, eligible[idxs], tau, budget,
                cost=cost_chunk,
                cost_budget=constraint.fused_cost_budget(st.cstate))
            cstate = constraint.fused_spend(
                st.cstate,
                jnp.sum(jnp.where(mask, cost_chunk, jnp.float32(0.0))))
        else:
            # vector-state constraints (partition matroid): the per-part
            # counts can't ride the kernels' scalar carry, so the sweep
            # runs as the reference scan with a per-row admit consult
            plane_chunk = jax.lax.dynamic_slice_in_dim(cplane, base, B)
            mask, oracle_state, cstate, g_chunk = constrained_chunk_accept(
                oracle, constraint, st.oracle_state, st.cstate, feats_chunk,
                plane_chunk, eligible[idxs], tau, budget)
        mask = mask.astype(bool)
        g_stale = jax.lax.dynamic_update_slice_in_dim(st.g_stale, g_chunk,
                                                      c, axis=0)
        # in-order append of every accepted row; slot k = out-of-bounds
        # sentinel dropped by the scatter (budget keeps real slots < k)
        m32 = mask.astype(jnp.int32)
        slots = jnp.where(mask, st.sol_size + jnp.cumsum(m32) - 1, k)
        sol_ids = st.sol_ids.at[slots].set(cand_ids[idxs], mode="drop")
        sol_size = st.sol_size + jnp.sum(m32)
        taken = st.taken.at[idxs].set(st.taken[idxs] | mask)

        hot_left = cand_valid & ~taken & \
            _feasible(constraint, cstate, cplane, C) & (g_stale >= tau_row)
        return LazyState(oracle_state, sol_ids, sol_size, g_stale, taken,
                         done=~jnp.any(hot_left), n_evals=st.n_evals + B,
                         n_iters=st.n_iters + 1, cstate=cstate)

    def cond(st: LazyState):
        return (~st.done) & (st.sol_size < k_eff)

    init = LazyState(oracle_state, sol_ids, sol_size,
                     g_stale=jnp.full((C,), jnp.inf, jnp.float32),
                     taken=jnp.zeros((C,), bool),
                     done=~jnp.any(cand_valid),
                     n_evals=jnp.zeros((), jnp.int32),
                     n_iters=jnp.zeros((), jnp.int32), cstate=cstate)
    out = jax.lax.while_loop(cond, body, init)
    return (out.oracle_state, out.sol_ids, out.sol_size, out.cstate,
            GreedyStats(out.n_evals, out.n_iters))


@jax.named_scope("filter")
def threshold_filter(oracle, oracle_state, cand_feats, cand_valid, tau,
                     chunk=None):
    """Algorithm 2: keep candidates whose marginal w.r.t. the current
    solution is >= tau.  Returns the survivor mask.

    Marginals route through ``oracle.chunk_marginals`` rather than
    prep+marginals, so a kerneled oracle never materializes the full prep
    aux in HBM (for facility location that aux is the (C, r) similarity
    block — the fused kernel streams it through VMEM tiles instead).
    ``chunk`` optionally bounds the non-kernel path's transient aux too:
    candidates are swept in (chunk, d) tiles via lax.map, exactly like the
    lazy engine's streaming rescore (row-wise identical gains)."""
    if chunk is None:
        gains = oracle.chunk_marginals(oracle_state, cand_feats)
    else:
        C, d = cand_feats.shape
        B = max(1, min(chunk, C))
        T = -(-C // B)
        pad = T * B - C
        tiles = jnp.pad(cand_feats, ((0, pad), (0, 0))).reshape(T, B, d)
        gains = jax.lax.map(
            lambda t: oracle.chunk_marginals(oracle_state, t),
            tiles).reshape(-1)[:C]
    return cand_valid & (gains >= tau)


def exclude_ids(cand_ids, cand_valid, sol_ids):
    """Mask out candidates already selected (by global id)."""
    hit = jnp.any(cand_ids[:, None] == sol_ids[None, :], axis=-1)
    return cand_valid & ~hit


@partial(jax.jit, static_argnums=(3,))
@jax.named_scope("pack")
def pack_by_mask(feats, ids, mask, cap: int, priority=None):
    """Compress masked rows into a fixed-capacity buffer.

    MRC messages are variable-size; XLA buffers are not.  This is the bridge:
    take (up to) ``cap`` masked rows — in stream order, or by descending
    ``priority`` if given (the "O(k) largest elements" of Algorithm 7) — and
    report the overflow count so the paper's whp bounds become runtime checks.

    Returns (feats (cap, d), ids (cap,), valid (cap,), n_dropped ()).

    Selection is a single ``lax.top_k`` on a composite descending key —
    O(n log cap)-ish work instead of the O(n log n) full argsort/lexsort
    this used to run, and top_k's tie rule (equal keys -> lower index
    first) is exactly the stream-order tie-break the MRC messages need.
    Masked rows must sort strictly after every valid row: keying them
    -inf alone would let a valid row whose priority is itself -inf tie
    with (and, earlier in the stream, lose to) a masked row — so valid
    ±inf priorities are clamped to the finite float32 extremes, keeping
    them above every masked key while preserving their order.
    """
    n = ids.shape[0]
    if priority is None:
        # stream order: descending key = -index, masked rows last
        key = jnp.where(mask, -jnp.arange(n, dtype=jnp.float32), -jnp.inf)
    else:
        fmax = jnp.finfo(jnp.float32).max
        p = jnp.clip(priority.astype(jnp.float32), -fmax, fmax)
        key = jnp.where(mask, p, -jnp.inf)
    _, take = jax.lax.top_k(key, min(cap, n))
    valid_sorted = mask[take]
    count = jnp.sum(mask)
    n_dropped = jnp.maximum(count - cap, 0)
    return feats[take], jnp.where(valid_sorted, ids[take], -1), valid_sorted, n_dropped
