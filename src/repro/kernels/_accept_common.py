"""Shared row-sweep skeleton for the fused chunk-accept kernels.

A chunk-accept kernel runs the ThresholdGreedy inner loop *inside* one
``pallas_call``: it sweeps a (B, d) candidate tile row by row, computing
each row's marginal against the live oracle state held in VMEM scratch,
accepting the row (state update in scratch, no HBM round-trip) whenever
the gain clears tau and budget remains, and emitting

    mask  (1, B) int32 — 1 where the row was accepted, in stream order
    state (1, dp) f32  — the post-sweep oracle state
    gains (1, B) f32   — each row's fresh marginal *at the moment it was
                         scanned* (a valid stale upper bound forever, by
                         submodularity — the engine feeds these straight
                         into its stale-gains buffer)

This is exactly the paper's Algorithm-1 accept loop restricted to the
tile, so the accepted sequence is bit-identical to what the dense engine
produces one full-block rescore at a time (accept="first").

The sweep is shared; each oracle kernel supplies two callbacks working on
(1, dp)-shaped f32 VMEM blocks:

    row_fn(i)        -> the i-th candidate row (features, or a
                        precomputed similarity row held in scratch)
    step_fn(st, row) -> (gain (), new_state (1, dp))

Every per-row vector (eligibility, costs, mask, gains) is a lane-dense
(1, B) row: eligibility is selected per row with a masked reduce (no
dynamic scalar loads); tau/budget arrive as (1, 1) blocks (SMEM-shaped
scalars).  Per-row outputs are kept in loop-carried rows and written once
at the end — no dynamic vector stores.  Candidate rows are read with a
dynamic sublane index, which the TPU compiler only accepts on an unpacked
32-bit tile, so a bf16 tile is upcast once into f32 scratch
(:func:`f32_rows`).

Many lanes at once.  A vmap over threshold lanes hands the sweep L
independent (candidates, state, tau, budget) problems.
:func:`run_lane_sweep` sweeps :data:`LANE_GROUP` of them together, one
lane per f32 sublane: row i of all eight lanes is one (8, dp) tile, each
lane's gain is a per-sublane sum, and the accept test, the state update
and the per-row outputs are per-sublane selects, so no value goes to the
scalar unit and the eight dependency chains do not wait on one another.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._tiling import ceil_to as _ceil_to
from repro.kernels._tiling import sublane as _sublane
from repro.kernels._tiling import pad_axis as _pad_axis


def run_sweep(nrows: int, elig_ref, tau_ref, budget_ref, mask_ref,
              state_out_ref, gains_ref, st_scratch, row_fn, step_fn,
              cost_ref=None, cbud_ref=None):
    """The sequential accept sweep.  ``st_scratch`` must already hold the
    incoming oracle state; on return it (and ``state_out_ref``) hold the
    post-sweep state.

    ``cost_ref`` / ``cbud_ref`` (both given or both None — a compile-time
    branch) add knapsack cost-ratio semantics: a row with cost c accepts
    only when gain >= tau * c AND the running spend + c stays within the
    (1, 1) remaining-budget scalar.  The cost=None lowering is exactly
    the pre-knapsack sweep."""
    B = nrows
    tau = tau_ref[0, 0]
    budget = budget_ref[0, 0]
    elig = elig_ref[...]                                   # (1, B) int32
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)
    if cost_ref is not None:
        cost = cost_ref[...]                               # (1, B) f32
        cbud = cbud_ref[0, 0]

    def body(i, carry):
        if cost_ref is None:
            n_acc, mask, gains = carry
        else:
            n_acc, spent, mask, gains = carry
        row = row_fn(i)                                    # (1, dp)
        st = st_scratch[...]
        gain, new_st = step_fn(st, row)
        here = row_iota == i
        ok = jnp.sum(jnp.where(here, elig, 0)) > 0         # elig[i], masked
        if cost_ref is None:
            acc = ok & (gain >= tau) & (n_acc < budget)
        else:
            ci = jnp.sum(jnp.where(here, cost, 0.0))       # cost[i], masked
            acc = ok & (gain >= tau * ci) & (n_acc < budget) \
                & (spent + ci <= cbud)

        @pl.when(acc)
        def _accept():
            st_scratch[...] = new_st

        mask = jnp.where(here, acc.astype(jnp.int32), mask)
        gains = jnp.where(here, gain, gains)
        if cost_ref is None:
            return n_acc + acc.astype(jnp.int32), mask, gains
        spent = spent + jnp.where(acc, ci, jnp.float32(0.0))
        return n_acc + acc.astype(jnp.int32), spent, mask, gains

    init = (jnp.zeros((), jnp.int32),
            jnp.zeros((1, B), jnp.int32),
            jnp.zeros((1, B), jnp.float32))
    if cost_ref is not None:
        init = (init[0], jnp.zeros((), jnp.float32), init[1], init[2])
    out = jax.lax.fori_loop(0, B, body, init)
    mask, gains = out[-2], out[-1]
    mask_ref[...] = mask
    gains_ref[...] = gains
    state_out_ref[...] = st_scratch[...]


#: lanes a many-lane sweep carries at once, one per f32 sublane
LANE_GROUP = 8


def run_lane_sweep(row0, nrows: int, elig_ref, tau_ref, budget_ref,
                   mask_ref, gains_ref, st_scratch, n_scratch, row_fn,
                   step_fn):
    """Rows ``row0 .. row0 + nrows - 1`` (``row0`` may be traced) of the
    accept sweep for :data:`LANE_GROUP` lanes at once, one lane per
    sublane.

    The carry lives in refs, so a sweep may run in row blocks: the
    (G, dp) states in ``st_scratch``, the (G, 1) accept counts in
    ``n_scratch``, and the (G, Bp) mask and gains rows in ``mask_ref`` /
    ``gains_ref``, all zeroed or loaded by the caller before the first
    block.  ``elig_ref`` is (G, Bp) int32, ``tau_ref`` (G, 1) f32 and
    ``budget_ref`` (G, 1) int32, a lane's values on its sublane.

        row_fn(i)         -> the (G, dp) f32 rows at block offset i
        step_fn(st, rows) -> (gain (G, 1), new_state (G, dp))

    Each sublane runs :func:`run_sweep`'s cardinality sweep (no knapsack
    carry): the same gain, the same accept test, the same update."""
    Bp = mask_ref.shape[1]
    tau = tau_ref[...]
    budget = budget_ref[...]
    elig = elig_ref[...]
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (1, Bp), 1)

    def body(i, carry):
        n_acc, mask, gains = carry
        rows = row_fn(i)
        st = st_scratch[...]
        gain, new_st = step_fn(st, rows)
        here = row_iota == row0 + i
        ok = jnp.sum(jnp.where(here, elig, 0), axis=1, keepdims=True) > 0
        acc = ok & (gain >= tau) & (n_acc < budget)          # (G, 1)
        st_scratch[...] = jnp.where(acc, new_st, st)
        mask = jnp.where(here, acc.astype(jnp.int32), mask)
        gains = jnp.where(here, gain, gains)
        return n_acc + acc.astype(jnp.int32), mask, gains

    n_acc, mask, gains = jax.lax.fori_loop(
        0, nrows, body, (n_scratch[...], mask_ref[...], gains_ref[...]))
    n_scratch[...] = n_acc
    mask_ref[...] = mask
    gains_ref[...] = gains


def row_operands(n: int, eligible, tau, budget, cost=None,
                 cost_budget=None):
    """The trailing per-row operands in :func:`row_specs` order; rows pad
    with eligibility 0 (never accepted) and cost 0."""
    ops = [_pad_axis(eligible.astype(jnp.int32), 0, n)[None, :],
           jnp.asarray(tau, jnp.float32).reshape(1, 1),
           jnp.asarray(budget, jnp.int32).reshape(1, 1)]
    if cost is not None:
        ops += [_pad_axis(cost.astype(jnp.float32), 0, n)[None, :],
                jnp.asarray(cost_budget, jnp.float32).reshape(1, 1)]
    return ops


def row_specs(n: int, with_cost: bool):
    """BlockSpecs of the trailing per-row operands: eligibility (1, n),
    tau (1, 1), budget (1, 1), and for knapsack sweeps cost (1, n) plus
    the remaining budget (1, 1)."""
    vec = pl.BlockSpec((1, n), lambda i: (0, 0))
    one = pl.BlockSpec((1, 1), lambda i: (0, 0))
    return [vec, one, one] + ([vec, one] if with_cost else [])


def f32_rows(x_ref, upcast):
    """The candidate tile as f32 rows that a dynamic row index may read:
    ``x_ref`` itself when ``upcast`` — the scratch refs
    :func:`upcast_scratch` asked for — is empty, else the (Bp, d) f32
    scratch after one upcast copy of the tile."""
    if not upcast:
        return x_ref
    (scratch,) = upcast
    scratch[...] = x_ref[...].astype(jnp.float32)
    return scratch


def upcast_scratch(x):
    """The f32 scratch :func:`f32_rows` needs for a non-f32 tile ``x``."""
    if x.dtype == jnp.float32:
        return []
    return [pltpu.VMEM(x.shape, jnp.float32)]


def accept_call(step_from, x, state, extras, eligible, tau, budget, *,
                interpret: bool, cost=None, cost_budget=None):
    """Shared ``pallas_call`` plumbing for the elementwise-state accept
    kernels (state and every extra operand are (d,)-broadcast rows, all
    zero-padded — each oracle's gain/update contributes exactly 0 on
    zero-padded feature columns; facility location, whose state pads with
    +inf, rolls its own call in kernels/facility_accept.py).

    ``extras`` are (d,) operands (weights / caps / totals);
    ``step_from(*extra_refs)`` builds the ``step_fn(st, x)`` callback for
    :func:`run_sweep`.

    ``cost``/``cost_budget`` (optional, both or neither) append a (B,)
    per-row cost operand + (1, 1) remaining-budget scalar and switch
    :func:`run_sweep` to knapsack cost-ratio accepts.  With cost=None the
    pallas_call is built EXACTLY as before — the cardinality path's
    lowering (and therefore its bits) cannot drift.

    Returns ``(mask (B,) bool, state (d,) f32, gains (B,) f32)``.
    """
    B, d = x.shape
    Bp, dp = _ceil_to(B, _sublane(x.dtype)), _ceil_to(d, 128)
    n_extras = len(extras)
    with_cost = cost is not None

    x_p = _pad_axis(_pad_axis(x, 0, Bp), 1, dp)
    state_p = _pad_axis(state.astype(jnp.float32), 0, dp)[None, :]
    extras_p = [_pad_axis(e.astype(jnp.float32), 0, dp)[None, :]
                for e in extras]
    row_ops = row_operands(Bp, eligible, tau, budget, cost, cost_budget)
    upcast = upcast_scratch(x_p)

    def kernel(*refs):
        x_ref, state_ref = refs[0], refs[1]
        extra_refs = refs[2:2 + n_extras]
        elig_ref, tau_ref, budget_ref = refs[2 + n_extras:5 + n_extras]
        base = 5 + n_extras
        cost_ref = cbud_ref = None
        if with_cost:
            cost_ref, cbud_ref = refs[base:base + 2]
            base += 2
        mask_ref, state_out_ref, gains_ref, st_scratch = refs[base:base + 4]
        st_scratch[...] = state_ref[...]
        rows = f32_rows(x_ref, refs[base + 4:])

        def row(i):
            return rows[i, :][None, :]

        run_sweep(Bp, elig_ref, tau_ref, budget_ref, mask_ref,
                  state_out_ref, gains_ref, st_scratch, row,
                  step_from(*extra_refs),
                  cost_ref=cost_ref, cbud_ref=cbud_ref)

    mask, state_out, gains = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((Bp, dp), lambda i: (0, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
            *[pl.BlockSpec((1, dp), lambda i: (0, 0))] * n_extras,
            *row_specs(Bp, with_cost),
        ],
        out_specs=[
            pl.BlockSpec((1, Bp), lambda i: (0, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
            pl.BlockSpec((1, Bp), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Bp), jnp.int32),
            jax.ShapeDtypeStruct((1, dp), jnp.float32),
            jax.ShapeDtypeStruct((1, Bp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, dp), jnp.float32), *upcast],
        interpret=interpret,
    )(x_p, state_p, *extras_p, *row_ops)
    return mask[0, :B] != 0, state_out[0, :d], gains[0, :B]
