"""Pallas TPU kernel: fused FeatureCoverage chunk-accept sweep.

Runs the ThresholdGreedy inner loop over a (B, d) candidate tile inside
ONE kernel: row i's marginal

    gain_i = sum_f w_f * ( sqrt(st_f + x_{i,f}) - sqrt(st_f) )

is computed against the live accumulator ``st`` held in VMEM scratch; an
accepted row applies the O(d) elementwise update ``st += x_i`` in scratch
and the sweep continues — the dense engine's one-kernel-launch-per-accept
(plus a tree-wide jnp.where over the state in HBM) collapses into a
single launch per *chunk*.  Outputs: accepted-row mask, post-sweep state,
and each row's fresh gain at scan time (stale upper bounds for the lazy
buffer) — see kernels/_accept_common.py for the shared sweep.

Padding: x/state pad with 0 (padded features contribute sqrt(0+0) -
sqrt(0) = 0 and stay 0 under the additive update); eligibility pads with
0 so padded rows never accept.

Many lanes, one sweep.  The threshold grid runs the fused engine as a vmap
over lanes (the 37 guesses, times the queries of a served batch), and
Pallas batches a vmap by an outer grid axis, one serial sweep per lane.
``coverage_accept_lanes`` sweeps eight lanes a grid step, one per sublane
(``_accept_common.run_lane_sweep``), with each lane's arithmetic the
one-lane kernel's in the same order; ``routed`` picks it from what a vmap
batches.  The lane batch stays lane-major in HBM: a row block of eight
lanes is copied in VMEM into (dp / 128) column tiles of (8 * rows, 128),
lane after lane, so row i of the eight lanes is one sublane-strided load
per column tile.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._accept_common import LANE_GROUP
from repro.kernels._accept_common import accept_call
from repro.kernels._accept_common import run_lane_sweep
from repro.kernels._tiling import LANES
from repro.kernels._tiling import all_batched as _all_batched
from repro.kernels._tiling import ceil_to as _ceil_to
from repro.kernels._tiling import pad_axis as _pad_axis
from repro.kernels._tiling import pallas_vmap as _pallas_vmap
from repro.kernels._tiling import sublane as _sublane
from repro.kernels._tiling import unbatch as _unbatch

# Candidate rows a grid step of the many-lane sweep copies in: eight lanes
# of 32 rows at d = 3,072 are a 3 MiB f32 block, so the double-buffered
# input and its column-tile copy fit the default scoped VMEM.
LANE_ROWS = 32


@functools.partial(jax.jit, static_argnames=("interpret",))
def coverage_accept(x, state, weights, eligible, tau, budget, *,
                    interpret: bool = False, cost=None, cost_budget=None):
    """(B, d), (d,)[, (d,)], (B,) bool, (), () -> (mask (B,) bool,
    state (d,) f32, gains (B,) f32) — the FeatureCoverage accept sweep."""
    d = x.shape[1]
    w = weights if weights is not None else jnp.ones((d,), jnp.float32)

    def step_from(w_ref):
        def step(st, x_row):
            gain = jnp.sum((jnp.sqrt(st + x_row) - jnp.sqrt(st)) * w_ref[...])
            return gain, st + x_row
        return step

    return accept_call(step_from, x, state, [w], eligible, tau, budget,
                       interpret=interpret, cost=cost,
                       cost_budget=cost_budget)


def _lanes_kernel(x_ref, st_ref, w_ref, elig_ref, tau_ref, budget_ref,
                  mask_ref, st_out_ref, gains_ref, st_scr, n_scr, cols_scr,
                  *, bs: int):
    """One grid step: rows ``r * bs ..`` of the group's eight lanes."""
    G = LANE_GROUP
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        st_scr[...] = st_ref[...]
        n_scr[...] = jnp.zeros_like(n_scr)
        mask_ref[...] = jnp.zeros_like(mask_ref)
        gains_ref[...] = jnp.zeros_like(gains_ref)

    n_cols, height, _ = cols_scr.shape
    for c in range(n_cols):
        tile = x_ref[..., c * LANES:(c + 1) * LANES].astype(jnp.float32)
        cols_scr[c] = tile.reshape(height, LANES)

    def rows(i):
        tiles = [cols_scr[c, pl.ds(i, G, stride=bs), :]
                 for c in range(n_cols)]
        return jnp.concatenate(tiles, axis=1)

    def step(st, x_rows):
        gain = jnp.sum((jnp.sqrt(st + x_rows) - jnp.sqrt(st)) * w_ref[...],
                       axis=-1, keepdims=True)
        return gain, st + x_rows

    run_lane_sweep(r * bs, bs, elig_ref, tau_ref, budget_ref, mask_ref,
                   gains_ref, st_scr, n_scr, rows, step)
    st_out_ref[...] = st_scr[...]


def _lane_rows(Bp: int, sub: int) -> int:
    """The largest multiple of ``sub`` up to LANE_ROWS that divides Bp."""
    return max(b for b in range(sub, max(LANE_ROWS, sub) + 1, sub)
               if Bp % b == 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def coverage_accept_lanes(x, states, weights, eligible, tau, budget, *,
                          interpret: bool = False):
    """(L, B, d), (L, d)[, (d,)], (L, B) bool, (L,), (L,)
    -> (mask (L, B) bool, state (L, d) f32, gains (L, B) f32): lane l is
    ``coverage_accept(x[l], states[l], weights, eligible[l], tau[l],
    budget[l])``, bit for bit, with eight lanes swept a grid step.

    The lane axis pads to the group of eight in the small operands only:
    a ragged last group reads its real lanes' rows, and its pad lanes have
    no eligible row, so they never accept and slice off."""
    L, B, d = x.shape
    G = LANE_GROUP
    sub = _sublane(x.dtype)
    Bp, dp, Lp = _ceil_to(B, sub), _ceil_to(d, LANES), _ceil_to(L, G)
    bs = _lane_rows(Bp, sub)
    w = weights if weights is not None else jnp.ones((d,), jnp.float32)

    x_p = _pad_axis(_pad_axis(x, 1, Bp), 2, dp)

    def lanes_p(a, dtype, cols=None):
        a = _pad_axis(a.astype(dtype), 0, Lp)
        return a if cols is None else _pad_axis(a, 1, cols)

    operands = [x_p, lanes_p(states, jnp.float32, dp),
                _pad_axis(w.astype(jnp.float32), 0, dp)[None, :],
                lanes_p(eligible, jnp.int32, Bp),
                lanes_p(tau.reshape(L, 1), jnp.float32),
                lanes_p(budget.reshape(L, 1), jnp.int32)]
    lane_row = pl.BlockSpec((G, Bp), lambda g, r: (g, 0))
    lane_col = pl.BlockSpec((G, 1), lambda g, r: (g, 0))
    lane_state = pl.BlockSpec((G, dp), lambda g, r: (g, 0))

    mask, state_out, gains = pl.pallas_call(
        functools.partial(_lanes_kernel, bs=bs),
        grid=(Lp // G, Bp // bs),
        in_specs=[pl.BlockSpec((G, bs, dp), lambda g, r: (g, r, 0)),
                  lane_state,
                  pl.BlockSpec((1, dp), lambda g, r: (0, 0)),
                  lane_row, lane_col, lane_col],
        out_specs=[lane_row, lane_state, lane_row],
        out_shape=[jax.ShapeDtypeStruct((Lp, Bp), jnp.int32),
                   jax.ShapeDtypeStruct((Lp, dp), jnp.float32),
                   jax.ShapeDtypeStruct((Lp, Bp), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((G, dp), jnp.float32),
            pltpu.VMEM((G, 1), jnp.int32),
            pltpu.VMEM((dp // LANES, G * bs, LANES), jnp.float32)],
        interpret=interpret,
        name="coverage_accept",
    )(*operands)
    return mask[:L, :B] != 0, state_out[:L, :d], gains[:L, :B]


# ---------------------------------------------------------------------------
# routing under vmap
# ---------------------------------------------------------------------------

_STATS = collections.Counter()


def lane_stats() -> dict:
    """Trace-time counts since the process started: ``fused_calls``
    vmapped accepts traced onto the many-lane sweep (a vmapped
    ``while_loop`` traces its body more than once) and ``fused_lanes``
    lanes they fold (summed), ``per_lane_calls`` vmaps left to Pallas's
    own batching (a knapsack sweep, or weights that differ along the
    axis)."""
    return {k: _STATS[k]
            for k in ("fused_calls", "fused_lanes", "per_lane_calls")}


def _fold(a, batched: bool, n: int):
    """(n, L, ...) -> (n * L, ...); an operand the outer axis does not
    batch is broadcast along it first."""
    if not batched:
        a = jnp.broadcast_to(a, (n, *a.shape))
    return a.reshape(n * a.shape[1], *a.shape[2:])


@functools.cache
def routed(interpret: bool):
    """``coverage_accept`` whose vmap over the lanes (the state, and the
    candidates, eligibility, tau and budget batched or broadcast; the
    weights shared; no knapsack cost) runs ``coverage_accept_lanes`` once
    for the whole axis; nested vmaps fold into its lane axis.  A knapsack
    sweep or batched weights keep Pallas's own batching, an outer grid
    axis.  An axis of size 1 is no axis.

    The state is broadcast too where the vmap leaves it unbatched: a
    vmapped ``while_loop`` first traces its body with the initial carry's
    batching (an empty solution is unbatched) to find which carries the
    body batches, and that probe takes the same route."""
    def one(x, state, weights, eligible, tau, budget, cost, cost_budget):
        return coverage_accept(x, state, weights, eligible, tau, budget,
                               interpret=interpret, cost=cost,
                               cost_budget=cost_budget)

    many = functools.partial(coverage_accept_lanes, interpret=interpret)

    @jax.custom_batching.custom_vmap
    def lanes(x, states, weights, eligible, tau, budget):
        return many(x, states, weights, eligible, tau, budget)

    @lanes.def_vmap
    def _lanes_rule(axis_size, in_batched, x, states, weights, eligible,
                    tau, budget):
        args = (x, states, weights, eligible, tau, budget)
        if axis_size == 1:
            return _unbatch(lanes, in_batched, args)
        if in_batched[2]:
            return _pallas_vmap(many, in_batched, args)
        n, L = axis_size, states.shape[-2]
        _STATS["fused_lanes"] += (n - 1) * L
        x, states, eligible, tau, budget = [
            _fold(a, b, n) for a, b in zip(
                (x, states, eligible, tau, budget),
                (in_batched[0], in_batched[1], *in_batched[3:]))]
        mask, st, gains = lanes(x, states, weights, eligible, tau, budget)
        out = (mask.reshape(n, L, -1), st.reshape(n, L, -1),
               gains.reshape(n, L, -1))
        return out, _all_batched(out)

    accept = jax.custom_batching.custom_vmap(one)

    @accept.def_vmap
    def _one_rule(axis_size, in_batched, x, state, weights, eligible, tau,
                  budget, cost, cost_budget):
        args = (x, state, weights, eligible, tau, budget, cost, cost_budget)
        if axis_size == 1:
            return _unbatch(accept, in_batched, args)
        if not in_batched[2] and cost is None:
            n = axis_size
            _STATS["fused_calls"] += 1
            _STATS["fused_lanes"] += n

            def lane(a, b, dtype):
                a = jnp.asarray(a, dtype)
                return a if b else jnp.broadcast_to(a, (n, *a.shape))

            out = lanes(lane(x, in_batched[0], x.dtype),
                        lane(state, in_batched[1], jnp.float32), weights,
                        lane(eligible, in_batched[3], jnp.bool_),
                        lane(tau, in_batched[4], jnp.float32),
                        lane(budget, in_batched[5], jnp.int32))
            return out, _all_batched(out)
        _STATS["per_lane_calls"] += 1
        return _pallas_vmap(one, in_batched, args)

    return accept
