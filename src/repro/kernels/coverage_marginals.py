"""Pallas TPU kernel: FeatureCoverage marginal gains.

    gains[i] = sum_f w_f * ( sqrt(state_f + x_{i,f}) - sqrt(state_f) )

This is the other oracle hot spot of the selection engine (the default
data-curation oracle is FeatureCoverage).  The op is memory-bound
(~3 FLOPs per 4 bytes), so the kernel's job is streaming (bc, bf) tiles at
full HBM bandwidth while keeping the broadcast `state + x` and both sqrt
intermediates in VMEM/VREGs instead of HBM — the XLA path materializes
`sqrt(state[None,:] + x)` as a full (C, d) f32 buffer.

Grid: (C/bc, d/bf); the f axis accumulates into the (1, bc) output row block
(init at f-block 0).  Padding: x pads with 0 and state with 0, so padded
features contribute sqrt(0+0)-sqrt(0) = 0 exactly.

Many states, one block.  The threshold-grid filter asks for the gains of
one candidate block under J states at once (one per threshold lane, times
the queries of a served batch).  Pallas batches a vmap over ``state`` by an
outer grid axis whose x tiles ignore the lane index, so it streams the
block from HBM once per lane.  ``coverage_marginals_lanes`` reads each
(bc, bf) x tile once and loops over the L lane states while the tile sits
in VMEM: (C, d), (L, d) -> (L, C), each lane's row summed exactly as the
one-lane kernel sums it (same tile shape, same f-block order), so the
gains are bit-identical.  ``routed`` picks it from what a vmap batches.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._tiling import ceil_to as _ceil_to
from repro.kernels._tiling import gains_out as _gains_out
from repro.kernels._tiling import row_block as _row_block
from repro.kernels._tiling import pad_axis as _pad_axis
from repro.kernels._tiling import sublane as _sublane

DEFAULT_BC = 256
DEFAULT_BF = 512
# Row block of the many-lane kernel.  37 lanes over 262,144 x 3,072 f32 on
# one TPU v5e: 77 ms at 512 rows, 76 at 1,024 and 2,048 (which need a
# larger scoped-VMEM limit); 512 fits the default.  The f block stays at
# DEFAULT_BF: it sets the order of each row's sum.
LANES_BC = 512


def _cov_kernel(x_ref, state_ref, w_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    st = state_ref[...]                                  # (1, bf) f32
    x = x_ref[...].astype(jnp.float32)                   # (bc, bf)
    gain = jnp.sqrt(st + x) - jnp.sqrt(st)
    gain = gain * w_ref[...]
    out_ref[...] += jnp.sum(gain, axis=-1)[None, :]


def _tiles(x, block_c, block_f):
    C, d = x.shape
    bc, Cp = _row_block(C, block_c, x.dtype)
    bf = min(block_f, _ceil_to(d, 128))
    return bc, Cp, bf, _ceil_to(d, bf)


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_f", "interpret"))
def coverage_marginals(x, state, weights=None, *, block_c: int = DEFAULT_BC,
                       block_f: int = DEFAULT_BF, interpret: bool = False):
    """(C, d), (d,)[, (d,)] -> (C,) f32 FeatureCoverage marginal gains."""
    C, d = x.shape
    bc, Cp, bf, dp = _tiles(x, block_c, block_f)

    x_p = _pad_axis(_pad_axis(x, 0, Cp), 1, dp)
    state_p = _pad_axis(state.astype(jnp.float32), 0, dp)[None, :]
    w = weights if weights is not None else jnp.ones((d,), jnp.float32)
    w_p = _pad_axis(w.astype(jnp.float32), 0, dp)[None, :]

    out_spec, out_shape = _gains_out(bc, Cp)
    grid = (Cp // bc, dp // bf)
    out = pl.pallas_call(
        _cov_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, bf), lambda i, j: (i, j)),
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(x_p, state_p, w_p)
    return out[0, :C]


def _lanes_kernel(x_ref, st_ref, *refs, lanes: int, weighted: bool):
    """Lanes in groups of 8 (one f32 sublane tile): each group's row sums
    are stacked and added to the output in one aligned (8, bc) store; the
    ragged tail group computes its real lanes only."""
    w_ref, out_ref = refs if weighted else (None, refs[0])
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def row_sums(sts, n):
        x = x_ref[...].astype(jnp.float32)               # (bc, bf)
        rows = []
        for i in range(n):
            st = sts[i:i + 1, :]                         # (1, bf) f32
            gain = jnp.sqrt(st + x) - jnp.sqrt(st)
            if weighted:
                gain = gain * w_ref[...]
            rows.append(jnp.sum(gain, axis=-1)[None, :])
        return rows

    G = _sublane(jnp.float32)
    full, rem = divmod(lanes, G)

    def group(g, carry):
        base = pl.multiple_of(g * G, G)
        rows = row_sums(st_ref[pl.ds(base, G), :], G)
        out_ref[pl.ds(base, G), :] += jnp.concatenate(rows, axis=0)
        return carry

    jax.lax.fori_loop(0, full, group, 0)
    if rem:
        rows = row_sums(st_ref[full * G:, :], rem)
        rows.append(jnp.zeros((G - rem, out_ref.shape[1]), jnp.float32))
        out_ref[full * G:, :] += jnp.concatenate(rows, axis=0)


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_f", "interpret"))
def coverage_marginals_lanes(x, states, weights=None, *,
                             block_c: int = LANES_BC,
                             block_f: int = DEFAULT_BF,
                             interpret: bool = False):
    """(C, d), (L, d)[, (d,)] -> (L, C) f32: row l is
    ``coverage_marginals(x, states[l], weights)``, bit for bit at the same
    ``block_f``, from one pass over ``x``.  The lane axis pads to the f32
    sublane multiple; padded lanes are never computed and slice off."""
    C, d = x.shape
    L = states.shape[0]
    bc, Cp, bf, dp = _tiles(x, block_c, block_f)
    Lp = _ceil_to(L, _sublane(jnp.float32))

    x_p = _pad_axis(_pad_axis(x, 0, Cp), 1, dp)
    st_p = _pad_axis(_pad_axis(states.astype(jnp.float32), 0, Lp), 1, dp)
    operands = [x_p, st_p]
    in_specs = [pl.BlockSpec((bc, bf), lambda i, j: (i, j)),
                pl.BlockSpec((Lp, bf), lambda i, j: (0, j))]
    if weights is not None:
        operands.append(_pad_axis(weights.astype(jnp.float32), 0, dp)[None])
        in_specs.append(pl.BlockSpec((1, bf), lambda i, j: (0, j)))

    out = pl.pallas_call(
        functools.partial(_lanes_kernel, lanes=L,
                          weighted=weights is not None),
        grid=(Cp // bc, dp // bf),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((Lp, bc), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((Lp, Cp), jnp.float32),
        interpret=interpret,
        name="coverage_marginals",
    )(*operands)
    return out[:L, :C]


# ---------------------------------------------------------------------------
# routing under vmap
# ---------------------------------------------------------------------------

_STATS = collections.Counter()


def lane_stats() -> dict:
    """Trace-time counts since the process started: ``fused_calls``
    programs built on the many-lane kernel and ``fused_lanes`` lanes they
    fold (summed), ``per_lane_calls`` vmaps left to Pallas's own batching
    (the candidate block or the weights differ along the axis)."""
    return {k: _STATS[k]
            for k in ("fused_calls", "fused_lanes", "per_lane_calls")}


def _unbatch(fn, in_batched, args):
    """Call ``fn`` on an axis of size 1 as on no axis."""
    args = [a[0] if b else a for a, b in zip(args, in_batched)]
    return fn(*args)[None], True


def _pallas_vmap(fn, in_batched, args):
    axes = [0 if b else None for b in in_batched]
    return jax.vmap(fn, in_axes=axes)(*args), True


@functools.cache
def routed(block_c: int, block_f: int, interpret: bool):
    """``coverage_marginals`` whose vmap over ``state`` alone (the
    candidate block and the weights shared) runs the many-lane kernel
    once for the whole axis; nested vmaps over states alone fold into its
    lane axis.  A vmap that batches ``x`` or ``weights`` keeps Pallas's
    own batching, an outer grid axis.  An axis of size 1 is no axis."""
    one = functools.partial(coverage_marginals, block_c=block_c,
                            block_f=block_f, interpret=interpret)
    many = functools.partial(coverage_marginals_lanes, block_f=block_f,
                             interpret=interpret)

    @jax.custom_batching.custom_vmap
    def lanes(x, states, weights):
        return many(x, states, weights)

    @lanes.def_vmap
    def _lanes_rule(axis_size, in_batched, x, states, weights):
        args = (x, states, weights)
        if axis_size == 1:
            return _unbatch(lanes, in_batched, args)
        if in_batched[1] and not in_batched[0] and not in_batched[2]:
            B, L, d = states.shape
            _STATS["fused_lanes"] += (B - 1) * L
            out = lanes(x, states.reshape(B * L, d), weights)
            return out.reshape(B, L, -1), True
        return _pallas_vmap(many, in_batched, args)

    @jax.custom_batching.custom_vmap
    def marginals(x, state, weights):
        return one(x, state, weights)

    @marginals.def_vmap
    def _one_rule(axis_size, in_batched, x, state, weights):
        args = (x, state, weights)
        if axis_size == 1:
            return _unbatch(marginals, in_batched, args)
        if in_batched[1] and not in_batched[0] and not in_batched[2]:
            _STATS["fused_calls"] += 1
            _STATS["fused_lanes"] += axis_size
            return lanes(x, state, weights), True
        _STATS["per_lane_calls"] += 1
        return _pallas_vmap(one, in_batched, args)

    return marginals
