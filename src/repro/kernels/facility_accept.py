"""Pallas TPU kernel: fused facility-location chunk-accept sweep.

One kernel = one MXU matmul + the whole ThresholdGreedy inner loop over
the tile: the (B, r) similarity block

    sims = max(cand @ ref.T, 0)

is computed once into VMEM scratch (it never exists in HBM — same
roofline argument as kernels/facility_marginals.py), then the sweep walks
its rows against the live cover vector ``st`` (second VMEM scratch):

    gain_i = sum_j max(sims[i, j] - st_j, 0)
    accept: st = max(st, sims[i, :])        (O(r) elementwise, in scratch)

See kernels/_accept_common.py for the shared sweep and output contract
(accepted-row mask, post-sweep cover vector, per-row fresh gains).

Padding: reference columns pad with state=+inf (residual contributes 0
and max(inf, sims) stays inert); candidate rows pad with eligibility 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import mxu_for
from repro.kernels._accept_common import row_operands, row_specs, run_sweep
from repro.kernels._tiling import ceil_to as _ceil_to
from repro.kernels._tiling import sublane as _sublane
from repro.kernels._tiling import pad_axis as _pad_axis


def _fa_kernel(*refs, nrows, with_cost):
    cand_ref, refT_ref, state_ref, elig_ref, tau_ref, budget_ref = refs[:6]
    base = 6
    cost_ref = cbud_ref = None
    if with_cost:
        cost_ref, cbud_ref = refs[base:base + 2]
        base += 2
    mask_ref, state_out_ref, gains_ref, sims_scratch, st_scratch = refs[base:]
    # MXU: the (B, r) similarity block, rectified, lives only in scratch
    sims = jnp.dot(cand_ref[...], refT_ref[...],
                   preferred_element_type=jnp.float32,
                   precision=mxu_for(cand_ref.dtype))
    sims_scratch[...] = jnp.maximum(sims, 0.0)
    st_scratch[...] = state_ref[...]

    def row(i):
        return sims_scratch[i, :][None, :]

    def step(st, s):
        gain = jnp.sum(jnp.maximum(s - st, 0.0))
        return gain, jnp.maximum(st, s)

    run_sweep(nrows, elig_ref, tau_ref, budget_ref, mask_ref,
              state_out_ref, gains_ref, st_scratch, row, step,
              cost_ref=cost_ref, cbud_ref=cbud_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def facility_accept(cand, ref, state, eligible, tau, budget, *,
                    interpret: bool = False, cost=None, cost_budget=None):
    """(B, d), (r, d), (r,), (B,) bool, (), () -> (mask (B,) bool,
    state (r,) f32, gains (B,) f32) — the facility-location accept sweep."""
    B, d = cand.shape
    r = ref.shape[0]
    Bp, rp = _ceil_to(B, _sublane(cand.dtype)), _ceil_to(r, 128)
    with_cost = cost is not None

    cand_p = _pad_axis(cand, 0, Bp)
    refT_p = _pad_axis(ref.T, 1, rp)                        # (d, rp)
    state_p = _pad_axis(state.astype(jnp.float32), 0, rp,
                        value=jnp.inf)[None, :]             # (1, rp)
    row_ops = row_operands(Bp, eligible, tau, budget, cost, cost_budget)

    mask, state_out, gains = pl.pallas_call(
        functools.partial(_fa_kernel, nrows=Bp, with_cost=with_cost),
        grid=(1,),
        in_specs=[
            pl.BlockSpec((Bp, d), lambda i: (0, 0)),
            pl.BlockSpec((d, rp), lambda i: (0, 0)),
            pl.BlockSpec((1, rp), lambda i: (0, 0)),
            *row_specs(Bp, with_cost),
        ],
        out_specs=[
            pl.BlockSpec((1, Bp), lambda i: (0, 0)),
            pl.BlockSpec((1, rp), lambda i: (0, 0)),
            pl.BlockSpec((1, Bp), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Bp), jnp.int32),
            jax.ShapeDtypeStruct((1, rp), jnp.float32),
            jax.ShapeDtypeStruct((1, Bp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((Bp, rp), jnp.float32),
            pltpu.VMEM((1, rp), jnp.float32),
        ],
        interpret=interpret,
    )(cand_p, refT_p, state_p, *row_ops)
    return mask[0, :B] != 0, state_out[0, :r], gains[0, :B]
