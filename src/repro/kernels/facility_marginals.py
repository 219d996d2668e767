"""Pallas TPU kernel: fused facility-location marginal gains.

    gains[i] = sum_j max( max(<cand_i, ref_j>, 0) - state[j], 0 )

This is the oracle hot spot of ThresholdGreedy/ThresholdFilter (DESIGN.md
§2): every greedy iteration and every filter round scores a whole candidate
block against the current cover vector.  The naive path materializes the
(C, r) similarity matrix in HBM (prep) and re-reads it every iteration; the
fused kernel streams (bc, bd)x(br, bd) tiles through VMEM, feeds the MXU,
rectifies in VREGs and reduces to a (bc,) partial — the (C, r) intermediate
never leaves VMEM.

Arithmetic intensity: 2*C*r*d FLOPs over (C*d + r*d + C*r) * 4 bytes of HBM
traffic naive vs (C*d + r*d) fused — for C=r=4096, d=256 that moves the op
from ~1 FLOP/B (memory-bound) to ~250 FLOP/B (MXU-bound), i.e. the kernel
turns a bandwidth problem into a compute problem, which is the right trade
on a 197 TFLOP/s : 819 GB/s chip (ridge ~240 FLOP/B).

Grid: (C/bc, r/br); d is kept resident (embedding dims here are <= 1k).
The j axis accumulates into the output block (revisited, init at j==0) —
the standard Pallas reduction pattern.  Block sizes default to MXU/VPU
alignment (multiples of 128 on the matmul dims, 8 on sublanes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.precision import mxu_for
from repro.kernels._tiling import ceil_to as _ceil_to
from repro.kernels._tiling import gains_out as _gains_out
from repro.kernels._tiling import mxu_params as _mxu_params
from repro.kernels._tiling import row_block as _row_block
from repro.kernels._tiling import pad_axis as _pad_axis

DEFAULT_BC = 256   # candidate rows per tile
DEFAULT_BR = 512   # reference cols per tile


def _fm_kernel(cand_ref, refT_ref, state_ref, out_ref):
    """One (i, j) tile: out[i-block] += reduce(rectify(cand @ refT - state))."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # MXU: (bc, d) @ (d, br) -> (bc, br) in f32
    sims = jnp.dot(cand_ref[...], refT_ref[...],
                   preferred_element_type=jnp.float32,
                   precision=mxu_for(cand_ref.dtype))
    sims = jnp.maximum(sims, 0.0)                    # prep rectification
    resid = jnp.maximum(sims - state_ref[...], 0.0)  # marginal residual
    out_ref[...] += jnp.sum(resid, axis=-1)[None, :]


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_r", "interpret"))
def facility_marginals(cand, ref, state, *, block_c: int = DEFAULT_BC,
                       block_r: int = DEFAULT_BR, interpret: bool = False):
    """(C, d), (r, d), (r,) -> (C,) float32 marginal gains.

    Pads C and r up to block multiples; state padding is +inf so padded
    reference columns contribute exactly 0 to the rectified residual.
    """
    C, d = cand.shape
    r = ref.shape[0]
    bc, Cp = _row_block(C, block_c, cand.dtype)
    br = min(block_r, _ceil_to(r, 128))
    rp = _ceil_to(r, br)

    cand_p = _pad_axis(cand, 0, Cp)
    refT_p = _pad_axis(ref.T, 1, rp)                       # (d, rp)
    state_p = _pad_axis(state.astype(jnp.float32), 0, rp,
                        value=jnp.inf)[None, :]            # (1, rp)

    out_spec, out_shape = _gains_out(bc, Cp)
    grid = (Cp // bc, rp // br)
    out = pl.pallas_call(
        _fm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, br), lambda i, j: (0, j)),
            pl.BlockSpec((1, br), lambda i, j: (0, j)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        compiler_params=_mxu_params(),
        interpret=interpret,
    )(cand_p, refT_p, state_p)
    return out[0, :C]


def _rrs_kernel(aux_ref, state_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    resid = jnp.maximum(aux_ref[...].astype(jnp.float32) - state_ref[...],
                        0.0)
    out_ref[...] += jnp.sum(resid, axis=-1)[None, :]


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_r", "interpret"))
def rectified_residual_sum(aux, state, *, block_c: int = DEFAULT_BC,
                           block_r: int = DEFAULT_BR,
                           interpret: bool = False):
    """(C, r), (r,) -> (C,): the prep-based (unfused) marginal.

    Memory-bound (1 FLOP/4B); the kernel's job is just to stream (bc, br)
    tiles at full HBM bandwidth without materializing the broadcast
    `aux - state` intermediate.
    """
    C, r = aux.shape
    bc, Cp = _row_block(C, block_c, aux.dtype)
    br = min(block_r, _ceil_to(r, 128))
    rp = _ceil_to(r, br)
    aux_p = _pad_axis(_pad_axis(aux, 0, Cp), 1, rp)
    state_p = _pad_axis(state.astype(jnp.float32), 0, rp,
                        value=jnp.inf)[None, :]

    out_spec, out_shape = _gains_out(bc, Cp)
    grid = (Cp // bc, rp // br)
    out = pl.pallas_call(
        _rrs_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, br), lambda i, j: (i, j)),
            pl.BlockSpec((1, br), lambda i, j: (0, j)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(aux_p, state_p)
    return out[0, :C]
