"""Pallas TPU kernel: fused exemplar-clustering marginal gains.

    gains[i] = sum_j max( state[j] - d2(i, j), 0 )
    d2(i, j) = max( ||ref_j||^2 - 2*<x_i, ref_j> + ||x_i||^2, 0 )

This is ExemplarClustering's marginal (the k-medoid loss reduction a
candidate buys over the reference set, given the current min-distance
vector `state`) — see repro.core.functions.ExemplarClustering.

Same roofline story as the facility kernel, with distances instead of
similarities: the naive path materializes the (C, r) squared-distance
matrix in HBM at `prep`; the fused kernel expands the distance from one
(bc, d) x (d, br) MXU matmul plus two precomputable norms, rectifies in
VREGs and reduces to a (bc,) partial — the (C, r) intermediate never
leaves VMEM.

Grid: (C/bc, r/br); d is kept resident.  Padding: ref/refsq pad with 0,
so a padded column's distance is the finite ||x_i||^2, and state pads with
-inf, making its residual max(-inf - d2, 0) = 0 exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.precision import MXU
from repro.kernels._tiling import ceil_to as _ceil_to
from repro.kernels._tiling import gains_out as _gains_out
from repro.kernels._tiling import mxu_params as _mxu_params
from repro.kernels._tiling import row_block as _row_block
from repro.kernels._tiling import pad_axis as _pad_axis

DEFAULT_BC = 256   # candidate rows per tile
DEFAULT_BR = 512   # reference cols per tile


def _ex_kernel(cand_ref, refT_ref, refsq_ref, state_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = cand_ref[...].astype(jnp.float32)                # (bc, d)
    # MXU: (bc, d) @ (d, br) -> (bc, br) in f32
    sims = jnp.dot(x, refT_ref[...], preferred_element_type=jnp.float32,
                   precision=MXU)
    sq = jnp.sum(x * x, axis=-1, keepdims=True)          # (bc, 1)
    d2 = jnp.maximum(refsq_ref[...] - 2.0 * sims + sq, 0.0)
    resid = jnp.maximum(state_ref[...] - d2, 0.0)
    out_ref[...] += jnp.sum(resid, axis=-1)[None, :]


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_r", "interpret"))
def exemplar_marginals(cand, ref, state, *, block_c: int = DEFAULT_BC,
                       block_r: int = DEFAULT_BR, interpret: bool = False):
    """(C, d), (r, d), (r,) -> (C,) f32 exemplar-clustering marginal gains."""
    C, d = cand.shape
    r = ref.shape[0]
    bc, Cp = _row_block(C, block_c, cand.dtype)
    br = min(block_r, _ceil_to(r, 128))
    rp = _ceil_to(r, br)

    cand_p = _pad_axis(cand, 0, Cp)
    ref32 = ref.astype(jnp.float32)
    refT_p = _pad_axis(ref32.T, 1, rp)                                # (d, rp)
    refsq_p = _pad_axis(jnp.sum(ref32 * ref32, axis=-1), 0, rp)[None, :]
    state_p = _pad_axis(state.astype(jnp.float32), 0, rp,
                        value=-jnp.inf)[None, :]                      # (1, rp)

    out_spec, out_shape = _gains_out(bc, Cp)
    grid = (Cp // bc, rp // br)
    out = pl.pallas_call(
        _ex_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, br), lambda i, j: (0, j)),
            pl.BlockSpec((1, br), lambda i, j: (0, j)),
            pl.BlockSpec((1, br), lambda i, j: (0, j)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        compiler_params=_mxu_params(),
        interpret=interpret,
    )(cand_p, refT_p, refsq_p, state_p)
    return out[0, :C]
