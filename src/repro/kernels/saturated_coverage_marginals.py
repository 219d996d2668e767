"""Pallas TPU kernel: SaturatedCoverage marginal gains.

    gains[i] = sum_f w_f * ( min(state_f + x_{i,f}, cap_f)
                             - min(state_f, cap_f) )

Same roofline story as the FeatureCoverage kernel (the truncation is one
extra min per element): memory-bound streaming of (bc, bf) tiles, with the
broadcast `state + x` and both clamped intermediates living in VMEM/VREGs
instead of a materialized (C, d) HBM buffer.

Grid: (C/bc, d/bf); the f axis accumulates into the (1, bc) output row block
(init at f-block 0).  Padding: x, state, cap and w all pad with 0, so
padded features contribute min(0, 0) - min(0, 0) = 0 exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._tiling import ceil_to as _ceil_to
from repro.kernels._tiling import gains_out as _gains_out
from repro.kernels._tiling import row_block as _row_block
from repro.kernels._tiling import pad_axis as _pad_axis

DEFAULT_BC = 256
DEFAULT_BF = 512


def _sat_kernel(x_ref, state_ref, cap_ref, w_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    st = state_ref[...]                                  # (1, bf) f32
    cap = cap_ref[...]                                   # (1, bf) f32
    x = x_ref[...].astype(jnp.float32)                   # (bc, bf)
    gain = jnp.minimum(st + x, cap) - jnp.minimum(st, cap)
    gain = gain * w_ref[...]
    out_ref[...] += jnp.sum(gain, axis=-1)[None, :]


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_f", "interpret"))
def saturated_coverage_marginals(x, state, cap, weights=None, *,
                                 block_c: int = DEFAULT_BC,
                                 block_f: int = DEFAULT_BF,
                                 interpret: bool = False):
    """(C, d), (d,), (d,)[, (d,)] -> (C,) f32 SaturatedCoverage gains."""
    C, d = x.shape
    bc, Cp = _row_block(C, block_c, x.dtype)
    bf = min(block_f, _ceil_to(d, 128))
    dp = _ceil_to(d, bf)

    x_p = _pad_axis(_pad_axis(x, 0, Cp), 1, dp)
    state_p = _pad_axis(state.astype(jnp.float32), 0, dp)[None, :]
    cap_p = _pad_axis(cap.astype(jnp.float32), 0, dp)[None, :]
    w = weights if weights is not None else jnp.ones((d,), jnp.float32)
    w_p = _pad_axis(w.astype(jnp.float32), 0, dp)[None, :]

    out_spec, out_shape = _gains_out(bc, Cp)
    grid = (Cp // bc, dp // bf)
    out = pl.pallas_call(
        _sat_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, bf), lambda i, j: (i, j)),
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(x_p, state_p, cap_p, w_p)
    return out[0, :C]
