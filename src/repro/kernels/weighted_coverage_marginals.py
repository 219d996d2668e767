"""Pallas TPU kernel: fused weighted-coverage marginal gains.

    gains[i] = sum_u state_u * x_{i,u}

This is WeightedCoverage's marginal: ``state`` is the remaining
(uncovered) weight per universe item and ``x`` the candidates' incidence
rows, so the gain is the uncovered weight the row picks up — see
repro.core.functions.WeightedCoverage.

The op is a pure (C, U) x (U,) contraction (~2 FLOPs per 4 bytes of
incidence row — memory-bound), so the kernel's job is streaming (bc, bu)
tiles at HBM bandwidth while keeping the broadcast ``state * x`` product
in VMEM/VREGs — the XLA path materializes it as a full (C, U) f32 buffer.

Grid: (C/bc, U/bu); the u axis accumulates into the (1, bc) output row block
(init at u-block 0).  Padding: x and state both pad with 0, so padded
universe items contribute exactly 0.
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
DEFAULT_BU = 512


def _wc_kernel(x_ref, state_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)                   # (bc, bu)
    out_ref[...] += jnp.sum(x * state_ref[...], axis=-1)[None, :]


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_u", "interpret"))
def weighted_coverage_marginals(x, state, *, block_c: int = DEFAULT_BC,
                                block_u: int = DEFAULT_BU,
                                interpret: bool = False):
    """(C, U), (U,) -> (C,) f32 WeightedCoverage marginal gains."""
    C, U = x.shape
    bc, Cp = _row_block(C, block_c, x.dtype)
    bu = min(block_u, _ceil_to(U, 128))
    Up = _ceil_to(U, bu)

    x_p = _pad_axis(_pad_axis(x, 0, Cp), 1, Up)
    state_p = _pad_axis(state.astype(jnp.float32), 0, Up)[None, :]

    out_spec, out_shape = _gains_out(bc, Cp)
    grid = (Cp // bc, Up // bu)
    out = pl.pallas_call(
        _wc_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, bu), lambda i, j: (i, j)),
            pl.BlockSpec((1, bu), lambda i, j: (0, j)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(x_p, state_p)
    return out[0, :C]
