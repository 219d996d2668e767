"""Pallas TPU kernel: fused graph-cut marginal gains.

    gains[i] = sum_f x[i,f] * (total[f] - 2*lam*state[f]) - lam * x[i,f]^2

This is GraphCut's marginal  <x_e, t> - lam*(2<x_e, s> + ||x_e||^2)  with
t = sum of all element features (a dataset constant) and s = sum of the
selected features (the state) — see repro.core.functions.GraphCut.

Like the coverage kernel, the op is memory-bound (~5 FLOPs per 4 bytes of
candidate row), so the kernel's job is streaming (bc, bf) tiles at HBM
bandwidth while keeping the broadcast `t - 2*lam*s` coefficient row and
the x^2 intermediate in VMEM/VREGs — the XLA path materializes both as
full (C, d) f32 buffers.

Grid: (C/bc, d/bf); the f axis accumulates into the (1, bc) output row block
(init at f-block 0).  Padding: x/total/state all pad with 0, so padded
features contribute exactly 0 to the linear and quadratic terms.
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


def _gc_kernel(x_ref, total_ref, state_ref, out_ref, *, lam):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)                    # (bc, bf)
    coef = total_ref[...] - 2.0 * lam * state_ref[...]    # (1, bf)
    out_ref[...] += jnp.sum(x * coef - lam * x * x, axis=-1)[None, :]


@functools.partial(jax.jit,
                   static_argnames=("lam", "block_c", "block_f", "interpret"))
def graph_cut_marginals(x, total, state, lam: float = 0.5, *,
                        block_c: int = DEFAULT_BC, block_f: int = DEFAULT_BF,
                        interpret: bool = False):
    """(C, d), (d,), (d,) -> (C,) f32 GraphCut marginal gains."""
    C, d = x.shape
    bc, Cp = _row_block(C, block_c, x.dtype)
    bf = min(block_f, _ceil_to(d, 128))
    dp = _ceil_to(d, bf)

    x_p = _pad_axis(_pad_axis(x, 0, Cp), 1, dp)
    total_p = _pad_axis(total.astype(jnp.float32), 0, dp)[None, :]
    state_p = _pad_axis(state.astype(jnp.float32), 0, dp)[None, :]

    out_spec, out_shape = _gains_out(bc, Cp)
    grid = (Cp // bc, dp // bf)
    out = pl.pallas_call(
        functools.partial(_gc_kernel, lam=lam),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, bf), lambda i, j: (i, j)),
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(x_p, total_p, state_p)
    return out[0, :C]
