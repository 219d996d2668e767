"""Pallas TPU kernel: fused log-det diversity marginal gains.

    gains[i] = log( 1 + alpha*||x_i||^2 - alpha^2*||U @ x_i||^2 )

where U = L^{-1} X_S is LogDetDiversity's whitened selected-feature basis
(see repro.core.functions.LogDetDiversity): the bracket is the Schur
complement of the bordered Gram matrix, i.e. exactly f(S+e) - f(S) for
f(S) = log det(I + alpha * X_S X_S^T).

The hot part is the (C, d) x (d, k) projection — an MXU matmul — followed
by two row-norm reductions and a transcendental, all fused so the (C, k)
projection block never leaves VMEM (the XLA path materializes it in HBM
plus a separate (C,) norm pass).  k <= the cardinality budget (tiny), so U
is kept fully resident; the grid tiles candidates only.

Grid: (C/bc,).  Padding: candidate rows pad with 0 (their gains are sliced
off); U rows beyond |S| are zero by construction and padded k columns are
zero too, contributing exactly 0 to the projection norm.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.precision import MXU
from repro.kernels._tiling import ceil_to as _ceil_to
from repro.kernels._tiling import gains_out as _gains_out
from repro.kernels._tiling import row_block as _row_block
from repro.kernels._tiling import pad_axis as _pad_axis

DEFAULT_BC = 256
RESID_EPS = 1e-12   # clamp for the Schur complement (exact math keeps it >= 1)


def _ld_kernel(x_ref, ut_ref, out_ref, *, alpha, eps, scale):
    x = x_ref[...].astype(jnp.float32)                   # (bc, d)
    # MXU: (bc, d) @ (d, kp) projection onto the whitened selected basis
    proj = jnp.dot(x, ut_ref[...], preferred_element_type=jnp.float32,
                   precision=MXU)
    sq = jnp.sum(x * x, axis=-1)
    resid = 1.0 + alpha * sq - (alpha * alpha) * jnp.sum(proj * proj, axis=-1)
    gains = jnp.log(jnp.maximum(resid, eps))
    # scale=0.5 is the mutual-information oracle (0.5 * log det); the
    # python-level branch keeps the scale=1.0 lowering bit-identical
    out_ref[...] = (gains if scale == 1.0 else scale * gains)[None, :]


@functools.partial(jax.jit,
                   static_argnames=("alpha", "eps", "block_c", "interpret",
                                    "scale"))
def logdet_marginals(x, U, alpha: float = 1.0, eps: float = RESID_EPS, *,
                     block_c: int = DEFAULT_BC, interpret: bool = False,
                     scale: float = 1.0):
    """(C, d), (k, d) -> (C,) f32 log-det diversity marginal gains
    (times the compile-time ``scale`` — 0.5 for the MI oracle)."""
    C, d = x.shape
    k = U.shape[0]
    bc, Cp = _row_block(C, block_c, x.dtype)
    kp = _ceil_to(max(k, 1), 8)

    x_p = _pad_axis(x, 0, Cp)
    ut_p = _pad_axis(U.astype(jnp.float32).T, 1, kp)     # (d, kp)

    out_spec, out_shape = _gains_out(bc, Cp)
    grid = (Cp // bc,)
    out = pl.pallas_call(
        functools.partial(_ld_kernel, alpha=alpha, eps=eps, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, d), lambda i: (i, 0)),
            pl.BlockSpec((d, kp), lambda i: (0, 0)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(x_p, ut_p)
    return out[0, :C]
