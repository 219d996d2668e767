"""Pallas TPU kernel: fused log-det / mutual-information chunk-accept sweep.

The last accept-kernel gap in the zoo: ThresholdGreedy's inner loop over
a (B, d) candidate tile for LogDetDiversity (and, at compile-time
``scale=0.5``, MutualInformationGaussian) in ONE kernel.  The whitened
selected basis U = L^{-1} X_S lives in VMEM scratch; per row i

    v    = alpha * U x_i                   (the Cholesky border)
    d^2  = max(1 + alpha*||x_i||^2 - ||v||^2, eps)
    gain = scale * log(d^2)

and an accepted row applies the rank-1 Gram–Schmidt append IN SCRATCH:

    U[size + n_acc] = (x_i - v^T U) / d,     logdet += gain

so a multi-accept sweep never round-trips the (k, d) basis through HBM.
The row write is a masked full-matrix select (row_iota == target) — no
dynamic vector stores, per the TPU Pallas constraints.  An append at
size == k_max matches no scratch row and is dropped, mirroring the jnp
path's out-of-bounds ``at[].set`` semantics (harmless: engines never
accept past the budget).

State is (U (k, d) f32, logdet () f32, size () int32) — the extra
scalars ride (1, 1) blocks.  Outputs extend the shared accept contract
(see kernels/_accept_common.py) with the post-sweep U/logdet/size.

``cost``/``cost_budget`` switch the sweep to knapsack cost-ratio accepts
(gain >= tau * c_i, running spend capped), same semantics as
:func:`repro.kernels._accept_common.run_sweep`.

Padding: candidate rows pad with eligibility 0; U pads to the sublane
multiple with zero rows (inert — they contribute 0 to the projection).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import MXU
from repro.kernels._accept_common import (f32_rows, row_operands, row_specs,
                                          upcast_scratch)
from repro.kernels._tiling import ceil_to as _ceil_to
from repro.kernels._tiling import sublane as _sublane
from repro.kernels._tiling import pad_axis as _pad_axis
from repro.kernels.logdet_marginals import RESID_EPS


def _la_kernel(*refs, nrows, alpha, scale, eps, with_cost):
    (x_ref, u_ref, ld_ref, size_ref, elig_ref, tau_ref,
     budget_ref) = refs[:7]
    base = 7
    cost_ref = cbud_ref = None
    if with_cost:
        cost_ref, cbud_ref = refs[base:base + 2]
        base += 2
    (mask_ref, u_out_ref, ld_out_ref, size_out_ref, gains_ref,
     u_scratch) = refs[base:base + 6]
    B = nrows
    u_scratch[...] = u_ref[...]
    rows = f32_rows(x_ref, refs[base + 6:])
    tau = tau_ref[0, 0]
    budget = budget_ref[0, 0]
    size0 = size_ref[0, 0]
    elig = elig_ref[...]                                   # (1, B) int32
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)
    kp = u_scratch.shape[0]
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (kp, 1), 0)
    if with_cost:
        cost = cost_ref[...]                               # (1, B) f32
        cbud = cbud_ref[0, 0]

    def body(i, carry):
        if with_cost:
            n_acc, spent, ld, mask, gains = carry
        else:
            n_acc, ld, mask, gains = carry
        x_i = rows[i, :][None, :]                          # (1, d)
        U = u_scratch[...]                                 # (kp, d)
        # MXU: border projection v = alpha * U x_i, contracted over d
        proj = jax.lax.dot_general(x_i, U, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32,
                                   precision=MXU)
        v = alpha * proj                                   # (1, kp)
        sq = jnp.sum(x_i * x_i)
        d2 = jnp.maximum(1.0 + alpha * sq - jnp.sum(v * v), eps)
        gain_raw = jnp.log(d2)
        # scale=0.5 is the MI oracle; the python-level branch keeps the
        # scale=1.0 lowering bit-identical to LogDetDiversity
        gain = gain_raw if scale == 1.0 else scale * gain_raw
        here = row_iota == i
        ok = jnp.sum(jnp.where(here, elig, 0)) > 0         # elig[i], masked
        if with_cost:
            ci = jnp.sum(jnp.where(here, cost, 0.0))       # cost[i], masked
            acc = ok & (gain >= tau * ci) & (n_acc < budget) \
                & (spent + ci <= cbud)
        else:
            acc = ok & (gain >= tau) & (n_acc < budget)

        @pl.when(acc)
        def _accept():
            # rank-1 Gram–Schmidt append, written as a masked full-matrix
            # select onto the target row (no dynamic vector stores)
            u_new = (x_i - jnp.dot(v, U, preferred_element_type=jnp.float32,
                                   precision=MXU)) / jnp.sqrt(d2)  # (1, d)
            u_scratch[...] = jnp.where(k_iota == size0 + n_acc, u_new, U)

        ld = ld + jnp.where(acc, gain, jnp.float32(0.0))
        mask = jnp.where(here, acc.astype(jnp.int32), mask)
        gains = jnp.where(here, gain, gains)
        if with_cost:
            spent = spent + jnp.where(acc, ci, jnp.float32(0.0))
            return n_acc + acc.astype(jnp.int32), spent, ld, mask, gains
        return n_acc + acc.astype(jnp.int32), ld, mask, gains

    init = (jnp.zeros((), jnp.int32),
            ld_ref[0, 0],
            jnp.zeros((1, B), jnp.int32),
            jnp.zeros((1, B), jnp.float32))
    if with_cost:
        init = (init[0], jnp.zeros((), jnp.float32)) + init[1:]
    out = jax.lax.fori_loop(0, B, body, init)
    n_acc = out[0]
    ld, mask, gains = out[-3], out[-2], out[-1]
    mask_ref[...] = mask
    gains_ref[...] = gains
    u_out_ref[...] = u_scratch[...]
    ld_out_ref[...] = ld.reshape(1, 1)
    size_out_ref[...] = (size0 + n_acc).reshape(1, 1)


@functools.partial(jax.jit,
                   static_argnames=("alpha", "scale", "eps", "interpret"))
def logdet_accept(x, U, logdet, size, eligible, tau, budget,
                  alpha: float = 1.0, *, scale: float = 1.0,
                  eps: float = RESID_EPS, interpret: bool = False,
                  cost=None, cost_budget=None):
    """(B, d), (k, d), (), (), (B,) bool, (), () -> (mask (B,) bool,
    U (k, d) f32, logdet () f32, size () int32, gains (B,) f32) — the
    log-det (scale=1) / mutual-information (scale=0.5) accept sweep."""
    B, d = x.shape
    k = U.shape[0]
    Bp = _ceil_to(B, _sublane(x.dtype))
    kp = _ceil_to(max(k, 1), 8)
    with_cost = cost is not None

    x_p = _pad_axis(x, 0, Bp)
    u_p = _pad_axis(U.astype(jnp.float32), 0, kp)          # (kp, d)
    ld_b = jnp.asarray(logdet, jnp.float32).reshape(1, 1)
    size_b = jnp.asarray(size, jnp.int32).reshape(1, 1)
    row_ops = row_operands(Bp, eligible, tau, budget, cost, cost_budget)

    mask, u_out, ld_out, size_out, gains = pl.pallas_call(
        functools.partial(_la_kernel, nrows=Bp, alpha=alpha, scale=scale,
                          eps=eps, with_cost=with_cost),
        grid=(1,),
        in_specs=[
            pl.BlockSpec((Bp, d), lambda i: (0, 0)),
            pl.BlockSpec((kp, d), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            *row_specs(Bp, with_cost),
        ],
        out_specs=[
            pl.BlockSpec((1, Bp), lambda i: (0, 0)),
            pl.BlockSpec((kp, d), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, Bp), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Bp), jnp.int32),
            jax.ShapeDtypeStruct((kp, d), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, Bp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((kp, d), jnp.float32),
                        *upcast_scratch(x_p)],
        interpret=interpret,
    )(x_p, u_p, ld_b, size_b, *row_ops)
    return (mask[0, :B] != 0, u_out[:k], ld_out[0, 0], size_out[0, 0],
            gains[0, :B])
