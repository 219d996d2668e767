"""Shared tiling helpers for the Pallas kernels in this package.

Every kernel pads its operands up to block multiples before `pallas_call`
and slices the padding back off the output; the pad *value* is chosen per
operand so padded rows/columns contribute exactly zero to the reduction
(e.g. +inf state columns under a rectified residual, -inf state columns
under a distance residual, zero feature columns under a linear term).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def sublane(dtype) -> int:
    """Minimum second-to-last-dim tile multiple for ``dtype`` on TPU:
    8 for f32, 16 for bf16, 32 for int8/fp8 (the lane dim is always 128).
    The wrappers size their row padding with this so storage-dtype (bf16)
    candidate tiles stay legal VMEM blocks."""
    return max(8, 32 // jnp.dtype(dtype).itemsize)


def pad_axis(x, axis: int, target: int, value=0.0):
    pad = target - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def row_block(C: int, block_c: int, dtype) -> tuple[int, int]:
    """``(bc, Cp)``: the candidate tile height and the padded candidate
    count for a kernel whose per-candidate results leave it as a
    lane-dense ``(1, Cp)`` row (:func:`gains_out`).  Either one tile spans
    the whole padded axis, or ``bc`` is a multiple of the 128-lane width —
    the TPU compiler refuses any other partial block of that row."""
    full = ceil_to(C, sublane(dtype))
    if full <= block_c:
        return full, full
    bc = ceil_to(block_c, LANES)
    return bc, ceil_to(C, bc)


def gains_out(bc: int, Cp: int):
    """``(out_specs, out_shape)`` for per-candidate f32 gains written as a
    lane-dense ``(1, Cp)`` row, tile i owning columns ``[i*bc, (i+1)*bc)``
    of the first grid axis.  A 1-D ``(Cp,)`` output in ``bc``-blocks does
    not compile for the TPU: XLA tiles a 1-D f32 array by 1024, Mosaic by
    the block."""
    return (pl.BlockSpec((1, bc), lambda i, *_: (0, i)),
            jax.ShapeDtypeStruct((1, Cp), jnp.float32))


def mxu_params():
    """Compiler params of the kernels with a (bc, d) x (d, br) MXU tile:
    at d = 3,072 their double-buffered f32 tiles, and the bf16 halves an
    f32-precision matmul splits them into, overflow the TPU compiler's
    default 16 MiB of scoped VMEM; a v5e core has 128 MiB."""
    return pltpu.CompilerParams(vmem_limit_bytes=64 * 2**20)


# ---------------------------------------------------------------------------
# vmap rules of the many-lane kernels (jax.custom_batching.custom_vmap)
# ---------------------------------------------------------------------------

def all_batched(out):
    """Every leaf of a rule's output batched on axis 0."""
    return jax.tree.map(lambda _: True, out)


def unbatch(fn, in_batched, args):
    """Call ``fn`` on an axis of size 1 as on no axis."""
    args = [a[0] if b else a for a, b in zip(args, in_batched)]
    out = fn(*args)
    return jax.tree.map(lambda o: o[None], out), all_batched(out)


def pallas_vmap(fn, in_batched, args):
    """``fn`` under jax.vmap: a pallas_call gets Pallas's own batching, an
    outer grid axis."""
    axes = [0 if b else None for b in in_batched]
    out = jax.vmap(fn, in_axes=axes)(*args)
    return out, all_batched(out)
