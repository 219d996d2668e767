"""Precision — the one policy object for dtypes across the selection stack.

Three planes, one invariant:

  storage     feature rows at rest (HBM corpus, gather/survivor messages,
              sieve pools, HostCorpus chunks, checkpoint tails).  This is
              the bandwidth plane: the marginals/accept kernels are
              bandwidth-bound and Lemma-2/6 message sizes are bytes, so
              halving the element width (bf16) doubles effective HBM
              bandwidth and halves gather traffic.
  compute     what the MXU/VPU multiplies.  bf16 inputs with
              ``preferred_element_type=f32`` is the native TPU contract:
              bf16 operands, f32 partial sums.
  accumulate  oracle state, gains, thresholds, solution values.  Always
              f32 here: ThresholdGreedy compares gains against tau and the
              guarantee proofs assume those comparisons are not drowned in
              rounding — a bf16 state accumulated over k ~ 1e3 adds loses
              ~3 decimal digits and breaks the (1/2 - eps) band.

The DEFAULT policy is f32/f32/f32 and is a strict no-op: every cast helper
returns its input unchanged when the dtype already matches, so pre-refactor
golden outputs stay bit-identical (tests/test_precision.py enforces this).

Specs carry the policy by *name* ("f32" | "bf16") so frozen dataclasses
stay hashable and CLI flags map 1:1; resolve() returns the shared policy
instance.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

#: precision of every MXU matmul of the oracles and kernels.  The compute
#: plane of the f32 policy is f32: on the TPU, XLA's DEFAULT precision
#: would round f32 operands to bf16 before multiplying.  bf16 operands
#: (the bf16 policy) multiply exactly either way, and on the CPU f32 is all
#: there is, so HIGHEST changes no CPU result.
MXU = jax.lax.Precision.HIGHEST


def mxu_for(dtype):
    """:data:`MXU` for f32 operands; None (the compiler's default) for
    bf16 ones, which multiply exactly — and on which the TPU kernel
    compiler refuses an f32 contract."""
    return MXU if jnp.dtype(dtype) == jnp.float32 else None


@dataclasses.dataclass(frozen=True)
class Precision:
    """A named (storage, compute, accumulate) dtype policy."""

    name: str
    storage: jnp.dtype
    compute: jnp.dtype
    accumulate: jnp.dtype

    @property
    def storage_itemsize(self) -> int:
        """Bytes per feature element at rest — the Lemma-2/6 wire width."""
        return jnp.dtype(self.storage).itemsize

    @property
    def np_storage(self) -> np.dtype:
        """Numpy view of the storage dtype (bf16 via ml_dtypes, which jax
        ships and registers with numpy) for HostCorpus / checkpoints."""
        return np.dtype(self.storage)

    @property
    def is_default(self) -> bool:
        return self.name == "f32"

    def cast_storage(self, x):
        """Cast a feature array onto the storage plane.  Identity (same
        object, same bits) when the dtype already matches — the f32 policy
        must never perturb the pre-refactor path."""
        if x.dtype == self.storage:
            return x
        return x.astype(self.storage)

    def cast_accum(self, x):
        """Lift an array onto the accumulate plane (f32).  Oracles call
        this at their math boundary so bf16 feature rows never accumulate
        in bf16; identity for f32 inputs."""
        if x.dtype == self.accumulate:
            return x
        return x.astype(self.accumulate)


F32 = Precision(name="f32", storage=jnp.float32, compute=jnp.float32,
                accumulate=jnp.float32)
BF16 = Precision(name="bf16", storage=jnp.bfloat16, compute=jnp.bfloat16,
                 accumulate=jnp.float32)

POLICIES = {p.name: p for p in (F32, BF16)}
PRECISION_NAMES = tuple(POLICIES)


def resolve(name) -> Precision:
    """Map a policy name (or an already-resolved Precision) to the shared
    instance; raises ValueError with the registered names otherwise."""
    if isinstance(name, Precision):
        return name
    try:
        return POLICIES[name]
    except KeyError:
        raise ValueError(f"unknown precision {name!r}; "
                         f"registered: {PRECISION_NAMES}") from None


def validate(name, where: str) -> None:
    """__post_init__ hook for MRConfig / SelectorSpec / SieveSpec."""
    if name not in POLICIES:
        raise ValueError(f"{where}: unknown precision {name!r}; "
                         f"registered: {PRECISION_NAMES}")


def accum32(x):
    """Module-level shortcut for the accumulate plane: cast feature/aux
    arrays to f32 at the oracle math boundary.  Identity for f32 input
    (same array object — bit-compat), a fused convert for bf16."""
    if x.dtype == jnp.float32:
        return x
    return x.astype(jnp.float32)
