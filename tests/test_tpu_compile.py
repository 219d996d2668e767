"""Compile guards: every Pallas kernel compiles for a TPU v5e at the
deployment width (d = 3,072, GreeDi's Tiny Images rows), in f32 and bf16.

The chip is described, not attached: the TPU compiler installed with
libtpu compiles for it here, and each compiled program must hold a
``tpu_custom_call`` (the kernel lowered through Mosaic, not interpreted).
Interpret mode, which every other kernel test runs in, cannot see what
these catch: blocks the TPU layout refuses, dynamic reads of packed bf16
tiles, tiles that overflow the scoped VMEM limit.

The topology is described inside a module fixture, never at import: only
one process at a time may load libtpu, and a worker that loads it while
importing would change which tests the other workers collect.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import coverage_accept as ca
from repro.kernels import coverage_marginals as cm
from repro.kernels import exemplar_accept as ea
from repro.kernels import exemplar_marginals as em
from repro.kernels import facility_accept as fa
from repro.kernels import facility_marginals as fm
from repro.kernels import graph_cut_accept as ga
from repro.kernels import graph_cut_marginals as gm
from repro.kernels import logdet_accept as la
from repro.kernels import logdet_marginals as lm
from repro.kernels import saturated_coverage_accept as sa
from repro.kernels import saturated_coverage_marginals as sm
from repro.kernels import weighted_coverage_accept as wa
from repro.kernels import weighted_coverage_marginals as wm

D = 3_072      # feature width of the deployment
C = 4_096      # candidates per marginals call (a filter tile)
B = 128        # rows per accept sweep (the fused engine's default chunk)
R = 1_024      # reference rows (facility / exemplar)
KB = 64        # log-det basis rows (k)
J = 37         # threshold lanes at eps = 0.15


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _kernel_calls(one_chip, fn, *shapes):
    """Compile ``fn`` for the described chip from (shape, dtype) pairs and
    count its Mosaic kernel launch sites."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


F32 = jnp.float32
I32 = jnp.int32

#: name -> (kernel call over the operands, operand shapes; ``X`` marks the
#: candidate operand, which takes the parametrized storage dtype)
MARGINALS = {
    "coverage": (lambda x, s: cm.coverage_marginals(x, s),
                 [("X", (C, D)), (F32, (D,))]),
    # the threshold lanes of one selection, and of an 8-slot served batch
    "coverage_lanes37": (lambda x, s: cm.coverage_marginals_lanes(x, s),
                         [("X", (C, D)), (F32, (J, D))]),
    "coverage_lanes296": (lambda x, s: cm.coverage_marginals_lanes(x, s),
                          [("X", (C, D)), (F32, (8 * J, D))]),
    "coverage_lanes37_weighted": (
        lambda x, s, w: cm.coverage_marginals_lanes(x, s, w),
        [("X", (C, D)), (F32, (J, D)), (F32, (D,))]),
    "coverage_vmapped_lanes": (
        lambda x, s: jax.vmap(lambda st: cm.routed(
            cm.DEFAULT_BC, cm.DEFAULT_BF, False)(x, st, None))(s),
        [("X", (C, D)), (F32, (J, D))]),
    "saturated_coverage": (
        lambda x, s, c: sm.saturated_coverage_marginals(x, s, c),
        [("X", (C, D)), (F32, (D,)), (F32, (D,))]),
    "weighted_coverage": (lambda x, s: wm.weighted_coverage_marginals(x, s),
                          [("X", (C, D)), (F32, (D,))]),
    "graph_cut": (lambda x, t, s: gm.graph_cut_marginals(x, t, s, 0.5),
                  [("X", (C, D)), (F32, (D,)), (F32, (D,))]),
    "facility": (lambda x, r, s: fm.facility_marginals(x, r, s),
                 [("X", (C, D)), ("X", (R, D)), (F32, (R,))]),
    "rectified_residual": (lambda a, s: fm.rectified_residual_sum(a, s),
                           [("X", (C, R)), (F32, (R,))]),
    "exemplar": (lambda x, r, s: em.exemplar_marginals(x, r, s),
                 [("X", (C, D)), ("X", (R, D)), (F32, (R,))]),
    "logdet": (lambda x, u: lm.logdet_marginals(x, u, 1.0),
               [("X", (C, D)), (F32, (KB, D))]),
}

_SWEEP = [(jnp.bool_, (B,)), (F32, ()), (I32, ())]   # eligible, tau, budget

ACCEPTS = {
    "coverage": (lambda x, s, e, t, b: ca.coverage_accept(x, s, None, e, t, b),
                 [("X", (B, D)), (F32, (D,))] + _SWEEP),
    "coverage_knapsack": (
        lambda x, s, e, t, b, c, cb: ca.coverage_accept(
            x, s, None, e, t, b, cost=c, cost_budget=cb),
        [("X", (B, D)), (F32, (D,))] + _SWEEP + [(F32, (B,)), (F32, ())]),
    "saturated_coverage": (
        lambda x, s, c, e, t, b: sa.saturated_coverage_accept(
            x, s, c, None, e, t, b),
        [("X", (B, D)), (F32, (D,)), (F32, (D,))] + _SWEEP),
    "weighted_coverage": (
        lambda x, s, e, t, b: wa.weighted_coverage_accept(x, s, e, t, b),
        [("X", (B, D)), (F32, (D,))] + _SWEEP),
    "graph_cut": (
        lambda x, tt, s, e, t, b: ga.graph_cut_accept(x, tt, s, e, t, b, 0.5),
        [("X", (B, D)), (F32, (D,)), (F32, (D,))] + _SWEEP),
    "facility": (lambda x, r, s, e, t, b: fa.facility_accept(x, r, s, e, t, b),
                 [("X", (B, D)), ("X", (R, D)), (F32, (R,))] + _SWEEP),
    "exemplar": (lambda x, r, s, e, t, b: ea.exemplar_accept(x, r, s, e, t, b),
                 [("X", (B, D)), ("X", (R, D)), (F32, (R,))] + _SWEEP),
    "logdet": (
        lambda x, u, ld, z, e, t, b: la.logdet_accept(x, u, ld, z, e, t, b),
        [("X", (B, D)), (F32, (KB, D)), (F32, ()), (I32, ())] + _SWEEP),
}


def _shapes(spec, dtype):
    return [(s, dtype if dt == "X" else dt) for dt, s in spec]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", sorted(MARGINALS))
def test_marginals_kernel_compiles_for_v5e(one_chip, name, dtype):
    fn, spec = MARGINALS[name]
    assert _kernel_calls(one_chip, fn, *_shapes(spec, dtype)) == 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", sorted(ACCEPTS))
def test_accept_kernel_compiles_for_v5e(one_chip, name, dtype):
    fn, spec = ACCEPTS[name]
    assert _kernel_calls(one_chip, fn, *_shapes(spec, dtype)) == 1
