"""Controls and planted faults: the timed path broken underneath, to show
that the comparison in ``bench/check.py`` fails them.  Used by
``bench/control.py`` on the chip and by ``bench/tests`` on the CPU; a
benchmark run never applies one.

Controls (the nearest precision below the configuration's):

* ``bf16``: the program's own bf16 storage path (``precision="bf16"``);
* ``high``: every f32 matmul of the objective and its kernels at
  ``Precision.HIGH`` (three bf16 passes) instead of HIGHEST.

Faults, each where it is produced:

* ``alter_answer``: the first id of every answer moved to the next row;
* ``stale_state``: the central accept kernel returns the objective's state
  unchanged, so the state never advances;
* ``half_slots``: each serving step answers only the first half of the
  requests it admitted;
* ``half_corpus``: each batch selection sees the second half of the
  corpus as zero rows, which no objective gains from: half of it left out;
* ``half_features``: the coverage kernels (marginals and accept) weigh the
  second half of the features by zero in every gain, so the rounds judge
  each row by half of what it covers and choose the wrong rows, while the
  state, and so the reported f(S), still adds every feature;
* ``no_exchange``: the survivor all-gather left out, each machine's
  buffer repeated in place of the others'.
"""

from __future__ import annotations

import contextlib

CONTROLS = ("bf16", "high")
FAULTS = ("alter_answer", "stale_state", "half_slots", "half_corpus",
          "half_features", "no_exchange")


def applies(mode: str, cell: dict) -> bool:
    """Whether the cell can have this fault."""
    driver = cell["traffic_spec"]["driver"]
    if mode == "half_slots":
        return driver == "open_loop"
    if mode == "half_corpus":
        return driver == "batch_loop"
    if mode == "no_exchange":
        return cell["chips"] > 1
    if mode == "high":
        return cell["config_spec"]["oracle"] == "exemplar"
    if mode == "half_features":
        return cell["config_spec"]["oracle"] == "feature_coverage"
    return True


def config_overrides(mode: str) -> dict:
    return {"precision": "bf16"} if mode == "bf16" else {}


def _alter(res, n: int):
    import jax.numpy as jnp
    ids = res.sol_ids
    first = ids[..., :1]
    moved = jnp.where(first >= 0, (first + 1) % n, first)
    return res._replace(sol_ids=jnp.concatenate([moved, ids[..., 1:]],
                                                axis=-1))


@contextlib.contextmanager
def planted(mode: str):
    """Patches of the program that must be in place before it compiles."""
    import jax
    from repro.core import functions, precision, rounds
    from repro.kernels import exemplar_accept, exemplar_marginals, ops

    saved = []

    def patch(mod, name, value):
        saved.append((mod, name, getattr(mod, name)))
        setattr(mod, name, value)

    if mode == "high":
        for mod in (precision, functions, exemplar_marginals,
                    exemplar_accept):
            patch(mod, "MXU", jax.lax.Precision.HIGH)
    elif mode == "stale_state":
        for name in ("coverage_accept", "exemplar_accept"):
            real = getattr(ops, name)

            def unchanged(x, ref_or_state, *args, _real=real, _name=name,
                          **kw):
                out = _real(x, ref_or_state, *args, **kw)
                state = args[0] if _name == "exemplar_accept" else \
                    ref_or_state
                return (out[0], state) + tuple(out[2:])
            patch(ops, name, unchanged)
    elif mode == "half_features":
        def halved(real, at):
            def call(*args, **kw):
                import jax.numpy as jnp
                args = list(args)
                d = args[0].shape[-1]
                half = (jnp.arange(d) < d // 2).astype(jnp.float32)
                w = args[at] if len(args) > at else kw.pop("weights", None)
                w = half if w is None else w * half
                if len(args) > at:
                    args[at] = w
                else:
                    kw["weights"] = w
                return real(*args, **kw)
            return call
        # coverage_marginals(x, state, weights); coverage_accept(x, state,
        # weights, eligible, ...)
        patch(ops, "coverage_marginals", halved(ops.coverage_marginals, 2))
        patch(ops, "coverage_accept", halved(ops.coverage_accept, 2))
    elif mode == "no_exchange":
        def local_only(x, gather_axes, lead=0):
            import jax.numpy as jnp
            axes = gather_axes if isinstance(gather_axes, tuple) else \
                (gather_axes,)
            m = 1
            for a in axes:
                m *= jax.lax.axis_size(a)
            return jnp.concatenate([x] * m, axis=lead)
        patch(rounds, "gather_packed", local_only)
    try:
        yield
    finally:
        for mod, name, value in reversed(saved):
            setattr(mod, name, value)


def after_setup(mode: str):
    """A patch of the traffic driver's context after set-up, or None."""
    if mode == "alter_answer":
        def patch(ctx):
            n = ctx.config["n"]
            if hasattr(ctx, "select"):
                real = ctx.select
                ctx.select = lambda X, key: _alter(real(X, key=key), n)
            else:
                real_served = ctx.served
                ctx.served = lambda q, key: _alter(real_served(q, key), n)
        return patch
    if mode == "half_corpus":
        def patch(ctx):
            real, n = ctx.select, ctx.config["n"]
            half = ctx.X.at[n // 2:].set(0.0)
            ctx.select = lambda X, key: real(half, key=key)
        return patch
    if mode == "half_slots":
        def patch(ctx):
            base = ctx.ServeLoop

            class HalfLoop(base):
                def run_step(self):
                    rows = super().run_step()
                    return rows[:(len(rows) + 1) // 2]
            ctx.ServeLoop = HalfLoop
        return patch
    return None
