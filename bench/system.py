"""The system under test, built from a configuration file, and the corpus
it selects from.

The corpus is made on the device in one jitted call from the run's key.
Its rows stand for Tiny Images rows (32 x 32 x 3 pixels in [0, 1]), which
the source keeps grouped by the keyword each image was found under: the n
rows fall into ``groups`` equal runs of contiguous rows.  The d features
are cut into ``groups`` equal bands (stretches of a colour plane), one to
each group: a row is lit on its group's band, at the group's brightness
(uniform between ``bright_min`` and ``bright_max``) times a uniform draw
in [0.75, 1.25) per pixel, and dark elsewhere.  A good set takes one row
from each of the best groups; a set drawn from half of the corpus, or
chosen by gains over half of the features, misses half of the bands and
is worth clearly less.

Every seed gets the same multiset of (band, brightness) pairs, drawn once
from a fixed generator, laid on the row groups in an order of its own,
and its own pixel noise: the seed changes which rows hold which band, not
how much work a selection is.

A reference set, where the objective has one, is every (n // r)-th row.
Those r rows are drawn from one fixed key, the same in every run: the
program builds the reference set into its compiled step as a constant, so
a reference set that changed with the seed would compile the step anew in
every run's set-up.
"""

from __future__ import annotations

import numpy as np


def selector_spec(cfg: dict):
    from repro.core.selector import SelectorSpec
    return SelectorSpec(k=cfg["k"], oracle=cfg["oracle"],
                        algorithm=cfg["algorithm"], eps=cfg["eps"],
                        engine=cfg["engine"], use_kernel=cfg["use_kernel"],
                        reference_size=cfg.get("reference_size", 256),
                        precision=cfg["precision"])


def mesh_for(chips: int):
    from repro.launch.mesh import make_mesh_for
    return make_mesh_for(chips, model_parallel=1)


#: the key of the reference rows, the same in every run
REFERENCE_SEED = 1411_0541
#: the fixed generator of the groups' brightness every seed shares
BANDS_SEED = 80_000_000


def bands(cfg: dict, seed: int):
    """(start, end, brightness) of each row group's band, (groups,) each:
    one multiset for every seed, in the seed's order."""
    g, d = cfg["groups"], cfg["d"]
    edges = np.arange(g + 1) * d // g
    bright = np.random.default_rng(BANDS_SEED).uniform(
        cfg["bright_min"], cfg["bright_max"], g).astype(np.float32)
    order = np.random.default_rng(seed).permutation(g)
    return (edges[:-1].astype(np.int32)[order],
            edges[1:].astype(np.int32)[order], bright[order])


def corpus(key, cfg: dict, sharding, seed: int):
    """(n, d) float32 rows in [0, 1], grouped as the module says, made
    where ``sharding`` puts them; the reference rows, where the objective
    has them, from :data:`REFERENCE_SEED`."""
    import jax
    import jax.numpy as jnp
    n, d, g = cfg["n"], cfg["d"], cfg["groups"]
    if not 1 <= g <= min(n, d):
        raise ValueError(f"groups must be in [1, min(n, d)], got {g}")

    def make(k, start, end, bright):
        grp = (jnp.arange(n, dtype=jnp.int32) * g) // n
        f = jnp.arange(d, dtype=jnp.int32)[None, :]
        lit = (f >= start[grp][:, None]) & (f < end[grp][:, None])
        u = jax.random.uniform(k, (n, d), jnp.float32, 0.75, 1.25)
        X = jnp.where(lit, bright[grp][:, None] * u, 0.0)
        if cfg["oracle"] == "exemplar":
            r = cfg["reference_size"]
            ref = jax.random.uniform(jax.random.PRNGKey(REFERENCE_SEED),
                                     (r, d), jnp.float32)
            X = X.at[jnp.arange(r) * (n // r)].set(ref)
        return X
    start, end, bright = bands(cfg, seed)
    return jax.block_until_ready(jax.jit(make, out_shardings=sharding)(
        key, jnp.asarray(start), jnp.asarray(end), jnp.asarray(bright)))


def reference_rows(X, cfg: dict):
    """The objective's reference set (r, d) on the device, or None."""
    if cfg["oracle"] != "exemplar":
        return None
    r = cfg["reference_size"]
    return X[::cfg["n"] // r][:r]


def host_rows(X, width: int):
    """``rows_of(ids)`` over a device corpus: the rows of ``ids`` as
    float64 on the host, gathered on the device in one shape (``width``
    ids, padded) so that no new program is compiled per answer."""
    import jax
    import jax.numpy as jnp
    take = jax.jit(lambda X, ids: X[ids])

    def rows_of(ids):
        ids = np.asarray(ids, np.int32)
        pad = np.zeros(max(width, ids.size), np.int32)
        pad[:ids.size] = ids
        return np.asarray(take(X, jnp.asarray(pad)), np.float64)[:ids.size]
    return rows_of
