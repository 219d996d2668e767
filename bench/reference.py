"""Plain references for the selection objectives, independent of ``src/``.

* ``value_f64``: f(S) recomputed on the host in float64 from the rows of S.
* ``prefix_values_f64``: f of every prefix of an ordered set, so that one
  greedy run of k steps gives the greedy value at every budget b <= k (the
  greedy prefix of length b is greedy at budget b).
* ``greedy_ids``: classic greedy (Nemhauser-Wolsey-Fisher), k argmax steps
  in straightforward ``jax.numpy`` at float32 with HIGHEST matmuls, run on
  the device over the whole corpus.  Only its ids are used; its values are
  recomputed in float64 by ``prefix_values_f64``.

Objectives (the configuration's ``oracle``):

* ``feature_coverage``: f(S) = sum_f sqrt(sum_{e in S} x_ef)
  (Wei, Iyer & Bilmes, ICML 2015);
* ``exemplar``: f(S) = L({0}) - L(S + {0}),
  L(S) = sum_{v in R} min_{e in S} ||v - x_e||^2, with the phantom
  exemplar at the origin (GreeDi, arXiv 1411.0541).
"""

from __future__ import annotations

import numpy as np


def prefix_values_f64(oracle: str, rows, ref=None) -> np.ndarray:
    """f of rows[:1], rows[:2], ..., rows[:m] in float64; (m,)."""
    rows = np.asarray(rows, np.float64)
    if oracle == "feature_coverage":
        return np.sqrt(np.cumsum(rows, axis=0)).sum(axis=1)
    if oracle == "exemplar":
        ref = np.asarray(ref, np.float64)
        m0 = (ref * ref).sum(axis=1)
        d2 = m0[:, None] - 2.0 * ref @ rows.T + (rows * rows).sum(axis=1)[None]
        best = np.minimum.accumulate(np.minimum(d2, m0[:, None]), axis=1)
        return (m0[:, None] - best).sum(axis=0)
    raise ValueError(f"no reference for oracle {oracle!r}")


def value_f64(oracle: str, rows, ref=None) -> float:
    """f(S) in float64 from the rows of S; f of the empty set is 0."""
    if len(rows) == 0:
        return 0.0
    return float(prefix_values_f64(oracle, rows, ref)[-1])


def greedy_ids(oracle: str, X, k: int, ref=None):
    """Ids of k classic greedy steps over the corpus X (n, d), on X's
    device(s); a step whose best gain is not positive selects nothing (-1).
    """
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def run(X, ref):
        n = X.shape[0]
        if oracle == "feature_coverage":
            def gains(s):
                return jnp.sum(jnp.sqrt(s[None, :] + X) - jnp.sqrt(s)[None, :],
                               axis=1)

            def add(s, i):
                return s + X[i]
            s0 = jnp.zeros((X.shape[1],), jnp.float32)
        elif oracle == "exemplar":
            m0 = jnp.sum(ref * ref, axis=1)
            d2 = jnp.maximum(
                m0[None, :] - 2.0 * jnp.matmul(X, ref.T, precision=hi)
                + jnp.sum(X * X, axis=1)[:, None], 0.0)          # (n, r)

            def gains(s):
                return jnp.sum(jnp.maximum(s[None, :] - d2, 0.0), axis=1)

            def add(s, i):
                return jnp.minimum(s, d2[i])
            s0 = m0
        else:
            raise ValueError(f"no reference for oracle {oracle!r}")

        def body(t, carry):
            s, ids, taken = carry
            g = jnp.where(taken, -jnp.inf, gains(s))
            i = jnp.argmax(g)
            ok = g[i] > 0.0
            s = jnp.where(ok, add(s, i), s)
            ids = ids.at[t].set(jnp.where(ok, i, -1).astype(jnp.int32))
            taken = taken.at[i].set(taken[i] | ok)
            return s, ids, taken

        _, ids, _ = jax.lax.fori_loop(
            0, k, body, (s0, jnp.full((k,), -1, jnp.int32),
                         jnp.zeros((n,), bool)))
        return ids

    return np.asarray(jax.jit(run)(X, ref))
