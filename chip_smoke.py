"""Smoke run of the selection system on a TPU, through its user entry points.

    python chip_smoke.py                # one chip: the five phases below
    python chip_smoke.py --chips 4      # four chips: the mesh paths only,
                                        # each against its vmap simulation

The corpus is generated on the device from ``--seed`` at the widths of a
public deployment: exemplar-based clustering of Tiny Images as run by GreeDi
(Mirzasoleiman et al., arXiv 1411.0541) — rows of 32x32x3 = 3,072 features
with values in [0, 1].  Its ~80 M rows are cut to what one chip holds
beside the selection step's temporaries; k, the reference-set size and the
request mix are assumptions.  Every cut and assumption is printed before
the phases run.

One chip (``--chips 1``, the default) runs, in one process:

1. device check — anything but a TPU exits non-zero, there is no fallback;
2. two-round selection: ``DistributedSelector`` (feature coverage, fused
   engine, Pallas kernels) on a one-device mesh;
3. multi-epoch selection, the same selector with eps = 0.25;
4. exemplar selection against a reference set, same selector and kernels;
5. the selection service (``SelectionService`` + ``ServeLoop``): mixed
   budgets in fixed slots over the same corpus.

Each phase checks: ids unique and in range with ``|S| <= k``; f(S)
recomputed on the host in float64 from the returned ids matches the device
value within :data:`VALUE_RTOL`; f(S) >= (1/2 - eps) of sequential greedy
on the same corpus (greedy runs on the chip, on the plain jnp oracle); and
the compiled step holds ``tpu_custom_call`` (the kernels compiled natively,
none was interpreted).  Any failed check exits non-zero.

Four chips (``--chips 4``) run only what exists across chips: the two-round
selector on a 4-device mesh (survivor ``all_gather``) against the same
epoch engine on the vmap substrate, and ``sieve_and_merge_mesh`` against
``sieve_and_merge_sim``: ids equal, values within :data:`VALUE_RTOL`.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# -- the deployment (GreeDi exemplar clustering of Tiny Images) and its cuts
D = 3_072              # 32 x 32 x 3 pixels per row (the source's width)
N = 262_144            # cut from ~80 M rows: the most one chip holds with
#                        the two-round step's survivor buffers (~7.5 GB)
K = 64                 # assumed budget
R = 1_024              # assumed reference-set size (exemplar phase)
N_EXEMPLAR = 8_192     # cut: the exemplar filter recomputes the (n, r, d)
#                        distance matmul once per threshold lane (37 lanes):
#                        2 * 37 * n * r * d = 61 PFLOP at n = N, in f32
N_SERVICE = 4_096      # cut: the Q-slot program holds Q * 37 lanes of
#                        4 * sqrt(n * k) survivor rows of d floats, 7.7 GiB
#                        at this n (10.5 GiB at 8,192)
N_COMPARE = 131_072    # cut (--chips 4): the vmap sim of 4 machines holds
#                        every machine's survivor stack on one chip, twice:
#                        17.15 GB at n = N, over the v5e's 15.75 GB
EPS_MULTI = 0.25
SLOTS, REQUESTS = 8, 16
#: relative tolerance of a device f(S) against the float64 host recompute:
#: f32 sums of up to d = 3,072 terms carry a worst-case relative error of
#: d * 2**-24 = 1.8e-4; 2e-4 leaves no room for a lower-precision pass
VALUE_RTOL = 2e-4


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


# ---------------------------------------------------------------------------
# host references (float64, plain numpy, independent of the code under test)
# ---------------------------------------------------------------------------

def coverage_value(rows):
    """Feature coverage f(S) = sum_f sqrt(sum_{e in S} x_ef)."""
    import numpy as np
    return float(np.sqrt(rows.sum(axis=0)).sum())


def exemplar_value(rows, ref):
    """Exemplar clustering f(S) = L({0}) - L(S + {0}),
    L(S) = sum_v min_{e in S} ||v - x_e||^2 (phantom exemplar at 0)."""
    import numpy as np
    m0 = (ref * ref).sum(axis=1)
    d2 = m0[:, None] - 2.0 * ref @ rows.T + (rows * rows).sum(axis=1)[None]
    return float((m0 - np.minimum(m0, d2.min(axis=1))).sum())


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def selected(res, n: int, k: int, name: str):
    """Validated ids of one SelectionResult (1-D fields)."""
    import numpy as np
    ids = np.asarray(res.sol_ids)
    size = int(res.sol_size)
    sel = ids[ids >= 0]
    check(size <= k, f"{name}: |S| = {size} > k = {k}")
    check(sel.size == size, f"{name}: {sel.size} ids for |S| = {size}")
    check(np.unique(sel).size == sel.size, f"{name}: repeated ids")
    check(bool(np.all(sel < n)), f"{name}: id out of range")
    check(int(res.n_dropped) == 0, f"{name}: {int(res.n_dropped)} dropped")
    return sel


def check_value(name: str, dev: float, host: float) -> float:
    gap = abs(dev - host) / abs(host)
    log(f"{name}: f(S) device {dev!r} host-f64 {host!r} rel gap {gap:.3e} "
        f"(tolerance {VALUE_RTOL:g})")
    check(gap <= VALUE_RTOL, f"{name}: device f(S) off the float64 "
          f"recompute by {gap:.3e} > {VALUE_RTOL:g}")
    return gap


def check_ratio(name: str, value: float, greedy: float, eps: float) -> None:
    ratio = value / greedy
    log(f"{name}: f(S) / greedy = {ratio:.6f} (bound 1/2 - eps = "
        f"{0.5 - eps:.3f})")
    check(ratio >= 0.5 - eps, f"{name}: ratio {ratio:.4f} < {0.5 - eps}")


def custom_calls(jitted, *args) -> int:
    """tpu_custom_call sites in the compiled program of ``jitted(*args)``
    (served from the compile cache the first call filled)."""
    return jitted.lower(*args).compile().as_text().count(
        "custom_call_target=\"tpu_custom_call\"")


def timed(fn, *args, **kw):
    import jax
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def peak_gb(dev) -> str:
    stats = dev.memory_stats() or {}
    return f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB"


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def run_selector_phase(name, sel, X, key, host_value, greedy_value, eps):
    """Drive ``sel.select`` twice (compile-bearing, then steady), then check
    ids, the host recompute, the greedy ratio and the kernels."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    res, t_first = timed(sel.select, X, key=key)
    res, t_steady = timed(sel.select, X, key=key)
    n = X.shape[0]
    sel_ids = selected(res, n, sel.spec.k, name)
    rows = np.asarray(X[jnp.asarray(sel_ids)], np.float64)
    value = float(res.value)
    check_value(name, value, host_value(rows))
    check_ratio(name, value, greedy_value, eps)
    n_kernels = custom_calls(sel._jitted, X, jnp.arange(n, dtype=jnp.int32),
                             key)
    check(n_kernels > 0, f"{name}: no tpu_custom_call in the compiled step")
    log(f"{name}: |S|={len(sel_ids)} first call {t_first:.2f}s (incl. "
        f"compile) steady {t_steady:.3f}s, {n_kernels} tpu_custom_call "
        f"sites, events {sel.runtime_events()}, device peak "
        f"{peak_gb(jax.devices()[0])}")
    log(sel.round_log.summary().replace("\n", "\n[chip_smoke]   "))
    return res


def run_greedy(oracle, X, k):
    import jax
    import jax.numpy as jnp
    from repro.core.sequential import greedy
    fn = jax.jit(lambda x: greedy(oracle, x, jnp.ones((x.shape[0],), bool),
                                  k))
    (ids, size, value), t = timed(fn, X)
    log(f"greedy reference (jnp oracle, on chip): |S|={int(size)} "
        f"f(S)={float(value)!r} in {t:.2f}s (incl. compile)")
    return float(value)


def corpus(key, n, sharding):
    """Uniform [0, 1) pixel rows, generated on the device(s) in place."""
    import jax
    import jax.numpy as jnp
    make = jax.jit(lambda k: jax.random.uniform(k, (n, D), jnp.float32),
                   out_shardings=sharding)
    X, t = timed(make, key)
    log(f"corpus ({n}, {D}) f32 generated on device in {t:.2f}s")
    return X


def one_chip(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import functions as F
    from repro.core.mapreduce import make_query_batch
    from repro.core.selector import DistributedSelector, SelectorSpec
    from repro.launch.mesh import make_mesh_for
    from repro.launch.select_serve import Request, SelectionService, ServeLoop

    mesh = make_mesh_for(1, model_parallel=1)
    key = jax.random.PRNGKey(seed)
    kx, ks, km, ke, kv = jax.random.split(key, 5)
    base = dict(k=K, engine="fused", use_kernel=True)

    # ---- 2. two-round ------------------------------------------------------
    spec = SelectorSpec(oracle="feature_coverage", algorithm="two_round",
                        **base)
    sel = DistributedSelector(spec, mesh, n_total=N, feat_dim=D)
    X = corpus(kx, N, sel.data_sharding())
    g_cov = run_greedy(F.FeatureCoverage(feat_dim=D), X, K)
    run_selector_phase("two_round", sel, X, ks, coverage_value, g_cov,
                       spec.eps)

    # ---- 3. multi-epoch ----------------------------------------------------
    spec_me = SelectorSpec(oracle="feature_coverage", algorithm="multi_epoch",
                           eps=EPS_MULTI, **base)
    sel_me = DistributedSelector(spec_me, mesh, n_total=N, feat_dim=D)
    run_selector_phase("multi_epoch", sel_me, X, km, coverage_value, g_cov,
                       spec_me.eps)
    del sel, sel_me

    # ---- 4. exemplar -------------------------------------------------------
    Xe = X[:N_EXEMPLAR]
    ref = X[:: N // R][:R]
    ref_host = np.asarray(ref, np.float64)
    spec_ex = SelectorSpec(oracle="exemplar", algorithm="two_round",
                           reference_size=R, **base)
    sel_ex = DistributedSelector(spec_ex, mesh, n_total=N_EXEMPLAR,
                                 feat_dim=D, reference=ref)
    g_ex = run_greedy(F.ExemplarClustering(feat_dim=D, reference=ref), Xe, K)
    run_selector_phase("exemplar", sel_ex, Xe, ke,
                       lambda rows: exemplar_value(rows, ref_host), g_ex,
                       spec_ex.eps)
    del sel_ex, Xe

    # ---- 5. service --------------------------------------------------------
    spec_sv = SelectorSpec(oracle="feature_coverage", algorithm="two_round",
                           **base)
    Xs = X[:N_SERVICE]
    del X
    g_sv = run_greedy(F.FeatureCoverage(feat_dim=D), Xs, K)
    svc = SelectionService(spec_sv, mesh, np.asarray(Xs))
    rehearse_batch(svc, kv)
    loop = ServeLoop(svc, SLOTS, kv)
    budgets = [K] + [int(b) for b in
                     np.random.default_rng(seed).integers(1, K + 1,
                                                          REQUESTS - 1)]
    for rid, b in enumerate(budgets):
        loop.submit(Request(id=rid, k=b))
    t0 = time.perf_counter()
    steps = []
    while len(loop.queue):
        t1 = time.perf_counter()
        loop.run_step()
        steps.append(time.perf_counter() - t1)
    t_serve = time.perf_counter() - t0
    check(len(loop.done) + len(loop.shed) == REQUESTS,
          f"service: served {len(loop.done)} + shed {len(loop.shed)} != "
          f"submitted {REQUESTS}")
    for row in loop.done:
        check(row["size"] <= row["k"], f"service: request {row['id']} got "
              f"{row['size']} > k = {row['k']}")
    # requests are admitted in order (no deadlines), so request 0
    # (k = spec.k) took slot 0 of step 0: replay that step's batch for its
    # ids, and hold the lane to its own select()
    lane = next(r for r in loop.done if r["id"] == 0)
    key0 = jax.random.fold_in(kv, 0)
    step0 = svc.select_batch(make_query_batch(budgets[:SLOTS]), key0)
    check(np.float32(step0.value[0]) == np.float32(lane["value"]),
          "service: replaying step 0 changed lane 0")
    alone = svc.selector.select(svc.materialize(), key=key0)
    check(np.array_equal(np.asarray(step0.sol_ids[0]),
                         np.asarray(alone.sol_ids)),
          f"service: lane 0 ids {np.asarray(step0.sol_ids[0]).tolist()} != "
          f"select() ids {np.asarray(alone.sol_ids).tolist()}")
    lane_ids = selected(alone, N_SERVICE, K, "service lane 0")
    check(abs(lane["value"] - float(alone.value)) <= VALUE_RTOL *
          abs(float(alone.value)), f"service: lane 0 f(S) {lane['value']!r}"
          f" != select() f(S) {float(alone.value)!r}")
    check_value("service lane 0", lane["value"], coverage_value(
        np.asarray(Xs[jnp.asarray(lane_ids)], np.float64)))
    check_ratio("service lane 0", lane["value"], g_sv, spec_sv.eps)
    n_kernels = custom_calls(svc.selector._batch_run,
                             *batch_args(svc, jax.random.fold_in(kv, 0)))
    check(n_kernels > 0, "service: no tpu_custom_call in the batch step")
    events = svc.selector.runtime_events()
    check(not events.get("kernel_bypassed"),
          f"service: kernels bypassed {events}")
    log(f"service: {len(loop.done)} served + {len(loop.shed)} shed of "
        f"{REQUESTS} in {len(steps)} steps of {SLOTS} slots, {t_serve:.2f}s "
        f"(first step {steps[0]:.2f}s incl. compile, then "
        f"{', '.join(f'{s:.3f}' for s in steps[1:])}s); lane 0 ids == "
        f"select() ids; "
        f"{n_kernels} tpu_custom_call sites; events {events}; device peak "
        f"{peak_gb(jax.devices()[0])}")
    log(svc.summary())


def batch_args(svc, key):
    import jax.numpy as jnp
    from repro.core.mapreduce import make_query_batch
    emb = svc.materialize()
    return (emb, jnp.arange(emb.shape[0], dtype=jnp.int32),
            make_query_batch([K] * SLOTS), key)


def rehearse_batch(svc, key) -> None:
    """Compile the service's Q-slot program before it serves and hold its
    memory_analysis against what the device has left after the earlier
    phases."""
    import jax
    from repro.core import mapreduce as mr
    sel = svc.selector
    run, _ = mr.two_round_batch_mesh(sel.oracle, sel.cfg, sel.mesh, sel.axes,
                                     data_spec=sel._data_spec)
    t0 = time.perf_counter()
    ma = jax.jit(run).lower(*batch_args(svc, key)).compile().memory_analysis()
    need = ma.temp_size_in_bytes + ma.argument_size_in_bytes + \
        ma.output_size_in_bytes
    stats = jax.devices()[0].memory_stats() or {}
    free = stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)
    log(f"service rehearsal: Q={SLOTS} program compiled in "
        f"{time.perf_counter() - t0:.1f}s, needs {need / 2**30:.2f} GiB "
        f"(temp {ma.temp_size_in_bytes / 2**30:.2f}); device holds "
        f"{stats.get('bytes_in_use', 0) / 2**30:.2f} GiB from earlier "
        f"phases, {free / 2**30:.2f} GiB free")
    check(need <= free, f"service: the Q={SLOTS} program needs "
          f"{need / 2**30:.2f} GiB, the device has {free / 2**30:.2f} free")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def four_chips(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import functions as F
    from repro.core import mapreduce as mr
    from repro.core import rounds
    from repro.core.selector import DistributedSelector, SelectorSpec
    from repro.launch.mesh import make_mesh_for
    from repro.streaming import SieveSpec
    from repro.streaming.distributed_sieve import (sieve_and_merge_mesh,
                                                   sieve_and_merge_sim)

    m = 4
    mesh = make_mesh_for(m, model_parallel=1)
    key = jax.random.PRNGKey(seed)
    kx, ks = jax.random.split(key)
    spec = SelectorSpec(k=K, oracle="feature_coverage", algorithm="two_round",
                        engine="fused", use_kernel=True)

    class MeshKeyedSimRounds(rounds.SimRounds):
        """The vmap substrate with the mesh's per-machine sample keys
        (fold_in of the raw key by machine index) — two_round_sim splits
        its key instead, so its samples, and so its ids, differ by
        construction, not by a fault of either substrate."""

        def sample(self, key, p, cap):
            keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
                jnp.arange(self.m))
            sf, si, sv, sdrop = jax.vmap(
                lambda ky, f, i, v: rounds.local_sample(
                    self.oracle, ky, f, i, v, p, cap)
            )(keys, self.feats_mk, self.ids_mk, self.valid_mk)
            return ((sf.reshape(self.m * cap, -1), si.reshape(-1),
                     sv.reshape(-1)), jnp.sum(sdrop))

    def sim_two_round(sel, n):
        def run(x, i, key):
            rr = MeshKeyedSimRounds(sel.oracle, x.reshape(m, n // m, D),
                                    i.reshape(m, n // m),
                                    jnp.ones((m, n // m), bool),
                                    precision=sel.cfg.precision_policy)
            return mr._epoch_select(sel.oracle, rr, sel.cfg, [key], 1,
                                    sel.cfg.schedule_kind)
        return jax.jit(run)

    # ---- two-round on the 4-chip mesh at full n ---------------------------
    sel = DistributedSelector(spec, mesh, n_total=N, feat_dim=D)
    X = corpus(kx, N, sel.data_sharding())
    res, t_first = timed(sel.select, X, key=ks)
    res, t_steady = timed(sel.select, X, key=ks)
    ids = selected(res, N, K, "two_round mesh")
    check_value("two_round mesh", float(res.value),
                coverage_value(np.asarray(X[jnp.asarray(ids)], np.float64)))
    n_kernels = custom_calls(sel._jitted, X, jnp.arange(N, dtype=jnp.int32),
                             ks)
    check(n_kernels > 0, "two_round mesh: no tpu_custom_call")
    log(f"two_round mesh m={m} n={N}: first call {t_first:.2f}s (incl. "
        f"compile) steady {t_steady:.3f}s, {n_kernels} tpu_custom_call "
        f"sites, device peaks {[peak_gb(dv) for dv in jax.devices()]}")
    log(sel.round_log.summary().replace("\n", "\n[chip_smoke]   "))

    # ---- two-round: shard_map + all_gather vs the vmap substrate ----------
    sel_c = DistributedSelector(spec, mesh, n_total=N_COMPARE, feat_dim=D)
    Xc = jax.device_put(X[:N_COMPARE], sel_c.data_sharding())
    ids_c = jnp.arange(N_COMPARE, dtype=jnp.int32)
    res_mesh, t_mesh = timed(sel_c.select, Xc, key=ks)
    dev0 = jax.devices()[0]
    res_sim, t_sim = timed(sim_two_round(sel_c, N_COMPARE),
                           jax.device_put(Xc, dev0),
                           jax.device_put(ids_c, dev0), ks)
    compare(f"two_round n={N_COMPARE}", res_mesh, res_sim, Xc)
    log(f"two_round n={N_COMPARE}: mesh {t_mesh:.2f}s, vmap sim on one "
        f"chip {t_sim:.2f}s (both incl. compile)")
    del Xc, sel_c

    # ---- sieve-and-merge: mesh vs sim at full n ----------------------------
    oracle = F.FeatureCoverage(feat_dim=D, use_kernel=True)
    sspec = SieveSpec(k=K, engine="fused")
    all_ids = jnp.arange(N, dtype=jnp.int32)
    run, slog = sieve_and_merge_mesh(oracle, sspec, mesh)
    res_sm, t_sm = timed(jax.jit(run), X, all_ids)
    res_ss, t_ss = timed(jax.jit(
        lambda x, i: sieve_and_merge_sim(
            oracle, x.reshape(m, N // m, D), i.reshape(m, N // m),
            jnp.ones((m, N // m), bool), sspec)[0]),
        jax.device_put(X, dev0), jax.device_put(all_ids, dev0))
    compare("sieve_and_merge", res_sm, res_ss, X)
    log(f"sieve_and_merge n={N}: mesh {t_sm:.2f}s, sim on one chip "
        f"{t_ss:.2f}s (both incl. compile); device peaks "
        f"{[peak_gb(dv) for dv in jax.devices()]}")
    log(slog.summary().replace("\n", "\n[chip_smoke]   "))


def compare(name, res_mesh, res_sim, X) -> None:
    import jax.numpy as jnp
    import numpy as np
    n = X.shape[0]
    a = selected(res_mesh, n, K, f"{name} mesh")
    b = selected(res_sim, n, K, f"{name} sim")
    check(np.array_equal(np.asarray(res_mesh.sol_ids),
                         np.asarray(res_sim.sol_ids)),
          f"{name}: mesh ids {a.tolist()} != sim ids {b.tolist()}")
    vm, vs = float(res_mesh.value), float(res_sim.value)
    gap = abs(vm - vs) / abs(vs)
    check(gap <= VALUE_RTOL, f"{name}: mesh f(S) {vm!r} vs sim {vs!r}")
    check_value(f"{name} mesh", vm,
                coverage_value(np.asarray(X[jnp.asarray(a)], np.float64)))
    log(f"{name}: mesh ids == sim ids (|S|={a.size}), f(S) mesh {vm!r} "
        f"sim {vs!r} rel gap {gap:.3e}")


# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the five single-chip phases; 4: only the "
                         "mesh paths, each against its vmap simulation")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        fail(f"the repro package is not beside chip_smoke.py ({e})")
    import jax

    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX sees {len(devices)} {dev.platform!r} "
             f"device(s); this smoke runs on the chip only")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(devices)} TPU(s)")
    log(f"device: {dev.device_kind!r} x{len(devices)}, jax "
        f"{jax.__version__}, compile cache {cache}")
    log(f"deployment: GreeDi exemplar clustering of Tiny Images "
        f"(arXiv 1411.0541), d={D}; cut: n ~80M -> {N}; assumed: k={K}, "
        f"r={R} reference rows, {REQUESTS} requests in {SLOTS} slots")
    if args.chips == 1:
        log(f"cuts: exemplar phase n={N_EXEMPLAR}, service corpus "
            f"n={N_SERVICE} (see the constants for why)")
    else:
        log(f"cut: mesh-vs-sim two-round compared at n={N_COMPARE} (the "
            f"sim of 4 machines must fit one chip); mesh alone at n={N}")
    t0 = time.perf_counter()
    (one_chip if args.chips == 1 else four_chips)(args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
