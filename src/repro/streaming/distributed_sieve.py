"""Distributed sieve-and-merge: every machine sieves its local stream,
the packed survivors are gathered once, and a central completion finishes
with the existing ThresholdGreedy engines.

This is the GreeDi / randomized-core-set shape (Mirzasoleiman et al.;
Barbosa et al.) on the repo's substrates: "each shard compresses its
stream, a central machine finishes".  Compared with `two_round_mesh` it
trades the Bernoulli-sample round for a *single* gather — one round, one
pass over every shard — at the cost of the weaker one-pass constant; the
central completion over the pooled survivors recovers most of the gap in
practice (benchmarks/streaming.py reports the value-ratio table).

Like mapreduce.py, the same per-shard local function runs on two
substrates:

* `sieve_and_merge_sim`  — machines as a leading vmap axis (executable
  MRC model, used by the parity tests/benchmarks);
* `sieve_and_merge_mesh` — machines as mesh axes under shard_map; the
  survivor gather is one `lax.all_gather` and the completion runs
  redundantly replicated (DESIGN.md §2), with RoundLog byte accounting
  identical in structure to `two_round_mesh`'s.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import faults as faults_mod
from repro.core.mapreduce import SelectionResult
from repro.core.rounds import RoundLog, gather_packed, log_gather
from repro.core.threshold import pack_by_mask
from repro.streaming.sieve import (SieveSpec, merge_pool, sieve_best,
                                   sieve_chunks, sieve_init, sieve_update)


def _pool_cap(spec: SieveSpec, cap: Optional[int]) -> int:
    # every lane can contribute k survivors; the default cap is lossless
    return cap or spec.lanes * spec.k


def _local_sieve(oracle, spec: SieveSpec, feats, ids, valid,
                 chunk_elems: int, cap: int):
    """One machine's half: sieve the local stream chunk-by-chunk, then pack
    the union of lane solutions (features + ids) to the message cap,
    prioritized by lane value so a tight cap keeps the best lanes whole."""
    state = sieve_init(oracle, spec, feats.shape[-1])
    fs, is_, vs = sieve_chunks(feats, ids, valid, chunk_elems)

    def step(st, chunk):
        f, i, v = chunk
        return sieve_update(oracle, spec, st, f, i, v), None

    state, _ = jax.lax.scan(step, state, (fs, is_, vs))

    L, k = spec.lanes, spec.k
    d = feats.shape[-1]
    lane_vals = jax.vmap(oracle.value)(state.oracle_states)    # (L,)
    prio = jnp.broadcast_to(lane_vals[:, None], (L, k)).reshape(L * k)
    pool_feats = state.sol_feats.reshape(L * k, d)
    pool_ids = state.sol_ids.reshape(L * k)
    pf, pi, pv, dropped = pack_by_mask(pool_feats, pool_ids, pool_ids >= 0,
                                       cap, priority=prio)
    # the top-singleton reservoir rides along uncapped (it is already the
    # Algorithm-7 message size, O(k) per machine)
    pf = jnp.concatenate([pf, state.top_feats])
    pi = jnp.concatenate([pi, state.top_ids])
    pv = jnp.concatenate([pv, state.top_ids >= 0])
    b_sol, b_size, b_val = sieve_best(oracle, state)
    return pf, pi, pv, dropped, state.v_max, b_sol, b_size, b_val


def sieve_and_merge_sim(oracle, feats_mk, ids_mk, valid_mk, spec: SieveSpec,
                        chunk_elems: int = 512,
                        pool_cap: Optional[int] = None,
                        faults: Optional[faults_mod.FaultPlan] = None
                        ) -> Tuple[SelectionResult, RoundLog]:
    """Sieve-and-merge with the m machines as a vmap axis.
    feats_mk: (m, n/m, d) — the same layout the MapReduce sims take.
    ``faults`` injects the plan's epoch-0/gather-0 faults on the single
    survivor gather (the ride-along best-lane/v_max statistics of dead
    machines are masked too — a lost shard contributes nothing)."""
    m, n_loc, d = feats_mk.shape
    cap = _pool_cap(spec, pool_cap)
    msg = cap + spec.tops     # packed lane survivors + top-singleton ride
    log = RoundLog()

    pf, pi, pv, dropped, v_loc, b_sol, b_size, b_val = jax.vmap(
        lambda f, i, v: _local_sieve(oracle, spec, f, i, v, chunk_elems, cap)
    )(feats_mk, ids_mk, valid_mk)
    log_gather(log, "gather-sieve-survivors", msg, m, d,
               f"L={spec.lanes} lanes, pool cap={cap}+top "
               f"{spec.tops}/machine",
               itemsize=spec.precision_policy.storage_itemsize)

    pool = (pf.reshape(m * msg, d), pi.reshape(-1), pv.reshape(-1))
    b_eff = jnp.where(b_size > 0, b_val, -jnp.inf)
    v_all = v_loc
    if faults is not None:
        w = faults_mod.FaultyRounds(None, faults, log, m, m * n_loc)
        pool, _ = w.degrade(pool, jnp.zeros((), jnp.int32))
        if w.last_dead is not None:
            dm = jnp.asarray(w.last_dead)
            b_eff = jnp.where(dm, -jnp.inf, b_eff)
            v_all = jnp.where(dm, -jnp.inf, v_all)

    # central completion on the gathered pool; the best local lane solution
    # rides along so merge never returns less than the best machine
    best = jnp.argmax(b_eff)
    ride_val = b_val[best] if faults is None else b_eff[best]
    res = merge_pool(oracle, spec, *pool, jnp.max(v_all),
                     b_sol[best], b_size[best],
                     jnp.maximum(ride_val, 0.0))
    res = res._replace(n_dropped=jnp.sum(dropped))
    return faults_mod.apply_fault_flags(res, log), log


def sieve_and_merge_mesh(oracle, spec: SieveSpec, mesh: Mesh,
                         axes=("data",), data_spec=None,
                         chunk_elems: int = 512,
                         pool_cap: Optional[int] = None,
                         faults: Optional[faults_mod.FaultPlan] = None):
    """Sieve-and-merge on a device mesh.  Returns a jit-able
    (feats_global, ids_global) -> SelectionResult plus the RoundLog.
    feats_global: (n, d) sharded over ``axes`` on dim 0; each shard is that
    machine's stream.  No RNG input: the whole driver is deterministic —
    including under ``faults``, whose seeded plan realizes the same dead
    machines as the sim driver (record parity by construction)."""
    axes = tuple(a for a in axes if a in mesh.shape)
    m = math.prod(mesh.shape[a] for a in axes)
    cap = _pool_cap(spec, pool_cap)
    gather_axes = axes if len(axes) > 1 else axes[0]
    data_spec = data_spec or P(axes if len(axes) > 1 else axes[0])
    ids_spec = P(data_spec[0])

    msg = cap + spec.tops
    log = RoundLog()
    log_gather(log, "gather-sieve-survivors", msg, m, oracle.feat_dim,
               f"L={spec.lanes} lanes, pool cap={cap}+top "
               f"{spec.tops}/machine",
               itemsize=spec.precision_policy.storage_itemsize)

    def body(feats, ids):
        valid = ids >= 0
        pf, pi, pv, dropped, v_loc, b_sol, b_size, b_val = _local_sieve(
            oracle, spec, feats, ids, valid, chunk_elems, cap)
        Pf = gather_packed(pf, gather_axes)
        Pi = gather_packed(pi, gather_axes)
        Pv = gather_packed(pv, gather_axes)
        pool = (Pf, Pi, Pv)
        v_all = jax.lax.all_gather(v_loc, gather_axes)
        # replicate every machine's best-lane candidate, keep the argmax
        b_vals = jax.lax.all_gather(jnp.where(b_size > 0, b_val, -jnp.inf),
                                    gather_axes)
        b_sols = jax.lax.all_gather(b_sol, gather_axes)
        b_sizes = jax.lax.all_gather(b_size, gather_axes)
        if faults is not None:
            w = faults_mod.FaultyRounds(None, faults, log, m,
                                        m * feats.shape[0])
            pool, _ = w.degrade(pool, jnp.zeros((), jnp.int32))
            if w.last_dead is not None:
                dm = jnp.asarray(w.last_dead)
                b_vals = jnp.where(dm, -jnp.inf, b_vals)
                v_all = jnp.where(dm, -jnp.inf, v_all)
        best = jnp.argmax(b_vals)
        res = merge_pool(oracle, spec, *pool, jnp.max(v_all), b_sols[best],
                         b_sizes[best], jnp.maximum(b_vals[best], 0.0))
        return res._replace(n_dropped=jax.lax.psum(dropped, gather_axes))

    fn = jax.shard_map(body, mesh=mesh, in_specs=(data_spec, ids_spec),
                       out_specs=P(), check_vma=False)

    def run(feats_global, ids_global):
        res = SelectionResult(*fn(feats_global, ids_global))
        return faults_mod.apply_fault_flags(res, log)

    return run, log
