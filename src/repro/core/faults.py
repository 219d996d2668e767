"""Deterministic fault injection for the round-primitives layer.

Real MapReduce deployments lose machines mid-round; the paper's sample
round is naturally loss-tolerant (random partitioning means losing
machines is statistically a smaller sample — the observation exploited by
Barbosa et al. 2015 and the RandGreeDi line).  This module makes that
robustness explicit, injectable, and *measured*:

* ``FaultPlan`` — a seeded, stateless chaos schedule: per-epoch shard
  loss, per-gather dropped / corrupted messages, and stragglers that miss
  the round deadline.  Every mask is a pure function of
  (seed, fault kind, epoch-or-round index), so a plan realizes the same
  faults on every trace, on both backends, and across process restarts.
* ``FaultyRounds`` — a wrapper conforming to the SimRounds/MeshRounds
  five-op contract that injects the plan's faults at the gather
  boundaries, records every event as a ``FaultRecord`` in the driver's
  RoundLog, and compensates where the math allows (boosting the Bernoulli
  sample probability for shards known lost at epoch start).

Degradation model (see DESIGN.md §9): a fault never silently corrupts a
selection — affected rows are invalidated before the central accept (with
corrupted rows additionally scrambled to a finite canary so accidental
consumption is loud), every event is recorded, and the result carries a
``degraded`` flag plus a guarantee ``haircut`` = the worst per-round
survivor fraction.  With ``plan=None`` (or an all-zero plan realizing no
faults) the wrapper is a pure pass-through: bit-identical to the bare
substrate.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.rounds import (FaultRecord, RoundLog,
                               filter_accept_gathered)

#: fault kinds a FaultPlan can realize, in record order
FAULT_KINDS = ("shard_loss", "msg_drop", "msg_corrupt", "straggler")

#: corrupted rows get every feature column set to this before they are
#: invalidated — large and *finite* (a NaN would survive where-masked
#: reductions as quiet poison), so a consumed corrupted row shows up as an
#: absurd value instead of a plausible one
CORRUPT_CANARY = 1.0e30


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """A deterministic chaos schedule.

    Rates are per-machine Bernoulli probabilities: ``loss_rate`` is drawn
    once per *epoch* (the machine is gone for both of that epoch's
    rounds — its messages vanish and the sample probability is boosted to
    compensate); the other three are drawn per *gather* (transient: the
    machine is back next round, and no compensation is applied).
    Stragglers model a machine that answers after the round deadline —
    under a synchronous barrier that is indistinguishable from a drop, so
    the injected effect is the timeout outcome and ``straggler_deadline_ms``
    is reporting detail.
    """
    loss_rate: float = 0.0
    drop_rate: float = 0.0
    corrupt_rate: float = 0.0
    straggler_rate: float = 0.0
    straggler_deadline_ms: float = 50.0
    seed: int = 0

    def __post_init__(self):
        for name in ("loss_rate", "drop_rate", "corrupt_rate",
                     "straggler_rate"):
            r = getattr(self, name)
            if not 0.0 <= float(r) <= 1.0:
                raise ValueError(f"FaultPlan: {name}={r} not in [0, 1]")

    @property
    def active(self) -> bool:
        return (self.loss_rate > 0 or self.drop_rate > 0
                or self.corrupt_rate > 0 or self.straggler_rate > 0)

    def _draw(self, tag: int, idx: int, rate: float, m: int) -> np.ndarray:
        """Stateless Bernoulli mask over m machines: keyed by
        (seed, kind tag, epoch/round index), so the same call always
        realizes the same machines regardless of call order or retraces."""
        if rate <= 0.0:
            return np.zeros(m, bool)
        rng = np.random.default_rng([int(self.seed) & 0x7FFFFFFF, tag, idx])
        return rng.random(m) < rate

    def loss_mask(self, epoch: int, m: int) -> np.ndarray:
        """Machines lost for the whole of ``epoch``.  Spare-one guard:
        losing *every* shard is a total outage, not a degraded run — the
        layer above must abort/retry, so the plan never realizes it (one
        rotating machine is spared instead, and DESIGN.md §9 documents the
        abort boundary)."""
        lost = self._draw(0, epoch, self.loss_rate, m)
        if lost.all():
            lost[epoch % m] = False
        return lost

    def round_masks(self, round_index: int, m: int) -> Dict[str, np.ndarray]:
        """The transient per-gather masks for gather #``round_index``."""
        return {
            "msg_drop": self._draw(1, round_index, self.drop_rate, m),
            "msg_corrupt": self._draw(2, round_index, self.corrupt_rate, m),
            "straggler": self._draw(3, round_index, self.straggler_rate, m),
        }

    def grid_pad(self, eps: float) -> int:
        """Extra unknown-OPT grid points: lost shards can depress the
        sampled max-singleton estimate v by roughly the loss fraction, and
        the tau grid ascends from v/2k — so keeping OPT covered costs
        ~log_{1+eps} 1/(1-loss) more points."""
        r = min(float(self.loss_rate), 0.75)
        if r <= 0.0:
            return 0
        return int(math.ceil(math.log(1.0 / (1.0 - r)) / math.log1p(eps)))


def chaos_plan(rate: float, seed: int = 0) -> Optional[FaultPlan]:
    """The launcher/CI chaos profile for a single ``--fault-rate`` knob:
    shard loss at the full rate (the dominant real-world failure), message
    drops at half, corruption and stragglers at a quarter each.  rate=0
    returns None — the un-wrapped fast path."""
    rate = float(rate)
    if rate <= 0.0:
        return None
    return FaultPlan(loss_rate=rate, drop_rate=rate / 2,
                     corrupt_rate=rate / 4, straggler_rate=rate / 4,
                     seed=seed)


class FaultyRounds:
    """Fault-injecting wrapper over a SimRounds/MeshRounds substrate.

    Conforms to the same five-op contract (sample / tops / filter /
    filter_grid / finalize_drops, plus the ``begin_epoch`` boundary hook
    and ``filter_accept_grid``, here always one block),
    so every epoch-engine driver runs over it unmodified.  Faults are
    realized HOST-SIDE from the plan's stateless draws at trace time: both
    backends issue the same op sequence in the same order, so the realized
    masks — and the FaultRecords — are identical on sim and mesh by
    construction.  Attribute access (oracle, constraint, feat_dim, ...)
    delegates to the wrapped substrate.
    """

    def __init__(self, inner, plan: Optional[FaultPlan], log: RoundLog,
                 m: int, n_total: int):
        self.inner = inner
        self.plan = plan if (plan is not None and plan.active) else None
        self.log = log
        self.m = int(m)
        self.n_total = int(n_total)
        self._round = 0
        self._epoch: Optional[int] = None
        self._lost = np.zeros(self.m, bool)
        #: the last degrade()'s realized dead-machine mask (np bool (m,)),
        #: or None when that gather was clean — single-gather drivers (the
        #: distributed sieve) read it to mask their ride-along statistics
        self.last_dead: Optional[np.ndarray] = None
        # a driver may retrace (jit of a shard_map'd body, vmap re-entry):
        # the records are rebuilt from scratch per trace, never duplicated
        log.faults.clear()

    def __getattr__(self, name):
        if name == "inner":
            raise AttributeError(name)
        return getattr(self.inner, name)

    @property
    def survivors(self) -> int:
        return self.m - int(self._lost.sum())

    def _eff_n(self, eff_machines: int) -> int:
        return int(round(self.n_total * eff_machines / self.m))

    # -- epoch boundary ----------------------------------------------------

    def begin_epoch(self, e: int) -> None:
        # ``inner=None`` is the shim mode for single-gather drivers that
        # are not five-op substrates (the distributed sieve): only
        # degrade() is used, nothing delegates
        if self.inner is not None:
            self.inner.begin_epoch(e)
        if self.plan is None or self._epoch == e:
            return
        self._epoch = e
        self._lost = self.plan.loss_mask(e, self.m)
        down = np.flatnonzero(self._lost)
        if down.size:
            eff = self.m - int(down.size)
            self.log.fault(FaultRecord(
                "shard_loss", e, self._round,
                tuple(int(x) for x in down), self.m, eff, self._eff_n(eff),
                f"epoch {e}: {down.size}/{self.m} shards lost; sample_p "
                f"boosted x{self.m / max(eff, 1):.3f}"))

    def _ensure_epoch(self) -> None:
        # the unknown-OPT drivers draw epoch 1's sample before run_epochs
        # announces the epoch — realize epoch 0's loss mask lazily
        if self._epoch is None:
            self.begin_epoch(0)

    # -- gather-boundary fault application ---------------------------------

    def degrade(self, gathered, drops):
        """Apply this gather's transient faults plus the epoch loss mask to
        a machine-major packed triple (rows [c*cap, (c+1)*cap) belong to
        machine c; any leading grid/query axes broadcast).  Also the hook
        the batched mesh driver calls on its manually-gathered stacks."""
        self._ensure_epoch()
        f, i, v = gathered
        r = self._round
        self._round += 1
        self.last_dead = None
        if self.plan is None:
            return gathered, drops
        masks = self.plan.round_masks(r, self.m)
        dead = self._lost.copy()
        detail = {
            "msg_drop": "gather message dropped",
            "msg_corrupt": "gather message corrupted (detected, discarded)",
            "straggler": (f"reply past the "
                          f"{self.plan.straggler_deadline_ms:g}ms round "
                          "deadline (counted out)"),
        }
        for kind in ("msg_drop", "msg_corrupt", "straggler"):
            mk = masks[kind] & ~dead
            if not mk.any():
                continue
            dead |= mk
            eff = self.m - int(dead.sum())
            self.log.fault(FaultRecord(
                kind, self._epoch or 0, r,
                tuple(int(x) for x in np.flatnonzero(mk)), self.m, eff,
                self._eff_n(eff), detail[kind]))
        if not dead.any():
            return gathered, drops
        self.last_dead = dead
        cap = i.shape[-1] // self.m
        corrupt = masks["msg_corrupt"] & ~self._lost
        if corrupt.any():
            crow = jnp.asarray(np.repeat(corrupt, cap))
            f = jnp.where(crow[:, None], jnp.asarray(CORRUPT_CANARY, f.dtype),
                          f)
        keep = jnp.asarray(np.repeat(~dead, cap))
        return (f, i, v & keep), drops

    # -- the five ops ------------------------------------------------------

    def sample(self, key, p, cap):
        self._ensure_epoch()
        s = self.survivors
        if self.plan is not None and s < self.m:
            # shards lost at epoch start are *known* lost: boost the
            # Bernoulli rate so the survivors' union keeps the expected
            # p*n sample size the caps and tau estimates are built on
            p = min(1.0, p * self.m / max(s, 1))
        return self.degrade(*self.inner.sample(key, p, cap))

    def tops(self, oracle, cap):
        return self.degrade(*self.inner.tops(oracle, cap))

    def filter(self, oracle, st, sol, size, cstate, tau, cap, k, chunk):
        return self.degrade(*self.inner.filter(oracle, st, sol, size, cstate,
                                               tau, cap, k, chunk))

    def filter_grid(self, oracle, st_j, sol_j, size_j, cstate_j, taus, cap,
                    k, chunk):
        return self.degrade(*self.inner.filter_grid(
            oracle, st_j, sol_j, size_j, cstate_j, taus, cap, k, chunk))

    def filter_accept_grid(self, oracle, carry, taus, cap, k, chunk, accept):
        # one block: the faults land on the whole gathered stack at once
        return filter_accept_gathered(self, oracle, carry, taus, cap, k,
                                      chunk, accept)

    def finalize_drops(self, drops):
        return self.inner.finalize_drops(drops)


def with_faults(rr, plan: Optional[FaultPlan], log: RoundLog, m: int,
                n_total: int):
    """Wrap a substrate when a fault plan is configured.  ``plan=None``
    returns the substrate untouched, so the production fast path traces
    exactly as before."""
    if plan is None:
        return rr
    return FaultyRounds(rr, plan, log, m, n_total)


def degrade_gathered(rr, gathered, drops):
    """Apply ``rr``'s fault injection to a manually-gathered packed triple
    (the batched mesh driver gathers its query stacks outside the five
    ops).  Identity when ``rr`` is a bare substrate."""
    if isinstance(rr, FaultyRounds):
        return rr.degrade(gathered, drops)
    return gathered, drops


def fault_summary(log: RoundLog) -> Tuple[bool, float]:
    """(degraded?, haircut) from a driver's recorded faults.

    The haircut is the worst per-round survivor fraction (M-m)/M: under
    random partitioning the optimum's elements land uniformly across
    machines, so losing m of M shards in a round preserves
    E[f(OPT ∩ survivors)] >= ((M-m)/M) f(OPT) for monotone submodular f —
    every downstream approximation factor scales by that fraction, and the
    worst round bounds the run (DESIGN.md §9 derives this)."""
    if not log.faults:
        return False, 1.0
    frac = min(rec.eff_machines / rec.n_machines for rec in log.faults)
    return True, float(frac)


def apply_fault_flags(res, log: RoundLog):
    """Stamp ``degraded``/``haircut`` onto a SelectionResult from the
    RoundLog's fault records.  No records — including the plan=None fast
    path — returns ``res`` unchanged (bit-identity preserved)."""
    degraded, haircut = fault_summary(log)
    if not degraded:
        return res
    return res._replace(degraded=jnp.ones((), jnp.int32),
                        haircut=jnp.asarray(haircut, jnp.float32))
