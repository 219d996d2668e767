"""Continuous-batching selection service: many concurrent (oracle, k)
queries against one corpus, served by the batched two-round driver — with
deadline-aware admission, an online ingestion path that admits new
documents between serve steps, and checkpoint/restore of the online state
so a killed service warm-starts instead of re-ingesting.

    PYTHONPATH=src python -m repro.launch.select_serve --n 4096 --k 32 \
        --slots 8 --requests 24 --oracle graph_cut [--engine lazy] \
        [--deadline-ms 500] [--ingest-docs 512 --ingest-every 2] \
        [--checkpoint-dir ck --checkpoint-every 4] [--restore]

The serving analogue of launch/serve.py's token loop, for selection:
requests occupy a fixed number of SLOTS (the compiled program specializes
on the slot count Q, exactly like a serving batch dimension).  Each step
the admission queue fills free slots **earliest-deadline-first**; requests
whose deadline cannot be met even if served this step (the per-step
latency EWMA says the step would finish too late) are SHED — reported
with a reason and counted in the service stats, never silently dropped.
Every occupied slot is answered with ONE `DistributedSelector.select_batch`
call — one shared sample round, one gather round, Q answers — and retired
the same step, independently of the ingest cadence.  Unfilled slots are
masked with k=0 (they select nothing and cost no extra rounds).

Corpus-level statistics are computed ONCE at startup and cached across
every request on the corpus: the graph-cut / saturated-coverage
feature-sum ``total`` and the facility/exemplar reference set are
per-corpus, not per-query, so no request pays for them again — this is
the GreeDi-style amortization the paper's query-oblivious partition
enables.  (Under ingestion these statistics stay pinned at their
service-start values — the standard practice of a fixed reference
subsample / an a-priori total estimate — so the compiled programs and
the live sieve state stay valid as the corpus grows.)

`SelectionService.ingest()` is the online path (DESIGN.md §8): new
documents stream host->device through the out-of-core sieve
(repro.streaming), each document exactly once, ever; a subsequent
`select_warm()` reads the answer out of the live sieve state in O(L*k)
work — independent of the corpus size — instead of recomputing a full
MapReduce pass from scratch.

`SelectionService.save()/restore()` persist the online-path state (the
live sieve pytree + the host-corpus cursor + the service stats) through
`repro.checkpoint.Checkpointer` via the `repro.streaming.persist` codec:
a restarted service restores mid-stream and subsequent ingest()/
select_warm() calls are bit-identical to the uninterrupted run (the batch
path needs no persistence — it rebuilds from the corpus the caller hands
the restarted service).  `benchmarks/selection_slo.py` measures sustained
p50/p99 latency + QPS under this loop and asserts the kill/restore
parity.

Requests carry per-query budgets (k <= --k), optional deadlines, and,
where the oracle has the knob, per-query hyper-parameters (graph_cut lam
/ log_det alpha), so the slots genuinely serve *different* queries in one
program.  Per-request stats surface `tau_fallback` (degenerate-sample
events, split batch-path vs warm-path) and the service aggregates them,
so a silent no-signal corpus is visible in serving.
"""

from __future__ import annotations

import argparse
import dataclasses
import heapq
import math
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
# CLI choices derive from the central registries — registering a new
# oracle/engine/constraint makes it servable with no CLI edit
from repro.core.constraints import CONSTRAINT_NAMES, make_constraint
from repro.core.grids import SCHEDULE_KINDS
from repro.core.mapreduce import make_query_batch
from repro.core.precision import PRECISION_NAMES
from repro.core.selector import (DistributedSelector, OPT_FREE_ALGORITHMS,
                                 ORACLE_NAMES, SelectorSpec, make_oracle)
from repro.core.threshold import ENGINES
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh_for
from repro.streaming import SieveSpec, StreamingSelector
from repro.streaming import persist

#: errors a retry cannot cure: the same program fails the same way again
_NOT_TRANSIENT = (jax.errors.JaxRuntimeError, jax.errors.JAXTypeError,
                  jax.errors.JAXIndexError)


class SelectionService:
    """One corpus, two serve paths, shared statistics.

    * ``select_batch(requests, key)`` — the batched slot path: Q concurrent
      queries against the materialized corpus in one mesh program.
    * ``ingest(docs)`` / ``select_warm(budget)`` — the online path: new
      documents are absorbed into a live one-pass sieve (host-resident
      corpus, device sees one chunk at a time) and selections warm-start
      from its state instead of recomputing from scratch.
    * ``save(ckpt, step)`` / ``restore(ckpt)`` — online-state persistence:
      sieve state + stream cursor + stats through the Checkpointer, so a
      restart continues mid-stream bit-identically.

    Corpus statistics (reference / total) are computed once from the
    initial corpus and pinned for the service lifetime.  The host pin on
    the initial corpus itself is released once BOTH serve paths have
    consumed it (device copy materialized + sieve absorbed it) — a
    long-lived service holds one corpus, not two.
    """

    def __init__(self, spec: SelectorSpec, mesh, init_corpus,
                 reference=None, total=None, stream_chunk: int = 512,
                 constraint=None, retry_attempts: int = 3,
                 retry_backoff_s: float = 0.05):
        # corpus statistics are accumulate-plane quantities: compute them
        # in f32, then hold the corpus itself at the policy's storage dtype
        # (identity under the default f32 policy)
        init_corpus = np.asarray(init_corpus, np.float32)
        n0, d = init_corpus.shape
        self.spec, self.mesh, self.feat_dim = spec, mesh, d
        if reference is None and spec.oracle in ("facility_location",
                                                 "exemplar"):
            step = max(1, n0 // spec.reference_size)
            reference = jnp.asarray(init_corpus[::step][:spec.reference_size])
        if total is None and spec.oracle in ("graph_cut",
                                             "saturated_coverage"):
            total = jnp.asarray(init_corpus.sum(axis=0))
        self.reference, self.total = reference, total
        init_corpus = init_corpus.astype(spec.precision_policy.np_storage,
                                         copy=False)

        self.selector = DistributedSelector(
            spec, mesh, n_total=n0, feat_dim=d, reference=reference,
            total=total)
        self._emb = None          # materialized (device) corpus, batch path

        # the online path is built eagerly (cheap: jit closures + empty
        # state) but the initial corpus is only streamed through the sieve
        # on FIRST use of ingest()/select_warm() — a static-corpus serve
        # (no --ingest-docs) never pays the sieve compile or the n-row scan
        oracle = make_oracle(spec, d, reference=reference, total=total)
        # the constraint rides the ONLINE path only: the sieve honors it
        # per lane and at merge; the batched query path stays unconstrained
        # (per-query feasibility states don't compose with the shared
        # sample/gather rounds — the batch drivers refuse them loudly)
        sieve_spec = SieveSpec(k=spec.k, eps=spec.eps, accept=spec.accept,
                               engine=spec.engine, chunk=spec.chunk,
                               precision=spec.precision,
                               constraint=constraint)
        self.stream = StreamingSelector(oracle, sieve_spec, d,
                                        chunk_elems=stream_chunk)
        self._init_corpus = init_corpus
        self._stream_started = False
        self._init_used_batch = False
        self._init_used_stream = False
        # transient-failure policy for the serving paths (ingest absorb,
        # checkpoint writes): bounded retries with exponential backoff,
        # every retry and every exhausted failure counted — never silent
        self.retry_attempts = max(1, int(retry_attempts))
        self.retry_backoff_s = float(retry_backoff_s)
        self.stats = {"served": 0, "shed": 0, "deadline_miss": 0,
                      "tau_fallback_batch": 0, "tau_fallback_warm": 0,
                      "n_dropped": 0, "ingested": int(n0),
                      "warm_selects": 0, "ingest_retries": 0,
                      "ingest_failures": 0, "checkpoint_retries": 0}

    def _maybe_release_init(self):
        """Both serve paths hold their own copy now (device corpus / sieve
        state + host tail), so drop the host pin on the initial corpus —
        keeping it would double host memory per service, forever."""
        if self._init_used_batch and self._init_used_stream:
            self._init_corpus = None

    # ---- batched slot path ---------------------------------------------
    def materialize(self):
        """Device-put the initial corpus with the selector's sharding (the
        batch path serves the corpus the selector was built for)."""
        if self._emb is None:
            with self.mesh:
                self._emb = jax.device_put(jnp.asarray(self._init_corpus),
                                           self.selector.data_sharding())
            self._init_used_batch = True
            self._maybe_release_init()
        return self._emb

    def _ensure_stream(self):
        """First online-path use: absorb the initial corpus into the sieve
        (deferred from __init__ so static-corpus serving never pays it)."""
        if not self._stream_started:
            self._stream_started = True
            self.stream.ingest(self._init_corpus)
            self._init_used_stream = True
            self._maybe_release_init()

    def select_batch(self, queries, key):
        res = self.selector.select_batch(self.materialize(), queries, key)
        return res

    def account(self, rows):
        """Fold one step's retired rows into the service stats, from the
        ints each row already holds (``dropped``, ``tau_fallback``): one
        row per real request, so masked k=0 filler slots, which share the
        corpus-wide degenerate flag, never inflate the event counts."""
        self.stats["served"] += len(rows)
        self.stats["tau_fallback_batch"] += sum(r["tau_fallback"]
                                                for r in rows)
        self.stats["n_dropped"] += sum(r["dropped"] for r in rows)

    def account_shed(self, n_shed: int, n_miss: int = 0):
        """Deadline outcomes: ``n_shed`` requests refused at admission
        (their deadline was unmeetable) and ``n_miss`` served-but-late —
        both reported, neither silent."""
        self.stats["shed"] += n_shed
        self.stats["deadline_miss"] += n_miss

    # ---- online ingestion path -----------------------------------------
    def _retrying(self, what: str, fn):
        """Run ``fn`` with bounded retry + exponential backoff.  Each
        retried failure bumps ``<what>_retries``; exhaustion bumps
        ``<what>_failures`` and re-raises (the caller reports the reason —
        a failure is never swallowed here).  JAX/XLA errors (a program the
        compiler refuses, a device that ran out of memory, a tracing
        error) are not transient: they fail at once, without a retry."""
        for attempt in range(self.retry_attempts):
            try:
                return fn()
            except Exception as e:      # noqa: BLE001
                if attempt == self.retry_attempts - 1 or \
                        isinstance(e, _NOT_TRANSIENT):
                    self.stats[f"{what}_failures"] = \
                        self.stats.get(f"{what}_failures", 0) + 1
                    raise
                self.stats[f"{what}_retries"] = \
                    self.stats.get(f"{what}_retries", 0) + 1
                time.sleep(self.retry_backoff_s * (2 ** attempt))

    def ingest(self, docs) -> dict:
        """Admit new documents between serve steps: host-side append +
        one-pass sieve absorption (each document streamed exactly once).
        The append happens ONCE, outside the retry loop — retrying it
        would duplicate documents; the absorb that follows is cursor-
        driven and idempotent, so retrying it never re-streams a row."""
        self._ensure_stream()
        first = self.stream.corpus.append(docs)
        info = self._retrying("ingest", self.stream.absorb)
        info["first_id"] = first
        self.stats["ingested"] = info["n_total"]
        return info

    def select_warm(self, budget=None):
        """Answer a selection request from the live sieve state: O(L*k)
        central completion, independent of how much has been ingested."""
        self._ensure_stream()
        res = self.stream.select(budget)
        self.stats["warm_selects"] += 1
        self.stats["tau_fallback_warm"] += int(res.tau_fallback)
        return res

    # ---- persistence ----------------------------------------------------
    def save(self, ckpt: Checkpointer, step: int, blocking: bool = True):
        """Checkpoint the online-path state: the live SieveState pytree,
        the host-corpus cursor + un-streamed tail, and the service stats.
        Flushes nothing — the snapshot is read-only, so saving mid-stream
        never perturbs the replay."""
        self._ensure_stream()   # the snapshot must cover the initial corpus
        # the checkpointer retries transient write failures internally
        # (bounded + backoff); surface its running retry count in the
        # service stats so flakiness that never became an error is visible
        self.stats["checkpoint_retries"] = int(ckpt.n_retries)
        state = {"stream": persist.snapshot_selector(self.stream),
                 "stats": {k: np.asarray(v, np.int64)
                           for k, v in self.stats.items()}}
        ckpt.save(step, state, blocking=blocking)
        self.stats["checkpoint_retries"] = int(ckpt.n_retries)

    def restore(self, ckpt: Checkpointer, step: Optional[int] = None) -> int:
        """Warm-start from a checkpoint: the restored service continues
        mid-stream (no re-ingest of anything already absorbed) and every
        subsequent ingest()/select_warm() is bit-identical to the
        uninterrupted run.  The service must be built from the same spec /
        stream_chunk (mismatches fail loudly)."""
        tmpl = {"stream": persist.selector_template(self.stream),
                "stats": {k: np.zeros((), np.int64) for k in self.stats}}
        state, step = ckpt.restore(tmpl, step)
        persist.restore_selector(self.stream, state["stream"])
        self.stats = {k: int(v) for k, v in state["stats"].items()}
        self._stream_started = True
        self._init_used_stream = True
        self._maybe_release_init()
        return step

    def summary(self) -> str:
        s = self.stats
        return (f"[service] served={s['served']} shed={s['shed']} "
                f"deadline_miss={s['deadline_miss']} "
                f"warm={s['warm_selects']} ingested={s['ingested']} docs; "
                f"events: tau_fallback_batch={s['tau_fallback_batch']} "
                f"tau_fallback_warm={s['tau_fallback_warm']} "
                f"n_dropped={s['n_dropped']}; retries: "
                f"ingest={s.get('ingest_retries', 0)}"
                f"(+{s.get('ingest_failures', 0)} failed) "
                f"checkpoint={s.get('checkpoint_retries', 0)}")


# ---------------------------------------------------------------------------
# deadline-aware admission
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    """One selection request.  ``deadline_ms`` is relative to arrival;
    None = best-effort (admitted after every deadlined request, EDF)."""
    id: int
    k: int
    lam: Optional[float] = None       # graph_cut per-query knob
    alpha: Optional[float] = None     # log_det per-query knob
    deadline_ms: Optional[float] = None
    arrival_s: float = 0.0            # monotonic clock, set at submit

    @property
    def abs_deadline_s(self) -> float:
        if self.deadline_ms is None:
            return math.inf
        return self.arrival_s + self.deadline_ms / 1e3


class AdmissionQueue:
    """Pending requests, admitted earliest-deadline-first.

    ``admit`` pops up to ``n_slots`` requests in deadline order; a popped
    request whose deadline cannot be met even if served THIS step
    (now + est_step_s > deadline) is returned in the shed list instead of
    occupying a slot it would waste — the caller reports it.  Best-effort
    requests (no deadline) sort after every deadlined one and are never
    shed."""

    def __init__(self):
        self._heap: list = []
        self._seq = 0               # FIFO tie-break among equal deadlines

    def submit(self, req: Request, now: Optional[float] = None) -> None:
        req.arrival_s = time.monotonic() if now is None else now
        heapq.heappush(self._heap, (req.abs_deadline_s, self._seq, req))
        self._seq += 1

    def __len__(self) -> int:
        return len(self._heap)

    def admit(self, n_slots: int, now: float,
              est_step_s: Optional[float]) -> tuple:
        """-> (admitted <= n_slots by EDF, shed).  Until a step-latency
        estimate exists (first steps), only already-expired deadlines
        shed — admission is optimistic, never silently lossy."""
        admitted, shed = [], []
        est = est_step_s or 0.0
        while self._heap and len(admitted) < n_slots:
            _, _, req = heapq.heappop(self._heap)
            if now + est > req.abs_deadline_s:
                shed.append(req)
            else:
                admitted.append(req)
        return admitted, shed


class ServeLoop:
    """Admission -> serve -> retire around a SelectionService.

    One `run_step()` = admit free slots EDF (shedding infeasible requests,
    reported), answer every occupied slot with one select_batch program,
    retire them with per-request latency + deadline outcome.  Slot
    retirement is per-step and independent of any ingest cadence the
    caller runs between steps.  The per-step latency EWMA (compile-bearing
    step 0 excluded) drives the admission feasibility check."""

    def __init__(self, svc: SelectionService, slots: int, key,
                 est_step_s: Optional[float] = None, ewma_alpha: float = 0.3):
        self.svc, self.slots, self.key = svc, slots, key
        self.queue = AdmissionQueue()
        self.est_step_s = est_step_s
        self.ewma_alpha = ewma_alpha
        self.step = 0
        self.t_first: Optional[float] = None   # compile-bearing step secs
        self.first_step_served = 0
        self.done: list = []        # served rows (status="ok")
        self.shed: list = []        # shed rows (status="shed", with reason)

    def submit(self, req: Request, now: Optional[float] = None) -> None:
        self.queue.submit(req, now)

    def run_step(self) -> list:
        """One serve step; returns the rows retired this step.

        A step is one ``serve.step`` profiler span (args: step, admitted,
        shed, queued) over five children in order: serve.admit,
        serve.batch, serve.dispatch, serve.wait, serve.retire.  A row's
        latency and the step estimate end after the retire readbacks."""
        with jax.profiler.TraceAnnotation("serve.step") as span:
            svc, spec = self.svc, self.svc.spec
            with jax.profiler.TraceAnnotation("serve.admit"):
                now = time.monotonic()
                active, shed = self.queue.admit(self.slots, now,
                                                self.est_step_s)
                for req in shed:
                    row = {"id": req.id, "k": req.k, "status": "shed",
                           "latency_s": now - req.arrival_s,
                           "reason": (f"deadline {req.deadline_ms:.0f}ms "
                                      f"unmeetable (est step "
                                      f"{(self.est_step_s or 0.0) * 1e3:.0f}"
                                      f"ms)")}
                    self.shed.append(row)
                svc.account_shed(len(shed))
            span.set_metadata(step=self.step, admitted=len(active),
                              shed=len(shed), queued=len(self.queue))
            if not active:
                return []

            with jax.profiler.TraceAnnotation("serve.batch"):
                Q = self.slots
                ks_q = [r.k for r in active] + [0] * (Q - len(active))
                lam_q = [r.lam if r.lam is not None else spec.graph_cut_lam
                         for r in active] + \
                    [spec.graph_cut_lam] * (Q - len(active))
                alpha_q = [r.alpha if r.alpha is not None
                           else spec.logdet_alpha for r in active] + \
                    [spec.logdet_alpha] * (Q - len(active))
                qb = make_query_batch(ks_q, graph_cut_lam=lam_q,
                                      logdet_alpha=alpha_q)

            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation("serve.dispatch"):
                res = svc.select_batch(qb, key=jax.random.fold_in(
                    self.key, self.step))
            with jax.profiler.TraceAnnotation("serve.wait"):
                jax.block_until_ready(res.value)

            with jax.profiler.TraceAnnotation("serve.retire"):
                outs = [(int(res.sol_size[slot]), float(res.value[slot]),
                         int(res.n_dropped[slot]),
                         int(res.tau_fallback[slot]))
                        for slot in range(len(active))]
                finish = time.monotonic()
                rows, n_miss = [], 0
                for req, (size, value, dropped, fallback) in zip(active,
                                                                  outs):
                    missed = finish > req.abs_deadline_s
                    n_miss += int(missed)
                    rows.append({"id": req.id, "k": req.k, "status": "ok",
                                 "size": size, "value": value,
                                 "dropped": dropped, "tau_fallback": fallback,
                                 "latency_s": finish - req.arrival_s,
                                 "deadline_miss": missed})
                self.done.extend(rows)
                svc.account(rows)
                svc.account_shed(0, n_miss)

            dt = finish - t0
            if self.step == 0 and self.t_first is None:
                # the compile-bearing step: report it, keep it out of EWMA
                self.t_first = dt
                self.first_step_served = len(active)
            elif self.est_step_s is None:
                self.est_step_s = dt
            else:
                a = self.ewma_alpha
                self.est_step_s = (1 - a) * self.est_step_s + a * dt
            self.step += 1
            return rows


def synth_requests(n_requests: int, k_max: int, oracle: str, seed: int,
                   deadline_ms: Optional[float] = None):
    """A synthetic request stream: per-request budget + hyper-parameters
    (+ a jittered deadline when --deadline-ms is set).  In the framework
    these arrive from users; the shapes are what matters."""
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n_requests):
        req = Request(id=rid,
                      k=int(rng.integers(max(1, k_max // 4), k_max + 1)))
        if oracle == "graph_cut":
            req.lam = float(rng.uniform(0.1, 0.5))
        if oracle == "log_det":
            req.alpha = float(rng.uniform(0.5, 2.0))
        if deadline_ms is not None:
            req.deadline_ms = float(rng.uniform(0.5, 1.5) * deadline_ms)
        reqs.append(req)
    return reqs


def synth_docs(key, step: int, n_docs: int, d: int) -> np.ndarray:
    """Fresh documents for ingest step ``step``: the ingest key is folded
    by step so every cadence step streams NEW rows.  (Regression: a single
    pre-generated block was re-ingested at every cadence step, so the
    'growing corpus' was the same rows duplicated.)"""
    k = jax.random.fold_in(key, step)
    return np.asarray(jax.random.uniform(k, (n_docs, d)) ** 2)


def main() -> None:
    ap = argparse.ArgumentParser(description="batched selection service")
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--k", type=int, default=32,
                    help="max per-request budget (= slot buffer capacity)")
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--slots", type=int, default=8,
                    help="request slots Q (the compiled batch dimension)")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--oracle", default="feature_coverage",
                    choices=list(ORACLE_NAMES))
    ap.add_argument("--engine", default="dense", choices=list(ENGINES))
    ap.add_argument("--precision", default="f32",
                    choices=list(PRECISION_NAMES),
                    help="storage/compute precision policy for the corpus, "
                         "gather messages and sieve pools (accumulators "
                         "stay f32)")
    ap.add_argument("--algorithm", default="two_round",
                    choices=list(OPT_FREE_ALGORITHMS),
                    help="OPT-free selection driver backing the service "
                         "(the batch path always runs the 1-epoch pipeline; "
                         "multi_epoch upgrades warm/cold single selects)")
    ap.add_argument("--constraint", default="cardinality",
                    choices=list(CONSTRAINT_NAMES),
                    help="feasibility constraint on the ONLINE (sieve) "
                         "path's warm selections; the batched query path "
                         "stays unconstrained.  The launcher draws "
                         "synthetic per-element costs / part labels over "
                         "the maximum corpus the service can grow to")
    ap.add_argument("--budget", type=float, default=None,
                    help="knapsack cost budget (default: k * mean cost / 2)")
    ap.add_argument("--n-parts", type=int, default=8,
                    help="partition_matroid: number of parts (capacities "
                         "split k evenly)")
    ap.add_argument("--epochs", type=int, default=None,
                    help="multi_epoch threshold levels; None derives "
                         "ceil(1/eps)")
    ap.add_argument("--schedule", default="paper",
                    choices=list(SCHEDULE_KINDS),
                    help="multi_epoch descending-threshold schedule family")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline budget (jittered 0.5-1.5x "
                         "per request); unmeetable requests are shed and "
                         "reported, never silently dropped")
    ap.add_argument("--ingest-docs", type=int, default=0,
                    help="admit this many new docs between serve steps "
                         "(0 = static corpus)")
    ap.add_argument("--ingest-every", type=int, default=2,
                    help="ingest cadence in serve steps")
    ap.add_argument("--stream-chunk", type=int, default=512,
                    help="out-of-core sieve chunk (device footprint rows)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="persist the online state (sieve + cursor + "
                         "stats) here")
    ap.add_argument("--checkpoint-every", type=int, default=4,
                    help="serve steps between async checkpoints")
    ap.add_argument("--restore", action="store_true",
                    help="warm-start the online state from the latest "
                         "checkpoint in --checkpoint-dir (no re-ingest)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    mesh = make_mesh_for(len(jax.devices()), model_parallel=1)
    key = jax.random.PRNGKey(args.seed)
    kd, ki, ks = jax.random.split(key, 3)
    emb = np.asarray(jax.random.uniform(kd, (args.n, args.d)) ** 2)

    # ---- per-CORPUS statistics: computed once, cached for every request --
    t0 = time.time()
    spec = SelectorSpec(k=args.k, oracle=args.oracle,
                        algorithm=args.algorithm, epochs=args.epochs,
                        schedule_kind=args.schedule, engine=args.engine,
                        precision=args.precision)
    # synthetic per-element constraint data sized for the LARGEST corpus
    # the service can reach (initial + every possible ingest step), so
    # the attribute plane lookup covers every id the sieve will ever see
    constraint = None
    if args.constraint != "cardinality":
        n_max = args.n + args.ingest_docs * max(1, args.requests)
        kc = jax.random.fold_in(key, 7)
        costs = parts = part_caps = None
        budget = None
        if args.constraint == "knapsack":
            costs = jax.random.uniform(kc, (n_max,), minval=0.5, maxval=2.0)
            budget = (args.budget if args.budget is not None
                      else args.k * 1.25 / 2.0)
        elif args.constraint == "partition_matroid":
            parts = jax.random.randint(kc, (n_max,), 0, args.n_parts)
            cap = max(1, args.k // args.n_parts)
            part_caps = jnp.full((args.n_parts,), cap, jnp.int32)
        constraint = make_constraint(args.constraint, n_max, costs=costs,
                                     budget=budget, parts=parts,
                                     capacities=part_caps)
    svc = SelectionService(spec, mesh, emb, stream_chunk=args.stream_chunk,
                           constraint=constraint)
    ckpt = Checkpointer(args.checkpoint_dir) if args.checkpoint_dir else None
    if args.restore:
        assert ckpt is not None, "--restore needs --checkpoint-dir"
        step0 = svc.restore(ckpt)
        print(f"[select_serve] restored online state @ checkpoint step "
              f"{step0}: corpus={svc.stream.n_total} docs already absorbed "
              f"(no re-ingest)")
    svc.materialize()
    t_prep = time.time() - t0
    print(f"[select_serve] corpus ready: n={args.n} d={args.d} "
          f"oracle={args.oracle} constraint={args.constraint} "
          f"stats cached in {t_prep * 1e3:.0f}ms")

    loop = ServeLoop(svc, args.slots, ks)
    for req in synth_requests(args.requests, args.k, args.oracle, args.seed,
                              deadline_ms=args.deadline_ms):
        loop.submit(req)
    failed = []        # ingests/checkpoints that exhausted their retries
    t_online = 0.0     # ingest/warm time, excluded from the serving qps
    t_serve = time.time()
    with mesh:
        while len(loop.queue):
            # ---- online path between steps (its own cadence; slot
            # retirement below never waits on it) ------------------------
            if args.ingest_docs and loop.step and \
                    loop.step % args.ingest_every == 0:
                t0o = time.time()
                docs = synth_docs(ki, loop.step, args.ingest_docs, args.d)
                try:
                    info = svc.ingest(docs)
                    warm = svc.select_warm()
                    jax.block_until_ready(warm.value)
                    print(f"[select_serve] step {loop.step}: ingested "
                          f"{args.ingest_docs} docs "
                          f"(corpus={info['n_total']}), "
                          f"warm f(S)={float(warm.value):.4f} "
                          f"|S|={int(warm.sol_size)}")
                except Exception as e:      # noqa: BLE001
                    # retries exhausted: report the reason (shed-style,
                    # never silent) and keep serving the batch path — the
                    # cursor-driven absorb will catch up next cadence step;
                    # the run still exits non-zero at the end
                    failed.append(f"ingest @ step {loop.step}")
                    print(f"[select_serve] step {loop.step}: INGEST "
                          f"FAILED after {svc.retry_attempts} attempts "
                          f"({type(e).__name__}: {e}) — continuing; "
                          f"absorb resumes at the stream cursor")
                t_online += time.time() - t0o

            # ---- admit (EDF, shed infeasible) / serve / retire ----------
            loop.run_step()

            # ---- async checkpoint on its own cadence --------------------
            if ckpt and args.checkpoint_every and loop.step and \
                    loop.step % args.checkpoint_every == 0:
                try:
                    svc.save(ckpt, loop.step, blocking=False)
                except RuntimeError as e:
                    # a PREVIOUS async save exhausted its retries; report
                    # it (never silent) and try again this step — the
                    # final blocking save below re-raises if it persists,
                    # and the run exits non-zero at the end either way
                    failed.append(f"checkpoint before step {loop.step}")
                    print(f"[select_serve] step {loop.step}: CHECKPOINT "
                          f"FAILED ({e}) — retrying this step")
                    svc.save(ckpt, loop.step, blocking=False)
    if ckpt:
        svc.save(ckpt, max(loop.step, 1))   # final blocking save (+ waits
        #                                     out and surfaces async errors)
    t_total = time.time() - t_serve

    done, shed, step = loop.done, loop.shed, loop.step
    # steady-state excludes the first (compile-bearing) step from BOTH the
    # numerator and the denominator, or its served requests inflate qps;
    # with a single step there is no warm window to measure, so say so
    # instead of passing a compile-dominated figure off as steady-state
    t_first = loop.t_first or 0.0
    if step > 1:
        qps = (len(done) - loop.first_step_served) \
            / max(t_total - t_first - t_online, 1e-9)
        rate = f"steady-state {qps:.1f} queries/s"
    else:
        rate = (f"{len(done) / max(t_total, 1e-9):.1f} queries/s "
                f"incl. compile (single step — no steady-state window)")
    print(f"[select_serve] slots={args.slots} served={len(done)} "
          f"shed={len(shed)} steps={step} "
          f"first-step {t_first * 1e3:.0f}ms (incl. compile), {rate}")
    if done:
        lat = np.asarray([r["latency_s"] for r in done])
        print(f"[select_serve] latency p50={np.percentile(lat, 50) * 1e3:.0f}ms "
              f"p99={np.percentile(lat, 99) * 1e3:.0f}ms")
    if done:     # the batch log only exists once a step actually served
        print(svc.selector.round_log_batch.summary())
    print(svc.summary())
    for r in done[: min(8, len(done))]:
        print(f"[select_serve]   req {r['id']:3d}: k={r['k']:3d} "
              f"|S|={r['size']:3d} f(S)={r['value']:.4f} "
              f"dropped={r['dropped']} tau_fallback={r['tau_fallback']} "
              f"lat={r['latency_s'] * 1e3:.0f}ms")
    for r in shed[: min(4, len(shed))]:
        print(f"[select_serve]   req {r['id']:3d}: SHED ({r['reason']})")
    assert len(done) + len(shed) == args.requests, \
        "requests lost: every submitted request must be served or " \
        "reported shed"
    bad = [r for r in done if r["size"] > r["k"]]
    assert not bad, f"slots exceeded their budget: {bad}"
    if failed:
        print(f"[select_serve] FAILED for good: {', '.join(failed)}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
