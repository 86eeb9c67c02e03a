"""Multi-tenant private query-release service.

The serving tier the Fast-MWEM paper makes economical: selection is
Θ(√m) per iteration, the whole T-iteration run is one fused scan, and the
vmapped batch driver releases B synthetic histograms per dispatch — so the
service coalesces pending release requests *across tenants* into fixed-size
waves (padding short waves with replica slots, like the LM engine pads
request slots) and answers read traffic from already-released histograms at
zero additional ε (post-processing).

Flow (DESIGN.md §5):

  submit ──► AdmissionController.check (ledger preview, nothing spent)
     │            │
     │ rejected ──┴──► ReleaseTicket(status="rejected", decision)
     ▼
  pending queue, grouped by n_records (a compile-time static)
     ▼ wave of exactly `wave_size` slots
  run_mwem_batch (one dispatch; per-lane ledgers charge each tenant)
     ▼
  TenantSession.releases ──► answer()/AnswerCache (zero-ε reads)

Budget reservations: a queued-but-unexecuted request already counts against
its tenant's budget at admission time (its cost bundle is held as a
reservation on the tenant *ledger* — `PrivacyLedger.reserve` — and
previewed together with it), so two requests that individually fit but
jointly overspend cannot both be admitted.

Fault tolerance (DESIGN.md §10): budget moves through a two-phase commit —
reserve at submit, commit only after the wave's results land, abort on
expiry/failure/shedding — with every transition written ahead to an
optional JSONL `Journal` so `journal.recover()` can rebuild sessions and
ledgers after a crash. Waves are exception-safe: on a retryable failure
the tickets stay at the queue head and the wave re-dispatches with capped
exponential backoff; because lanes are keyed by ``PRNGKey(ticket.seed)``,
a retried wave is bitwise identical to a clean run, so retries cost zero
additional privacy and commit exactly once. Per-ticket deadlines expire
still-queued tickets with a refunded reservation; a `CircuitBreaker`
around the kernel seams pins the service to the XLA reference route after
repeated runtime failures; and queue-depth load shedding rejects before
any reservation is taken.

Streaming mode (DESIGN.md §11): ``streaming=True`` replaces the
synchronous fixed-wave drain with a pipelined one. Requests are admitted
continuously; every `pump` tick expires overdue tickets, then a
deadline/occupancy coalescing policy (`serve.coalesce`) cuts a wave when
it is full or when the oldest ticket's latency budget is half-spent. The
wave runs on the smallest AOT-precompiled executable in a power-of-two
lane ladder (`prewarm`) instead of padding to the batch wave size, and
dispatch is split launch/finish (`core.launch_mwem_batch` /
`finish_mwem_batch`): the next wave's histogram transfer and journal
writes overlap the in-flight wave's scan, with the scan's carried state
donated inside the compiled driver. Freed slots (expiry between retry
attempts) are refilled from the queue mid-wave — the serve-engine
``free_slots`` trick promoted into the release path. Every coalescer
decision rides the ``dispatch-started`` WAL record (trigger reason, wave
size, occupancy), so `coalesce.replay_decisions` can audit a crashed
service's wave cuts. Lanes stay keyed by ``PRNGKey(ticket.seed)``:
however the policy slices the admitted set, each lane's release is
bitwise identical to the fixed-wave path (tests/test_streaming.py).

The LP workload (paper §4, DESIGN.md §6) rides the same machinery:
`attach_lp` registers a scalar-private feasibility LP (public A,
curator-held private b, one shared k-MIPS index over [A_i, b_i]);
`submit_lp` admission-gates on the solver's own `lp_release_cost` bundle
(reservations pool across both workloads), and admitted solves drain in
fixed-size waves through one `solve_lp_batch` dispatch — per-lane ledgers,
pad-by-replication, and marginal-cost replay identical to histogram waves.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, replace
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.accountant import PrivacyLedger
from repro.core.distributed import _data_shards, run_mwem_sharded_batch
from repro.core.lp_dual import lp_release_cost
from repro.core.lp_scalar import (LPPendingBatch, ScalarLPConfig,
                                  aot_compile_lp_batch, finish_lp_batch,
                                  launch_lp_batch, solve_lp_batch)
from repro.core.mwem import (MWEMConfig, MWEMPendingBatch, aot_compile_batch,
                             finish_mwem_batch, launch_mwem_batch,
                             release_cost, run_mwem_batch)
from repro.core.workload import Workload, as_workload
from repro.faults import fault_site
from repro.mips import (FlatAbsIndex, FlatIndex, IVFIndex, LSHIndex,
                        MarginalIVFIndex, ShardedIVFIndex,
                        augment_complement, lp_scalar_rows)
from repro.obs import trace as obs
from repro.obs.clock import monotonic, sleep
from repro.obs.metrics import MetricsRegistry, default_registry
from repro.serve.admission import AdmissionController, AdmissionDecision
from repro.serve.breaker import CircuitBreaker
from repro.serve.coalesce import (DeadlineOccupancyPolicy, WaveDecision,
                                  WaveLadder)
from repro.serve.journal import Journal, RecoveredState, encode_bundle
from repro.serve.session import (Answer, ReleasedHistogram, ReleasedLP,
                                 TenantSession)


def _retryable(exc: BaseException) -> bool:
    """Transient-vs-programming-error classification for wave failures.

    Device/runtime faults (XLA runtime errors subclass ``RuntimeError``,
    injected `FaultInjected` faults do too, I/O hiccups are ``OSError``)
    re-dispatch; ``ValueError``/``TypeError``/``NotImplementedError`` are
    bugs or unsupported configs and propagate to the caller unchanged —
    retrying cannot fix them and would burn the backoff budget."""
    if isinstance(exc, NotImplementedError):
        return False
    return isinstance(exc, (RuntimeError, OSError))


@dataclass
class ReleaseTicket:
    """Handle returned by `submit`/`submit_lp`; resolved by the wave that
    executes it (or by a deadline/retry-limit along the way)."""

    ticket_id: int
    tenant_id: str
    seed: int
    # "queued" | "rejected" | "retrying" | "done" | "failed" | "expired"
    status: str
    decision: AdmissionDecision
    kind: str = "mwem"               # "mwem" | "lp"
    cost_bundle: tuple = ()          # (events, gamma, slack) reservation
    rid: Optional[int] = None        # ledger reservation id (until resolved)
    attempts: int = 0                # dispatch attempts that included this ticket
    deadline: Optional[float] = None  # absolute monotonic expiry, or None
    error: str = ""                  # last failure, when status == "failed"
    release: Optional[object] = None  # ReleasedHistogram | ReleasedLP
    final_error: float = float("nan")
    submit_time: float = float("nan")   # monotonic stamp at submit()
    latency_seconds: float = float("nan")  # admission → answered


@dataclass
class ServiceStats:
    dispatches: int = 0
    released: int = 0
    lp_released: int = 0
    rejected: int = 0
    padded_slots: int = 0
    retries: int = 0
    failed: int = 0
    expired: int = 0
    shed: int = 0
    refilled_slots: int = 0      # queue tickets promoted into freed lanes
    pad_slots_saved: int = 0     # pad lanes avoided by the AOT size ladder

    def as_dict(self) -> dict:
        return dict(dispatches=self.dispatches, released=self.released,
                    lp_released=self.lp_released, rejected=self.rejected,
                    padded_slots=self.padded_slots, retries=self.retries,
                    failed=self.failed, expired=self.expired, shed=self.shed,
                    refilled_slots=self.refilled_slots,
                    pad_slots_saved=self.pad_slots_saved)


@dataclass
class _LPWorkload:
    """The service's scalar-LP workload (DESIGN.md §6): public constraint
    matrix A, the curator-held private bounds b, the release config, and
    the k-MIPS index over the concatenated rows [A_i, b_i]. Tenants are
    budget principals drawing private solves against it."""

    A: jax.Array
    b: jax.Array
    cfg: ScalarLPConfig
    index: Optional[object]
    cost: tuple                      # (events, gamma, slack) per release
    pending: List[ReleaseTicket]


@dataclass
class _InflightWave:
    """One launched-but-unfinished streaming wave: the popped tickets, the
    async dispatch handle, and the journaled coalescer decision. Exactly
    one wave is in flight at a time (`ReleaseService._inflight`) — the
    double buffer: while this wave's scan runs on device, the next wave's
    host prep, transfers, and WAL writes proceed; resolving this handle is
    the only point that blocks."""

    kind: str                        # "mwem" | "lp"
    n_records: Optional[int]         # mwem group key (None for lp)
    tickets: List[ReleaseTicket]
    n_pad: int
    size: int                        # ladder executable lane count
    pending: object                  # MWEMPendingBatch | LPPendingBatch
    decision: WaveDecision
    attempt: int


class ReleaseService:
    """Coalescing, budget-admitted front end over `run_mwem_batch`.

    One service owns one query workload Q (m × U) and one k-MIPS index over
    it — tenants share the compiled wave executable and differ only in
    their histogram lane, PRNG key, and ledger. Release parameters
    (per-release ε, δ, T, mode) are fixed at construction so every wave is
    one `run_mwem_batch` dispatch of exactly ``wave_size`` lanes; requests
    from datasets of different sizes (``n_records`` is a compile-time
    static through the noise scales) batch in separate per-size groups.

    Passing a ``mesh`` puts the service on a device mesh: the index becomes
    a per-shard `ShardedIVFIndex` and waves drain through
    `run_mwem_sharded_batch` — one mesh-wide scan dispatch per lane, the
    compiled executable shared across lanes, the same per-lane ledger
    charging. Admission, sessions, and the answer cache are unchanged.
    """

    def __init__(self, Q, cfg: MWEMConfig, wave_size: int = 8,
                 index_kind: str = "flat", seed: int = 0,
                 tight_composition: bool = False, auto_flush: bool = True,
                 mesh=None, use_pallas: str = "auto",
                 registry: Optional[MetricsRegistry] = None,
                 journal: Optional[Journal] = None, retry_limit: int = 3,
                 backoff_base: float = 0.05, backoff_cap: float = 2.0,
                 default_deadline: Optional[float] = None,
                 max_queue_depth: Optional[int] = None,
                 breaker_threshold: int = 3, streaming: bool = False,
                 policy=None):
        if streaming and mesh is not None:
            raise ValueError(
                "streaming waves are single-device: the sharded driver "
                "dispatches lanes sequentially with no launch/finish split")
        # the workload seam: a raw (m, U) matrix or any `core.workload`
        # family — `MarginalWorkload` releases run factored end to end
        # through the same admission/cost/wave path (DESIGN.md §9)
        if mesh is not None and not isinstance(Q, Workload):
            # rows over the mesh's data axes from the start, as the sharded
            # driver holds them — no full replica on the default device
            Q = jax.device_put(np.asarray(Q, np.float32), NamedSharding(
                mesh, PartitionSpec(_data_shards(mesh)[0], "model")))
        self.workload = as_workload(Q)
        self.Q = self.workload.Q if self.workload.is_dense else None
        self.m, self.U = self.workload.m, self.workload.U
        # where this service publishes its metrics; the process-wide
        # default registry unless the caller isolates it (tests do)
        self.metrics = registry if registry is not None else default_registry()
        # the service-level knob also drives the drivers' fused step body
        # (megakernel vs classic — DESIGN.md §7), so batched waves pick up
        # the VMEM-resident `kernels.mwem_step` route alongside the probe
        self.cfg = replace(cfg, use_pallas=use_pallas)
        self.wave_size = int(wave_size)
        self.auto_flush = auto_flush
        # a mesh routes waves through the sharded driver (one mesh-wide
        # scan dispatch per lane) instead of the vmapped fused batch
        self.mesh = mesh
        self.admission = AdmissionController(tight=tight_composition)
        self.sessions: Dict[str, TenantSession] = {}
        self.stats = ServiceStats()
        self._pending: "OrderedDict[int, List[ReleaseTicket]]" = OrderedDict()
        self.lp: Optional[_LPWorkload] = None
        self._next_ticket = 0
        self._next_release = 0
        self._next_seed = seed
        # every seed ever handed to a lane (auto or explicit) — the auto
        # counter skips issued values so two tickets can never share a PRNG
        # stream by accident (identical seeds ⇒ identical releases ⇒ the
        # second tenant pays ε for an answer the first already published)
        self._issued_seeds: set = set()
        # fault-tolerance knobs (DESIGN.md §10)
        self.journal = journal
        self.retry_limit = int(retry_limit)
        self.backoff_base = float(backoff_base)
        self.backoff_cap = float(backoff_cap)
        self.default_deadline = default_deadline
        self.max_queue_depth = max_queue_depth
        # streaming drain (DESIGN.md §11): continuous admission, the
        # deadline/occupancy coalescer cuts adaptive-size waves, dispatch
        # is pipelined launch/finish with one wave in flight
        self.streaming = bool(streaming)
        self.policy = (policy if policy is not None else
                       (DeadlineOccupancyPolicy(wave_size=self.wave_size)
                        if self.streaming else None))
        self.wave_log: List[WaveDecision] = []
        self._inflight: Optional[_InflightWave] = None
        self.degraded = False
        self.breaker = CircuitBreaker(threshold=breaker_threshold,
                                      registry=self.metrics)
        self.breaker.on_trip(self._degrade_to_ref)
        # `use_pallas` ("auto" | "always" | "never") routes the per-wave
        # probe through the fused kernels where the index supports them
        # (kernels/ivf_probe for IVF, mips_topk for flat) — "auto" falls
        # back to the XLA probe off-TPU automatically
        if cfg.mode == "fast":
            factored = not self.workload.is_dense
            if mesh is not None:
                # the sharded driver needs the per-shard structure, whatever
                # single-device kind was asked for; factored workloads
                # densify here or fail loudly (the documented fallback)
                self.index = ShardedIVFIndex(
                    self.workload.require_dense("ReleaseService[mesh]"),
                    n_shards=_data_shards(mesh)[1],
                    seed=seed, use_pallas=use_pallas)
            elif index_kind in ("ivf", "marginal_ivf") and factored:
                # the clique-structured family is the factored counterpart
                # of IVF — exact probe, no row table (DESIGN.md §9)
                self.index = MarginalIVFIndex(self.workload)
            elif index_kind == "marginal_ivf":
                raise ValueError(
                    "index_kind='marginal_ivf' needs a MarginalWorkload; "
                    "dense services use flat/ivf/lsh")
            elif index_kind == "flat":
                self.index = FlatAbsIndex(self.workload,
                                          use_pallas=use_pallas)
            elif index_kind == "ivf":
                self.index = IVFIndex(augment_complement(np.asarray(self.Q)),
                                      seed=seed, use_pallas=use_pallas)
            elif index_kind == "lsh":
                self.index = LSHIndex(
                    augment_complement(np.asarray(self.workload.require_dense(
                        "ReleaseService[lsh]"))),
                    seed=seed)
            else:
                raise ValueError(f"unknown index kind {index_kind!r}")
        else:
            self.index = None

    # ------------------------------------------------------------ sessions
    def create_session(self, tenant_id: str, *, eps_budget: float,
                       delta_budget: float, tokens=None, h=None,
                       n_records: Optional[int] = None) -> TenantSession:
        """Register a tenant: histogram from raw ``tokens`` (binned over the
        service domain U) or a pre-built normalized ``h`` + ``n_records``."""
        if tenant_id in self.sessions:
            raise ValueError(f"session {tenant_id!r} already exists")
        if tokens is not None:
            sess = TenantSession.from_tokens(tenant_id, tokens, self.U,
                                             eps_budget, delta_budget)
        else:
            if h is None or n_records is None:
                raise ValueError("provide tokens=, or h= with n_records=")
            h = np.asarray(h, np.float32)
            if h.shape != (self.U,):
                raise ValueError(f"h must have shape ({self.U},), got {h.shape}")
            sess = TenantSession(tenant_id=tenant_id, h=h,
                                 n_records=int(n_records),
                                 eps_budget=eps_budget,
                                 delta_budget=delta_budget)
        self.sessions[tenant_id] = sess
        self._register_ledger_gauges(sess)
        self._journal("session-created", tenant_id=tenant_id,
                      h=sess.h.tolist(), n_records=sess.n_records,
                      eps_budget=sess.eps_budget,
                      delta_budget=sess.delta_budget)
        return sess

    def adopt(self, recovered: RecoveredState) -> None:
        """Install sessions rebuilt by `journal.recover` into this (fresh)
        service — ledgers arrive already charged per the journal's
        committed/in-doubt records, and every counter a pre-crash record
        could collide with fast-forwards: seeds, ticket/release ids, and
        each ledger's *reservation* ids (a reused rid would let the next
        replay resolve a pre-crash in-doubt record against a post-adopt
        reservation, silently under-counting spent ε).

        If this service journals, the adopted state is re-journaled as a
        snapshot (session-created / ledger-snapshot / release-delivered
        per tenant, aborted markers for the crash's resolved rids, one
        service-snapshot) so the post-adopt WAL is self-contained: a
        second recovery — from a fresh journal file, or from the same
        file this service keeps appending to — reconstructs the adopted
        state exactly, with the old in-doubt charges carried by the
        ledger snapshot rather than re-resolved (no double charge, no
        loss)."""
        for tenant_id, sess in recovered.sessions.items():
            if tenant_id in self.sessions:
                raise ValueError(
                    f"session {tenant_id!r} already exists; adopt into a "
                    "fresh service")
            self.sessions[tenant_id] = sess
            self._register_ledger_gauges(sess)
            sess.ledger.advance_rid(recovered.next_rids.get(tenant_id, 0))
        self._issued_seeds |= set(recovered.issued_seeds)
        self._next_release = max(self._next_release,
                                 recovered.next_release_id)
        self._next_ticket = max(self._next_ticket, recovered.next_ticket_id)
        self._journal_adoption_snapshot(recovered)

    def _journal_adoption_snapshot(self, recovered: RecoveredState) -> None:
        """Re-journal adopted state (see `adopt`). Record order matters
        for same-WAL appends: each tenant's ``session-created`` resets the
        replayed session before ``ledger-snapshot``/``release-delivered``
        rebuild it, and the ``aborted`` markers resolve the pre-crash
        reservations the old records leave pending (their in-doubt charge
        already lives inside the ledger snapshot)."""
        if self.journal is None:
            return
        for tenant_id, sess in recovered.sessions.items():
            self._journal("session-created", tenant_id=tenant_id,
                          h=sess.h.tolist(), n_records=sess.n_records,
                          eps_budget=sess.eps_budget,
                          delta_budget=sess.delta_budget)
            self._journal("ledger-snapshot", tenant_id=tenant_id,
                          bundle=encode_bundle(sess.ledger.bundle()),
                          next_rid=sess.ledger.next_rid)
            for rel in sess.releases:
                self._journal("release-delivered", tenant_id=tenant_id,
                              release_kind="mwem",
                              release_id=rel.release_id, seed=rel.seed,
                              p_hat=np.asarray(rel.p_hat).tolist(),
                              final_error=rel.final_error,
                              eps_cost=rel.eps_cost,
                              delta_cost=rel.delta_cost)
            for rel in sess.lp_releases:
                self._journal("release-delivered", tenant_id=tenant_id,
                              release_kind="lp",
                              release_id=rel.release_id, seed=rel.seed,
                              x_bar=np.asarray(rel.x_bar).tolist(),
                              violated_frac=rel.violated_frac,
                              eps_cost=rel.eps_cost,
                              delta_cost=rel.delta_cost)
        for tenant_id, rid in recovered.in_doubt + recovered.refunded:
            self._journal("aborted", tenant_id=tenant_id, rid=rid,
                          reason="adoption-snapshot")
        self._journal("service-snapshot",
                      issued_seeds=sorted(self._issued_seeds),
                      next_ticket_id=self._next_ticket,
                      next_release_id=self._next_release)

    def _register_ledger_gauges(self, sess: TenantSession) -> None:
        """Hang the obs gauges off the tenant's ledger: after every
        mutating record, the per-tenant ε/δ-spent and remaining-budget
        gauges recompute from `ledger.composed()` in the service's
        composition mode — the snapshot always agrees with the ledger."""
        tight = self.admission.tight
        metrics = self.metrics

        def update(ledger, sess=sess):
            if not obs.enabled():
                return
            eps, delta = ledger.composed(tight=tight)
            labels = dict(tenant=sess.tenant_id)
            metrics.gauge("tenant_eps_spent", **labels).set(eps)
            metrics.gauge("tenant_delta_spent", **labels).set(delta)
            metrics.gauge("tenant_eps_remaining", **labels).set(
                sess.eps_budget - eps)
            metrics.gauge("tenant_delta_remaining", **labels).set(
                sess.delta_budget - delta)

        sess.ledger.add_hook(update)
        update(sess.ledger)  # publish the zero-spend baseline immediately

    def session(self, tenant_id: str) -> TenantSession:
        return self.sessions[tenant_id]

    # ------------------------------------------------------------- submit
    def _group_cfg(self, n_records: int) -> MWEMConfig:
        return replace(self.cfg, n_records=n_records)

    def _reserved(self, tenant_id: str):
        """Cost bundles of this tenant's open (phase-one) reservations —
        held on the tenant *ledger*, so they pool across both workloads: a
        queued LP solve reserves budget against a pending histogram
        release and vice versa."""
        return self.sessions[tenant_id].ledger.reserved_bundle()

    def _take_seed(self, seed: Optional[int]) -> int:
        """Issue a lane seed. Auto-issued seeds skip every seed already
        handed out (including explicit ones — the historical bug let the
        counter re-issue an explicitly-requested value); explicit seeds are
        honored verbatim and registered so the counter avoids them."""
        if seed is None:
            while self._next_seed in self._issued_seeds:
                self._next_seed += 1
            seed = self._next_seed
            self._next_seed += 1
        seed = int(seed)
        self._issued_seeds.add(seed)
        return seed

    # ----------------------------------------------------- fault tolerance
    def _journal(self, rec_kind: str, **payload) -> None:
        """Write one WAL record, riding the service's own retry/backoff
        policy: a transient append failure (full disk buffer, injected
        fault) retries; a persistent one propagates — budget transitions
        must not proceed unlogged."""
        if self.journal is None:
            return
        for attempt in range(self.retry_limit + 1):
            try:
                self.journal.append(rec_kind, **payload)
                return
            except Exception as exc:
                if not _retryable(exc) or attempt >= self.retry_limit:
                    raise
                self._backoff(attempt)

    def _backoff(self, attempt: int) -> None:
        sleep(min(self.backoff_cap, self.backoff_base * (2.0 ** attempt)))

    def _abort_ticket(self, ticket: ReleaseTicket, reason: str,
                      status: str) -> None:
        """Refund a ticket's phase-one reservation and resolve the ticket
        (``status`` ∈ {"expired", "failed"})."""
        if ticket.rid is not None:
            self.sessions[ticket.tenant_id].ledger.abort(ticket.rid)
            self._journal("aborted", tenant_id=ticket.tenant_id,
                          rid=ticket.rid, reason=reason)
            ticket.rid = None
        ticket.status = status
        if obs.enabled():
            self.metrics.counter("reservations_aborted_total",
                                 reason=reason).inc()

    def _expire_deadlines(self, queue: List[ReleaseTicket]) -> None:
        """Expire still-queued tickets past their deadline: the reservation
        is refunded in full — nothing ran, no randomness was realized, so
        the refund leaks nothing."""
        now = monotonic()
        expired = [t for t in queue
                   if t.deadline is not None and now >= t.deadline]
        for t in expired:
            queue.remove(t)
            self._abort_ticket(t, reason="expired", status="expired")
            self.stats.expired += 1

    def _commit_ticket(self, ticket: ReleaseTicket) -> None:
        """Phase two for one delivered lane. `PrivacyLedger.commit` checks
        its fault site *before* popping the reservation, so a failed
        attempt leaves the reservation intact and the retry commits exactly
        once. The journal record lands *after* the ledger moves: if the
        process dies in between, recovery's in-doubt rule (dispatched, no
        resolution ⇒ committed) reconstructs the same ledger state."""
        sess = self.sessions[ticket.tenant_id]
        for attempt in range(self.retry_limit + 1):
            try:
                sess.ledger.commit(ticket.rid)
                break
            except KeyError:
                raise
            except Exception as exc:
                if not _retryable(exc) or attempt >= self.retry_limit:
                    raise
                self._backoff(attempt)
        rid, ticket.rid = ticket.rid, None
        self._journal("committed", tenant_id=ticket.tenant_id, rid=rid)

    def _note_dispatch_failure(self, exc: BaseException,
                               wave: List[ReleaseTicket], attempt: int,
                               kind: str) -> bool:
        """Account one failed wave attempt; returns True iff the wave
        should re-dispatch (retryable and under the retry budget)."""
        site = getattr(exc, "site", "wave.dispatch")
        if obs.enabled():
            self.metrics.counter("dispatch_failures_total", site=site).inc()
        # failures only count toward the breaker while the Pallas route is
        # still live — once degraded to the reference path, further faults
        # are not the kernels' doing; neither are WAL write failures, which
        # pinning to the reference route could never fix
        if self.cfg.use_pallas != "never" and site != "journal.append":
            self.breaker.record_failure()
        retry = _retryable(exc) and attempt <= self.retry_limit
        for t in wave:
            t.attempts += 1
            t.error = repr(exc)
            t.status = "retrying" if retry else "failed"
        if retry:
            self.stats.retries += 1
            if obs.enabled():
                self.metrics.counter("wave_retries_total", kind=kind).inc()
            self._backoff(attempt - 1)
        return retry

    def _fail_wave(self, wave: List[ReleaseTicket],
                   exc: BaseException) -> None:
        """Resolve a wave that exhausted its retries (or hit a
        programming error): reservations are refunded — the dispatch never
        produced output, so no randomness escaped and the refund is safe."""
        for t in wave:
            self._abort_ticket(t, reason="failed", status="failed")
            t.error = repr(exc)
        self.stats.failed += len(wave)

    def _resolve_stranded(self, tickets: List[ReleaseTicket],
                          exc: BaseException) -> None:
        """Resolve tickets a phase-two failure would otherwise strand.

        The delivery loop runs after the wave was popped from the queue,
        so a ticket it leaves unresolved would hold its reservation open
        forever — a live budget leak. Open reservations are refunded
        (their outputs are dropped undelivered, so nothing escaped),
        best-effort: when the journal is itself the failure, the WAL
        ``aborted`` record may not land, and recovery's in-doubt rule then
        re-charges the rid — a conservative overcharge, never a leak. A
        ticket whose ledger commit landed but whose ``committed`` record
        didn't (rid already cleared) stays charged, matching the same
        rule."""
        for t in tickets:
            if t.status == "done":
                continue
            try:
                if t.rid is not None:
                    self._abort_ticket(t, reason="commit-failed",
                                       status="failed")
                else:
                    t.status = "failed"
            except Exception:
                t.rid = None
                t.status = "failed"
            t.error = repr(exc)
            self.stats.failed += 1

    def _degrade_to_ref(self) -> None:
        """Breaker trip: pin the service to the XLA reference route. The
        megakernel and classic paths compute the same expressions (on the
        chip up to f32 reduction order, DESIGN.md §7), so degradation
        changes throughput, not the mechanism."""
        self.cfg = replace(self.cfg, use_pallas="never")
        indexes = [self.index]
        if self.lp is not None:
            indexes.append(self.lp.index)
        for idx in indexes:
            if idx is not None:
                # the fused drivers key their executable caches on this
                # attribute, so flipping it re-routes cleanly
                idx._use_pallas = "never"
        self.degraded = True
        if obs.enabled():
            self.metrics.counter("service_degraded_total").inc()

    def _shed_check(self, tenant_id: str,
                    kind: str) -> Optional[ReleaseTicket]:
        """Queue-depth load shedding: reject before any seed is issued or
        reservation taken, so a shed request is free to retry later."""
        if self.max_queue_depth is None:
            return None
        depth = self.pending_count()
        if depth < self.max_queue_depth:
            return None
        sess = self.sessions[tenant_id]
        decision = AdmissionDecision(
            admitted=False, tenant_id=tenant_id,
            eps_projected=float("nan"), delta_projected=float("nan"),
            eps_budget=sess.eps_budget, delta_budget=sess.delta_budget,
            eps_cost=float("nan"), delta_cost=float("nan"),
            reason=f"load shed: queue depth {depth} >= "
                   f"{self.max_queue_depth}")
        ticket = ReleaseTicket(
            ticket_id=self._next_ticket, tenant_id=tenant_id, seed=-1,
            status="rejected", decision=decision, kind=kind,
            submit_time=monotonic())
        self._next_ticket += 1
        self.stats.shed += 1
        if obs.enabled():
            self.metrics.counter("load_shed_total", kind=kind).inc()
        return ticket

    def submit(self, tenant_id: str, seed: Optional[int] = None,
               deadline: Optional[float] = None) -> ReleaseTicket:
        """Request one release for a tenant.

        Admission previews the tenant ledger with the release's exact cost
        bundle (plus any still-open reservations) appended; over-budget
        requests are rejected *before* anything is spent, with the
        projected composed (ε, δ) reported on the decision. Admitted
        requests take a phase-one ledger reservation (journaled) that a
        successful wave commits and an expiry/failure refunds.
        ``deadline`` (seconds from now; falls back to the service's
        ``default_deadline``) expires the ticket if it is still queued when
        a wave next drains.
        """
        shed = self._shed_check(tenant_id, kind="mwem")
        if shed is not None:
            return shed
        sess = self.sessions[tenant_id]
        cfg = self._group_cfg(sess.n_records)
        bundle = release_cost(cfg, self.m, self.U, index=self.index)
        decision = self.admission.check(sess, bundle,
                                        reserved=self._reserved(tenant_id))
        ticket = ReleaseTicket(
            ticket_id=self._next_ticket, tenant_id=tenant_id,
            seed=self._take_seed(seed),
            status="queued" if decision.admitted else "rejected",
            decision=decision, cost_bundle=bundle,
            submit_time=monotonic(),
        )
        self._next_ticket += 1
        if not decision.admitted:
            sess.rejected_count += 1
            self.stats.rejected += 1
            if obs.enabled():
                self.metrics.counter("admission_rejections_total",
                                     kind="mwem", tenant=tenant_id).inc()
            return ticket
        ticket.rid = sess.ledger.reserve(*bundle)
        d = deadline if deadline is not None else self.default_deadline
        if d is not None:
            ticket.deadline = ticket.submit_time + d
        try:
            self._journal("reserved", tenant_id=tenant_id, rid=ticket.rid,
                          ticket_id=ticket.ticket_id, workload="mwem",
                          seed=ticket.seed, bundle=encode_bundle(bundle))
        except Exception:
            # an unjournaled reservation must not outlive the failed
            # submit — the ticket never queues, so nothing would ever
            # commit or abort it: refund so the raise is budget-neutral
            sess.ledger.abort(ticket.rid)
            ticket.rid = None
            ticket.status = "failed"
            raise
        self._pending.setdefault(sess.n_records, []).append(ticket)
        if self.streaming:
            if self.auto_flush:
                self.pump()
        elif self.auto_flush and len(self._pending[sess.n_records]) >= self.wave_size:
            self._run_wave(sess.n_records)
        return ticket

    # ----------------------------------------------------------------- LP
    def attach_lp(self, A, b, cfg: Optional[ScalarLPConfig] = None,
                  index_kind: str = "flat", seed: int = 0,
                  use_pallas: str = "auto") -> None:
        """Register the service's scalar-LP workload (paper §4.1).

        ``A`` is the public constraint matrix, ``b`` the curator-held
        private bounds (Δ∞ sensitivity); tenants draw private solves
        against their budgets via `submit_lp`. Fast mode builds the k-MIPS
        index over the concatenated rows [A_i, b_i] once, here — every LP
        wave shares it and the compiled `solve_lp_batch` executable.
        """
        if self.lp is not None:
            raise ValueError("an LP workload is already attached")
        if self.mesh is not None:
            raise ValueError("LP waves are not mesh-sharded; attach to an "
                             "off-mesh service")
        A = jnp.asarray(A, jnp.float32)
        b = jnp.asarray(b, jnp.float32)
        cfg = cfg or ScalarLPConfig()
        if cfg.driver == "host":
            # refuse now, not at wave time: _run_lp_wave pops its tickets
            # before dispatching, so a late solve_lp_batch rejection would
            # strand admitted (budget-reserved) requests
            raise ValueError("LP waves run the fused batch driver; "
                             "cfg.driver='host' cannot serve")
        index = None
        if cfg.mode == "fast":
            rows = lp_scalar_rows(np.asarray(A), np.asarray(b))
            if index_kind == "flat":
                index = FlatIndex(rows, use_pallas=use_pallas)
            elif index_kind == "ivf":
                index = IVFIndex(rows, seed=seed, use_pallas=use_pallas)
            else:
                raise ValueError(f"unknown LP index kind {index_kind!r}")
        self.lp = _LPWorkload(A=A, b=b, cfg=cfg, index=index,
                              cost=lp_release_cost(cfg, A, index=index),
                              pending=[])

    def submit_lp(self, tenant_id: str, seed: Optional[int] = None,
                  deadline: Optional[float] = None) -> ReleaseTicket:
        """Request one private LP solve for a tenant.

        Admission previews the tenant ledger with the solve's exact cost
        bundle (`lp_release_cost` — the solver's own `lp_em` /
        `approx_slack` / `index_failure` schedule) plus any still-open
        reservations from either workload, exactly like `submit`; admitted
        solves take the same journaled phase-one reservation.
        """
        if self.lp is None:
            raise ValueError("no LP workload attached; call attach_lp first")
        shed = self._shed_check(tenant_id, kind="lp")
        if shed is not None:
            return shed
        sess = self.sessions[tenant_id]
        decision = self.admission.check(sess, self.lp.cost,
                                        reserved=self._reserved(tenant_id))
        ticket = ReleaseTicket(
            ticket_id=self._next_ticket, tenant_id=tenant_id,
            seed=self._take_seed(seed),
            status="queued" if decision.admitted else "rejected",
            decision=decision, kind="lp", cost_bundle=self.lp.cost,
            submit_time=monotonic(),
        )
        self._next_ticket += 1
        if not decision.admitted:
            sess.rejected_count += 1
            self.stats.rejected += 1
            if obs.enabled():
                self.metrics.counter("admission_rejections_total",
                                     kind="lp", tenant=tenant_id).inc()
            return ticket
        ticket.rid = sess.ledger.reserve(*self.lp.cost)
        d = deadline if deadline is not None else self.default_deadline
        if d is not None:
            ticket.deadline = ticket.submit_time + d
        try:
            self._journal("reserved", tenant_id=tenant_id, rid=ticket.rid,
                          ticket_id=ticket.ticket_id, workload="lp",
                          seed=ticket.seed,
                          bundle=encode_bundle(self.lp.cost))
        except Exception:
            # see submit(): a failed submit must be budget-neutral
            sess.ledger.abort(ticket.rid)
            ticket.rid = None
            ticket.status = "failed"
            raise
        self.lp.pending.append(ticket)
        if self.streaming:
            if self.auto_flush:
                self.pump()
        elif self.auto_flush and len(self.lp.pending) >= self.wave_size:
            self._run_lp_wave()
        return ticket

    # -------------------------------------------------------------- waves
    def pending_count(self) -> int:
        n = sum(len(g) for g in self._pending.values())
        if self.lp is not None:
            n += len(self.lp.pending)
        return n

    def flush(self) -> List[ReleaseTicket]:
        """Drain every pending group (histogram and LP). Batch mode drains
        through fixed-size waves; streaming mode force-pumps the coalescer
        (reason "flush") until every queue and the in-flight wave are
        resolved."""
        if self.streaming:
            done: List[ReleaseTicket] = []
            while True:
                done.extend(self.pump(force=True))
                if (self._inflight is None
                        and not any(self._pending.values())
                        and (self.lp is None or not self.lp.pending)):
                    return done
        done = []
        for n_records in list(self._pending):
            while self._pending.get(n_records):
                done.extend(self._run_wave(n_records))
        while self.lp is not None and self.lp.pending:
            done.extend(self._run_lp_wave())
        return done

    # -------------------------------------------------- streaming pipeline
    def _ladder(self) -> WaveLadder:
        if self.policy is not None and getattr(self.policy, "ladder", None):
            return self.policy.ladder
        return WaveLadder.for_wave_size(self.wave_size)

    def prewarm(self, n_records: Optional[int] = None,
                lp: bool = False) -> Dict[int, bool]:
        """AOT-compile the wave-size ladder ahead of traffic.

        One executable per ladder lane count lands in the batched driver's
        cache (`core.aot_compile_batch`), so streaming waves pick the
        smallest compiled size that fits their occupancy with zero
        first-wave trace+compile cost. Histogram executables are keyed by
        ``n_records`` (a compile-time static through the noise scales) —
        pass it, or omit it to prewarm every registered session's group.
        Returns {lane_count: newly_compiled}.
        """
        ladder = self._ladder()
        out: Dict[int, bool] = {}
        if lp:
            if self.lp is None:
                raise ValueError("no LP workload attached; call attach_lp "
                                 "first")
            for s in ladder.sizes:
                out[s] = aot_compile_lp_batch(self.lp.A, self.lp.b,
                                              self.lp.cfg, s,
                                              index=self.lp.index)
            return out
        groups = ([n_records] if n_records is not None
                  else sorted({s.n_records for s in self.sessions.values()}))
        for n in groups:
            cfg = self._group_cfg(n)
            for s in ladder.sizes:
                compiled = aot_compile_batch(self.workload, cfg, s,
                                             index=self.index)
                out[s] = out.get(s, False) or compiled
        return out

    def pump(self, force: bool = False) -> List[ReleaseTicket]:
        """One coalescer tick.

        Every tick — batch or streaming — expires overdue tickets in all
        queues and refunds their reservations (the PR 10 fix: expiry used
        to run only inside the wave drains, so under continuous admission
        a ticket could sit past its deadline forever while no wave
        formed). In streaming mode the tick then asks the policy, per
        compatible group, whether to cut a wave; cut waves launch
        asynchronously and the previously in-flight wave resolves while
        the new one runs. A ready (or ``force``-drained) in-flight wave is
        resolved at the end of the tick; otherwise it stays in flight and
        the next tick collects it. Returns tickets resolved this tick.
        """
        done: List[ReleaseTicket] = []
        for n_records in list(self._pending):
            queue = self._pending[n_records]
            self._expire_deadlines(queue)
            if not queue:
                del self._pending[n_records]
        if self.lp is not None:
            self._expire_deadlines(self.lp.pending)
        if not self.streaming:
            return done
        for n_records in list(self._pending):
            done.extend(self._pump_queue("mwem", n_records, force))
        if self.lp is not None and self.lp.pending:
            done.extend(self._pump_queue("lp", None, force))
        if self._inflight is not None and (force or self._inflight_ready()):
            done.extend(self._resolve_inflight())
        return done

    def _pump_queue(self, kind: str, n_records: Optional[int],
                    force: bool) -> List[ReleaseTicket]:
        """Coalesce one queue: policy decision → pop → async launch →
        resolve the previous in-flight wave while the new one runs."""
        done: List[ReleaseTicket] = []
        queue = (self.lp.pending if kind == "lp"
                 else self._pending.get(n_records))
        while queue:
            self._expire_deadlines(queue)
            if not queue:
                break
            oldest = queue[0]
            decision = self.policy.decide(
                len(queue), monotonic(),
                oldest_submit=oldest.submit_time,
                oldest_deadline=oldest.deadline,
                force=force)
            if obs.enabled():
                self.metrics.gauge("coalescer_occupancy", kind=kind).set(
                    decision.occupancy)
                self.metrics.counter("wave_trigger_total", kind=kind,
                                     reason=decision.reason).inc()
            if not decision.dispatch:
                break
            take = min(len(queue), decision.wave_size, decision.occupancy)
            wave = queue[:take]
            del queue[:take]
            inflight = self._launch_streaming(kind, n_records, wave, decision)
            prev, self._inflight = self._inflight, inflight
            if prev is not None:
                # the new wave's scan is already running on device — this
                # block only waits on the *previous* wave (double buffer)
                done.extend(self._resolve_wave(prev))
        if kind == "mwem" and not self._pending.get(n_records):
            self._pending.pop(n_records, None)
        return done

    def _refill_wave(self, kind: str, wave: List[ReleaseTicket],
                     queue: List[ReleaseTicket]) -> None:
        """Between dispatch attempts: expire overdue in-wave tickets (the
        failed attempt produced nothing, so the refund leaks nothing) and
        promote queued tickets into the freed lanes — the serve-engine
        ``free_slots`` mid-wave refill lifted into the release path."""
        target = len(wave)
        now = monotonic()
        for t in list(wave):
            if t.deadline is not None and now >= t.deadline:
                wave.remove(t)
                self._abort_ticket(t, reason="expired", status="expired")
                self.stats.expired += 1
        while queue and len(wave) < target:
            t = queue.pop(0)
            if t.deadline is not None and now >= t.deadline:
                self._abort_ticket(t, reason="expired", status="expired")
                self.stats.expired += 1
                continue
            t.status = "queued"
            wave.append(t)
            self.stats.refilled_slots += 1
            if obs.enabled():
                self.metrics.counter("wave_slot_refills_total",
                                     kind=kind).inc()

    def _launch_streaming(self, kind: str, n_records: Optional[int],
                          wave: List[ReleaseTicket], decision: WaveDecision,
                          attempt: int = 0) -> Optional[_InflightWave]:
        """Journal and asynchronously dispatch one streaming wave on the
        smallest fitting ladder executable. Returns the in-flight handle,
        or None when every slot expired away or the dispatch failed
        terminally (tickets already resolved, reservations refunded)."""
        queue = (self.lp.pending if kind == "lp"
                 else self._pending.get(n_records, []))
        while True:
            if attempt > 0:
                self._refill_wave(kind, wave, queue)
            if not wave:
                return None
            size = min(self._ladder().fit(len(wave)), decision.wave_size)
            n_pad = size - len(wave)
            lanes = wave + [wave[0]] * n_pad
            keys = jnp.stack([jax.random.PRNGKey(t.seed) for t in lanes])
            # the decision rides the WAL record (trigger/wave_size/
            # occupancy) so `coalesce.replay_decisions` can rebuild the
            # coalescer's cuts from the journal alone; outside the
            # breaker-attributed try — see _run_lp_wave
            self._journal("dispatch-started", workload=kind, attempt=attempt,
                          rids=[[t.tenant_id, t.rid] for t in wave],
                          trigger=decision.reason, wave_size=size,
                          occupancy=decision.occupancy)
            self.wave_log.append(WaveDecision(True, decision.reason, size,
                                              decision.occupancy))
            try:
                with obs.annotate(f"serve/wave/{kind}/stream"):
                    fault_site("wave.dispatch")
                    if kind == "lp":
                        pending = launch_lp_batch(self.lp.A, self.lp.b,
                                                  self.lp.cfg, keys,
                                                  index=self.lp.index)
                    else:
                        # device_put starts the histogram transfer now, so
                        # it overlaps the still-running previous wave; the
                        # scan's carried state is donated inside the
                        # compiled driver (core._fused_driver)
                        h_stack = jax.device_put(np.stack(
                            [self.sessions[t.tenant_id].h for t in lanes]))
                        pending = launch_mwem_batch(
                            self.workload, h_stack,
                            self._group_cfg(n_records), keys,
                            index=self.index)
            except Exception as exc:
                attempt += 1
                if self._note_dispatch_failure(exc, wave, attempt, kind):
                    continue
                self._fail_wave(wave, exc)
                if not _retryable(exc):
                    raise
                return None
            return _InflightWave(kind=kind, n_records=n_records,
                                 tickets=wave, n_pad=n_pad, size=size,
                                 pending=pending,
                                 decision=WaveDecision(
                                     True, decision.reason, size,
                                     decision.occupancy),
                                 attempt=attempt)

    def _inflight_ready(self) -> bool:
        """Whether the in-flight wave's device work has landed (so
        resolving it will not block). Falls back to "ready" when the array
        type cannot say — resolving then blocks, which is correct, just
        not overlapped."""
        fl = self._inflight
        if fl is None:
            return False
        arr = (fl.pending.x_bar if fl.kind == "lp"
               else fl.pending.final_state.p_sum)
        is_ready = getattr(arr, "is_ready", None)
        return True if is_ready is None else bool(is_ready())

    def _resolve_inflight(self) -> List[ReleaseTicket]:
        fl, self._inflight = self._inflight, None
        if fl is None:
            return []
        return self._resolve_wave(fl)

    def _resolve_wave(self, fl: _InflightWave) -> List[ReleaseTicket]:
        """Block on one launched wave and run phase two. A retryable
        finish failure re-*launches* the wave (a failed computation cannot
        be re-blocked) with freed slots refilled; lanes are keyed by
        ``PRNGKey(ticket.seed)``, so the relaunch is bitwise identical and
        costs zero additional privacy — same contract as the batch retry
        loop."""
        while True:
            try:
                with obs.annotate(f"serve/wave/{fl.kind}/finish"):
                    if fl.kind == "lp":
                        result = finish_lp_batch(fl.pending)
                    else:
                        result = finish_mwem_batch(fl.pending)
            except Exception as exc:
                fl.attempt += 1
                if self._note_dispatch_failure(exc, fl.tickets, fl.attempt,
                                               fl.kind):
                    relaunched = self._launch_streaming(
                        fl.kind, fl.n_records, fl.tickets, fl.decision,
                        attempt=fl.attempt)
                    if relaunched is None:
                        return []
                    fl = relaunched
                    continue
                self._fail_wave(fl.tickets, exc)
                if not _retryable(exc):
                    raise
                return []
            break
        self.breaker.record_success()
        self.stats.dispatches += 1
        self.stats.padded_slots += fl.n_pad
        saved = self.wave_size - fl.size
        if saved > 0:
            # lanes the fixed-size path would have padded by replication
            self.stats.pad_slots_saved += saved
            if obs.enabled():
                self.metrics.counter("wave_pad_slots_saved_total",
                                     kind=fl.kind).inc(saved)
        self._record_wave_metrics(fl.kind, len(fl.tickets), fl.n_pad,
                                  lanes=fl.size)
        if obs.enabled():
            self.metrics.histogram("wave_latency_seconds", kind=fl.kind,
                                   lanes=fl.size).observe(
                                       result.total_seconds)
        if fl.kind == "lp":
            return self._deliver_lp(fl.tickets, result,
                                    trigger=fl.decision.reason)
        return self._deliver_mwem(fl.tickets, result,
                                  trigger=fl.decision.reason)

    def _lane_cost(self, sess: TenantSession, snap, per_run: PrivacyLedger,
                   k: int) -> tuple:
        """Marginal composed (ε, δ) of a tenant's (k+1)-th lane in one wave:
        replay the pre-dispatch snapshot plus k earlier lanes, then preview
        one more — a plain before/after ledger diff would double-count when
        one tenant holds several lanes."""
        tight = self.admission.tight
        ev0, g0, s0 = snap
        scratch = PrivacyLedger(
            target_delta_prime=sess.ledger.target_delta_prime)
        scratch.events = ev0 + list(per_run.events) * k
        scratch.index_failure_mass = g0 + k * per_run.index_failure_mass
        scratch.approx_slack = s0 + k * per_run.approx_slack
        before = scratch.composed(tight=tight)
        after = scratch.preview(per_run.events,
                                per_run.index_failure_mass,
                                per_run.approx_slack, tight=tight)
        return after[0] - before[0], after[1] - before[1]

    def _record_wave_metrics(self, kind: str, n_real: int, n_pad: int,
                             lanes: Optional[int] = None) -> None:
        """Per-dispatch wave health: occupancy (real lanes / executed
        lanes) and the padding waste the replication trick pays for short
        waves. ``lanes`` is the executed executable width — the adaptive
        ladder size in streaming mode, ``wave_size`` in batch mode."""
        if not obs.enabled():
            return
        lanes = lanes if lanes is not None else self.wave_size
        self.metrics.counter("wave_dispatches_total", kind=kind).inc()
        self.metrics.counter("wave_padded_slots_total", kind=kind).inc(n_pad)
        self.metrics.gauge("wave_occupancy", kind=kind).set(n_real / lanes)
        self.metrics.gauge("wave_padding_waste", kind=kind).set(n_pad / lanes)

    def _record_ticket_latency(self, ticket: ReleaseTicket,
                               trigger: Optional[str] = None) -> None:
        """Admission→answer latency for one resolved ticket, bucketed per
        workload kind ("mwem" | "lp"); the ticket keeps its own stamp too.
        Streaming waves pass the coalescer ``trigger`` so the distribution
        also splits by why the wave was cut (full vs deadline vs flush) —
        on a separate series, so the per-kind one batch mode populates
        keeps its identity."""
        ticket.latency_seconds = monotonic() - ticket.submit_time
        if obs.enabled():
            self.metrics.histogram("admission_to_answer_seconds",
                                   kind=ticket.kind).observe(
                                       ticket.latency_seconds)
            if trigger is not None:
                self.metrics.histogram("admission_to_answer_seconds",
                                       kind=ticket.kind,
                                       trigger=trigger).observe(
                                           ticket.latency_seconds)

    def _run_lp_wave(self) -> List[ReleaseTicket]:
        """Execute one LP wave: exactly ``wave_size`` seed lanes through one
        `solve_lp_batch` dispatch — the same pad-by-replication, retry
        discipline, two-phase commit, and marginal-cost replay as
        histogram waves (see `_run_wave`)."""
        lp = self.lp
        attempt = 0
        while True:
            self._expire_deadlines(lp.pending)
            if not lp.pending:
                return []
            # peek, don't pop: a failed dispatch leaves the tickets at the
            # queue head for the retry
            wave = lp.pending[:self.wave_size]
            n_pad = self.wave_size - len(wave)
            lanes = wave + [wave[0]] * n_pad
            keys = jnp.stack([jax.random.PRNGKey(t.seed) for t in lanes])
            # outside the breaker-attributed try: a WAL failure is not the
            # kernels' doing — it rides _journal's own retry policy, and a
            # persistent one propagates with the queue and reservations
            # intact (tickets were only peeked) instead of tripping the
            # breaker into a permanent degrade
            self._journal("dispatch-started", workload="lp",
                          attempt=attempt,
                          rids=[[t.tenant_id, t.rid] for t in wave])
            try:
                with obs.annotate("serve/wave/lp"):
                    fault_site("wave.dispatch")
                    result = solve_lp_batch(lp.A, lp.b, lp.cfg, keys,
                                            index=lp.index)
            except Exception as exc:
                attempt += 1
                if self._note_dispatch_failure(exc, wave, attempt, "lp"):
                    continue
                del lp.pending[:len(wave)]
                self._fail_wave(wave, exc)
                if not _retryable(exc):
                    raise
                return []
            self.breaker.record_success()
            break
        del lp.pending[:len(wave)]
        self.stats.padded_slots += n_pad
        self.stats.dispatches += 1
        self._record_wave_metrics("lp", len(wave), n_pad)
        return self._deliver_lp(wave, result)

    def _deliver_lp(self, wave: List[ReleaseTicket], result,
                    trigger: Optional[str] = None) -> List[ReleaseTicket]:
        """Phase two for an executed LP wave: per-ticket commit, marginal
        cost replay, journaled delivery. Shared verbatim between the batch
        drain and the streaming pipeline (``trigger`` is the coalescer
        reason, streaming only), so the two paths cannot drift."""
        # pre-commit ledger snapshots, for per-ticket marginal costs
        snaps = {t.tenant_id: self.sessions[t.tenant_id].ledger.bundle()
                 for t in wave}
        x_bar = np.asarray(result.x_bar)
        lanes_seen: Dict[str, int] = {}
        for i, ticket in enumerate(wave):
            # phase two per ticket, exception-safe: a commit/journal
            # failure fails *this* ticket (refunding its still-open
            # reservation) and moves on; a programming error fails the
            # rest of the wave too, then propagates — either way no
            # popped ticket is left stranded with a reservation held
            try:
                sess = self.sessions[ticket.tenant_id]
                self._commit_ticket(ticket)
                k = lanes_seen.get(ticket.tenant_id, 0)
                lanes_seen[ticket.tenant_id] = k + 1
                eps_cost, delta_cost = self._lane_cost(
                    sess, snaps[ticket.tenant_id], result.ledger, k)
                rel = ReleasedLP(
                    release_id=self._next_release,
                    x_bar=x_bar[i],
                    violated_frac=float(result.violated_fracs[i]),
                    eps_cost=eps_cost,
                    delta_cost=delta_cost,
                    seed=ticket.seed,
                )
                self._next_release += 1
                # WAL before state: if the delivery record can't land,
                # the session must not keep an artifact recovery would
                # lose (the charge stands either way — in-doubt rule)
                self._journal("release-delivered",
                              tenant_id=ticket.tenant_id,
                              ticket_id=ticket.ticket_id, release_kind="lp",
                              release_id=rel.release_id, seed=ticket.seed,
                              x_bar=x_bar[i].tolist(),
                              violated_frac=rel.violated_frac,
                              eps_cost=eps_cost, delta_cost=delta_cost)
                sess.add_lp_release(rel)
                ticket.release = rel
                ticket.final_error = rel.violated_frac
                ticket.status = "done"
                self.stats.lp_released += 1
                self._record_ticket_latency(ticket, trigger)
            except Exception as exc:
                if not _retryable(exc):
                    self._resolve_stranded(wave[i:], exc)
                    raise
                self._resolve_stranded([ticket], exc)
        return wave

    def _run_wave(self, n_records: int) -> List[ReleaseTicket]:
        """Execute one wave: exactly ``wave_size`` lanes, one dispatch.

        Short waves are padded by replicating the first slot (same
        histogram/key shapes keep the compiled executable; pad lanes carry
        no budget reservation and their outputs are dropped) — the
        slot-reuse trick the LM engine uses for ragged request batches.

        Exception safety (DESIGN.md §10): tickets are *peeked*, not
        popped. A retryable dispatch failure leaves them at the queue head
        and re-dispatches after capped exponential backoff; since every
        lane is keyed by ``PRNGKey(ticket.seed)``, the retry realizes
        bitwise-identical noise, so it costs zero additional privacy and
        commits exactly once. Budget commits only after the wave's results
        land — each lane's phase-one reservation is committed per ticket,
        then the delivered artifact is journaled.
        """
        queue = self._pending[n_records]
        attempt = 0
        while True:
            self._expire_deadlines(queue)
            if not queue:
                del self._pending[n_records]
                return []
            # peek, don't pop: a failed dispatch leaves the tickets at the
            # queue head for the retry
            wave = queue[:self.wave_size]
            # sharded lanes dispatch sequentially (no vmap), so padding a
            # short wave would burn a whole extra mesh run per pad slot
            n_pad = 0 if self.mesh is not None else self.wave_size - len(wave)
            lanes = wave + [wave[0]] * n_pad
            cfg = self._group_cfg(n_records)
            h_stack = jnp.asarray(
                np.stack([self.sessions[t.tenant_id].h for t in lanes]))
            keys = jnp.stack([jax.random.PRNGKey(t.seed) for t in lanes])
            # outside the breaker-attributed try — see _run_lp_wave
            self._journal("dispatch-started", workload="mwem",
                          attempt=attempt,
                          rids=[[t.tenant_id, t.rid] for t in wave])
            try:
                with obs.annotate("serve/wave/mwem"):
                    fault_site("wave.dispatch")
                    if self.mesh is not None:
                        result = run_mwem_sharded_batch(
                            self.workload, h_stack, cfg, keys,
                            mesh=self.mesh, index=self.index)
                    else:
                        result = run_mwem_batch(self.workload, h_stack, cfg,
                                                keys, index=self.index)
            except Exception as exc:
                attempt += 1
                if self._note_dispatch_failure(exc, wave, attempt, "mwem"):
                    continue
                del queue[:len(wave)]
                if not queue:
                    del self._pending[n_records]
                self._fail_wave(wave, exc)
                if not _retryable(exc):
                    raise
                return []
            self.breaker.record_success()
            break
        del queue[:len(wave)]
        if not queue:
            del self._pending[n_records]
        self.stats.padded_slots += n_pad
        self.stats.dispatches += 1
        self._record_wave_metrics("mwem", len(wave), n_pad)
        return self._deliver_mwem(wave, result)

    def _deliver_mwem(self, wave: List[ReleaseTicket], result,
                      trigger: Optional[str] = None) -> List[ReleaseTicket]:
        """Phase two for an executed histogram wave — see `_deliver_lp`."""
        # pre-commit ledger snapshots, for per-ticket marginal costs
        snaps = {t.tenant_id: self.sessions[t.tenant_id].ledger.bundle()
                 for t in wave}
        p_hat = np.asarray(result.p_hat)
        lanes_seen: Dict[str, int] = {}
        for i, ticket in enumerate(wave):
            # exception-safe phase two — see _run_lp_wave
            try:
                sess = self.sessions[ticket.tenant_id]
                self._commit_ticket(ticket)
                k = lanes_seen.get(ticket.tenant_id, 0)
                lanes_seen[ticket.tenant_id] = k + 1
                eps_cost, delta_cost = self._lane_cost(
                    sess, snaps[ticket.tenant_id], result.ledger, k)
                rel = ReleasedHistogram(
                    release_id=self._next_release,
                    p_hat=p_hat[i],
                    final_error=float(result.final_errors[i]),
                    eps_cost=eps_cost,
                    delta_cost=delta_cost,
                    seed=ticket.seed,
                )
                self._next_release += 1
                # WAL before state — see _run_lp_wave
                self._journal("release-delivered",
                              tenant_id=ticket.tenant_id,
                              ticket_id=ticket.ticket_id,
                              release_kind="mwem",
                              release_id=rel.release_id, seed=ticket.seed,
                              p_hat=p_hat[i].tolist(),
                              final_error=rel.final_error,
                              eps_cost=eps_cost, delta_cost=delta_cost)
                sess.add_release(rel)
                ticket.release = rel
                ticket.final_error = rel.final_error
                ticket.status = "done"
                self.stats.released += 1
                self._record_ticket_latency(ticket, trigger)
            except Exception as exc:
                if not _retryable(exc):
                    self._resolve_stranded(wave[i:], exc)
                    raise
                self._resolve_stranded([ticket], exc)
        return wave

    # ------------------------------------------------------------- answers
    def answer(self, tenant_id: str, q,
               release_id: Optional[int] = None) -> Answer:
        """Answer a linear query from the tenant's released histogram(s) —
        post-processing, zero additional ε; repeats served from the cache."""
        t0 = monotonic()
        ans = self.sessions[tenant_id].answer(q, release_id=release_id)
        self._record_answer(ans, t0)
        return ans

    def answer_derived(self, tenant_id: str, coeffs,
                       release_id: Optional[int] = None) -> Optional[Answer]:
        t0 = monotonic()
        ans = self.sessions[tenant_id].answer_derived(coeffs,
                                                      release_id=release_id)
        if ans is not None:
            self._record_answer(ans, t0)
        return ans

    def _record_answer(self, ans: Answer, t0: float) -> None:
        if not obs.enabled():
            return
        self.metrics.histogram("admission_to_answer_seconds",
                               kind="answer").observe(monotonic() - t0)
        name = ("answer_cache_hits_total" if ans.cached
                else "answer_cache_misses_total")
        self.metrics.counter(name).inc()

    # ------------------------------------------------------------- metrics
    def metrics_snapshot(self) -> dict:
        """Plain-dict view of the service's registry — admission→answer
        latency quantiles (p50/p95/p99) per workload kind, wave occupancy /
        padding gauges, per-tenant ε/δ-spent gauges kept consistent with
        each session ledger by its hook, cache and rejection counters, and
        the mechanism telemetry the drivers published. `benchmarks/run.py`
        embeds the same snapshot into BENCH_results.json."""
        return self.metrics.snapshot()
