"""Write-ahead journal for the release service (DESIGN.md §10).

Privacy budget is irreplaceable, so the serving tier's budget state must
survive the process: every transition of the two-phase budget commit is
appended to a JSONL journal *before* the in-memory state moves, and
`recover()` replays the journal into fresh `TenantSession`s whose ledgers
equal the live service's (bitwise — JSON floats round-trip exactly via
shortest-repr, and commit replays through the same `record_events` path).

Record kinds, in the order one release produces them:

* ``session-created``   — tenant id, histogram, n_records, (ε, δ) budget
* ``reserved``          — phase one: rid + the exact cost bundle held
* ``dispatch-started``  — a wave attempt began for these rids
* ``committed``         — phase two: the rid's bundle entered the ledger
* ``aborted``           — the rid was refunded (expired / failed / shed)
* ``release-delivered`` — the released artifact (p_hat or x_bar) landed

plus two snapshot kinds written only by `ReleaseService.adopt` so the
post-adoption WAL is self-contained (a second recovery — from a fresh
journal file, or from the same file the adopter keeps appending to —
reconstructs the adopted state without re-reading the pre-crash records):

* ``ledger-snapshot``   — one tenant's full committed bundle + next rid
* ``service-snapshot``  — issued seeds and the ticket/release counters

In-doubt resolution (the crash-recovery rule the chaos suite pins): a
reservation with a ``dispatch-started`` record but no ``committed`` /
``aborted`` resolution is replayed as **committed** — the dispatch may
have realized noise (and even delivered) before the crash, so the
conservative reading charges the budget. A reservation that never reached
dispatch is refunded: no randomness was consumed, nothing could have
leaked, and the request is simply gone with the queue.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from repro.faults import fault_site
from repro.obs import trace as obs
from repro.obs.clock import perf_counter
from repro.obs.metrics import MetricsRegistry, default_registry
from repro.serve.session import ReleasedHistogram, ReleasedLP, TenantSession


class Journal:
    """Append-only JSONL write-ahead log.

    Each `append` writes one self-contained JSON object and flushes it to
    the OS; ``fsync=True`` additionally forces it to disk per record (the
    durable-against-power-loss mode — default off so tests and benchmarks
    stay fast while still surviving process crashes).
    """

    def __init__(self, path, fsync: bool = False):
        self.path = os.fspath(path)
        self._fsync = fsync
        self._fh = open(self.path, "a", encoding="utf-8")
        self._seq = 0

    def append(self, rec_kind: str, **payload) -> dict:
        fault_site("journal.append")
        # seq/kind are authoritative — a payload key can never shadow them
        rec = {**payload, "seq": self._seq, "kind": rec_kind}
        with obs.annotate("serve/journal/append"):
            self._fh.write(json.dumps(rec, sort_keys=True) + "\n")
            self._fh.flush()
            if self._fsync:
                with obs.annotate("serve/journal/fsync"):
                    os.fsync(self._fh.fileno())
        self._seq += 1
        return rec

    def close(self) -> None:
        if not self._fh.closed:
            self._fh.close()

    def __enter__(self) -> "Journal":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def read_records(path) -> List[dict]:
    """All journal records, in append order. A torn final line (crash mid-
    write) is dropped — everything before it was flushed whole."""
    records: List[dict] = []
    with open(os.fspath(path), "r", encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                break  # torn tail record — the crash interrupted this write
    return records


def encode_bundle(bundle) -> dict:
    events, gamma, slack = bundle
    return {"events": [[e0, d0, label] for e0, d0, label in events],
            "gamma": gamma, "slack": slack}


def decode_bundle(obj) -> tuple:
    return ([(e0, d0, label) for e0, d0, label in obj["events"]],
            obj["gamma"], obj["slack"])


@dataclass
class RecoveredState:
    """What `recover()` reconstructs from a journal."""

    sessions: Dict[str, TenantSession] = field(default_factory=dict)
    # reservations resolved by the in-doubt rule (dispatched, no commit
    # record) — charged conservatively; surface them so an operator can see
    # exactly which budget was burned by the crash
    in_doubt: List[tuple] = field(default_factory=list)   # (tenant_id, rid)
    refunded: List[tuple] = field(default_factory=list)   # never dispatched
    issued_seeds: set = field(default_factory=set)
    # per-tenant: one past the highest rid the journal ever mentioned —
    # recovered ledgers are fast-forwarded to it, and `adopt` re-applies
    # it, so a post-recovery reserve can never reuse a journaled rid
    next_rids: Dict[str, int] = field(default_factory=dict)
    next_release_id: int = 0
    next_ticket_id: int = 0
    seconds: float = 0.0


def recover(path, registry: Optional[MetricsRegistry] = None,
            tight: bool = False) -> RecoveredState:
    """Replay a journal into fresh sessions + ledgers.

    Commits replay in journal order through `PrivacyLedger.record_events`
    — the same call `commit` makes live — so a recovered ledger equals the
    live one (dataclass equality over events/γ/slack) in either
    composition mode; ``tight`` only selects the mode used for the
    recovery-time budget gauges.
    """
    t0 = perf_counter()
    state = RecoveredState()
    # (tenant_id, rid) -> (bundle, dispatched?)
    pending: Dict[tuple, list] = {}

    def saw_rid(tenant_id: str, next_rid: int) -> None:
        state.next_rids[tenant_id] = max(
            state.next_rids.get(tenant_id, 0), int(next_rid))

    for rec in read_records(path):
        kind = rec["kind"]
        if kind == "session-created":
            # a repeated session-created (an adoption snapshot appended to
            # the same WAL) supersedes the earlier replay: the snapshot
            # records that follow carry the full post-recovery state
            sess = TenantSession(
                tenant_id=rec["tenant_id"],
                h=np.asarray(rec["h"], np.float32),
                n_records=int(rec["n_records"]),
                eps_budget=rec["eps_budget"],
                delta_budget=rec["delta_budget"],
            )
            state.sessions[sess.tenant_id] = sess
        elif kind == "reserved":
            key = (rec["tenant_id"], rec["rid"])
            pending[key] = [decode_bundle(rec["bundle"]), False]
            state.issued_seeds.add(int(rec["seed"]))
            saw_rid(rec["tenant_id"], rec["rid"] + 1)
            state.next_ticket_id = max(state.next_ticket_id,
                                       rec["ticket_id"] + 1)
        elif kind == "dispatch-started":
            for tenant_id, rid in rec["rids"]:
                entry = pending.get((tenant_id, rid))
                if entry is not None:
                    entry[1] = True
        elif kind == "committed":
            # tolerate duplicate commit records (a crash between the ledger
            # move and the journal write, then an in-doubt resolution on a
            # previous recovery, can journal the same rid twice)
            entry = pending.pop((rec["tenant_id"], rec["rid"]), None)
            if entry is not None:
                state.sessions[rec["tenant_id"]].ledger.record_events(
                    *entry[0])
        elif kind == "aborted":
            pending.pop((rec["tenant_id"], rec["rid"]), None)
        elif kind == "ledger-snapshot":
            # adoption snapshot: the tenant's full committed bundle in one
            # record (the session-created just before it reset the ledger)
            state.sessions[rec["tenant_id"]].ledger.record_events(
                *decode_bundle(rec["bundle"]))
            saw_rid(rec["tenant_id"], rec.get("next_rid", 0))
        elif kind == "service-snapshot":
            state.issued_seeds |= {int(s) for s in rec["issued_seeds"]}
            state.next_ticket_id = max(state.next_ticket_id,
                                       int(rec["next_ticket_id"]))
            state.next_release_id = max(state.next_release_id,
                                        int(rec["next_release_id"]))
        elif kind == "release-delivered":
            sess = state.sessions[rec["tenant_id"]]
            if rec["release_kind"] == "mwem":
                sess.add_release(ReleasedHistogram(
                    release_id=rec["release_id"],
                    p_hat=np.asarray(rec["p_hat"], np.float32),
                    final_error=rec["final_error"],
                    eps_cost=rec["eps_cost"],
                    delta_cost=rec["delta_cost"],
                    seed=rec["seed"],
                ))
            else:
                sess.add_lp_release(ReleasedLP(
                    release_id=rec["release_id"],
                    x_bar=np.asarray(rec["x_bar"], np.float32),
                    violated_frac=rec["violated_frac"],
                    eps_cost=rec["eps_cost"],
                    delta_cost=rec["delta_cost"],
                    seed=rec["seed"],
                ))
            state.next_release_id = max(state.next_release_id,
                                        rec["release_id"] + 1)
        # unknown kinds are skipped: journals are forward-compatible

    # resolve what the crash left open, in reservation order
    for (tenant_id, rid), (bundle, dispatched) in pending.items():
        if dispatched:
            # noise may already have been realized — charge conservatively
            state.sessions[tenant_id].ledger.record_events(*bundle)
            state.in_doubt.append((tenant_id, rid))
        else:
            state.refunded.append((tenant_id, rid))

    # recovered ledgers must never re-issue a rid the WAL already holds —
    # an in-doubt reservation's record would then resolve the wrong one
    # on the next replay
    for tenant_id, sess in state.sessions.items():
        sess.ledger.advance_rid(state.next_rids.get(tenant_id, 0))

    state.seconds = perf_counter() - t0
    if obs.enabled():
        reg = registry if registry is not None else default_registry()
        reg.histogram("recovery_seconds").observe(state.seconds)
        reg.counter("recovery_in_doubt_total").inc(len(state.in_doubt))
        reg.counter("recovery_refunded_total").inc(len(state.refunded))
        for sess in state.sessions.values():
            eps, delta = sess.ledger.composed(tight=tight)
            reg.gauge("tenant_eps_spent", tenant=sess.tenant_id).set(eps)
            reg.gauge("tenant_delta_spent", tenant=sess.tenant_id).set(delta)
    return state
