"""One general traffic generator, driven by a mix file (``traffic/*.json``).

A mix has two streams, both fixed by the run's seed:

* release requests — a closed loop of ``clients`` callers, one per
  tenant, each issuing its next request the moment the previous one is
  delivered;
* zero-ε answer reads — an open loop of Poisson arrivals at
  ``rate_per_s``, each on a tenant drawn uniformly from those holding a
  release when the read is due, with a uniformly drawn query row.

The idea is `repro.serve.loadgen`'s open loop, with three faults fixed:
every request is timed from when it was due (reads) or issued
(releases), not from when the service was called; failed, rejected and
expired requests are counted against the attempts instead of dropped; and
how late the generator ran is recorded beside each read.

The loop is single-threaded, like the service's own front end: a call
that blocks (a release wave resolving inside ``submit``) delays every read
due meanwhile, and that delay is part of the read's latency.

The measured window starts at the first delivery (the warm-up wave has
then run) and ends at the first delivery at or after ``seconds``, so the
release rate is not quantised by the wave period.
"""

from __future__ import annotations

import time
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Callable, List

import numpy as np


@dataclass
class Release:
    tenant: str
    issued: float
    handle: object
    delivered: float = float("nan")
    status: str = "pending"          # "pending" | "done" | "failed"


@dataclass
class Read:
    due: float
    start: float
    end: float
    tenant: str
    row: int
    ok: bool
    value: float = float("nan")
    release_id: int = -1


@dataclass
class Record:
    """Everything one run did, on the host clock (seconds)."""

    window: tuple = (float("nan"), float("nan"))
    releases: List[Release] = field(default_factory=list)
    reads: List[Read] = field(default_factory=list)
    spans: List[tuple] = field(default_factory=list)  # (name, start, end)

    def in_window(self) -> List[Release]:
        t0, t1 = self.window
        return [r for r in self.releases
                if r.status != "pending" and t0 < r.delivered <= t1]

    def window_reads(self) -> List[Read]:
        t0, t1 = self.window
        return [r for r in self.reads if t0 <= r.due <= t1]


def tenant_names(mix: dict) -> List[str]:
    return [f"tenant-{i:03d}" for i in range(mix["releases"]["clients"])]


def drive(target, mix: dict, seed: int, seconds: float, *,
          clock: Callable[[], float] = time.perf_counter,
          on_window_start: Callable[[], None] = lambda: None,
          annotate: Callable[[str], object] = lambda name: nullcontext(),
          max_wall: float = 600.0) -> Record:
    """Run ``mix`` against ``target`` until the window closes.

    ``target`` offers ``submit(tenant)`` → handle,
    ``state(handle)`` → "pending" | "done" | "failed", ``pump()``,
    ``released(tenant)`` → bool, ``read(tenant, row)`` → (value,
    release_id), and ``n_rows``. Calls into ``target`` are timed as spans
    (and, with ``annotate``, marked for a profiler as ``bench/<call>``).
    """
    if mix["releases"].get("loop") != "closed":
        raise ValueError("this generator drives closed-loop releases only")
    tenants = tenant_names(mix)
    q_rng = np.random.default_rng(
        np.random.SeedSequence([int(seed) % 2**63, 0x6C6F6164]))
    rec = Record()
    read_gap = 1.0 / float(mix["reads"]["rate_per_s"])

    def call(name, fn, *args):
        t0 = clock()
        try:
            with annotate(f"bench/{name}"):
                return fn(*args)
        finally:
            rec.spans.append((name, t0, clock()))

    outstanding: List[Release] = []
    resubmit = deque(tenants)
    released: List[str] = []
    state = {"start": None, "end": None, "next_read": None}
    t_begin = clock()

    def collect(t):
        """Stamp the requests a call just delivered with its return time."""
        for r in [r for r in outstanding if target.state(r.handle) != "pending"]:
            outstanding.remove(r)
            r.status, r.delivered = target.state(r.handle), t
            if r.status == "done" and r.tenant not in released \
                    and target.released(r.tenant):
                released.append(r.tenant)
            resubmit.append(r.tenant)
            if state["start"] is None:
                state["start"] = t
                state["next_read"] = t + q_rng.exponential(read_gap)
                on_window_start()
            elif state["end"] is None and t - state["start"] >= seconds:
                state["end"] = t

    def service(name, fn, *args):
        out = call(name, fn, *args)
        collect(rec.spans[-1][2])
        return out

    def issue(tenant):
        r = Release(tenant=tenant, issued=clock(), handle=None)
        r.handle = call("submit", target.submit, tenant)
        outstanding.append(r)
        rec.releases.append(r)
        collect(rec.spans[-1][2])

    while True:
        now = clock()
        if now - t_begin > max_wall:
            raise RuntimeError(f"the window did not close within {max_wall} s")
        # every read due by now (and, after the close, by the close)
        while state["next_read"] is not None and state["next_read"] <= now \
                and (state["end"] is None or state["next_read"] <= state["end"]):
            due = state["next_read"]
            state["next_read"] += q_rng.exponential(read_gap)
            # one draw each, whatever the timing, so the seed fixes the
            # schedule and the rows
            row = int(q_rng.integers(target.n_rows))
            u = q_rng.random()
            tenant = released[int(u * len(released))] if released else ""
            start = clock()
            try:
                value, rid = call("answer", target.read, tenant, row)
                ok = True
            except Exception:   # a failed read counts as missing
                value, rid, ok = float("nan"), -1, False
            rec.reads.append(Read(due, start, clock(), tenant, row, ok,
                                  value, rid))
            now = clock()
        if state["end"] is not None:
            rec.window = (state["start"], state["end"])
            break
        if resubmit:
            issue(resubmit.popleft())
        else:
            if outstanding:
                service("pump", target.pump)
            nxt = state["next_read"]
            pause = nxt - clock() if nxt is not None else 0.001
            time.sleep(min(max(pause, 0.0), 0.001))
    return rec


def quantile(values, q: float) -> float:
    """The ``q`` quantile (0..1) by linear interpolation; NaN when empty."""
    v = np.asarray(values, np.float64)
    return float(np.quantile(v, q)) if v.size else float("nan")
