"""The load generator against a fake service: reads are timed from their
due moment, a stall inside the service shows in the read tail, and
failures count against the attempts."""

import time

import pytest

from bench import loadgen


class FakeService:
    """Resolves, inside each call, the requests submitted at least
    ``period`` seconds before, as the service resolves a wave only inside
    ``submit`` or ``pump``. The ``stall_at``-th submit holds the caller
    for ``stall`` seconds, the way a wave resolving inside ``submit``
    holds the service's front end."""

    n_rows = 10

    def __init__(self, period=0.02, stall_at=None, stall=0.0, bad=()):
        self.period, self.stall_at, self.stall = period, stall_at, stall
        self.bad = set(bad)
        self.submits = 0
        self.done = set()
        self.open = []

    def _resolve(self):
        now = time.perf_counter()
        for h in [h for h in self.open if now - h["t"] >= self.period]:
            self.open.remove(h)
            h["state"] = "failed" if h["tenant"] in self.bad else "done"
            self.done.add(h["tenant"])

    def submit(self, tenant):
        self.submits += 1
        if self.submits == self.stall_at:
            time.sleep(self.stall)
        h = {"tenant": tenant, "t": time.perf_counter(), "state": "pending"}
        self.open.append(h)
        self._resolve()
        return h

    def state(self, h):
        return h["state"]

    def pump(self):
        self._resolve()

    def released(self, tenant):
        return tenant in self.done and tenant not in self.bad

    def read(self, tenant, row):
        return float(row), 0


MIX = {"releases": {"loop": "closed", "clients": 4, "deadline_s": None},
       "reads": {"rate_per_s": 400}}


def test_a_stall_shows_in_the_read_tail_timed_from_due():
    svc = FakeService(stall_at=12, stall=0.25)
    rec = loadgen.drive(svc, MIX, seed=3, seconds=0.6)
    reads = rec.window_reads()
    assert len(reads) > 100 and all(r.ok for r in reads)
    lat = [r.end - r.due for r in reads]
    late = [r.start - r.due for r in reads]
    # reads due during the stall waited for it: the tail holds the stall
    assert loadgen.quantile(lat, 0.99) > 0.15
    assert max(late) > 0.15
    # and without a stall the same mix is served promptly
    calm = loadgen.drive(FakeService(), MIX, seed=3, seconds=0.6)
    assert loadgen.quantile([r.end - r.due for r in calm.window_reads()],
                            0.99) < 0.1


def test_closed_loop_resubmits_on_delivery_and_times_from_issue():
    rec = loadgen.drive(FakeService(period=0.05), MIX, seed=1, seconds=0.4)
    t0, t1 = rec.window
    assert t1 - t0 >= 0.4
    done = rec.in_window()
    assert done and all(r.status == "done" for r in done)
    assert min(r.delivered - r.issued for r in done) >= 0.05
    # each client has at most one request outstanding
    for tenant in loadgen.tenant_names(MIX):
        mine = [r for r in rec.releases if r.tenant == tenant]
        assert all(a.delivered <= b.issued for a, b in zip(mine, mine[1:]))


def test_failures_count_against_attempts():
    svc = FakeService(bad={"tenant-001"})
    rec = loadgen.drive(svc, MIX, seed=2, seconds=0.3)
    rel = rec.in_window()
    assert any(r.status == "failed" for r in rel)
    assert any(r.status == "done" for r in rel)
    # reads go only to tenants that hold a release
    assert all(r.tenant != "tenant-001" for r in rec.window_reads())


def test_same_seed_same_schedule():
    a = loadgen.drive(FakeService(), MIX, seed=7, seconds=0.2)
    b = loadgen.drive(FakeService(), MIX, seed=7, seconds=0.2)
    gaps = lambda rec: [y.due - x.due for x, y in zip(rec.reads, rec.reads[1:])]  # noqa: E731
    n = min(len(a.reads), len(b.reads))
    assert gaps(a)[:n - 1] == pytest.approx(gaps(b)[:n - 1])
    assert [r.row for r in a.reads[:n]] == [r.row for r in b.reads[:n]]
