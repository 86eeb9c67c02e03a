"""Streaming release serving (DESIGN.md §11).

The headline invariant: lanes are keyed by ``PRNGKey(ticket.seed)``, so
*however* the coalescing policy slices the admitted set into waves — and
whatever ladder executable each wave runs on — every lane's release is
bitwise identical to the fixed-wave batch path, the per-tenant ledgers
end in the same state, and the admission-time preview equals the
composed cost actually charged.

Also here: the coalescing-policy property tests (pure `decide`, driven
through arbitrary clock/occupancy trajectories by hypothesis), the
expire-on-every-tick regression (PR 10 fixed deadline expiry only
running inside wave drains), the AOT wave-size ladder (prewarm compiles
once; short waves run the smaller executable instead of padding), the
coalescer observability series, WAL replay of dispatch decisions, and a
short open-loop load-generator smoke for the CI fast lane.
"""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax

from repro.core import MWEMConfig
from repro.core.queries import gaussian_histogram, random_binary_queries
from repro.obs.metrics import MetricsRegistry
from repro.serve import (DeadlineOccupancyPolicy, LoadSpec, ReleaseService,
                         ScriptedPolicy, WaveLadder, replay_decisions,
                         run_open_loop)
from repro.serve.journal import Journal, read_records

U, M, N_RECORDS, WAVE = 64, 128, 300, 4
TENANTS = ("alice", "bob", "carol")


def make_workload():
    key = jax.random.PRNGKey(11)
    kh, kq = jax.random.split(key)
    h = gaussian_histogram(kh, N_RECORDS, U)
    return random_binary_queries(kq, M, U), np.asarray(h)


@pytest.fixture(scope="module")
def workload():
    return make_workload()


def make_service(Q, **kw):
    kw.setdefault("wave_size", WAVE)
    kw.setdefault("auto_flush", False)
    kw.setdefault("registry", MetricsRegistry())
    cfg = MWEMConfig(eps=0.5, delta=1e-3, T=4, mode="fast")
    return ReleaseService(Q, cfg, **kw)


def add_tenants(svc, h, names=TENANTS):
    for name in names:
        svc.create_session(name, eps_budget=50.0, delta_budget=0.9, h=h,
                           n_records=N_RECORDS)


def lp_workload(Q):
    A = np.abs(np.asarray(Q[:8]))
    b = np.full(8, 0.9, np.float32)
    return A, b


# --------------------------------------------------------------------------
# the AOT wave-size ladder
# --------------------------------------------------------------------------
class TestWaveLadder:
    def test_powers_of_two_up_to_max(self):
        assert WaveLadder.for_wave_size(8).sizes == (2, 4, 8)
        assert WaveLadder.for_wave_size(1).sizes == (1,)
        # a non-power-of-two max still tops the ladder
        assert WaveLadder.for_wave_size(6).sizes == (2, 4, 6)

    def test_fit_picks_smallest_holding_size(self):
        ladder = WaveLadder.for_wave_size(8)
        assert [ladder.fit(n) for n in (1, 2, 3, 4, 5, 8)] == [2, 2, 4, 4,
                                                               8, 8]
        assert ladder.fit(9) == 8  # capped at max

    def test_singleton_waves_pad_to_two_lanes(self):
        """The B=1 hazard: the degenerate single-lane executable lowers
        differently under XLA and can flip near-tied EM selections, so
        the ladder floors at 2 — a 1-ticket wave pads one replica slot
        instead of running the one executable whose answers can drift."""
        assert WaveLadder.for_wave_size(8).fit(1) == 2
        # wave_size 1 shares the single-lane executable with the batch
        # path, so parity holds trivially and the floor doesn't apply
        assert WaveLadder.for_wave_size(1).fit(1) == 1

    def test_rejects_degenerate(self):
        with pytest.raises(ValueError):
            WaveLadder.for_wave_size(0)
        with pytest.raises(ValueError):
            WaveLadder.for_wave_size(4).fit(0)

    @given(max_size=st.integers(1, 64), n=st.integers(1, 128))
    @settings(max_examples=100, deadline=None)
    def test_fit_bounds_padding_waste(self, max_size, n):
        ladder = WaveLadder.for_wave_size(max_size)
        s = ladder.fit(n)
        assert s in ladder.sizes
        if n > max_size:
            assert s == max_size
        elif n == 1:
            # the 2-lane floor (B=1 lowers differently; see coalesce.py)
            assert s == (1 if max_size == 1 else 2)
        else:
            # the power-of-two ladder's guarantee: <2× padding waste
            assert n <= s < 2 * n


# --------------------------------------------------------------------------
# the deadline/occupancy coalescing policy (pure — hypothesis drives it)
# --------------------------------------------------------------------------
class TestCoalescingPolicy:
    def test_empty_never_dispatches_even_forced(self):
        pol = DeadlineOccupancyPolicy(wave_size=WAVE)
        d = pol.decide(0, now=5.0, force=True)
        assert (d.dispatch, d.reason, d.wave_size) == (False, "empty", 0)

    def test_full_dispatches_at_max(self):
        pol = DeadlineOccupancyPolicy(wave_size=WAVE)
        d = pol.decide(WAVE, now=0.0)
        assert d.dispatch and d.reason == "full" and d.wave_size == WAVE

    def test_partial_without_deadline_holds(self):
        pol = DeadlineOccupancyPolicy(wave_size=WAVE)
        d = pol.decide(2, now=1e9)
        assert not d.dispatch and d.reason == "hold"

    def test_force_flushes_partial_on_fitting_size(self):
        pol = DeadlineOccupancyPolicy(wave_size=8)
        d = pol.decide(3, now=0.0, force=True)
        assert d.dispatch and d.reason == "flush" and d.wave_size == 4

    def test_half_spent_budget_triggers(self):
        pol = DeadlineOccupancyPolicy(wave_size=WAVE)
        # budget 10s from t=100: holds before t=105, dispatches from it
        hold = pol.decide(2, now=104.9, oldest_submit=100.0,
                          oldest_deadline=110.0)
        fire = pol.decide(2, now=105.0, oldest_submit=100.0,
                          oldest_deadline=110.0)
        assert not hold.dispatch and hold.reason == "hold"
        assert fire.dispatch and fire.reason == "deadline"
        assert fire.wave_size == 2

    def test_non_positive_budget_dispatches_immediately(self):
        pol = DeadlineOccupancyPolicy(wave_size=WAVE)
        d = pol.decide(1, now=0.0, oldest_submit=7.0, oldest_deadline=7.0)
        assert d.dispatch and d.reason == "deadline"

    def test_rejects_bad_half_frac(self):
        with pytest.raises(ValueError):
            DeadlineOccupancyPolicy(wave_size=2, half_frac=0.0)

    @given(occ=st.integers(0, 32), wave=st.integers(1, 16),
           budget=st.floats(0.01, 100.0),
           frac=st.floats(0.0, 2.0), force=st.booleans())
    @settings(max_examples=200, deadline=None)
    def test_policy_invariants(self, occ, wave, budget, frac, force):
        """The satellite-4 property suite, one trajectory per example:
        never dispatch empty, never hold a full wave, the half-budget
        bound, and a chosen wave size that always fits the occupancy."""
        pol = DeadlineOccupancyPolicy(wave_size=wave)
        submit = 100.0
        d = pol.decide(occ, now=submit + frac * budget,
                       oldest_submit=submit,
                       oldest_deadline=submit + budget, force=force)
        if occ == 0:                      # never dispatch an empty wave
            assert not d.dispatch and d.reason == "empty"
            return
        if occ >= wave:                   # never hold a full wave
            assert d.dispatch and d.reason == "full"
        if d.dispatch:                    # the executable fits the wave
            assert d.wave_size >= min(occ, pol.ladder.max_size)
            assert d.wave_size in pol.ladder.sizes
        assert d.occupancy == occ
        if 0 < occ < wave and not force and abs(frac - 0.5) > 1e-6:
            # the half-budget bound, both directions (away from the
            # boundary, where float rounding could flip the comparison)
            if frac >= 0.5:
                assert d.dispatch and d.reason == "deadline"
            else:
                assert not d.dispatch and d.reason == "hold"

    @given(occ=st.integers(1, 32), wave=st.integers(1, 16))
    @settings(max_examples=100, deadline=None)
    def test_deadline_free_tickets_only_ride_full_or_flush(self, occ, wave):
        pol = DeadlineOccupancyPolicy(wave_size=wave)
        d = pol.decide(occ, now=1e12)     # no deadline info, however late
        assert d.dispatch == (occ >= wave)
        forced = pol.decide(occ, now=1e12, force=True)
        assert forced.dispatch


# --------------------------------------------------------------------------
# deadline expiry runs on every tick (PR 10 regression)
# --------------------------------------------------------------------------
class TestExpiryOnPump:
    @pytest.mark.parametrize("streaming", [True, False])
    def test_pump_expires_without_a_wave(self, workload, streaming):
        """The fix: an overdue ticket is expired and refunded by the next
        `pump` tick even though no wave ever forms around it. Before, the
        expiry check lived inside the wave drains only, so under
        continuous admission a lone ticket could sit past its deadline
        holding its reservation until some unrelated wave drained."""
        Q, h = workload
        svc = make_service(Q, streaming=streaming)
        add_tenants(svc, h, ["alice"])
        t = svc.submit("alice", deadline=0.0)
        assert t.status == "queued" and t.rid is not None
        done = svc.pump()
        assert done == []
        assert t.status == "expired" and t.rid is None
        assert not svc.session("alice").ledger.reservations
        assert svc.stats.expired == 1
        assert svc.stats.dispatches == 0     # no wave ran to expire it
        assert svc.pending_count() == 0

    def test_pump_expires_lp_queue_too(self, workload):
        Q, h = workload
        svc = make_service(Q, streaming=True)
        svc.attach_lp(*lp_workload(Q))
        add_tenants(svc, h, ["alice"])
        t = svc.submit_lp("alice", deadline=0.0)
        svc.pump()
        assert t.status == "expired" and t.rid is None
        assert not svc.session("alice").ledger.reservations

    def test_pump_holds_partial_wave(self, workload):
        Q, h = workload
        svc = make_service(Q, streaming=True)
        add_tenants(svc, h, ["alice"])
        t = svc.submit("alice")              # no deadline: holds forever
        assert svc.pump() == []
        assert t.status == "queued" and svc.stats.dispatches == 0
        svc.flush()
        assert t.status == "done"


# --------------------------------------------------------------------------
# the headline invariant: streaming ≡ fixed-wave, bitwise, any slicing
# --------------------------------------------------------------------------
SLICINGS = [[1, 1, 1, 1, 1], [2, 1, 2], [3, 2], [4, 1], [5]]


class TestStreamingParity:
    def _batch_oracle(self, Q, h, seeds, lp_seeds=()):
        svc = make_service(Q)
        if lp_seeds:
            svc.attach_lp(*lp_workload(Q))
        add_tenants(svc, h)
        tickets = [svc.submit(TENANTS[i % len(TENANTS)], seed=s)
                   for i, s in enumerate(seeds)]
        lp_tickets = [svc.submit_lp(TENANTS[i % len(TENANTS)], seed=s)
                      for i, s in enumerate(lp_seeds)]
        svc.flush()
        return svc, tickets, lp_tickets

    def _streaming(self, Q, h, seeds, slices, lp_seeds=()):
        svc = make_service(
            Q, streaming=True,
            policy=ScriptedPolicy(wave_size=WAVE, slices=slices))
        if lp_seeds:
            svc.attach_lp(*lp_workload(Q))
        add_tenants(svc, h)
        tickets = [svc.submit(TENANTS[i % len(TENANTS)], seed=s)
                   for i, s in enumerate(seeds)]
        lp_tickets = [svc.submit_lp(TENANTS[i % len(TENANTS)], seed=s)
                      for i, s in enumerate(lp_seeds)]
        svc.flush()
        return svc, tickets, lp_tickets

    @pytest.mark.parametrize("slices", SLICINGS,
                             ids=["x".join(map(str, s)) for s in SLICINGS])
    def test_mwem_bitwise_any_slicing(self, workload, slices):
        Q, h = workload
        seeds = [100 + i for i in range(5)]
        svc_b, batch, _ = self._batch_oracle(Q, h, seeds)
        svc_s, stream, _ = self._streaming(Q, h, seeds, slices)
        assert all(t.status == "done" for t in batch + stream)
        for a, b in zip(batch, stream):
            np.testing.assert_array_equal(a.release.p_hat, b.release.p_hat)
            assert a.release.eps_cost == b.release.eps_cost
            assert a.final_error == b.final_error
        for name in TENANTS:
            lb, ls = svc_b.session(name).ledger, svc_s.session(name).ledger
            assert lb == ls
            assert lb.composed() == ls.composed()
        # the coalescer actually followed the script (plus the script-dry
        # waves that drain whatever the slices left behind)
        expected, left = [], len(seeds)
        for s in slices:
            if left <= 0:
                break
            take = max(1, min(s, left, WAVE))
            expected.append(take)
            left -= take
        while left > 0:
            take = min(left, WAVE)
            expected.append(take)
            left -= take
        assert [d.occupancy for d in svc_s.wave_log] == expected

    @pytest.mark.parametrize("slices", [[1, 1, 1], [2, 1], [3]],
                             ids=["1x1x1", "2x1", "3"])
    def test_lp_bitwise_any_slicing(self, workload, slices):
        Q, h = workload
        lp_seeds = [200, 201, 202]
        svc_b, _, batch = self._batch_oracle(Q, h, [], lp_seeds=lp_seeds)
        svc_s, _, stream = self._streaming(Q, h, [], slices,
                                           lp_seeds=lp_seeds)
        assert all(t.status == "done" for t in batch + stream)
        for a, b in zip(batch, stream):
            np.testing.assert_array_equal(a.release.x_bar, b.release.x_bar)
            assert a.release.violated_frac == b.release.violated_frac
            assert a.release.eps_cost == b.release.eps_cost
        for name in TENANTS:
            assert (svc_b.session(name).ledger
                    == svc_s.session(name).ledger)

    def test_mixed_tenants_and_workloads(self, workload):
        """Both workloads in flight, tenants holding multiple lanes: the
        scripted cuts land across both queues, and every artifact and
        every ledger still matches the fixed-wave oracle bitwise."""
        Q, h = workload
        seeds, lp_seeds = [300 + i for i in range(5)], [400, 401, 402]
        svc_b, mb, lb = self._batch_oracle(Q, h, seeds, lp_seeds=lp_seeds)
        svc_s, ms, ls = self._streaming(Q, h, seeds, [2, 1, 2, 2, 1],
                                        lp_seeds=lp_seeds)
        for a, b in zip(mb, ms):
            np.testing.assert_array_equal(a.release.p_hat, b.release.p_hat)
        for a, b in zip(lb, ls):
            np.testing.assert_array_equal(a.release.x_bar, b.release.x_bar)
        for name in TENANTS:
            blg, slg = svc_b.session(name).ledger, svc_s.session(name).ledger
            assert blg == slg
            assert blg.composed() == slg.composed()

    def test_preview_equals_composed(self, workload):
        """Admission's projected (ε, δ) — previewed over the ledger plus
        every open reservation — equals the cost actually composed once
        all the previewed lanes commit, in both drain modes."""
        Q, h = workload
        seeds = [500 + i for i in range(4)]
        for streaming in (False, True):
            svc = make_service(
                Q, streaming=streaming,
                policy=(ScriptedPolicy(wave_size=WAVE, slices=[1, 2, 1])
                        if streaming else None))
            add_tenants(svc, h, ["alice"])
            tickets = [svc.submit("alice", seed=s) for s in seeds]
            svc.flush()
            last = tickets[-1].decision
            assert svc.session("alice").ledger.composed() == (
                last.eps_projected, last.delta_projected)

    def test_wave_log_replays_from_journal(self, workload, tmp_path):
        """Every streaming dispatch decision rides the WAL: rebuilding
        the decision sequence from the journal alone reproduces the live
        `wave_log` — trigger reasons, ladder sizes, occupancies."""
        Q, h = workload
        path = tmp_path / "wal.jsonl"
        svc = make_service(
            Q, streaming=True, journal=Journal(path),
            policy=ScriptedPolicy(wave_size=WAVE, slices=[2, 1, 2]))
        add_tenants(svc, h)
        for i in range(5):
            svc.submit(TENANTS[i % len(TENANTS)], seed=600 + i)
        svc.flush()
        svc.journal.close()
        assert replay_decisions(read_records(path)) == svc.wave_log
        assert [d.reason for d in svc.wave_log] == ["scripted"] * 3

    def test_batch_journal_records_replay_empty(self, workload, tmp_path):
        """Pre-PR-10 `dispatch-started` records carry no trigger field;
        `replay_decisions` skips them instead of crashing — the WAL stays
        forward/backward compatible."""
        Q, h = workload
        path = tmp_path / "wal.jsonl"
        svc = make_service(Q, journal=Journal(path))
        add_tenants(svc, h, ["alice"])
        svc.submit("alice", seed=1)
        svc.flush()
        svc.journal.close()
        assert replay_decisions(read_records(path)) == []


# --------------------------------------------------------------------------
# the streaming service: ladder executables, prewarm, double buffer, obs
# --------------------------------------------------------------------------
class TestStreamingService:
    def test_streaming_forbids_mesh(self, workload):
        Q, _ = workload
        with pytest.raises(ValueError, match="single-device"):
            make_service(Q, streaming=True, mesh=object())

    def test_prewarm_compiles_ladder_once(self, workload):
        Q, h = workload
        svc = make_service(Q, streaming=True)
        add_tenants(svc, h, ["alice"])
        first = svc.prewarm(n_records=N_RECORDS)
        assert set(first) == {2, 4}
        # the second prewarm is a pure cache hit — nothing recompiles
        assert svc.prewarm(n_records=N_RECORDS) == {2: False, 4: False}

    def test_prewarm_lp_requires_attach(self, workload):
        Q, _ = workload
        svc = make_service(Q, streaming=True)
        with pytest.raises(ValueError, match="attach_lp"):
            svc.prewarm(lp=True)

    def test_short_wave_runs_smaller_executable(self, workload):
        """The acceptance criterion: a 2-ticket wave runs on the 2-lane
        ladder executable instead of being padded to ``wave_size`` by
        slot replication — no pad lanes burned, the saving accounted."""
        Q, h = workload
        svc = make_service(Q, streaming=True)
        add_tenants(svc, h)
        t0 = svc.submit("alice", seed=1)
        t1 = svc.submit("bob", seed=2)
        svc.flush()
        assert t0.status == t1.status == "done"
        assert svc.stats.padded_slots == 0
        assert svc.stats.pad_slots_saved == WAVE - 2
        (decision,) = svc.wave_log
        assert decision.wave_size == 2 and decision.reason == "flush"
        assert svc.metrics.counter("wave_pad_slots_saved_total",
                                   kind="mwem").value == WAVE - 2

    def test_full_wave_saves_nothing(self, workload):
        Q, h = workload
        svc = make_service(Q, streaming=True)
        add_tenants(svc, h)
        for i in range(WAVE):
            svc.submit(TENANTS[i % len(TENANTS)], seed=10 + i)
        svc.pump()
        assert svc.stats.pad_slots_saved == 0
        (decision,) = svc.wave_log
        assert decision.reason == "full" and decision.wave_size == WAVE

    def test_auto_flush_dispatches_full_wave_via_pump(self, workload):
        Q, h = workload
        svc = make_service(Q, streaming=True, auto_flush=True)
        add_tenants(svc, h)
        tickets = [svc.submit(TENANTS[i % len(TENANTS)], seed=20 + i)
                   for i in range(WAVE)]
        svc.flush()                      # collects the in-flight wave
        assert all(t.status == "done" for t in tickets)
        assert any(d.reason == "full" for d in svc.wave_log)

    def test_double_buffer_overlaps_waves(self, workload):
        """Two scripted waves in one tick: the first wave is resolved
        *after* the second is launched (the double buffer), yet delivery
        order and results are unchanged."""
        Q, h = workload
        svc = make_service(
            Q, streaming=True,
            policy=ScriptedPolicy(wave_size=WAVE, slices=[2, 2]))
        add_tenants(svc, h)
        tickets = [svc.submit(TENANTS[i % len(TENANTS)], seed=30 + i)
                   for i in range(4)]
        done = svc.flush()
        assert [t.ticket_id for t in done] == [t.ticket_id for t in tickets]
        assert len(svc.wave_log) == 2
        assert svc._inflight is None

    def test_coalescer_obs_series(self, workload):
        """Satellite 4's obs assertions: the occupancy gauge and trigger
        counter publish per kind, per-wave-size phase histograms (wait,
        final error, delivery) key by executed lane count, and `admission_to_answer_seconds` splits by
        trigger reason on its own series — the plain per-kind series the
        batch path populates keeps its identity."""
        Q, h = workload
        svc = make_service(Q, streaming=True)
        add_tenants(svc, h)
        for i in range(WAVE):            # a full wave...
            svc.submit(TENANTS[i % len(TENANTS)], seed=40 + i)
        svc.pump()
        svc.submit("alice", seed=50)     # ...then a flushed short one
        svc.flush()
        snap = svc.metrics.snapshot()
        hists, counters = snap["histograms"], snap["counters"]
        assert "admission_to_answer_seconds{kind=mwem}" in hists
        assert "admission_to_answer_seconds{kind=mwem,trigger=full}" in hists
        assert ("admission_to_answer_seconds{kind=mwem,trigger=flush}"
                in hists)
        # one observation per phase of each resolved wave, keyed by lanes
        for lanes in (4, 2):
            for phase in ("wait", "final_error", "deliver"):
                key = (f"wave_phase_seconds{{kind=mwem,lanes={lanes},"
                       f"phase={phase}}}")
                assert hists[key]["count"] == 1, key
        assert counters["wave_trigger_total{kind=mwem,reason=full}"] >= 1
        assert counters["wave_trigger_total{kind=mwem,reason=flush}"] >= 1
        assert "coalescer_occupancy{kind=mwem}" in snap["gauges"]
        # the trigger split partitions the per-kind distribution
        split = [v for k, v in hists.items()
                 if k.startswith("admission_to_answer_seconds{kind=mwem,")]
        total = hists["admission_to_answer_seconds{kind=mwem}"]
        assert sum(s["count"] for s in split) == total["count"]


# --------------------------------------------------------------------------
# open-loop load generator — the CI fast-lane smoke (satellite 6)
# --------------------------------------------------------------------------
class TestLoadgenSmoke:
    def test_short_open_loop_run(self, workload):
        Q, h = workload
        svc = make_service(Q, streaming=True, default_deadline=30.0)
        add_tenants(svc, h)
        svc.prewarm(n_records=N_RECORDS)
        spec = LoadSpec(duration=0.25, rate=40.0, seed=3,
                        mix={"mwem": 0.7, "answer": 0.3}, max_wall=60.0)
        rep = run_open_loop(svc, spec)
        assert rep.counts["offered"] > 0
        assert rep.counts["done"] > 0
        assert rep.counts["done"] + rep.counts["expired"] + \
            rep.counts["failed"] == len(rep.tickets)
        assert rep.sustained_qps > 0
        q = rep.quantiles["mwem"]
        assert np.isfinite([q["p50"], q["p95"], q["p99"]]).all()
        assert q["p50"] <= q["p95"] <= q["p99"]
        assert rep.latencies["mwem"].size == rep.counts["done"]
        # nothing left holding budget after the final flush
        for sess in svc.sessions.values():
            assert not sess.ledger.reservations

    def test_lp_mass_folds_into_mwem_without_attach(self, workload):
        Q, h = workload
        svc = make_service(Q, streaming=True)
        add_tenants(svc, h, ["alice"])
        spec = LoadSpec(duration=0.1, rate=30.0, seed=5,
                        mix={"mwem": 0.5, "lp": 0.5})
        rep = run_open_loop(svc, spec)
        assert all(t.kind == "mwem" for t in rep.tickets)
        assert rep.latencies["lp"].size == 0

    def test_no_tenants_rejected(self, workload):
        Q, _ = workload
        svc = make_service(Q, streaming=True)
        with pytest.raises(ValueError, match="no tenant"):
            run_open_loop(svc, LoadSpec(duration=0.01))
