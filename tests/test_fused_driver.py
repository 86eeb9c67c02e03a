"""Fused `lax.scan` driver vs the host loop (DESIGN.md §2).

The two drivers consume randomness through the identical split chain, so on
one backend they should agree exactly (up to XLA float reassociation flipping
rare near-ties); the statistical tests below are robust to those flips while
still failing loudly on any systematic divergence.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MWEMConfig, run_mwem, run_mwem_batch, run_mwem_fused
from repro.core.accountant import PrivacyLedger
from repro.core.queries import gaussian_histogram, max_error, random_binary_queries
from repro.mips import FlatAbsIndex, NSWIndex, augment_complement
from repro.obs import trace as obs_trace
from repro.obs.metrics import default_registry


@pytest.fixture(scope="module")
def workload():
    key = jax.random.PRNGKey(0)
    kh, kq = jax.random.split(key)
    U, m, n = 64, 128, 300
    h = gaussian_histogram(kh, n, U)
    Q = random_binary_queries(kq, m, U)
    return Q, h, n


@pytest.fixture(scope="module")
def index(workload):
    Q, _, _ = workload
    return FlatAbsIndex(Q)


def _tv(p, q):
    return 0.5 * np.abs(np.asarray(p) - np.asarray(q)).sum()


class TestEquivalence:
    def test_routing(self, workload, index):
        Q, h, n = workload
        aug = augment_complement(np.asarray(Q))
        nsw = NSWIndex(aug, deg=8, ef=16, rounds=2, seed=0)
        from repro.core.mwem import _resolve_driver

        assert _resolve_driver(MWEMConfig(n_records=n), index) == "fused"
        # NSW's fixed-shape beam search traces since the megakernel PR —
        # auto-routing sends it through the fused scan like every other
        # built-in index (host remains available explicitly)
        assert _resolve_driver(MWEMConfig(n_records=n), nsw) == "fused"
        assert _resolve_driver(MWEMConfig(mode="exact", n_records=n), None) == "fused"
        res = run_mwem(Q, h, MWEMConfig(T=4, n_records=n, driver="fused"),
                       jax.random.PRNGKey(0), index=nsw)
        assert len(res.selected) == 4

        class HostOnly:
            supports_in_graph = False
            approx_margin = 0.0
            failure_mass = 0.0

        assert _resolve_driver(MWEMConfig(n_records=n), HostOnly()) == "host"
        with pytest.raises(ValueError, match="host"):
            run_mwem(Q, h, MWEMConfig(n_records=n, driver="fused"),
                     jax.random.PRNGKey(0), index=HostOnly())

    def test_selection_distributions_match(self, workload, index):
        """TV distance between fused and host-loop selection frequencies
        over many seeds is tiny (they share the PRNG chain)."""
        Q, h, n = workload
        m = Q.shape[0]
        T, B = 6, 25
        cfg = MWEMConfig(T=T, mode="fast", n_records=n)
        cfg_host = MWEMConfig(T=T, mode="fast", n_records=n, driver="host")
        keys = jnp.stack([jax.random.PRNGKey(s) for s in range(B)])
        fused = run_mwem_batch(Q, h, cfg, keys, index=index)
        host_sel = []
        for s in range(B):
            host_sel.extend(
                run_mwem(Q, h, cfg_host, jax.random.PRNGKey(s), index=index).selected)
        f = np.bincount(fused.selected.ravel(), minlength=m) / (B * T)
        g = np.bincount(np.asarray(host_sel), minlength=m) / (B * T)
        assert _tv(f, g) < 0.1

    def test_identical_ledger_totals(self, workload, index):
        Q, h, n = workload
        for mode, idx in (("fast", index), ("exact", None)):
            cfg = MWEMConfig(eps=1.0, delta=1e-3, T=16, mode=mode, n_records=n)
            cfg_host = MWEMConfig(eps=1.0, delta=1e-3, T=16, mode=mode,
                                  n_records=n, driver="host")
            rf = run_mwem(Q, h, cfg, jax.random.PRNGKey(5), index=idx)
            rh = run_mwem(Q, h, cfg_host, jax.random.PRNGKey(5), index=idx)
            assert rf.ledger.composed() == rh.ledger.composed()
            assert rf.ledger.basic() == rh.ledger.basic()
            assert len(rf.ledger.events) == len(rh.ledger.events)

    def test_fused_error_tracks_host(self, workload, index):
        Q, h, n = workload
        cfg = MWEMConfig(T=60, mode="fast", n_records=n)
        cfg_host = MWEMConfig(T=60, mode="fast", n_records=n, driver="host")
        rf = run_mwem(Q, h, cfg, jax.random.PRNGKey(7), index=index)
        rh = run_mwem(Q, h, cfg_host, jax.random.PRNGKey(7), index=index)
        assert abs(rf.final_error - rh.final_error) < 0.05
        uniform = float(max_error(Q, h, jnp.full_like(h, 1 / h.shape[0])))
        assert rf.final_error < uniform

    def test_eval_every_trace(self, workload, index):
        Q, h, n = workload
        cfg = MWEMConfig(T=20, mode="fast", eval_every=5, n_records=n)
        res = run_mwem(Q, h, cfg, jax.random.PRNGKey(6), index=index)
        assert [t for t, _ in res.errors] == [5, 10, 15, 20]
        assert all(np.isfinite(e) for _, e in res.errors)


class TestOverflowFallback:
    def test_tiny_tail_cap_falls_back_in_graph(self, workload, index):
        """tail_cap=1 forces C > cap almost every step; the in-graph
        `lax.cond` fallback must reproduce the host loop's exhaustive redo."""
        Q, h, n = workload
        cfg = MWEMConfig(T=12, mode="fast", n_records=n, tail_cap=1)
        cfg_host = MWEMConfig(T=12, mode="fast", n_records=n, tail_cap=1,
                              driver="host")
        rf = run_mwem(Q, h, cfg, jax.random.PRNGKey(3), index=index)
        rh = run_mwem(Q, h, cfg_host, jax.random.PRNGKey(3), index=index)
        assert rf.overflow_count > 0
        assert rf.overflow_count == rh.overflow_count
        m = Q.shape[0]
        assert all(0 <= sel < m for sel in rf.selected)
        # fallback iterations score all m candidates, lazy ones ≤ k+1
        assert sum(s == m for s in rf.n_scored) == rf.overflow_count
        assert rf.n_scored == rh.n_scored
        assert np.isfinite(rf.final_error)

    def test_no_overflow_with_default_cap(self, workload, index):
        Q, h, n = workload
        cfg = MWEMConfig(T=30, mode="fast", n_records=n)
        res = run_mwem(Q, h, cfg, jax.random.PRNGKey(4), index=index)
        assert res.overflow_count == 0
        # sublinear scoring: mean evaluations well below m
        assert np.mean(res.n_scored) < Q.shape[0] * 0.9


class TestAbsTopKKernel:
    def test_matches_jnp_abs_path(self):
        """`mips_abs_topk` (one streaming pass merging both signs) returns
        the same augmented-id top-k as the jnp abs path."""
        from repro.kernels.mips_topk import mips_abs_topk

        Q = jax.random.uniform(jax.random.PRNGKey(0), (200, 64))
        v = jax.random.normal(jax.random.PRNGKey(1), (64,))
        k = 15
        aug_k, s_k = mips_abs_topk(Q, v, k, block_n=64, block_d=32,
                                   interpret=True)
        aug_j, s_j = FlatAbsIndex(Q).query(v, k)
        assert set(np.asarray(aug_k).tolist()) == set(np.asarray(aug_j).tolist())
        np.testing.assert_allclose(np.sort(np.asarray(s_k)),
                                   np.sort(np.asarray(s_j)), atol=1e-5)


@pytest.fixture(scope="module")
def ivf_indices(workload):
    """The same IVF structure under both probe routes: XLA gather vs the
    fused Pallas kernel (interpret mode on CPU)."""
    from repro.mips import IVFIndex

    Q, _, _ = workload
    aug = augment_complement(np.asarray(Q))
    return (IVFIndex(aug, seed=0, train_iters=3, use_pallas="never"),
            IVFIndex(aug, seed=0, train_iters=3, use_pallas="always"))


class TestKernelizedProbe:
    """DESIGN.md §3: swapping the kernelized IVF probe into the fused scan
    must leave the driver's traces unchanged."""

    def test_fused_traces_unchanged(self, workload, ivf_indices):
        Q, h, n = workload
        ivf_xla, ivf_ker = ivf_indices
        cfg = MWEMConfig(T=6, mode="fast", n_records=n)
        rx = run_mwem_fused(Q, h, cfg, jax.random.PRNGKey(2), index=ivf_xla)
        rk = run_mwem_fused(Q, h, cfg, jax.random.PRNGKey(2), index=ivf_ker)
        assert rx.selected == rk.selected
        assert rx.n_scored == rk.n_scored
        assert rx.overflow_count == rk.overflow_count
        assert abs(rx.final_error - rk.final_error) < 1e-5

    def test_waved_batch_matches_singles(self, workload, ivf_indices):
        """`run_mwem_batch` routes batch-probe indices through the waved
        scan core (one probe call per iteration for all lanes); every lane
        must reproduce its standalone fused run exactly."""
        ivf_xla, _ = ivf_indices
        Q, h, n = workload
        B = 3
        cfg = MWEMConfig(T=6, mode="fast", n_records=n)
        keys = jnp.stack([jax.random.PRNGKey(s) for s in range(B)])
        batch = run_mwem_batch(Q, h, cfg, keys, index=ivf_xla)
        for b in range(B):
            single = run_mwem_fused(Q, h, cfg, jax.random.PRNGKey(b),
                                    index=ivf_xla)
            assert list(batch.selected[b]) == single.selected
            assert list(batch.n_scored[b]) == single.n_scored

    @pytest.mark.parametrize("use_pallas", ["auto", "never"])
    def test_waved_redo_only_where_a_lane_overflowed(self, workload,
                                                     ivf_indices, use_pallas):
        """The waved core runs the exhaustive redo under one wave-level
        `cond` on any(overflow). A tail_cap of 12 gives iterations where
        some lanes overflow and others do not, and iterations where none
        does: every lane must still equal its standalone fused run bit
        for bit, in both scan bodies ("auto": the carried-density step,
        "never": the classic body), and the redo counter must count the
        iterations with any overflow."""
        ivf_xla, _ = ivf_indices
        Q, h, n = workload
        m, B, T = Q.shape[0], 4, 12
        cfg = MWEMConfig(T=T, mode="fast", n_records=n, tail_cap=12,
                         use_pallas=use_pallas)
        keys = jnp.stack([jax.random.PRNGKey(s) for s in range(B)])
        series = ("mechanism_wave_redo_total"
                  "{driver=waved,mode=fast,workload=mwem}")
        before = default_registry().snapshot()["counters"].get(series, 0.0)
        batch = run_mwem_batch(Q, h, cfg, keys, index=ivf_xla)
        after = default_registry().snapshot()["counters"].get(series, 0.0)
        for b in range(B):
            single = run_mwem_fused(Q, h, cfg, jax.random.PRNGKey(b),
                                    index=ivf_xla)
            assert list(batch.selected[b]) == single.selected
            assert list(batch.n_scored[b]) == single.n_scored
            assert int(batch.overflow_counts[b]) == single.overflow_count
        # an overflowed step scores all m rows, a lazy one fewer
        over = np.asarray(batch.n_scored) == m                  # (B, T)
        np.testing.assert_array_equal(over.sum(axis=1),
                                      batch.overflow_counts)
        redone = over.any(axis=0)
        assert (redone & ~over.all(axis=0)).any()    # mixed iterations
        assert (~redone).any()                       # iterations with none
        tel = batch.telemetry
        assert tel.driver == "waved"
        assert tel.wave_redo_count == int(redone.sum())
        assert tel.wave_redo_rate == pytest.approx(redone.sum() / T)
        assert obs_trace.enabled()
        assert after - before == redone.sum()

    def test_waved_batch_kernel_route(self, workload, ivf_indices):
        """The Pallas batch kernel route agrees with the XLA waved route
        (away from exact ties both orderings retrieve the same set)."""
        ivf_xla, ivf_ker = ivf_indices
        Q, h, n = workload
        cfg = MWEMConfig(T=5, mode="fast", n_records=n)
        keys = jnp.stack([jax.random.PRNGKey(s) for s in range(2)])
        bx = run_mwem_batch(Q, h, cfg, keys, index=ivf_xla)
        bk = run_mwem_batch(Q, h, cfg, keys, index=ivf_ker)
        assert np.array_equal(bx.selected, bk.selected)
        np.testing.assert_allclose(np.asarray(bx.final_errors),
                                   np.asarray(bk.final_errors), atol=1e-5)

    def test_waved_eval_every_matches_single(self, workload, ivf_indices):
        ivf_xla, _ = ivf_indices
        Q, h, n = workload
        cfg = MWEMConfig(T=6, mode="fast", n_records=n, eval_every=3)
        keys = jnp.stack([jax.random.PRNGKey(s) for s in range(2)])
        batch = run_mwem_batch(Q, h, cfg, keys, index=ivf_xla)
        single = run_mwem_fused(Q, h, cfg, jax.random.PRNGKey(1),
                                index=ivf_xla)
        lane = batch.unbatch()[1].errors
        assert [t for t, _ in lane] == [t for t, _ in single.errors]
        np.testing.assert_allclose([e for _, e in lane],
                                   [e for _, e in single.errors], atol=1e-5)


class TestBatch:
    def test_shapes_and_determinism(self, workload, index):
        Q, h, n = workload
        U, m = h.shape[0], Q.shape[0]
        B, T = 5, 8
        cfg = MWEMConfig(T=T, mode="fast", n_records=n)
        keys = jnp.stack([jax.random.PRNGKey(s) for s in range(B)])
        r1 = run_mwem_batch(Q, h, cfg, keys, index=index)
        r2 = run_mwem_batch(Q, h, cfg, keys, index=index)
        assert r1.p_hat.shape == (B, U)
        assert r1.selected.shape == (B, T)
        assert r1.n_scored.shape == (B, T)
        assert r1.final_errors.shape == (B,)
        assert np.array_equal(r1.selected, r2.selected)
        assert np.allclose(np.asarray(r1.p_hat), np.asarray(r2.p_hat))

    def test_batch_lane_matches_single_run(self, workload, index):
        Q, h, n = workload
        cfg = MWEMConfig(T=8, mode="fast", n_records=n)
        keys = jnp.stack([jax.random.PRNGKey(s) for s in range(3)])
        batch = run_mwem_batch(Q, h, cfg, keys, index=index)
        single = run_mwem_fused(Q, h, cfg, jax.random.PRNGKey(1), index=index)
        assert list(batch.selected[1]) == single.selected
        assert abs(float(batch.final_errors[1]) - single.final_error) < 1e-4

    def test_batched_histograms(self, workload, index):
        Q, h, n = workload
        B = 3
        cfg = MWEMConfig(T=6, mode="fast", n_records=n)
        keys = jnp.stack([jax.random.PRNGKey(s) for s in range(B)])
        hb = jnp.stack([h] * B)
        shared = run_mwem_batch(Q, h, cfg, keys, index=index)
        per = run_mwem_batch(Q, hb, cfg, keys, index=index)
        assert np.array_equal(shared.selected, per.selected)
        assert np.allclose(shared.final_errors, per.final_errors, atol=1e-5)

    def test_host_driver_rejected(self, workload, index):
        Q, h, n = workload
        cfg = MWEMConfig(T=4, mode="fast", n_records=n, driver="host")
        keys = jnp.stack([jax.random.PRNGKey(0), jax.random.PRNGKey(1)])
        with pytest.raises(ValueError, match="fused driver"):
            run_mwem_batch(Q, h, cfg, keys, index=index)

    def test_eval_every_trace_matches_single(self, workload, index):
        Q, h, n = workload
        cfg = MWEMConfig(T=10, mode="fast", eval_every=5, n_records=n)
        keys = jnp.stack([jax.random.PRNGKey(s) for s in range(2)])
        batch = run_mwem_batch(Q, h, cfg, keys, index=index)
        single = run_mwem_fused(Q, h, cfg, jax.random.PRNGKey(1), index=index)
        assert batch.errors.shape == (2, 2)
        lane = batch.unbatch()[1].errors
        assert [t for t, _ in lane] == [t for t, _ in single.errors]
        np.testing.assert_allclose([e for _, e in lane],
                                   [e for _, e in single.errors], atol=1e-5)

    def test_unbatch(self, workload, index):
        Q, h, n = workload
        cfg = MWEMConfig(T=6, mode="fast", n_records=n)
        keys = jnp.stack([jax.random.PRNGKey(s) for s in range(2)])
        batch = run_mwem_batch(Q, h, cfg, keys, index=index)
        results = batch.unbatch()
        assert len(results) == 2
        for b, res in enumerate(results):
            assert res.selected == list(batch.selected[b])
            assert res.p_hat.shape == h.shape
            assert np.isfinite(res.final_error)

    def test_unbatch_full_trace_fields(self, workload, index):
        """unbatch() must reproduce every per-lane trace field of a
        standalone fused run — n_scored, overflow_count, honest timing
        via the telemetry record, and the shared-ledger default."""
        Q, h, n = workload
        B, T = 3, 8
        cfg = MWEMConfig(T=T, mode="fast", n_records=n)
        keys = jnp.stack([jax.random.PRNGKey(s) for s in range(B)])
        batch = run_mwem_batch(Q, h, cfg, keys, index=index)
        results = batch.unbatch()
        single = run_mwem_fused(Q, h, cfg, jax.random.PRNGKey(2), index=index)
        assert results[2].selected == single.selected
        assert results[2].n_scored == single.n_scored
        assert results[2].overflow_count == single.overflow_count
        np.testing.assert_allclose(np.asarray(results[2].p_hat),
                                   np.asarray(single.p_hat), atol=1e-6)
        for b, res in enumerate(results):
            # a lane has no per-iteration wall clock of its own — unbatch
            # refuses to fabricate one (it used to hand out total/T per lane)
            assert res.iter_seconds == []
            assert res.telemetry is not None
            assert res.telemetry.amortized
            assert res.telemetry.total_seconds == pytest.approx(
                batch.total_seconds, rel=1e-9)
            assert res.telemetry.lanes == 1
            assert res.telemetry.T == T
            assert res.telemetry.overflow_count == res.overflow_count
            assert res.telemetry.n_scored_total == sum(res.n_scored)
            assert res.ledger is batch.ledger  # shared per-run ledger
        # the batch record itself covers all lanes
        assert batch.telemetry.lanes == B
        assert batch.telemetry.n_scored_total == int(
            np.asarray(batch.n_scored).sum())


class TestBatchLedgerContract:
    """DESIGN.md §2 'Batched replication': the result ledger is per *run*;
    releasing B replicas composes B× the budget — the caller's contract,
    asserted here, and discharged by the per-lane `ledgers` plumbing."""

    def test_per_run_ledger_equals_single_run(self, workload, index):
        Q, h, n = workload
        cfg = MWEMConfig(eps=1.0, delta=1e-3, T=10, mode="fast", n_records=n)
        keys = jnp.stack([jax.random.PRNGKey(s) for s in range(4)])
        batch = run_mwem_batch(Q, h, cfg, keys, index=index)
        single = run_mwem_fused(Q, h, cfg, jax.random.PRNGKey(0), index=index)
        # the batch ledger records ONE run's events, not 4×
        assert batch.ledger.composed() == single.ledger.composed()
        assert batch.ledger.basic() == single.ledger.basic()
        assert len(batch.ledger.events) == len(single.ledger.events)

    def test_b_replica_composition_is_b_times(self, workload, index):
        """Charging one consumer ledger for all B lanes composes exactly
        B× the per-run event multiset (B× under basic composition; the
        √B-ish advanced-composition total matches an explicit preview)."""
        Q, h, n = workload
        B = 3
        cfg = MWEMConfig(eps=1.0, delta=1e-3, T=10, mode="fast", n_records=n)
        keys = jnp.stack([jax.random.PRNGKey(s) for s in range(B)])
        consumer = PrivacyLedger()
        batch = run_mwem_batch(Q, h, cfg, keys, index=index,
                               ledgers=[consumer] * B)
        per_run = batch.ledger
        assert len(consumer.events) == B * len(per_run.events)
        eps_b, delta_b = consumer.basic()
        eps_1, delta_1 = per_run.basic()
        assert eps_b == pytest.approx(B * eps_1, rel=1e-12)
        assert delta_b == pytest.approx(B * delta_1, rel=1e-12)
        # advanced composition of the B× multiset, cross-checked via preview
        expected = PrivacyLedger().preview(
            list(per_run.events) * B,
            gamma=B * per_run.index_failure_mass,
            slack=B * per_run.approx_slack)
        assert consumer.composed() == expected

    def test_per_lane_ledgers_reach_unbatch(self, workload, index):
        Q, h, n = workload
        B = 3
        cfg = MWEMConfig(T=6, mode="fast", n_records=n)
        keys = jnp.stack([jax.random.PRNGKey(s) for s in range(B)])
        lanes = [PrivacyLedger(), None, PrivacyLedger()]
        batch = run_mwem_batch(Q, h, cfg, keys, index=index, ledgers=lanes)
        for lane in (lanes[0], lanes[2]):
            assert lane.composed() == batch.ledger.composed()
        results = batch.unbatch()
        assert results[0].ledger is lanes[0]
        assert results[1].ledger is None  # skipped lane carries no ledger
        assert results[2].ledger is lanes[2]

    def test_ledgers_length_mismatch_raises(self, workload, index):
        Q, h, n = workload
        cfg = MWEMConfig(T=4, mode="fast", n_records=n)
        keys = jnp.stack([jax.random.PRNGKey(s) for s in range(2)])
        with pytest.raises(ValueError, match="one entry per lane"):
            run_mwem_batch(Q, h, cfg, keys, index=index,
                           ledgers=[PrivacyLedger()])
