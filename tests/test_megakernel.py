"""Host-vs-megakernel conformance (DESIGN.md §7, ISSUE 6 acceptance).

The iteration megakernel must be *invisible*: for every driver × index ×
mode cell, the selection trace (the privacy-relevant artifact) of the
fused scan running the mega step — kernel or XLA ref, whatever
``use_pallas`` resolves to — must be bitwise the host loop's, and the
classic pre-fusion body (``use_pallas="never"``) must agree too. U = 128
here so the shape qualifies for the real kernel gate
(`mwem_step_supported`); the driver tier at U = 64 covers the mega-ref
fallback route.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MWEMConfig, run_mwem, run_mwem_batch, run_mwem_fused
from repro.core.mwem import _run_mwem_host
from repro.core.queries import gaussian_histogram, random_binary_queries
from repro.mips import FlatAbsIndex, IVFIndex, NSWIndex, augment_complement

U, M, N, T = 128, 128, 300, 10


@pytest.fixture(scope="module")
def workload():
    kh, kq = jax.random.split(jax.random.PRNGKey(0))
    h = gaussian_histogram(kh, N, U)
    Q = random_binary_queries(kq, M, U)
    return Q, h


def _indexes(Q):
    aug = augment_complement(np.asarray(Q))
    return {
        "flat": FlatAbsIndex(Q),
        "ivf": IVFIndex(aug, seed=0, train_iters=4),
        "nsw": NSWIndex(aug, deg=8, ef=24, rounds=3, seed=0),
    }


def _cfg(**kw):
    kw.setdefault("T", T)
    kw.setdefault("n_records", N)
    return MWEMConfig(**kw)


def _traces(res):
    return res.selected, res.n_scored, res.overflow_count


class TestHostMegaConformance:
    @pytest.mark.parametrize("use_pallas", ["auto", "always"])
    @pytest.mark.parametrize("kind", ["flat", "ivf", "nsw"])
    def test_fast_mode(self, workload, kind, use_pallas):
        Q, h = workload
        ix = _indexes(Q)[kind]
        key = jax.random.PRNGKey(7)
        host = _run_mwem_host(Q, h, _cfg(), key, index=ix)
        mega = run_mwem_fused(Q, h, _cfg(use_pallas=use_pallas), key, index=ix)
        assert _traces(mega) == _traces(host)
        np.testing.assert_allclose(np.asarray(mega.p_hat),
                                   np.asarray(host.p_hat),
                                   rtol=1e-6, atol=1e-7)

    @pytest.mark.parametrize("use_pallas", ["auto", "always"])
    def test_exact_mode(self, workload, use_pallas):
        Q, h = workload
        key = jax.random.PRNGKey(3)
        host = _run_mwem_host(Q, h, _cfg(mode="exact"), key)
        mega = run_mwem_fused(Q, h, _cfg(mode="exact", use_pallas=use_pallas),
                              key)
        assert _traces(mega) == _traces(host)
        np.testing.assert_allclose(np.asarray(mega.p_hat),
                                   np.asarray(host.p_hat),
                                   rtol=1e-6, atol=1e-7)

    @pytest.mark.parametrize("kind", ["flat", "ivf", "nsw"])
    def test_classic_body_unchanged(self, workload, kind):
        """``use_pallas="never"`` (the pre-fusion baseline) and the mega
        route tell the same story — fusing moved bytes, not math."""
        Q, h = workload
        ix = _indexes(Q)[kind]
        key = jax.random.PRNGKey(11)
        classic = run_mwem_fused(Q, h, _cfg(use_pallas="never"), key, index=ix)
        mega = run_mwem_fused(Q, h, _cfg(use_pallas="auto"), key, index=ix)
        assert _traces(mega) == _traces(classic)
        np.testing.assert_allclose(np.asarray(mega.p_hat),
                                   np.asarray(classic.p_hat),
                                   rtol=1e-6, atol=1e-7)

    @pytest.mark.parametrize("kind", ["flat", "ivf", "nsw"])
    def test_forced_overflow_parity(self, workload, kind):
        """tail_cap=1 overflows nearly every iteration: the `lax.cond`
        fallback (which lives *outside* the kernel precisely for this)
        must redo selection with the same folded key as the host."""
        Q, h = workload
        ix = _indexes(Q)[kind]
        key = jax.random.PRNGKey(5)
        host = _run_mwem_host(Q, h, _cfg(tail_cap=1), key, index=ix)
        mega = run_mwem_fused(Q, h, _cfg(tail_cap=1, use_pallas="always"),
                              key, index=ix)
        assert host.overflow_count > 0  # the regime actually triggered
        assert _traces(mega) == _traces(host)

    def test_run_mwem_autoroutes_mega(self, workload):
        """The public entry point reaches the mega scan by default."""
        Q, h = workload
        ix = _indexes(Q)["ivf"]
        key = jax.random.PRNGKey(2)
        res = run_mwem(Q, h, _cfg(driver="fused"), key, index=ix)
        host = _run_mwem_host(Q, h, _cfg(), key, index=ix)
        assert _traces(res) == _traces(host)


class TestWavedConformance:
    def test_batch_lanes_match_single_runs(self, workload):
        Q, h = workload
        ix = _indexes(Q)["ivf"]
        keys = jax.random.split(jax.random.PRNGKey(9), 4)
        batch = run_mwem_batch(Q, h, _cfg(use_pallas="always"), keys, index=ix)
        for b, key in enumerate(keys):
            single = run_mwem_fused(Q, h, _cfg(use_pallas="always"), key,
                                    index=ix)
            assert [int(s) for s in batch.selected[b]] == single.selected
            # waved lanes run a different jit program than the single
            # scan — densities agree to float noise, traces exactly
            np.testing.assert_allclose(np.asarray(batch.p_hat[b]),
                                       np.asarray(single.p_hat),
                                       rtol=1e-5, atol=1e-6)

    def test_batch_never_vs_always(self, workload):
        Q, h = workload
        ix = _indexes(Q)["ivf"]
        keys = jax.random.split(jax.random.PRNGKey(4), 3)
        classic = run_mwem_batch(Q, h, _cfg(use_pallas="never"), keys,
                                 index=ix)
        mega = run_mwem_batch(Q, h, _cfg(use_pallas="always"), keys, index=ix)
        np.testing.assert_array_equal(np.asarray(mega.selected),
                                      np.asarray(classic.selected))


class TestShardedSeam:
    def test_sharded_mwu_seam_parity(self, workload):
        """1-device mesh: ``use_pallas="always"`` routes the sharded MWU
        tail + lazy tail scoring through the kernels; traces must match
        the XLA tail."""
        from repro.core.distributed import run_mwem_sharded
        from repro.mips.ivf import ShardedIVFIndex

        Q, h = workload
        key = jax.random.PRNGKey(8)
        out = {}
        for up in ("never", "always"):
            ix = ShardedIVFIndex(augment_complement(np.asarray(Q)),
                                 n_shards=1, seed=0, use_pallas=up)
            out[up] = run_mwem_sharded(Q, h, _cfg(), key, index=ix)
        assert _traces(out["always"]) == _traces(out["never"])
        np.testing.assert_allclose(np.asarray(out["always"].p_hat),
                                   np.asarray(out["never"].p_hat),
                                   rtol=1e-5, atol=1e-6)


class TestNoPerCallRecompilation:
    """The megakernel wrappers are module-level jits — repeat dispatches
    with fresh same-shaped arrays must hit the cache (the drivers call
    them every scan trace)."""

    def _burn(self, seed):
        from repro.kernels.mwem_step import ops as step_ops

        rng = np.random.default_rng(seed)
        lw = jnp.asarray(rng.standard_normal(U).astype(np.float32))
        lw = lw - jnp.max(lw)
        p = jax.nn.softmax(lw)
        ps = jnp.zeros((U,), jnp.float32)
        rows = jnp.asarray(rng.integers(0, 2, (M, U)).astype(np.float32))
        h = jnp.asarray(rng.uniform(0, 1, U).astype(np.float32))
        step_ops.mwem_step(lw, p, ps, rows, jnp.int32(1), h,
                           jnp.float32(0.1), rule="hardt", eta=0.5)
        step_ops.mwem_step_batch(lw[None], p[None], ps[None], rows,
                                 jnp.zeros((1,), jnp.int32), h,
                                 jnp.zeros((1,), jnp.float32),
                                 rule="hardt", eta=0.5)
        step_ops.mwu_apply(lw, p, ps, rows[0], h, jnp.float32(0.1),
                           rule="hardt", eta=0.5)

    def test_step_ops_share_compiled_programs(self):
        from repro.kernels.mwem_step import ops as step_ops

        fns = (step_ops.mwem_step, step_ops.mwem_step_batch,
               step_ops.mwu_apply)
        self._burn(0)
        sizes = [f._cache_size() for f in fns]
        self._burn(1)
        assert [f._cache_size() for f in fns] == sizes


class TestRoofline:
    def test_megakernel_halves_hbm_bytes(self):
        """ISSUE 6 acceptance: ≥2× modeled per-iteration HBM reduction."""
        from repro.analysis.roofline import mwem_step_roofline

        for m in (4096, 8192, 32768):
            mega = mwem_step_roofline(m=m, U=256, megakernel=True)
            classic = mwem_step_roofline(m=m, U=256, megakernel=False)
            assert mega["hbm_bytes"] * 2 <= classic["hbm_bytes"], m
