"""Per-kernel allclose validation against the pure-jnp oracles.

Every Pallas kernel runs in interpret mode (this container is CPU-only; TPU
is the compile target) and is swept over shapes/dtypes with hypothesis.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.ivf_probe.ops import ivf_probe_topk, ivf_probe_topk_batch
from repro.kernels.ivf_probe.ref import (ivf_probe_topk_batch_ref,
                                         ivf_probe_topk_ref)
from repro.kernels.mips_topk.ops import mips_topk
from repro.kernels.mips_topk.ref import mips_topk_ref
from repro.kernels.mwu_update.ops import mwu_update
from repro.kernels.mwu_update.ref import mwu_update_ref
from repro.kernels.mwem_step import ops as step_ops
from repro.kernels.mwem_step.ref import UPDATE_RULES, mwem_step_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref, ssd_chunked_jnp


class TestMipsTopk:
    @given(n=st.integers(8, 300), d=st.integers(4, 70),
           k=st.integers(1, 16), seed=st.integers(0, 10_000))
    @settings(max_examples=25, deadline=None)
    def test_matches_ref(self, n, d, k, seed):
        k = min(k, n)
        rng = np.random.default_rng(seed)
        V = rng.standard_normal((n, d)).astype(np.float32)
        q = rng.standard_normal((d,)).astype(np.float32)
        idx_k, s_k = mips_topk(jnp.asarray(V), jnp.asarray(q), k,
                               block_n=64, block_d=32)
        idx_r, s_r = mips_topk_ref(jnp.asarray(V), jnp.asarray(q), k)
        np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                                   rtol=1e-5, atol=1e-5)
        # indices may differ on exact ties; scores already checked — compare sets
        assert set(np.asarray(idx_k).tolist()) == set(np.asarray(idx_r).tolist())

    def test_bf16_inputs(self):
        rng = np.random.default_rng(0)
        V = jnp.asarray(rng.standard_normal((256, 128)), jnp.bfloat16)
        q = jnp.asarray(rng.standard_normal((128,)), jnp.bfloat16)
        idx_k, s_k = mips_topk(V, q, 8, block_n=128, block_d=64)
        idx_r, s_r = mips_topk_ref(V.astype(jnp.float32), q.astype(jnp.float32), 8)
        # bf16 rounding: require ≥75% top-8 recall and close scores
        inter = set(np.asarray(idx_k).tolist()) & set(np.asarray(idx_r).tolist())
        assert len(inter) >= 6


class TestMipsTopkAbs:
    @given(n=st.integers(8, 300), d=st.integers(4, 70),
           k=st.integers(1, 16), seed=st.integers(0, 10_000))
    @settings(max_examples=15, deadline=None)
    def test_absolute_mode_matches_jnp(self, n, d, k, seed):
        k = min(k, n)
        rng = np.random.default_rng(seed)
        V = rng.standard_normal((n, d)).astype(np.float32)
        q = rng.standard_normal((d,)).astype(np.float32)
        idx_k, s_k = mips_topk(jnp.asarray(V), jnp.asarray(q), k,
                               block_n=64, block_d=32, absolute=True)
        s_r, i_r = jax.lax.top_k(jnp.abs(jnp.asarray(V) @ jnp.asarray(q)), k)
        np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                                   rtol=1e-5, atol=1e-5)
        assert set(np.asarray(idx_k).tolist()) == set(np.asarray(i_r).tolist())


def _ivf_structure(n, dim, nlist, cap, seed, integer=False):
    """A small IVF layout: random rows dealt round-robin into padded cells,
    centroids = member means, plus the cell-grouped row copy the kernel
    streams from. ``integer`` data makes every dot exactly representable so
    scores collide — the tie-break parity regime."""
    rng = np.random.default_rng(seed)
    if integer:
        V = rng.integers(-4, 5, size=(n, dim)).astype(np.float32)
    else:
        V = rng.standard_normal((n, dim)).astype(np.float32)
    perm = rng.permutation(n)
    cells = np.full((nlist, cap), -1, np.int32)
    for j, idx in enumerate(perm):
        c, s = j % nlist, j // nlist
        if s < cap:
            cells[c, s] = idx
    cents = np.zeros((nlist, dim), np.float32)
    for c in range(nlist):
        members = cells[c][cells[c] >= 0]
        if len(members):
            cents[c] = V[members].mean(0)
    cell_rows = V[np.clip(cells, 0, None)] * (cells >= 0)[..., None]
    return tuple(map(jnp.asarray, (V, cents, cells, cell_rows)))


class TestIVFProbe:
    """Interpret-mode parity for the fused IVF probe vs the XLA reference —
    exact index/score agreement, ties broken identically (the stable
    incremental merge equals one stable top_k in the same candidate
    order)."""

    @given(n=st.integers(40, 400), d=st.integers(4, 48),
           k=st.integers(1, 16), nprobe=st.integers(1, 6),
           seed=st.integers(0, 10_000))
    @settings(max_examples=15, deadline=None)
    def test_matches_ref(self, n, d, k, nprobe, seed):
        nlist = max(4, int(np.sqrt(n)))
        cap = -(-n // nlist) + 2
        nprobe = min(nprobe, nlist)
        V, cents, cells, cell_rows = _ivf_structure(n, d, nlist, cap, seed)
        q = jnp.asarray(np.random.default_rng(seed + 1)
                        .standard_normal(d).astype(np.float32))
        for absolute in (False, True):
            i_k, s_k, n_k = ivf_probe_topk(cents, cell_rows, cells, q, k,
                                           nprobe, interpret=True,
                                           absolute=absolute)
            i_r, s_r, n_r = ivf_probe_topk_ref(cents, cells, V, q, k,
                                               nprobe, absolute=absolute)
            np.testing.assert_array_equal(np.asarray(i_k), np.asarray(i_r))
            np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                                       rtol=1e-6, atol=1e-6)
            assert int(n_k) == int(n_r)

    def test_tie_break_parity(self):
        """Integer-valued rows make duplicate scores the norm; the kernel
        must pick the *same* candidates in the same slots as the ref."""
        V, cents, cells, cell_rows = _ivf_structure(
            200, 16, 10, 24, seed=7, integer=True)
        q = jnp.asarray(np.random.default_rng(3)
                        .integers(-3, 4, size=16).astype(np.float32))
        i_k, s_k, _ = ivf_probe_topk(cents, cell_rows, cells, q, 12, 5,
                                     interpret=True)
        i_r, s_r, _ = ivf_probe_topk_ref(cents, cells, V, q, 12, 5)
        np.testing.assert_array_equal(np.asarray(i_k), np.asarray(i_r))
        np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_r))

    def test_overfill_pads_minus_one(self):
        """k beyond the probed cells' valid rows pads ids with −1/−inf."""
        V, cents, cells, cell_rows = _ivf_structure(30, 8, 6, 8, seed=2)
        q = jnp.asarray(np.ones(8, np.float32))
        k = 12  # > the ~10 valid rows in two probed cells (5 each + pads)
        i_k, s_k, _ = ivf_probe_topk(cents, cell_rows, cells, q, k, 2,
                                     interpret=True)
        i_r, s_r, _ = ivf_probe_topk_ref(cents, cells, V, q, k, 2)
        np.testing.assert_array_equal(np.asarray(i_k), np.asarray(i_r))
        assert (np.asarray(i_k) == -1).any()
        assert np.isneginf(np.asarray(s_k)[np.asarray(i_k) == -1]).all()

    @given(b=st.integers(1, 6), k=st.integers(1, 12),
           nprobe=st.integers(1, 5), seed=st.integers(0, 10_000))
    @settings(max_examples=15, deadline=None)
    def test_batch_matches_ref(self, b, k, nprobe, seed):
        n, d, nlist, cap = 240, 20, 12, 24
        V, cents, cells, cell_rows = _ivf_structure(n, d, nlist, cap, seed)
        Vb = jnp.asarray(np.random.default_rng(seed + 5)
                         .standard_normal((b, d)).astype(np.float32))
        for absolute in (False, True):
            i_k, s_k, n_k = ivf_probe_topk_batch(
                cents, cell_rows, cells, Vb, k, nprobe, interpret=True,
                absolute=absolute)
            i_r, s_r, n_r = ivf_probe_topk_batch_ref(
                cents, cells, V, Vb, k, nprobe, absolute=absolute)
            np.testing.assert_array_equal(np.asarray(i_k), np.asarray(i_r))
            np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_array_equal(np.asarray(n_k), np.asarray(n_r))

    def test_batch_lane_matches_single_probe(self):
        """Away from exact ties, each wave lane retrieves the same
        candidate set as its standalone probe (dedup/masking is invisible)."""
        n, d, nlist, cap, k, nprobe = 300, 24, 16, 24, 10, 4
        V, cents, cells, cell_rows = _ivf_structure(n, d, nlist, cap, 11)
        Vb = jnp.asarray(np.random.default_rng(6)
                         .standard_normal((5, d)).astype(np.float32))
        ib, sb, _ = ivf_probe_topk_batch(cents, cell_rows, cells, Vb, k,
                                         nprobe, interpret=True)
        for b in range(5):
            i1, s1, _ = ivf_probe_topk(cents, cell_rows, cells, Vb[b], k,
                                       nprobe, interpret=True)
            assert (set(np.asarray(ib[b]).tolist())
                    == set(np.asarray(i1).tolist()))
            np.testing.assert_allclose(np.sort(np.asarray(sb[b])),
                                       np.sort(np.asarray(s1)),
                                       rtol=1e-5, atol=1e-5)


class TestMWUUpdate:
    @given(u=st.integers(4, 5000), seed=st.integers(0, 10_000),
           coef=st.floats(-3, 3))
    @settings(max_examples=25, deadline=None)
    def test_matches_ref(self, u, seed, coef):
        rng = np.random.default_rng(seed)
        lw = rng.standard_normal(u).astype(np.float32) * 3
        c = rng.uniform(0, 1, u).astype(np.float32)
        lw_k, p_k = mwu_update(jnp.asarray(lw), jnp.asarray(c), coef, block_u=256)
        lw_r, p_r = mwu_update_ref(jnp.asarray(lw), jnp.asarray(c), coef)
        np.testing.assert_allclose(np.asarray(lw_k), np.asarray(lw_r),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(p_k), np.asarray(p_r),
                                   rtol=2e-5, atol=1e-7)
        assert np.isclose(np.asarray(p_k).sum(), 1.0, atol=1e-5)


def _step_state(u, seed):
    """A carried (log_w, p, p_sum) triple honoring the max-shift invariant
    (max(log_w) == 0, p == softmax(log_w)) plus a row table and histogram."""
    rng = np.random.default_rng(seed)
    lw = rng.standard_normal(u).astype(np.float32) * 2
    lw = jnp.asarray(lw)
    lw = lw - jnp.max(lw)
    p = jax.nn.softmax(lw)
    ps = jnp.asarray(rng.uniform(0, 3, u).astype(np.float32))
    rows = jnp.asarray(rng.integers(0, 2, size=(16, u)).astype(np.float32))
    h = jnp.asarray(rng.uniform(0, 1, u).astype(np.float32))
    h = h / jnp.sum(h)
    return lw, p, ps, rows, h


class TestMWEMStep:
    """The iteration megakernel must be *bitwise* against the jit'd oracle —
    the fused drivers interleave kernel and XLA steps (overflow fallback),
    so any drift would break the host-vs-fused trace conformance tier."""

    @pytest.mark.parametrize("rule", UPDATE_RULES)
    @pytest.mark.parametrize("u", [128, 256])
    def test_bitwise_vs_ref(self, rule, u):
        lw, p, ps, rows, h = _step_state(u, seed=u)
        noise = jnp.float32(0.37)
        sel = jnp.int32(5)
        ref = jax.jit(lambda *a: mwem_step_ref(*a, rule=rule, eta=0.5))
        out_k = step_ops.mwem_step(lw, p, ps, rows, sel, h, noise,
                                   rule=rule, eta=0.5)
        out_r = ref(lw, p, ps, rows[5], h, noise)
        for a, b in zip(out_k, out_r):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # invariant out: max-shifted log-weights, density sums to 1
        assert float(jnp.max(out_k[0])) == 0.0
        np.testing.assert_allclose(float(jnp.sum(out_k[1])), 1.0, atol=1e-5)

    @given(seed=st.integers(0, 10_000), sel=st.integers(0, 15),
           rule=st.sampled_from(UPDATE_RULES))
    @settings(max_examples=15, deadline=None)
    def test_bitwise_sweep(self, seed, sel, rule):
        lw, p, ps, rows, h = _step_state(128, seed)
        noise = jnp.float32(np.random.default_rng(seed).laplace() * 0.1)
        ref = jax.jit(lambda *a: mwem_step_ref(*a, rule=rule, eta=0.3))
        out_k = step_ops.mwem_step(lw, p, ps, rows, jnp.int32(sel), h, noise,
                                   rule=rule, eta=0.3)
        out_r = ref(lw, p, ps, rows[sel], h, noise)
        for a, b in zip(out_k, out_r):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("shared_h", [True, False])
    def test_batch_matches_single_lanes(self, shared_h):
        B, u = 5, 128
        states = [_step_state(u, seed=100 + b) for b in range(B)]
        lw = jnp.stack([s[0] for s in states])
        p = jnp.stack([s[1] for s in states])
        ps = jnp.stack([s[2] for s in states])
        rows = states[0][3]
        h = states[0][4] if shared_h else jnp.stack([s[4] for s in states])
        sel = jnp.arange(B, dtype=jnp.int32) % rows.shape[0]
        noise = jnp.linspace(-0.2, 0.2, B, dtype=jnp.float32)
        out_b = step_ops.mwem_step_batch(lw, p, ps, rows, sel, h, noise,
                                         rule="hardt", eta=0.5)
        for b in range(B):
            hb = h if shared_h else h[b]
            out_1 = step_ops.mwem_step(lw[b], p[b], ps[b], rows, sel[b], hb,
                                       noise[b], rule="hardt", eta=0.5)
            # batch and single are *different jit programs*: on CPU the
            # interpret-mode emulation may fuse the dot reductions
            # differently, so lanes agree to 1 ulp here (on TPU the grid
            # programs share one kernel body and match bitwise)
            for a, s in zip(out_b, out_1):
                np.testing.assert_allclose(np.asarray(a[b]), np.asarray(s),
                                           rtol=3e-7, atol=3e-7)

    def test_unsupported_shape_falls_back(self):
        # U = 96 is not lane-aligned: the wrapper must silently take the ref
        lw, p, ps, rows, h = _step_state(96, seed=9)
        ref = jax.jit(lambda *a: mwem_step_ref(*a, rule="signed", eta=0.4))
        out_k = step_ops.mwem_step(lw, p, ps, rows, jnp.int32(3), h,
                                   jnp.float32(0.1), rule="signed", eta=0.4)
        out_r = ref(lw, p, ps, rows[3], h, jnp.float32(0.1))
        for a, b in zip(out_k, out_r):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_supported_gate(self):
        assert step_ops.mwem_step_supported(128)
        assert step_ops.mwem_step_supported(1024)
        assert not step_ops.mwem_step_supported(96)       # not lane-aligned
        assert not step_ops.mwem_step_supported(1 << 20)  # VMEM blowout

    def test_bad_rule_raises(self):
        lw, p, ps, rows, h = _step_state(128, seed=1)
        with pytest.raises(ValueError, match="rule"):
            step_ops.mwem_step(lw, p, ps, rows, jnp.int32(0), h,
                               jnp.float32(0.0), rule="nope", eta=0.5)

    @given(seed=st.integers(0, 10_000), c=st.integers(1, 40))
    @settings(max_examples=15, deadline=None)
    def test_aug_gather_score_bitwise(self, seed, c):
        """The dense lazy-EM tail scores its candidates with the XLA
        gather (`core.mwem._aug_score`), bitwise the per-row matvec."""
        from repro.core.mwem import _aug_score
        from repro.core.workload import as_workload

        rng = np.random.default_rng(seed)
        m, u = 64, 128
        Q = jnp.asarray(rng.integers(0, 2, size=(m, u)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal(u).astype(np.float32))
        idx = jnp.asarray(rng.integers(0, 2 * m, size=c).astype(np.int32))
        ref = jax.jit(lambda Q, v, i: (Q[i % m] @ v)
                      * jnp.where(i < m, 1.0, -1.0))
        got = jax.jit(lambda Q, v, i: _aug_score(as_workload(Q), v, i))(
            Q, v, idx)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(ref(Q, v, idx)))

    def test_mwu_apply_matches_step(self):
        """The sharded tail's materialized-row variant is the same body."""
        lw, p, ps, rows, h = _step_state(128, seed=21)
        out_a = step_ops.mwu_apply(lw, p, ps, rows[7], h, jnp.float32(0.2),
                                   rule="hardt", eta=0.5)
        out_s = step_ops.mwem_step(lw, p, ps, rows, jnp.int32(7), h,
                                   jnp.float32(0.2), rule="hardt", eta=0.5)
        for a, b in zip(out_a, out_s):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestRoutes:
    """Each kernel call records the route it was traced on, so a run that
    must have used the chip can check that nothing fell back. Shapes here
    are used nowhere else in this file, so each call traces afresh."""

    def test_interpret_route_recorded_off_tpu(self):
        from repro.kernels import route
        route.reset()
        V = jnp.ones((37, 19), jnp.float32)
        mips_topk(V, jnp.ones((19,), jnp.float32), 3)
        want = (route.COMPILED if jax.default_backend() == "tpu"
                else route.INTERPRET)
        assert route.taken()["mips_topk"] == {want}

    def test_shape_gate_records_ref(self):
        from repro.kernels import route
        route.reset()
        lw, p, ps, rows, h = _step_state(224, seed=4)   # 224 % 128 != 0
        step_ops.mwem_step(lw, p, ps, rows, jnp.int32(1), h,
                           jnp.float32(0.0), rule="hardt", eta=0.5)
        assert route.taken() == {"mwem_step": {route.REF}}


class TestFlashAttention:
    @pytest.mark.parametrize("mode,window", [
        ("full", 0), ("causal", 0), ("window", 16), ("chunk", 32)])
    def test_modes_match_ref(self, mode, window):
        rng = np.random.default_rng(0)
        B, Hq, Hkv, S, D = 2, 4, 2, 80, 16
        q = jnp.asarray(rng.standard_normal((B, Hq, S, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
        out_k = flash_attention(q, k, v, mode=mode, window=window,
                                block_q=32, block_kv=32)
        out_r = attention_ref(q, k, v, mode=mode, window=window)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=2e-4, atol=2e-4)

    @given(b=st.integers(1, 3), g=st.integers(1, 4), hkv=st.integers(1, 3),
           sq=st.integers(1, 40), skv=st.integers(8, 80), d=st.integers(4, 24),
           seed=st.integers(0, 1000))
    @settings(max_examples=20, deadline=None)
    def test_decode_offset_sweep(self, b, g, hkv, sq, skv, d, seed):
        """decode / prefill-continuation: q rows sit at offset ≥ 0 in the cache."""
        rng = np.random.default_rng(seed)
        sq = min(sq, skv)
        q_offset = skv - sq
        q = jnp.asarray(rng.standard_normal((b, hkv * g, sq, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, hkv, skv, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, hkv, skv, d)), jnp.float32)
        out_k = flash_attention(q, k, v, mode="causal", q_offset=q_offset,
                                block_q=16, block_kv=32)
        out_r = attention_ref(q, k, v, mode="causal", q_offset=q_offset)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=2e-4, atol=2e-4)

    def test_softcap(self):
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.standard_normal((1, 2, 32, 8)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 2, 32, 8)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 2, 32, 8)), jnp.float32)
        out_k = flash_attention(q, k, v, mode="causal", logit_softcap=20.0,
                                block_q=16, block_kv=16)
        out_r = attention_ref(q, k, v, mode="causal", logit_softcap=20.0)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=2e-4, atol=2e-4)

    def test_bf16(self):
        rng = np.random.default_rng(4)
        q = jnp.asarray(rng.standard_normal((1, 2, 64, 16)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((1, 1, 64, 16)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((1, 1, 64, 16)), jnp.bfloat16)
        out_k = flash_attention(q, k, v, mode="causal", block_q=32, block_kv=32)
        out_r = attention_ref(q, k, v, mode="causal")
        np.testing.assert_allclose(
            np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
            rtol=2e-2, atol=2e-2)


class TestSSDScan:
    @given(b=st.integers(1, 2), s=st.integers(3, 70), h=st.integers(1, 3),
           p=st.integers(2, 12), n=st.integers(2, 12), seed=st.integers(0, 1000))
    @settings(max_examples=20, deadline=None)
    def test_kernel_matches_sequential(self, b, s, h, p, n, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.01, 0.5, (b, s, h)), jnp.float32)
        A = jnp.asarray(-rng.uniform(0.1, 2.0, (h,)), jnp.float32)
        Bm = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
        Cm = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
        y_k = ssd_scan(x, dt, A, Bm, Cm, chunk=16)
        y_r, _ = ssd_scan_ref(x, dt, A, Bm, Cm)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                                   rtol=2e-4, atol=2e-4)

    @given(s=st.integers(5, 90), chunk=st.sampled_from([8, 16, 32]),
           seed=st.integers(0, 1000))
    @settings(max_examples=15, deadline=None)
    def test_chunked_jnp_matches_sequential(self, s, chunk, seed):
        rng = np.random.default_rng(seed)
        b, h, p, n = 2, 2, 8, 4
        x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.01, 0.5, (b, s, h)), jnp.float32)
        A = jnp.asarray(-rng.uniform(0.1, 2.0, (h,)), jnp.float32)
        Bm = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
        Cm = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
        y_c, hT_c = ssd_chunked_jnp(x, dt, A, Bm, Cm, chunk=chunk)
        y_r, hT_r = ssd_scan_ref(x, dt, A, Bm, Cm)
        np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_r),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(hT_c), np.asarray(hT_r),
                                   rtol=2e-4, atol=2e-4)

    def test_state_continuation(self):
        """Chunked scan with h0 continues exactly (the decode path)."""
        rng = np.random.default_rng(9)
        b, s, h, p, n = 1, 48, 2, 4, 4
        x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.01, 0.5, (b, s, h)), jnp.float32)
        A = jnp.asarray(-rng.uniform(0.1, 2.0, (h,)), jnp.float32)
        Bm = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
        Cm = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
        y_full, hT = ssd_chunked_jnp(x, dt, A, Bm, Cm, chunk=16)
        y1, h1 = ssd_chunked_jnp(x[:, :32], dt[:, :32], A, Bm[:, :32], Cm[:, :32], chunk=16)
        y2, h2 = ssd_chunked_jnp(x[:, 32:], dt[:, 32:], A, Bm[:, 32:], Cm[:, 32:],
                                 chunk=16, h0=h1)
        np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                                   np.asarray(y_full), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(h2), np.asarray(hT), rtol=2e-4,
                                   atol=2e-4)
