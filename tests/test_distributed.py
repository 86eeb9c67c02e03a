"""Multi-device behaviour (run in subprocesses with forced host devices):
int8 error-feedback all-reduce, the sharded Fast-MWEM driver (host-parity
selections, overflow fallback, ledger totals, service waves on a mesh),
dry-run machinery on a small mesh.

All inline scripts build meshes through `repro.launch.mesh.make_mesh`
(Auto axis types) and run on forced CPU host devices: each child is given
``JAX_PLATFORMS=cpu`` explicitly, so none of them reaches for an
accelerator the parent may hold."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO_SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


class TestMeshCompat:
    def test_make_mesh_compat_no_axis_type_attribute_error(self):
        """`make_mesh` builds Auto-typed meshes with the installed JAX's
        `jax.sharding.AxisType` (the seed crashed on its absence)."""
        out = _run("""
            from repro.launch.mesh import make_mesh, make_driver_mesh
            mesh = make_mesh((4, 2), ("data", "model"))
            assert mesh.shape == {"data": 4, "model": 2}
            mesh2 = make_driver_mesh(8, model_degree=2)
            assert mesh2.shape == {"data": 4, "model": 2}
            print("OK")
        """)
        assert "OK" in out


class TestCompression:
    def test_ring_allreduce_int8_matches_mean(self):
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from jax import shard_map
            from jax.sharding import PartitionSpec as P
            from repro.train.compression import ring_allreduce_int8
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((8,), ("pod",))
            n = 4096
            xs = jax.random.normal(jax.random.PRNGKey(0), (8, n))
            f = shard_map(lambda x: ring_allreduce_int8(x[0], "pod")[None],
                          mesh=mesh, in_specs=P("pod", None),
                          out_specs=P("pod", None), check_vma=False)
            got = np.asarray(f(xs))
            want = np.asarray(xs.mean(0))
            for i in range(8):
                err = np.abs(got[i] - want)
                rel = err.max() / (np.abs(want).max() + 1e-9)
                assert rel < 0.02, rel   # int8 quantization noise only
            print("OK")
        """)
        assert "OK" in out

    def test_error_feedback_reduces_bias(self):
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from jax import shard_map
            from jax.sharding import PartitionSpec as P
            from repro.train.compression import ef_allreduce_grads
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4,), ("pod",))
            grads = {"w": jax.random.normal(jax.random.PRNGKey(1), (4, 1000))}
            def step(g, err):
                out, st = ef_allreduce_grads({"w": g["w"][0]},
                                             {"ef_error": err[0]}, "pod")
                return out["w"][None], st["ef_error"][None]
            f = shard_map(step, mesh=mesh,
                          in_specs=(P("pod", None), P("pod", None)),
                          out_specs=(P("pod", None), P("pod", None)),
                          check_vma=False)
            err = jnp.zeros((4, 1000))
            acc_true = np.zeros(1000)
            acc_comp = np.zeros(1000)
            for t in range(20):
                g = {"w": jax.random.normal(jax.random.PRNGKey(t), (4, 1000))}
                out, err = f(g, err)
                acc_true += np.asarray(g["w"]).mean(0)
                acc_comp += np.asarray(out)[0]
            # error feedback keeps the *accumulated* signal nearly unbiased
            denom = np.abs(acc_true).mean() + 1e-9
            assert np.abs(acc_comp - acc_true).mean() / denom < 0.05
            print("OK")
        """)
        assert "OK" in out


class TestDistributedMWEM:
    def test_lazy_iteration_runs_and_selects(self):
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np, math
            from repro.core.distributed import make_mwem_iteration
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4, 2), ("data", "model"))
            m, U = 1024, 64
            n_data, m_loc = 4, 256
            fn = make_mwem_iteration(mesh, m=m, U=U, nlist=32, cap=16,
                                     nprobe=4, k_loc=16, tail_cap=64,
                                     scale=20.0, eta=0.05, mode="lazy",
                                     multi_pod=False)
            rng = np.random.default_rng(0)
            Q = jnp.asarray(rng.uniform(0, 1, (m, U)), jnp.float32)
            # per-shard IVF stand-in: random centroids + cells
            cents = jnp.asarray(rng.standard_normal((n_data, 32, U)), jnp.float32)
            cells = jnp.asarray(rng.integers(0, m_loc, (n_data, 32, 16)), jnp.int32)
            logw = jnp.zeros((U,))
            h = jnp.asarray(rng.dirichlet(np.ones(U)), jnp.float32)
            key = jax.random.PRNGKey(0)
            with mesh:
                logw2, stats = jax.jit(fn)(Q, cents, cells, logw, h,
                                           jax.random.key_data(key))
            assert logw2.shape == (U,)
            assert 0 <= int(stats["winner"]) < m
            assert not bool(stats["overflow"])
            # scored work excludes nothing here (all cell slots valid) but
            # must stay well below m
            assert float(stats["n_scored"]) < m
            assert np.isfinite(np.asarray(logw2)).all()
            print("OK", int(stats["winner"]), float(stats["n_scored"]))
        """)
        assert "OK" in out

    def test_invalid_cell_slots_not_counted_as_scored(self):
        """Padded (-1) cell slots cost no FLOPs and must not inflate
        n_scored (the overcount bug)."""
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.core.distributed import make_mwem_iteration
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 2), ("data", "model"))
            m, U, nlist, cap, nprobe = 256, 32, 16, 16, 4
            n_data, m_loc = 2, 128
            # scale ≫ Gumbel spread → the tail margin B is huge, C = 0, and
            # the scored-row count is deterministic
            fn = make_mwem_iteration(mesh, m=m, U=U, nlist=nlist, cap=cap,
                                     nprobe=nprobe, k_loc=8, tail_cap=32,
                                     scale=1000.0, eta=0.05, mode="lazy",
                                     multi_pod=False)
            rng = np.random.default_rng(0)
            Q = jnp.asarray(rng.uniform(0, 1, (m, U)), jnp.float32)
            cents = jnp.asarray(rng.standard_normal((n_data, nlist, U)),
                                jnp.float32)
            # half of every cell is padding (-1)
            cells = np.full((n_data, nlist, cap), -1, np.int32)
            cells[:, :, :cap // 2] = rng.integers(
                0, m_loc, (n_data, nlist, cap // 2))
            cells = jnp.asarray(cells)
            logw = jnp.zeros((U,))
            h = jnp.asarray(rng.dirichlet(np.ones(U)), jnp.float32)
            with mesh:
                _, stats = jax.jit(fn)(Q, cents, cells, logw, h,
                    jax.random.key_data(jax.random.PRNGKey(0)))
            # per shard: nlist centroids + exactly the valid half of the
            # probed slots, no tail; the old code charged the full
            # nprobe·cap regardless of padding
            expected = n_data * (nlist + nprobe * (cap // 2))
            assert float(stats["n_scored"]) == expected, \\
                (float(stats["n_scored"]), expected)
            print("OK", float(stats["n_scored"]))
        """, devices=4)
        assert "OK" in out

    def test_exhaustive_vs_lazy_collective_volume(self):
        """The lazy iteration must move far fewer collective bytes."""
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.core.distributed import make_mwem_iteration
            from repro.analysis.hlo import analyze_hlo
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4, 2), ("data", "model"))
            # sublinearity needs m_loc ≫ √m_loc·probe width — use a scale
            # where the exhaustive psum of m_loc scores dominates
            m, U = 262144, 64
            vols = {}
            for mode in ("exhaustive", "lazy"):
                # fallback=False: measure the hot path — the static
                # analyzer would otherwise weigh the e^{-Ω(√m)}-rare
                # overflow branch (a full Θ(m) psum) at 1×
                fn = make_mwem_iteration(mesh, m=m, U=U, nlist=512, cap=256,
                                         nprobe=4, k_loc=256, tail_cap=1024,
                                         scale=20.0, eta=0.05, mode=mode,
                                         multi_pod=False, fallback=False)
                Q = jax.ShapeDtypeStruct((m, U), jnp.float32)
                cents = jax.ShapeDtypeStruct((4, 512, U), jnp.float32)
                cells = jax.ShapeDtypeStruct((4, 512, 256), jnp.int32)
                w = jax.ShapeDtypeStruct((U,), jnp.float32)
                key = jax.ShapeDtypeStruct((2,), jnp.uint32)
                with mesh:
                    c = jax.jit(fn).lower(Q, cents, cells, w, w, key).compile()
                vols[mode] = analyze_hlo(c.as_text()).collective_bytes
            assert vols["lazy"] < vols["exhaustive"], vols
            print("OK", vols)
        """)
        assert "OK" in out


class TestShardedDriver:
    def test_exact_mode_matches_host_selections_and_ledger(self):
        """Acceptance: on a forced 8-device mesh the sharded driver makes
        the same selections and charges the same ledger totals as the host
        driver on identical inputs."""
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.core import MWEMConfig, run_mwem, run_mwem_sharded
            from repro.core.queries import (gaussian_histogram,
                                            random_binary_queries)
            from repro.launch.mesh import make_mesh
            kh, kq = jax.random.split(jax.random.PRNGKey(0))
            U, m, n = 64, 512, 300
            h = gaussian_histogram(kh, n, U)
            Q = random_binary_queries(kq, m, U)
            mesh = make_mesh((4, 2), ("data", "model"))
            cfg = MWEMConfig(T=8, mode="exact", n_records=n)
            cfg_host = MWEMConfig(T=8, mode="exact", n_records=n,
                                  driver="host")
            rs = run_mwem_sharded(Q, h, cfg, jax.random.PRNGKey(3), mesh=mesh)
            rh = run_mwem(Q, h, cfg_host, jax.random.PRNGKey(3))
            assert rs.selected == rh.selected, (rs.selected, rh.selected)
            assert rs.n_scored == rh.n_scored
            assert rs.ledger.composed() == rh.ledger.composed()
            assert rs.ledger.basic() == rh.ledger.basic()
            assert len(rs.ledger.events) == len(rh.ledger.events)
            np.testing.assert_allclose(np.asarray(rs.p_hat),
                                       np.asarray(rh.p_hat), atol=1e-5)
            assert abs(rs.final_error - rh.final_error) < 1e-5
            print("OK")
        """)
        assert "OK" in out

    def test_lazy_mode_sublinear_scoring_and_ledger_parity(self):
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.core import (MWEMConfig, release_cost, run_mwem,
                                    run_mwem_sharded)
            from repro.core.accountant import PrivacyLedger
            from repro.core.queries import (gaussian_histogram,
                                            random_binary_queries)
            from repro.mips import (IVFIndex, ShardedIVFIndex,
                                    augment_complement)
            from repro.launch.mesh import make_mesh
            kh, kq = jax.random.split(jax.random.PRNGKey(0))
            U, m, n = 64, 512, 300
            h = gaussian_histogram(kh, n, U)
            Q = random_binary_queries(kq, m, U)
            mesh = make_mesh((4, 2), ("data", "model"))
            idx = ShardedIVFIndex(Q, n_shards=4, seed=0)
            cfg = MWEMConfig(T=10, mode="fast", n_records=n)
            rs = run_mwem_sharded(Q, h, cfg, jax.random.PRNGKey(5),
                                  mesh=mesh, index=idx)
            assert all(0 <= s < m for s in rs.selected)
            assert rs.overflow_count == 0
            # Θ(√m)-ish scoring: every iteration touches far fewer rows
            assert max(rs.n_scored) < m * 0.75, rs.n_scored
            # ledger totals: exactly the previewed release cost, and equal
            # to the host driver's totals with a same-γ index
            exp = PrivacyLedger().preview(*release_cost(cfg, m, U, index=idx))
            assert rs.ledger.composed() == exp
            host_idx = IVFIndex(augment_complement(np.asarray(Q)), seed=0,
                                failure_mass=idx.failure_mass)
            cfg_host = MWEMConfig(T=10, mode="fast", n_records=n,
                                  driver="host")
            rh = run_mwem(Q, h, cfg_host, jax.random.PRNGKey(5),
                          index=host_idx)
            assert rs.ledger.composed() == rh.ledger.composed()
            assert rs.ledger.basic() == rh.ledger.basic()
            # both drivers beat the uniform baseline on the same workload
            from repro.core.queries import max_error
            uniform = float(max_error(Q, h, jnp.full_like(h, 1 / U)))
            assert rs.final_error < uniform
            print("OK", rs.n_scored)
        """)
        assert "OK" in out

    def test_kernelized_probe_matches_xla_probe(self):
        """The fused `kernels.ivf_probe` per-shard probe (use_pallas,
        interpret mode off-TPU, valid only at model extent 1) must leave
        the sharded driver's selections and scored-rows traces unchanged
        vs the XLA gather probe."""
        out = _run("""
            import jax
            from repro.core import MWEMConfig, run_mwem_sharded
            from repro.core.queries import (gaussian_histogram,
                                            random_binary_queries)
            from repro.mips import ShardedIVFIndex
            from repro.launch.mesh import make_mesh
            kh, kq = jax.random.split(jax.random.PRNGKey(0))
            U, m, n = 32, 128, 300
            h = gaussian_histogram(kh, n, U)
            Q = random_binary_queries(kq, m, U)
            mesh = make_mesh((2, 1), ("data", "model"))
            cfg = MWEMConfig(T=5, mode="fast", n_records=n)
            ix_x = ShardedIVFIndex(Q, n_shards=2, seed=0, train_iters=3,
                                   use_pallas="never")
            ix_p = ShardedIVFIndex(Q, n_shards=2, seed=0, train_iters=3,
                                   use_pallas="always")
            rx = run_mwem_sharded(Q, h, cfg, jax.random.PRNGKey(3),
                                  mesh=mesh, index=ix_x)
            rp = run_mwem_sharded(Q, h, cfg, jax.random.PRNGKey(3),
                                  mesh=mesh, index=ix_p)
            assert rx.selected == rp.selected, (rx.selected, rp.selected)
            assert rx.n_scored == rp.n_scored
            assert abs(rx.final_error - rp.final_error) < 1e-6
            # model-sharded meshes silently fall back to the XLA probe
            mesh2 = make_mesh((2, 2), ("data", "model"))
            r2 = run_mwem_sharded(Q, h, cfg, jax.random.PRNGKey(3),
                                  mesh=mesh2, index=ShardedIVFIndex(
                                      Q, n_shards=2, seed=0, train_iters=3,
                                      use_pallas="always"))
            assert all(0 <= s < m for s in r2.selected)
            print("OK")
        """, devices=4)
        assert "OK" in out

    def test_overflow_falls_back_to_exhaustive_exactly(self):
        """tail_cap=1 forces every shard's binomial past the buffer; the
        iteration must lax.cond into the exhaustive per-shard scan — which
        redraws under `lazy_em.fallback_key(k_sel)` (the lazy pass already
        consumed k_sel's stream), the same fold the host driver applies.
        With every step overflowing, the index never decides anything, so
        the sharded run matches the host fast-mode driver selection-for-
        selection."""
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.core import MWEMConfig, run_mwem, run_mwem_sharded
            from repro.core.queries import (gaussian_histogram,
                                            random_binary_queries)
            from repro.mips import (IVFIndex, ShardedIVFIndex,
                                    augment_complement)
            from repro.launch.mesh import make_mesh
            kh, kq = jax.random.split(jax.random.PRNGKey(0))
            U, m, n = 64, 512, 300
            h = gaussian_histogram(kh, n, U)
            Q = random_binary_queries(kq, m, U)
            mesh = make_mesh((4, 2), ("data", "model"))
            idx = ShardedIVFIndex(Q, n_shards=4, seed=0)
            T = 6
            cfg = MWEMConfig(T=T, mode="fast", n_records=n, tail_cap=1)
            rs = run_mwem_sharded(Q, h, cfg, jax.random.PRNGKey(7),
                                  mesh=mesh, index=idx)
            assert rs.overflow_count == T
            assert rs.n_scored == [m] * T  # fallback scores every row
            hidx = IVFIndex(augment_complement(np.asarray(Q)), seed=0,
                            train_iters=4)
            cfg_h = MWEMConfig(T=T, mode="fast", n_records=n, tail_cap=1,
                               driver="host")
            rh = run_mwem(Q, h, cfg_h, jax.random.PRNGKey(7), index=hidx)
            assert rh.overflow_count == T  # same all-overflow regime
            assert rs.selected == rh.selected, (rs.selected, rh.selected)
            print("OK")
        """)
        assert "OK" in out

    def test_routing_and_batch(self):
        """driver="auto" picks the sharded driver on a multi-device mesh;
        `run_mwem_sharded_batch` lanes match standalone runs."""
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.core import (MWEMConfig, run_mwem, run_mwem_sharded,
                                    run_mwem_sharded_batch)
            from repro.core.accountant import PrivacyLedger
            from repro.core.mwem import _resolve_driver
            from repro.core.queries import (gaussian_histogram,
                                            random_binary_queries)
            from repro.mips import FlatAbsIndex, ShardedIVFIndex
            from repro.launch.mesh import make_mesh
            kh, kq = jax.random.split(jax.random.PRNGKey(0))
            U, m, n = 32, 256, 300
            h = gaussian_histogram(kh, n, U)
            Q = random_binary_queries(kq, m, U)
            mesh = make_mesh((4, 2), ("data", "model"))
            idx = ShardedIVFIndex(Q, n_shards=4, seed=0)
            # auto routing: >1 device + shardable workload → sharded
            assert _resolve_driver(MWEMConfig(mode="exact", n_records=n),
                                   None) == "sharded"
            assert _resolve_driver(MWEMConfig(n_records=n), idx) == "sharded"
            # a non-sharded index keeps the fused driver even multi-device
            flat = FlatAbsIndex(Q)
            assert _resolve_driver(MWEMConfig(n_records=n), flat) == "fused"
            cfg = MWEMConfig(T=4, mode="fast", n_records=n)
            r = run_mwem(Q, h, cfg, jax.random.PRNGKey(1), index=idx,
                         mesh=mesh)
            assert all(0 <= s < m for s in r.selected)
            # batch: lanes reproduce standalone runs and charge per-lane
            keys = jnp.stack([jax.random.PRNGKey(s) for s in range(3)])
            lanes = [PrivacyLedger(), None, PrivacyLedger()]
            batch = run_mwem_sharded_batch(Q, h, cfg, keys, mesh=mesh,
                                           index=idx, ledgers=lanes)
            solo = run_mwem_sharded(Q, h, cfg, jax.random.PRNGKey(1),
                                    mesh=mesh, index=idx)
            assert list(batch.selected[1]) == solo.selected
            np.testing.assert_allclose(np.asarray(batch.p_hat[1]),
                                       np.asarray(solo.p_hat), atol=1e-6)
            assert lanes[0].composed() == batch.ledger.composed()
            assert lanes[2].composed() == batch.ledger.composed()
            print("OK")
        """)
        assert "OK" in out

    def test_service_waves_dispatch_on_mesh(self):
        """ReleaseService with a mesh: wave lanes run the sharded driver,
        tenants are charged per lane, and releases match standalone sharded
        runs with the same seed."""
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.core import MWEMConfig, run_mwem_sharded
            from repro.core.accountant import PrivacyLedger
            from repro.core.mwem import release_cost
            from repro.serve import ReleaseService
            from repro.launch.mesh import make_mesh
            kh, kq = jax.random.split(jax.random.PRNGKey(0))
            U, m, n = 32, 256, 300
            from repro.core.queries import (gaussian_histogram,
                                            random_binary_queries)
            h = np.asarray(gaussian_histogram(kh, n, U))
            Q = random_binary_queries(kq, m, U)
            mesh = make_mesh((4, 2), ("data", "model"))
            cfg = MWEMConfig(eps=0.5, delta=1e-3, T=4, mode="fast")
            svc = ReleaseService(Q, cfg, wave_size=2, mesh=mesh,
                                 auto_flush=False)
            for name in ("a", "b"):
                svc.create_session(name, eps_budget=50.0, delta_budget=0.5,
                                   h=h, n_records=n)
            ta = svc.submit("a", seed=11)
            tb = svc.submit("b", seed=12)
            svc.flush()
            assert ta.status == tb.status == "done"
            assert svc.stats.dispatches == 1
            gcfg = svc._group_cfg(n)
            for name, seed in (("a", 11), ("b", 12)):
                solo = run_mwem_sharded(Q, jnp.asarray(h), gcfg,
                                        jax.random.PRNGKey(seed), mesh=mesh,
                                        index=svc.index)
                rel = svc.session(name).latest
                np.testing.assert_allclose(np.asarray(rel.p_hat),
                                           np.asarray(solo.p_hat), atol=1e-6)
                # charged exactly the previewed bundle
                exp = PrivacyLedger().preview(
                    *release_cost(gcfg, m, U, index=svc.index))
                assert svc.session(name).ledger.composed() == exp
                assert rel.eps_cost == ta.decision.eps_cost
            print("OK")
        """)
        assert "OK" in out


class TestDryRunMachinery:
    def test_cell_builds_and_compiles_on_small_mesh(self):
        out = _run("""
            import jax, jax.numpy as jnp
            import repro.launch.cells as C
            C.MODEL_DEGREE = 2
            from repro.configs import get_smoke_config
            import repro.launch.cells as cells_mod
            # monkeypatch get_config to the smoke config for a tiny compile
            import repro.configs as cfgs
            from repro.launch.mesh import make_mesh
            orig = cells_mod.get_config
            cells_mod.get_config = lambda name: cfgs.get_smoke_config(name)
            mesh = make_mesh((2, 2), ("data", "model"))
            from repro.configs.base import SHAPES, ShapeConfig
            SHAPES["train_4k"] = ShapeConfig("train_4k", 64, 8, "train")
            cell = cells_mod.build_cell("llama3-8b", "train_4k", mesh, False)
            with mesh:
                compiled = jax.jit(cell.fn).lower(*cell.args).compile()
            assert compiled.cost_analysis()
            print("OK")
        """, devices=4)
        assert "OK" in out

    def test_paper_cell_lowers_both_modes(self):
        """The dry-run cell is built on the real driver (`make_mwem_scan`)
        and must lower/compile in both modes on a small mesh."""
        out = _run("""
            import jax
            from repro.core.distributed import build_distributed_mwem_cell
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 2), ("data", "model"))
            for mode in ("exhaustive", "lazy"):
                fn, args, meta = build_distributed_mwem_cell(
                    mesh, False, mode=mode, m=2**14, U=2**8)
                with mesh:
                    compiled = jax.jit(fn).lower(*args).compile()
                assert meta["mode"] == mode and meta["T"] == 1
            print("OK")
        """, devices=4)
        assert "OK" in out


class TestMoEEP:
    def test_ep_matches_dense_path(self):
        out = _run("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_smoke_config
            from repro.models import mlp as M
            from repro.models.common import sharding_ctx, ParamBuilder
            from repro.configs.base import ShardingRules
            from repro.launch.mesh import make_mesh
            cfg = get_smoke_config("qwen3-moe-30b-a3b").with_(
                dtype="float32", moe_capacity_factor=8.0)
            pb = ParamBuilder(jax.random.PRNGKey(0), dtype=jnp.float32)
            M.init_mlp(pb, cfg, "mlp")
            p = pb.params["mlp"]
            x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
            y_dense = M.moe_mlp_dense(p, x, cfg)
            mesh = make_mesh((2, 4), ("data", "model"))
            rules = ShardingRules(batch="data", experts="model")
            with mesh:
                y_ep = jax.jit(lambda p, x: M.moe_mlp_ep(p, x, cfg, mesh,
                                                         rules))(p, x)
            # routing identical; combine order differs → fp tolerance
            np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_ep),
                                       rtol=2e-4, atol=2e-4)
            print("OK")
        """, devices=8)
        assert "OK" in out
