"""Bring-up smoke: the release service's main path on one TPU chip.

Runs a multi-tenant `ReleaseService` doing dense linear-query releases on
the IVF index through the normal entry points, at the paper's domain width
(U = 2¹⁴, T = 1000), and checks what comes out:

  (a) the device is a TPU — there is no CPU path;
  (b) every ticket is done, with no retry, failure or expiry, and the
      breaker never tripped;
  (c) every kernel the waves use took its compiled route: the wave
      executables hold the Pallas custom calls, and no interpret or ref
      route was traced;
  (d) each released histogram is finite, ≥ 0, and sums to 1;
  (e) final errors and zero-ε answers match a float64 recomputation;
  (f) each release beats the uniform histogram in float64;
  (g) each tenant's ledger spend equals its admission preview;
  (h) each kernel agrees with its ``ref.py`` on identical inputs;
  (i) probe scores match float64 scores to f32 precision (an f32 matmul
      at the TPU's default precision would round to bf16 and fail here).

The last line of stdout is ``{"ok": true, "device": {...}}``; any failed
check exits non-zero before it. ``--four-chips`` runs only the mesh path:
`run_mwem_sharded` in exact and fast mode, one ``ReleaseService(mesh=)``
wave, and the single-chip fused driver on the same workload and keys.

    python chip_smoke.py [--seed N] [--four-chips]

Data is generated from ``--seed``; the script reads no file but the repo's
own sources.
"""

from __future__ import annotations

import argparse
import copy
import json
import re
import sys
import time
from collections import Counter
from dataclasses import replace
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# The paper's workload (configs/fastmwem_synth.py) has U = 2¹⁴ and
# m = 2²⁴ complement-augmented queries. Dense f32 at that m is 2⁴⁰ bytes,
# so m is cut by 2⁹ to fit one chip: 2¹⁴ base queries, 2¹⁵ with
# complements. U and T are the paper's.
M = 2 ** 14
U = 2 ** 14
T = 1000
WAVE = 8
TENANTS = 8
N_RECORDS = 10 ** 6
PARTIAL = 3          # a flushed partial wave: runs on the ladder's 4 lanes

# f32 sums of n products, reduced in any order, stay within
# ~log2(n)·2⁻²⁴ of the sum of the products' magnitudes; 2⁻¹⁸ leaves a
# margin of 4 over U = 2¹⁴ terms. bf16 operand rounding (2⁻⁹ per term)
# exceeds it by about 8× on these workloads.
F32_REL = 2.0 ** -18
# mwem_step vs its ref: the U-long reductions run in another order on the
# chip; that moves log-weights by ~2⁻²³·|lw| and, through exp, the
# density by the same relative amount. 2⁻¹⁶ is a margin of ~100.
STEP_REL = 2.0 ** -16


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def within_f32(got, want64, scale64, what: str) -> float:
    """|got − want| ≤ F32_REL·scale (+ one f32 ulp of 1e-30 for zeros)."""
    err = np.abs(np.asarray(got, np.float64) - want64)
    bound = F32_REL * np.asarray(scale64, np.float64) + 1e-30
    worst = float(np.max(err / bound))
    check(worst <= 1.0, f"{what}: error {worst:.3g}× the f32 bound")
    return worst


def pallas_calls(hlo_text: str) -> Counter:
    """Kernel names of the Pallas TPU custom calls in a compiled module."""
    names = re.findall(r"%([A-Za-z_][\w]*?)(?:\.\d+)? = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', hlo_text)
    return Counter(names)


def wave_executables(index):
    """The batched driver's AOT executables cached on ``index``."""
    for _, exes in index.__dict__.get("_fused_driver_cache", {}).values():
        yield from exes.values()


# --------------------------------------------------------------- (h), (i)
def kernel_checks(jax, jnp, Q, h0, ivf, rng):
    """Each main-path kernel against its ref on identical inputs."""
    from repro.core.workload import MarginalWorkload
    from repro.kernels.ivf_probe import (ivf_probe_topk, ivf_probe_topk_batch,
                                         ivf_probe_topk_batch_ref,
                                         ivf_probe_topk_ref)
    from repro.kernels.mips_topk import mips_abs_topk, mips_topk, mips_topk_ref
    from repro.kernels.mwem_step import (mwem_step, mwem_step_batch,
                                         mwem_step_ref, mwu_apply)
    from repro.kernels.mwem_step.ops import marginal_gather_score
    from repro.mips.flat import _flat_abs_query_scores

    k, nprobe = 128, ivf.nprobe
    # integer probes make every score an exact integer in f32, so kernel
    # and ref rank identical numbers and must return identical ids
    vi = jnp.asarray(rng.integers(-3, 4, size=U).astype(np.float32))
    vb = jnp.asarray(rng.integers(-3, 4, size=(WAVE, U)).astype(np.float32))

    i_k, s_k = mips_topk(Q, vi, k)
    i_r, s_r = mips_topk_ref(Q, vi, k)
    check(np.array_equal(i_k, i_r) and np.array_equal(s_k, s_r),
          "mips_topk ids differ from ref")
    i_k, s_k = mips_topk(Q, vi, k, absolute=True)
    s_r, i_r = jax.lax.top_k(jnp.abs(Q @ vi), k)
    check(np.array_equal(i_k, i_r), "mips_topk(abs) ids differ from ref")
    a_k, s_k = mips_abs_topk(Q, vi, k)
    a_r, s_r, _ = _flat_abs_query_scores(Q, vi, k)
    check(np.array_equal(a_k, a_r), "mips_abs_topk ids differ from ref")

    # the service index's cell layout with integer-valued centroids, so
    # the centroid stage is exact too
    cents = jnp.round(ivf._cents * 8.0)
    cells, rows, V = ivf._cells, ivf._rows_by_cell(), ivf._v
    for absolute in (False, True):
        got = ivf_probe_topk(cents, rows, cells, vi, k, nprobe,
                             absolute=absolute)
        ref = ivf_probe_topk_ref(cents, cells, V, vi, k, nprobe,
                                 absolute=absolute)
        check(all(np.array_equal(a, b) for a, b in zip(got, ref)),
              f"ivf_probe_topk(absolute={absolute}) differs from ref")
        got = ivf_probe_topk_batch(cents, rows, cells, vb, k, nprobe,
                                   absolute=absolute)
        ref = ivf_probe_topk_batch_ref(cents, cells, V, vb, k, nprobe,
                                       absolute=absolute)
        check(all(np.array_equal(a, b) for a, b in zip(got, ref)),
              f"ivf_probe_topk_batch(absolute={absolute}) differs from ref")

    W = MarginalWorkload.all_kway((2,) * (U.bit_length() - 1), 3)  # U cells
    midx = jnp.asarray(rng.integers(0, 2 * W.m, size=300).astype(np.int32))
    got = marginal_gather_score(W, vi, midx)
    check(np.array_equal(got, W.score_in_graph(vi, midx)),
          "marginal_gather_score differs from ref")

    # mwem_step on a realistic carried state
    lw = jnp.asarray(rng.standard_normal((WAVE, U)).astype(np.float32) * 2)
    lw = lw - jnp.max(lw, axis=1, keepdims=True)
    p = jax.nn.softmax(lw, axis=1)
    ps = jnp.asarray(rng.uniform(0, 3, (WAVE, U)).astype(np.float32))
    sel = jnp.asarray(rng.integers(0, M, size=WAVE).astype(np.int32))
    noise = jnp.asarray(rng.laplace(size=WAVE).astype(np.float32) * 1e-3)
    worst = 0.0
    step_ref = jax.jit(jax.vmap(
        lambda *a: mwem_step_ref(*a, rule="hardt", eta=0.1),
        in_axes=(0, 0, 0, 0, None, 0)))
    want = step_ref(lw, p, ps, Q[sel], h0, noise)
    outs = {
        "mwem_step_batch": mwem_step_batch(lw, p, ps, Q, sel, h0, noise,
                                           rule="hardt", eta=0.1),
        "mwem_step": [jnp.stack(o) for o in zip(*[
            mwem_step(lw[b], p[b], ps[b], Q, sel[b], h0, noise[b],
                      rule="hardt", eta=0.1) for b in range(WAVE)])],
        "mwu_apply": [jnp.stack(o) for o in zip(*[
            mwu_apply(lw[b], p[b], ps[b], Q[sel[b]], h0, noise[b],
                      rule="hardt", eta=0.1) for b in range(WAVE)])],
    }
    for name, got in outs.items():
        for part, g, w in zip(("log_w", "p", "p_sum"), got, want):
            g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
            scale = np.maximum(np.abs(w), 1.0) if part == "log_w" else np.abs(w)
            err = float(np.max(np.abs(g - w) / (STEP_REL * scale + 1e-30)))
            check(err <= 1.0, f"{name} {part}: {err:.3g}× the tolerance")
            worst = max(worst, err)
    say("kernel_vs_ref_ids", "identical (mips_topk plain/abs/aug, "
        "ivf_probe_topk, ivf_probe_topk_batch, marginal_gather_score)")
    say("mwem_step_vs_ref_worst_fraction_of_tolerance", worst)

    # (i) probe scores on real probe vectors, against float64
    v = (np.asarray(h0, np.float32)[None]
         - np.asarray(p[:WAVE], np.float32)).astype(np.float32)
    vd = jnp.asarray(v)
    ids, scores, _ = ivf_probe_topk_batch(ivf._cents, rows, cells, vd, k,
                                          nprobe)
    ids, scores = np.asarray(ids), np.asarray(scores)
    ok = ids >= 0
    got_rows = np.asarray(V[np.where(ok, ids, 0)], np.float64)  # (B, k, U)
    want = np.einsum("bku,bu->bk", got_rows, v)
    scale = np.einsum("bku,bu->bk", np.abs(got_rows), np.abs(v))
    w1 = within_f32(scores[ok], want[ok], scale[ok], "ivf probe scores")
    fi, fs = mips_topk(Q, vd[0], k)
    q_rows = np.asarray(Q[np.asarray(fi)], np.float64)
    w2 = within_f32(fs, q_rows @ v[0], np.abs(q_rows) @ np.abs(v[0]),
                    "flat probe scores")
    say("probe_scores_vs_float64_worst_fraction_of_f32_bound", max(w1, w2))


# ------------------------------------------------------------ one chip
def release_checks(svc, tickets, Q64, h_by_tenant):
    """(b), (d), (e), (f), (g) for a service's finished tickets."""
    check(all(t.status == "done" for t in tickets),
          f"ticket states {Counter(t.status for t in tickets)}")
    st = svc.stats
    check(st.retries == 0 and st.failed == 0 and st.expired == 0,
          f"service stats {st.as_dict()}")
    check(svc.breaker.trips == 0 and not svc.breaker.is_open
          and not svc.degraded, "the breaker tripped")
    uniform = np.full(U, 1.0 / U)
    worst = 0.0
    for t in tickets:
        p_hat = np.asarray(t.release.p_hat, np.float64)
        check(np.all(np.isfinite(p_hat)) and np.all(p_hat >= 0)
              and abs(p_hat.sum() - 1.0) <= 1e-5,
              f"ticket {t.ticket_id}: not a distribution")
        h = h_by_tenant[t.tenant_id]
        d = p_hat - h
        errs = Q64 @ d
        err64 = float(np.max(np.abs(errs)))
        worst = max(worst, within_f32(          # |Q| = Q: 0/1 queries
            [t.final_error], [err64], [float(np.max(Q64 @ np.abs(d)))],
            f"ticket {t.ticket_id} final_error"))
        unif = float(np.max(np.abs(Q64 @ (uniform - h))))
        check(err64 < unif, f"ticket {t.ticket_id}: error {err64} is not "
              f"below the uniform histogram's {unif}")
    for tid in {t.tenant_id for t in tickets}:
        sess = svc.session(tid)
        last = [t for t in tickets if t.tenant_id == tid][-1]
        spent = sess.spent()
        check(spent == (last.decision.eps_projected,
                        last.decision.delta_projected),
              f"{tid}: spent {spent} != preview "
              f"{(last.decision.eps_projected, last.decision.delta_projected)}")
    return worst


def routes_and_hlo(index, want_kernels):
    from repro.kernels import route

    taken = route.taken()
    say("routes", json.dumps({k: sorted(v) for k, v in sorted(taken.items())}))
    for name in want_kernels:
        check(taken.get(name) == {route.COMPILED},
              f"kernel {name} took route {taken.get(name)}")
    check(all(v == {route.COMPILED} for v in taken.values()),
          "a kernel took an interpret or ref route")
    exes = list(wave_executables(index))
    check(exes, "no wave executable was compiled")
    calls = Counter()
    for exe in exes:
        found = pallas_calls(exe.as_text())
        check(found, "a wave executable holds no tpu_custom_call")
        calls.update(found)
    say("wave_executables", len(exes))
    say("wave_pallas_calls", json.dumps(dict(sorted(calls.items()))))
    return calls


def one_chip(jax, jnp, seed: int) -> None:
    from repro.core import MWEMConfig, run_mwem_batch
    from repro.core.queries import gaussian_histogram, random_binary_queries
    from repro.serve.release_service import ReleaseService

    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    kq, kh = jax.random.split(key)
    t0 = time.perf_counter()
    Q = random_binary_queries(kq, M, U)
    hs = {f"tenant-{i}": np.asarray(gaussian_histogram(k, N_RECORDS, U))
          for i, k in enumerate(jax.random.split(kh, TENANTS))}
    Q64 = np.asarray(Q, np.float64)
    h64 = {t: h.astype(np.float64) for t, h in hs.items()}
    say("data_seconds", time.perf_counter() - t0)

    cfg = MWEMConfig(eps=1.0, delta=1e-3, T=T, mode="fast")
    t0 = time.perf_counter()
    svc = ReleaseService(Q, cfg, wave_size=WAVE, index_kind="ivf", seed=seed,
                         streaming=True)
    say("ivf_index", f"nlist={svc.index.nlist} cap={svc.index.cap} "
        f"nprobe={svc.index.nprobe} rows={svc.index.n}")
    say("index_build_seconds", time.perf_counter() - t0)
    for tid, h in hs.items():
        svc.create_session(tid, eps_budget=10.0, delta_budget=0.1, h=h,
                           n_records=N_RECORDS)

    kernel_checks(jax, jnp, Q, jnp.asarray(hs["tenant-0"]), svc.index, rng)

    # Routes are recorded when a kernel is traced, and jit reuses an inner
    # kernel's trace for shapes it has seen (the flat wave's step kernel is
    # the IVF wave's), so the record is kept for the whole run: every
    # kernel traced anywhere in it must have taken the compiled route.
    t0 = time.perf_counter()
    svc.prewarm(N_RECORDS)
    say("compile_seconds_wave_ladder", time.perf_counter() - t0)
    t0 = time.perf_counter()
    full = [svc.submit(tid) for tid in hs]
    svc.flush()
    say("full_wave_seconds", time.perf_counter() - t0)
    t0 = time.perf_counter()
    partial = [svc.submit(tid) for tid in list(hs)[:PARTIAL]]
    svc.flush()
    say("partial_wave_seconds", time.perf_counter() - t0)
    sizes = [d.wave_size for d in svc.wave_log if d.dispatch]
    say("wave_sizes", sizes)
    check(sizes == [WAVE, 4], f"wave sizes {sizes}, want [8, 4]")
    check(svc.stats.padded_slots == 4 - PARTIAL,
          f"padded slots {svc.stats.padded_slots}")
    tickets = full + partial
    worst = release_checks(svc, tickets, Q64, h64)
    calls = routes_and_hlo(svc.index, ("ivf_probe_batch", "mwem_step_batch"))
    check(calls["ivf_probe_scores"] and calls["mwem_step"],
          "the IVF wave executables lack the probe or step kernel")

    # zero-ε answer reads, against float64
    for tid in list(hs)[:3]:
        rel = svc.session(tid).latest
        for j in rng.integers(0, M, size=2):
            q = np.asarray(Q[j])
            a = svc.answer(tid, q)
            p64 = np.asarray(rel.p_hat, np.float64)
            worst = max(worst, within_f32(
                [a.value], [q.astype(np.float64) @ p64], [np.abs(q) @ p64],
                f"answer {tid} q{j}"))
            check(svc.answer(tid, q).cached, "a repeated answer missed")
    say("release_vs_float64_worst_fraction_of_f32_bound", worst)
    say("final_errors", [round(t.final_error, 6) for t in tickets])

    # the same seeds on the XLA route: printed, not gated (near-ties flip)
    xla_index = copy.copy(svc.index)
    xla_index._use_pallas = "never"
    xla_index._fused_driver_cache = {}      # its own executables
    xcfg = replace(cfg, n_records=N_RECORDS, use_pallas="never")
    same, dmax = 0, 0.0
    for group in (full, partial):
        res = run_mwem_batch(
            Q, jnp.asarray(np.stack([hs[t.tenant_id] for t in group])), xcfg,
            jnp.stack([jax.random.PRNGKey(t.seed) for t in group]),
            index=xla_index)
        for t, p_x in zip(group, np.asarray(res.p_hat)):
            diff = float(np.max(np.abs(p_x - t.release.p_hat)))
            same += diff == 0.0
            dmax = max(dmax, diff)
    say("xla_route_identical_releases", f"{same}/{len(tickets)}")
    say("xla_route_max_abs_p_hat_diff", dmax)

    # the flat index as the control
    flat = ReleaseService(Q, cfg, wave_size=WAVE, index_kind="flat",
                          seed=seed + 1, streaming=True)
    flat.create_session("tenant-0", eps_budget=10.0, delta_budget=0.1,
                        h=hs["tenant-0"], n_records=N_RECORDS)
    t0 = time.perf_counter()
    ft = flat.submit("tenant-0")
    flat.flush()
    say("flat_release_seconds_with_compile", time.perf_counter() - t0)
    release_checks(flat, [ft], Q64, h64)
    calls = routes_and_hlo(flat.index, ("mwem_step_batch",))
    check(calls["mwem_step"], "the flat wave executable lacks the step kernel")
    say("flat_final_error", ft.final_error)

    stats = jax.devices()[0].memory_stats() or {}
    say("peak_bytes_in_use", stats.get("peak_bytes_in_use", "not reported"))


# ---------------------------------------------------------- four chips
def four_chips(jax, jnp, seed: int) -> None:
    from repro.core import MWEMConfig, run_mwem_fused, run_mwem_sharded
    from repro.core.queries import gaussian_histogram, random_binary_queries
    from repro.launch.mesh import make_driver_mesh
    from repro.mips import ShardedIVFIndex
    from repro.serve.release_service import ReleaseService

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    mesh = make_driver_mesh(4)
    key = jax.random.PRNGKey(seed)
    kq, kh, kr = jax.random.split(key, 3)
    Q = np.array(random_binary_queries(kq, M, U))     # host copy only
    hs = [np.asarray(gaussian_histogram(k, N_RECORDS, U))
          for k in jax.random.split(kh, 4)]
    h = hs[0]
    Q64 = np.asarray(Q, np.float64)
    unif = float(np.max(np.abs(Q64 @ (np.full(U, 1.0 / U) - h))))
    exact = MWEMConfig(eps=1.0, delta=1e-3, T=T, mode="exact",
                       n_records=N_RECORDS)
    fast = replace(exact, mode="fast")

    t0 = time.perf_counter()
    r_ex = run_mwem_sharded(Q, h, exact, kr, mesh=mesh)
    say("sharded_exact_seconds_with_compile", time.perf_counter() - t0)
    t0 = time.perf_counter()
    sidx = ShardedIVFIndex(Q, n_shards=4, seed=seed)
    r_fast = run_mwem_sharded(Q, h, fast, kr, mesh=mesh, index=sidx)
    say("sharded_fast_seconds_with_index_and_compile",
        time.perf_counter() - t0)
    placed = sidx.cells
    shard_devs = {s.device for s in placed.addressable_shards}
    check(len(shard_devs) == 4, f"IVF shards on {len(shard_devs)} devices")

    svc = ReleaseService(Q, replace(fast, n_records=None), wave_size=4,
                         seed=seed, mesh=mesh)
    for i, hh in enumerate(hs):
        svc.create_session(f"tenant-{i}", eps_budget=10.0, delta_budget=0.1,
                           h=hh, n_records=N_RECORDS)
    t0 = time.perf_counter()
    tickets = [svc.submit(f"tenant-{i}") for i in range(4)]
    svc.flush()
    say("mesh_wave_seconds_with_compile", time.perf_counter() - t0)
    check(all(t.status == "done" for t in tickets),
          f"mesh wave tickets {Counter(t.status for t in tickets)}")
    check(not svc.degraded and svc.breaker.trips == 0, "breaker tripped")

    # the service's table: four distinct devices, a quarter of Q on each
    shards = svc.Q.addressable_shards
    check(len({s.device for s in shards}) == 4
          and all(s.data.shape == (M // 4, U) for s in shards),
          "the service's Q is not split over four devices")
    whole = [a for a in jax.live_arrays()
             if a.size >= M * U and len(a.sharding.device_set) == 1]
    check(not whole, f"whole query tables on one device: "
          f"{[(a.shape, str(a.sharding)) for a in whole]}")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]
    say("bytes_in_use_per_device", in_use)
    if all(in_use):
        check(max(in_use) <= 1.5 * min(in_use) + 2 ** 28,
              "device memory is piled on one device")

    for name, r in (("exact", r_ex), ("fast", r_fast)):
        p = np.asarray(r.p_hat, np.float64)
        err = float(np.max(np.abs(Q64 @ (p - h))))
        check(np.all(np.isfinite(p)) and abs(p.sum() - 1) <= 1e-5
              and err < unif, f"sharded {name}: bad release (err {err})")
        say(f"sharded_{name}_error_float64", err)
    for t in tickets:
        p = np.asarray(t.release.p_hat, np.float64)
        check(abs(p.sum() - 1) <= 1e-5 and np.all(p >= 0),
              "mesh wave: not a distribution")
    say("mesh_wave_final_errors", [round(t.final_error, 6) for t in tickets])

    # single-chip fused driver on the same workload and keys
    with jax.default_device(devs[0]):
        Q1 = jnp.asarray(Q)
        t0 = time.perf_counter()
        r1 = run_mwem_fused(Q1, jnp.asarray(h), exact, kr)
        say("single_chip_exact_seconds_with_compile",
            time.perf_counter() - t0)
        agree = float(np.mean(np.asarray(r1.selected)
                              == np.asarray(r_ex.selected)))
        say("exact_selection_agreement_sharded_vs_single_chip", agree)
        say("single_chip_exact_error", r1.final_error)
        say("sharded_exact_error", r_ex.final_error)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh phases")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); there is no "
              "CPU path", file=sys.stderr)
        return 1
    say("compile_cache", enable_compile_cache())
    say("device", f"{dev.platform} {dev.device_kind} x{len(jax.devices())}")
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(jax, jnp, args.seed)
        else:
            one_chip(jax, jnp, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    say("total_seconds", time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
