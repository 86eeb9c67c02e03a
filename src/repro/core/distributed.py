"""Distributed Fast-MWEM: the sharded driver for the production mesh.

Layout (DESIGN.md §4):
  * Q (m × U):   rows over the batch axes ("pod","data"), cols over "model"
  * log-weights (U,): sharded over "model", replicated over data
  * per-data-shard IVF structure: centroids (nlist × U_loc, model-sharded
    cols) + padded cell tables (nlist × cap, local row ids) — built offline
    per shard by `repro.mips.ShardedIVFIndex`, never gathered.

Two iteration flavours share one body (`_make_iteration_body`):
  * ``exhaustive``: every shard scores all its rows; the partial inner
    products are psum-ed over "model" (m_loc floats of wire per iteration) —
    the distributed Θ(m) baseline. Per-row Gumbels are sliced out of the
    *global* (m,)-shaped draw keyed by the per-iteration selection key, so
    the sharded exhaustive mechanism is bitwise the host `_exact_argmax`
    (modulo psum float reassociation) — the host-parity anchor the
    equivalence tests lean on.
  * ``lazy`` (the paper): centroid scores (psum of nlist floats) pick
    nprobe cells; only the valid probed rows plus a Gumbel tail are scored
    and psum-ed — Θ(√m)-ish wire and FLOPs. The tail uses *binomial
    thinning*: C ~ Bin(m−k, p) splits exactly into independent per-shard
    Bin(m_loc − k_loc, p) draws, and each shard's tail reuses the
    single-device dedup machinery (`lazy_em.draw_distinct_tail`:
    complement-shift around the shard's top-k, sort-and-mask rejection of
    duplicate draws) so no element carries two truncated Gumbels. If any
    shard's tail buffer overflows, the whole iteration `lax.cond`s into the
    exhaustive per-shard scan — exactness is preserved, mirroring the fused
    driver's fallback.

Selection is reproduced exactly: every shard computes the same global
argmax from the all-gathered (id, score+Gumbel) candidates, then the
winning query row is broadcast by a one-hot psum and the multiplicative-
weights update (the same `_mwu_step` semantics as the host/fused drivers,
including the Laplace measurement) is applied to the model-sharded state.

`run_mwem_sharded` wraps the iteration in a full T-step `lax.scan` inside
one `shard_map` — a single dispatch for the whole run, per-iteration traces
returned as stacked scan outputs, and `PrivacyLedger` bookkeeping through
the same `_record_iteration` path as the other drivers.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.accountant import PrivacyLedger
from repro.core.gumbel import tail_prob, truncated_gumbel
from repro.core.lazy_em import default_tail_cap, draw_distinct_tail, fallback_key
from repro.core.mwem import (
    MWEMBatchResult,
    MWEMConfig,
    MWEMResult,
    _calibrate,
    _check_fast_index,
    _compiled_driver,
    _measure_noise,
    _record_iteration,
    release_cost,
    split_chain,
)
from repro.core.queries import max_error
from repro.kernels.mwem_step.ops import mwem_step_supported, mwu_apply
from repro.kernels.route import default_interpret
from repro.obs.clock import perf_counter
from repro.obs.telemetry import aggregate_traces, record_run
from repro.obs.trace import annotate as obs_annotate
from repro.precision import dot as _dot


def _fold_axes(key, axes):
    for ax in axes:
        key = jax.random.fold_in(key, jax.lax.axis_index(ax))
    return key


def _raw_key(key):
    key = jnp.asarray(key)
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        return jax.random.key_data(key)
    return key


@partial(jax.jit, static_argnames="T")
def _split_chain(key, T: int):
    """`mwem.split_chain` (the one shared key chain, so all three drivers
    consume identical randomness) as (T, 2)-stacked *raw* key data —
    shard_map replicates raw uint32 cleanly."""
    sel, meas = split_chain(key, T)
    return _raw_key(sel), _raw_key(meas)


def _make_iteration_body(mesh, *, m: int, U: int, nlist: int, cap: int,
                         nprobe: int, k_loc: int, tail_cap: int,
                         scale: float, eta: float, lap_scale: float,
                         rule: str, mode: str, multi_pod: bool,
                         fallback: bool = True, use_pallas: bool = False,
                         interpret: bool = True):
    """Returns ``(body, data_axes)`` where ``body`` is the per-shard
    iteration ``(Q, cents, cells, cell_rows, h, logw, p_sum, k_sel, k_meas)
    → (logw', p_sum', stats)`` run inside shard_map. All array arguments
    are the *local* shards; keys are replicated raw key data.

    ``use_pallas`` swaps the lazy probe's gather → matvec → top_k for the
    fused `kernels.ivf_probe` kernel (valid only when "model" has extent 1 —
    the kernel fuses dot+top-k, so the partial-dot psum of a model-sharded
    probe cannot interpose; `run_mwem_sharded` gates this). ``cell_rows``
    is the per-shard (nlist, cap, U_loc) cell-grouped copy of Q the kernel
    streams from, built once per dispatch by the scan wrapper (a dummy
    (1, 1, U_loc) when the XLA path runs)."""
    data_axes = ("pod", "data") if multi_pod else ("data",)
    n_data = math.prod(mesh.shape[a] for a in data_axes)
    m_loc = m // n_data
    n_cand = k_loc + tail_cap  # fixed candidate buffer per shard

    def _global_softmax(logw):
        lmax = jax.lax.pmax(jnp.max(logw), "model")
        ex = jnp.exp(logw - lmax)
        Z = jax.lax.psum(jnp.sum(ex), "model")
        return ex / Z

    def _shard_id():
        sid = jnp.int32(0)
        for ax in data_axes:
            sid = sid * mesh.shape[ax] + jax.lax.axis_index(ax)
        return sid

    def _exhaustive_candidates(Q, v, k_sel, shard_id):
        """Score all local rows; Gumbels come from the *global* (m,) draw
        keyed by k_sel (each shard slices its segment), so the mechanism is
        bitwise the host `_exact_argmax`. Output padded to the lazy
        candidate buffer so both `lax.cond` branches agree on shapes."""
        scores = jax.lax.psum(_dot(Q, v), "model")         # (m_loc,)
        x = jnp.abs(scores) * scale
        g_full = jax.random.gumbel(k_sel, (m,))
        g = jax.lax.dynamic_slice(g_full, (shard_id * m_loc,), (m_loc,))
        pert = x + g
        best = jnp.argmax(pert)
        cand_gids = jnp.zeros((n_cand,), jnp.int32)
        cand_gids = cand_gids.at[0].set(shard_id * m_loc + best.astype(jnp.int32))
        cand_pert = jnp.full((n_cand,), -jnp.inf, jnp.float32)
        cand_pert = cand_pert.at[0].set(pert[best])
        return cand_gids, cand_pert, jnp.float32(m_loc)

    def _lazy_candidates(Q, cents, cells, cell_rows, v, k_sel, shard_id):
        """IVF-pruned top-k plus the thinned Gumbel tail, per shard.
        Returns the candidate buffer and this shard's overflow flag."""
        k1 = _fold_axes(k_sel, data_axes)                  # per-shard stream
        kg, kc, kt, kg2 = jax.random.split(k1, 4)

        if use_pallas:
            # ---- fused kernel probe (model extent 1, no psums needed):
            # centroid top-nprobe + scalar-prefetched cell streaming, the
            # gathered candidate matrix never materialized (kernels/ivf_probe)
            from repro.kernels.ivf_probe import ivf_probe_topk

            top_ids, top_abs, n_valid = ivf_probe_topk(
                cents[0], cell_rows, cells[0], v, k_loc, nprobe,
                interpret=interpret, absolute=True)
            top_x = top_abs * scale                        # -inf pads survive
            top_valid = top_ids >= 0
            n_probe_scored = jnp.float32(nlist) + n_valid.astype(jnp.float32)
        else:
            # ---- IVF pruning: pick nprobe cells by centroid score ----
            cscores = jax.lax.psum(_dot(cents[0], v), "model")  # (nlist,)
            _, probe = jax.lax.top_k(jnp.abs(cscores), nprobe)
            cand = cells[0][probe].reshape(-1)             # (nprobe·cap,)
            valid = cand >= 0
            rows = Q[jnp.clip(cand, 0)]                    # (cand, U_loc)
            cscore = jax.lax.psum(_dot(rows, v), "model")
            x_cand = jnp.where(valid, jnp.abs(cscore) * scale, -jnp.inf)
            top_x, top_pos = jax.lax.top_k(x_cand, k_loc)
            top_ids = cand[top_pos]
            top_valid = top_ids >= 0
            n_probe_scored = (jnp.float32(nlist)
                              + jnp.sum(valid).astype(jnp.float32))

        # ---- lazy Gumbel over the shard's top-k ----
        g = jax.random.gumbel(kg, (k_loc,))
        pert_top = top_x + g
        M = jnp.max(pert_top)
        # an all-padding probe gives M = min = -inf and B = NaN; force the
        # margin to +inf instead (C = 0, tail inert) so the shard simply
        # contributes no candidates rather than poisoning the binomial
        B = M - jnp.min(top_x)
        B = jnp.where(jnp.isnan(B), jnp.inf, B)
        # binomial thinning of the global tail across shards
        pt = tail_prob(B)
        C = jax.random.binomial(kc, m_loc - k_loc, pt).astype(jnp.int32)
        # distinct tail draws from [m_loc] \ top-k — the same complement-
        # shift + sort-and-mask dedup the single-device LazyEM uses (a
        # with-replacement draw would bias the max upward, lazy_em.py §).
        # Invalid top slots map to distinct ≥ m_loc sentinels: they exclude
        # nothing and keep the shift monotone; they can only occur when the
        # probe found < k_loc rows, in which case B = ∞ ⇒ C = 0 and the
        # tail is inert anyway.
        safe_top = jnp.where(top_valid, top_ids,
                             m_loc + jnp.arange(k_loc, dtype=top_ids.dtype))
        tail_ids, active, overflow = draw_distinct_tail(
            kt, safe_top, m_loc, tail_cap, C)
        tail_ids = jnp.clip(tail_ids, 0, m_loc - 1)
        trows = Q[tail_ids]
        tscore = jax.lax.psum(_dot(trows, v), "model")
        tx = jnp.abs(tscore) * scale
        tg = truncated_gumbel(kg2, (tail_cap,), B)
        pert_tail = jnp.where(active, tx + tg, -jnp.inf)

        local_ids = jnp.concatenate([jnp.clip(top_ids, 0), tail_ids])
        cand_gids = shard_id * m_loc + local_ids.astype(jnp.int32)
        cand_pert = jnp.concatenate([pert_top, pert_tail])
        # scored work: centroid scan + *valid* probed rows (padded -1 slots
        # are masked — they cost no FLOPs) + live tail draws
        n_scored = n_probe_scored + jnp.sum(active).astype(jnp.float32)
        return cand_gids, cand_pert, n_scored, overflow

    def body(Q, cents, cells, cell_rows, h, logw, p_sum, k_sel, k_meas):
        p = _global_softmax(logw)
        v = h - p                                          # (U_loc,)
        shard_id = _shard_id()

        if mode == "exhaustive":
            cand_gids, cand_pert, n_loc = _exhaustive_candidates(
                Q, v, k_sel, shard_id)
            overflow = jnp.bool_(False)
        elif mode == "lazy":
            lazy = _lazy_candidates(Q, cents, cells, cell_rows, v, k_sel,
                                    shard_id)
            # any shard overflowing redoes the *whole* iteration
            # exhaustively (the fallback must cover every shard's rows, and
            # the predicate must be replicated for the collectives inside
            # the branches) — same exactness contract as the fused driver.
            # ``fallback=False`` drops the redo branch: for HLO wire/FLOP
            # analysis of the hot path only — the Θ(m) branch would be
            # counted at full weight by the static analyzer even though it
            # executes with probability e^{-Ω(√m)}. The driver always runs
            # with the fallback on.
            overflow = jax.lax.psum(
                lazy[3].astype(jnp.int32), data_axes) > 0
            if fallback:
                # the redo draws fresh Gumbels under `lazy_em.fallback_key`
                # (the lazy pass already consumed k_sel's) — same fold the
                # host/fused drivers apply on their overflow branch
                cand_gids, cand_pert, n_loc = jax.lax.cond(
                    overflow,
                    lambda _: _exhaustive_candidates(
                        Q, v, fallback_key(k_sel), shard_id),
                    lambda _: lazy[:3],
                    operand=None,
                )
            else:
                cand_gids, cand_pert, n_loc = lazy[:3]
        else:
            raise ValueError(f"unknown distributed mode {mode!r}")
        n_scored = jax.lax.psum(n_loc, data_axes)

        # ---- global argmax over all shards' candidates ----
        all_ids = jax.lax.all_gather(cand_gids, data_axes, tiled=True)
        all_pert = jax.lax.all_gather(cand_pert, data_axes, tiled=True)
        winner_pos = jnp.argmax(all_pert)
        winner_gid = all_ids[winner_pos]

        # ---- broadcast the winning row via one-hot psum ----
        local_row = winner_gid - shard_id * m_loc
        is_owner = (local_row >= 0) & (local_row < m_loc)
        row = jnp.where(is_owner,
                        Q[jnp.clip(local_row, 0, m_loc - 1)],
                        jnp.zeros((Q.shape[1],), Q.dtype))
        row = jax.lax.psum(row, data_axes)                 # (U_loc,)

        # ---- MW update ----
        if use_pallas and mwem_step_supported(U):
            # Megakernel seam (DESIGN.md §7): model extent is 1 whenever
            # ``use_pallas`` is live (`run_mwem_sharded` gates it), so the
            # psum/pmax collectives in the XLA tail below are identities —
            # hand the one-hot-psum'd winner row straight to the fused
            # measure→MWU→renorm kernel, the same `kernels.mwem_step` seam
            # the fused drivers run.
            noise = _measure_noise(k_meas, rule, lap_scale)
            logw_new, p_new, ps_new = mwu_apply(
                logw, p, p_sum, row, h, noise, rule=rule, eta=eta,
                interpret=interpret)
            stats = {"winner": winner_gid, "n_scored": n_scored,
                     "overflow": overflow}
            return logw_new, ps_new, stats
        # the host `_mwu_step` math on the model-sharded state
        if rule == "paper":
            logw_new = logw - eta * row
        else:
            true_ans = jax.lax.psum(_dot(row, h), "model")
            noise = lap_scale * jax.random.laplace(k_meas)
            measured = true_ans + noise
            est = jax.lax.psum(_dot(row, p), "model")
            if rule == "signed":
                logw_new = logw + eta * jnp.sign(measured - est) * row
            elif rule == "hardt":
                logw_new = logw + row * (measured - est) / 2.0
            else:
                raise ValueError(f"unknown update rule {rule!r}")
        logw_new = logw_new - jax.lax.pmax(jnp.max(logw_new), "model")
        p_new = _global_softmax(logw_new)
        stats = {"winner": winner_gid, "n_scored": n_scored,
                 "overflow": overflow}
        return logw_new, p_sum + p_new, stats

    return body, data_axes


_STAT_SPECS = {"winner": P(), "n_scored": P(), "overflow": P()}


def _cell_grouped_rows(Q, cells, use_pallas: bool):
    """Per-shard (nlist, cap⌈8⌉, U_loc) cell-grouped copy of the local Q
    rows — the contiguous HBM blocks the fused probe kernel streams, cap
    pre-padded to the sublane multiple so the kernel wrapper's pad is a
    no-op inside the scan body. Gathered once per dispatch (amortized over
    the T-iteration scan); a (1, 8, U_loc) dummy when the XLA probe runs."""
    if not use_pallas:
        return jnp.zeros((1, 8, Q.shape[1]), Q.dtype)
    local = cells[0]
    rows = Q[jnp.clip(local, 0)] * (local >= 0)[..., None].astype(Q.dtype)
    pad = (-rows.shape[1]) % 8
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad), (0, 0)))
    return rows


def make_mwem_iteration(mesh, *, m: int, U: int, nlist: int, cap: int,
                        nprobe: int, k_loc: int, tail_cap: int,
                        scale: float, eta: float, mode: str,
                        multi_pod: bool, rule: str = "hardt",
                        lap_scale: float = 0.0, fallback: bool = True,
                        use_pallas: bool = False, interpret: bool = True):
    """One shard-mapped iteration ``(Q, cents, cells, logw, h, key) →
    (logw', stats)`` — the scan body of `run_mwem_sharded` exposed on its
    own for HLO/roofline analysis (dry-run cells) and per-iteration tests.
    All arrays are the *global* logical views; shard_map splits them.
    ``fallback=False`` lowers the lazy hot path without the overflow-redo
    branch (static analyzers weigh the rare branch at 1×).
    """
    body, data_axes = _make_iteration_body(
        mesh, m=m, U=U, nlist=nlist, cap=cap, nprobe=nprobe, k_loc=k_loc,
        tail_cap=tail_cap, scale=scale, eta=eta, lap_scale=lap_scale,
        rule=rule, mode=mode, multi_pod=multi_pod, fallback=fallback,
        use_pallas=use_pallas, interpret=interpret)

    q_spec = P(data_axes, "model")
    cent_spec = P(data_axes, None, "model")   # (shards, nlist, U_loc)
    cell_spec = P(data_axes, None, None)      # (shards, nlist, cap)
    w_spec = P("model")

    def iteration(Q, cents, cells, logw, h, key):
        _, k_sel, k_meas = jax.random.split(key, 3)
        cell_rows = _cell_grouped_rows(Q, cells, use_pallas)
        logw_new, _, stats = body(Q, cents, cells, cell_rows, h, logw,
                                  jnp.zeros_like(logw),
                                  _raw_key(k_sel), _raw_key(k_meas))
        return logw_new, stats

    return jax.shard_map(
        iteration, mesh=mesh,
        in_specs=(q_spec, cent_spec, cell_spec, w_spec, w_spec, P()),
        out_specs=(w_spec, _STAT_SPECS),
        check_vma=False,
    )


def make_mwem_scan(mesh, *, T: int, m: int, U: int, nlist: int, cap: int,
                   nprobe: int, k_loc: int, tail_cap: int, scale: float,
                   eta: float, lap_scale: float, rule: str, mode: str,
                   multi_pod: bool, eval_every: int = 0,
                   fallback: bool = True, use_pallas: bool = False,
                   interpret: bool = True):
    """The full T-iteration sharded driver: one shard_map around one
    `lax.scan` — a single dispatch per run, traces as stacked scan outputs.

    Signature of the returned function (global logical views):
      ``(Q, cents, cells, h, logw0, p_sum0, sel_keys, meas_keys)
        → (logw_T, p_sum_T, traces)``
    with ``sel_keys``/``meas_keys`` the (T, 2) pre-split raw key chain
    (`_split_chain`) and traces a dict of (T,)-stacked per-iteration
    ``winner`` / ``n_scored`` / ``overflow`` (plus ``error`` when
    ``eval_every`` is set, NaN off-schedule like the fused driver).
    ``fallback=False`` drops the overflow-redo branch — analysis lowers
    only; the driver always runs with the fallback on.
    """
    body, data_axes = _make_iteration_body(
        mesh, m=m, U=U, nlist=nlist, cap=cap, nprobe=nprobe, k_loc=k_loc,
        tail_cap=tail_cap, scale=scale, eta=eta, lap_scale=lap_scale,
        rule=rule, mode=mode, multi_pod=multi_pod, fallback=fallback,
        use_pallas=use_pallas, interpret=interpret)

    q_spec = P(data_axes, "model")
    cent_spec = P(data_axes, None, "model")
    cell_spec = P(data_axes, None, None)
    w_spec = P("model")

    def scan_fn(Q, cents, cells, h, logw0, p_sum0, sel_keys, meas_keys):
        # one cell-grouped gather per dispatch, amortized over the T scan
        # iterations (kernel route only)
        cell_rows = _cell_grouped_rows(Q, cells, use_pallas)

        def step(carry, xs):
            logw, p_sum = carry
            t, k_sel, k_meas = xs
            logw2, p_sum2, stats = body(Q, cents, cells, cell_rows, h,
                                        logw, p_sum, k_sel, k_meas)
            if eval_every:
                # gated: the Θ(m_loc · U_loc) error matmul only runs on the
                # eval schedule, mirroring the fused driver
                def _err(_):
                    v_err = h - p_sum2 / t.astype(jnp.float32)
                    s = jax.lax.psum(_dot(Q, v_err), "model")
                    return jax.lax.pmax(jnp.max(jnp.abs(s)), data_axes)

                stats = dict(stats, error=jax.lax.cond(
                    t % eval_every == 0, _err,
                    lambda _: jnp.float32(jnp.nan), operand=None))
            return (logw2, p_sum2), stats

        ts = jnp.arange(1, T + 1)
        (logw, p_sum), traces = jax.lax.scan(
            step, (logw0, p_sum0), (ts, sel_keys, meas_keys))
        return logw, p_sum, traces

    stat_specs = dict(_STAT_SPECS)
    if eval_every:
        stat_specs["error"] = P()
    return jax.shard_map(
        scan_fn, mesh=mesh,
        in_specs=(q_spec, cent_spec, cell_spec, w_spec, w_spec, w_spec,
                  P(), P()),
        out_specs=(w_spec, w_spec, stat_specs),
        check_vma=False,
    )


_SCAN_CACHE: dict = {}


def _jitted_scan(mesh, statics: dict):
    """(jitted fn, AOT-executable cache) per (mesh, statics) — the same
    entry shape `_compiled_driver` consumes, so trace+compile stay out of
    the timed region exactly like the fused driver."""
    ck = (mesh, tuple(sorted(statics.items())))
    entry = _SCAN_CACHE.get(ck)
    if entry is None:
        entry = (jax.jit(make_mwem_scan(mesh, **statics)), {})
        _SCAN_CACHE[ck] = entry
    return entry


def _data_shards(mesh) -> tuple[tuple, int]:
    multi_pod = "pod" in mesh.axis_names
    data_axes = ("pod", "data") if multi_pod else ("data",)
    return data_axes, math.prod(mesh.shape[a] for a in data_axes)


def shard_selection_params(m_loc: int, index, k: Optional[int] = None,
                           tail_cap: Optional[int] = None) -> tuple[int, int]:
    """Per-shard top-k size and tail buffer capacity — the driver's own
    derivation (cfg overrides, √m_loc defaults, probe-width/buffer clamps),
    exposed so benchmarks and analysis cells lower exactly the program
    `run_mwem_sharded` executes."""
    k_loc = min(m_loc, index.nprobe * index.cap,
                k or max(1, math.ceil(math.sqrt(m_loc))))
    return k_loc, max(1, min(m_loc, tail_cap or default_tail_cap(m_loc)))


def run_mwem_sharded(
    Q: jax.Array,
    h: jax.Array,
    cfg: MWEMConfig,
    key: jax.Array,
    mesh=None,
    index=None,
    ledger: Optional[PrivacyLedger] = None,
) -> MWEMResult:
    """Run (Fast-)MWEM on a device mesh as one shard-mapped scan dispatch.

    Args:
      mesh: a ("data", "model") (optionally + "pod") mesh; defaults to
        `repro.launch.mesh.make_driver_mesh()` over all visible devices.
        ``m`` must divide over the data axes and ``U`` over "model".
      index: a `repro.mips.ShardedIVFIndex` whose shard count matches the
        mesh's data extent (``mode="fast"``). ``None`` builds one on the
        fly (per-shard k-means — the sharded build path; reuse the index
        across runs to amortize it).

    Selections and ledger totals reproduce the host driver: ``mode="exact"``
    is bitwise host-parity (global-sliced Gumbels, same key chain), and
    privacy events flow through the same `_record_iteration`/`_calibrate`
    path, so sharded runs compose to identical (ε, δ).

    Workload note: this driver shards explicit rows over the data axes, so
    factored workloads take the documented densify-fallback —
    `Workload.require_dense` materializes the (m, U) table or raises past
    the densify limit (auto-routing never sends such workloads here).
    """
    from repro.core.workload import as_workload
    from repro.launch.mesh import make_driver_mesh
    from repro.mips.ivf import ShardedIVFIndex

    Q = as_workload(Q).require_dense("run_mwem_sharded")
    m, U = Q.shape
    if mesh is None:
        mesh = make_driver_mesh()
    data_axes, n_data = _data_shards(mesh)
    n_model = mesh.shape["model"]
    if m % n_data:
        raise ValueError(f"m={m} must divide over {n_data} data shards")
    if U % n_model:
        raise ValueError(f"U={U} must divide over {n_model} model shards")
    m_loc = m // n_data

    if cfg.mode == "fast" and index is None:
        index = ShardedIVFIndex(Q, n_shards=n_data)
    cal = _calibrate(cfg, m, U)
    c_idx = _check_fast_index(cfg, index, fused=False)

    use_pallas = False
    if cfg.mode == "fast":
        if not getattr(index, "supports_sharded", False):
            raise ValueError(
                f"{type(index).__name__} has no per-shard structure "
                "(supports_sharded=False); pass a ShardedIVFIndex or None")
        if index.n_shards != n_data:
            raise ValueError(f"index built for {index.n_shards} shards, "
                             f"mesh has {n_data}")
        cents, cells = index.cents, index.cells
        nlist, cap, nprobe = index.nlist, index.cap, index.nprobe
        k_loc, tail_cap = shard_selection_params(m_loc, index,
                                                 k=cfg.k,
                                                 tail_cap=cfg.tail_cap)
        use_pallas = index.kernel_probe(n_model)
    else:
        # dummy per-shard structure: the exhaustive body never reads it
        cents = jnp.zeros((n_data, 1, U), jnp.float32)
        cells = jnp.full((n_data, 1, 1), -1, jnp.int32)
        nlist, cap, nprobe, k_loc, tail_cap = 1, 1, 1, 1, 1

    statics = dict(T=cfg.T, m=m, U=U, nlist=nlist, cap=cap, nprobe=nprobe,
                   k_loc=k_loc, tail_cap=tail_cap, scale=cal.scale,
                   eta=cal.eta, lap_scale=cal.lap_scale,
                   rule=cfg.update_rule,
                   mode="exhaustive" if cfg.mode == "exact" else "lazy",
                   multi_pod="pod" in mesh.axis_names,
                   eval_every=cfg.eval_every,
                   use_pallas=use_pallas,
                   interpret=default_interpret())
    entry = _jitted_scan(mesh, statics)

    # device_put is a no-op for arrays already placed with the target
    # sharding, so repeat runs (and batch lanes) re-transfer nothing;
    # writing the placed index structure back makes that stick for the
    # index too.
    ns = lambda *spec: NamedSharding(mesh, P(*spec))  # noqa: E731
    Qd = jax.device_put(jnp.asarray(Q, jnp.float32), ns(data_axes, "model"))
    cents_d = jax.device_put(jnp.asarray(cents, jnp.float32),
                             ns(data_axes, None, "model"))
    cells_d = jax.device_put(jnp.asarray(cells, jnp.int32),
                             ns(data_axes, None, None))
    if cfg.mode == "fast":
        index.cents, index.cells = cents_d, cells_d
    h_d = jax.device_put(jnp.asarray(h, jnp.float32), ns("model"))
    logw0 = jax.device_put(jnp.zeros((U,), jnp.float32), ns("model"))
    p_sum0 = jax.device_put(jnp.zeros((U,), jnp.float32), ns("model"))
    sel_keys, meas_keys = _split_chain(jnp.asarray(key), cfg.T)
    sel_keys = jax.device_put(sel_keys, ns())
    meas_keys = jax.device_put(meas_keys, ns())

    res = MWEMResult(p_hat=None, final_error=float("nan"),
                     ledger=ledger if ledger is not None else PrivacyLedger())
    if cfg.mode == "fast":
        res.ledger.record_index_failure(getattr(index, "failure_mass", 1.0 / m))

    args = (Qd, cents_d, cells_d, h_d, logw0, p_sum0, sel_keys, meas_keys)
    driver = _compiled_driver(entry, *args)
    t0 = perf_counter()
    with obs_annotate("mwem/sharded"):
        logw, p_sum, traces = driver(*args)
        jax.block_until_ready(p_sum)
    total = perf_counter() - t0

    traces = jax.device_get(traces)
    res.selected = [int(w) for w in traces["winner"]]
    res.n_scored = [int(s) for s in traces["n_scored"]]
    res.overflow_count = int(np.sum(traces["overflow"]))
    res.iter_seconds = [total / cfg.T] * cfg.T
    for _ in range(cfg.T):
        _record_iteration(res.ledger, cfg.mode, cfg.update_rule, cal,
                          c_idx, cfg.margin_slack)
    if cfg.eval_every:
        errs = traces["error"]
        res.errors = [(t, float(errs[t - 1]))
                      for t in range(cfg.eval_every, cfg.T + 1,
                                     cfg.eval_every)]
    res.p_hat = jnp.asarray(jax.device_get(p_sum)) / cfg.T
    res.final_error = float(max_error(jnp.asarray(Q, jnp.float32),
                                      jnp.asarray(h, jnp.float32),
                                      res.p_hat))
    res.telemetry = record_run(
        workload="mwem", driver="sharded", mode=cfg.mode, m=m,
        n_scored=res.n_scored, overflow_count=res.overflow_count,
        total_seconds=total, amortized=True)
    return res


def run_mwem_sharded_batch(
    Q: jax.Array,
    h: jax.Array,
    cfg: MWEMConfig,
    keys: jax.Array,
    mesh=None,
    index=None,
    ledgers: Optional[list] = None,
) -> MWEMBatchResult:
    """B releases through the sharded driver — the mesh counterpart of
    `run_mwem_batch` for the release service's waves.

    Lanes run sequentially, each as one mesh-wide scan dispatch (vmapping a
    shard_map would replicate the whole mesh program per lane); the
    compiled executable is shared across lanes, and per-lane ``ledgers``
    charge each tenant exactly as `run_mwem_batch` does. The result's
    per-run ledger carries one lane's event bundle (the B× composition is
    the caller's contract, DESIGN.md §2).
    """
    from repro.core.workload import as_workload
    from repro.mips.ivf import ShardedIVFIndex

    Q = as_workload(Q).require_dense("run_mwem_sharded_batch")
    m, U = Q.shape
    keys = jnp.asarray(keys)
    B = keys.shape[0]
    if ledgers is not None and len(ledgers) != B:
        raise ValueError(f"ledgers must have one entry per lane "
                         f"({len(ledgers)} != {B})")
    h = jnp.asarray(h, jnp.float32)
    batched_h = h.ndim == 2
    if mesh is None:
        from repro.launch.mesh import make_driver_mesh
        mesh = make_driver_mesh()
    if cfg.mode == "fast" and index is None:
        index = ShardedIVFIndex(Q, n_shards=_data_shards(mesh)[1])
    # place Q on the mesh once — the per-lane device_put then no-ops
    data_axes = _data_shards(mesh)[0]
    Q = jax.device_put(jnp.asarray(Q, jnp.float32),
                       NamedSharding(mesh, P(data_axes, "model")))

    results = []
    t0 = perf_counter()
    for b in range(B):
        lane_ledger = ledgers[b] if ledgers is not None else None
        if ledgers is not None and lane_ledger is None:
            lane_ledger = PrivacyLedger()  # pad lane: charged nowhere
        results.append(run_mwem_sharded(
            Q, h[b] if batched_h else h, cfg, keys[b], mesh=mesh,
            index=index, ledger=lane_ledger))
    total = perf_counter() - t0

    per_run = PrivacyLedger()
    per_run.record_events(*release_cost(cfg, m, U, index=index))
    errors = None
    if cfg.eval_every:
        errors = np.asarray([[e for _, e in r.errors] for r in results])
    # aggregate only (no publish): each lane's run_mwem_sharded already
    # published its own record — re-publishing here would double-count
    telemetry = aggregate_traces(
        workload="mwem", driver="sharded", mode=cfg.mode, m=m,
        n_scored=np.asarray([r.n_scored for r in results]),
        overflow_count=int(sum(r.overflow_count for r in results)),
        total_seconds=total, amortized=True, lanes=B)
    return MWEMBatchResult(
        p_hat=jnp.stack([r.p_hat for r in results]),
        final_errors=np.asarray([r.final_error for r in results]),
        selected=np.asarray([r.selected for r in results]),
        n_scored=np.asarray([r.n_scored for r in results]),
        overflow_counts=np.asarray([r.overflow_count for r in results]),
        errors=errors,
        eval_every=cfg.eval_every,
        total_seconds=total,
        ledger=per_run,
        ledgers=list(ledgers) if ledgers is not None else None,
        telemetry=telemetry,
    )


def build_distributed_mwem_cell(mesh, multi_pod: bool, *, mode: str = "lazy",
                                m: int = 2 ** 24, U: int = 2 ** 14,
                                T: int = 1, fallback: bool = False):
    """Dry-run cell: allocation-free specs for the sharded driver.

    Built on the *real* driver — the cell's fn is `make_mwem_scan` with the
    same body `run_mwem_sharded` executes, so the lowered specs cannot
    drift from what production runs (T=1 keeps the recorded per-device
    numbers per-iteration comparable). The one analysis-only deviation:
    ``fallback`` defaults to False here, dropping the e^{-Ω(√m)}-rare
    overflow-redo branch that a static HLO analyzer would weigh at 1× —
    with it on, the recorded "lazy" FLOPs/wire would be dominated by the
    Θ(m) branch and the exhaustive-vs-lazy §Perf comparison this cell
    exists for would be meaningless. Pass ``fallback=True`` to lower
    exactly what production dispatches."""
    data_axes = ("pod", "data") if multi_pod else ("data",)
    n_data = math.prod(mesh.shape[a] for a in data_axes)
    m_loc = m // n_data
    nlist = 2 * int(math.sqrt(m_loc))
    cap = max(8, math.ceil(2.0 * m_loc / nlist))
    nprobe = 10
    k_loc = max(32, int(math.sqrt(m_loc)))
    tail_cap = 4 * int(math.sqrt(m_loc))
    scale = 50.0
    eta = 0.05

    fn = make_mwem_scan(
        mesh, T=T, m=m, U=U, nlist=nlist, cap=cap, nprobe=nprobe,
        k_loc=k_loc, tail_cap=tail_cap, scale=scale, eta=eta,
        lap_scale=0.01, rule="hardt", mode=mode, multi_pod=multi_pod,
        fallback=fallback)

    ns = lambda *spec: NamedSharding(mesh, P(*spec))  # noqa: E731
    Q = jax.ShapeDtypeStruct((m, U), jnp.float32,
                             sharding=ns(data_axes, "model"))
    cents = jax.ShapeDtypeStruct((n_data, nlist, U), jnp.float32,
                                 sharding=ns(data_axes, None, "model"))
    cells = jax.ShapeDtypeStruct((n_data, nlist, cap), jnp.int32,
                                 sharding=ns(data_axes, None, None))
    logw = jax.ShapeDtypeStruct((U,), jnp.float32, sharding=ns("model"))
    h = jax.ShapeDtypeStruct((U,), jnp.float32, sharding=ns("model"))
    keys = jax.ShapeDtypeStruct((T, 2), jnp.uint32, sharding=ns())

    meta = {"arch": "fastmwem-dist", "shape": f"m{m}_U{U}_{mode}",
            "kind": "mwem_iteration", "mode": mode, "m": m, "U": U,
            "m_loc": m_loc, "nlist": nlist, "cap": cap, "nprobe": nprobe,
            "k_loc": k_loc, "tail_cap": tail_cap, "T": T,
            "fallback": fallback,
            "tokens_per_step": 0, "n_params": m * U, "n_active_params": m * U,
            "multi_pod": multi_pod}
    return fn, (Q, cents, cells, h, logw, logw, keys, keys), meta
