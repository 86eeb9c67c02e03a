"""Constraint-private LPs via dense MWU on the dual (paper §4.2, Thm 4.4).

Packing/covering LPs ``max c^T x s.t. Ax ≤ b`` where neighboring databases
differ by one *constraint row*. The dual player maintains a 1/s-dense
distribution ``y`` over constraints (Bregman-projected after each MWU step,
Lemma A.3 bounds the sensitivity); the primal oracle picks the vertex
``v_j = (OPT/c_j)·e_j`` of ``K_OPT`` minimizing expected violation, i.e.
maximizes ``⟨y, N_j⟩`` with the *preprocessed* vectors

    N_j = −(OPT/c_j) · A[:, j]  ∈ R^m,  j ∈ [d].

LazyEM over a k-MIPS index on {N_j} gives O(m√d) per-iteration time instead
of O(md) — the large-width regime of Thm 4.4.

Like the scalar solver (and the MWEM engine it mirrors), two drivers execute
the same iteration: `solve_constraint_private_lp_fused` runs the whole
T-iteration loop as one jitted `lax.scan` — in-graph index probe, LazyEM,
`lax.cond` overflow fallback (fresh `fallback_key` stream), the vertex
pick, and the Bregman projection all on device — while ``driver="host"``
keeps the reference Python loop. Both consume the identical `lp_split_chain`
key chain, so they are bitwise interchangeable (tests/test_lp_fused.py).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.accountant import PrivacyLedger
from repro.core.bregman import bregman_project_dense
from repro.core.gumbel import gumbel
from repro.core.lazy_em import (default_tail_cap, fallback_key,
                                lazy_em_from_topk)
from repro.core.lp_scalar import (ScalarLPConfig, _check_lp_fast_index,
                                  _lp_fused_driver, _record_lp_iteration,
                                  _resolve_lp_driver, lp_split_chain,
                                  scalar_lp_release_cost)
from repro.mips.base import index_state
from repro.obs.clock import perf_counter
from repro.obs.telemetry import MechanismTelemetry, record_run
from repro.obs.trace import annotate as obs_annotate


@dataclass(frozen=True)
class DualLPConfig:
    eps: float = 1.0
    delta: float = 1e-3
    alpha: float = 0.5
    s: int = 16                  # density parameter: ≤ s−1 constraints may violate
    T: int = 200
    mode: str = "fast"           # "exact" | "fast"
    driver: str = "auto"         # "auto" | "fused" | "host"
    k: Optional[int] = None
    tail_cap: Optional[int] = None
    margin_slack: float = 0.0
    eta: Optional[float] = None


@dataclass
class DualLPResult:
    x_bar: jax.Array
    violations: jax.Array
    n_violated: int              # constraints with A x̄ > b + α
    selected: list = field(default_factory=list)
    n_scored: list = field(default_factory=list)
    overflow_count: int = 0
    iter_seconds: list = field(default_factory=list)
    ledger: PrivacyLedger = field(default_factory=PrivacyLedger)
    telemetry: Optional[MechanismTelemetry] = None  # repro.obs aggregation


class _DualCalibration(NamedTuple):
    T: int
    eta: float
    rho: float
    eps_prime: float
    scale: float
    k: int
    tail_cap: int


def _dual_eps_prime(cfg: DualLPConfig) -> float:
    """Per-iteration budget ε′ = ε/√(2T ln 1/δ) — cfg-only, so the cost
    bundle (`dual_lp_release_cost`) and the drivers (`_dual_calibrate`)
    cannot drift apart."""
    return cfg.eps / math.sqrt(2.0 * cfg.T * math.log(1.0 / cfg.delta))


def _dual_calibrate(A, b, c, opt: float, cfg: DualLPConfig) -> _DualCalibration:
    """Per-iteration budget and scales — one point of truth shared by both
    drivers and by `dual_lp_release_cost` (the admission contract)."""
    m, d = A.shape
    c_min = float(jnp.min(c))
    b_max = float(jnp.max(b))
    rho = max(opt / c_min - b_max, 1e-6)   # §G width
    T = cfg.T
    eta = cfg.eta if cfg.eta is not None else min(0.5, math.sqrt(math.log(m) / T))
    eps_prime = _dual_eps_prime(cfg)
    sensitivity = 3.0 * opt / (c_min * cfg.s)  # §G: y moves ≤ 2/s, one row add
    return _DualCalibration(
        T=T,
        eta=float(eta),
        rho=float(rho),
        eps_prime=eps_prime,
        scale=float(eps_prime / (2.0 * sensitivity)),
        k=cfg.k or max(1, math.ceil(math.sqrt(d))),
        tail_cap=cfg.tail_cap or default_tail_cap(d),
    )


def dual_lp_release_cost(A, cfg: DualLPConfig, index=None
                         ) -> tuple[list, float, float]:
    """The exact privacy-cost bundle one `solve_constraint_private_lp*` run
    records — ``(events, gamma, slack)``; ``PrivacyLedger.preview`` of it
    equals the post-run ``composed()`` in both composition modes.

    Only budget-relevant calibration is needed: ε′ depends on cfg alone and
    the failure mass defaults to 1/d, so ``A`` supplies shapes only.
    """
    d = jnp.asarray(A).shape[1]
    eps_prime = _dual_eps_prime(cfg)
    c_idx = _check_lp_fast_index(cfg, index, fused=False, what="N_j rows")
    tmp = PrivacyLedger()
    if cfg.mode == "fast":
        tmp.record_index_failure(getattr(index, "failure_mass", 1.0 / d))
    for _ in range(cfg.T):
        _record_lp_iteration(tmp, cfg.mode, eps_prime, "dual_oracle",
                             c_idx, cfg.margin_slack)
    return tmp.bundle()


def lp_release_cost(cfg, A, index=None) -> tuple[list, float, float]:
    """Cost bundle for either LP solver, dispatched on the config type —
    the single admission-control entry point (`ReleaseService.submit_lp`,
    `AdmissionController.check_lp`)."""
    if isinstance(cfg, ScalarLPConfig):
        return scalar_lp_release_cost(A, cfg, index=index)
    if isinstance(cfg, DualLPConfig):
        return dual_lp_release_cost(A, cfg, index=index)
    raise TypeError(f"unknown LP config type {type(cfg).__name__}")


def _exact_select_dual_raw(key, N, y, scale):
    """Exhaustive EM oracle over the d vertices: score_j = ⟨y, N_j⟩."""
    scores = (N @ y) * scale     # N is (d, m)
    g = gumbel(key, scores.shape)
    return jnp.argmax(scores + g).astype(jnp.int32)


_exact_select_dual = jax.jit(_exact_select_dual_raw, static_argnames=("scale",))


def _vertex_raw(j, c, opt: float, d: int):
    """The K_OPT vertex v_j = (OPT/c_j)·e_j, built in-graph so host and
    fused drivers round identically."""
    return jnp.zeros((d,), jnp.float32).at[j].set(opt / c[j])


_vertex = jax.jit(_vertex_raw, static_argnames=("opt", "d"))


def _dual_step(logY, x_vertex, A, b, eta: float, rho: float, s: int):
    """One MWU step of the constraint player: upweight violated constraints
    (loss (b − A x*)/ρ), then Bregman-project onto the 1/s-dense simplex."""
    loss = (b - A @ x_vertex) / rho
    logY_new = logY - eta * loss
    logY_new = logY_new - jnp.max(logY_new)
    y = bregman_project_dense(jnp.exp(logY_new), float(s))
    return logY_new, y


_dual_update = jax.jit(_dual_step, static_argnames=("eta", "rho", "s"))


# ---------------------------------------------------------------------------
# Fused on-device driver (DESIGN.md §6)
# ---------------------------------------------------------------------------

def _dual_core(A: jax.Array, b: jax.Array, c: jax.Array, N: jax.Array,
               key: jax.Array, *, query_fn, T: int, mode: str, eta: float,
               rho: float, s: int, opt: float, scale: float, k: int,
               tail_cap: int, margin_slack: float):
    """The whole §4.2 dual loop as one `lax.scan` — selection, the overflow
    fallback, the vertex pick, and the Bregman projection stay on device;
    the projection's piecewise-linear solve (`bregman_project_dense`) is
    sort+cumsum+argmax, so it traces straight into the scan body."""
    m, d = A.shape
    sel_keys = lp_split_chain(key, T)

    def body(carry, k_sel):
        logY, y, x_sum = carry
        if mode == "exact":
            j = _exact_select_dual_raw(k_sel, N, y, scale)
            n_scored = jnp.int32(d)
            tail_count = jnp.int32(0)
            overflow = jnp.bool_(False)
        else:
            idx, raw = query_fn(y, k)
            out = lazy_em_from_topk(
                k_sel, idx, raw * scale, d,
                score_fn=lambda i: (N[i] @ y) * scale,
                tail_cap=tail_cap,
                margin_slack=margin_slack * scale if margin_slack else 0.0,
            )
            j = jax.lax.cond(
                out.overflow,
                lambda _: _exact_select_dual_raw(fallback_key(k_sel), N, y,
                                                 scale),
                lambda _: out.index.astype(jnp.int32),
                operand=None,
            )
            n_scored = jnp.where(out.overflow, jnp.int32(d), out.n_scored)
            tail_count = out.tail_count
            overflow = out.overflow
        x_vertex = _vertex_raw(j, c, opt, d)
        logY, y = _dual_step(logY, x_vertex, A, b, eta, rho, s)
        return (logY, y, x_sum + x_vertex), (j, n_scored, tail_count, overflow)

    init = (jnp.zeros((m,), jnp.float32),
            jnp.full((m,), 1.0 / m, jnp.float32),
            jnp.zeros((d,), jnp.float32))
    (_, _, x_sum), traces = jax.lax.scan(body, init, sel_keys)
    return x_sum / T, traces


def _dual_statics(cfg: DualLPConfig, cal: _DualCalibration, opt: float) -> dict:
    return dict(T=cal.T, mode=cfg.mode, eta=cal.eta, rho=cal.rho,
                s=int(cfg.s), opt=float(opt), scale=cal.scale, k=cal.k,
                tail_cap=cal.tail_cap, margin_slack=cfg.margin_slack)


def solve_constraint_private_lp_fused(
    A: jax.Array,
    b: jax.Array,
    c: jax.Array,
    opt: float,
    cfg: DualLPConfig,
    key: jax.Array,
    index=None,
    ledger: Optional[PrivacyLedger] = None,
) -> DualLPResult:
    """Run the dense-MWU dual solver as a single fused scan dispatch."""
    from repro.core.mwem import _compiled_driver

    A = jnp.asarray(A, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    c = jnp.asarray(c, jnp.float32)
    m, d = A.shape
    cal = _dual_calibrate(A, b, c, opt, cfg)
    c_idx = _check_lp_fast_index(cfg, index, fused=True, what="N_j rows")

    res = DualLPResult(x_bar=None, violations=None, n_violated=-1,
                       ledger=ledger if ledger is not None else PrivacyLedger())
    if cfg.mode == "fast":
        res.ledger.record_index_failure(getattr(index, "failure_mass", 1.0 / d))

    N = -(opt / c)[:, None] * A.T          # (d, m): N_j as rows
    entry = _lp_fused_driver(index if cfg.mode == "fast" else None,
                             _dual_core, _dual_statics(cfg, cal, opt), "dual")
    args = (A, b, c, N, key,
            index_state(index if cfg.mode == "fast" else None))
    driver = _compiled_driver(entry, *args)
    t0 = perf_counter()
    with obs_annotate("lp_dual/fused"):
        x_bar, traces = driver(*args)
        jax.block_until_ready(x_bar)
    total = perf_counter() - t0

    sel_t, n_scored_t, _tail_t, over_t = jax.device_get(traces)
    res.selected = [int(s) for s in sel_t]
    res.n_scored = [int(s) for s in n_scored_t]
    res.overflow_count = int(np.sum(over_t))
    res.iter_seconds = [total / cal.T] * cal.T
    # the dual oracle scores the d vertices {N_j}, so d is this
    # mechanism's candidate-set size ("m" in telemetry terms)
    res.telemetry = record_run(
        workload="lp_dual", driver="fused", mode=cfg.mode, m=d,
        n_scored=n_scored_t, overflow_count=res.overflow_count,
        total_seconds=total, amortized=True)
    for _ in range(cal.T):
        _record_lp_iteration(res.ledger, cfg.mode, cal.eps_prime,
                             "dual_oracle", c_idx, cfg.margin_slack)
    res.x_bar = x_bar
    res.violations = A @ x_bar - b
    res.n_violated = int(jnp.sum(res.violations > cfg.alpha))
    return res


# ---------------------------------------------------------------------------
# Host-loop driver (reference / non-traceable indices)
# ---------------------------------------------------------------------------

def _solve_constraint_private_lp_host(
    A: jax.Array,
    b: jax.Array,
    c: jax.Array,
    opt: float,
    cfg: DualLPConfig,
    key: jax.Array,
    index=None,
    ledger: Optional[PrivacyLedger] = None,
) -> DualLPResult:
    """One jit dispatch per step; `bool(out.overflow)` syncs to the host."""
    m, d = A.shape
    N = -(opt / c)[:, None] * A.T          # (d, m): N_j as rows
    cal = _dual_calibrate(A, b, c, opt, cfg)
    c_idx = _check_lp_fast_index(cfg, index, fused=False, what="N_j rows")

    res = DualLPResult(x_bar=None, violations=None, n_violated=-1,
                       ledger=ledger if ledger is not None else PrivacyLedger())
    if cfg.mode == "fast":
        res.ledger.record_index_failure(getattr(index, "failure_mass", 1.0 / d))

        @jax.jit
        def fast_select(key, topk_idx, topk_scores, y):
            return lazy_em_from_topk(
                key, topk_idx, topk_scores * cal.scale, d,
                score_fn=lambda idx: (N[idx] @ y) * cal.scale,
                tail_cap=cal.tail_cap,
                margin_slack=(cfg.margin_slack * cal.scale
                              if cfg.margin_slack else 0.0),
            )

    logY = jnp.zeros((m,), jnp.float32)
    y = jnp.full((m,), 1.0 / m, jnp.float32)
    x_sum = jnp.zeros((d,), jnp.float32)

    for _ in range(cal.T):
        key, k_sel = jax.random.split(key)
        t0 = perf_counter()
        if cfg.mode == "exact":
            j = int(_exact_select_dual(k_sel, N, y, cal.scale))
            res.n_scored.append(d)
        else:
            idx, raw = index.query(y, cal.k)
            out = fast_select(k_sel, idx, raw, y)
            if bool(out.overflow):
                # fresh-stream redo, bitwise-matching the fused lax.cond
                j = int(_exact_select_dual(fallback_key(k_sel), N, y,
                                           cal.scale))
                res.overflow_count += 1
                res.n_scored.append(d)
            else:
                j = int(out.index)
                res.n_scored.append(int(out.n_scored))
        _record_lp_iteration(res.ledger, cfg.mode, cal.eps_prime,
                             "dual_oracle", c_idx, cfg.margin_slack)
        x_vertex = _vertex(jnp.int32(j), c, float(opt), d)
        x_sum = x_sum + x_vertex
        logY, y = _dual_update(logY, x_vertex, A, b, cal.eta, cal.rho,
                               int(cfg.s))
        jax.block_until_ready(y)
        res.iter_seconds.append(perf_counter() - t0)
        res.selected.append(j)

    x_bar = x_sum / cal.T
    res.x_bar = x_bar
    res.violations = A @ x_bar - b
    res.n_violated = int(jnp.sum(res.violations > cfg.alpha))
    res.telemetry = record_run(
        workload="lp_dual", driver="host", mode=cfg.mode, m=d,
        n_scored=res.n_scored, overflow_count=res.overflow_count,
        total_seconds=sum(res.iter_seconds), amortized=False)
    return res


def solve_constraint_private_lp(
    A: jax.Array,
    b: jax.Array,
    c: jax.Array,
    opt: float,
    cfg: DualLPConfig,
    key: jax.Array,
    index=None,
    ledger: Optional[PrivacyLedger] = None,
) -> DualLPResult:
    """Dense-MWU dual solver. ``index`` must be built on rows of N (d, m)
    (`mips.lp_dual_rows`); routes between the fused scan and the host loop
    via ``cfg.driver``."""
    if _resolve_lp_driver(cfg, index) == "fused":
        return solve_constraint_private_lp_fused(A, b, c, opt, cfg, key,
                                                 index=index, ledger=ledger)
    return _solve_constraint_private_lp_host(A, b, c, opt, cfg, key,
                                             index=index, ledger=ledger)
