"""Fast scalar-private LP solver (paper §4.1, Algorithm 3).

Feasibility LPs ``Ax ≤ b`` over the simplex ``x ∈ Δ([d])`` in the
scalar-private, low-sensitivity setting: neighboring databases only move
``b`` by ``‖b−b'‖_∞ ≤ Δ_∞`` (A and c public). Each iteration selects the
most-violated constraint privately; the EM score is the inner product

    Q_t(i) = A_i·x − b_i = ⟨A_i ∘ b_i, x ∘ −1⟩

so LazyEM over a k-MIPS index on the concatenated rows ``{A_i ∘ b_i}``
gives O(d√m) expected per-iteration time (Thm 4.1) vs Θ(dm) exhaustive.

Two drivers execute the same iteration (DESIGN.md §6), mirroring the MWEM
engine's architecture exactly:

* **fused** (`solve_scalar_lp_fused`): the whole T-iteration loop is one
  jitted `lax.scan` — the in-graph index probe (`query_in_graph`), LazyEM,
  the `lax.cond` overflow fallback to the exhaustive Gumbel-max, and the
  multiplicative-weights update all stay on device. The per-iteration key
  chain is pre-split through `lp_split_chain`, which walks the host loop's
  exact ``key → (key, k_sel)`` chain, so the two drivers make bitwise the
  same selections (up to XLA float reassociation on exact ties).
* **host** (`driver="host"`): the original Python loop, one dispatch per
  step — the reference for the conformance tier (tests/test_lp_fused.py)
  and the only driver for non-traceable indices (NSW).

`solve_scalar_lp` routes between them (`ScalarLPConfig.driver`);
`solve_lp_batch` vmaps the fused scan over seed lanes (and per-lane ``b``
instances in exact mode) — the dispatch the serving tier's LP waves ride.

Overflow fallback keys: the lazy draw consumes splits of ``k_sel``, so the
exhaustive redo draws from `lazy_em.fallback_key(k_sel)` — a fresh stream,
decorrelated from the failed lazy draw (both drivers, bitwise-aligned).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.accountant import PrivacyLedger, calibrate_eps0
from repro.core.gumbel import gumbel
from repro.core.lazy_em import (default_tail_cap, fallback_key,
                                lazy_em_from_topk)
from repro.mips.base import bind_state, index_state
from repro.obs.clock import perf_counter
from repro.obs.telemetry import MechanismTelemetry, record_run
from repro.obs.trace import annotate as obs_annotate


@dataclass(frozen=True)
class ScalarLPConfig:
    eps: float = 1.0
    delta: float = 1e-3
    alpha: float = 0.5
    delta_inf: float = 0.1        # Δ∞ sensitivity of b
    T: Optional[int] = None       # default 9ρ² log d / α²
    mode: str = "fast"            # "exact" | "fast"
    driver: str = "auto"          # "auto" | "fused" | "host"
    k: Optional[int] = None
    tail_cap: Optional[int] = None
    margin_slack: float = 0.0
    eta: Optional[float] = None


@dataclass
class ScalarLPResult:
    x_bar: jax.Array
    violations: jax.Array          # A x̄ − b
    violated_frac: float           # fraction with A x̄ > b + α
    selected: list = field(default_factory=list)
    n_scored: list = field(default_factory=list)
    overflow_count: int = 0
    iter_seconds: list = field(default_factory=list)
    ledger: PrivacyLedger = field(default_factory=PrivacyLedger)
    telemetry: Optional[MechanismTelemetry] = None  # repro.obs aggregation


@dataclass
class ScalarLPBatchResult:
    """Stacked outputs of `solve_lp_batch` (leading axis = batch lanes)."""

    x_bar: jax.Array              # (B, d)
    violated_fracs: np.ndarray    # (B,)
    selected: np.ndarray          # (B, T)
    n_scored: np.ndarray          # (B, T)
    overflow_counts: np.ndarray   # (B,)
    total_seconds: float = 0.0
    ledger: PrivacyLedger = field(default_factory=PrivacyLedger)  # per run
    ledgers: Optional[list] = None  # per-lane ledgers when the caller passed them
    telemetry: Optional[MechanismTelemetry] = None  # whole-batch aggregation


class _LPCalibration(NamedTuple):
    T: int
    eta: float
    rho: float
    eps0: float
    scale: float      # EM log-space factor ε₀/(2Δ∞)
    k: int
    tail_cap: int


def _scalar_calibrate(A: jax.Array, cfg: ScalarLPConfig) -> _LPCalibration:
    """Per-iteration budget, EM scale and buffer sizes — one point of truth
    shared by both drivers and by `lp_release_cost`, so the cost bundle an
    admission controller previews is exactly what execution records."""
    m, d = A.shape
    rho = float(jnp.max(jnp.abs(A)))
    T = cfg.T or max(1, math.ceil(9.0 * rho * rho * math.log(d) / (cfg.alpha ** 2)))
    eta = cfg.eta if cfg.eta is not None else math.sqrt(math.log(d) / T)
    eps0 = calibrate_eps0(cfg.eps, cfg.delta, T, scheme="lp")
    return _LPCalibration(
        T=T,
        eta=float(eta),
        rho=rho,
        eps0=eps0,
        scale=float(eps0 / (2.0 * cfg.delta_inf)),
        k=cfg.k or max(1, math.ceil(math.sqrt(m))),
        tail_cap=cfg.tail_cap or default_tail_cap(m),
    )


def _check_lp_fast_index(cfg, index, fused: bool, what: str) -> float:
    """Validate the (mode, index, driver) combination; returns the index's
    approximation margin c ≥ 0 (0 in exact mode)."""
    if cfg.mode not in ("exact", "fast"):
        raise ValueError(f"unknown mode {cfg.mode!r}")
    if cfg.mode != "fast":
        return 0.0
    if index is None:
        raise ValueError(f"fast mode requires a k-MIPS index over {what}")
    if fused and not getattr(index, "supports_in_graph", False):
        raise ValueError(
            f"{type(index).__name__} cannot be traced into the fused scan "
            "(supports_in_graph=False); use driver='host'")
    return float(getattr(index, "approx_margin", 0.0))


def _record_lp_iteration(ledger: PrivacyLedger, mode: str, eps0: float,
                         label: str, c_idx: float, margin_slack: float) -> None:
    """Ledger entries for one LP iteration — shared by both drivers and by
    the cost-bundle builders, so fused and host runs compose to identical
    privacy totals and `lp_release_cost` previews exactly them."""
    ledger.record(eps0, 0.0, label)
    if mode == "fast" and c_idx > 0.0 and margin_slack == 0.0:
        ledger.record_approx_slack(c_idx)  # Thm F.2 runtime mode


def scalar_lp_release_cost(A, cfg: ScalarLPConfig, index=None
                           ) -> tuple[list, float, float]:
    """The exact privacy-cost bundle one `solve_scalar_lp*` run records.

    Returns ``(events, gamma, slack)`` built through the same
    `_scalar_calibrate`/`_record_lp_iteration` path the drivers use, so
    ``PrivacyLedger.preview(*scalar_lp_release_cost(...))`` equals the
    post-run ``composed()`` — the LP counterpart of `mwem.release_cost`,
    and the bundle `ReleaseService.submit_lp` admission-gates on.
    """
    A = jnp.asarray(A, jnp.float32)
    m = A.shape[0]
    cal = _scalar_calibrate(A, cfg)
    c_idx = _check_lp_fast_index(cfg, index, fused=False, what="[A_i, b_i]")
    tmp = PrivacyLedger()
    if cfg.mode == "fast":
        tmp.record_index_failure(getattr(index, "failure_mass", 1.0 / m))
    for _ in range(cal.T):
        _record_lp_iteration(tmp, cfg.mode, cal.eps0, "lp_em",
                             c_idx, cfg.margin_slack)
    return tmp.bundle()


def lp_split_chain(key: jax.Array, T: int) -> jax.Array:
    """Pre-split the per-iteration selection keys by walking the LP host
    loops' exact chain (``key → key, k_sel``) as one key-only scan.

    This is THE key chain for both LP solvers: the host loops consume it
    step by step, the fused drivers pre-split it through this helper — one
    point of truth, so cross-driver bitwise selection parity cannot drift
    (the LP analog of `mwem.split_chain`). Returns (T,)-stacked keys.
    """

    def body(carry_key, _):
        carry_key, k_sel = jax.random.split(carry_key)
        return carry_key, k_sel

    _, sel_keys = jax.lax.scan(body, key, None, length=T)
    return sel_keys


def _scalar_scores(A, b, x, scale):
    return (A @ x - b) * scale


def _exact_select_lp_raw(key, A, b, x, scale):
    """Exhaustive EM oracle over the m constraints (Alg. 3 selection)."""
    scores = _scalar_scores(A, b, x, scale)
    g = gumbel(key, scores.shape)
    return jnp.argmax(scores + g).astype(jnp.int32)


_exact_select_lp = jax.jit(_exact_select_lp_raw, static_argnames=("scale",))


def _lp_step(logX, A_row, eta: float, rho: float):
    """One MWU step of the primal player x ∈ Δ([d])."""
    logX = logX - (eta / rho) * A_row
    logX = logX - jnp.max(logX)
    return logX, jax.nn.softmax(logX)


_lp_update = jax.jit(_lp_step, static_argnames=("eta", "rho"))


# ---------------------------------------------------------------------------
# Fused on-device driver (DESIGN.md §6)
# ---------------------------------------------------------------------------

def _scalar_core(A: jax.Array, b: jax.Array, key: jax.Array, *, query_fn,
                 T: int, mode: str, eta: float, rho: float, scale: float,
                 k: int, tail_cap: int, margin_slack: float):
    """The whole Alg. 3 loop as one `lax.scan` — zero host round-trips.

    The probe vector ``[x, −1]`` and the concatenated score rows
    ``Ab = [A | b]`` are built in-graph, so the scan body scores tail
    candidates with one (t, d+1) gather-matvec (the §4.1 identity
    ``Q_t(i) = ⟨[A_i, b_i], [x, −1]⟩``) and never re-touches A and b
    separately. Under `solve_lp_batch`'s vmap, per-lane ``b`` instances
    therefore get their own in-graph Ab for free.
    """
    m, d = A.shape
    Ab = jnp.concatenate([A, b[:, None]], axis=1)
    sel_keys = lp_split_chain(key, T)

    def body(carry, k_sel):
        logX, x, x_sum = carry
        if mode == "exact":
            sel = _exact_select_lp_raw(k_sel, A, b, x, scale)
            n_scored = jnp.int32(m)
            tail_count = jnp.int32(0)
            overflow = jnp.bool_(False)
        else:
            xq = jnp.concatenate([x, -jnp.ones((1,), x.dtype)])
            idx, raw = query_fn(xq, k)
            out = lazy_em_from_topk(
                k_sel, idx, raw * scale, m,
                score_fn=lambda i: (Ab[i] @ xq) * scale,
                tail_cap=tail_cap,
                margin_slack=margin_slack * scale if margin_slack else 0.0,
            )
            # In-graph fallback: on tail-buffer overflow redo the step with
            # the exhaustive Gumbel-max from a *fresh* key stream
            # (`fallback_key`) — the lazy draw already consumed splits of
            # k_sel, and the host driver folds in the same tag.
            sel = jax.lax.cond(
                out.overflow,
                lambda _: _exact_select_lp_raw(fallback_key(k_sel), A, b, x,
                                               scale),
                lambda _: out.index.astype(jnp.int32),
                operand=None,
            )
            n_scored = jnp.where(out.overflow, jnp.int32(m), out.n_scored)
            tail_count = out.tail_count
            overflow = out.overflow
        logX, x = _lp_step(logX, A[sel], eta, rho)
        return (logX, x, x_sum + x), (sel, n_scored, tail_count, overflow)

    init = (jnp.zeros((d,), jnp.float32),
            jnp.full((d,), 1.0 / d, jnp.float32),
            jnp.zeros((d,), jnp.float32))
    (_, _, x_sum), traces = jax.lax.scan(body, init, sel_keys)
    return x_sum / T, traces


_LP_EXACT_DRIVER_CACHE: dict = {}


def _lp_fused_driver(index, core, statics: dict, tag: str,
                     batch_axes=None):
    """Build (or fetch) the jitted fused LP driver for an (index, config)
    pair — the LP counterpart of `mwem._fused_driver`. Compiled drivers are
    cached on the index instance (module-level for ``mode="exact"``);
    ``batch_axes`` is a vmap ``in_axes`` tuple for the batched driver. The
    driver's last argument is ``index_state(index)``."""
    cache = (_LP_EXACT_DRIVER_CACHE if index is None
             else index.__dict__.setdefault("_lp_fused_driver_cache", {}))
    ck = (tag, tuple(sorted(statics.items())), batch_axes,
          getattr(index, "_use_pallas", None))
    entry = cache.get(ck)
    if entry is None:
        # the index's tables are the driver's last argument, never
        # constants captured from the closure
        def fn(*args):
            *arrays, state = args
            bound = bind_state(index, state)
            return core(*arrays, query_fn=None if bound is None
                        else bound.query_in_graph, **statics)

        if batch_axes is not None:
            fn = jax.vmap(fn, in_axes=batch_axes + (None,))
        entry = (jax.jit(fn), {})
        cache[ck] = entry
    return entry


def _scalar_statics(cfg: ScalarLPConfig, cal: _LPCalibration) -> dict:
    return dict(T=cal.T, mode=cfg.mode, eta=cal.eta, rho=cal.rho,
                scale=cal.scale, k=cal.k, tail_cap=cal.tail_cap,
                margin_slack=cfg.margin_slack)


def solve_scalar_lp_fused(
    A: jax.Array,
    b: jax.Array,
    cfg: ScalarLPConfig,
    key: jax.Array,
    index=None,
    ledger: Optional[PrivacyLedger] = None,
) -> ScalarLPResult:
    """Run Alg. 3 as a single fused scan dispatch.

    Exactly one device→host transfer moves the stacked per-iteration traces
    (`selected`, `n_scored`, tail counts, overflow flags) back.
    ``iter_seconds`` holds the amortized *execution* wall-clock per
    iteration (total / T): trace+compile happen outside the timed region
    via a cached AOT executable.
    """
    from repro.core.mwem import _compiled_driver

    A = jnp.asarray(A, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    m, _ = A.shape
    cal = _scalar_calibrate(A, cfg)
    c_idx = _check_lp_fast_index(cfg, index, fused=True, what="[A_i, b_i]")

    res = ScalarLPResult(x_bar=None, violations=None, violated_frac=float("nan"),
                         ledger=ledger if ledger is not None else PrivacyLedger())
    if cfg.mode == "fast":
        res.ledger.record_index_failure(getattr(index, "failure_mass", 1.0 / m))

    entry = _lp_fused_driver(index if cfg.mode == "fast" else None,
                             _scalar_core, _scalar_statics(cfg, cal), "scalar")
    args = (A, b, key, index_state(index if cfg.mode == "fast" else None))
    driver = _compiled_driver(entry, *args)
    t0 = perf_counter()
    with obs_annotate("lp_scalar/fused"):
        x_bar, traces = driver(*args)
        jax.block_until_ready(x_bar)
    total = perf_counter() - t0

    sel_t, n_scored_t, _tail_t, over_t = jax.device_get(traces)
    res.selected = [int(s) for s in sel_t]
    res.n_scored = [int(s) for s in n_scored_t]
    res.overflow_count = int(np.sum(over_t))
    res.iter_seconds = [total / cal.T] * cal.T
    res.telemetry = record_run(
        workload="lp_scalar", driver="fused", mode=cfg.mode, m=m,
        n_scored=n_scored_t, overflow_count=res.overflow_count,
        total_seconds=total, amortized=True)
    for _ in range(cal.T):
        _record_lp_iteration(res.ledger, cfg.mode, cal.eps0, "lp_em",
                             c_idx, cfg.margin_slack)
    res.x_bar = x_bar
    res.violations = A @ x_bar - b
    res.violated_frac = float(jnp.mean(res.violations > cfg.alpha))
    return res


@dataclass
class LPPendingBatch:
    """Handle for an in-flight `launch_lp_batch` dispatch — the LP
    counterpart of `mwem.MWEMPendingBatch`. Device buffers are futures
    until `finish_lp_batch` blocks on them."""

    x_bar: jax.Array
    traces: tuple
    t0: float
    A: jax.Array
    b: jax.Array
    batched_b: bool
    cfg: ScalarLPConfig
    cal: _LPCalibration
    c_idx: float
    index: object
    lanes: int


def launch_lp_batch(
    A: jax.Array,
    b: jax.Array,
    cfg: ScalarLPConfig,
    keys: jax.Array,
    index=None,
) -> LPPendingBatch:
    """Dispatch one batched LP wave asynchronously — the launch half of
    `solve_lp_batch`. ``solve_lp_batch(...)`` is exactly
    ``finish_lp_batch(launch_lp_batch(...))``."""
    from repro.core.mwem import _compiled_driver

    if cfg.driver == "host":
        raise ValueError("solve_lp_batch always uses the fused driver; "
                         "loop solve_scalar_lp(..., driver='host') for host runs")
    A = jnp.asarray(A, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    keys = jnp.asarray(keys)
    B = keys.shape[0]
    batched_b = b.ndim == 2
    if batched_b and cfg.mode == "fast":
        raise ValueError(
            "per-lane b instances require mode='exact': the k-MIPS index "
            "rows [A_i, b_i] embed a single b")
    cal = _scalar_calibrate(A, cfg)
    c_idx = _check_lp_fast_index(cfg, index, fused=True, what="[A_i, b_i]")

    entry = _lp_fused_driver(index if cfg.mode == "fast" else None,
                             _scalar_core, _scalar_statics(cfg, cal), "scalar",
                             batch_axes=(None, 0 if batched_b else None, 0))
    args = (A, b, keys, index_state(index if cfg.mode == "fast" else None))
    driver = _compiled_driver(entry, *args)
    t0 = perf_counter()
    with obs_annotate("lp_scalar/batch"):
        x_bar, traces = driver(*args)
    return LPPendingBatch(x_bar=x_bar, traces=traces, t0=t0, A=A, b=b,
                          batched_b=batched_b, cfg=cfg, cal=cal, c_idx=c_idx,
                          index=index, lanes=B)


def finish_lp_batch(pending: LPPendingBatch,
                    ledgers: Optional[list] = None) -> ScalarLPBatchResult:
    """Block on a launched LP wave and assemble its `ScalarLPBatchResult` —
    the finish half of `solve_lp_batch`."""
    A, b, cfg, cal = pending.A, pending.b, pending.cfg, pending.cal
    index, B, batched_b = pending.index, pending.lanes, pending.batched_b
    m, _ = A.shape
    if ledgers is not None and len(ledgers) != B:
        raise ValueError(f"ledgers must have one entry per lane "
                         f"({len(ledgers)} != {B})")
    with obs_annotate("lp_scalar/batch/finish"):
        x_bar, traces = pending.x_bar, pending.traces
        jax.block_until_ready(x_bar)
    total = perf_counter() - pending.t0

    viol = x_bar @ A.T - (b if batched_b else b[None, :])   # (B, m)
    violated_fracs = np.asarray(jnp.mean(viol > cfg.alpha, axis=1))

    ledger = PrivacyLedger()
    if cfg.mode == "fast":
        ledger.record_index_failure(getattr(index, "failure_mass", 1.0 / m))
    for _ in range(cal.T):
        _record_lp_iteration(ledger, cfg.mode, cal.eps0, "lp_em",
                             pending.c_idx, cfg.margin_slack)
    if ledgers is not None:
        for lane in ledgers:
            if lane is not None:
                lane.record_events(ledger.events, ledger.index_failure_mass,
                                   ledger.approx_slack)

    traces = jax.device_get(traces)
    telemetry = record_run(
        workload="lp_scalar", driver="fused", mode=cfg.mode, m=m,
        n_scored=np.asarray(traces[1]),
        overflow_count=int(np.asarray(traces[3]).sum()),
        total_seconds=total, amortized=True, lanes=B)
    return ScalarLPBatchResult(
        x_bar=x_bar,
        violated_fracs=violated_fracs,
        selected=np.asarray(traces[0]),
        n_scored=np.asarray(traces[1]),
        overflow_counts=np.asarray(traces[3]).sum(axis=1),
        total_seconds=total,
        ledger=ledger,
        ledgers=list(ledgers) if ledgers is not None else None,
        telemetry=telemetry,
    )


def aot_compile_lp_batch(A, b, cfg: ScalarLPConfig, lanes: int,
                         index=None) -> bool:
    """Populate the batched LP driver's AOT executable cache for a
    ``lanes``-wide wave without dispatching — the LP counterpart of
    `mwem.aot_compile_batch`. Returns True when a new executable was
    compiled for this lane count."""
    from repro.core.mwem import _compiled_driver

    if cfg.driver == "host":
        raise ValueError("solve_lp_batch always uses the fused driver; "
                         "loop solve_scalar_lp(..., driver='host') for host runs")
    A = jnp.asarray(A, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    cal = _scalar_calibrate(A, cfg)
    _check_lp_fast_index(cfg, index, fused=True, what="[A_i, b_i]")
    entry = _lp_fused_driver(index if cfg.mode == "fast" else None,
                             _scalar_core, _scalar_statics(cfg, cal), "scalar",
                             batch_axes=(None, None, 0))
    keys = jnp.stack([jax.random.PRNGKey(0)] * lanes)
    n_before = len(entry[1])
    _compiled_driver(entry, A, b, keys,
                     index_state(index if cfg.mode == "fast" else None))
    return len(entry[1]) > n_before


def solve_lp_batch(
    A: jax.Array,
    b: jax.Array,
    cfg: ScalarLPConfig,
    keys: jax.Array,
    index=None,
    ledgers: Optional[list] = None,
) -> ScalarLPBatchResult:
    """Vmapped fused scan over a batch of lanes — the LP serving dispatch.

    Args:
      b: shared ``(m,)`` constraint bounds, or ``(B, m)`` per-lane
        instances (exact mode only: the fast probe's k-MIPS rows
        ``[A_i, b_i]`` embed one ``b``, so per-lane instances would probe a
        stale index).
      keys: (B,)-stacked PRNG keys; each lane reproduces exactly what
        `solve_scalar_lp_fused` produces for that key.
      ledgers: optional list of B `PrivacyLedger`s, one per lane — each
        receives that lane's full event bundle (`scalar_lp_release_cost`),
        the same per-tenant charging contract as `run_mwem_batch`.
        ``None`` entries skip a lane (padding slots).

    The result ledger is *per run*; serving B lanes spends B× the budget,
    accounted by the per-lane ``ledgers`` (DESIGN.md §2 contract). Batching
    is fused-only (``driver="host"`` raises). Note the overflow-fallback
    `lax.cond` lowers to a select under vmap, so every batched iteration
    pays the exhaustive branch — same caveat as `run_mwem_batch`.
    """
    if cfg.driver == "host":
        raise ValueError("solve_lp_batch always uses the fused driver; "
                         "loop solve_scalar_lp(..., driver='host') for host runs")
    B = jnp.asarray(keys).shape[0]
    if ledgers is not None and len(ledgers) != B:
        raise ValueError(f"ledgers must have one entry per lane "
                         f"({len(ledgers)} != {B})")
    return finish_lp_batch(launch_lp_batch(A, b, cfg, keys, index=index),
                           ledgers=ledgers)


# ---------------------------------------------------------------------------
# Host-loop driver (reference / non-traceable indices)
# ---------------------------------------------------------------------------

def _solve_scalar_lp_host(
    A: jax.Array,
    b: jax.Array,
    cfg: ScalarLPConfig,
    key: jax.Array,
    index=None,
    ledger: Optional[PrivacyLedger] = None,
) -> ScalarLPResult:
    """One jit dispatch per step; `bool(out.overflow)` syncs to the host."""
    m, d = A.shape
    cal = _scalar_calibrate(A, cfg)
    c_idx = _check_lp_fast_index(cfg, index, fused=False, what="[A_i, b_i]")

    res = ScalarLPResult(x_bar=None, violations=None, violated_frac=float("nan"),
                         ledger=ledger if ledger is not None else PrivacyLedger())
    if cfg.mode == "fast":
        res.ledger.record_index_failure(getattr(index, "failure_mass", 1.0 / m))

        Ab = jnp.concatenate([A, b[:, None]], axis=1)  # for tail score gathers

        @jax.jit
        def fast_select(key, topk_idx, topk_scores, xq):
            return lazy_em_from_topk(
                key, topk_idx, topk_scores * cal.scale, m,
                score_fn=lambda idx: (Ab[idx] @ xq) * cal.scale,
                tail_cap=cal.tail_cap,
                margin_slack=(cfg.margin_slack * cal.scale
                              if cfg.margin_slack else 0.0),
            )

    logX = jnp.zeros((d,), jnp.float32)
    x = jnp.full((d,), 1.0 / d, jnp.float32)
    x_sum = jnp.zeros((d,), jnp.float32)

    for _ in range(cal.T):
        key, k_sel = jax.random.split(key)
        t0 = perf_counter()
        if cfg.mode == "exact":
            sel = int(_exact_select_lp(k_sel, A, b, x, cal.scale))
            res.n_scored.append(m)
        else:
            xq = jnp.concatenate([x, -jnp.ones((1,), x.dtype)])
            idx, raw = index.query(xq, cal.k)
            out = fast_select(k_sel, idx, raw, xq)
            if bool(out.overflow):
                # fresh-stream redo, bitwise-matching the fused lax.cond
                sel = int(_exact_select_lp(fallback_key(k_sel), A, b, x,
                                           cal.scale))
                res.overflow_count += 1
                res.n_scored.append(m)
            else:
                sel = int(out.index)
                res.n_scored.append(int(out.n_scored))
        _record_lp_iteration(res.ledger, cfg.mode, cal.eps0, "lp_em",
                             c_idx, cfg.margin_slack)
        logX, x = _lp_update(logX, A[sel], cal.eta, cal.rho)
        x_sum = x_sum + x
        jax.block_until_ready(x)
        res.iter_seconds.append(perf_counter() - t0)
        res.selected.append(sel)

    x_bar = x_sum / cal.T
    res.x_bar = x_bar
    res.violations = A @ x_bar - b
    res.violated_frac = float(jnp.mean(res.violations > cfg.alpha))
    res.telemetry = record_run(
        workload="lp_scalar", driver="host", mode=cfg.mode, m=m,
        n_scored=res.n_scored, overflow_count=res.overflow_count,
        total_seconds=sum(res.iter_seconds), amortized=False)
    return res


def _resolve_lp_driver(cfg, index) -> str:
    """Shared auto-routing for both LP solvers, mirroring `run_mwem`:
    fuse whenever the selection is traceable, fall back to the host loop
    for host-only indices (NSW)."""
    if cfg.driver not in ("auto", "fused", "host"):
        raise ValueError(f"unknown driver {cfg.driver!r}")
    if cfg.driver != "auto":
        return cfg.driver
    if cfg.mode == "exact":
        return "fused"
    if index is not None and getattr(index, "supports_in_graph", False):
        return "fused"
    return "host"


def solve_scalar_lp(
    A: jax.Array,
    b: jax.Array,
    cfg: ScalarLPConfig,
    key: jax.Array,
    index=None,
    ledger: Optional[PrivacyLedger] = None,
) -> ScalarLPResult:
    """Algorithm 3. ``index`` must be built on rows ``[A_i, b_i] ∈ R^{d+1}``
    (`mips.lp_scalar_rows`); routes between the fused scan and the host
    loop via ``cfg.driver``."""
    if _resolve_lp_driver(cfg, index) == "fused":
        return solve_scalar_lp_fused(A, b, cfg, key, index=index, ledger=ledger)
    return _solve_scalar_lp_host(A, b, cfg, key, index=index, ledger=ledger)
