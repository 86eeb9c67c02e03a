"""MWEM (Alg. 1) and Fast-MWEM (Alg. 2) for private linear query release.

The engine is written so that *the only difference* between classic MWEM and
Fast-MWEM is the private-selection oracle — exhaustive EM vs LazyEM over a
k-MIPS index — exactly the surface the paper modifies. Everything else
(multiplicative-weights update, accounting, output averaging) is shared.

Two drivers execute the same iteration (DESIGN.md §2):

* **fused** (`run_mwem_fused`): the whole T-iteration loop is one jitted
  `jax.lax.scan` — selection, the overflow fallback (`lax.cond` to the
  exhaustive Gumbel-max), and the MW update all stay on device; per-iteration
  traces come back as stacked scan outputs in a single transfer. Requires an
  index whose `query(v, k)` is traceable (`supports_in_graph`).
* **host** (`driver="host"`): the original Python loop, one dispatch per
  step. Retained for indices whose search cannot be traced into a scan
  and as the reference for equivalence tests (every built-in index —
  flat/IVF/LSH/NSW — now traces, so auto-routing only lands here for
  third-party indices without ``supports_in_graph``).
* **sharded** (`repro.core.distributed.run_mwem_sharded`, DESIGN.md §4):
  the same scan shard-mapped over a device mesh — Q rows over the data
  axes, the weight state over "model", per-shard IVF selection. Selected
  automatically when more than one device is visible and the workload can
  shard.

`run_mwem` routes between them (`MWEMConfig.driver`); `run_mwem_batch` vmaps
the fused scan over a batch of seeds (and optionally histograms) for
replicated/ensemble release.

Implementation notes:
* weights live in log-space (`log_w`); the multiplicative update is additive
  and `p = softmax(log_w)` — numerically stable for tens of thousands of
  iterations.
* absolute-value scores use the complement closure (§3.4): since
  ``Σ(h − p) = 0``, ``⟨1−q, h−p⟩ = −⟨q, h−p⟩``; augmented index id ``j``
  encodes query ``j % m`` with sign ``+1`` for ``j < m`` else ``−1`` — the
  augmented matrix is never materialized for scoring.
* the update rule is selectable (`"paper"`, `"signed"`, `"hardt"`) — see
  DESIGN.md §1: Alg. 1 as printed omits the sign/measurement step; the
  default `"hardt"` is the original MWEM update. Comparisons always use the
  same rule on both sides so the EM-vs-LazyEM effect is isolated.
* the LazyEM tail buffer can overflow (prob. ≈ e^{-Ω(√m)}); both drivers
  fall back to the exhaustive oracle for that iteration, preserving
  exactness — the fused driver does so in-graph via `lax.cond`.
* both drivers consume randomness through the identical split chain
  (`key → (key, k_sel, k_meas)` per iteration; the fused driver pre-splits
  the whole chain with a key-only scan), so on the same backend they make
  the same selections up to float reassociation in XLA fusion.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.accountant import PrivacyLedger, calibrate_eps0
from repro.obs.clock import perf_counter
from repro.obs.telemetry import MechanismTelemetry, aggregate_traces, record_run
from repro.obs.trace import annotate as obs_annotate
from repro.core.gumbel import gumbel
from repro.core.lazy_em import default_tail_cap, fallback_key, lazy_em_from_topk
from repro.core.queries import max_error
from repro.core.workload import Workload, as_workload
from repro.kernels.mwem_step import ops as step_ops
from repro.kernels.mwem_step.ref import mwem_step_ref, mwu_apply_ref
from repro.mips.base import bind_state, index_state, resolve_pallas
from repro.precision import dot as _dot


@dataclass(frozen=True)
class MWEMConfig:
    eps: float = 1.0
    delta: float = 1e-3
    T: int = 100
    update_rule: str = "hardt"   # "paper" | "signed" | "hardt"
    mode: str = "fast"           # "exact" | "fast"
    driver: str = "auto"         # "auto" | "fused" | "host" | "sharded"
    k: Optional[int] = None      # top-k size; default ceil(√m)
    tail_cap: Optional[int] = None
    margin_slack: float = 0.0    # c ≥ 0 → Alg. 6 privacy-preserving approx mode
    eta: Optional[float] = None  # default √(ln U / T)
    measure_frac: float = 0.5    # ε₀ fraction spent on the Laplace measurement
    eval_every: int = 0          # 0 → only final error
    n_records: Optional[int] = None  # dataset size n → sensitivity Δu = 1/n
    # Megakernel knob for the fused/sharded scans (mips.base semantics):
    # "auto"/"always" run the carried-density mega step — Pallas kernel when
    # resolve_pallas says so AND the shape qualifies, else the XLA ref, both
    # the host math; "never" keeps the classic pre-fusion body (the
    # roofline baseline).
    use_pallas: str = "auto"

    @staticmethod
    def iterations_for(alpha: float, m: int) -> int:
        """T = 4 α⁻² ln m (Alg. 1/2 line 3)."""
        return max(1, math.ceil(4.0 * math.log(m) / (alpha * alpha)))


def mwem_iteration_counts(alpha: float, m: int) -> int:
    return MWEMConfig.iterations_for(alpha, m)


class MWEMState(NamedTuple):
    log_w: jax.Array   # (U,) log weights
    p_sum: jax.Array   # (U,) running sum of iterates for the averaged output


@dataclass
class MWEMResult:
    p_hat: jax.Array
    final_error: float
    errors: list = field(default_factory=list)        # (t, ‖Q(p−h)‖_∞) pairs
    selected: list = field(default_factory=list)      # chosen query index per t
    n_scored: list = field(default_factory=list)      # score evaluations per t
    overflow_count: int = 0
    iter_seconds: list = field(default_factory=list)
    ledger: PrivacyLedger = field(default_factory=PrivacyLedger)
    # host-side aggregation of the scan traces (repro.obs.telemetry) —
    # always populated by the drivers; `amortized=True` marks timing that
    # covers a whole scan/batch rather than measured per-iteration steps
    telemetry: Optional[MechanismTelemetry] = None


@dataclass
class MWEMBatchResult:
    """Stacked outputs of `run_mwem_batch` (leading axis = batch of seeds)."""

    p_hat: jax.Array            # (B, U)
    final_errors: np.ndarray    # (B,)
    selected: np.ndarray        # (B, T)
    n_scored: np.ndarray        # (B, T)
    overflow_counts: np.ndarray  # (B,)
    errors: Optional[np.ndarray] = None  # (B, n_evals) when eval_every set
    eval_every: int = 0
    total_seconds: float = 0.0
    ledger: PrivacyLedger = field(default_factory=PrivacyLedger)  # per run
    ledgers: Optional[list] = None  # per-lane ledgers when the caller passed them
    telemetry: Optional[MechanismTelemetry] = None  # whole-batch aggregation

    def unbatch(self) -> list:
        """Materialize one MWEMResult per batch element.

        Each element carries its own ledger when the caller passed per-lane
        ledgers to `run_mwem_batch`; otherwise all elements share the
        per-run ledger (and the B× composition is the caller's contract —
        DESIGN.md §2). Lanes execute concurrently under vmap, so there is
        no honest per-lane, per-iteration wall-clock: ``iter_seconds``
        stays empty and each element's ``telemetry`` record carries the
        whole batch's ``total_seconds`` with ``amortized=True`` — callers
        that need timing read it there instead of mistaking an invented
        ``total/T`` split for a measurement.
        """
        B, T = self.selected.shape
        out = []
        for b in range(B):
            errors = []
            if self.errors is not None:
                errors = [(t, float(e)) for t, e in
                          zip(range(self.eval_every, T + 1, self.eval_every),
                              self.errors[b])]
            tel = None
            if self.telemetry is not None:
                tel = aggregate_traces(
                    workload=self.telemetry.workload,
                    driver=self.telemetry.driver,
                    mode=self.telemetry.mode,
                    m=self.telemetry.m,
                    n_scored=self.n_scored[b],
                    overflow_count=int(self.overflow_counts[b]),
                    total_seconds=self.total_seconds,  # whole-batch wall-clock
                    amortized=True,
                    lanes=1,
                )
            out.append(MWEMResult(
                p_hat=self.p_hat[b],
                final_error=float(self.final_errors[b]),
                errors=errors,
                selected=[int(s) for s in self.selected[b]],
                n_scored=[int(s) for s in self.n_scored[b]],
                overflow_count=int(self.overflow_counts[b]),
                iter_seconds=[],
                ledger=self.ledgers[b] if self.ledgers is not None else self.ledger,
                telemetry=tel,
            ))
        return out


class _Calibration(NamedTuple):
    eps_em: float
    eps_meas: float
    scale: float      # EM log-space factor ε₀/(2Δu)
    lap_scale: float  # Laplace measurement noise scale
    eta: float
    k: int
    tail_cap: int


def _calibrate(cfg: MWEMConfig, m: int, U: int) -> _Calibration:
    """Per-iteration budgets, noise scales and buffer sizes from the config."""
    eps0 = calibrate_eps0(cfg.eps, cfg.delta, cfg.T, scheme="mwem")
    if cfg.update_rule == "paper":
        eps_em, eps_meas = eps0, 0.0
    else:
        eps_em = eps0 * (1.0 - cfg.measure_frac)
        eps_meas = eps0 * cfg.measure_frac
    # Δu = 1/n: changing one of the n records moves one histogram cell by 1/n,
    # so each |⟨q, h−p⟩| utility moves by at most 1/n (q ∈ [0,1]^U).
    if cfg.n_records is None:
        raise ValueError("MWEMConfig.n_records (dataset size n) is required")
    sensitivity = 1.0 / cfg.n_records
    return _Calibration(
        eps_em=eps_em,
        eps_meas=eps_meas,
        scale=float(eps_em / (2.0 * sensitivity)),
        lap_scale=float(sensitivity / max(eps_meas, 1e-12)),
        eta=float(cfg.eta if cfg.eta is not None else math.sqrt(math.log(U) / cfg.T)),
        k=cfg.k or max(1, math.ceil(math.sqrt(m))),
        tail_cap=cfg.tail_cap or default_tail_cap(2 * m),
    )


def _aug_score(W: Workload, v: jax.Array, aug_idx: jax.Array) -> jax.Array:
    """Scores of augmented ids: ⟨q_{j%m}, v⟩ · sign(j<m) (== |·| at the top).

    Delegates to the workload's traceable `score_in_graph` — on dense
    workloads this is verbatim the pre-refactor gather (`(Q[base] @ v) ·
    sign`); factored workloads build the candidate rows implicitly."""
    return W.score_in_graph(v, aug_idx)


def _gumbel_argmax(key: jax.Array, x: jax.Array) -> jax.Array:
    g = gumbel(key, x.shape)
    return jnp.argmax(x + g).astype(jnp.int32)


def _exact_argmax(key: jax.Array, W: Workload, v: jax.Array, scale: float) -> jax.Array:
    """Exhaustive EM (Alg. 1 oracle): score all m queries, Gumbel-max.

    `Workload.scores` is the parity path: dense is ``Q @ v`` unchanged,
    factored is the same-shaped implicit-row matmul (bitwise for
    ``m ≤ score_block``)."""
    return _gumbel_argmax(key, jnp.abs(W.scores(v)) * scale)


_exact_select = jax.jit(_exact_argmax, static_argnames=("scale",))


def _measure_noise(key: jax.Array, rule: str, lap_scale: float) -> jax.Array:
    """Realized Laplace measurement noise — drawn outside the MWU seam so
    the arithmetic below (and the megakernel behind it) is deterministic.
    ``rule="paper"`` takes no measurement and must not consume the key."""
    if rule == "paper":
        return jnp.float32(0.0)
    return lap_scale * jax.random.laplace(key)


@partial(jax.jit, static_argnames=("rule", "eta", "lap_scale"))
def _mwu_step(state: MWEMState, p: jax.Array, q_row: jax.Array, h: jax.Array,
              key: jax.Array, rule: str, eta: float, lap_scale: float) -> MWEMState:
    """One multiplicative-weights update given the selected query row.

    ``p = softmax(state.log_w)`` is passed in (every caller already has it
    for the probe vector) rather than recomputed. This is the ONE MWU entry
    point (host loop + classic scan bodies); the arithmetic lives in
    `kernels.mwem_step.mwu_apply_ref`, the same expression the megakernel
    route and the sharded tail consume — a single integration seam.
    """
    noise = _measure_noise(key, rule, lap_scale)
    log_w, p_new = mwu_apply_ref(state.log_w, p, q_row, h, noise,
                                 rule=rule, eta=eta)
    return MWEMState(log_w=log_w, p_sum=state.p_sum + p_new)


def _record_iteration(ledger: PrivacyLedger, mode: str, rule: str,
                      cal: _Calibration, c_idx: float, margin_slack: float) -> None:
    """Ledger entries for one iteration — shared by both drivers so fused
    and host runs compose to identical privacy totals."""
    if mode == "exact":
        ledger.record(cal.eps_em, 0.0, "em")
    else:
        ledger.record(cal.eps_em, 0.0, "lazy_em")
        if c_idx > 0.0 and margin_slack == 0.0:
            ledger.record_approx_slack(c_idx)  # Thm F.2 runtime mode
    if rule != "paper":
        ledger.record(cal.eps_meas, 0.0, "laplace")


def release_cost(cfg: MWEMConfig, m: int, U: int, index=None
                 ) -> tuple[list, float, float]:
    """The exact privacy-cost bundle one `run_mwem*` run records.

    Returns ``(events, gamma, slack)`` — the (ε₀, δ₀, label) event list for
    T iterations, the index failure mass γ (Thm 3.3), and the already-
    doubled approx slack Σ2c (Thm F.2) — built through the same
    `_calibrate`/`_record_iteration` path the drivers use, so an admission
    controller previews *precisely* what execution will spend
    (`PrivacyLedger.preview(*release_cost(...))` == post-run `composed()`).
    """
    cal = _calibrate(cfg, m, U)
    c_idx = _check_fast_index(cfg, index, fused=False)
    tmp = PrivacyLedger()
    if cfg.mode == "fast":
        tmp.record_index_failure(getattr(index, "failure_mass", 1.0 / m))
    for _ in range(cfg.T):
        _record_iteration(tmp, cfg.mode, cfg.update_rule, cal,
                          c_idx, cfg.margin_slack)
    return list(tmp.events), tmp.index_failure_mass, tmp.approx_slack


def split_chain(key: jax.Array, T: int):
    """Pre-split the per-iteration key pairs by walking the host loop's
    exact chain (``key → key, k_sel, k_meas``) as one key-only scan.

    This is THE key chain: the host loop consumes it step by step, the
    fused and sharded drivers pre-split it through this helper — one point
    of truth, so cross-driver bitwise selection parity cannot drift.
    Returns ``(sel_keys, meas_keys)``, each (T,)-stacked.
    """

    def body(carry_key, _):
        carry_key, k_sel, k_meas = jax.random.split(carry_key, 3)
        return carry_key, (k_sel, k_meas)

    _, keys = jax.lax.scan(body, key, None, length=T)
    return keys


# ---------------------------------------------------------------------------
# Fused on-device driver (DESIGN.md §2)
# ---------------------------------------------------------------------------

_FUSED_STATICS = ("T", "mode", "rule", "eta", "scale", "lap_scale", "k",
                  "tail_cap", "margin_slack", "eval_every", "use_pallas")


def _mega_route(use_pallas: str, U: int) -> tuple[bool, bool]:
    """Resolve the scan-body route from the `use_pallas` knob (static).

    Returns ``(mega, kernel)``: ``mega`` picks the carried-density fused
    step (the megakernel dataflow — DESIGN.md §7) vs the classic
    softmax-per-step body; ``kernel`` picks the Pallas `mwem_step` kernel
    inside the mega route vs its XLA ref — "auto" off-TPU and shapes the
    kernel cannot take fall back to the ref automatically.
    """
    mega = use_pallas != "never"
    kernel = (mega and resolve_pallas(use_pallas)
              and step_ops.mwem_step_supported(U))
    return mega, kernel


def _fused_core(W: Workload, h: jax.Array, state0: MWEMState, key: jax.Array,
                *, query_fn: Optional[Callable], T: int, mode: str, rule: str,
                eta: float, scale: float, lap_scale: float, k: int,
                tail_cap: int, margin_slack: float, eval_every: int,
                use_pallas: str = "auto", query_returns_scores: bool = False):
    """The whole (Fast-)MWEM loop as one `lax.scan` — zero host round-trips.

    Pre-splits the per-iteration key pairs with a key-only scan that walks
    the exact chain the host loop uses (``key → key, k_sel, k_meas``), so
    the two drivers are distributionally (and, modulo XLA float
    reassociation, bitwise) interchangeable.

    ``query_returns_scores``: the probe is exhaustive and hands back the
    full (m,) signed score vector — tail scoring and the overflow fallback
    become O(tail_cap)/O(m) lookups instead of re-touching Q.

    ``use_pallas != "never"`` swaps the step tail for the megakernel
    dataflow: the scan carries ``(state, p)`` so the per-step softmax
    disappears (the MWU renormalizes in the same pass), and measure + MWU +
    renorm run as one VMEM-resident `kernels.mwem_step` call that streams
    only the winning query row. Selection and the overflow `lax.cond` stay
    outside the kernel — bitwise host parity is the contract.
    """
    m = W.m
    U = state0.log_w.shape[-1]
    mega, kernel = _mega_route(use_pallas, U)
    sel_keys, meas_keys = split_chain(key, T)

    def select(k_sel, v):
        """Private selection → ``(sel, n_scored, tail_count, overflow)``.

        On tail-buffer overflow the `lax.cond` redoes the step with the
        exhaustive Gumbel-max under `lazy_em.fallback_key` (a fresh key —
        the lazy pass already consumed ``k_sel``'s Gumbels, and the host
        driver folds identically, so parity holds). The cond keeps the
        heavy branch unexecuted on the non-overflow path of an unbatched
        run.
        """
        if mode == "exact":
            return (_exact_argmax(k_sel, W, v, scale), jnp.int32(m),
                    jnp.int32(0), jnp.bool_(False))
        if query_returns_scores:
            aug_idx, raw, s_full = query_fn(v, k)
            score_fn = lambda idx: jnp.where(  # noqa: E731
                idx < m, s_full[idx % m], -s_full[idx % m]) * scale
            fallback = lambda _: _gumbel_argmax(  # noqa: E731
                fallback_key(k_sel), jnp.abs(s_full) * scale)
        else:
            aug_idx, raw = query_fn(v, k)
            if kernel and not W.is_dense:
                # factored row fetch: offsets + implicit one-hot products,
                # no (m, U) gather anywhere
                score_fn = lambda idx: (  # noqa: E731
                    step_ops.marginal_gather_score(W, v, idx) * scale)
            else:
                score_fn = lambda idx: _aug_score(W, v, idx) * scale  # noqa: E731
            fallback = lambda _: _exact_argmax(  # noqa: E731
                fallback_key(k_sel), W, v, scale)
        out = lazy_em_from_topk(
            k_sel, aug_idx, raw * scale, 2 * m,
            score_fn=score_fn,
            tail_cap=tail_cap,
            margin_slack=margin_slack * scale if margin_slack else 0.0,
        )
        sel = jax.lax.cond(
            out.overflow,
            fallback,
            lambda _: (out.index % m).astype(jnp.int32),
            operand=None,
        )
        n_scored = jnp.where(out.overflow, jnp.int32(m), out.n_scored)
        return sel, n_scored, out.tail_count, out.overflow

    def eval_ys(t, p_sum):
        # Gated on the eval schedule: the Θ(mU) error matmul would
        # otherwise run every iteration and erase the sublinear win.
        return jax.lax.cond(
            t % eval_every == 0,
            lambda _: max_error(W, h, p_sum / t.astype(jnp.float32)),
            lambda _: jnp.float32(jnp.nan),
            operand=None,
        )

    ts = jnp.arange(1, T + 1)

    if mega:
        def body(carry, xs):
            state, p = carry
            t, k_sel, k_meas = xs
            v = h - p
            sel, n_scored, tail_count, overflow = select(k_sel, v)
            noise = _measure_noise(k_meas, rule, lap_scale)
            if kernel and W.is_dense:
                lw, p_new, ps = step_ops.mwem_step(
                    state.log_w, p, state.p_sum, W.Q, sel, h, noise,
                    rule=rule, eta=eta)
            elif kernel:
                # factored winner row arrives materialized (one implicit
                # one-hot expansion); same kernel body via the
                # no-prefetch-table variant
                lw, p_new, ps = step_ops.mwu_apply(
                    state.log_w, p, state.p_sum, W.row(sel), h, noise,
                    rule=rule, eta=eta)
            else:
                lw, p_new, ps = mwem_step_ref(
                    state.log_w, p, state.p_sum, W.row(sel), h, noise,
                    rule=rule, eta=eta)
            new_state = MWEMState(log_w=lw, p_sum=ps)
            ys = (sel, n_scored, tail_count, overflow)
            if eval_every:
                ys = ys + (eval_ys(t, new_state.p_sum),)
            return (new_state, p_new), ys

        carry0 = (state0, jax.nn.softmax(state0.log_w))
        (final_state, _), traces = jax.lax.scan(
            body, carry0, (ts, sel_keys, meas_keys))
        return final_state, traces

    def body(state, xs):
        t, k_sel, k_meas = xs
        p = jax.nn.softmax(state.log_w)
        v = h - p
        sel, n_scored, tail_count, overflow = select(k_sel, v)
        new_state = _mwu_step(state, p, W.row(sel), h, k_meas, rule=rule,
                              eta=eta, lap_scale=lap_scale)
        ys = (sel, n_scored, tail_count, overflow)
        if eval_every:
            ys = ys + (eval_ys(t, new_state.p_sum),)
        return new_state, ys

    return jax.lax.scan(body, state0, (ts, sel_keys, meas_keys))


def _fused_core_waved(W: Workload, h: jax.Array, state0: MWEMState,
                      keys: jax.Array, *, batch_query_fn: Callable, T: int,
                      mode: str, rule: str, eta: float, scale: float,
                      lap_scale: float, k: int, tail_cap: int,
                      margin_slack: float, eval_every: int,
                      use_pallas: str = "auto"):
    """The batched fused loop with a *wave-batched* probe (DESIGN.md §3).

    `run_mwem_batch`'s default shape is `vmap(_fused_core)`: every lane
    probes the index independently, which XLA lowers to per-lane scattered
    gathers. When the index serves a whole wave per call
    (``supports_batch_probe``), this core scans once over T carrying all B
    lanes and hands the stacked (B, U) probe block to
    ``index.query_in_graph_batch`` — on the kernel route, cells probed by
    several lanes stream from HBM once and scoring is MXU-batched.
    Everything after the probe (LazyEM, overflow fallback, MW update) is
    the vmapped per-lane math of `_fused_core`, and the key chain is the
    per-lane `split_chain`, so lane b reproduces `run_mwem_fused(key_b)`
    (same trace fields, same ledger path; bitwise when the batched probe
    equals the per-lane probe — exactly true on the XLA route, up to exact
    score ties on the batch-kernel route).
    """
    m = W.m
    B = keys.shape[0]
    U = state0.log_w.shape[-1]
    if mode != "fast":
        raise ValueError("the waved core only serves mode='fast' probes")
    mega, kernel = _mega_route(use_pallas, U)
    sel_keys, meas_keys = jax.vmap(lambda kk: split_chain(kk, T))(keys)
    sel_keys = jnp.moveaxis(sel_keys, 0, 1)    # (T, B, key)
    meas_keys = jnp.moveaxis(meas_keys, 0, 1)
    batched_h = h.ndim == 2
    mwu = partial(_mwu_step, rule=rule, eta=eta, lap_scale=lap_scale)

    def select_one(k_sel, v, aug_idx, raw):
        out = lazy_em_from_topk(
            k_sel, aug_idx, raw * scale, 2 * m,
            score_fn=lambda idx: _aug_score(W, v, idx) * scale,
            tail_cap=tail_cap,
            margin_slack=margin_slack * scale if margin_slack else 0.0,
        )
        sel = jax.lax.cond(
            out.overflow,
            lambda _: _exact_argmax(fallback_key(k_sel), W, v, scale),
            lambda _: (out.index % m).astype(jnp.int32),
            operand=None,
        )
        n_scored = jnp.where(out.overflow, jnp.int32(m), out.n_scored)
        return sel, n_scored, out.tail_count, out.overflow

    def eval_ys(t, p_sum):
        err_fn = jax.vmap(partial(max_error, W),
                          in_axes=(0 if batched_h else None, 0))
        return jax.lax.cond(
            t % eval_every == 0,
            lambda _: err_fn(h, p_sum / t.astype(jnp.float32)),
            lambda _: jnp.full((B,), jnp.nan, jnp.float32),
            operand=None,
        )

    ts = jnp.arange(1, T + 1)

    if mega:
        noise_fn = jax.vmap(partial(_measure_noise, rule=rule,
                                    lap_scale=lap_scale))
        step_ref = partial(mwem_step_ref, rule=rule, eta=eta)

        def body(carry, xs):
            state, p = carry                        # (B, U) each
            t, k_sel, k_meas = xs                   # keys (B, ...)
            v = h - p                               # (B, U)
            aug_idx, raw = batch_query_fn(v, k)     # (B, k) each
            sel, n_scored, tail_count, overflow = jax.vmap(select_one)(
                k_sel, v, aug_idx, raw)
            noise = noise_fn(k_meas)                # (B,)
            if kernel and W.is_dense:
                lw, p_new, ps = step_ops.mwem_step_batch(
                    state.log_w, p, state.p_sum, W.Q, sel, h, noise,
                    rule=rule, eta=eta)
            else:
                lw, p_new, ps = jax.vmap(
                    step_ref, in_axes=(0, 0, 0, 0, 0 if batched_h else None,
                                       0))(state.log_w, p, state.p_sum,
                                           W.rows(sel), h, noise)
            new_state = MWEMState(log_w=lw, p_sum=ps)
            ys = (sel, n_scored, tail_count, overflow)
            if eval_every:
                ys = ys + (eval_ys(t, new_state.p_sum),)
            return (new_state, p_new), ys

        carry0 = (state0, jax.nn.softmax(state0.log_w, axis=-1))
        (final_state, _), traces = jax.lax.scan(
            body, carry0, (ts, sel_keys, meas_keys))
    else:
        def body(state, xs):
            t, k_sel, k_meas = xs                   # keys (B, ...)
            p = jax.nn.softmax(state.log_w, axis=-1)   # (B, U)
            v = h - p                                   # (B, U)
            aug_idx, raw = batch_query_fn(v, k)         # (B, k) each
            sel, n_scored, tail_count, overflow = jax.vmap(select_one)(
                k_sel, v, aug_idx, raw)
            new_state = jax.vmap(mwu, in_axes=(0, 0, 0,
                                               0 if batched_h else None,
                                               0))(state, p, W.rows(sel), h,
                                                   k_meas)
            ys = (sel, n_scored, tail_count, overflow)
            if eval_every:
                ys = ys + (eval_ys(t, new_state.p_sum),)
            return new_state, ys

        final_state, traces = jax.lax.scan(body, state0,
                                           (ts, sel_keys, meas_keys))
    # (T, B) stacked scan outputs → the (B, T) layout vmap(core) produces
    traces = jax.tree_util.tree_map(lambda x: jnp.swapaxes(x, 0, 1), traces)
    return final_state, traces


_EXACT_DRIVER_CACHE: dict = {}


def _waved_route(index, batch_axes) -> bool:
    """Whether the batched driver should scan with the wave-batched probe
    instead of vmapping the per-lane core: the index must serve whole
    waves, and must not be on the full-score-reuse path (which hands the
    scan body the (m,) score vector the waved probe never materializes)."""
    return (batch_axes is not None
            and getattr(index, "supports_batch_probe", False)
            and not getattr(index, "has_full_scores", False))


def _fused_driver(index, statics: dict, batch_axes=None) -> Callable:
    """Build (or fetch) the jitted fused driver for an (index, config) pair.

    Compiled drivers are cached on the index instance (module-level for
    ``mode="exact"``) so repeated runs with the same shapes re-dispatch the
    cached executable. The carried `MWEMState` buffers are donated.
    ``batch_axes`` is a vmap ``in_axes`` tuple over (Q, h, state0, key) for
    the batched driver, or None for the single-run driver. The driver's
    arguments are ``(Q, h, state0, key, index_state(index))``.
    """
    cache = (_EXACT_DRIVER_CACHE if index is None
             else index.__dict__.setdefault("_fused_driver_cache", {}))
    waved = _waved_route(index, batch_axes)
    # the route (and the kernel-vs-XLA probe under it) is resolved at trace
    # time, so a flipped `use_pallas` knob must never reuse a stale entry
    ck = (tuple(sorted(statics.items())), batch_axes, waved,
          getattr(index, "_use_pallas", None))
    entry = cache.get(ck)
    if entry is None:
        # the index's tables are the driver's last argument (`index_state`),
        # never constants captured from the closure
        if waved:
            def core(W, h, state0, keys, state):
                return _fused_core_waved(
                    W, h, state0, keys,
                    batch_query_fn=bind_state(index, state)
                    .query_in_graph_batch, **statics)
        else:
            full = getattr(index, "has_full_scores", False)
            if full:
                statics = dict(statics, query_returns_scores=True)

            def per_lane(W, h, state0, key, state):
                bound = bind_state(index, state)
                query_fn = (None if bound is None
                            else bound.query_in_graph_with_scores if full
                            else bound.query_in_graph)
                return _fused_core(W, h, state0, key, query_fn=query_fn,
                                   **statics)

            core = per_lane
            if batch_axes is not None:
                core = jax.vmap(core, in_axes=batch_axes + (None,))
        entry = (jax.jit(core, donate_argnums=(2,)), {})
        cache[ck] = entry
    return entry


def _compiled_driver(entry, *args) -> Callable:
    """AOT-compile the driver for these arg shapes (cached), so callers can
    keep trace+compile out of the timed region — fused ``iter_seconds``
    measures execution only."""
    fn, exes = entry
    # treedef joins the key: workloads are pytrees whose aux (cliques,
    # chunk sizes) can differ between instances with identical leaf shapes
    skey = (jax.tree_util.tree_structure(args),
            tuple((tuple(x.shape), str(x.dtype))
                  for x in jax.tree_util.tree_leaves(args)))
    exe = exes.get(skey)
    if exe is None:
        exe = fn.lower(*args).compile()
        exes[skey] = exe
    return exe


def _fused_statics(cfg: MWEMConfig, cal: _Calibration) -> dict:
    return dict(T=cfg.T, mode=cfg.mode, rule=cfg.update_rule, eta=cal.eta,
                scale=cal.scale, lap_scale=cal.lap_scale, k=cal.k,
                tail_cap=cal.tail_cap, margin_slack=cfg.margin_slack,
                eval_every=cfg.eval_every, use_pallas=cfg.use_pallas)


def _check_fast_index(cfg: MWEMConfig, index, fused: bool) -> float:
    if cfg.mode not in ("exact", "fast"):
        raise ValueError(f"unknown mode {cfg.mode!r}")
    if cfg.mode != "fast":
        return 0.0
    if index is None:
        raise ValueError("fast mode requires a k-MIPS index")
    if fused and not getattr(index, "supports_in_graph", False):
        raise ValueError(
            f"{type(index).__name__} cannot be traced into the fused scan "
            "(supports_in_graph=False); use driver='host'")
    return float(getattr(index, "approx_margin", 0.0))


def run_mwem_fused(
    Q: jax.Array,
    h: jax.Array,
    cfg: MWEMConfig,
    key: jax.Array,
    index=None,
    ledger: Optional[PrivacyLedger] = None,
) -> MWEMResult:
    """Run (Fast-)MWEM as a single fused scan dispatch.

    Exactly one device→host transfer moves the stacked per-iteration traces
    (`selected`, `n_scored`, `tail_count`, `overflow`, and the running error
    when ``eval_every`` is set) back; `MWEMResult` is reconstructed from
    them. ``iter_seconds`` holds the amortized *execution* wall-clock per
    iteration (total / T): trace+compile happen outside the timed region
    via a cached AOT executable, and individual steps are not observable
    from the host.
    """
    W = as_workload(Q)
    m, U = W.m, W.U
    cal = _calibrate(cfg, m, U)
    c_idx = _check_fast_index(cfg, index, fused=True)

    res = MWEMResult(p_hat=None, final_error=float("nan"),
                     ledger=ledger if ledger is not None else PrivacyLedger())
    if cfg.mode == "fast":
        res.ledger.record_index_failure(getattr(index, "failure_mass", 1.0 / m))

    entry = _fused_driver(index if cfg.mode == "fast" else None,
                          _fused_statics(cfg, cal))
    state0 = MWEMState(log_w=jnp.zeros((U,), jnp.float32),
                       p_sum=jnp.zeros((U,), jnp.float32))
    args = (W, jnp.asarray(h, jnp.float32), state0, key,
            index_state(index if cfg.mode == "fast" else None))
    driver = _compiled_driver(entry, *args)
    t0 = perf_counter()
    with obs_annotate("mwem/fused"):
        final_state, traces = driver(*args)
        jax.block_until_ready(final_state.p_sum)
    total = perf_counter() - t0

    traces = jax.device_get(traces)
    sel_t, n_scored_t, _tail_t, over_t = traces[:4]
    res.selected = [int(s) for s in sel_t]
    res.n_scored = [int(s) for s in n_scored_t]
    res.overflow_count = int(np.sum(over_t))
    res.iter_seconds = [total / cfg.T] * cfg.T
    res.telemetry = record_run(
        workload="mwem", driver="fused", mode=cfg.mode, m=m,
        n_scored=n_scored_t, overflow_count=res.overflow_count,
        total_seconds=total, amortized=True)
    for _ in range(cfg.T):
        _record_iteration(res.ledger, cfg.mode, cfg.update_rule, cal,
                          c_idx, cfg.margin_slack)
    if cfg.eval_every:
        errs = traces[4]
        res.errors = [(t, float(errs[t - 1]))
                      for t in range(cfg.eval_every, cfg.T + 1, cfg.eval_every)]

    res.p_hat = final_state.p_sum / cfg.T
    res.final_error = float(max_error(W, h, res.p_hat))
    return res


@dataclass
class MWEMPendingBatch:
    """Handle for an in-flight `launch_mwem_batch` dispatch.

    Holds the device futures the async dispatch returned plus everything
    `finish_mwem_batch` needs to rebuild the exact `MWEMBatchResult` that
    `run_mwem_batch` would have produced synchronously. Nothing here has
    been blocked on: the scan may still be executing when the caller gets
    this object back, which is what lets a streaming server overlap the
    next wave's host-side prep and transfers with this wave's scan."""

    final_state: MWEMState      # (B, U) device futures
    traces: tuple               # stacked scan outputs, unfetched
    t0: float                   # perf_counter stamp at dispatch
    W: Workload
    h: jax.Array
    batched_h: bool
    cfg: MWEMConfig
    cal: _Calibration
    c_idx: float
    index: object
    lanes: int
    driver_label: str


def launch_mwem_batch(
    Q: jax.Array,
    h: jax.Array,
    cfg: MWEMConfig,
    keys: jax.Array,
    index=None,
) -> MWEMPendingBatch:
    """Dispatch one batched wave asynchronously — the launch half of
    `run_mwem_batch`.

    Calibration, driver lookup, and the cached AOT compile all happen
    here; the compiled executable is dispatched *without* blocking, so the
    returned handle's device buffers are futures. `finish_mwem_batch`
    blocks and assembles the result; ``run_mwem_batch(...)`` is exactly
    ``finish_mwem_batch(launch_mwem_batch(...))``, so a launched wave is
    bitwise identical to a synchronous one.
    """
    if cfg.driver == "host":
        raise ValueError("run_mwem_batch always uses the fused driver; "
                         "loop run_mwem(..., driver='host') for host runs")
    W = as_workload(Q)
    m, U = W.m, W.U
    keys = jnp.asarray(keys)
    B = keys.shape[0]
    h = jnp.asarray(h, jnp.float32)
    batched_h = h.ndim == 2
    cal = _calibrate(cfg, m, U)
    c_idx = _check_fast_index(cfg, index, fused=True)

    batch_axes = (None, 0 if batched_h else None, 0, 0)
    entry = _fused_driver(index if cfg.mode == "fast" else None,
                          _fused_statics(cfg, cal),
                          batch_axes=batch_axes)
    driver_label = ("waved"
                    if _waved_route(index if cfg.mode == "fast" else None,
                                    batch_axes)
                    else "fused")
    state0 = MWEMState(log_w=jnp.zeros((B, U), jnp.float32),
                       p_sum=jnp.zeros((B, U), jnp.float32))
    args = (W, h, state0, keys,
            index_state(index if cfg.mode == "fast" else None))
    driver = _compiled_driver(entry, *args)
    t0 = perf_counter()
    with obs_annotate(f"mwem/batch/{driver_label}"):
        final_state, traces = driver(*args)
    return MWEMPendingBatch(
        final_state=final_state, traces=traces, t0=t0, W=W, h=h,
        batched_h=batched_h, cfg=cfg, cal=cal, c_idx=c_idx, index=index,
        lanes=B, driver_label=driver_label)


def finish_mwem_batch(pending: MWEMPendingBatch,
                      ledgers: Optional[list] = None) -> MWEMBatchResult:
    """Block on a launched wave and assemble its `MWEMBatchResult` — the
    finish half of `run_mwem_batch` (ledger charging, trace fetch, and
    telemetry all happen here, after the device work lands)."""
    W, cfg, cal = pending.W, pending.cfg, pending.cal
    index, B = pending.index, pending.lanes
    h, batched_h = pending.h, pending.batched_h
    m = W.m
    if ledgers is not None and len(ledgers) != B:
        raise ValueError(f"ledgers must have one entry per lane "
                         f"({len(ledgers)} != {B})")
    with obs_annotate(f"mwem/batch/{pending.driver_label}/finish"):
        final_state, traces = pending.final_state, pending.traces
        jax.block_until_ready(final_state.p_sum)
    total = perf_counter() - pending.t0

    p_hat = final_state.p_sum / cfg.T
    if W.is_dense:  # pre-refactor expression, kept bitwise
        final_errors = jnp.max(jnp.abs(_dot(h - p_hat, W.Q.T)), axis=-1)
    else:
        final_errors = jax.vmap(
            lambda hh, pp: max_error(W, hh, pp),
            in_axes=(0 if batched_h else None, 0))(h, p_hat)

    ledger = PrivacyLedger()
    if cfg.mode == "fast":
        ledger.record_index_failure(getattr(index, "failure_mass", 1.0 / m))
    for _ in range(cfg.T):
        _record_iteration(ledger, cfg.mode, cfg.update_rule, cal,
                          pending.c_idx, cfg.margin_slack)
    if ledgers is not None:
        for lane in ledgers:
            if lane is not None:
                lane.record_events(ledger.events, ledger.index_failure_mass,
                                   ledger.approx_slack)

    traces = jax.device_get(traces)
    errors = None
    if cfg.eval_every:
        eval_ts = range(cfg.eval_every, cfg.T + 1, cfg.eval_every)
        errors = np.asarray(traces[4])[:, [t - 1 for t in eval_ts]]
    telemetry = record_run(
        workload="mwem", driver=pending.driver_label, mode=cfg.mode, m=m,
        n_scored=np.asarray(traces[1]),
        overflow_count=int(np.asarray(traces[3]).sum()),
        total_seconds=total, amortized=True, lanes=B)
    return MWEMBatchResult(
        p_hat=p_hat,
        final_errors=np.asarray(final_errors),
        selected=np.asarray(traces[0]),
        n_scored=np.asarray(traces[1]),
        overflow_counts=np.asarray(traces[3]).sum(axis=1),
        errors=errors,
        eval_every=cfg.eval_every,
        total_seconds=total,
        ledger=ledger,
        ledgers=list(ledgers) if ledgers is not None else None,
        telemetry=telemetry,
    )


def aot_compile_batch(Q, cfg: MWEMConfig, lanes: int, index=None,
                      batched_h: bool = True) -> bool:
    """Populate the batched driver's AOT executable cache for a
    ``lanes``-wide wave without dispatching any work.

    The streaming serving tier compiles one executable per wave size in a
    small ladder up front (`ReleaseService.prewarm`), then picks the best
    fit per wave instead of padding every short wave to one size. Returns
    True when a new executable was compiled, False when the cache already
    held this (shape, statics) entry. The compiled artifact lands in the
    same cache `run_mwem_batch`/`launch_mwem_batch` consult, so the first
    live wave at this lane count pays zero trace+compile.
    """
    if cfg.driver == "host":
        raise ValueError("run_mwem_batch always uses the fused driver; "
                         "loop run_mwem(..., driver='host') for host runs")
    W = as_workload(Q)
    m, U = W.m, W.U
    cal = _calibrate(cfg, m, U)
    _check_fast_index(cfg, index, fused=True)
    batch_axes = (None, 0 if batched_h else None, 0, 0)
    entry = _fused_driver(index if cfg.mode == "fast" else None,
                          _fused_statics(cfg, cal),
                          batch_axes=batch_axes)
    h = jnp.zeros((lanes, U) if batched_h else (U,), jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(0)] * lanes)
    state0 = MWEMState(log_w=jnp.zeros((lanes, U), jnp.float32),
                       p_sum=jnp.zeros((lanes, U), jnp.float32))
    n_before = len(entry[1])
    _compiled_driver(entry, W, h, state0, keys,
                     index_state(index if cfg.mode == "fast" else None))
    return len(entry[1]) > n_before


def run_mwem_batch(
    Q: jax.Array,
    h: jax.Array,
    cfg: MWEMConfig,
    keys: jax.Array,
    index=None,
    ledgers: Optional[list] = None,
) -> MWEMBatchResult:
    """Vmapped fused scan over a batch of PRNG keys — replicated release.

    Args:
      keys: (B,)-stacked PRNG keys (e.g. ``jnp.stack([PRNGKey(s) for s in
        seeds])``); each batch element reproduces exactly what
        `run_mwem_fused` produces for that key.
      h: shared ``(U,)`` histogram, or ``(B, U)`` for per-element data.
      ledgers: optional list of B `PrivacyLedger`s, one per lane — each
        receives that lane's full event bundle (`release_cost`), which is
        how a multi-tenant caller (repro.serve) charges each tenant's
        session for its own slot in the wave. ``None`` entries skip a lane
        (padding slots).

    The privacy ledger on the result is *per run* (each batch element
    composes the same totals); serving B replicas spends B× the budget and
    the caller accounts for the multiplicity — either manually or by
    passing per-lane ``ledgers``.

    Batching is fused-only (``driver="host"`` raises). Indices that serve
    whole waves (``supports_batch_probe`` — IVF, and FlatAbs on TPU) route
    through the wave-batched scan core instead of `vmap`: one probe call
    covers all B lanes per iteration (the kernelized route reads cells
    probed by several lanes once — DESIGN.md §3). Per-lane parity with
    `run_mwem_fused` is bitwise on the XLA probe route; the TPU batch
    kernel's slot ordering can break *exact* score ties differently than
    a standalone probe (kernels/ivf_probe/ref.py). Cost caveat: under
    either route the
    overflow-fallback `lax.cond` lowers to a select that executes both
    branches every iteration, so for probe-only indices (IVF/LSH) each
    batched iteration pays the Θ(mU) exhaustive branch — batch those
    through a Python loop over `run_mwem` if selection cost matters more
    than dispatch (DESIGN.md §2).
    """
    if cfg.driver == "host":
        raise ValueError("run_mwem_batch always uses the fused driver; "
                         "loop run_mwem(..., driver='host') for host runs")
    B = jnp.asarray(keys).shape[0]
    if ledgers is not None and len(ledgers) != B:
        raise ValueError(f"ledgers must have one entry per lane "
                         f"({len(ledgers)} != {B})")
    return finish_mwem_batch(launch_mwem_batch(Q, h, cfg, keys, index=index),
                             ledgers=ledgers)


# ---------------------------------------------------------------------------
# Host-loop driver (reference / non-traceable indices)
# ---------------------------------------------------------------------------

def _run_mwem_host(
    Q: jax.Array,
    h: jax.Array,
    cfg: MWEMConfig,
    key: jax.Array,
    index=None,
    ledger: Optional[PrivacyLedger] = None,
) -> MWEMResult:
    """One jit dispatch per step; `bool(out.overflow)` syncs to the host."""
    W = as_workload(Q)
    m, U = W.m, W.U
    cal = _calibrate(cfg, m, U)
    c_idx = _check_fast_index(cfg, index, fused=False)

    res = MWEMResult(p_hat=None, final_error=float("nan"),
                     ledger=ledger if ledger is not None else PrivacyLedger())
    state = MWEMState(log_w=jnp.zeros((U,), jnp.float32),
                      p_sum=jnp.zeros((U,), jnp.float32))

    if cfg.mode == "fast":
        res.ledger.record_index_failure(getattr(index, "failure_mass", 1.0 / m))

        @jax.jit
        def fast_select(key, topk_idx, topk_scores, Wm, v):
            return lazy_em_from_topk(
                key, topk_idx,
                topk_scores * cal.scale,
                2 * m,
                score_fn=lambda idx: _aug_score(Wm, v, idx) * cal.scale,
                tail_cap=cal.tail_cap,
                margin_slack=cfg.margin_slack * cal.scale if cfg.margin_slack else 0.0,
            )

    with obs_annotate("mwem/host"):
        for t in range(cfg.T):
            key, k_sel, k_meas = jax.random.split(key, 3)
            t0 = perf_counter()
            p = jax.nn.softmax(state.log_w)
            v = h - p
            if cfg.mode == "exact":
                sel = int(_exact_select(k_sel, W, v, scale=cal.scale))
                res.n_scored.append(m)
            else:
                aug_idx, raw = index.query(v, cal.k)
                out = fast_select(k_sel, aug_idx, raw, W, v)
                if bool(out.overflow):
                    # fresh fold of k_sel (lazy_em.fallback_key) — the lazy
                    # pass already consumed k_sel's Gumbels; the fused
                    # drivers fold identically in-graph so parity holds
                    sel = int(_exact_select(fallback_key(k_sel), W, v,
                                            scale=cal.scale))
                    res.overflow_count += 1
                    res.n_scored.append(m)
                else:
                    sel = int(out.index) % m
                    res.n_scored.append(int(out.n_scored))
            _record_iteration(res.ledger, cfg.mode, cfg.update_rule, cal,
                              c_idx, cfg.margin_slack)
            state = _mwu_step(state, p, W.row(sel), h, k_meas,
                              rule=cfg.update_rule, eta=cal.eta,
                              lap_scale=cal.lap_scale)
            jax.block_until_ready(state.log_w)
            res.iter_seconds.append(perf_counter() - t0)
            res.selected.append(sel)
            if cfg.eval_every and (t + 1) % cfg.eval_every == 0:
                p_avg = state.p_sum / (t + 1)
                res.errors.append((t + 1, float(max_error(W, h, p_avg))))

    p_hat = state.p_sum / cfg.T
    res.p_hat = p_hat
    res.final_error = float(max_error(W, h, p_hat))
    res.telemetry = record_run(
        workload="mwem", driver="host", mode=cfg.mode, m=m,
        n_scored=res.n_scored, overflow_count=res.overflow_count,
        total_seconds=sum(res.iter_seconds), amortized=False)
    return res


def _sharded_fits(index, mesh, shape) -> bool:
    """Whether (m, U) actually divides over the mesh (or the default driver
    mesh) and the index's shard count matches — auto-routing must not pick
    a driver that will refuse the workload."""
    if shape is None:
        return True  # no workload in hand (introspection) — assume fits
    m, U = shape
    sharded_index = getattr(index, "supports_sharded", False)
    if mesh is not None:
        from repro.core.distributed import _data_shards

        n_data = _data_shards(mesh)[1]
        n_model = mesh.shape["model"]
    else:
        # default make_driver_mesh(): all devices on "data", model degree 1
        n_data, n_model = jax.device_count(), 1
    if sharded_index and index.n_shards != n_data:
        return False
    return m % n_data == 0 and U % n_model == 0


def _resolve_driver(cfg: MWEMConfig, index, mesh=None, shape=None,
                    densifiable: bool = True) -> str:
    if cfg.driver not in ("auto", "fused", "host", "sharded"):
        raise ValueError(f"unknown driver {cfg.driver!r}")
    if cfg.driver != "auto":
        return cfg.driver
    # the sharded driver kicks in when there is real device parallelism (or
    # the caller handed us a mesh, or the index only works sharded) and the
    # workload can shard: exact mode always can; fast mode needs a
    # per-shard index structure. Factored workloads past the densify limit
    # never auto-shard (the sharded driver's documented fallback is a dense
    # table) — they stay on the fused/host factored path.
    sharded_ok = (densifiable
                  and (cfg.mode == "exact"
                       or getattr(index, "supports_sharded", False)))
    sharded_only = (getattr(index, "supports_sharded", False)
                    and not getattr(index, "supports_in_graph", False))
    want = mesh is not None or jax.device_count() > 1 or sharded_only
    if sharded_ok and want and _sharded_fits(index, mesh, shape):
        return "sharded"
    if sharded_only:
        # a per-shard-only index has no host/fused query path — surface the
        # mismatch instead of crashing mid-run in the host loop
        raise ValueError(
            f"{type(index).__name__} only runs on the sharded driver, but "
            "the workload/mesh/shard counts do not line up "
            "(m must divide over the data shards, U over the model shards, "
            "and index.n_shards must equal the mesh's data extent)")
    if cfg.mode == "exact":
        return "fused"
    if index is not None and getattr(index, "supports_in_graph", False):
        return "fused"
    return "host"


def run_mwem(
    Q: jax.Array,
    h: jax.Array,
    cfg: MWEMConfig,
    key: jax.Array,
    index=None,
    ledger: Optional[PrivacyLedger] = None,
    mesh=None,
) -> MWEMResult:
    """Run (Fast-)MWEM for ``cfg.T`` iterations.

    Args:
      Q: (m, U) query matrix with entries in [0, 1].
      h: (U,) true normalized histogram.
      cfg: engine configuration. ``mode="fast"`` requires ``index``
        (``driver="sharded"`` builds a per-shard one when ``index=None``).
        ``driver="auto"`` shards the run across devices when more than one
        is visible (or a ``mesh`` is passed) and the index has a per-shard
        structure (`ShardedIVFIndex`); otherwise it fuses the loop
        on-device whenever the index's query is traceable (all built-in
        indices — flat/IVF/LSH/NSW); host-only third-party indices fall
        back to the Python loop. ``cfg.use_pallas`` picks the fused scan's
        step body (megakernel vs classic — DESIGN.md §7).
      key: PRNG key.
      index: a k-MIPS index over the complement-augmented queries
        (see repro.mips); must expose ``query(v, k) -> (aug_idx, raw_scores)``
        and attributes ``approx_margin`` (c ≥ 0) and ``failure_mass`` (γ).
      mesh: device mesh for the sharded driver (forces ``driver="auto"``
        routing onto it; ignored by the fused/host drivers).

    ``Q`` may be a raw ``(m, U)`` array or any `core.workload.Workload`
    (`MarginalWorkload` runs factored end to end on the fused/host
    drivers; the sharded driver densifies — its documented fallback).
    """
    W = as_workload(Q)
    from repro.core.workload import _DENSIFY_LIMIT_BYTES
    densifiable = W.is_dense or W.dense_nbytes <= _DENSIFY_LIMIT_BYTES
    driver = _resolve_driver(cfg, index, mesh=mesh, shape=(W.m, W.U),
                             densifiable=densifiable)
    if driver == "sharded":
        from repro.core.distributed import run_mwem_sharded

        return run_mwem_sharded(W, h, cfg, key, mesh=mesh, index=index,
                                ledger=ledger)
    if driver == "fused":
        return run_mwem_fused(W, h, cfg, key, index=index, ledger=ledger)
    return _run_mwem_host(W, h, cfg, key, index=index, ledger=ledger)
