"""Mechanism telemetry: host-side aggregation of the drivers' scan traces.

Every driver (host / fused / waved / sharded MWEM, both LP solvers)
already returns per-iteration traces — `n_scored`, `overflow`,
selection ids — stacked on device and transferred once. This module
turns that free data into the numbers the paper's claim is about:

* `overflow_rate` — fraction of iterations that fell back from the
  lazy Θ(√m)-expected path to the exhaustive Θ(m) Gumbel-max;
* `n_scored_mean/max/total` — actual scored-rows cost per iteration;
* `lazy_fraction` — fraction of iterations resolved without scoring
  the full candidate set;
* `sqrt_m_ratio` — mean scored rows ÷ √m: ~O(1) when the sublinear
  claim holds, → √m when every iteration degenerates to exhaustive;
* `wave_redo_count` (waved driver only) — scan iterations at which the
  wave's exhaustive redo ran: those where any lane overflowed. Published
  as `mechanism_wave_redo_total` and, ÷ T, `mechanism_wave_redo_rate`.

`aggregate_traces` is pure (no registry side effects, always runs, so
the `telemetry` record on results exists even with obs disabled —
it's part of the result, like `n_scored` itself). `publish` pushes a
record into the registry and is gated on `trace.enabled()`.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional

import numpy as np

from repro.obs import trace as _trace
from repro.obs.metrics import MetricsRegistry, default_registry


@dataclass(frozen=True)
class MechanismTelemetry:
    """Structured per-run (or per-batch) mechanism statistics."""

    workload: str  # "mwem" | "lp_scalar" | "lp_dual"
    driver: str  # "host" | "fused" | "waved" | "sharded"
    mode: str  # "exact" | "fast"
    m: int  # candidate-set size the mechanism scores over
    T: int  # iterations per lane
    lanes: int  # batch lanes aggregated into this record
    n_scored_total: int
    n_scored_mean: float
    n_scored_max: int
    overflow_count: int
    overflow_rate: float  # overflows / (T * lanes)
    lazy_fraction: float  # iterations that scored < m rows
    sqrt_m_ratio: float  # n_scored_mean / sqrt(m)
    total_seconds: float
    amortized: bool  # True when total_seconds covers >1 lane / whole scan
    wave_redo_count: Optional[int] = None  # waved driver: iterations redone

    @property
    def wave_redo_rate(self) -> Optional[float]:
        if self.wave_redo_count is None:
            return None
        return self.wave_redo_count / self.T if self.T else 0.0

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def aggregate_traces(
    *,
    workload: str,
    driver: str,
    mode: str,
    m: int,
    n_scored,
    overflow_count: int,
    total_seconds: float,
    amortized: bool,
    lanes: int = 1,
    wave_redo_count: Optional[int] = None,
) -> MechanismTelemetry:
    """Fold stacked per-iteration traces into one `MechanismTelemetry`.

    `n_scored` accepts anything array-like — a host list (T,), a stacked
    device trace (T,), or a batched one (B, T); it is flattened, so pass
    `lanes` explicitly for batches.
    """
    ns = np.asarray(n_scored, dtype=np.int64).reshape(-1)
    iters = int(ns.size)
    total = int(ns.sum()) if iters else 0
    mean = float(ns.mean()) if iters else 0.0
    lazy = float((ns < int(m)).mean()) if iters and m > 0 else 0.0
    return MechanismTelemetry(
        workload=workload,
        driver=driver,
        mode=mode,
        m=int(m),
        T=iters // max(lanes, 1),
        lanes=int(lanes),
        n_scored_total=total,
        n_scored_mean=mean,
        n_scored_max=int(ns.max()) if iters else 0,
        overflow_count=int(overflow_count),
        overflow_rate=float(overflow_count) / iters if iters else 0.0,
        lazy_fraction=lazy,
        sqrt_m_ratio=mean / math.sqrt(m) if m > 0 else 0.0,
        total_seconds=float(total_seconds),
        amortized=bool(amortized),
        wave_redo_count=(None if wave_redo_count is None
                         else int(wave_redo_count)),
    )


def publish(
    tel: MechanismTelemetry, registry: Optional[MetricsRegistry] = None
) -> MechanismTelemetry:
    """Push one telemetry record into the registry (no-op when obs is off)."""
    if not _trace.enabled():
        return tel
    reg = registry if registry is not None else default_registry()
    labels = dict(workload=tel.workload, driver=tel.driver, mode=tel.mode)
    reg.counter("mechanism_runs_total", **labels).inc(tel.lanes)
    reg.counter("mechanism_iterations_total", **labels).inc(tel.T * tel.lanes)
    reg.counter("mechanism_overflow_total", **labels).inc(tel.overflow_count)
    reg.counter("mechanism_scored_rows_total", **labels).inc(tel.n_scored_total)
    reg.gauge("mechanism_overflow_rate", **labels).set(tel.overflow_rate)
    reg.gauge("mechanism_lazy_fraction", **labels).set(tel.lazy_fraction)
    reg.gauge("mechanism_sqrt_m_ratio", **labels).set(tel.sqrt_m_ratio)
    reg.histogram("mechanism_scored_rows_per_iter", **labels).observe(
        tel.n_scored_mean
    )
    reg.histogram("mechanism_run_seconds", **labels).observe(tel.total_seconds)
    if tel.wave_redo_count is not None:
        reg.counter("mechanism_wave_redo_total", **labels).inc(
            tel.wave_redo_count)
        reg.gauge("mechanism_wave_redo_rate", **labels).set(tel.wave_redo_rate)
    return tel


def record_run(
    *,
    workload: str,
    driver: str,
    mode: str,
    m: int,
    n_scored,
    overflow_count: int,
    total_seconds: float,
    amortized: bool,
    lanes: int = 1,
    wave_redo_count: Optional[int] = None,
    registry: Optional[MetricsRegistry] = None,
) -> MechanismTelemetry:
    """aggregate_traces + publish in one call — the driver-side entry point."""
    tel = aggregate_traces(
        workload=workload,
        driver=driver,
        mode=mode,
        m=m,
        n_scored=n_scored,
        overflow_count=overflow_count,
        total_seconds=total_seconds,
        amortized=amortized,
        lanes=lanes,
        wave_redo_count=wave_redo_count,
    )
    return publish(tel, registry=registry)
