"""One run of one cell: resolve it by name, drive it, measure, check.

Everything a cell is made of is found by name under ``bench/``:

* ``configs/<config>.json`` — the deployment's sizes, guarantees and the
  limits of its comparison; its ``"deployment"`` key names a module
  ``deployments/<deployment>.py`` (data, service, checks) with its plain
  reference beside it;
* ``traffic/<mix>.json`` — the parameters `bench.loadgen` reads;
* ``metrics/<metric>.py`` — one reader per per-layer metric.

Adding a cell, a mix or a metric adds files and entries; nothing here
changes.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from contextlib import nullcontext
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

from bench import loadgen, trace_reduce
from bench.peaks import peaks_for

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Context:
    """What a per-layer metric reader may look at."""

    cfg: dict
    mix: dict
    peaks: dict
    record: loadgen.Record
    trace: Optional[trace_reduce.Trace]


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(spec: dict, cell_name: str, root: Path = ROOT) -> tuple:
    """(cell, config, mix, per-layer metrics) of ``cell_name``."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no cell {cell_name!r} in BENCHMARK.json")
    cell = cells[cell_name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    per_layer = [m for m in spec["per_layer"]
                 if cell_name in m.get("workloads", [cell_name])]
    return cell, cfg, mix, per_layer


def reader(metric: str):
    """``read`` of ``metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{metric}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def deployment(cfg: dict):
    return importlib.import_module(f"bench.deployments.{cfg['deployment']}")


def check_chip(jax, chips: int) -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform}, not a TPU; the "
                     "benchmark has no CPU path")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")


def run_cell(cell: dict, cfg: dict, mix: dict, per_layer: list, seed: int,
             seconds: float, trace: bool, t_start: float,
             require_chip: bool = True, say=print) -> dict:
    """Drive one cell and return the result line's object."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    if require_chip:
        check_chip(jax, cell["chips"])
    timings: dict = {"start_s": time.perf_counter() - t_start}
    if require_chip:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = jax.devices()[0]
    peaks = peaks_for(dev.device_kind) if require_chip else {}

    dep_mod = deployment(cfg)
    tenants = loadgen.tenant_names(mix)
    dep = dep_mod.Deployment(cfg, mix, tenants, seed, timings)
    dep.warm(timings)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    marks = {}

    def window_start():
        marks["ready"] = time.perf_counter()
        if trace:
            jax.profiler.start_trace(trace_dir)
            marks["ann"] = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            marks["ann"].__enter__()

    record = loadgen.drive(
        dep, mix, seed, seconds, on_window_start=window_start,
        annotate=(jax.profiler.TraceAnnotation if trace
                  else lambda name: nullcontext()))
    if trace:
        marks["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))

    t0, t1 = record.window
    timings["warmup_s"] = marks["ready"] - t_start - sum(timings.values())
    setup_s = marks["ready"] - t_start
    rel = record.in_window()
    done = [r for r in rel if r.status == "done"]
    reads = record.window_reads()
    lat = [r.delivered - r.issued for r in done]
    read_lat = [(r.end - r.due) if r.ok else float("inf") for r in reads]
    lateness = [r.start - r.due for r in reads]
    say(f"setup: {json.dumps(timings)}")
    say(f"window_s: {t1 - t0}")
    say(f"releases_in_window: {len(done)} (failed {len(rel) - len(done)})")
    say(f"reads_in_window: {len(reads)} (failed "
        f"{sum(not r.ok for r in reads)})")
    say(f"generator_lateness_s: p50 {loadgen.quantile(lateness, 0.5)} "
        f"p99 {loadgen.quantile(lateness, 0.99)} max "
        f"{max(lateness, default=float('nan'))}")

    snap = dep.snapshot(record)
    say(f"routes: {json.dumps(snap['routes'])}")
    dep.free()          # the program's state goes before the float64 checks
    gc.collect()
    values = dep.check(snap, record)
    limits = cfg["limits"]
    for k in sorted(set(values) - set(limits)):
        say(f"{k}: {values[k]}")
    checks = {k: {"value": values.get(k), "limit": limits[k]}
              for k in limits}
    failed = (len(rel) - len(done)) + sum(not r.ok for r in reads)
    correct = (failed == 0 and bool(done) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values()))

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(rel) + len(reads),
           "failed": failed}
    if trace:
        tr = trace_reduce.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = trace_reduce.busy_seconds(tr)
        device["window_s"] = tr.window_s
        ctx = Context(cfg=cfg, mix=mix, peaks=peaks, record=record, trace=tr)
        metrics = {}
        for m in per_layer:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        out["breakdown"] = {
            "device_ops": trace_reduce.top_ops(tr),
            "idle_gaps": [[trace_reduce.host_activity(tr, a, b), b - a]
                          for a, b in trace_reduce.idle_gaps(tr)[:10]],
        }
    else:
        out["metrics"] = {
            "release_rate": {"value": len(done) / (t1 - t0),
                             "unit": "releases/s"},
            "release_p95_s": {"value": loadgen.quantile(lat, 0.95),
                              "unit": "s"},
            "answer_p99_ms": {"value": 1e3 * loadgen.quantile(read_lat, 0.99),
                              "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    out["device"] = device
    out["checks"] = checks
    return out


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        t_start: float, root: Path = ROOT) -> dict:
    cell, cfg, mix, per_layer = resolve(load_spec(root), cell_name, root)
    return run_cell(cell, cfg, mix, per_layer, seed, seconds, trace, t_start,
                    say=lambda s: print(s, flush=True))


def print_checks(out: dict, stream=sys.stderr) -> None:
    """Each number compared beside its limit, as the last lines."""
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=stream, flush=True)
