"""Benchmark runner — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and writes a ``BENCH_results.json``
artifact (per-bench rows — iter/call microseconds plus the derived column
carrying rows_scored / wave-throughput / speedup metrics — and wall-clock),
which CI uploads so the perf trajectory is tracked across PRs. ``--full``
runs the paper-scale sweeps; the default quick mode keeps the whole suite
CPU-friendly.
"""

from __future__ import annotations

import argparse
import json
import platform
import sys

from repro.obs import clock


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma-separated bench names to run")
    ap.add_argument("--json", default="BENCH_results.json",
                    help="path of the results artifact ('' disables)")
    args, _ = ap.parse_known_args()
    quick = not args.full

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (bench_distributed, bench_error_parity,
                            bench_ivf_probe, bench_linear_queries, bench_lp,
                            bench_margin, bench_marginals, bench_mwem_step,
                            bench_n_ablation, bench_release_service,
                            bench_streaming, roofline_report)
    from benchmarks.common import print_rows

    benches = {
        "linear_queries": bench_linear_queries,
        "error_parity": bench_error_parity,
        "lp": bench_lp,
        "margin": bench_margin,
        "n_ablation": bench_n_ablation,
        "release_service": bench_release_service,
        "streaming": bench_streaming,
        "distributed": bench_distributed,
        "ivf_probe": bench_ivf_probe,
        "marginals": bench_marginals,
        "mwem_step": bench_mwem_step,
        "roofline": roofline_report,
    }
    selected = [s for s in args.only.split(",") if s] or list(benches)

    results: dict = {}
    print("name,us_per_call,derived")
    for name in selected:
        mod = benches[name]
        t0 = clock.perf_counter()
        try:
            rows = mod.run(quick=quick)
            print_rows(rows)
            dt = clock.perf_counter() - t0
            results[name] = {"rows": rows, "seconds": round(dt, 2)}
            print(f"# {name}: {len(rows)} rows in {dt:.1f}s",
                  file=sys.stderr)
        except Exception as e:  # keep the suite running; fail at the end
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}")
            results[name] = {"rows": [], "seconds": round(clock.perf_counter() - t0, 2),
                             "error": f"{type(e).__name__}: {e}"}

    if args.json:
        import jax

        artifact = {
            "schema": 1,
            "quick": quick,
            "generated_at": clock.timestamp(),
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "platform": platform.platform(),
            "benches": results,
        }
        try:  # obs snapshot: mechanism telemetry + serving latencies the
            # benches accumulated in the default registry during this run
            from repro.obs.metrics import default_registry

            artifact["metrics"] = default_registry().snapshot()
        except Exception as e:  # never let obs break the artifact
            artifact["metrics"] = {"error": f"{type(e).__name__}: {e}"}
        with open(args.json, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"# wrote {args.json}", file=sys.stderr)

    failed = [n for n, r in results.items() if "error" in r]
    if failed:
        # every selected bench ran (errors don't stop the suite), but a
        # crashed bench must still fail the invocation — CI would otherwise
        # go green with zero coverage of the section it smoke-tests
        print(f"# FAILED benches: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
