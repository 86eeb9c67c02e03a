"""Run one cell several times, one process per run, and summarise spreads.

    python3 bench/repeat.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...] [--sets 2] [--trace 0] [--out FILE]

Each run is ``bench/run_cell.py`` in a fresh process (this parent never
touches JAX, so the child holds the chip). With ``--sets 2`` the seeds
run twice, as two sets. For each end-to-end metric it prints each set's
median and spread — the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median — and
appends every result line to ``--out``.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def spread(values) -> float:
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    results = []
    for s in range(args.sets):
        for seed in args.seeds:
            t0 = time.perf_counter()
            p = subprocess.run(
                [sys.executable, str(ROOT / "bench" / "run_cell.py"),
                 "--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(args.seconds), "--trace",
                 str(args.trace)], cwd=ROOT, capture_output=True, text=True)
            wall = time.perf_counter() - t0
            lines = p.stdout.strip().splitlines()
            try:
                res = json.loads(lines[-1])
            except (IndexError, json.JSONDecodeError):
                res = {"correct": None}
            res.update(set=s, seed=seed, rc=p.returncode, wall_s=wall)
            results.append(res)
            print(json.dumps({k: res.get(k) for k in
                              ("set", "seed", "rc", "wall_s", "correct")}),
                  flush=True)
            print("\n".join(lines[:-1]), flush=True)
            if p.returncode:
                print(p.stderr[-3000:], flush=True)
            else:
                print("\n".join(p.stderr.strip().splitlines()[-6:]),
                      flush=True)
            if args.out:
                with open(args.out, "a") as fh:
                    fh.write(json.dumps(res) + "\n")
    ok = [r for r in results if r.get("metrics")]
    names = sorted({k for r in ok for k in r["metrics"]})
    for s in range(args.sets):
        runs = [r for r in ok if r["set"] == s]
        for k in names:
            v = [r["metrics"][k]["value"] for r in runs if k in r["metrics"]]
            if len(v) >= 2:
                print(f"set {s} {k}: median {statistics.median(v)} "
                      f"spread {spread(v)} values {v}", flush=True)
    return 0 if all(r["rc"] == 0 for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
