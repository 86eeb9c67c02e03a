"""Run one cell of the benchmark on the chip this process is started on.

    python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
(`BENCHMARK.json`, `bench/harness.py`). The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the end-to-end metrics, or with ``--trace 1`` the per-layer ones),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared with the plain reference beside its limit. The same
numbers end standard error. Without a TPU, or with fewer chips than the
cell asks for, the run exits 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START, ROOT)
    except harness.NoChip as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2
    harness.print_checks(out)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
