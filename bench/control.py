"""Readings for the limits of a cell's comparison, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...] [--control low|high|default]

Runs the cell once per seed in this one process (data, service and
window each time; the compile cache is shared) and prints every number
the comparison reads, per seed, and the largest and smallest over the
seeds.

``--control`` puts the plain reference in the program's place one
precision step below the configuration's (`linq_reference.CONTROLS`):
each wave's releases come from `linq_reference.mechanism_low`, the
probe the checks call from `linq_reference.probe_low`, and each read
from `linq_reference.answer_low`. ``low`` (bf16 state and arithmetic,
``HIGH`` products) is the control the comparison has to find not
correct; ``high`` and ``default`` step only the products down. The
benchmark's own runs never run this file.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from contextlib import contextmanager
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


@contextmanager
def reference_in_place(control: str = "low"):
    """Swap the program's wave results, probe and reads for the
    reference's at the precision of ``CONTROLS[control]``."""
    import numpy as np

    from bench.deployments import linq_reference as ref
    from bench.deployments.linq_release import Deployment
    from repro.serve import release_service as rs
    from repro.serve.session import Answer, TenantSession

    launch, finish, answer = (rs.launch_mwem_batch, rs.finish_mwem_batch,
                              TenantSession.answer)
    probe = Deployment.probe

    def launch_ref(Q, h, cfg, keys, index=None):
        pending = launch(Q, h, cfg, keys, index=index)
        pending.ref_keys = keys
        return pending

    def finish_ref(pending, ledgers=None):
        res = finish(pending, ledgers=ledgers)
        cfg = pending.cfg
        p, err, sel = ref.mechanism_low(
            pending.W.Q, pending.h, pending.ref_keys, T=cfg.T, eps=cfg.eps,
            delta=cfg.delta, n_records=cfg.n_records, control=control)
        res.p_hat, res.final_errors = p, np.asarray(err)
        res.selected = np.asarray(sel)
        return res

    def answer_ref(self, q, release_id=None):
        rel = self._release(release_id)
        return Answer(ref.answer_low(q, rel.p_hat, control), cached=False,
                      release_id=rel.release_id, fingerprint="")

    def probe_ref(self, V):
        return ref.probe_low(self.Q, V, self.k, control)

    rs.launch_mwem_batch, rs.finish_mwem_batch = launch_ref, finish_ref
    TenantSession.answer = answer_ref
    Deployment.probe = probe_ref
    try:
        yield
    finally:
        rs.launch_mwem_batch, rs.finish_mwem_batch = launch, finish
        TenantSession.answer = answer
        Deployment.probe = probe


def readings(cell_name: str, seeds, seconds: float, control=None,
             require_chip: bool = True, overrides=None, say=print) -> dict:
    """{seed: {check: value}}, and the per-check largest and smallest
    under "max" and "min". ``control`` names one of
    `linq_reference.CONTROLS`, or is None for the program."""
    from contextlib import nullcontext

    from bench import harness

    cell, cfg, mix, _ = harness.resolve(harness.load_spec(), cell_name)
    cfg.update(overrides or {})
    out = {}
    for seed in seeds:
        with reference_in_place(control) if control else nullcontext():
            res = harness.run_cell(cell, cfg, mix, [], seed, seconds, False,
                                   time.perf_counter(),
                                   require_chip=require_chip, say=say)
        out[seed] = {k: c["value"] for k, c in res["checks"].items()}
        out[seed]["correct"] = res["correct"]
        say(f"seed {seed}: {json.dumps(out[seed])}")
    names = [k for k in cfg["limits"]]
    out["max"] = {k: max((out[s][k] for s in seeds
                          if out[s][k] is not None), default=None)
                  for k in names}
    out["min"] = {k: min((out[s][k] for s in seeds
                          if out[s][k] is not None), default=None)
                  for k in names}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", choices=("low", "high", "default"),
                    default=None)
    args = ap.parse_args(argv)
    from bench import harness

    try:
        res = readings(args.workload, args.seeds, args.seconds, args.control,
                       say=lambda s: print(s, flush=True))
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    print(json.dumps({"control": args.control, "max": res["max"],
                      "min": res["min"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
