"""BENCHMARK.json resolves by name, and the command refuses a CPU."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
SPEC = harness.load_spec(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_every_cell_resolves_its_config_mix_and_metrics(cell):
    c, cfg, mix, per_layer = harness.resolve(SPEC, cell, ROOT)
    assert NAME.match(cell) and c["chips"] in (1, 4)
    conf = {x["name"]: x for x in SPEC["configs"]}[c["config"]]
    assert cfg["name"] == conf["name"]
    assert set(conf["reduced"]) <= set(cfg["reduced_from"])
    dep = harness.deployment(cfg)
    assert hasattr(dep, "Deployment")
    assert {"releases", "reads", "ladder", "budget"} <= set(mix)
    for m in per_layer:
        assert callable(harness.reader(m["name"]))
    assert per_layer, "every cell reports a per-layer metric"
    assert set(cfg["limits"]) >= {"release_replay_gap", "answer_gap",
                                  "ledger_gap"}


def test_every_metric_is_well_formed():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert 0 < m["bound"] <= 0.25 and m["source"] in ("host_clock",
                                                            "device_trace")
    cells = {c["name"] for c in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()


def test_run_cell_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = SPEC["workloads"][0]["name"]
    p = subprocess.run([sys.executable, "bench/run_cell.py", "--workload",
                        cell, "--seed", "1", "--seconds", "1", "--trace",
                        "0"], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    with pytest.raises(json.JSONDecodeError):
        json.loads(p.stdout.strip().splitlines()[-1] if p.stdout.strip()
                   else "")
