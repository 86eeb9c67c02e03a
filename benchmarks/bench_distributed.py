"""Sharded driver benchmark: forced host device counts 1/2/4/8,
exhaustive-vs-lazy scored rows, wire, and time per iteration.

Each device count runs in a subprocess (``XLA_FLAGS=--xla_force_host_
platform_device_count=N`` must be set before JAX initializes) that times
`run_mwem_sharded` in both modes on the same workload and lowers the
single-iteration cell for the HLO collective-byte (wire) count. The paper's
claim at this tier: lazy mode scores strictly fewer rows per iteration than
the exhaustive Θ(m) baseline, at less collective wire on a model-sharded
mesh.

The children are CPU processes (``JAX_PLATFORMS=cpu`` is set for them
explicitly, so none reaches for a chip the parent may hold); the rows
name the platform they ran on.

Rows: ``distributed/cpu/d{N}/{mode}`` with per-iteration execution µs on
forced CPU host devices;
derived packs ``rows=<scored rows/iter>;wire=<collective bytes/iter>;
sublinear=<lazy rows < exhaustive rows>``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import row

_REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = """
    import json, time
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import MWEMConfig, run_mwem_sharded
    from repro.core.distributed import (make_mwem_iteration,
                                        shard_selection_params)
    from repro.core.queries import gaussian_histogram, random_binary_queries
    from repro.mips import ShardedIVFIndex
    from repro.launch.mesh import make_mesh
    from repro.analysis.hlo import analyze_hlo

    d, m, U, T = {devices}, {m}, {U}, {T}
    model = 2 if d >= 2 else 1
    n_data = d // model
    mesh = make_mesh((n_data, model), ("data", "model"))
    key = jax.random.PRNGKey(0)
    kh, kq = jax.random.split(key)
    n_records = 3000
    h = gaussian_histogram(kh, n_records, U)
    Q = random_binary_queries(kq, m, U)
    idx = ShardedIVFIndex(Q, n_shards=n_data, seed=0)

    out = {{}}
    for mode, cfg in (
        ("exhaustive", MWEMConfig(T=T, mode="exact", n_records=n_records)),
        ("lazy", MWEMConfig(T=T, mode="fast", n_records=n_records)),
    ):
        index = idx if mode == "lazy" else None
        run_mwem_sharded(Q, h, cfg, key, mesh=mesh, index=index)  # compile
        t0 = clock.perf_counter()
        res = run_mwem_sharded(Q, h, cfg, key, mesh=mesh, index=index)
        dt = clock.perf_counter() - t0
        m_loc = m // n_data
        k_loc, tail_cap = shard_selection_params(m_loc, idx)  # == the run's
        fn = make_mwem_iteration(
            mesh, m=m, U=U, nlist=idx.nlist, cap=idx.cap, nprobe=idx.nprobe,
            k_loc=k_loc, tail_cap=tail_cap,
            scale=20.0, eta=0.05, mode=mode, multi_pod=False,
            fallback=False)  # hot-path wire; the redo branch is e^-sqrt(m) rare
        args = (
            jax.ShapeDtypeStruct((m, U), jnp.float32),
            jax.ShapeDtypeStruct((n_data, idx.nlist, U), jnp.float32),
            jax.ShapeDtypeStruct((n_data, idx.nlist, idx.cap), jnp.int32),
            jax.ShapeDtypeStruct((U,), jnp.float32),
            jax.ShapeDtypeStruct((U,), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        )
        with mesh:
            compiled = jax.jit(fn).lower(*args).compile()
        out[mode] = dict(
            iter_us=dt / T * 1e6,
            rows=float(np.mean(res.n_scored)),
            wire=analyze_hlo(compiled.as_text()).collective_bytes,
            err=res.final_error,
        )
    print("BENCH" + json.dumps(out))
"""


def _probe(devices: int, m: int, U: int, T: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO_SRC
    script = textwrap.dedent(_SCRIPT.format(devices=devices, m=m, U=U, T=T))
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"d={devices}: {out.stderr[-2000:]}")
    line = [l for l in out.stdout.splitlines() if l.startswith("BENCH")][-1]
    return json.loads(line[len("BENCH"):])


def run(quick: bool = True):
    m, U, T = (2048, 64, 6) if quick else (32768, 128, 10)
    rows = []
    for devices in (1, 2, 4, 8):
        r = _probe(devices, m, U, T)
        sublinear = r["lazy"]["rows"] < r["exhaustive"]["rows"]
        for mode in ("exhaustive", "lazy"):
            rows.append(row(
                f"distributed/cpu/d{devices}/{mode}", r[mode]["iter_us"],
                f"rows={r[mode]['rows']:.0f};wire={r[mode]['wire']:.0f};"
                f"sublinear={sublinear}"))
    return rows


if __name__ == "__main__":
    for r in run(quick=True):
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")
