"""Compile the main-path Pallas kernels for a TPU v5e without the chip.

The TPU compiler is installed with JAX and compiles for a described,
unattached chip. Interpret mode cannot show what it refuses — blocks off
the (8, 128) tiling, slices of HBM that do not cover whole tiles,
primitives Mosaic cannot lower — so each kernel of the release service's
main path is compiled here at the chip smoke's widths (U = 2¹⁴, m = 2¹⁴
base rows, the IVF index over 2¹⁵ augmented rows, waves of 8), and the
compiled module must hold the kernel's ``tpu_custom_call``.

The release service's wave executables — the batched fused driver with
the kernels inside it — are compiled the same way, with the index tables
described at the smoke's sizes: they must arrive as arguments (a table
captured as a program constant ran a one-chip host out of memory), and
the smoke's own HLO parser must find the kernels in the compiled text.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""

import importlib.util
import math
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import (_callees, _operand_names,
                                _parse_computations, _shape_dims)
from repro.core.mwem import (MWEMConfig, MWEMState, _calibrate,
                             _fused_driver, _fused_statics)
from repro.core.workload import DenseWorkload, MarginalWorkload
from repro.kernels import route
from repro.mips.flat import FlatAbsIndex
from repro.mips.ivf import IVFIndex
from repro.kernels.ivf_probe import ops as ivf
from repro.kernels.mips_topk import ops as mt
from repro.kernels.mwem_step import ops as st

M = U = 2 ** 14
NLIST, CAP, CAP_PAD, NPROBE, K, B = 362, 182, 184, 10, 128, 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _step(fn, B_):
    return (lambda lw, p, ps, Q, sel, h, n: fn(
        lw, p, ps, Q, sel, h, n, rule="hardt", eta=0.1, interpret=False),
        [(B_, U), (B_, U), (B_, U), (M, U), ((B_,), jnp.int32), (U,),
         (B_,)])


# name → (function of traced arrays, argument shapes [(shape) | (shape,
# dtype)]); shapes are the chip smoke's
CASES = {
    "mips_topk": (lambda V, q: mt.mips_topk(V, q, K, interpret=False),
                  [(M, U), (U,)]),
    "mips_topk_abs_centroids": (
        lambda V, q: mt.mips_topk(V, q, NPROBE, interpret=False,
                                  absolute=True),
        [(NLIST, U), (U,)]),
    "mips_abs_topk": (lambda V, q: mt.mips_abs_topk(V, q, K, interpret=False),
                      [(M, U), (U,)]),
    "ivf_probe_topk": (
        lambda c, r, ce, q: ivf.ivf_probe_topk(c, r, ce, q, K, NPROBE,
                                               interpret=False),
        [(NLIST, U), (NLIST, CAP_PAD, U), ((NLIST, CAP), jnp.int32), (U,)]),
    "ivf_probe_topk_batch": (
        lambda c, r, ce, q: ivf.ivf_probe_topk_batch(c, r, ce, q, K, NPROBE,
                                                     interpret=False),
        [(NLIST, U), (NLIST, CAP_PAD, U), ((NLIST, CAP), jnp.int32),
         (B, U)]),
    "mwem_step": (
        lambda lw, p, ps, Q, sel, h, n: st.mwem_step(
            lw, p, ps, Q, sel, h, n, rule="hardt", eta=0.1, interpret=False),
        [(U,), (U,), (U,), (M, U), ((), jnp.int32), (U,), ()]),
    "mwem_step_batch": _step(st.mwem_step_batch, B),
    "mwem_step_batch_partial_wave": _step(st.mwem_step_batch, 3),
    "mwu_apply": (
        lambda lw, p, ps, r, h, n: st.mwu_apply(
            lw, p, ps, r, h, n, rule="hardt", eta=0.1, interpret=False),
        [(U,), (U,), (U,), (U,), (U,), ()]),
}


def _specs(shapes, sharding):
    out = []
    for s in shapes:
        shape, dtype = (s if len(s) == 2 and isinstance(s[0], tuple)
                        else (s, jnp.float32))
        out.append(jax.ShapeDtypeStruct(shape, dtype, sharding=sharding))
    return out


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    text = jax.jit(fn).lower(*_specs(shapes, one_chip)).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text


def test_marginal_gather_score_compiles_for_v5e(one_chip):
    W = MarginalWorkload.all_kway((2,) * 14, 3)          # U = 2¹⁴ cells
    Ws = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        W)
    args = _specs([(U,), ((300,), jnp.int32)], one_chip)
    text = jax.jit(lambda W, v, i: st.marginal_gather_score(
        W, v, i, interpret=False)).lower(Ws, *args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text


def _smoke_module():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _largest_constant(hlo_text: str) -> int:
    """Element count of the largest array constant in an HLO module."""
    dims = re.findall(r"= \w+\[([\d,]*)\][^ ]* constant\(", hlo_text)
    return max((math.prod(int(d) for d in ds.split(",") if d)
                for ds in dims), default=0)


def _table_contractions(hlo_text: str, dims: tuple) -> list:
    """Where each contraction over a ``dims``-shaped table runs, one entry
    per contraction: "branch" when the first loop body or conditional
    branch that encloses it (up through fusions and calls) is a branch
    computation, "body" when it is a while body. An operand whose trailing
    dims are ``dims`` counts too: a per-lane `cond` under `vmap` contracts
    a lane-broadcast copy of the table."""
    comps, _ = _parse_computations(hlo_text)
    callers = {}
    for name, ops in comps.items():
        for op in ops:
            for attr, callee in _callees(op):
                callers.setdefault(callee, []).append((attr, name))

    def places(comp):
        out = []
        for attr, parent in callers.get(comp, []):
            out += ([attr] if attr in ("branch", "body")
                    else places(parent))
        return out

    found = []
    for name, ops in comps.items():
        shapes = {op.name: _shape_dims(op.type_str)[1] for op in ops}
        for op in ops:
            if (op.opcode in ("dot", "convolution")
                    and any(shapes.get(o, ())[-len(dims):] == dims
                            for o in _operand_names(op))):
                found += places(name)
    return found


# the smoke's IVF wave: 2·M augmented rows (nlist 362, cap 182 — 184 in the
# cell-grouped layout), nprobe 10
IVF_TABLES = {"_v": ((2 * M, U),), "_cents": ((NLIST, U),),
              "_cells": ((NLIST, CAP), jnp.int32),
              "_cell_rows": ((NLIST, CAP_PAD, U),)}


@pytest.mark.parametrize("kind,lanes", [("ivf", 8), ("ivf", 4),
                                        ("flat", 8)])
def test_wave_driver_compiles_for_v5e(kind, lanes, one_chip, monkeypatch):
    monkeypatch.setattr(route, "default_interpret", lambda: False)
    S = (lambda shape, dtype=jnp.float32:
         jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip))
    W = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(DenseWorkload(np.zeros((1, 1)))),
        [S((M, U))])
    # a small index of the same kind supplies the static attributes; its
    # tables are replaced by descriptions at the smoke's sizes, so a driver
    # that closed over the index's own arrays instead of taking them as
    # arguments could not even be traced
    rng = np.random.default_rng(0)
    small = rng.integers(0, 2, (512, 128)).astype(np.float32)
    if kind == "ivf":
        index = IVFIndex(small, nlist=32, nprobe=NPROBE, use_pallas="always")
        state = {k: S(*v) for k, v in IVF_TABLES.items()}
        want = {"ivf_probe_scores", "mwem_step"}
    else:
        index = FlatAbsIndex(small, use_pallas="always")
        state = {"_w": W, "_q": W.Q}
        want = {"mwem_step"}     # a wave probes Q with one XLA matmul
    index.__dict__.update(state)
    cfg = MWEMConfig(eps=1.0, delta=1e-3, T=1000, mode="fast",
                     n_records=10 ** 6, use_pallas="always")
    fn, _ = _fused_driver(index, _fused_statics(cfg, _calibrate(cfg, M, U)),
                          batch_axes=(None, 0, 0, 0))
    state0 = MWEMState(log_w=S((lanes, U)), p_sum=S((lanes, U)))
    compiled = fn.lower(W, S((lanes, U)), state0,
                        S((lanes, 2), jnp.uint32), state).compile()
    text = compiled.as_text()
    assert _largest_constant(text) < 2 ** 20
    # the table the wave streams (IVF: the cell-grouped rows, flat: Q) is
    # a parameter of the executable
    largest = max(math.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(state))
    assert compiled.memory_analysis().argument_size_in_bytes >= largest
    assert set(_smoke_module().pallas_calls(text)) == want
    # the exhaustive overflow redo (Q @ v for every lane) runs only inside
    # the wave-level conditional's branch; the only contraction over Q left
    # in the loop body is the flat wave's probe
    where = _table_contractions(text, (M, U))
    assert "branch" in where
    assert where.count("body") == (1 if kind == "flat" else 0)
