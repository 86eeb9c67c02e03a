"""Public jit'd wrappers for the fused MWEM-step megakernel.

Dispatch contract (the drivers rely on it): every wrapper takes the full
row table plus the winner id — selection, lazy-EM and the overflow
`lax.cond` happen *before* this seam — and every wrapper degrades to
`ref.mwem_step_ref` when `mwem_step_supported` says the shape cannot take
the kernel route (U not lane-aligned, the whole-U working set would not
fit VMEM, or the row table is not f32 in whole 8-row windows). The ref is
op-for-op the host `_mwu_step` math. Each call records its route
(`kernels.route`), so a run that must have used the kernel can check that
the gate never sent it to the ref.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.faults import fault_site
from repro.kernels import route
from repro.kernels.mwem_step.mwem_step import (ROW_TILE,
                                               lane_block,
                                               marginal_gather_score_pallas,
                                               mwem_step_pallas)
from repro.kernels.mwem_step.ref import UPDATE_RULES, mwem_step_ref
from repro.obs.trace import scope as obs_scope
from repro.precision import dot

# Whole-U residency budget: a program keeps (nb, U) f32 blocks live — 3
# state in + h + 3 out, each double-buffered by the pipeline — plus the
# row-fetch scratch (nb 8-row windows and nb rows), so peak VMEM ≈
# (2·7 + ROW_TILE + 1)·4·nb·U bytes. Cap at three quarters of the 16 MiB
# default scoped VMEM of a v5e core.
_VMEM_BUDGET_BYTES = 12 * 2**20


def mwem_step_supported(U: int, batch: int = 1, n_rows: int = ROW_TILE,
                        dtype=jnp.float32) -> bool:
    """Static gate for the kernel route (the drivers' automatic fallback).

    The kernel is whole-U single-block, so U must fill TPU lanes exactly
    (padding would enter max/sum) and one program's lanes must fit VMEM.
    The row table (``n_rows`` rows of ``dtype``) is read in aligned f32
    8-row windows, so it must hold f32 rows in whole windows.
    """
    return (U % 128 == 0 and n_rows % ROW_TILE == 0
            and jnp.dtype(dtype) == jnp.float32
            and ((2 * 7 + ROW_TILE + 1) * 4 * lane_block(batch) * U
                 <= _VMEM_BUDGET_BYTES))


def _table_ok(q_rows, batch: int = 1) -> bool:
    return mwem_step_supported(q_rows.shape[1], batch, q_rows.shape[0],
                               q_rows.dtype)


def _check_rule(rule: str) -> None:
    if rule not in UPDATE_RULES:
        raise ValueError(f"unknown update rule {rule!r}")


def _step(name, log_w, p, p_sum, q_rows, sel, h, noise, rule, eta,
          interpret):
    """(B, U) state, (B,) sel/noise, (1 | B, U) h → the kernel call."""
    interpret = route.pallas_route(name, interpret)
    with obs_scope(f"kernel/{name}"):
        return mwem_step_pallas(
            sel.astype(jnp.int32), log_w, p, p_sum, q_rows, h,
            noise.astype(jnp.float32).reshape(-1, 1), rule=rule, eta=eta,
            interpret=interpret)


@partial(jax.jit, static_argnames=("rule", "eta", "interpret"))
def mwem_step(log_w: jax.Array, p: jax.Array, p_sum: jax.Array,
              q_rows: jax.Array, sel: jax.Array, h: jax.Array,
              noise: jax.Array, *, rule: str, eta: float,
              interpret: bool | None = None):
    """Single-lane fused step: ``(log_w', p', p_sum')`` from winner ``sel``.

    Args:
      log_w/p/p_sum: (U,) carried state (``p == softmax(log_w)``).
      q_rows: (R, U) row table; only the 8-row window holding row ``sel``
        is copied on the kernel route.
      sel: scalar int winner id into ``q_rows``.
      h: (U,) histogram.
      noise: scalar realized Laplace noise (0.0 for ``rule="paper"``).
    """
    fault_site("kernel.mwem_step")
    _check_rule(rule)
    if not _table_ok(q_rows):
        route.note("mwem_step", route.REF)
        return mwem_step_ref(log_w, p, p_sum, q_rows[sel], h, noise,
                             rule=rule, eta=eta)
    out = _step("mwem_step", log_w[None], p[None], p_sum[None], q_rows,
                jnp.reshape(sel, (1,)), h[None], jnp.reshape(noise, (1,)),
                rule, eta, interpret)
    return tuple(o[0] for o in out)


@partial(jax.jit, static_argnames=("rule", "eta", "interpret"))
def mwem_step_batch(log_w: jax.Array, p: jax.Array, p_sum: jax.Array,
                    q_rows: jax.Array, sel: jax.Array, h: jax.Array,
                    noise: jax.Array, *, rule: str, eta: float,
                    interpret: bool | None = None):
    """Wave-batched fused step over B lanes.

    ``log_w/p/p_sum`` are (B, U); ``sel``/``noise`` are (B,); ``h`` is a
    shared (U,) or per-lane (B, U) histogram. Lane b computes `mwem_step`
    for its slice (lanes never mix).
    """
    fault_site("kernel.mwem_step")
    _check_rule(rule)
    if not _table_ok(q_rows, log_w.shape[0]):
        route.note("mwem_step_batch", route.REF)
        h_ax = 0 if h.ndim == 2 else None
        step = partial(mwem_step_ref, rule=rule, eta=eta)
        return jax.vmap(step, in_axes=(0, 0, 0, 0, h_ax, 0))(
            log_w, p, p_sum, q_rows[sel], h, noise)
    h2 = h if h.ndim == 2 else h[None]
    return _step("mwem_step_batch", log_w, p, p_sum, q_rows, sel, h2, noise,
                 rule, eta, interpret)


@partial(jax.jit, static_argnames=("interpret",))
def marginal_gather_score(W, v: jax.Array, aug_idx: jax.Array, *,
                          interpret: bool | None = None):
    """Complement-augmented candidate scores for factored workloads:
    ``sign · ⟨q_{j % m}, v⟩`` for ``aug_idx`` (C,) (query ``j % m``, sign
    +1 for ``j < m`` else −1 — the §3.4 closure). Candidate rows are
    rebuilt in-kernel from per-clique mixed-radix parameters — offsets +
    implicit one-hot products, never an ``(m, U)`` gather.

    ``W`` is a `core.workload.MarginalWorkload` (a pytree — flows through
    jit as an argument). The XLA side only gathers the (C,) candidate
    clique parameter rows (int32 scalars) before handing them to the
    scalar-prefetch grid. Unsupported shapes fall back to the workload's
    traceable `score_in_graph`.
    """
    m = W.m
    base = (aug_idx % m).astype(jnp.int32)
    sign = jnp.where(aug_idx < m, 1.0, -1.0).astype(jnp.float32)
    if not mwem_step_supported(W.U):
        route.note("marginal_gather_score", route.REF)
        return W.score_in_graph(v, aug_idx)
    cl = W.q_clique[base]
    tab = jnp.concatenate(
        [W.cl_dstride[cl], W.cl_card[cl], W.cl_stride[cl]], axis=1)
    interpret = route.pallas_route("marginal_gather_score", interpret)
    with obs_scope("kernel/marginal_gather_score"):
        return marginal_gather_score_pallas(
            tab, W.q_offset[base], sign, v, kmax=W.kmax, interpret=interpret)


@partial(jax.jit, static_argnames=("rule", "eta", "interpret"))
def mwu_apply(log_w: jax.Array, p: jax.Array, p_sum: jax.Array,
              q_row: jax.Array, h: jax.Array, noise: jax.Array, *,
              rule: str, eta: float, interpret: bool | None = None):
    """Materialized-row variant: the sharded driver's model tail, where the
    winner row arrives via a one-hot psum instead of an id into a local
    table. Same kernel body, ``sel = [0]`` into the row zero-padded to one
    8-row window."""
    fault_site("kernel.mwem_step")
    _check_rule(rule)
    table = jnp.pad(q_row[None], ((0, ROW_TILE - 1), (0, 0)))
    if not _table_ok(table):
        route.note("mwu_apply", route.REF)
        return mwem_step_ref(log_w, p, p_sum, q_row, h, noise,
                             rule=rule, eta=eta)
    out = _step("mwu_apply", log_w[None], p[None], p_sum[None], table,
                jnp.zeros((1,), jnp.int32), h[None],
                jnp.reshape(noise, (1,)), rule, eta, interpret)
    return tuple(o[0] for o in out)
