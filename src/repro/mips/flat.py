"""Flat (exact, linear-scan) MIPS index — the Θ(m) baseline.

On TPU this path is the `repro.kernels.mips_topk` Pallas kernel; on CPU the
jnp reference executes the same math. Exact ⇒ approx_margin = 0,
failure_mass = 0. Both indices are fully traceable (`supports_in_graph`),
so the fused MWEM driver inlines them into its scan body.

All search paths are module-level jitted functions: instances sharing
shapes share one compiled program (building a second index never
retraces — the per-tenant recompilation fix, see tests/test_mips.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.workload import Workload, as_workload
from repro.faults import fault_site
from repro.mips.base import resolve_pallas
from repro.precision import dot as _dot


@partial(jax.jit, static_argnames=("k", "pallas"))
def _flat_query(vectors, q, k: int, pallas: bool):
    if pallas:
        from repro.kernels.mips_topk import ops as topk_ops

        return topk_ops.mips_topk(vectors, q, k)
    scores = _dot(vectors, q)
    top_s, top_i = jax.lax.top_k(scores, k)
    return top_i.astype(jnp.int32), top_s


@partial(jax.jit, static_argnames=("k", "pallas"))
def _flat_abs_query(Qm, v, k: int, pallas: bool):
    if pallas:
        from repro.kernels.mips_topk import ops as topk_ops

        return topk_ops.mips_abs_topk(Qm, v, k)
    aug, top_a, _ = _flat_abs_query_scores(Qm, v, k)
    return aug, top_a


@partial(jax.jit, static_argnames=("k",))
def _flat_abs_query_scores(Qm, v, k: int):
    m = Qm.shape[0]
    s = _dot(Qm, v)
    a = jnp.abs(s)
    top_a, top_i = jax.lax.top_k(a, k)
    aug = jnp.where(s[top_i] >= 0, top_i, top_i + m)
    return aug.astype(jnp.int32), top_a, s


@partial(jax.jit, static_argnames=("k",))
def _flat_abs_workload_scores(W, v, k: int):
    """`_flat_abs_query_scores` over an implicit workload: ``W`` is a
    `core.workload.Workload` pytree, so factored families probe without a
    row table. For dense workloads `probe_scores` is the same ``Q @ v`` —
    and for factored ones within their parity block it is the same-shaped
    implicit-row matmul, keeping dense-vs-factored selections bitwise."""
    m = W.m
    s = W.probe_scores(v)
    a = jnp.abs(s)
    top_a, top_i = jax.lax.top_k(a, k)
    aug = jnp.where(s[top_i] >= 0, top_i, top_i + m)
    return aug.astype(jnp.int32), top_a, s


@partial(jax.jit, static_argnames=("k",))
def _flat_abs_query_batch(Qm, Vb, k: int):
    """Whole-wave exhaustive |·| probe: one (B × dim) @ (dim × m) MXU
    matmul reads Q once for every lane — already the amortization the
    batched IVF kernel buys, so no Pallas variant is needed here."""
    m = Qm.shape[0]
    s = _dot(Vb, Qm.T)                                  # (B, m)
    top_a, top_i = jax.lax.top_k(jnp.abs(s), k)
    aug = jnp.where(jnp.take_along_axis(s, top_i, axis=1) >= 0,
                    top_i, top_i + m)
    return aug.astype(jnp.int32), top_a


class FlatIndex:
    """Exact top-k by full matvec + top_k over arbitrary vectors."""

    approx_margin = 0.0
    failure_mass = 0.0
    supports_in_graph = True

    def __init__(self, vectors, use_pallas: str = "auto"):
        self._v = jnp.asarray(vectors, jnp.float32)
        self.n, self.dim = self._v.shape
        self._use_pallas = use_pallas

    def _resolve_pallas(self) -> bool:
        return resolve_pallas(self._use_pallas)

    def query(self, v, k: int):
        return self.query_in_graph(jnp.asarray(v, jnp.float32), k)

    def query_in_graph(self, v, k: int):
        fault_site("index.probe")
        return _flat_query(self._v, v, k, self._resolve_pallas())

    def query_cost(self, k: int) -> int:
        return self.n


class FlatAbsIndex:
    """Exact top-k of |⟨q_i, v⟩| without materializing the complement rows.

    Returns *augmented* ids (j < m ⇒ +⟨q_j, v⟩; j ≥ m ⇒ −⟨q_{j−m}, v⟩),
    matching the convention of `augment_complement`. On TPU the scan runs
    through the streaming `mips_abs_topk` kernel — one pass over Q scores
    both signs (half the HBM traffic of scoring q and −q).
    """

    approx_margin = 0.0
    failure_mass = 0.0
    supports_in_graph = True

    def __init__(self, Q, use_pallas: str = "auto"):
        """``Q``: a raw (m, U) matrix or any `core.workload.Workload` —
        factored workloads probe through their implicit score primitives
        (no dense table is ever built; the Pallas row-streaming kernel,
        which needs explicit rows, is unavailable for them)."""
        self._w = as_workload(Q)
        self._q = self._w.Q if self._w.is_dense else None
        self.m, self.dim = self._w.m, self._w.U
        self.n = 2 * self.m
        self._use_pallas = use_pallas

    def _resolve_pallas(self) -> bool:
        if not self._w.is_dense:
            if self._use_pallas == "always":
                raise ValueError(
                    "use_pallas='always' needs a dense row table; factored "
                    "workloads probe via their implicit score path")
            return False
        return resolve_pallas(self._use_pallas)

    @property
    def supports_batch_probe(self) -> bool:
        return self._w.is_dense

    def query(self, v, k: int):
        return self.query_in_graph(jnp.asarray(v, jnp.float32), k)

    def query_in_graph(self, v, k: int):
        fault_site("index.probe")
        if not self._w.is_dense:
            aug, top_a, _ = _flat_abs_workload_scores(self._w, v, k)
            return aug, top_a
        return _flat_abs_query(self._q, v, k, self._resolve_pallas())

    def query_in_graph_batch(self, Vb, k: int):
        fault_site("index.probe")
        if not self._w.is_dense:
            aug, top_a, _ = jax.vmap(
                lambda q: _flat_abs_workload_scores(self._w, q, k))(Vb)
            return aug, top_a
        return _flat_abs_query_batch(self._q, Vb, k)

    @property
    def has_full_scores(self) -> bool:
        """The fused driver prefers `query_in_graph_with_scores` when the
        probe materializes the score vector anyway (the jnp path); the
        streaming Pallas kernel exists precisely to avoid that, so on TPU
        the plain probe + re-gather is the right trade."""
        return not self._resolve_pallas()

    def query_in_graph_with_scores(self, v, k: int):
        """Exhaustive probe that also returns the full (m,) signed score
        vector — the fused driver reuses it for tail scoring and the
        overflow fallback instead of re-touching Q (DESIGN.md §2)."""
        fault_site("index.probe")
        if not self._w.is_dense:
            return _flat_abs_workload_scores(self._w, v, k)
        return _flat_abs_query_scores(self._q, v, k)

    def query_cost(self, k: int) -> int:
        return self.m
