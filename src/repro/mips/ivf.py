"""IVF (inverted file) k-MIPS index — TPU adaptation (paper §H).

FAISS-style IVF partitions the vectors into ``nlist`` Voronoi cells and
searches the ``nprobe`` closest cells. The TPU version keeps cells as a
*padded, capacity-bounded* (nlist × cap) id table. Two search paths share
that structure (DESIGN.md §3):

* **XLA** — gather → one dense (nprobe·cap × dim) @ v matvec → top_k:
  fixed shapes, MXU-batched, but the gathered candidate matrix round-trips
  HBM.
* **Pallas** (``use_pallas``) — the fused `repro.kernels.ivf_probe`
  kernel: centroid top-nprobe through the streaming `mips_topk` kernel,
  then only the probed cells' rows stream HBM→VMEM via scalar-prefetched
  cell ids; the candidate matrix never exists in HBM. Requires the rows
  duplicated in cell-grouped layout (``cell_rows``, built lazily on first
  kernel query — cap_factor× extra HBM, the price of contiguous streams).

``query_in_graph_batch`` serves a whole wave of probes per call
(`supports_batch_probe`); the kernel route dedups cells probed by several
lanes so shared cells are read from HBM once and scoring is one MXU
matmul per streamed tile.

Balanced assignment at build time bounds the padding waste. Defaults
follow the paper: nlist = max(2√n, 20), nprobe = min(nlist/4, 10).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.faults import fault_site
from repro.mips.base import resolve_pallas
from repro.precision import dot as _dot


def _vectors_from(vectors, context: str) -> np.ndarray:
    """Densify-fallback for the geometric IVF families (documented in
    DESIGN.md §9): k-means centroids and balanced cell assignment need
    explicit row coordinates, so a `core.workload.Workload` is materialized
    here — or refused past the densify limit. Callers that index the
    complement-augmented row space apply `augment_complement` themselves,
    exactly as with raw matrices. Factored workloads that must scale past
    the limit use `mips.marginal.MarginalIVFIndex`, whose cells are the
    workload's own cliques."""
    from repro.core.workload import Workload

    if isinstance(vectors, Workload):
        return np.asarray(vectors.require_dense(context), np.float32)
    return np.asarray(vectors, np.float32)


def _kmeans(V: np.ndarray, nlist: int, iters: int, rng: np.random.Generator) -> np.ndarray:
    n = V.shape[0]
    cents = V[rng.choice(n, size=nlist, replace=False)].copy()
    sample = V if n <= 200_000 else V[rng.choice(n, size=200_000, replace=False)]
    s_norm2 = (sample * sample).sum(1)
    for _ in range(iters):
        # blockwise assignment: argmin ‖x−c‖² = argmin (‖c‖² − 2 x·c)
        c_norm2 = (cents * cents).sum(1)
        assign = np.empty(sample.shape[0], np.int32)
        bs = max(1, 2_000_000 // max(nlist, 1))
        for i in range(0, sample.shape[0], bs):
            d = c_norm2[None, :] - 2.0 * (sample[i:i + bs] @ cents.T)
            assign[i:i + bs] = np.argmin(d, axis=1)
        for c in range(nlist):
            members = sample[assign == c]
            if len(members):
                cents[c] = members.mean(0)
            else:  # re-seed empty cell
                cents[c] = sample[rng.integers(sample.shape[0])]
    return cents


def _balanced_assign(V: np.ndarray, cents: np.ndarray, cap: int) -> np.ndarray:
    """Greedy nearest-available-cell assignment, capacity ``cap`` per cell."""
    n, nlist = V.shape[0], cents.shape[0]
    c_norm2 = (cents * cents).sum(1)
    ncand = min(8, nlist)
    pref = np.empty((n, ncand), np.int32)
    best = np.empty(n, np.float32)
    bs = max(1, 2_000_000 // max(nlist, 1))
    for i in range(0, n, bs):
        d = c_norm2[None, :] - 2.0 * (V[i:i + bs] @ cents.T)
        p = np.argpartition(d, ncand - 1, axis=1)[:, :ncand]
        rows = np.arange(p.shape[0])[:, None]
        order = np.argsort(d[rows, p], axis=1)
        pref[i:i + bs] = p[rows, order]
        best[i:i + bs] = d[rows, p[rows, order]][:, 0]
    cells = np.full((nlist, cap), -1, np.int32)
    fill = np.zeros(nlist, np.int32)
    # Confident points (smallest best-distance) pick first.
    for idx in np.argsort(best):
        placed = False
        for c in pref[idx]:
            if fill[c] < cap:
                cells[c, fill[c]] = idx
                fill[c] += 1
                placed = True
                break
        if not placed:  # all preferred cells full → first cell with space
            c = int(np.argmin(fill))
            cells[c, fill[c]] = idx
            fill[c] += 1
    return cells


# Module-level jitted search paths: every IVFIndex instance with the same
# shapes/statics shares one compiled program (the per-instance closure the
# seed used retraced per tenant/index build).

def _query_impl(V, cents, cells, q, k: int, nprobe: int):
    cscores = _dot(cents, q)
    _, probe = jax.lax.top_k(cscores, nprobe)
    cand = cells[probe].reshape(-1)                    # (nprobe·cap,)
    valid = cand >= 0
    scores = _dot(V[jnp.clip(cand, 0)], q)
    scores = jnp.where(valid, scores, -jnp.inf)
    top_s, pos = jax.lax.top_k(scores, k)
    return cand[pos].astype(jnp.int32), top_s


_query_xla = jax.jit(_query_impl, static_argnames=("k", "nprobe"))


@jax.jit
def _group_rows(V, cells):
    rows = jnp.take(V, jnp.clip(cells, 0), axis=0) * (cells >= 0)[..., None]
    pad = (-cells.shape[1]) % 8
    return jnp.pad(rows, ((0, 0), (0, pad), (0, 0)))


@partial(jax.jit, static_argnames=("k", "nprobe"))
def _query_xla_batch(V, cents, cells, Vb, k: int, nprobe: int):
    return jax.vmap(
        lambda q: _query_impl(V, cents, cells, q, k, nprobe))(Vb)


class IVFIndex:
    supports_in_graph = True  # padded cells ⇒ fixed-shape, traceable search
    supports_batch_probe = True

    def __init__(self, vectors, nlist: int | None = None, nprobe: int | None = None,
                 cap_factor: float = 2.0, train_iters: int = 10, seed: int = 0,
                 approx_margin: float = 0.0, failure_mass: float | None = None,
                 use_pallas: str = "auto"):
        V = _vectors_from(vectors, "IVFIndex build")
        self.n, self.dim = V.shape
        self.nlist = min(nlist or max(int(2 * math.sqrt(self.n)), 20), self.n)
        self.nprobe = nprobe or max(1, min(self.nlist // 4, 10))
        self.cap = max(4, math.ceil(cap_factor * self.n / self.nlist))
        rng = np.random.default_rng(seed)
        cents = _kmeans(V, self.nlist, train_iters, rng)
        cells = _balanced_assign(V, cents, self.cap)
        self._v = jnp.asarray(V)
        self._cents = jnp.asarray(cents)
        self._cells = jnp.asarray(cells)
        self._use_pallas = use_pallas
        self._cell_rows = None  # the kernel route's cell-grouped row copy
        if resolve_pallas(use_pallas):
            self._rows_by_cell()
        self.approx_margin = approx_margin
        self.failure_mass = (1.0 / self.n) if failure_mass is None else failure_mass

    def _resolve_pallas(self) -> bool:
        return resolve_pallas(self._use_pallas)

    def _rows_by_cell(self) -> jax.Array:
        """(nlist, cap⌈8⌉, dim) rows in cell-grouped layout — the
        contiguous HBM blocks the kernel's scalar-prefetched index_map
        streams, the cap axis padded to the sublane multiple. Built once,
        as one fused program, so the device never holds more than the
        finished table beside ``V``. Usually built at __init__; the lazy
        rebuild (a flipped `use_pallas` knob) pins compile-time eval so a
        driver tracing through the index can never cache a tracer here."""
        if self._cell_rows is None:
            with jax.ensure_compile_time_eval():
                self._cell_rows = _group_rows(self._v, self._cells)
        return self._cell_rows

    def query(self, v, k: int):
        return self.query_in_graph(jnp.asarray(v, jnp.float32), k)

    def query_in_graph(self, v, k: int):
        fault_site("index.probe")
        if self._resolve_pallas():
            from repro.kernels.ivf_probe import ivf_probe_topk

            idx, scores, _ = ivf_probe_topk(
                self._cents, self._rows_by_cell(), self._cells, v, k,
                self.nprobe)
            return idx, scores
        return _query_xla(self._v, self._cents, self._cells, v, k,
                          self.nprobe)

    def query_in_graph_batch(self, Vb, k: int):
        """Probe a whole wave (B, dim) in one call → ((B, k) ids, scores).

        The kernel route reads cells probed by several lanes once; the XLA
        route is the vmapped single probe (bitwise per-lane parity)."""
        fault_site("index.probe")
        if self._resolve_pallas():
            from repro.kernels.ivf_probe import ivf_probe_topk_batch

            idx, scores, _ = ivf_probe_topk_batch(
                self._cents, self._rows_by_cell(), self._cells, Vb, k,
                self.nprobe)
            return idx, scores
        return _query_xla_batch(self._v, self._cents, self._cells, Vb, k,
                                self.nprobe)

    def query_cost(self, k: int) -> int:
        return self.nlist + self.nprobe * self.cap


class ShardedIVFIndex:
    """Per-data-shard IVF structure for the sharded MWEM driver.

    The vector set is split row-wise into ``n_shards`` contiguous chunks —
    the exact layout `run_mwem_sharded` shards Q over the mesh's data axes —
    and an independent IVF (k-means centroids + balanced padded cell table)
    is built per chunk, offline in numpy. Cell ids are *local* row ids in
    ``[0, n_loc)``; shard ``s``'s global rows are ``s·n_loc + local``. The
    stacked ``cents (n_shards, nlist, dim)`` / ``cells (n_shards, nlist,
    cap)`` arrays device_put directly onto the mesh (centroid columns
    model-sharded, cell tables replicated over "model") — the structure is
    never gathered.

    Not a host-query index: searches only make sense inside the shard_map
    body (``supports_sharded``), where each shard probes its own cells and
    candidates meet at the all-gather. ``use_pallas`` routes that per-shard
    probe through the fused `kernels.ivf_probe` kernel when the mesh has no
    model sharding (`kernel_probe`, which the driver asks).
    """

    supports_in_graph = False
    supports_sharded = True

    def __init__(self, vectors, n_shards: int, nlist: int | None = None,
                 nprobe: int | None = None, cap_factor: float = 2.0,
                 train_iters: int = 10, seed: int = 0,
                 approx_margin: float = 0.0,
                 failure_mass: float | None = None,
                 use_pallas: str = "auto"):
        V = _vectors_from(vectors, "ShardedIVFIndex build")
        self.n, self.dim = V.shape
        if self.n % n_shards:
            raise ValueError(f"n={self.n} must divide over {n_shards} shards")
        self.n_shards = int(n_shards)
        self.n_loc = self.n // self.n_shards
        self.nlist = min(nlist or max(int(2 * math.sqrt(self.n_loc)), 8),
                         self.n_loc)
        self.nprobe = nprobe or max(1, min(self.nlist // 4, 10))
        self.cap = max(4, math.ceil(cap_factor * self.n_loc / self.nlist))
        rng = np.random.default_rng(seed)
        cents = np.empty((self.n_shards, self.nlist, self.dim), np.float32)
        cells = np.empty((self.n_shards, self.nlist, self.cap), np.int32)
        for s in range(self.n_shards):
            Vs = V[s * self.n_loc:(s + 1) * self.n_loc]
            cents[s] = _kmeans(Vs, self.nlist, train_iters, rng)
            cells[s] = _balanced_assign(Vs, cents[s], self.cap)
        self.cents = jnp.asarray(cents)
        self.cells = jnp.asarray(cells)
        self._use_pallas = use_pallas
        self.approx_margin = approx_margin
        self.failure_mass = (1.0 / self.n) if failure_mass is None else failure_mass

    def kernel_probe(self, n_model: int) -> bool:
        """Whether the per-shard probe runs the fused kernel on a mesh whose
        "model" axis has extent ``n_model``: the kernel fuses dot and top-k,
        so the partial-dot psum of a model-sharded probe cannot interpose —
        such meshes probe through XLA."""
        return resolve_pallas(self._use_pallas) and n_model == 1

    def query_cost(self, k: int) -> int:
        """Scored rows per iteration across all shards (excluding the tail)."""
        return self.n_shards * (self.nlist + self.nprobe * self.cap)
