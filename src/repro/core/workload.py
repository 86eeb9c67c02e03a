"""Workload abstraction: dense query matrices and factored k-way marginals.

Two implementations of one protocol (DESIGN.md §9):

- `DenseWorkload` wraps today's explicit ``(m, U)`` matrix. Every primitive
  is the exact expression the drivers inlined before the refactor, so the
  dense path stays bitwise identical.
- `MarginalWorkload` represents k-way marginals over a factored categorical
  domain ``U = Π card[i]`` as structured index maps — per query only a
  clique id and a cell offset; rows are *never* stored. The cell map of a
  clique (which marginal cell each domain point lands in) is recomputed on
  the fly from ``arange(U)`` by mixed-radix arithmetic, so the whole
  representation is ``O(m + n_cliques·kmax)`` integers.

Complement augmentation is by *sign convention*, not row doubling: for
probes with ``Σv = 0`` (histogram differences), ``⟨1−q, v⟩ = −⟨q, v⟩``, so
augmented id ``j`` means query ``j % m`` with sign ``+1 if j < m else −1``
(`aug_decompose`). No workload ever materializes ``[Q; 1−Q]``.

Bitwise-parity contract (the conformance safety rail): `scores(v)` is the
selection oracle. For ``m ≤ score_block`` it is a single ``(m, U) @ (U,)``
matmul over implicit one-hot rows — the same op shape and bitwise-equal
operands as the dense path, hence bitwise-equal scores. `answer_all(v)` is
the fast path (per-clique segment sums, ``O(n_cliques · U)`` work and
``O(chunk · U)`` memory); scatter reassociation makes it allclose, not
bitwise, which is why the two paths exist separately.

Instances are registered as JAX pytrees: they flow through ``jit`` as
*arguments* (index tables are leaves), so the drivers' compiled-fn caches
keyed on ``tree_structure(W)`` hit across instances of the same shape —
the repo's standing anti-retrace pattern.
"""

from __future__ import annotations

import itertools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.precision import dot as _dot


__all__ = [
    "Workload", "DenseWorkload", "MarginalWorkload", "as_workload",
    "aug_decompose",
]

# require_dense() refuses to materialize tables past this many bytes —
# callers that genuinely need dense (sharded driver, LSH builds) get a
# loud error at the scale the factored path exists to serve.
_DENSIFY_LIMIT_BYTES = 2**31


def aug_decompose(aug_idx: jax.Array, m: int) -> Tuple[jax.Array, jax.Array]:
    """Augmented id → (base query id, ±1 sign) under the §3.4 closure."""
    base = (aug_idx % m).astype(jnp.int32)
    sign = jnp.where(aug_idx < m, 1.0, -1.0).astype(jnp.float32)
    return base, sign


class Workload:
    """Protocol base. Subclasses provide ``m``/``U`` plus the primitives
    below; shared derived helpers live here."""

    m: int
    U: int
    is_dense: bool

    # -- primitives (subclass responsibility) ---------------------------
    def row(self, j) -> jax.Array:          # (U,) float32, traceable j
        raise NotImplementedError

    def rows(self, ids) -> jax.Array:       # (t, U) float32, traceable ids
        raise NotImplementedError

    def scores(self, v) -> jax.Array:       # (m,) oracle path (parity)
        raise NotImplementedError

    def answer_all(self, v) -> jax.Array:   # (m,) fast path
        raise NotImplementedError

    def densify(self, limit: int = _DENSIFY_LIMIT_BYTES) -> np.ndarray:
        raise NotImplementedError

    # -- shared derived API --------------------------------------------
    @property
    def n_aug(self) -> int:
        """Size of the complement-augmented id space (no rows doubled)."""
        return 2 * self.m

    @property
    def dense_nbytes(self) -> int:
        """Bytes a dense ``(m, U)`` float32 table takes (or would take)."""
        return 4 * self.m * self.U

    def matvec(self, v) -> jax.Array:
        """Workload answers ``Q v`` (fast path)."""
        return self.answer_all(v)

    def probe_scores(self, v) -> jax.Array:
        """Full (m,) signed scores for exhaustive probes: the bitwise
        parity matmul while it's affordable, the fast path past it."""
        return self.answer_all(v)

    def score_in_graph(self, v, aug_ids) -> jax.Array:
        """Traceable augmented-id scores over implicit one-hot products:
        ``sign_j · ⟨q_{j % m}, v⟩``. Same op shape as the dense tail gather
        (`(t, U) @ (U,)`), so bitwise with `core.mwem._aug_score`."""
        base, sign = aug_decompose(jnp.asarray(aug_ids), self.m)
        return _dot(self.rows(base), v) * sign

    def max_err(self, h, p) -> jax.Array:
        """‖Q(p − h)‖_∞ without densification (Eq. 1)."""
        return jnp.max(jnp.abs(self.answer_all(p - h)))

    def require_dense(self, context: str,
                      limit: int = _DENSIFY_LIMIT_BYTES) -> jnp.ndarray:
        """Dense table or a loud error naming the consumer — the documented
        densify-fallback for families without a factored build."""
        try:
            return jnp.asarray(self.densify(limit))
        except ValueError as e:
            raise ValueError(
                f"{context} requires a dense (m, U) table but "
                f"{type(self).__name__} with m={self.m}, U={self.U} "
                f"refuses to materialize it: {e}") from e


@jax.tree_util.register_pytree_node_class
class DenseWorkload(Workload):
    """Explicit ``(m, U)`` query matrix — the pre-refactor representation.

    Every primitive is verbatim the expression the drivers used inline, so
    swapping raw ``Q`` for ``DenseWorkload(Q)`` is bitwise-neutral.
    """

    is_dense = True

    def __init__(self, Q):
        self.Q = Q if isinstance(Q, jax.core.Tracer) else \
            jnp.asarray(Q, jnp.float32)

    @property
    def m(self) -> int:
        return int(self.Q.shape[0])

    @property
    def U(self) -> int:
        return int(self.Q.shape[1])

    @property
    def nbytes(self) -> int:
        return 4 * self.m * self.U

    def row(self, j) -> jax.Array:
        return self.Q[j]

    def rows(self, ids) -> jax.Array:
        return self.Q[jnp.asarray(ids)]

    def scores(self, v) -> jax.Array:
        return _dot(self.Q, v)

    def answer_all(self, v) -> jax.Array:
        return _dot(self.Q, v)

    def max_err(self, h, p) -> jax.Array:
        # verbatim queries.max_error — keeps the dense path bitwise
        return jnp.max(jnp.abs(_dot(self.Q, p - h)))

    def score_in_graph(self, v, aug_ids) -> jax.Array:
        base, sign = aug_decompose(jnp.asarray(aug_ids), self.m)
        return _dot(self.Q[base], v) * sign

    def densify(self, limit: int = _DENSIFY_LIMIT_BYTES) -> np.ndarray:
        return np.asarray(self.Q, np.float32)

    def require_dense(self, context: str,
                      limit: int = _DENSIFY_LIMIT_BYTES) -> jnp.ndarray:
        """The table itself, where it already lives (no host round trip)."""
        return self.Q

    def tree_flatten(self):
        # aux must not read the leaf: jax round-trips pytrees with
        # placeholder leaves during vmap/jit bookkeeping
        return (self.Q,), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        obj = object.__new__(cls)
        obj.Q = leaves[0]
        return obj

    def __repr__(self):
        return f"DenseWorkload(m={self.m}, U={self.U})"


@jax.tree_util.register_pytree_node_class
class MarginalWorkload(Workload):
    """k-way marginal cells over a factored domain, rows kept implicit.

    Domain: mixed-radix product of ``card`` (last attribute fastest), so
    point ``u`` has digit ``(u // dstride[i]) % card[i]`` on attribute
    ``i``. A clique ``(a_1..a_k)`` defines a marginal table whose cell map
    ``cm_c(u) = Σ_j digit_{a_j}(u) · cstride_j`` is recomputed from
    ``arange(U)`` whenever needed. Query ``t`` is the indicator of cell
    ``q_offset[t]`` of clique ``q_clique[t]``: one augmented marginal cell
    per query, ``m = Σ_c Π_j card[a_j]`` total.

    Leaves are the integer index maps (they ride through jit as arguments);
    the static shape/metadata tuple is pytree aux so compiled-driver caches
    key on it.
    """

    is_dense = False

    def __init__(self, card: Sequence[int],
                 cliques: Sequence[Sequence[int]], *,
                 score_block: int = 512, clique_chunk: int = 32):
        card = tuple(int(c) for c in card)
        cliques = tuple(tuple(int(a) for a in cl) for cl in cliques)
        if not cliques:
            raise ValueError("MarginalWorkload needs at least one clique")
        for cl in cliques:
            if len(set(cl)) != len(cl):
                raise ValueError(f"clique {cl} repeats an attribute")
            if any(a < 0 or a >= len(card) for a in cl):
                raise ValueError(f"clique {cl} references a missing "
                                 f"attribute (n_attrs={len(card)})")
        # mixed-radix domain strides, last attribute fastest
        dstr = np.ones(len(card), np.int64)
        for i in range(len(card) - 2, -1, -1):
            dstr[i] = dstr[i + 1] * card[i + 1]
        U = int(dstr[0] * card[0]) if card else 1
        if U >= 2**31:
            raise ValueError(f"domain size {U} overflows int32 cell maps")
        nc = len(cliques)
        kmax = max(len(cl) for cl in cliques)
        cl_dstride = np.ones((nc, kmax), np.int32)
        cl_card = np.ones((nc, kmax), np.int32)   # padding: card 1 → digit 0
        cl_stride = np.zeros((nc, kmax), np.int32)  # padding: stride 0
        cl_cells = np.ones((nc,), np.int32)
        qc, qo = [], []
        for c, cl in enumerate(cliques):
            strides = np.ones(len(cl), np.int64)
            for j in range(len(cl) - 2, -1, -1):
                strides[j] = strides[j + 1] * card[cl[j + 1]]
            ncells = int(strides[0] * card[cl[0]])
            cl_cells[c] = ncells
            for j, a in enumerate(cl):
                cl_dstride[c, j] = dstr[a]
                cl_card[c, j] = card[a]
                cl_stride[c, j] = strides[j]
            qc.append(np.full(ncells, c, np.int32))
            qo.append(np.arange(ncells, dtype=np.int32))
        self.card, self.cliques = card, cliques
        self._U, self.n_cliques, self.kmax = U, nc, kmax
        self.max_cells = int(cl_cells.max())
        self.score_block = int(score_block)
        self.clique_chunk = int(clique_chunk)
        self.q_clique = jnp.asarray(np.concatenate(qc))
        self.q_offset = jnp.asarray(np.concatenate(qo))
        self._m = int(self.q_clique.shape[0])
        self.cl_dstride = jnp.asarray(cl_dstride)
        self.cl_card = jnp.asarray(cl_card)
        self.cl_stride = jnp.asarray(cl_stride)
        self.cl_cells = jnp.asarray(cl_cells)

    @classmethod
    def all_kway(cls, card: Sequence[int], k: int, *,
                 max_cliques: int | None = None, **kw) -> "MarginalWorkload":
        """All (or the first ``max_cliques``) k-way marginals of ``card``."""
        cliques = itertools.combinations(range(len(card)), k)
        if max_cliques is not None:
            cliques = itertools.islice(cliques, max_cliques)
        return cls(card, list(cliques), **kw)

    # -- static metadata ------------------------------------------------
    @property
    def m(self) -> int:
        return self._m

    @property
    def U(self) -> int:
        return self._U

    @property
    def nbytes(self) -> int:
        """Bytes of the factored representation actually held."""
        return sum(4 * int(np.prod(a.shape)) for a in
                   (self.q_clique, self.q_offset, self.cl_dstride,
                    self.cl_card, self.cl_stride, self.cl_cells))

    # -- implicit rows --------------------------------------------------
    def cell_maps(self, cl_ids) -> jax.Array:
        """(t,) clique ids → (t, U) int32 marginal-cell map, recomputed
        from ``arange(U)`` by mixed-radix arithmetic (no stored table)."""
        cl_ids = jnp.asarray(cl_ids, jnp.int32)
        u = jnp.arange(self.U, dtype=jnp.int32)[None, :]
        cm = jnp.zeros((cl_ids.shape[0], self.U), jnp.int32)
        for j in range(self.kmax):  # kmax is tiny and static: unroll
            ds = self.cl_dstride[cl_ids, j][:, None]
            cd = self.cl_card[cl_ids, j][:, None]
            cs = self.cl_stride[cl_ids, j][:, None]
            cm = cm + ((u // ds) % cd) * cs
        return cm

    def rows(self, ids) -> jax.Array:
        ids = jnp.asarray(ids, jnp.int32)
        cm = self.cell_maps(self.q_clique[ids])
        return (cm == self.q_offset[ids][:, None]).astype(jnp.float32)

    def row(self, j) -> jax.Array:
        return self.rows(jnp.reshape(jnp.asarray(j, jnp.int32), (1,)))[0]

    # -- scoring --------------------------------------------------------
    def scores(self, v) -> jax.Array:
        """Oracle path: blockwise implicit-row matmul. A single block when
        ``m ≤ score_block`` — same op shape as dense ``Q @ v``, hence
        bitwise; larger workloads chunk (reassociation accepted there)."""
        B = self.score_block
        if self.m <= B:
            return _dot(self.rows(jnp.arange(self.m)), v)
        nb = -(-self.m // B)
        ids = jnp.clip(jnp.arange(nb * B), 0, self.m - 1)
        out = [_dot(self.rows(ids[b * B:(b + 1) * B]), v) for b in range(nb)]
        return jnp.concatenate(out)[:self.m]

    def marginal_tables(self, v) -> jax.Array:
        """(n_cliques, max_cells) per-clique marginals of ``v`` by segment
        sums, ``O(clique_chunk · U)`` live memory. Cells past a clique's
        arity stay 0."""
        C = min(self.clique_chunk, self.n_cliques)
        nb = -(-self.n_cliques // C)

        def block(b):
            ids = jnp.clip(b * C + jnp.arange(C), 0, self.n_cliques - 1)
            cm = self.cell_maps(ids)
            tab = jnp.zeros((C, self.max_cells), jnp.float32)
            return tab.at[jnp.arange(C)[:, None], cm].add(
                v.astype(jnp.float32)[None, :])

        if nb == 1:
            tabs = block(0)
        else:
            tabs = jax.lax.map(block, jnp.arange(nb))
            tabs = tabs.reshape(nb * C, self.max_cells)
        return tabs[:self.n_cliques]

    def answer_all(self, v) -> jax.Array:
        """Fast path: all m answers from the clique tables — sublinear in
        ``m · U`` (each domain point is touched once per clique, not once
        per query)."""
        tabs = self.marginal_tables(v)
        return tabs[self.q_clique, self.q_offset]

    def probe_scores(self, v) -> jax.Array:
        # the single-matmul parity path at small m (dense-vs-factored
        # bitwise probes), the segment-sum fast path beyond it
        if self.m <= self.score_block:
            return self.scores(v)
        return self.answer_all(v)

    def clique_abs_err(self, v) -> jax.Array:
        """(n_cliques,) max |cell score| per clique — the worst-approximated
        -marginal statistic driving adaptive selection."""
        tabs = jnp.abs(self.marginal_tables(v))
        valid = jnp.arange(self.max_cells)[None, :] < self.cl_cells[:, None]
        return jnp.max(jnp.where(valid, tabs, 0.0), axis=1)

    def clique_slice(self, c: int) -> Tuple[int, int]:
        """Host-side [start, stop) query-id range of clique ``c``."""
        starts = np.concatenate([[0], np.cumsum(np.asarray(self.cl_cells))])
        return int(starts[c]), int(starts[c + 1])

    # -- densification --------------------------------------------------
    def densify(self, limit: int = _DENSIFY_LIMIT_BYTES) -> np.ndarray:
        if self.dense_nbytes > limit:
            raise ValueError(
                f"dense table would be {self.dense_nbytes} bytes "
                f"(> limit {limit})")
        u = np.arange(self.U, dtype=np.int64)
        qc = np.asarray(self.q_clique)
        qo = np.asarray(self.q_offset)
        ds = np.asarray(self.cl_dstride, np.int64)
        cd = np.asarray(self.cl_card, np.int64)
        cs = np.asarray(self.cl_stride, np.int64)
        Q = np.empty((self.m, self.U), np.float32)
        for c in range(self.n_cliques):
            cm = np.zeros_like(u)
            for j in range(self.kmax):
                cm += ((u // ds[c, j]) % cd[c, j]) * cs[c, j]
            sel = qc == c
            Q[sel] = (cm[None, :] == qo[sel][:, None]).astype(np.float32)
        return Q

    # -- pytree ---------------------------------------------------------
    def tree_flatten(self):
        leaves = (self.q_clique, self.q_offset, self.cl_dstride,
                  self.cl_card, self.cl_stride, self.cl_cells)
        aux = (self.card, self.cliques, self._m, self._U, self.n_cliques,
               self.kmax, self.max_cells, self.score_block,
               self.clique_chunk)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        obj = object.__new__(cls)
        (obj.card, obj.cliques, obj._m, obj._U, obj.n_cliques, obj.kmax,
         obj.max_cells, obj.score_block, obj.clique_chunk) = aux
        (obj.q_clique, obj.q_offset, obj.cl_dstride, obj.cl_card,
         obj.cl_stride, obj.cl_cells) = leaves
        return obj

    def __repr__(self):
        return (f"MarginalWorkload(m={self.m}, U={self.U}, "
                f"n_cliques={self.n_cliques}, kmax={self.kmax})")


def as_workload(Q) -> Workload:
    """Coerce raw arrays to `DenseWorkload`; pass workloads through."""
    if isinstance(Q, Workload):
        return Q
    return DenseWorkload(Q)
