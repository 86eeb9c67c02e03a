"""Index protocol + complement augmentation (paper §3.4)."""

from __future__ import annotations

import copy
from typing import Protocol, Tuple, runtime_checkable

import jax
import numpy as np


def resolve_pallas(use_pallas: str) -> bool:
    """Shared `use_pallas` knob resolution for every index family:
    "always" | "never" | "auto" (TPU only — the automatic fallback where
    Pallas has no compiled backend)."""
    if use_pallas == "always":
        return True
    if use_pallas == "never":
        return False
    if use_pallas != "auto":
        raise ValueError(f"use_pallas must be auto|always|never, "
                         f"got {use_pallas!r}")
    return jax.default_backend() == "tpu"


def index_state(index) -> dict:
    """The device state an index holds: its array and workload attributes.

    Jitted drivers take this dict as an argument and call the index through
    `bind_state`, so an index's tables reach the executable as parameters —
    a closed-over array would be captured into the program as a constant,
    several GB of it at a real table size."""
    if index is None:
        return {}
    from repro.core.workload import Workload

    return {k: v for k, v in vars(index).items()
            if isinstance(v, (jax.Array, Workload))}


def bind_state(index, state: dict):
    """A shallow copy of ``index`` holding ``state`` (tracers, in a trace)."""
    if index is None:
        return None
    bound = copy.copy(index)
    bound.__dict__.update(state)
    return bound


@runtime_checkable
class MIPSIndex(Protocol):
    """k-MIPS index protocol.

    Attributes:
      approx_margin: the retrieval approximation constant ``c`` of Def. 3.4
        (0 for exact indices). Feeds the (ε+2c) accounting of Thm F.2 or the
        margin lowering of Alg. 6.
      failure_mass: γ — probability mass of the index answering incorrectly
        over a whole run (adds to δ per Thm 3.3).
      supports_in_graph: whether ``query_in_graph`` is traceable — fixed
        output shapes, no host syncs — so the fused MWEM driver can inline
        the search into its ``lax.scan`` body (DESIGN.md §2).
    """

    approx_margin: float
    failure_mass: float
    supports_in_graph: bool

    def query(self, v: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
        """Return (idx, scores): the (approximate) top-k inner products."""
        ...

    def query_in_graph(self, v: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
        """`query` accepting a traced probe, callable inside jit/scan/vmap.

        Indices that cannot be traced (``supports_in_graph=False``) raise
        NotImplementedError; the MWEM driver routes them to the host loop.
        """
        ...

    def query_cost(self, k: int) -> int:
        """Analytic count of candidate score evaluations per query."""
        ...


def augment_complement(Q: np.ndarray) -> np.ndarray:
    """Close a query set under complements: rows ``[Q; 1 − Q]`` (§3.4).

    For probe vectors with ``Σv = 0`` (histogram differences),
    ``⟨1−q, v⟩ = −⟨q, v⟩`` — so top-k over the augmented set retrieves the
    top absolute scores. Augmented id ``j`` ↦ query ``j % m``, sign
    ``+1 if j < m else −1``.
    """
    Q = np.asarray(Q, np.float32)
    return np.concatenate([Q, 1.0 - Q], axis=0)
