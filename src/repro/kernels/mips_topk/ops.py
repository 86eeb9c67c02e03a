"""Public jit'd wrappers for the mips_topk kernel (tiling + top-k)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.mips_topk.mips_topk import mips_scores_pallas
from repro.kernels.route import pallas_route


def pick_block(size: int, pref: int, align: int = 128) -> int:
    """Largest multiple of ``align`` that divides ``size`` and is ≤ ``pref``;
    the whole axis when none does. A block that divides the axis needs no
    padded copy of the table on any call."""
    b = (min(pref, size) // align) * align
    while b >= align:
        if size % b == 0:
            return b
        b -= align
    return size


def _row_block(n: int, pref: int) -> int:
    """Row tile of the (1, n) score output: a lane multiple, or all of n."""
    return n if n <= pref else max(128, (pref // 128) * 128)


def _scores(V, q, block_n, block_d, interpret, name):
    n, d = V.shape
    interpret = pallas_route(name, interpret)
    return mips_scores_pallas(V, q.reshape(1, d),
                              block_n=_row_block(n, block_n),
                              block_d=pick_block(d, block_d),
                              interpret=interpret)[0]


@partial(jax.jit,
         static_argnames=("k", "block_n", "block_d", "interpret", "absolute"))
def mips_topk(V: jax.Array, q: jax.Array, k: int, *, block_n: int = 512,
              block_d: int = 1024, interpret: bool | None = None,
              absolute: bool = False):
    """Top-k inner products of ``q`` against rows of ``V``.

    The kernel streams V once and emits the (n,) scores; `jax.lax.top_k`
    ranks them, so ties break exactly like `ref.mips_topk_ref`.
    ``absolute=True`` ranks by |⟨v_j, q⟩| and returns the absolute scores
    (the IVF centroid-probe ordering).
    """
    s = _scores(V, q, block_n, block_d, interpret,
                "mips_topk_abs" if absolute else "mips_topk")
    top_s, top_i = jax.lax.top_k(jnp.abs(s) if absolute else s, k)
    return top_i.astype(jnp.int32), top_s


@partial(jax.jit, static_argnames=("k", "block_n", "block_d", "interpret"))
def mips_abs_topk(V: jax.Array, q: jax.Array, k: int, *, block_n: int = 512,
                  block_d: int = 1024, interpret: bool | None = None):
    """Top-k of ``|V @ q|`` as complement-augmented ids (paper §3.4).

    Returned id ``j < n`` means ``+⟨v_j, q⟩``; ``j ≥ n`` means
    ``−⟨v_{j−n}, q⟩`` (the complement row's score for zero-sum probes).
    One streaming pass over V; the 2n-row augmented matrix is never
    materialized. The ranking is `mips.flat`'s XLA abs probe on the
    kernel's scores (each base row contributes its larger signed score),
    so the two routes pick the same ids from the same scores. ``k ≤ n``.
    """
    n = V.shape[0]
    s = _scores(V, q, block_n, block_d, interpret, "mips_topk_aug")
    top_a, top_i = jax.lax.top_k(jnp.abs(s), k)
    aug = jnp.where(s[top_i] >= 0, top_i, top_i + n)
    return aug.astype(jnp.int32), top_a
