"""Streaming tiled inner-product scoring kernel for the flat MIPS scan.

The flat (exact) retrieval path of Fast-MWEM: score all n key vectors
against one probe, then keep the top-k. The kernel streams V HBM→VMEM in
(block_n × block_d) tiles and accumulates each row tile's partial dots in
its resident (1, block_n) output block across the d-tiles; only the
(n,) score vector — 1/d of the table's bytes — leaves the chip, and the
top-k over it runs in XLA (`ops.py`). Mosaic has no in-kernel `top_k`,
and the score vector is small next to the table, so V is still read
exactly once. Arithmetic intensity is ~0.5 flop/byte: the kernel is
HBM-bandwidth-bound by construction, which is the roofline the IVF/LSH/NSW
indices beat by touching fewer rows.

Scores are lane-dense: each tile is one ``q (1, block_d) · V_tileᵀ``
contraction at f32 precision (``Precision.HIGHEST`` — the MXU's default
bf16 operand rounding would change the exponential mechanism's scores).

Grid: (n_tiles, d_tiles), d innermost. ``block_d`` divides d (ops.py picks
it), so no padded column ever enters a sum; a ragged last row tile reads
rows past n whose scores land in the output's masked tail and are never
written.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.precision import HIGHEST

# contract the last dim of both operands: (1, bd) · (bn, bd)ᵀ → (1, bn)
_NT = (((1,), (1,)), ((), ()))


def row_scores(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a · bᵀ`` for (A, d) and (R, d) operands → (A, R), one f32-precision
    contraction per pair of rows (probes against table rows, or rows
    against a (1, d) probe)."""
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32), _NT,
        precision=HIGHEST, preferred_element_type=jnp.float32)


def _kernel(v_ref, q_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += row_scores(q_ref[...], v_ref[...])


def mips_scores_pallas(V: jax.Array, q2: jax.Array, *, block_n: int,
                       block_d: int, interpret: bool) -> jax.Array:
    """``(1, n)`` f32 scores ``V @ q``; use ops.mips_topk for the public API.

    ``q2`` is the probe as a (1, d) row; ``block_d`` must divide d.
    """
    n, d = V.shape
    assert d % block_d == 0, "ops.py picks a block_d that divides d"
    grid = (pl.cdiv(n, block_n), d // block_d)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j: (i, j)),
            pl.BlockSpec((1, block_d), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
        name="mips_scores",
    )(V, q2)
