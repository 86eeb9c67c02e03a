"""Pallas-fused IVF probe: scalar-prefetched cell streaming (DESIGN.md §3).

The Θ(√m) selection step of Fast-MWEM is an IVF probe: score the nlist
centroids, pick the top-nprobe cells, score only those cells' rows, keep
the top-k. The XLA lowering materializes the gathered (nprobe·cap, dim)
candidate matrix in HBM (gather out, matvec back in — the rows cross the
HBM bus three times). This kernel never materializes it:

* rows live in HBM pre-grouped by cell (``cell_rows`` (nlist, cap, dim),
  built once per index);
* the probed cell ids are a *scalar-prefetch* input
  (`pltpu.PrefetchScalarGridSpec`), so the Pallas pipeline's index_map
  reads them before the body runs and DMAs exactly the probed cells'
  (cap, block_d) tiles HBM→VMEM, double-buffered across grid steps;
* partial dots accumulate in the resident (slots, B, cap) score block;
  only those scores — 1/dim of the streamed bytes — leave the chip, and
  the masking and top-k run in XLA (`ops.py`: Mosaic has no in-kernel
  `top_k`).

Bytes touched: nlist·dim (centroids) + nprobe·cap·dim (probed rows, once)
— vs the XLA path's ~3× nprobe·cap·dim gather traffic
(`analysis.roofline.ivf_probe_roofline`).

One kernel serves a single probe (B = 1, slots = the probed cells) and a
serve wave of B probes. For a wave the union of all lanes' probed cells is
deduplicated: the unique cells stream first and the duplicate tail repeats
the last unique id. The grid runs d-tiles outermost, so consecutive
duplicate slots ask for the same block and the pipeline keeps the resident
one instead of streaming it again. Every streamed (cap, block_d) tile feeds
one (B × block_d) · (cap × block_d)ᵀ MXU contraction — the wave turns
gather-bound probing into matmuls (scoring runs for all B lanes per tile;
ops.py masks the lanes that did not probe a cell — the dedup shares reads,
not FLOPs). Contractions run at f32 precision (``Precision.HIGHEST``).

Grid: (d_tiles, n_slots). ``block_d`` divides dim (ops.py picks it).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mips_topk.mips_topk import row_scores


def _kernel(slots_ref, rows_ref, qb_ref, out_ref):
    del slots_ref  # consumed by the index_maps, not the body
    s = pl.program_id(1)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[s] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)

    # (B, block_d) · (cap, block_d)ᵀ → (B, cap) partial dots for this cell
    out_ref[s] += row_scores(qb_ref[...], rows_ref[0])


def ivf_scores_pallas(slots: jax.Array, rows_p: jax.Array, qb: jax.Array, *,
                      block_d: int, interpret: bool) -> jax.Array:
    """``(n_slots, B, cap)`` f32 scores of the probe rows ``qb`` (B, dim)
    against the rows of cell ``slots[s]`` of ``rows_p`` (nlist, cap, dim).

    ``slots`` (n_slots,) int32 is the scalar-prefetch operand: the
    index_map reads ``slots[s]`` to pick which HBM cell block the pipeline
    DMAs next, so cells that no slot names are never touched.
    """
    nlist, cap, dim = rows_p.shape
    n_slots = slots.shape[0]
    B = qb.shape[0]
    assert dim % block_d == 0, "ops.py picks a block_d that divides dim"
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(dim // block_d, n_slots),
        in_specs=[
            pl.BlockSpec((1, cap, block_d),
                         lambda j, s, slots_ref: (slots_ref[s], 0, j)),
            pl.BlockSpec((B, block_d), lambda j, s, slots_ref: (0, j)),
        ],
        out_specs=pl.BlockSpec((n_slots, B, cap),
                               lambda j, s, slots_ref: (0, 0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slots, B, cap), jnp.float32),
        interpret=interpret,
        name="ivf_probe_scores",
    )(slots, rows_p, qb)
