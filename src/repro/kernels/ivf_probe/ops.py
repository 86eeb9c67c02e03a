"""Public jit'd wrappers for the fused IVF probe (dispatch + top-k).

Layout contract: callers hold the index rows twice — the flat ``V`` the
XLA reference gathers from, and ``cell_rows`` (nlist, cap, dim), the same
rows pre-grouped by cell so a probed cell is one contiguous HBM block the
kernel's scalar-prefetched index_map can DMA directly (`mips.IVFIndex`
builds it once, only when the Pallas route is live). The kernel reads
``cell_rows`` as it is: no call pads or copies it.

Stage split: the single probe scores its centroids through the streaming
`mips_topk` kernel (mode "abs" for the sharded driver's |·| ordering); its
(nprobe,) cell ids feed the stream kernel's scalar prefetch with no host
round-trip. The batched wrapper plans its probes with one XLA
(B × dim) @ (dim × nlist) matmul instead — at wave width the centroid
stage is MXU-bound already, and the dedup/membership planning is pure jnp
either way. Both finish in XLA: mask pad slots (and, for a wave, cells a
lane did not probe) to −inf, then one `jax.lax.top_k` over the candidates
in slot order — the order `ref.py` ranks in, so ids and ties agree.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ivf_probe.ivf_probe import ivf_scores_pallas
from repro.kernels.ivf_probe.ref import batch_probe_slots, masked_top_k
from repro.kernels.mips_topk.ops import mips_topk, pick_block
from repro.kernels.route import pallas_route
from repro.obs.trace import scope as obs_scope


def _cell_ids(cells, probed, cap: int):
    """Row ids of the probed cells, padded with −1 to ``cell_rows``' cap
    (`mips.IVFIndex` pads its cell blocks to the sublane multiple)."""
    ids = cells[probed]
    pad = cap - cells.shape[1]
    if pad:
        ids = jnp.pad(ids, [(0, 0)] * (ids.ndim - 1) + [(0, pad)],
                      constant_values=-1)
    return ids


def _top_candidates(scores, ids, k: int, absolute: bool):
    """top-k over (..., n_cand) candidate scores; −1 ids and masked slots
    (score −inf) never win a finite rank."""
    if absolute:
        scores = jnp.abs(scores)
    return masked_top_k(jnp.where(ids >= 0, scores, -jnp.inf), ids, k)


@partial(jax.jit, static_argnames=("k", "nprobe", "block_d", "interpret",
                                   "absolute"))
def ivf_probe_topk(cents: jax.Array, cell_rows: jax.Array, cells: jax.Array,
                   q: jax.Array, k: int, nprobe: int, *, block_d: int = 2048,
                   interpret: bool | None = None, absolute: bool = False):
    """Fused IVF probe: top-k inner products over the top-``nprobe`` cells.

    Args:
      cents: (nlist, dim) cell centroids.
      cell_rows: (nlist, cap, dim) rows grouped by cell (pad slots zero).
      cells: (nlist, cap) int32 row-id table, −1 padding.
      q: (dim,) probe vector.
      absolute: rank centroids and candidates by |⟨·, q⟩| and return the
        absolute scores (the sharded driver's ordering); False matches
        `mips.IVFIndex`'s signed ordering.

    Returns ``(idx (k,) int32, scores (k,) f32, n_valid () int32)`` — the
    `ref.ivf_probe_topk_ref` candidates in the same order, with
    ``idx = −1`` beyond the valid candidates.
    """
    nlist, cap, dim = cell_rows.shape
    probe, _ = mips_topk(cents, q, nprobe, block_d=block_d,
                         interpret=interpret, absolute=absolute)
    interpret = pallas_route("ivf_probe", interpret)
    with obs_scope("kernel/ivf_probe"):
        s = ivf_scores_pallas(probe, cell_rows, q.reshape(1, dim),
                              block_d=pick_block(dim, block_d),
                              interpret=interpret)       # (nprobe, 1, cap)
    ids = _cell_ids(cells, probe, cap)                   # (nprobe, cap)
    out_i, out_s = _top_candidates(s[:, 0].reshape(-1), ids.reshape(-1), k,
                                   absolute)
    return out_i, out_s, jnp.sum(ids >= 0).astype(jnp.int32)


@partial(jax.jit, static_argnames=("k", "nprobe", "block_d", "interpret",
                                   "absolute"))
def ivf_probe_topk_batch(cents: jax.Array, cell_rows: jax.Array,
                         cells: jax.Array, Vb: jax.Array, k: int, nprobe: int,
                         *, block_d: int = 2048,
                         interpret: bool | None = None,
                         absolute: bool = False):
    """Wave-batched fused IVF probe over B probe vectors ``Vb`` (B, dim).

    Each cell of the lanes' deduplicated union streams HBM→VMEM once;
    each streamed tile is scored against the whole wave by one MXU
    contraction. Returns ``(idx (B, k), scores (B, k), n_valid (B,))`` —
    `ref.ivf_probe_topk_batch_ref`'s candidates in its order (ties break
    in ascending-cell slot order, see ref.py).
    """
    nlist, cap, dim = cell_rows.shape
    B = Vb.shape[0]
    slots, member, probe = batch_probe_slots(cents, cells, Vb, nprobe,
                                             absolute)
    interpret = pallas_route("ivf_probe_batch", interpret)
    with obs_scope("kernel/ivf_probe_batch"):
        s = ivf_scores_pallas(slots, cell_rows, Vb,
                              block_d=pick_block(dim, block_d),
                              interpret=interpret)       # (S, B, cap)
    ids = jnp.where(member.T[:, :, None] > 0,
                    _cell_ids(cells, slots, cap)[None], -1)  # (B, S, cap)
    out_i, out_s = _top_candidates(
        jnp.swapaxes(s, 0, 1).reshape(B, -1), ids.reshape(B, -1), k,
        absolute)
    n_valid = jnp.sum(cells[probe] >= 0, axis=(1, 2)).astype(jnp.int32)
    return out_i, out_s, n_valid
