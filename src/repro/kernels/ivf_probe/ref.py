"""Pure-jnp oracles for the fused IVF probe kernels.

`ivf_probe_topk_ref` is exactly the XLA probe `mips.IVFIndex` has always
run (centroid matvec → top_k → cell gather → candidate matvec → top_k),
with candidates laid out cell-probe-major / slot-minor — the same flat
order the streaming kernel merges in, so index/score agreement is exact
including ties (`jax.lax.top_k` is stable, and a stable incremental top-k
merge equals the stable global top-k).

`ivf_probe_topk_batch_ref` mirrors the batched kernel's candidate order
instead: the deduplicated cell union in *ascending cell id* order shared
by all lanes. On exact score ties the batched path can therefore pick a
different (equal-scoring) candidate than nprobe-ordered per-lane probes —
the only way the two orderings are observably different.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.precision import dot as _dot


def masked_top_k(scores: jax.Array, ids: jax.Array, k: int):
    """Stable top-k over (..., n) candidate ``scores`` (masked slots −inf)
    with their ``ids``; ranks past the finite candidates — including
    ranks past ``n`` when fewer than k candidates were probed — get id −1
    and score −inf. Returns ``(idx int32 (..., k), scores (..., k))``."""
    short = k - scores.shape[-1]
    if short > 0:
        pad = [(0, 0)] * (scores.ndim - 1) + [(0, short)]
        scores = jnp.pad(scores, pad, constant_values=-jnp.inf)
        ids = jnp.pad(ids, pad, constant_values=-1)
    top_s, pos = jax.lax.top_k(scores, k)
    top_i = jnp.take_along_axis(ids, pos, axis=-1)
    return jnp.where(jnp.isfinite(top_s), top_i, -1).astype(jnp.int32), top_s


def ivf_probe_topk_ref(cents: jax.Array, cells: jax.Array, V: jax.Array,
                       q: jax.Array, k: int, nprobe: int,
                       absolute: bool = False):
    """Returns (idx int32 (k,), scores f32 (k,), n_valid int32 ()).

    ``idx`` entries are row ids from the cell table (−1 where fewer than k
    valid candidates were probed); ``n_valid`` counts the valid (non-pad)
    row slots in the probed cells — the scored-rows term of ``n_scored``.
    """
    cscores = _dot(cents, q)
    order = jnp.abs(cscores) if absolute else cscores
    _, probe = jax.lax.top_k(order, nprobe)
    cand = cells[probe].reshape(-1)                       # (nprobe·cap,)
    valid = cand >= 0
    scores = _dot(V[jnp.clip(cand, 0)], q)
    if absolute:
        scores = jnp.abs(scores)
    scores = jnp.where(valid, scores, -jnp.inf)
    idx, top_s = masked_top_k(scores, cand, k)
    return idx, top_s, jnp.sum(valid).astype(jnp.int32)


def batch_probe_slots(cents: jax.Array, cells: jax.Array, Vb: jax.Array,
                      nprobe: int, absolute: bool = False):
    """Shared probe planning for the batched kernel and its reference.

    Returns ``(slots, member, probe)``: the (B·nprobe,) deduplicated cell
    union (unique ids first, ascending; the duplicate tail masked out of
    every lane and pinned to the *last* unique id, so the tail's grid
    steps revisit the block already resident in VMEM instead of
    re-streaming distinct cells), the (B·nprobe, B) float 0/1 membership
    mask, and the per-lane (B, nprobe) probed cells.
    """
    cscores = _dot(Vb, cents.T)                           # (B, nlist)
    order = jnp.abs(cscores) if absolute else cscores
    _, probe = jax.lax.top_k(order, nprobe)               # (B, nprobe)
    flat = jnp.sort(probe.reshape(-1))
    uniq = jnp.concatenate([jnp.ones((1,), bool), flat[1:] != flat[:-1]])
    # unique cells first (ascending), duplicates squeezed to the tail
    slots = flat[jnp.argsort(~uniq, stable=True)]
    slot_valid = jnp.sort(uniq)[::-1]
    # fully-masked tail slots all repeat the max (= last unique) cell id
    slots = jnp.where(slot_valid, slots, flat[-1])
    member = ((slots[:, None, None] == probe[None, :, :]).any(-1)
              & slot_valid[:, None]).astype(jnp.float32)  # (S, B)
    return slots.astype(jnp.int32), member, probe


def marginal_probe_topk_ref(tabs: jax.Array, cl_cells: jax.Array,
                            starts: jax.Array, m: int, k: int, nprobe: int):
    """Clique-structured probe for factored marginal workloads — the
    `ivf_probe` dataflow with the workload's own cliques as cells.

    The geometric IVF structure (centroids, row gathers) disappears: the
    per-clique marginal tables of the probe vector (``tabs`` =
    `MarginalWorkload.marginal_tables(v)`, (n_cliques, max_cells)) already
    hold every query's exact score, so the "centroid" statistic is the
    per-clique max |cell| — an exact upper bound, making the probe's top-k
    exact whenever the probed cliques cover k candidates. No (m, U) gather
    exists anywhere on this path: scoring is offsets + the segment sums
    that built ``tabs``.

    Args:
      tabs: (n_cliques, max_cells) f32 per-clique marginals of ``v``.
      cl_cells: (n_cliques,) int32 valid cell counts (tail cells are pad).
      starts: (n_cliques,) int32 first query id of each clique.
      m: total query count (augmented-id encoding).
      k / nprobe: top-k size and probed clique count.

    Returns ``(aug_idx (k,) int32, |scores| (k,) f32, n_scored int32)`` —
    augmented ids under the §3.4 sign convention, and the candidate count
    the probe actually scored (the n_scored trace term).
    """
    nc, mc = tabs.shape
    valid = jnp.arange(mc)[None, :] < cl_cells[:, None]
    a = jnp.where(valid, jnp.abs(tabs), -jnp.inf)
    cstat = jnp.max(a, axis=1)                       # exact per-clique bound
    _, probe = jax.lax.top_k(cstat, nprobe)
    cand_s = tabs[probe]                             # (nprobe, mc) signed
    cand_valid = valid[probe]
    qid = starts[probe][:, None] + jnp.arange(mc)[None, :]
    flat_s = cand_s.reshape(-1)
    flat_a = jnp.where(cand_valid.reshape(-1), jnp.abs(flat_s), -jnp.inf)
    top_a, pos = jax.lax.top_k(flat_a, k)
    qid_top = qid.reshape(-1)[pos]
    aug = jnp.where(flat_s[pos] >= 0, qid_top, qid_top + m)
    return (aug.astype(jnp.int32), top_a,
            jnp.sum(cand_valid).astype(jnp.int32))


def ivf_probe_topk_batch_ref(cents: jax.Array, cells: jax.Array,
                             V: jax.Array, Vb: jax.Array, k: int, nprobe: int,
                             absolute: bool = False):
    """Returns (idx (B, k), scores (B, k), n_valid (B,)) — candidates per
    lane in the batched kernel's slot order (ascending deduplicated cells,
    lane-masked), so parity with `ivf_probe_topk_batch` is exact."""
    slots, member, probe = batch_probe_slots(cents, cells, Vb, nprobe,
                                             absolute)
    cand = cells[slots]                                   # (S, cap)
    scores = jnp.einsum("scd,bd->bsc", V[jnp.clip(cand, 0)].astype(jnp.float32),
                        Vb.astype(jnp.float32),
                        precision="highest")              # (B, S, cap)
    if absolute:
        scores = jnp.abs(scores)
    mask = (cand[None, :, :] >= 0) & (member.T[:, :, None] > 0)
    scores = jnp.where(mask, scores, -jnp.inf)
    B = Vb.shape[0]
    flat_s = scores.reshape(B, -1)
    flat_i = jnp.broadcast_to(cand.reshape(-1)[None, :], flat_s.shape)
    idx, top_s = masked_top_k(flat_s, flat_i, k)
    n_valid = jnp.sum(cells[probe] >= 0, axis=(1, 2)).astype(jnp.int32)
    return idx, top_s, n_valid
