"""VMEM-resident fused MWEM step (measure → MWU → renormalize) kernel.

A wave's (B, U) weight state — log-weights, density, output accumulator —
sits in VMEM as whole (B, U) blocks (8-lane blocks past B = 8) for the
entire step. The row table stays in HBM (``memory_space=ANY``): for each
lane the kernel DMAs the aligned 8-row window holding row ``sel[b]`` — a
scalar-prefetched id — into VMEM and keeps that one row (a DMA slice of an
(8, 128)-tiled array must cover whole tiles, so 8 rows is the least the
chip can fetch). No XLA gather of ``Q[sel]`` is ever materialized in HBM.
Per-iteration HBM traffic for the MWU half drops from the classic route's
read/write per sub-op (~11 U-vectors: softmax, measure/estimate dots,
update, max-shift, renormalize, accumulate — each a separate HBM
round-trip) to 8 U-vector moves per lane plus the 8-row window (reads:
log_w, p, p_sum, h; 3 writes + noise), and the carried density means the
*next* step skips its softmax reads too.

What stays outside (DESIGN.md §7): the probe, the lazy-EM Gumbel top-k,
and the `lax.cond` exhaustive overflow fallback — they branch on data the
kernel cannot see (tail membership, overflow flag) and keeping them in XLA
is what preserves host parity and the PR 5 conformance tier. The kernel
receives only the resolved winner ids ``sel`` and the realized Laplace
noise.

Numerics vs `ref.mwem_step_ref`: the body is whole-U (no tiling, no
online rescaling) and computes the same expressions with the same
primitives — the measure/estimate dots as f32 elementwise products summed
along U, ``softmax(lw - max(lw))`` as ``e = exp(lw - max); e / sum(e)``,
which equals `jax.nn.softmax` because the max-shift is explicit in both.
Only the order of the U-long f32 reductions can differ between the chip's
kernel and XLA, so the two agree to f32 reduction tolerance, not bitwise.
`ops.mwem_step_supported` gates the route to lane-aligned U so no padding
lanes ever enter the reductions.

The gather-score kernel below serves the factored lazy-EM tail: it
rebuilds each candidate row from its clique's mixed-radix parameters, with
no row table behind it. The dense tail scores its candidates with the XLA
gather (`core.mwem._aug_score`): fetching an 8-row window per candidate
would move 8× the row bytes against the gather's ~3×.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mips_topk.mips_topk import row_scores

_LANES_PER_BLOCK = 8  # one f32 sublane tile of lanes per grid program
ROW_TILE = 8          # f32 sublane tile: the row table's DMA granule


def lane_block(n: int) -> int:
    """Lanes per grid program: all of them up to one sublane tile."""
    return n if n <= _LANES_PER_BLOCK else _LANES_PER_BLOCK


def _fetch_rows(ids_ref, table_hbm, win, sems, rows):
    """Copy row ``ids[g·nb + r]`` of the HBM table into row r of ``rows``.

    A DMA slice of an (8, 128)-tiled HBM array must cover whole sublane
    tiles, so each lane DMAs the aligned 8-row window holding its row (all
    nb copies in flight at once) and then loads its one row out of VMEM.
    Ids past the end of ``ids`` (the ragged last block) clamp to the last
    one; those lanes' results fall outside the output and are dropped.
    """
    nb, tile, _ = win.shape
    last = ids_ref.shape[0] - 1
    base = pl.program_id(0) * nb
    ids = [ids_ref[jnp.minimum(base + r, last)] for r in range(nb)]
    copies = []
    for r in range(nb):
        start = pl.multiple_of((ids[r] // tile) * tile, tile)
        cp = pltpu.make_async_copy(table_hbm.at[pl.ds(start, tile), :],
                                   win.at[r], sems.at[r])
        cp.start()
        copies.append(cp)
    for r, cp in enumerate(copies):
        cp.wait()
        rows[pl.ds(r, 1), :] = win[r, pl.ds(ids[r] % tile, 1), :]


def _row_scratch(nb: int, U: int):
    """VMEM for `_fetch_rows`: nb 8-row windows, nb rows, nb semaphores."""
    return [pltpu.VMEM((nb, ROW_TILE, U), jnp.float32),
            pltpu.SemaphoreType.DMA((nb,)),
            pltpu.VMEM((nb, U), jnp.float32)]


def _row_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """(nb, U) · (nb | 1, U) → (nb, 1): f32 products summed along U."""
    return jnp.sum(a * b, axis=1, keepdims=True)


def _step_kernel(sel_ref, lw_ref, p_ref, ps_ref, q_hbm, h_ref, noise_ref,
                 out_lw_ref, out_p_ref, out_ps_ref, win, sems, rows, *,
                 rule: str, eta: float):
    _fetch_rows(sel_ref, q_hbm, win, sems, rows)
    lw = lw_ref[...]
    q = rows[...]
    if rule == "paper":
        lw1 = lw - eta * q
    else:
        measured = _row_dot(q, h_ref[...].astype(jnp.float32)) + noise_ref[...]
        est = _row_dot(q, p_ref[...])
        if rule == "signed":
            lw1 = lw + eta * jnp.sign(measured - est) * q
        else:  # "hardt" (ops validates the rule set)
            lw1 = lw + q * (measured - est) / 2.0
    lw2 = lw1 - jnp.max(lw1, axis=1, keepdims=True)
    e = jnp.exp(lw2)
    p_new = e / jnp.sum(e, axis=1, keepdims=True)  # == softmax(lw2)
    out_lw_ref[...] = lw2
    out_p_ref[...] = p_new
    out_ps_ref[...] = ps_ref[...] + p_new


def mwem_step_pallas(sel: jax.Array, lw: jax.Array, p: jax.Array,
                     ps: jax.Array, q_rows: jax.Array, h: jax.Array,
                     noise: jax.Array, *, rule: str, eta: float,
                     interpret: bool):
    """Apply one fused MWEM step to B lanes.

    Args:
      sel: (B,) int32 winner row ids into ``q_rows`` (scalar-prefetched).
      lw/p/ps: (B, U) carried log-weights / density / output accumulator.
      q_rows: (R, U) f32 row table, R a multiple of 8, left in HBM — only
        the 8-row windows holding the ``sel[b]`` rows are copied to VMEM.
      h: (1, U) shared or (B, U) per-lane histogram.
      noise: (B, 1) realized Laplace measurement noise.

    Returns ``(lw', p', ps')``, each (B, U) f32.
    """
    B, U = lw.shape
    nb = lane_block(B)
    lanes = pl.BlockSpec((nb, U), lambda g, sel_ref: (g, 0))
    h_spec = (lanes if h.shape[0] > 1
              else pl.BlockSpec((1, U), lambda g, sel_ref: (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(B, nb),),
        in_specs=[lanes, lanes, lanes,
                  pl.BlockSpec(memory_space=pl.ANY),
                  h_spec,
                  pl.BlockSpec((nb, 1), lambda g, sel_ref: (g, 0))],
        out_specs=[lanes, lanes, lanes],
        scratch_shapes=_row_scratch(nb, U),
    )
    kern = functools.partial(_step_kernel, rule=rule, eta=eta)
    out_shape = [jax.ShapeDtypeStruct((B, U), jnp.float32)] * 3
    return pl.pallas_call(kern, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret, name="mwem_step")(
        sel, lw, p, ps, q_rows, h, noise)


def _marginal_score_kernel(tab_ref, off_ref, v_ref, sign_ref, out_ref, *,
                           kmax: int):
    """Per candidate: rebuild the implicit marginal-cell row over the U
    lanes by mixed-radix iota arithmetic (in f32, exact for U < 2^22 —
    vector integer division is slow to compile and to run on the VPU) and
    dot it with v.

    ``tab_ref`` (C, 3·kmax) SMEM holds [domain strides | cards | cell
    strides] for the candidate's clique; ``off_ref`` (C,) its cell offset.
    No row table exists anywhere — the row is (cm == offset) on the fly,
    so the only HBM traffic is v (resident across programs) and the SMEM
    scalars.
    """
    nb = out_ref.shape[0]
    last = off_ref.shape[0] - 1
    base = pl.program_id(0) * nb
    U = v_ref.shape[1]
    v = v_ref[...]
    u = jax.lax.broadcasted_iota(jnp.int32, (1, U), 1).astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (nb, 1), 0)

    def floor_div(x, d):
        # exact for integer-valued x < 2^22: (x + ½)/d sits ≥ ½/d away from
        # an integer, far beyond the f32 quotient's rounding
        return jnp.floor((x + 0.5) / d)

    def one(r, acc):
        c = jnp.minimum(base + r, last)
        cm = jnp.zeros((1, U), jnp.float32)
        for j in range(kmax):  # static unroll — kmax is tiny
            card = tab_ref[c, kmax + j].astype(jnp.float32)
            q = floor_div(u, tab_ref[c, j].astype(jnp.float32))
            digit = q - floor_div(q, card) * card
            cm = cm + digit * tab_ref[c, 2 * kmax + j].astype(jnp.float32)
        row = (cm == off_ref[c].astype(jnp.float32)).astype(jnp.float32)
        return jnp.where(lane == r, row_scores(row, v), acc)

    out_ref[...] = jax.lax.fori_loop(
        0, nb, one, jnp.zeros((nb, 1), jnp.float32)) * sign_ref[...]


def marginal_gather_score_pallas(tab: jax.Array, off: jax.Array,
                                 sign: jax.Array, v: jax.Array, *, kmax: int,
                                 interpret: bool):
    """Factored-row gather-and-score: ``sign[c] · ⟨q_c, v⟩`` for the
    lazy-EM tail's candidates, without any ``(m, U)`` table behind it.

    Args: tab (C, 3·kmax) int32 per-candidate clique params; off (C,) int32
    cell offsets; sign (C,) f32 ±1; v (U,). Returns (C,) f32 scores
    ``sign[c] · ⟨q_c, v⟩``.
    """
    C = off.shape[0]
    U = v.shape[0]
    nb = lane_block(C)
    cand = pl.BlockSpec((nb, 1), lambda g, tab_ref, off_ref: (g, 0))
    kern = functools.partial(_marginal_score_kernel, kmax=kmax)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(pl.cdiv(C, nb),),
        in_specs=[pl.BlockSpec((1, U), lambda g, tab_ref, off_ref: (0, 0)),
                  cand],
        out_specs=cand,
    )
    out = pl.pallas_call(kern, grid_spec=grid_spec,
                         out_shape=jax.ShapeDtypeStruct((C, 1), jnp.float32),
                         interpret=interpret, name="marginal_gather_score")(
        tab, off, v.reshape(1, U).astype(jnp.float32), sign.reshape(C, 1))
    return out[:, 0]
