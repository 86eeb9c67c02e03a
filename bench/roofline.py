"""Least work of the two Pallas kernels a release wave calls, from shapes.

The counts hold whatever implements the kernel: the 0/1 query rows count
one *bit* per entry (the information they carry), so a later int8 or
bit-packed table cannot read above 100%. The probe counts every lane's
``nprobe·cap`` rows: a kernel that reads a cell once for several lanes
does less than this count says, which makes the count stale, and only a
benchmark change may correct it. Floating-point operations are charged at
the chip's bf16 peak, its fastest float rate, so the least time is never
overstated. Both kernels are bound by bytes at the benchmark's shapes.
"""

from __future__ import annotations

F32 = 4


def least_seconds(flops: float, bytes_: float, peaks: dict) -> tuple:
    """(least seconds, bound) — the larger of the compute and byte times."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")


def ivf_probe_work(lanes: int, nprobe: int, cap: int, U: int) -> tuple:
    """(flops, bytes) of one wave probe call (`ivf_probe_scores`).

    Reads each lane's ``nprobe·cap`` candidate rows at one bit per entry
    and the (lanes, U) f32 probe block; writes (lanes, nprobe·cap) f32
    scores. One multiply-add per row entry and lane."""
    rows = lanes * nprobe * cap
    bytes_ = rows * U / 8 + lanes * U * F32 + rows * F32
    return 2.0 * rows * U, bytes_


def mwem_step_work(lanes: int, U: int) -> tuple:
    """(flops, bytes) of one batched MWU step call (`mwem_step`).

    f32 state in (log-weights, density, running sum) and out, the f32
    histogram, and each lane's winning 0/1 row at one bit per entry.
    About ten operations per entry (two products, update, max, exp, sum,
    scale, accumulate)."""
    bytes_ = lanes * U * F32 * (3 + 1 + 3) + lanes * U / 8
    return 10.0 * lanes * U, bytes_
