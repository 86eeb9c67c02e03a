"""Median time from dispatching a resolved wave's final errors to holding
them on the host (``mwem/batch/final_error``): the error matmul, and
whatever device work is queued ahead of it."""

from bench.program_spans import median_ms


def read(ctx):
    return median_ms(ctx, "mwem/batch/final_error")
