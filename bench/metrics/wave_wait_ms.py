"""Median host wait on a finished scan when a release wave is resolved
(``mwem/batch/wait``, the block on the wave's state). The single-threaded
front end serves no read while it lasts."""

from bench.program_spans import median_ms


def read(ctx):
    return median_ms(ctx, "mwem/batch/wait")
