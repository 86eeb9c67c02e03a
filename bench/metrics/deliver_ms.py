"""Median host time of a wave's phase two (``serve/wave/mwem/deliver``):
ledger commits, marginal-cost replays and the journal's delivery records.
The device idles through it until the next wave is launched."""

from bench.program_spans import median_ms


def read(ctx):
    return median_ms(ctx, "serve/wave/mwem/deliver")
