"""p99 of the benchmark's own host spans around each call into the
service's release path (``submit`` and ``pump``) in the window: the time
the single-threaded front end is held, during which no read is served."""

from bench.loadgen import quantile


def read(ctx):
    t0, t1 = ctx.record.window
    d = [b - a for name, a, b in ctx.record.spans
         if name in ("submit", "pump") and t0 <= a <= t1]
    return 1e3 * quantile(d, 0.99) if d else None
