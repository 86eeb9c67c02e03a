"""Share of the traced window in which no operation ran on the device."""

from bench import trace_reduce


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0 or not ctx.trace.ops:
        return None
    busy = trace_reduce.busy_seconds(ctx.trace)
    return 100.0 * (1.0 - busy / ctx.trace.window_s)
