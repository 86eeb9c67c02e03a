"""Device time of one wave iteration: the wave executable's execution time
(module events wholly inside the window that call the MWU step kernel)
divided by its T scan iterations."""

from bench import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    waves = trace_reduce.wave_modules(ctx.trace, "mwem_step")
    if not waves:
        return None
    total = sum(mod.dur for mod, _ in waves)
    return 1e3 * total / (len(waves) * ctx.cfg["T"])
