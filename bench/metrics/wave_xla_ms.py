"""Device time per wave iteration outside the Pallas custom calls: the
selection work XLA runs (probe planning and top-k, the lazy-EM tail, the
overflow redo, the flat probe's matmul)."""

from bench import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    waves = trace_reduce.wave_modules(ctx.trace, "mwem_step")
    if not waves:
        return None
    outside = 0.0
    for mod, ops in waves:
        kernel = trace_reduce.union(
            (o.start, o.end) for o in ops
            if trace_reduce.is_custom_call(o))
        outside += mod.dur - sum(b - a for a, b in kernel)
    return 1e3 * outside / (len(waves) * ctx.cfg["T"])
