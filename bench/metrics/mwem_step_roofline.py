"""The MWU step kernel's share of its roofline: least time from the
shapes (`bench.roofline.mwem_step_work`) over the kernel's device time."""

from bench import roofline, trace_reduce


def read(ctx):
    if ctx.trace is None or len(ctx.mix["ladder"]) != 1:
        return None     # per-call lane counts are known for one wave size
    ops = trace_reduce.kernel_ops(ctx.trace, "mwem_step")
    if not ops:
        return None
    flops, bytes_ = roofline.mwem_step_work(ctx.mix["ladder"][0],
                                            ctx.cfg["U"])
    least, _ = roofline.least_seconds(flops, bytes_, ctx.peaks)
    return 100.0 * least * len(ops) / sum(o.dur for o in ops)
