"""The IVF probe kernel's share of its roofline: least time from the
shapes (`bench.roofline.ivf_probe_work`) over the kernel's device time."""

from bench import roofline, trace_reduce


def read(ctx):
    if ctx.trace is None or len(ctx.mix["ladder"]) != 1:
        return None     # per-call lane counts are known for one wave size
    ops = trace_reduce.kernel_ops(ctx.trace, "ivf_probe_scores")
    idx = ctx.cfg.get("index") or {}
    if not ops or "nprobe" not in idx:
        return None
    flops, bytes_ = roofline.ivf_probe_work(ctx.mix["ladder"][0],
                                            idx["nprobe"], idx["cap"],
                                            ctx.cfg["U"])
    least, _ = roofline.least_seconds(flops, bytes_, ctx.peaks)
    return 100.0 * least * len(ops) / sum(o.dur for o in ops)
