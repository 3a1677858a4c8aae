"""Device milliseconds per train step in collective ops (all-reduce,
all-gather, reduce-scatter, all-to-all, collective-permute), from the trace,
averaged over the chips: the gradient sync's time on the device."""

from chipbench import devtrace


def read(ctx):
    steps = ctx.info.get("steps", 0)
    secs = sum(devtrace.op_seconds(ctx.trace, devtrace.is_collective).values())
    if not steps or secs <= 0:
        return None
    return secs / steps * 1e3
