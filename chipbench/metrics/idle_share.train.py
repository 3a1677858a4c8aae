"""Share of the traced training window in which no op ran on the device,
averaged over the chips."""

from chipbench import devtrace


def read(ctx):
    share = devtrace.idle_share(ctx.trace)
    return None if share is None else share * 100.0
