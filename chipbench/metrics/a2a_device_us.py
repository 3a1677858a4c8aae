"""Device microseconds per call of the timed alltoall program: its
executions in the trace, summed and averaged over the chips."""

from chipbench import devtrace


def read(ctx):
    calls, secs = devtrace.module_calls(ctx.trace, ctx.info["program"])
    if not calls or secs <= 0:
        return None
    return secs / calls * 1e6
