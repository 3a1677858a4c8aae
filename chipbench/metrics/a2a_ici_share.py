"""The least time the alltoall's semantics allow over its device time per
call: the bytes that must leave each chip ((P-1)/P of its buffer) at the
chip's inter-chip bandwidth.  It counts the same work whatever lowering
implements the alltoall."""

from chipbench import devtrace


def read(ctx):
    calls, secs = devtrace.module_calls(ctx.trace, ctx.info["program"])
    if ctx.peaks is None or not calls or secs <= 0:
        return None
    least = ctx.info["egress_bytes"] / ctx.peaks["ici_bytes_per_s"]
    return least / (secs / calls) * 100.0
