"""The 95th percentile of the device microseconds of one call of the timed
alltoall program, over every call in the window on every chip."""

import numpy as np

from chipbench import devtrace


def read(ctx):
    secs = devtrace.module_seconds(ctx.trace, ctx.info["program"])
    if not secs:
        return None
    return float(np.percentile(secs, 95)) * 1e6
