"""Milliseconds per train step that the loop waits for its next batch: the
union of the program's ``data.wait`` spans (the prefetcher's consumer round
its queue) over the steps of the window.  Nothing where the program opens no
``data.wait`` span."""

from chipbench import spanset


def read(ctx):
    steps = ctx.info.get("steps", 0)
    waits = spanset.closed(ctx.spans, "data.wait")
    if not steps or not waits:
        return None
    return spanset.length(spanset.union(waits)) / steps / 1e3
