"""Share of the routed assignments to held experts that capacity dropped,
from the step's ``moe_dropped`` and ``moe_routed`` counters (summed over
layers and chips), read on the steps where the loop reads the loss."""


def read(ctx):
    routed = ctx.info.get("moe_routed")
    if not routed:
        return None
    return ctx.info["moe_dropped"] / routed * 100.0
