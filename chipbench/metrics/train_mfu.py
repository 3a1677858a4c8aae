"""Model FLOP/s utilization of the train step: forward and backward
operations per token (from shapes, recomputation not counted) times the
tokens trained per second of the traced window, over the chips' bf16 peak."""


def read(ctx):
    info = ctx.info
    if ctx.peaks is None or not info.get("tokens"):
        return None
    rate = info["tokens"] / info["seconds"] * info["flops_per_token"]
    return rate / (ctx.chips * ctx.peaks["bf16_flop_per_s"]) * 100.0
