"""Milliseconds per planning request in the selector race: the host time
of ``api.plan_batch([request])``, measured by the benchmark around the
call, less what the program's own ``compile`` spans cover inside it."""

from chipbench.progspans import compile_us_within


def read(ctx):
    stamps = ctx.info.get("stamps")
    if not stamps or ctx.spans is None:
        return None
    sel = [(a / 1e3, b / 1e3) for a, b, _ in stamps]
    inside = compile_us_within(ctx.spans, sel)
    total = sum(hi - lo for lo, hi in sel) - sum(inside)
    return total / len(stamps) / 1e3
