"""Milliseconds per planning request inside the program's own ``compile``
spans (``core/schedule_ir.py``: generation, recipe replay, optimization
passes and oracle), from the program's tracer in the traced run."""

from chipbench.progspans import compile_us_within


def read(ctx):
    stamps = ctx.info.get("stamps")
    if not stamps or ctx.spans is None:
        return None
    whole = [(a / 1e3, c / 1e3) for a, _, c in stamps]
    return sum(compile_us_within(ctx.spans, whole)) / len(stamps) / 1e3
