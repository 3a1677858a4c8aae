"""Milliseconds per planning request in the selector race, from the
program's own spans: the union of its ``select.batch`` and ``select`` spans
less the ``compile`` spans inside them, over the requests of its ``plan``
spans.  Nothing where the program opens no ``plan`` span."""

from chipbench import spanset
from chipbench.progspans import compile_us_within


def read(ctx):
    requests = spanset.planned_requests(ctx.spans)
    if not requests:
        return None
    race = spanset.union(spanset.closed(ctx.spans, "select.batch", "select"))
    inside = sum(compile_us_within(ctx.spans, race))
    return (spanset.length(race) - inside) / requests / 1e3
