"""Schedule compiles per planning request: the program's ``compile`` spans
that no other ``compile`` span encloses (an optimized entry compiles its base
inside it), over the requests of its ``plan`` spans.  Nothing where the
program opens no ``plan`` span."""

from chipbench import spanset


def read(ctx):
    requests = spanset.planned_requests(ctx.spans)
    if not requests:
        return None
    by_sid = {r["sid"]: r for r in ctx.spans if r.get("ph") == "X"}
    outer = 0
    for r in spanset.closed(ctx.spans, "compile"):
        up = by_sid.get(r.get("parent"))
        while up is not None and up["name"] != "compile":
            up = by_sid.get(up.get("parent"))
        outer += up is None
    return outer / requests
