"""The program's own span records (``repro.obs.trace``: ``ts`` and ``dur``
in microseconds, ``sid`` and ``parent`` linking each span to the one open
round it) as sets of intervals, for the per-layer readers."""


def closed(spans, *names):
    """The finished spans (``ph`` "X") with one of ``names``."""
    return [r for r in spans or () if r.get("ph") == "X"
            and r.get("name") in names]


def union(spans) -> list[tuple[float, float]]:
    """The union of the spans' intervals, as sorted disjoint intervals."""
    out: list[list[float]] = []
    for lo, hi in sorted((r["ts"], r["ts"] + r["dur"]) for r in spans):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def length(intervals) -> float:
    return sum(hi - lo for lo, hi in intervals)


def planned_requests(spans) -> int:
    """Requests answered by ``api.plan_batch``: its ``plan`` spans'
    ``requests``."""
    return sum(r.get("args", {}).get("requests", 0)
               for r in closed(spans, "plan"))
