"""Time of the program's own ``compile`` spans inside each planning
request, for the planner's per-layer readers."""


def compile_us_within(spans, intervals):
    """For each ``(start_us, end_us)``, the microseconds of it covered by
    some ``compile`` span."""
    comp = sorted((r["ts"], r["ts"] + r["dur"]) for r in spans
                  if r.get("name") == "compile" and r.get("ph") == "X")
    out, j = [], 0
    for lo, hi in intervals:
        while j < len(comp) and comp[j][1] <= lo:
            j += 1
        covered, reach, k = 0.0, lo, j
        while k < len(comp) and comp[k][0] < hi:
            s, e = max(comp[k][0], reach), min(comp[k][1], hi)
            if e > s:
                covered += e - s
                reach = e
            k += 1
        out.append(covered)
    return out
