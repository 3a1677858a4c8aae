"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy and idle time,
per-op device time and per-program device time.

The profiler writes one plane per TPU chip (``/device:TPU:<n>``) whose
``XLA Ops`` line holds one event per executed HLO op and whose ``XLA Modules``
line holds one event per executed program, and host planes whose lines hold
``TraceAnnotation`` spans.  Device and host events share one clock, so the
benchmark marks its measured window with a host span named ``WINDOW`` and
every device number is clipped to that span.

Everything below :func:`load` works on plain tuples, so the arithmetic is
tested on synthetic traces without a profiler.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path

WINDOW = "chipbench.window"
_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# a TPU op event is named by its HLO text, "%fusion.12 = f32[...] fusion(...)"
_HLO_NAME = re.compile(r"^%?([^\s=]+)")
# HLO op names of the collectives, with or without the async -start/-done;
# the compiler writes some with underscores ("all_to_all.7")
_COLLECTIVE = re.compile(
    r"^(all[-_]reduce|all[-_]gather|reduce[-_]scatter|all[-_]to[-_]all"
    r"|collective[-_]permute)([-_]start|[-_]done)?(\.\d+)?$")


@dataclasses.dataclass
class Trace:
    """Events of one traced window, times in nanoseconds on the trace clock.

    ``ops[d]`` and ``modules[d]`` are ``(name, start_ns, dur_ns)`` lists for
    device ``d``; ``host`` is the same for host spans; ``window`` is the
    ``(start_ns, end_ns)`` of the measured window."""

    ops: dict[int, list[tuple[str, float, float]]]
    modules: dict[int, list[tuple[str, float, float]]]
    host: list[tuple[str, float, float]]
    window: tuple[float, float]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def short_name(name: str) -> str:
    """The HLO instruction's name, without its text."""
    m = _HLO_NAME.match(name)
    return m.group(1) if m else name


def self_times(events):
    """``(name, self_ns)`` per event: its duration less the part the events
    nested in it cover (a ``while`` op's event encloses its body's ops)."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    own = [d for _, _, d in evs]
    stack = []  # the enclosing events still open
    for i, (_, s, d) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            j = stack[-1]
            own[j] -= min(s + d, evs[j][1] + evs[j][2]) - s
        stack.append(i)
    return [(evs[i][0], max(own[i], 0.0)) for i in range(len(evs))]


def clip(events, window):
    """Events cut to the window; those wholly outside it dropped."""
    lo, hi = window
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def union_ns(events) -> float:
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for _, s, d in sorted(events, key=lambda ev: ev[1]):
        e = s + d
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(events, window):
    """``(start_ns, dur_ns)`` of each stretch of the window with no event."""
    out, t = [], window[0]
    for _, s, d in sorted(clip(events, window), key=lambda ev: ev[1]):
        if s > t:
            out.append((t, s - t))
        t = max(t, s + d)
    if window[1] > t:
        out.append((t, window[1] - t))
    return out


def busy_s(tr: Trace) -> float:
    """Seconds in which some op ran, averaged over the traced devices."""
    if not tr.ops:
        return 0.0
    per = [union_ns(clip(evs, tr.window)) for evs in tr.ops.values()]
    return sum(per) / len(per) * 1e-9


def idle_share(tr: Trace) -> float | None:
    """1 - busy / window, averaged over the traced devices."""
    if not tr.ops or tr.window_s <= 0:
        return None
    return 1.0 - busy_s(tr) / tr.window_s


def op_seconds(tr: Trace, match=None) -> dict[str, float]:
    """Device seconds per op name inside the window, each op's own time
    without its nested ops, averaged over devices; ``match(name)`` keeps
    only some names."""
    sums: dict[str, float] = {}
    for evs in tr.ops.values():
        for name, d in self_times(clip(evs, tr.window)):
            if match is None or match(name):
                sums[name] = sums.get(name, 0.0) + d
    n = max(len(tr.ops), 1)
    return {k: v / n * 1e-9 for k, v in sums.items()}


def is_collective(name: str) -> bool:
    return _COLLECTIVE.match(name) is not None


def module_seconds(tr: Trace, fragment: str) -> list[float]:
    """Device seconds of every execution, on every device, of programs whose
    name holds ``fragment`` and that started inside the window."""
    lo, hi = tr.window
    return [d * 1e-9 for evs in tr.modules.values() for name, s, d in evs
            if fragment in name and lo <= s < hi]


def module_calls(tr: Trace, fragment: str) -> tuple[int, float]:
    """(calls, device seconds) of programs whose name holds ``fragment``,
    per device on average, for programs that started inside the window."""
    secs = module_seconds(tr, fragment)
    n = max(len(tr.modules), 1)
    return int(round(len(secs) / n)), sum(secs) / n


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The ops that took most device time, and the longest idle gaps of
    the first device labelled with the host span that covers most of each,
    where one covers at least half of it."""
    ops = sorted(op_seconds(tr).items(), key=lambda kv: -kv[1])[:top]
    out = {"device_ops": [[k, v] for k, v in ops], "idle_gaps": []}
    if not tr.ops:
        return out
    dev = min(tr.ops)
    longest = sorted(gaps(tr.ops[dev], tr.window), key=lambda g: -g[1])[:top]
    host = [h for h in tr.host if h[0] != WINDOW]
    for start, dur in longest:
        best, cover = "no host span", dur / 2
        for name, s, d in host:
            c = min(s + d, start + dur) - max(s, start)
            if c > cover:
                best, cover = name, c
        out["idle_gaps"].append([best, dur * 1e-9])
    return out


def load(path: str | Path) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        m = _TPU_PLANE.match(plane.name)
        for line in plane.lines:
            evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in line.events]
            if m and line.name == "XLA Ops":
                ops[int(m.group(1))] = [(short_name(n), st, d)
                                        for n, st, d in evs]
            elif m and line.name == "XLA Modules":
                modules[int(m.group(1))] = evs
            elif plane.name.startswith("/host:"):
                host.extend(evs)
    marks = [(s, s + d) for name, s, d in host if name == WINDOW]
    if not marks:
        raise ValueError(f"{path}: no {WINDOW!r} span in the trace")
    return Trace(ops=ops, modules=modules, host=host, window=marks[0])


def find_xplane(log_dir: str | Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]
