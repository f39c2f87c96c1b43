"""The program's own spans in a profiler trace, and the idle gaps they name.

The solver marks its phases with host spans (``repro.spans``):
``partition.*``, ``schedule.*``, ``device.*`` and ``windows.*`` events on
the host plane, on the device planes' clock.  ``reduce_profile`` returns
what ``bench.trace_reduce.reduce_profile`` does, with two keys changed or
added:

* ``idle_gaps``: the device's idle time is cut at the edges of every
  ``solve`` and of every span, and each piece is named by the innermost
  host event that covers its midpoint, found through each host line's
  nesting stack, so a span is found however many events began inside it
  before the gap.  ``trace_reduce`` cuts at ``solve`` edges only, so one
  long gap is put down wholly to the span at its middle, and it looks only
  among the 256 host events that began last, so a gap late in a level
  that holds a device pass, after thousands of JAX runtime events, falls
  back to ``solve``.  On a trace without spans whose covers began fewer
  than 256 events before their gaps, the two agree exactly.
* ``spans``: per span name, ``count``, ``seconds`` (inclusive), ``p50_s``
  (the median of the events' durations), ``self_s`` (each event's duration
  less what its direct children on the same host line cover, children of
  any name) and ``self_p50_s`` (the median of the events' self times).

The benchmark's traced runs reduce their trace with ``reduce_dir``, after
the window has closed, and hand the result to the metric readers as
``ctx.trace``.
"""
from __future__ import annotations

import bisect
import glob
import os
import statistics

from bench import trace_reduce
from bench.trace_reduce import SOLVE, _clip, _events, _union

SPAN_PREFIXES = ("partition.", "schedule.", "device.", "windows.")


def is_span(name: str) -> bool:
    return name.startswith(SPAN_PREFIXES)


def host_lines(pd) -> list[list[tuple[float, float, str]]]:
    """``(start_ns, end_ns, name)`` of each host line's events, ordered so
    that an event comes after every event that encloses it."""
    lines = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(s, s + d, ev.name) for s, d, ev in _events(line)]
                evs.sort(key=lambda e: (e[0], -e[1]))
                lines.append(evs)
    return lines


def _nest(events):
    """Each event's parent index on its line (-1 at top level)."""
    parent, stack = [], []
    for i, (s, _, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        parent.append(stack[-1] if stack else -1)
        stack.append(i)
    return parent


def span_table(lines) -> dict[str, dict]:
    """Count, inclusive seconds and self seconds per span name."""
    durs: dict[str, list[float]] = {}
    selfs: dict[str, list[float]] = {}
    for events in lines:
        parent = _nest(events)
        covered = [0.0] * len(events)
        for i, (s, e, _) in enumerate(events):
            p = parent[i]
            if p >= 0:
                covered[p] += min(e, events[p][1]) - s
        for i, (s, e, name) in enumerate(events):
            if is_span(name):
                durs.setdefault(name, []).append(e - s)
                selfs.setdefault(name, []).append(e - s - covered[i])
    return {name: {"count": len(d), "seconds": sum(d) * 1e-9,
                   "p50_s": statistics.median(d) * 1e-9,
                   "self_s": sum(selfs[name]) * 1e-9,
                   "self_p50_s": statistics.median(selfs[name]) * 1e-9}
            for name, d in sorted(durs.items())}


def _shorter(ev, best) -> bool:
    """The shorter cover wins; of equal ones, the one that began last."""
    if best is None:
        return True
    d, bd = ev[1] - ev[0], best[1] - best[0]
    return d < bd or (d == bd and ev[0] > best[0])


def _innermost(events, times):
    """For each of the sorted ``times``, the shortest event of one line
    (ordered by ``host_lines``) that covers it, or None.  The stack holds
    the events begun so far, less those seen to have ended: on a thread,
    whose events nest, the chain of open events, a few deep."""
    out, stack, i = [], [], 0
    for t in times:
        while i < len(events) and events[i][0] <= t:
            while stack and stack[-1][1] < events[i][0]:
                stack.pop()
            stack.append(events[i])
            i += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        best = None
        for ev in stack:
            if ev[1] >= t and _shorter(ev, best):
                best = ev
        out.append(best)
    return out


def idle_gaps(busy, lo, hi, lines, solves) -> dict[str, float]:
    """Idle time between busy intervals, cut at the edges of solves and
    spans, each piece named by the shortest host event covering its
    midpoint (the innermost of its line), else ``solve`` inside a solve and
    ``between solves`` outside every one (``trace_reduce._gaps``'s rule)."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    cuts = {t for iv in solves for t in iv}
    cuts.update(t for events in lines for s, e, name in events
                if is_span(name) for t in (s, e))
    cuts = sorted(cuts)
    pieces = []
    for a, b in zip(edges[0::2], edges[1::2]):
        inner = cuts[bisect.bisect_right(cuts, a):bisect.bisect_left(cuts, b)]
        bounds = [a] + inner + [b]
        pieces += [(x, y) for x, y in zip(bounds[:-1], bounds[1:]) if y > x]
    mids = [0.5 * (a + b) for a, b in pieces]
    order = sorted(range(len(pieces)), key=mids.__getitem__)
    times = [mids[k] for k in order]
    best: list = [None] * len(pieces)
    for events in lines:
        cand = [ev for ev in events if ev[2] != SOLVE and ev[1] > ev[0]]
        for k, ev in zip(order, _innermost(cand, times)):
            if ev is not None and _shorter(ev, best[k]):
                best[k] = ev
    out: dict[str, float] = {}
    for (a, b), mid, ev in zip(pieces, mids, best):
        if ev is not None:
            name = ev[2]
        elif any(s <= mid <= e for s, e in solves):
            name = SOLVE
        else:
            name = "between solves"
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def reduce_profile(pd) -> dict:
    out = trace_reduce.reduce_profile(pd)
    lines = host_lines(pd)
    solves = [(s, e) for events in lines for s, e, name in events
              if name == SOLVE]
    if solves:
        lo, hi = min(s for s, _ in solves), max(e for _, e in solves)
    else:
        ends = [(s, s + d) for plane in pd.planes
                if plane.name.startswith("/device:TPU:")
                for line in plane.lines if line.name == "XLA Ops"
                for s, d, _ in _events(line)]
        ends += [(s, e) for events in lines for s, e, _ in events if e > s]
        lo = min((s for s, _ in ends), default=0.0)
        hi = max((e for _, e in ends), default=0.0)
    busy = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [(s, s + d) for line in plane.lines
                   if line.name == "XLA Ops" for s, d, _ in _events(line)]
            busy = _union(_clip(ops, lo, hi))
            break
    gaps = idle_gaps(busy, lo, hi, lines, solves)
    out["idle_gaps"] = [[k, v * 1e-9] for k, v in
                        sorted(gaps.items(), key=lambda kv: -kv[1])]
    out["spans"] = span_table(lines)
    return out


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def reduce_dir(trace_dir: str) -> dict:
    """Reduce the one ``*.xplane.pb`` that a ``start_trace`` wrote."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    return reduce_file(paths[-1])
