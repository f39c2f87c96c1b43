"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device metrics.

The trace holds one plane per device (``/device:TPU:<i>``) whose ``XLA Ops``
line has one event per operation run and whose ``XLA Modules`` line has one
event per program execution, and a host plane (``/host:CPU``) whose lines
are host threads, with the benchmark's ``solve`` annotations among their
events.  All events are on one clock, in nanoseconds.

``reduce_file`` returns:

* ``window_s``: the traced window, from the first ``solve`` annotation's
  start to the last one's end (the whole trace where there is none);
* ``busy_s``: the union of operation intervals inside the window, averaged
  over the device planes;
* ``device_ops``: ``[name, seconds]`` of the operations that took most
  device time, summed by name (``while`` and ``conditional`` events left
  out, since the events of their bodies are listed too);
* ``modules``: per program name (the ``XLA Modules`` event name without
  its ``(id)`` suffix), executions and device seconds;
* ``custom_calls``: per program, the device events of its Pallas kernels
  (``tpu_custom_call`` custom calls), as ``[start_ns, duration_ns, name]``;
* ``idle_gaps``: ``[host activity, seconds]``: the device's idle time in
  the window attributed to the innermost host event that covers each gap's
  midpoint, summed by name.
"""
from __future__ import annotations

import bisect
import re

SOLVE = "solve"
LABEL_CHARS = 160
_MODULE_ID = re.compile(r"\(\d+\)$")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
_CONTAINER = re.compile(r"(^|[\s)}])(while|conditional|call)\(")


def _events(line):
    for e in line.events:
        yield float(e.start_ns), float(e.duration_ns), e


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def module_name(name: str) -> str:
    return _MODULE_ID.sub("", name)


def is_kernel(name: str) -> bool:
    """A Pallas (Mosaic) kernel event.  On a TPU an ``XLA Ops`` event is
    named by its HLO instruction, ``%<name> = <shape> custom-call(<operands>),
    custom_call_target="tpu_custom_call", ...``; XLA's own custom calls
    (``AllocateBuffer``) name another target."""
    return KERNEL_TARGET in name


def is_container(name: str) -> bool:
    """A ``while``, ``conditional`` or ``call`` event: it spans the events
    of its body, which the trace lists too."""
    return _CONTAINER.search(name) is not None


def op_label(name: str) -> str:
    """An operation's name as the breakdown gives it: the HLO instruction,
    cut to ``LABEL_CHARS``."""
    return name[:LABEL_CHARS]


def reduce_profile(pd) -> dict:
    host_spans, solves = [], []
    device_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for s, d, ev in _events(line):
                    if ev.name == SOLVE:
                        solves.append((s, s + d))
                    elif d > 0:
                        host_spans.append((s, s + d, ev.name))

    ops_by_plane, modules_by_plane = [], []
    for plane in device_planes:
        ops, mods = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = [(s, d, ev) for s, d, ev in _events(line)]
            elif line.name == "XLA Modules":
                mods = [(s, d, ev.name) for s, d, ev in _events(line)]
        ops_by_plane.append(ops)
        modules_by_plane.append(mods)

    if solves:
        lo, hi = min(s for s, _ in solves), max(e for _, e in solves)
    else:
        ends = [(s, s + d) for ops in ops_by_plane for s, d, _ in ops]
        ends += [(s, e) for s, e, _ in host_spans]
        lo = min((s for s, _ in ends), default=0.0)
        hi = max((e for _, e in ends), default=0.0)
    window_ns = max(hi - lo, 0.0)

    busy, op_time, modules, custom = [], {}, {}, {}
    merged_first = []
    for i, ops in enumerate(ops_by_plane):
        merged = _union(_clip([(s, s + d) for s, d, _ in ops], lo, hi))
        if i == 0:
            merged_first = merged
        busy.append(sum(e - s for s, e in merged))
        for s, d, ev in ops:
            if s + d <= lo or s >= hi or is_container(ev.name):
                continue
            label = op_label(ev.name)
            op_time[label] = op_time.get(label, 0.0) + d
        mods = sorted(modules_by_plane[i])
        for s, d, name in mods:
            if s + d <= lo or s >= hi:
                continue
            m = modules.setdefault(module_name(name),
                                   {"count": 0, "seconds": 0.0})
            m["count"] += 1
            m["seconds"] += d * 1e-9
        starts = [s for s, _, _ in mods]
        for s, d, ev in ops:
            if s + d <= lo or s >= hi or not is_kernel(ev.name):
                continue
            owner = _owner(mods, starts, s)
            custom.setdefault(owner, []).append([s, d, ev.name])

    gaps = _gaps(merged_first, lo, hi, host_spans, solves)
    n_dev = max(len(device_planes), 1)
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "devices": len(device_planes),
        "solves": len(solves),
        "device_ops": [[k, v * 1e-9] for k, v in
                       sorted(op_time.items(), key=lambda kv: -kv[1])],
        "modules": modules,
        "custom_calls": custom,
        "idle_gaps": [[k, v * 1e-9] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])],
    }


def _owner(mods, starts, t):
    """Name of the program execution that contains time ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0:
        s, d, name = mods[i]
        if s <= t <= s + d:
            return module_name(name)
    return "?"


def _gaps(merged, lo, hi, host_spans, solves):
    """Idle time between busy intervals, cut at solve boundaries, each piece
    named by the innermost host event covering its midpoint: a piece inside
    a solve with no finer host event is the solve's own host work
    (``solve``), one outside every solve is ``between solves``."""
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    cuts = sorted({t for iv in solves for t in iv})
    pieces = []
    for a, b in zip(edges[0::2], edges[1::2]):
        inner = [t for t in cuts[bisect.bisect_right(cuts, a):
                                 bisect.bisect_left(cuts, b)]]
        bounds = [a] + inner + [b]
        pieces += list(zip(bounds[:-1], bounds[1:]))
    spans = sorted(host_spans)
    starts = [s for s, _, _ in spans]
    out: dict[str, float] = {}
    for a, b in pieces:
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        best, best_d = "between solves", None
        if any(s <= mid <= e for s, e in solves):
            best = SOLVE
        j = bisect.bisect_right(starts, mid)
        # scan back over the spans that start before the midpoint; host
        # events nest, so the covering ones are among the recent starts
        for s, e, name in reversed(spans[max(0, j - 256):j]):
            if e >= mid and (best_d is None or e - s < best_d):
                best, best_d = name, e - s
        out[best] = out.get(best, 0.0) + (b - a)
    return out


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))
