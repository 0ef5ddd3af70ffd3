"""From a profiler trace to device busy time, idle share and a breakdown.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane; host spans are the events that the benchmark's
own ``jax.profiler.TraceAnnotation`` calls write (names starting with
``bench.``) on the host plane, on the same clock.

* busy time: the union of a device's operation intervals, clipped to the
  traced window (span ``bench.window``), averaged over the chips;
* idle share: 1 - busy / window;
* top operations: device self time (an operation's time less that of the
  operations nested in it, such as a loop's body) per operation, named
  by its HLO name, output shape and fusion kind;
* idle gaps: the gaps in the union inside the window, each labelled with
  the innermost ``bench.`` span around its midpoint (and the innermost
  other host event on that thread inside it, where there is one), summed
  per label.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


class Event(NamedTuple):
    name: str
    start: float     # ns
    end: float       # ns
    where: str       # device plane, or host thread (line) name


class Trace(NamedTuple):
    ops: Dict[str, List[Event]]     # device plane -> its operations
    host: List[Event]               # every event on the host plane's lines


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def read(trace_dir: str) -> Trace:
    """Read the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(find_xplane(trace_dir))
    ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in prof.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend(Event(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns, plane.name)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, line.name)
                            for e in line.events)
    return Trace(ops, host)


def op_label(text: str) -> str:
    """Short name of a device operation from its HLO text:
    ``%fusion.6 = bf16[65537,2048]{...} fusion(...), kind=kCustom`` becomes
    ``fusion.6 bf16[65537,2048] kCustom``."""
    name, _, rest = text.partition(" = ")
    label = name.lstrip("%")
    shape = re.match(r"\w+\[[\d,]*\]", rest)
    kind = re.search(r"kind=(k\w+)", rest)
    return " ".join([label] + [m.group(m.lastindex or 0)
                               for m in (shape, kind) if m])


def self_times(events: List[Event]) -> List[Tuple[Event, float]]:
    """Each event with its self time: its duration less the overlap of the
    events directly nested in it (events on one line nest)."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    own = [e.end - e.start for e in evs]
    stack: List[int] = []
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e.end, evs[stack[-1]].end) - e.start
        stack.append(i)
    return list(zip(evs, own))


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: List[Tuple[float, float]], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the disjoint ``merged`` intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def gaps(merged: List[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The parts of ``[lo, hi]`` that ``merged`` leaves uncovered."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def spans(trace: Trace, name: Optional[str] = None) -> List[Event]:
    """The benchmark's host spans (all, or those called ``name``), in order."""
    return sorted((e for e in trace.host if e.name.startswith(SPAN_PREFIX)
                   and (name is None or e.name == name)),
                  key=lambda e: e.start)


def _labels(host: List[Event], thread: str,
            times: List[float]) -> List[str]:
    """What the host was doing at each of ``times``: the innermost bench
    span around it on ``thread`` and, inside that span, the innermost other
    event. Events on one thread nest, so one sweep with a stack of open
    events finds them."""
    evs = sorted((e for e in host if e.where == thread),
                 key=lambda e: (e.start, -e.end))
    order = sorted(range(len(times)), key=times.__getitem__)
    out = [""] * len(times)
    stack: List[Event] = []
    i = 0
    for k in order:
        t = times[k]
        while i < len(evs) and evs[i].start <= t:
            while stack and stack[-1].end <= evs[i].start:
                stack.pop()
            stack.append(evs[i])
            i += 1
        while stack and stack[-1].end <= t:
            stack.pop()
        bench = [j for j, e in enumerate(stack)
                 if e.name.startswith(SPAN_PREFIX)]
        if not bench:
            out[k] = "outside bench spans"
            continue
        name = stack[bench[-1]].name
        if bench[-1] < len(stack) - 1:
            name += "/" + stack[-1].name
        out[k] = name
    return out


def summarize(trace: Trace, busy_spans: Tuple[str, ...] = (),
              top: int = 10) -> dict:
    """Busy time, window, idle share, per-span busy and the breakdown.

    Returns ``busy_s`` (mean over the chips), ``window_s``, ``idle_share``,
    ``span_busy_s`` ({span name in ``busy_spans``: [busy seconds inside
    each such span, in order]}, first chip) and ``breakdown`` ({"device_ops": [[name, s]...],
    "idle_gaps": [[label, s]...]}, at most ``top`` each).
    """
    (win,) = spans(trace, WINDOW_SPAN) or [None]
    if win is None:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    lo, hi = win.start, win.end
    if not trace.ops or not any(trace.ops.values()):
        raise ValueError("trace has no device operations")
    merged = {dev: union((e.start, e.end) for e in evs)
              for dev, evs in trace.ops.items()}
    busy = [covered(m, lo, hi) for m in merged.values()]
    first = merged[sorted(merged)[0]]

    per_op: Dict[str, float] = defaultdict(float)
    for evs in trace.ops.values():
        for e, own in self_times(evs):
            if lo <= e.start < hi:
                per_op[op_label(e.name)] += own
    per_gap: Dict[str, float] = defaultdict(float)
    idle = gaps(first, lo, hi)
    for (s, e), label in zip(idle, _labels(trace.host, win.where,
                                           [(s + e) / 2 for s, e in idle])):
        per_gap[label] += e - s

    span_busy = {name: [covered(first, sp.start, sp.end) / 1e9
                        for sp in spans(trace, name)]
                 for name in busy_spans}

    def ranked(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top] if v > 0]

    busy_s = sum(busy) / len(busy) / 1e9
    window_s = (hi - lo) / 1e9
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s,
            "span_busy_s": span_busy,
            "breakdown": {"device_ops": ranked(per_op),
                          "idle_gaps": ranked(per_gap)}}
