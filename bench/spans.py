"""Reductions over the program's own host spans, shared by the
per-layer metric readers that read them.

The serving engine (``serving/engine.py``) emits ``ash.*`` spans with
``jax.profiler.TraceAnnotation``; they land in the ``.xplane.pb`` beside
the device operations, on the same clock.  A span is matched on its
name before any ``#`` (where the profiler keeps a span's metadata
inline) and only its part inside the traced window counts.  Each
reduction returns ``None`` where the window holds no ``ash.call``: a
program without the spans, or a stretch in which no fused call ran.
"""
from __future__ import annotations

import statistics

from bench.trace import _union

CALL = "ash.call"
PREP = "ash.prep"


def span_name(e) -> str:
    return e.name.split("#", 1)[0]


def named(trace, name: str) -> list:
    """Host spans called ``name`` that overlap the window."""
    a, b = trace.window
    return [
        e for e in trace.host
        if span_name(e) == name and e.end > a and e.start < b
    ]


def _complement(iv: list, a: float, b: float) -> list:
    """[a, b] minus the disjoint sorted intervals ``iv``."""
    out, t = [], a
    for x, y in iv:
        if x > t:
            out.append((t, x))
        t = max(t, y)
    if t < b:
        out.append((t, b))
    return out


def _overlap(xs: list, ys: list) -> float:
    """Total length common to two disjoint sorted interval lists."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        lo = max(xs[i][0], ys[j][0])
        hi = min(xs[i][1], ys[j][1])
        if hi > lo:
            total += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_split(trace):
    """(idle with an ``ash.call`` open, idle with none), each in % of
    the traced window.  Idle is what no device operation covers, per
    chip and averaged over chips as ``Trace.busy_s`` does, so the two
    add up to ``layers.idle_share``."""
    if trace is None or not trace.ops or trace.window_s <= 0:
        return None
    a, b = trace.window
    calls = _union([
        (max(e.start, a), min(e.end, b)) for e in named(trace, CALL)
    ])
    if not calls:
        return None
    between = _complement(calls, a, b)
    in_call = out_call = 0.0
    for plane in trace.ops:
        idle = _complement(trace.busy_intervals(plane), a, b)
        in_call += _overlap(idle, calls)
        out_call += _overlap(idle, between)
    scale = 100.0 / (len(trace.ops) * trace.window_s)
    return in_call * scale, out_call * scale


def prep_ms(trace):
    """Median duration (ms) of the ``ash.prep`` spans wholly inside the
    window: query prep and the prep cache, once per fused call."""
    if trace is None or not named(trace, CALL):
        return None
    a, b = trace.window
    ms = [
        1e3 * (e.end - e.start) for e in named(trace, PREP)
        if a <= e.start and e.end <= b
    ]
    return statistics.median(ms) if ms else None
