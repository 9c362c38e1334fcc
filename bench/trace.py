"""Profiler trace: recording a steady stretch, and reducing it.

The reduction reads the ``.xplane.pb`` the JAX profiler writes, with
nothing but ``jax.profiler.ProfileData``:

* device operations are the events of each TPU plane's op line, named
  by their HLO instruction (``ash_score_topk_pallas.1``, ``fusion.7``)
  and attributed to the jitted program (module line) that ran them;
  busy time is the union of their intervals inside the traced window,
  per chip, averaged over chips, and the device's idle share is
  ``1 - busy / window``;
* kernel time is the summed duration of the operations whose name
  matches a kernel's pattern (a per-layer metric file names it);
* the breakdown lists the operations that took the most time, and the
  longest idle gaps labelled by the innermost host span that covers
  each (a benchmark span, or whatever the host plane shows there).

The traced window is the host span ``WINDOW_SPAN`` that the benchmark
opens around the stretch it traces.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
WINDOW_SPAN = "bench.trace_window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# lines of a TPU plane that hold the individual operations (others hold
# modules, steps or source annotations that overlap them)
_OP_LINES = ("XLA Ops",)
_MODULE_LINES = ("XLA Modules",)
# "%fusion.7 = f32[...] fusion(...)" -> "fusion.7";
# "jit__score_gathered(9730854252342962442)" -> "jit__score_gathered"
_OP_NAME = re.compile(r"^%?([^\s=]+)")
_MODULE_NAME = re.compile(r"^(.*?)(\(\d+\))?$")


@dataclasses.dataclass
class Event:
    name: str
    start: float  # seconds on the profiler's clock
    end: float
    stats: dict
    whole: bool = True  # False once clipped to the window's edge
    module: str = ""  # the program an operation ran in


@dataclasses.dataclass
class Trace:
    window: tuple  # (start, end) seconds
    ops: dict  # device plane name -> [Event] inside the window
    host: list  # host spans: [Event] (every thread)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_intervals(self, plane: str) -> list:
        return _union([(e.start, e.end) for e in self.ops[plane]])

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over chips."""
        if not self.ops:
            return 0.0
        per = [sum(b - a for a, b in self.busy_intervals(p)) for p in self.ops]
        return sum(per) / len(per)

    def matching(self, pattern: str) -> list:
        """Operations whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return [
            e for evs in self.ops.values() for e in evs
            if rx.search(e.name)
        ]

    def top_ops(self, n: int = 10) -> list:
        """(program/operation, seconds) that took the most time."""
        tot: dict = {}
        for evs in self.ops.values():
            for e in evs:
                key = f"{e.module}/{e.name}"
                tot[key] = tot.get(key, 0.0) + (e.end - e.start)
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> list:
        """The ``n`` longest idle gaps of the first chip, each named by
        the innermost host span covering its midpoint."""
        if not self.ops:
            return []
        plane = sorted(self.ops)[0]
        busy = self.busy_intervals(plane)
        a0, b0 = self.window
        edges = [a0] + [x for ab in busy for x in ab] + [b0]
        gaps = [
            (edges[i], edges[i + 1])
            for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]
        ]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [(self._label((a + b) / 2), b - a) for a, b in gaps[:n]]

    def _label(self, t: float) -> str:
        best = None
        for e in self.host:
            if e.name == WINDOW_SPAN or not (e.start <= t <= e.end):
                continue
            if best is None or e.end - e.start < best.end - best.start:
                best = e
        return "unattributed" if best is None else best.name


def _union(iv: list) -> list:
    out: list = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def _event(e, name=None) -> Event:
    start = e.start_ns * 1e-9
    return Event(e.name if name is None else name, start,
                 start + e.duration_ns * 1e-9, dict(e.stats))


def _attribute(ops: list, modules: list) -> None:
    """Name each operation's module: the module event that holds its
    start (both lists sorted by start)."""
    starts = [m.start for m in modules]
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.start <= modules[i].end:
            op.module = modules[i].name


def _clip(evs: list, a: float, b: float) -> list:
    return [
        dataclasses.replace(
            e, start=max(e.start, a), end=min(e.end, b),
            whole=a <= e.start and e.end <= b,
        )
        for e in evs if e.end > a and e.start < b
    ]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    """Reduce an ``.xplane.pb`` to the window, device ops and host
    spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, dev = [], {}
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for ln in plane.lines:
                if ln.name in _OP_LINES:
                    ops += [_event(e, _OP_NAME.match(e.name).group(1))
                            for e in ln.events]
                elif ln.name in _MODULE_LINES:
                    modules += [
                        _event(e, _MODULE_NAME.match(e.name).group(1))
                        for e in ln.events
                    ]
            ops.sort(key=lambda e: e.start)
            modules.sort(key=lambda e: e.start)
            _attribute(ops, modules)
            dev[plane.name] = ops
        elif plane.name.startswith("/host:"):
            host += [
                _event(e) for ln in plane.lines for e in ln.events
                if e.duration_ns > 0
            ]
    spans = [e for e in host if e.name == WINDOW_SPAN]
    if spans:
        window = (spans[0].start, spans[0].end)
    else:
        all_ops = [e for evs in dev.values() for e in evs]
        if not all_ops:
            raise ValueError(f"{path}: no device operations and no window")
        window = (min(e.start for e in all_ops), max(e.end for e in all_ops))
    a, b = window
    return Trace(
        window=window, host=host,
        ops={p: _clip(evs, a, b) for p, evs in dev.items()},
    )


class Recorder:
    """Start the profiler and open the window span."""

    def __init__(self, log_dir: str):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # Python calls would swamp the host
        opts.host_tracer_level = 2
        self.log_dir = log_dir
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self) -> str:
        """Close the span and the profiler; the ``.xplane.pb`` path."""
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return find_xplane(self.log_dir)
