"""The readers of the program's own spans (``bench/spans.py``, the
``idle_*_share`` and ``prep_ms`` metrics) on a hand-built trace, and on
the committed chip traces, which predate the spans."""
import types

import pytest

from bench import layers, spec
from bench import trace as T

DATA = spec.BENCH / "testdata"
READERS = ("idle_in_call_share", "idle_between_calls_share", "prep_ms")


def _ev(name, a, b):
    return T.Event(name, a, b, {})


def _trace(planes):
    """Window [0, 1] s; two fused calls, each with its prep."""
    host = [
        _ev(T.WINDOW_SPAN, 0.0, 1.0),
        # metadata kept inline in the name must not hide the span
        _ev("ash.call#call=1,rows=4#", 0.05, 0.35),
        _ev("ash.call", 0.45, 0.95),
        _ev("ash.prep", 0.06, 0.09),  # 30 ms
        _ev("ash.prep", 0.46, 0.50),  # 40 ms
        _ev("ash.prep", 0.97, 1.02),  # past the window's end: not read
        _ev("ash.fetch", 0.31, 0.34),
    ]
    ops = {
        f"/device:TPU:{i}": [_ev("op", a, b) for a, b in busy]
        for i, busy in enumerate(planes)
    }
    return T.Trace(window=(0.0, 1.0), ops=ops, host=host)


def _read(name, tr):
    ctx = types.SimpleNamespace(trace=tr)
    return spec.metric_reader(name + ".closed")(ctx)


@pytest.mark.parametrize("planes, in_call, between", [
    # idle [0, .1] [.3, .5] [.9, 1]: .05 + .05 + .05 + .05 inside calls
    ([[(0.1, 0.3), (0.5, 0.9)]], 20.0, 20.0),
    # a second chip busy all window long halves both shares
    ([[(0.1, 0.3), (0.5, 0.9)], [(0.0, 1.0)]], 10.0, 10.0),
    # overlapping device operations count once
    ([[(0.1, 0.2), (0.15, 0.3), (0.5, 0.9)]], 20.0, 20.0),
])
def test_idle_split_adds_up_to_idle_share(planes, in_call, between):
    tr = _trace(planes)
    got_in = _read("idle_in_call_share", tr)
    got_out = _read("idle_between_calls_share", tr)
    assert got_in == pytest.approx(in_call)
    assert got_out == pytest.approx(between)
    idle = layers.idle_share(types.SimpleNamespace(trace=tr))
    assert got_in + got_out == pytest.approx(idle, abs=0.1)


def test_prep_median_reads_whole_spans_in_the_window():
    tr = _trace([[(0.1, 0.3), (0.5, 0.9)]])
    assert _read("prep_ms", tr) == pytest.approx(35.0)


def test_no_call_no_reading():
    tr = _trace([[(0.1, 0.3)]])
    tr.host = [e for e in tr.host if not e.name.startswith("ash.call")]
    for name in READERS:
        assert _read(name, tr) is None
    for name in READERS:
        assert _read(name, None) is None


@pytest.mark.parametrize(
    "cell", ["sift-flat-batch32-closed", "glove-ivf-single-open"])
def test_committed_traces_predate_the_spans(cell):
    tr = T.load(str(DATA / f"{cell}.xplane.pb"))
    for name in READERS:
        assert _read(name, tr) is None
