"""The trace reduction, checked on CPU against traces recorded on one
TPU v5 lite by ``run.py --trace 1 --keep-trace`` (3 s stretches of
``sift-flat-batch32-closed`` and ``glove-ivf-single-open``; the glove
trace was taken under that cell's first settings, metric ``cos`` at
2,000 rows/s, which change the shapes below and not the reduction)."""
import types

import pytest

from bench import roofline, spec
from bench import trace as T
from bench.harness import TRACE_S

DATA = spec.BENCH / "testdata"


@pytest.fixture(scope="module", params=[
    "sift-flat-batch32-closed", "glove-ivf-single-open"])
def recorded(request):
    return request.param, T.load(str(DATA / f"{request.param}.xplane.pb"))


def _busy_by_sweep(evs):
    """Busy time by a sweep over start/end edges: a second way to take
    the union."""
    edges = sorted([(e.start, 1) for e in evs] + [(e.end, -1) for e in evs])
    busy, depth, last = 0.0, 0, None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_window_busy_and_gaps(recorded):
    _, tr = recorded
    assert list(tr.ops) == ["/device:TPU:0"]
    assert tr.window_s == pytest.approx(TRACE_S, abs=0.05)
    evs = tr.ops["/device:TPU:0"]
    assert 0 < tr.busy_s <= tr.window_s
    assert tr.busy_s == pytest.approx(_busy_by_sweep(evs), rel=1e-9)
    gaps = tr.idle_gaps(10_000)
    assert sum(s for _, s in gaps) == pytest.approx(
        tr.window_s - tr.busy_s, rel=1e-6)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    assert all(isinstance(n, str) and n for n, _ in gaps)


def test_ops_carry_their_program(recorded):
    name, tr = recorded
    top = dict(tr.top_ops(10))
    if name.startswith("sift"):
        # the fused dense scan + top-k kernel is nearly all device time
        kernel = tr.matching(r"^ash_score_topk_pallas(\.\d+)?$")
        assert len(kernel) == 40
        assert sum(e.end - e.start for e in kernel) == pytest.approx(
            2.390049542, rel=1e-9)
        assert max(top, key=top.get) == (
            "jit__search_prepped/ash_score_topk_pallas.1")
    else:
        kernel = [e for e in tr.matching(r"^ash_score_gather_topk_pallas")
                  if e.whole]
        assert len(kernel) == 7
        assert all(e.module == "jit__score_gathered" for e in kernel)


def test_roofline_readers_find_their_kernels(recorded):
    name, tr = recorded
    metric = ("dense_topk_roofline.closed" if name.startswith("sift")
              else "gather_topk_roofline.open")
    shapes = (
        dict(n=1_000_000, words=4, b=2, d=64, n_landmarks=1024, dim=128,
             metric="l2") if name.startswith("sift") else
        dict(n=1_183_514, words=4, b=2, d=64, n_landmarks=1024, dim=100,
             metric="cos", max_list_len=4332)
    )
    t0 = 0.0
    ctx = types.SimpleNamespace(
        trace=tr, peaks=roofline.peaks("TPU v5 lite"), shapes=shapes,
        search=dict(k=10, rerank=100, nprobe=16), trace_host=(t0, 1.0),
        calls=lambda within=None: [(0.5, 0.06, 128, 128)],
    )
    share = spec.metric_reader(metric)(ctx)
    assert 0 < share < 100


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")
