"""The serving path's host spans and the search programs' named scopes.

Spans are recorded with the benchmark's own profiler session
(``bench.trace.Recorder``) around a stretch served through
``ServingFrontend`` and read back with its reduction
(``bench.trace.load``), as a traced benchmark run reads them.  Scopes
are read from the ``op_name`` metadata of the compiled CPU programs
(``tests/test_tpu_compile.py`` reads them from the chip's).
"""
import importlib
import pathlib
import re
import sys

import jax
import numpy as onp
import pytest

from repro.core import ASHConfig
from repro.core import scoring as S
from repro.data.synthetic import embedding_dataset
from repro.index import AshIndex
from repro.serving.engine import QueryEngine
from repro.serving.frontend import ServingFrontend

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace as T  # noqa: E402

flat = importlib.import_module("repro.index.flat")
ivf = importlib.import_module("repro.index.ivf")

SPANS = (
    "ash.submit", "ash.submit.backpressure", "ash.flush", "ash.mutations",
    "ash.plan", "ash.call", "ash.prep", "ash.search.dispatch",
    "ash.search.wait", "ash.fetch", "ash.scatter",
)
# the children every fused call holds, in this order
CALL_STEPS = (
    "ash.prep", "ash.search.dispatch", "ash.search.wait", "ash.fetch",
    "ash.scatter",
)
BUCKETS = (4, 16)
DIM = 32


@pytest.fixture(scope="module")
def indexes():
    key = jax.random.PRNGKey(7)
    kx, kb = jax.random.split(key)
    X = embedding_dataset(kx, 2000, DIM)
    cfg = ASHConfig(b=2, d=16, n_landmarks=8)
    model = AshIndex.build(kb, X, cfg, backend="flat").model
    return {
        "flat": AshIndex.build(kb, X, cfg, backend="flat", model=model,
                               keep_raw=True),
        "ivf": AshIndex.build(kb, X, cfg, backend="ivf", model=model,
                              keep_raw=True),
    }


def _search_kw(backend):
    kw = dict(k=5, index="t", rerank=20)
    if backend == "ivf":
        kw["nprobe"] = 4
    return kw


@pytest.fixture(scope="module", params=("flat", "ivf"))
def served(request, indexes, tmp_path_factory):
    """A traced stretch of serving: new rows, the same rows again and
    half old, half new rows, a submitter blocked on a full queue, and
    one mutation batch.
    Returns (trace, tickets, EngineStats batches over the stretch)."""
    backend = request.param
    eng = QueryEngine(
        {"t": indexes[backend]}, batch_buckets=BUCKETS, k_buckets=(8,),
        # long enough that a burst fills the queue before the driver's
        # timeout flush drains it
        max_wait_s=0.05,
    )
    kw = _search_kw(backend)
    rng = onp.random.default_rng(3)
    with ServingFrontend(eng, max_queue_rows=8) as fe:
        for b in BUCKETS:  # compile every bucket before the trace
            q = rng.standard_normal((b, DIM)).astype(onp.float32)
            fe.submit(q, **kw).result(timeout=120)
        before = eng.stats.snapshot()["batches"]
        rec = T.Recorder(str(tmp_path_factory.mktemp(f"trace-{backend}")))
        fresh = rng.standard_normal((12, DIM)).astype(onp.float32)
        newer = rng.standard_normal((6, DIM)).astype(onp.float32)
        tickets = []
        # new rows, then the same rows, then half old and half new
        # rows; each a burst of 4-row requests into an 8-row queue, so the third submit of a
        # burst blocks until the driver flushes
        for rows in (fresh, fresh, onp.concatenate([fresh[:6], newer])):
            burst = [fe.submit(rows[i:i + 4], **kw)
                     for i in range(0, len(rows), 4)]
            for t in burst:
                t.result(timeout=120)
            tickets += burst
        fe.submit_add(
            rng.standard_normal((3, DIM)).astype(onp.float32), index="t"
        ).result(timeout=120)
        path = rec.stop()
        batches = eng.stats.snapshot()["batches"] - before
    return T.load(path), tickets, batches


def _ash(trace, name=None):
    return sorted(
        (e for e in trace.host if e.name.startswith("ash.")
         and (name is None or e.name == name)),
        key=lambda e: e.start,
    )


def _inside(e, outer):
    return outer.start <= e.start and e.end <= outer.end


def test_every_span_appears(served):
    trace, _, _ = served
    assert set(SPANS) <= {e.name for e in _ash(trace)}


def test_each_call_holds_its_steps_in_order(served):
    trace, _, _ = served
    steps = [e for e in _ash(trace) if e.name in CALL_STEPS]
    calls = _ash(trace, "ash.call")
    assert calls
    for call in calls:
        held = [e for e in steps if _inside(e, call)]
        assert [e.name for e in held] == list(CALL_STEPS)
        for a, b in zip(held, held[1:]):
            assert a.end <= b.start
    # the prep is one step, with no child spans of its own
    assert not [e for e in _ash(trace) if e.name.startswith("ash.prep.")]


def test_calls_match_the_engine_counters(served):
    trace, tickets, batches = served
    calls = _ash(trace, "ash.call")
    assert len(calls) == batches
    rows = {e.stats["call"]: e.stats["rows"] for e in calls}
    assert len(rows) == len(calls)  # one sequence number per call
    served_rows: dict = {}
    for t in tickets:
        cid = t.stats.call_id
        served_rows[cid] = served_rows.get(cid, 0) + t.n_rows
    assert served_rows == rows
    for e in calls:
        assert e.stats["bucket"] in BUCKETS
        assert e.stats["k"] == 8
    preps = _ash(trace, "ash.prep")
    assert len(preps) == len(calls)
    assert sum(e.stats["rows"] for e in preps) == sum(
        t.n_rows for t in tickets)
    assert {e.stats["rows"] for e in preps} == set(rows.values())


def test_span_budget(served):
    """One span per submit, at most 15 per fused call, none per driver
    tick (the driver ticks every 0.5 ms)."""
    trace, tickets, _ = served
    spans = _ash(trace)
    calls = _ash(trace, "ash.call")
    assert len(_ash(trace, "ash.submit")) == len(tickets)
    per_submit = {"ash.submit", "ash.submit.backpressure"}
    # the one mutation batch stands outside any call
    rest = [e for e in spans
            if e.name not in per_submit and e.name != "ash.mutations"]
    assert len(rest) <= 15 * len(calls)
    # the stretch holds more driver ticks than that budget, so a span
    # per tick would break it
    assert trace.window_s / 0.0005 > 15 * len(calls)


def test_spans_lie_inside_the_window(served):
    trace, _, _ = served
    a, b = trace.window
    spans = _ash(trace)
    assert spans
    assert all(a <= e.start and e.end <= b for e in spans)


BUILD_STEPS = ("ash.build.train", "ash.build.encode", "ash.build.stats")


@pytest.mark.parametrize("backend", ("flat", "ivf"))
def test_build_spans_nest_in_order(backend, tmp_path, monkeypatch):
    """A build is one ``ash.build`` span holding train, encode (its rows
    and row blocks) and stats, in that order."""
    A = importlib.import_module("repro.core.ash")
    monkeypatch.setattr(A, "ENCODE_WHOLE_BYTES", 0)
    monkeypatch.setattr(A, "ENCODE_CHUNK_ROWS", 512)
    X = embedding_dataset(jax.random.PRNGKey(9), 1200, DIM)
    rec = T.Recorder(str(tmp_path))
    AshIndex.build(jax.random.PRNGKey(10), X,
                   ASHConfig(b=2, d=16, n_landmarks=8), backend=backend,
                   keep_raw=True)
    trace = T.load(rec.stop())
    (build,) = _ash(trace, "ash.build")
    assert build.stats["backend"] == backend
    assert build.stats["rows"] == 1200
    steps = [e for e in _ash(trace) if e.name.startswith("ash.build.")]
    assert [e.name for e in steps] == list(BUILD_STEPS)
    assert all(_inside(e, build) for e in steps)
    for a, b in zip(steps, steps[1:]):
        assert a.end <= b.start
    assert steps[1].stats["rows"] == 1200
    assert steps[1].stats["chunks"] == 3


def _scopes(compiled_text: str) -> set:
    names = " ".join(re.findall(r'op_name="([^"]*)"', compiled_text))
    return set(re.findall(r"ash\.[a-z]+", names))


def test_search_programs_carry_scopes(indexes):
    """On the CPU the scan, gather and merge run the jnp oracle; the
    chip's programs (test_tpu_compile.py) carry ``ash.gather`` too."""
    q = embedding_dataset(jax.random.PRNGKey(8), 8, DIM)
    model = indexes["flat"].model
    prep_text = (
        jax.jit(S.prepare_queries).lower(model, q).compile().as_text()
    )
    assert "ash.prep" in _scopes(prep_text)
    prep = S.prepare_queries(model, q)
    flat_text = flat._search_prepped.lower(
        indexes["flat"]._state, prep, k=5, rerank=20
    ).compile().as_text()
    assert {"ash.scan", "ash.merge", "ash.rerank"} <= _scopes(flat_text)
    ivf_text = ivf._score_gathered.lower(
        indexes["ivf"]._state, prep, 5, 4, 20
    ).compile().as_text()
    assert {
        "ash.probe", "ash.candidates", "ash.scan", "ash.merge",
        "ash.rerank",
    } <= _scopes(ivf_text)


def test_scan_scope_unwinds_when_nested():
    """Every scoped call opens its own scope: a nested call leaves the
    caller's name stack as it found it (one shared scope object would
    restore the inner call's saved stack on the way out)."""
    from jax._src.source_info_util import current_name_stack

    ops = importlib.import_module("repro.kernels.ops")
    inner = ops._scan_scope(lambda: str(current_name_stack()))
    outer = ops._scan_scope(inner)
    before = str(current_name_stack())
    assert outer() == "ash.scan/ash.scan"
    assert str(current_name_stack()) == before
