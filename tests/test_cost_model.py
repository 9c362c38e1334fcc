"""IVF candidate-row cost model + load-adaptive probing.

Core properties:

* **Pressure-off identity** — with ``row_budget`` armed (splits and
  budget-triggered flushes firing), every engine request is
  bit-identical (scores AND ids) to the direct full-nprobe search of
  the same rows.  The cost model is a batching POLICY: it may change
  how groups chunk into fused calls, never what a query returns.
* **Degradation is exact at the rung** — under pressure 1.0 with
  ``nprobe_min`` armed, results equal the direct search at the ladder
  floor exactly, and top-k overlap vs the full-nprobe answer stays
  above the configured recall floor.

Plus unit coverage of the accounting itself: union-dedup billing,
budget-boundary chunk planning, the halving ladder, the pressure
gauge, config validation, and the "budget" flush reason.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, st
from repro.core import ASHConfig
from repro.data.synthetic import embedding_dataset
from repro.index import AshIndex
from repro.index.api import IVFBackend
from repro.serving.engine import EngineConfig, QueryEngine, _Request

N = 2500
D = 32
NLIST = 8
RECALL_FLOOR = 0.3  # top-10 overlap floor under forced degradation


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(99)
    kx, kq, kb = jax.random.split(key, 3)
    X = embedding_dataset(kx, N, D)
    Qm = np.asarray(embedding_dataset(kq, 24, D))
    cfg = ASHConfig(b=2, d=D // 2, n_landmarks=NLIST)
    index = AshIndex.build(kb, X, cfg, backend="ivf")
    return index, Qm


def _request_mix(Qm, seed):
    rng = np.random.RandomState(seed)
    out, i = [], 0
    while i < Qm.shape[0]:
        m = min(int(rng.choice([1, 1, 2, 4])), Qm.shape[0] - i)
        out.append((i, m))
        i += m
    return out


# ---------------------------------------------------------------------------
# property: the cost model never changes results
# ---------------------------------------------------------------------------


@given(nprobe=st.sampled_from([2, 3, 4]), seed=st.integers(0, 7))
def test_pressure_off_identity(setup, nprobe, seed):
    """Budget splits + budget-triggered flushes engaged, pressure off:
    every request matches the direct search bit-for-bit."""
    index, Qm = setup
    # a budget well under the corpus forces unions over the cap, so
    # flushes split into sub-batches and submits trip "budget" flushes
    engine = QueryEngine(
        index, batch_buckets=(4, 8), max_wait_s=60.0,
        row_budget=max(1, N // 4),
    )
    tickets = [
        (i, m, engine.submit(Qm[i:i + m], k=10, nprobe=nprobe))
        for i, m in _request_mix(Qm, seed)
    ]
    engine.flush()
    for i, m, t in tickets:
        s_e, i_e = t.result()
        s_d, i_d = index.search(Qm[i:i + m], k=10, nprobe=nprobe)
        np.testing.assert_array_equal(s_e, np.asarray(s_d))
        np.testing.assert_array_equal(i_e, np.asarray(i_d))
        assert t.stats.effective_nprobe == nprobe  # never degraded
        assert t.stats.scanned_rows > 0  # but always billed


def test_degraded_flush_is_exact_at_the_rung(setup):
    """Pressure 1.0 lands on the nprobe_min rung; the degraded fused
    call must equal the DIRECT search at that rung exactly — adaptive
    probing trades recall via nprobe only, never via approximation —
    and keep top-k overlap vs full fidelity above the floor."""
    index, Qm = setup
    engine = QueryEngine(
        index, batch_buckets=(4, 8), max_wait_s=60.0, nprobe_min=2,
    )
    # 4 single-row requests: under the 8-row bucket, so nothing
    # flushes on size before the forced-pressure flush below
    tickets = [
        engine.submit(Qm[i:i + 1], k=10, nprobe=4) for i in range(4)
    ]
    engine._flush_all("manual", pressure=1.0)

    overlaps = []
    for j, t in enumerate(tickets):
        s_e, i_e = t.result()
        q = Qm[j:j + 1]
        s_d, i_d = index.search(q, k=10, nprobe=2)
        np.testing.assert_array_equal(s_e, np.asarray(s_d))
        np.testing.assert_array_equal(i_e, np.asarray(i_d))
        assert t.stats.effective_nprobe == 2
        _, i_full = index.search(q, k=10, nprobe=4)
        i_full = np.asarray(i_full)
        overlaps.append(
            len(set(i_e[0]) & set(i_full[0])) / i_full.shape[1]
        )
    assert np.mean(overlaps) >= RECALL_FLOOR
    snap = engine.stats.snapshot()
    assert snap["ivf_cost"]["degraded"] >= 1
    assert snap["ivf_cost"]["effective_nprobe"].get("2", 0) > 0


def test_pressure_below_ladder_threshold_never_degrades(setup):
    """An idle queue always serves full fidelity: small nonzero
    pressure maps to the top rung."""
    index, Qm = setup
    engine = QueryEngine(
        index, batch_buckets=(4, 8), max_wait_s=60.0, nprobe_min=2,
    )
    t = engine.submit(Qm[:4], k=10, nprobe=4)
    engine._flush_all("manual", pressure=0.2)  # < 1/len(ladder)=1/2
    s_d, i_d = index.search(Qm[:4], k=10, nprobe=4)
    s_e, i_e = t.result()
    np.testing.assert_array_equal(s_e, np.asarray(s_d))
    np.testing.assert_array_equal(i_e, np.asarray(i_d))
    assert t.stats.effective_nprobe == 4


# ---------------------------------------------------------------------------
# unit: accounting
# ---------------------------------------------------------------------------


def _engine(setup, **kw):
    index, _ = setup
    return QueryEngine(index, batch_buckets=(4, 8), max_wait_s=60.0,
                       **kw)


def test_union_bill_dedups_shared_lists(setup):
    engine = _engine(setup, row_budget=10)
    sizes = np.array([5, 7, 11, 2], dtype=np.int64)
    a = np.array([[0, 1]], dtype=np.int32)
    b = np.array([[1, 2]], dtype=np.int32)
    assert engine._union_bill(sizes, [a]) == 12
    # list 1 shared by both queries is billed once: 5+7+11, not +7
    assert engine._union_bill(sizes, [a, b]) == 23
    assert engine._union_bill(sizes, [a, a, a]) == 12
    assert engine._union_bill(sizes, []) == 0
    # pad sentinels and out-of-range ids cost nothing
    junk = np.array([[-1, 99]], dtype=np.int32)
    assert engine._union_bill(sizes, [junk]) == 0


def _req(q_rows, probe_lists, dim=D):
    q = np.zeros((q_rows, dim), dtype=np.float32)
    probe = np.asarray(probe_lists, dtype=np.int32)
    return _Request(q, 10, None, time.perf_counter(), None, probe)


def test_plan_chunks_splits_on_budget_not_on_sharing(setup):
    """Disjoint probe sets overflow the budget and split; queries
    sharing the same lists bill once and batch together.  (4-row
    requests: the smallest bucket is 4, so each request is splittable
    on its own — see the bucket-floor test for sub-bucket chunks.)"""
    engine = _engine(setup, row_budget=10)
    sizes = np.array([6, 6, 6, 6, 6, 6, 6, 6], dtype=np.int64)
    engine._live_list_sizes = lambda name, idx: sizes
    group = ("default", 2, 0, None, ())

    # shared lists: 3 requests x lists {0,1} bill 12 > 10? no — the
    # union stays {0,1} = 12... use budget 12 so sharing fits exactly
    engine2 = _engine(setup, row_budget=12)
    engine2._live_list_sizes = lambda name, idx: sizes
    shared = [_req(1, [[0, 1]]) for _ in range(3)]
    eff, chunks, bills = engine2._plan_chunks(group, shared, None)
    assert eff == 2
    assert len(chunks) == 1 and len(chunks[0]) == 3
    assert bills == [12]

    # disjoint lists: each request adds 12 fresh rows -> one per chunk
    disjoint = [_req(4, [[0, 1]]), _req(4, [[2, 3]]), _req(4, [[4, 5]])]
    eff, chunks, bills = engine2._plan_chunks(group, disjoint, None)
    assert len(chunks) == 3
    assert all(len(c) == 1 for c in chunks)
    assert bills == [12, 12, 12]
    assert engine2.stats.ivf_splits == 2  # two budget-induced splits

    # a single request alone over budget (12 > 10) still rides,
    # in its own chunk — there is nothing to split away from
    alone = [_req(4, [[0, 1]])]
    eff, chunks, bills = engine._plan_chunks(group, alone, None)
    assert len(chunks) == 1 and bills == [12]


def test_plan_chunks_bucket_floor(setup):
    """A budget split never cuts a chunk below the smallest bucket:
    the chunk would pad back up to the bucket anyway, so the split
    would add a dispatch without shrinking any gather.  Disjoint
    1-row requests therefore accrete to the 4-bucket before the
    budget bites, however far over it their bill runs."""
    engine = _engine(setup, row_budget=12)
    sizes = np.full(12, 6, dtype=np.int64)
    engine._live_list_sizes = lambda name, idx: sizes
    group = ("default", 2, 0, None, ())
    # 6 disjoint 1-row requests, 12 fresh rows each (bill 72 total):
    # chunks of 4 (the smallest bucket), never 1-row slivers
    reqs = [_req(1, [[2 * j, 2 * j + 1]]) for j in range(6)]
    eff, chunks, bills = engine._plan_chunks(group, reqs, None)
    assert [len(c) for c in chunks] == [4, 2]
    assert bills == [48, 24]

    # the budget-triggered early flush respects the same floor: a
    # group below the smallest bucket is never "budget"-flushed
    engine2 = _engine(setup, row_budget=1)
    name = engine2.index_names[0]
    g = (name, 2, 0, None, ())
    engine2.driven = True  # queue without flushing
    _, Qm = setup
    engine2.submit(Qm[:2], k=10, nprobe=2)
    assert not engine2._group_over_budget(g)  # 2 rows < bucket 4
    engine2.submit(Qm[2:4], k=10, nprobe=2)
    assert engine2._group_over_budget(g)  # 4 rows, bill >> 1
    engine2.driven = False
    engine2.flush()


def test_plan_chunks_degrades_on_prefix(setup):
    """Under pressure the bill is computed on the probe column prefix
    — the degraded rung reads fewer lists, so the same requests fit
    fewer chunks."""
    engine = _engine(setup, row_budget=12, nprobe_min=1)
    sizes = np.full(8, 6, dtype=np.int64)
    engine._live_list_sizes = lambda name, idx: sizes
    group = ("default", 2, 0, None, ())
    reqs = [_req(1, [[0, 1]]), _req(1, [[2, 3]])]
    eff, chunks, bills = engine._plan_chunks(group, reqs, 1.0)
    assert eff == 1  # ladder floor
    # prefix billing: each request now costs 6; union fits one chunk
    assert len(chunks) == 1
    assert bills == [12]


def test_effective_nprobe_ladder(setup):
    engine = _engine(setup, nprobe_min=2)
    # ladder from 8: [8, 4, 2]
    assert engine._effective_nprobe(8, 0.0) == 8
    assert engine._effective_nprobe(8, 0.2) == 8  # < 1/3
    assert engine._effective_nprobe(8, 0.5) == 4
    assert engine._effective_nprobe(8, 1.0) == 2
    assert engine._effective_nprobe(2, 1.0) == 2  # already at floor
    assert engine._effective_nprobe(1, 1.0) == 1  # below floor: as-is
    off = _engine(setup)  # nprobe_min unset: never degrade
    assert off._effective_nprobe(8, 1.0) == 8


@pytest.mark.parametrize("nprobe", (1, 4))
@pytest.mark.parametrize("m", (1, 6))
def test_host_probe_matches_device_probe(setup, m, nprobe):
    """The engine's one host probe path gives, row by row and
    best-first, the lists ``IVFBackend.probe_sets`` computes on the
    device, and its nprobe columns are the prefix of the full order
    (what a degraded nprobe reads)."""
    index, Qm = setup
    engine = _engine(setup, row_budget=N)
    name = engine.index_names[0]
    q = np.ascontiguousarray(Qm[:m])

    host = engine._host_probe(name, index, q, nprobe)
    assert host.shape == (m, nprobe) and host.dtype == np.int32
    device = IVFBackend.probe_sets(
        index._state, index.prepare(jnp.asarray(q)), nprobe
    )
    np.testing.assert_array_equal(host, device)
    full = engine._host_probe(name, index, q, NLIST)
    np.testing.assert_array_equal(full[:, :nprobe], host)
    assert all(sorted(row) == list(range(NLIST)) for row in full)


def test_queue_pressure_gauge(setup):
    index, Qm = setup
    engine = QueryEngine(
        index, batch_buckets=(4, 8), max_wait_s=60.0,
        max_pending=16, pressure_age_s=1e9,
    )
    assert engine.queue_pressure() == 0.0
    engine.driven = True  # queue without flushing
    engine.submit(Qm[:8], k=10, nprobe=2)
    assert engine.queue_pressure() == pytest.approx(0.5)  # 8/16 rows
    # age term: shrink the horizon so the queued ticket is instantly old
    object.__setattr__(engine.config, "pressure_age_s", 1e-9)
    assert engine.queue_pressure() == 1.0
    snap = engine.stats.snapshot()
    assert snap["queue_pressure"] == 1.0
    engine.driven = False
    engine.flush()


def test_config_validation():
    with pytest.raises(ValueError, match="row_budget"):
        EngineConfig(row_budget=0)
    with pytest.raises(ValueError, match="nprobe_min"):
        EngineConfig(nprobe_min=0)
    with pytest.raises(ValueError, match="pressure_age_s"):
        EngineConfig(pressure_age_s=0.0)
    cfg = EngineConfig(row_budget=1, nprobe_min=1, pressure_age_s=0.1)
    assert cfg.row_budget == 1


def test_budget_flush_reason_and_telemetry(setup):
    """A group whose bill exceeds row_budget flushes at submit time
    with reason "budget" instead of waiting for the bucket; tickets
    carry the billed rows and effective nprobe."""
    index, Qm = setup
    engine = QueryEngine(
        index, batch_buckets=(2, 32), max_wait_s=60.0, row_budget=1,
    )
    t0 = engine.submit(Qm[:1], k=10, nprobe=2)
    t1 = engine.submit(Qm[1:2], k=10, nprobe=2)
    # row_budget=1 is always exceeded: the first submit can't trigger
    # (one row is below the smallest-bucket floor), the second fills
    # the 2-bucket and flushes the group with reason "budget"
    t0.result(timeout=30.0)
    t1.result(timeout=30.0)
    engine.flush()
    assert engine.stats.flushes["budget"] >= 1
    assert t0.stats.flush_reason in ("budget", "manual")
    assert t0.stats.scanned_rows > 0
    assert t0.stats.effective_nprobe == 2
    snap = engine.stats.snapshot()
    assert snap["ivf_cost"]["scanned_rows"] > 0
    assert snap["ivf_cost"]["rows_per_query"] > 0


def test_uncosted_paths_unaffected(setup):
    """Knobs off, or a flat backend, or full-scan nprobe: no probes
    are computed and the ivf_cost counters stay zero."""
    index, Qm = setup
    engine = QueryEngine(index, batch_buckets=(4, 8), max_wait_s=60.0)
    t = engine.submit(Qm[:2], k=10, nprobe=2)
    engine.flush()
    t.result()
    assert t.stats.scanned_rows == 0
    assert t.stats.effective_nprobe == 0
    snap = engine.stats.snapshot()
    assert snap["ivf_cost"]["scanned_rows"] == 0
    assert snap["ivf_cost"]["effective_nprobe"] == {}

    # nprobe >= nlist runs the dense path: cost model stays out even
    # with the budget armed
    costed = QueryEngine(
        index, batch_buckets=(4, 8), max_wait_s=60.0, row_budget=5,
    )
    t = costed.submit(Qm[:2], k=10, nprobe=NLIST)
    costed.flush()
    t.result()
    assert t.stats.scanned_rows == 0


def test_deadline_counted_per_ticket_at_chunk_resolve(setup):
    """A budget split resolves each chunk at its own time: a ticket
    whose chunk lands before its deadline is never marked missed just
    because a LATER chunk of the same flush ran long, a ticket whose
    chunk resolves late is, and each ticket is counted exactly once
    in ``stats.deadline_missed`` — never once per chunk."""
    index, Qm = setup
    engine = _engine(setup, row_budget=12)
    sizes = np.array([6] * NLIST, dtype=np.int64)
    engine._live_list_sizes = lambda name, idx: sizes

    # warm the (bucket 4, k 10, nprobe 2) trace so the first chunk's
    # resolve time is millisecond-scale, far inside its deadline
    t = engine.submit(Qm[:4], k=10, nprobe=2)
    engine.flush()
    t.result(timeout=60.0)

    # fabricate disjoint probe pairs (12 fresh rows per request, the
    # 12-row budget splits one request per chunk); probes only steer
    # billing/planning — scoring reprobes in-graph from the queries
    pairs = iter([[0, 1], [2, 3], [4, 5]])
    engine._host_probe = lambda name, idx, q, nprobe: np.tile(
        np.asarray(next(pairs), dtype=np.int32), (q.shape[0], 1)
    )
    # chunks run FIFO; stall every chunk after the first so the same
    # 0.6 s deadline lands differently chunk by chunk
    real_run = engine._run_batch
    ran = []

    def staggered(group, chunk, reason, **kw):
        if ran:
            time.sleep(1.0)
        ran.append(len(chunk))
        return real_run(group, chunk, reason, **kw)

    engine._run_batch = staggered

    engine.driven = True  # queue without flushing
    t0 = engine.submit(Qm[:4], k=10, nprobe=2, deadline_s=0.6)
    t1 = engine.submit(Qm[4:8], k=10, nprobe=2, deadline_s=0.6)
    t2 = engine.submit(Qm[8:12], k=10, nprobe=2, deadline_s=0.0)
    engine.driven = False
    before = engine.stats.deadline_missed
    engine.flush()
    for tk in (t0, t1, t2):
        tk.result(timeout=60.0)

    assert ran == [1, 1, 1]  # three budget chunks, one request each
    # chunk 0 resolved within t0's deadline; chunk 1 resolved past
    # the SAME deadline value; t2's deadline was already due
    assert not t0.stats.deadline_missed
    assert t1.stats.deadline_missed
    assert t2.stats.deadline_missed
    assert engine.stats.deadline_missed - before == 2
