"""Host-memory tiered IVF backend: bit-identity to the HBM-resident
backend, paging under byte budgets, persistence, and the serving
engine's tier gauges / paging cost bill.

The contract under test is exact: at equal probe sets the tiered
backend returns bitwise-identical (scores, ids) to ``backend="ivf"``
for EVERY option combination and EVERY hot-set budget — including a
zero-byte budget (every probe pages) and a covering one (everything
resident after the first touch).  The budget may change what moves
over PCIe, never what comes back.
"""
import jax
import numpy as np
import pytest

from _hypothesis_compat import given, st
from repro.core import ASHConfig
from repro.data.synthetic import embedding_dataset
from repro.index import AshIndex
from repro.index.tiered import ByteLRU, TieredIVFBackend
from repro.serving.engine import EngineConfig, QueryEngine

METRICS = ("dot", "l2", "cos")
# zero = page every probe; small = constant eviction; huge = covering
BUDGETS = (0, 1 << 14, 1 << 30)
CHUNK = 16
N0 = 400
POOL = 1200


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(17)
    kx, kq, kb = jax.random.split(key, 3)
    X = embedding_dataset(kx, POOL, 24)
    Qm = embedding_dataset(kq, 6, 24)
    cfg = ASHConfig(b=2, d=12, n_landmarks=8)
    model = AshIndex.build(kb, X[:N0], cfg, backend="flat").model
    return np.asarray(X), Qm, cfg, model, kb


def _build(setup, backend, metric, X_rows, **opts):
    X, Qm, cfg, model, kb = setup
    import jax.numpy as jnp

    return AshIndex.build(
        kb, jnp.asarray(X_rows), cfg, backend=backend, metric=metric,
        model=model, keep_raw=True, **opts,
    )


def _assert_same(a, b, msg=None):
    np.testing.assert_array_equal(
        np.asarray(a[0]), np.asarray(b[0]), err_msg=msg
    )
    np.testing.assert_array_equal(
        np.asarray(a[1]), np.asarray(b[1]), err_msg=msg
    )


SEARCH_KW = (
    {"nprobe": 3},
    {"nprobe": 3, "rerank": 20},
    {"nprobe": 4, "coarse": "int8", "shortlist": 64},
    {"nprobe": 8},  # nprobe == nlist: the dense full-scan route
    {"nprobe": 99},  # over-asking clamps identically
)


@pytest.mark.parametrize("metric", METRICS)
def test_search_matches_ivf_bitwise(setup, metric):
    """Every search option x every budget, batched and single-query."""
    X, Qm, cfg, model, kb = setup
    hbm = _build(setup, "ivf", metric, X[:N0])
    for hot in BUDGETS:
        tv = _build(setup, "tiered_ivf", metric, X[:N0],
                    hot_bytes=hot)
        for kw in SEARCH_KW:
            _assert_same(
                tv.search(Qm, k=10, **kw), hbm.search(Qm, k=10, **kw),
                msg=f"hot={hot} kw={kw}",
            )
            _assert_same(  # m=1 pads through its own route
                tv.search(Qm[:1], k=5, **kw),
                hbm.search(Qm[:1], k=5, **kw),
                msg=f"m=1 hot={hot} kw={kw}",
            )


def _blk(n_floats):
    return (np.zeros(n_floats, np.float32),)


def test_byte_lru_evicts_least_recent_under_budget():
    """The hot set's LRU: entries sized by their arrays' bytes, a hit
    refreshes recency, and a put past the budget evicts the least
    recently used entry first, with hits, misses and evictions
    counted."""
    lru = ByteLRU(3 * 64)
    for key in "abc":
        lru.put(key, _blk(16))  # 64 bytes each: exactly the budget
    assert list(lru.keys()) == ["a", "b", "c"] and lru.nbytes == 192
    assert lru.get("a") is not None  # "a" becomes the most recent
    assert lru.get("z") is None
    lru.put("d", _blk(16))  # evicts "b", now the least recent
    assert list(lru.keys()) == ["c", "a", "d"]
    assert lru.get("b") is None
    # replacing "c" with 128 bytes frees its 64 first, then evicts "a"
    lru.put("c", _blk(32))
    assert list(lru.keys()) == ["d", "c"] and lru.nbytes == 192
    assert lru.stats() == {
        "entries": 2, "nbytes": 192, "max_bytes": 192, "hits": 1,
        "misses": 2, "hit_rate": 1 / 3, "evictions": 2,
    }


def test_byte_lru_admits_then_evicts_an_oversize_entry():
    """An entry larger than the whole budget is admitted (``put`` never
    raises) and evicted at once, along with everything it displaced."""
    lru = ByteLRU(100)
    lru.put("small", _blk(4))
    lru.put("big", _blk(64))  # 256 bytes > the 100-byte budget
    assert len(lru) == 0 and lru.nbytes == 0
    assert lru.evictions == 2
    assert lru.get("big") is None and lru.misses == 1
    lru.put("small", _blk(4))  # the budget still admits what fits
    assert list(lru.keys()) == ["small"] and lru.nbytes == 16
    lru.clear()  # a cold hot set, counters kept
    assert len(lru) == 0 and lru.nbytes == 0 and lru.evictions == 2


def test_zero_budget_pages_every_probe(setup):
    """hot_bytes=0 serves correctly while caching nothing: paging,
    not OOM, and the gauges show it."""
    X, Qm, cfg, model, kb = setup
    tv = _build(setup, "tiered_ivf", "l2", X[:N0], hot_bytes=0)
    hbm = _build(setup, "ivf", "l2", X[:N0])
    for _ in range(3):
        _assert_same(tv.search(Qm, k=10, nprobe=3),
                     hbm.search(Qm, k=10, nprobe=3))
    ts = TieredIVFBackend.tier_stats(tv._state)
    assert ts["hits"] == 0
    assert ts["resident_lists"] == 0
    assert ts["resident_bytes"] == 0
    assert ts["misses"] == ts["evictions"] > 0
    assert ts["paged_rows"] > 0 and ts["transfers"] > 0


def test_covering_budget_stops_paging(setup):
    """A covering budget pages each list once, then serves from the
    device-resident hot set."""
    X, Qm, cfg, model, kb = setup
    tv = _build(setup, "tiered_ivf", "l2", X[:N0], hot_bytes=1 << 30)
    tv.search(Qm, k=10, nprobe=8)  # full scan touches every list
    before = TieredIVFBackend.tier_stats(tv._state)
    assert before["resident_lists"] == before["nlist"]
    for _ in range(3):
        tv.search(Qm, k=10, nprobe=3)
    after = TieredIVFBackend.tier_stats(tv._state)
    assert after["paged_rows"] == before["paged_rows"]
    assert after["transfers"] == before["transfers"]
    assert after["hits"] > before["hits"]
    assert after["evictions"] == 0


def test_search_probed_matches_ivf(setup):
    """Explicit probe sets (the budgeted-gather entry point) agree,
    including the m=1 pad-probe route."""
    X, Qm, cfg, model, kb = setup
    from repro.core import scoring as S

    hbm = _build(setup, "ivf", "dot", X[:N0])
    tv = _build(setup, "tiered_ivf", "dot", X[:N0], hot_bytes=1 << 14)
    prep = S.prepare_queries(hbm.model, Qm)
    probe = TieredIVFBackend.probe_sets(tv._state, prep, nprobe=3)
    np.testing.assert_array_equal(
        probe, hbm._backend.probe_sets(hbm._state, prep, nprobe=3)
    )
    _assert_same(
        TieredIVFBackend.search_probed(tv._state, prep, probe, k=10),
        hbm._backend.search_probed(hbm._state, prep, probe, k=10),
    )
    prep1 = S.prepare_queries(hbm.model, Qm[:1])
    _assert_same(
        TieredIVFBackend.search_probed(
            tv._state, prep1, probe[:1], k=5),
        hbm._backend.search_probed(hbm._state, prep1, probe[:1], k=5),
    )


def test_save_load_roundtrip(setup, tmp_path):
    X, Qm, cfg, model, kb = setup
    tv = _build(setup, "tiered_ivf", "cos", X[:N0], hot_bytes=1 << 14)
    tv.add(X[N0:N0 + CHUNK])
    tv.delete(np.arange(10))
    tv.save(tmp_path / "t")
    back = AshIndex.load(tmp_path / "t")
    assert back.backend == "tiered_ivf"
    assert back._state.hot_bytes == 1 << 14
    _assert_same(back.search(Qm, k=10, nprobe=3, rerank=15),
                 tv.search(Qm, k=10, nprobe=3, rerank=15))
    # the budget is a load-time override, not baked into the arrays
    resized = AshIndex.load(tmp_path / "t", hot_bytes=0)
    assert resized._state.hot_bytes == 0
    _assert_same(resized.search(Qm, k=10, nprobe=3, rerank=15),
                 tv.search(Qm, k=10, nprobe=3, rerank=15))


def test_list_sizes_match_ivf(setup):
    """The engine's cost-model input agrees with the HBM backend's,
    before and after tombstones."""
    X, Qm, cfg, model, kb = setup
    hbm = _build(setup, "ivf", "dot", X[:N0])
    tv = _build(setup, "tiered_ivf", "dot", X[:N0])
    from repro.index.api import IVFBackend

    np.testing.assert_array_equal(
        TieredIVFBackend.list_sizes(tv._state),
        IVFBackend.list_sizes(hbm._state),
    )
    hbm.delete(np.arange(30))
    tv.delete(np.arange(30))
    np.testing.assert_array_equal(
        TieredIVFBackend.list_sizes(tv._state),
        IVFBackend.list_sizes(hbm._state),
    )


# -- satellite: property test under interleaved mutation traffic ------


@given(
    metric=st.sampled_from(METRICS),
    hot_bytes=st.sampled_from(BUDGETS),
    nprobe=st.sampled_from((2, 8)),
    rerank=st.sampled_from((0, 30)),
    seed=st.integers(min_value=0, max_value=10**6),
)
def test_tiered_tracks_ivf_under_mutations(
    setup, metric, hot_bytes, nprobe, rerank, seed
):
    """Random interleaved add/delete/compact scripts applied to a
    tiered index and an HBM IVF twin stay bitwise in lockstep at every
    probe depth and budget — including compaction mid-script, which
    re-sorts rows between lists and drops the whole hot set."""
    X, Qm, cfg, model, kb = setup
    rng = np.random.RandomState(seed)
    tv = _build(setup, "tiered_ivf", metric, X[:N0],
                hot_bytes=hot_bytes)
    hbm = _build(setup, "ivf", metric, X[:N0])
    kw = {"nprobe": nprobe, "rerank": rerank}
    live_ids = list(range(N0))
    next_id = N0

    for _ in range(rng.randint(2, 5)):
        op = rng.rand()
        if op < 0.35:
            rows = X[rng.randint(0, POOL, CHUNK)]
            tv.add(rows)
            hbm.add(rows)
            live_ids.extend(range(next_id, next_id + CHUNK))
            next_id += CHUNK
        elif op < 0.65 and len(live_ids) > CHUNK + 8:
            victims = rng.choice(live_ids, size=CHUNK, replace=False)
            assert tv.delete(victims) == hbm.delete(victims) == CHUNK
            live_ids = [i for i in live_ids if i not in set(victims)]
        elif op < 0.8:
            tv.compact()
            hbm.compact()
        else:
            _assert_same(tv.search(Qm, k=10, **kw),
                         hbm.search(Qm, k=10, **kw))

    assert tv.n_live == hbm.n_live == len(live_ids)
    _assert_same(tv.search(Qm, k=10, **kw), hbm.search(Qm, k=10, **kw))
    _assert_same(tv.search(Qm, k=10, nprobe=8), hbm.search(Qm, k=10, nprobe=8))


# -- serving engine integration ---------------------------------------


def test_engine_serves_tiered_bitwise_with_gauges(setup):
    X, Qm, cfg, model, kb = setup
    tv = _build(setup, "tiered_ivf", "l2", X[:N0], hot_bytes=1 << 14)
    hbm = _build(setup, "ivf", "l2", X[:N0])
    s_d, i_d = hbm.search(Qm, k=10, nprobe=3)
    eng = QueryEngine(tv)
    tix = [eng.submit(np.asarray(Qm)[i:i + 1], k=10, nprobe=3)
           for i in range(Qm.shape[0])]
    eng.flush()
    for i, t in enumerate(tix):
        s, ids = t.result(timeout=60)
        np.testing.assert_array_equal(ids[0], np.asarray(i_d[i]))
        np.testing.assert_array_equal(s[0], np.asarray(s_d[i]))
    snap = eng.stats.snapshot()
    ts = snap["tier"]["default"]
    for key in ("hits", "misses", "hit_rate", "evictions",
                "resident_lists", "resident_bytes", "hot_bytes",
                "total_bytes", "paged_rows", "paged_bytes",
                "transfers"):
        assert key in ts
    assert ts["hits"] + ts["misses"] > 0
    assert ts["total_bytes"] > ts["hot_bytes"]


def test_engine_mutations_keep_tier_counters(setup):
    """Mutation re-hosts must not reset the lifetime tier gauges."""
    X, Qm, cfg, model, kb = setup
    tv = _build(setup, "tiered_ivf", "dot", X[:N0], hot_bytes=1 << 14)
    eng = QueryEngine(tv)
    t = eng.submit(np.asarray(Qm), k=10, nprobe=3)
    eng.flush()
    t.result(timeout=60)
    before = eng.stats.snapshot()["tier"]["default"]
    tk = eng.submit_add(X[:CHUNK])
    eng.flush()
    tk.result(timeout=60)
    after = eng.stats.snapshot()["tier"]["default"]
    assert after["misses"] >= before["misses"]
    assert after["paged_rows"] >= before["paged_rows"]


def test_engine_bills_cold_lists_at_page_cost(setup):
    """_billed_list_sizes surcharges non-resident lists so the row
    budget and adaptive nprobe see paging cost."""
    X, Qm, cfg, model, kb = setup
    tv = _build(setup, "tiered_ivf", "dot", X[:N0], hot_bytes=1 << 30)
    eng = QueryEngine(tv, row_budget=100_000, page_row_cost=2.0)
    live = eng._live_list_sizes("default", eng._indexes["default"])
    # nothing resident yet: everything bills at the surcharge
    billed = eng._billed_list_sizes("default", eng._indexes["default"])
    np.testing.assert_array_equal(
        billed, np.ceil(live * 2.0).astype(np.int64)
    )
    tv.search(Qm, k=10, nprobe=8)  # covering budget: all lists warm
    billed = eng._billed_list_sizes("default", eng._indexes["default"])
    np.testing.assert_array_equal(billed, live)
    # non-tiered indexes never pay the surcharge
    hbm = _build(setup, "ivf", "dot", X[:N0])
    eng.register("h", hbm)
    np.testing.assert_array_equal(
        eng._billed_list_sizes("h", eng._indexes["h"]),
        eng._live_list_sizes("h", eng._indexes["h"]),
    )


def test_engine_config_rejects_bad_page_cost():
    with pytest.raises(ValueError, match="page_row_cost"):
        EngineConfig(page_row_cost=0.5)
