"""Micro-batching QueryEngine: bit-identical parity vs direct search,
bucket/flush semantics, one query prep per fused call, trace reuse."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from repro.core import ASHConfig
from repro.data.synthetic import embedding_dataset
from repro.index import AshIndex
from repro.index import flat as F
from repro.serving.engine import EngineConfig, QueryEngine

BACKENDS = ("flat", "ivf", "sharded")


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(99)
    kx, kq, kb = jax.random.split(key, 3)
    X = embedding_dataset(kx, 2500, 32)
    Qm = embedding_dataset(kq, 48, 32)
    cfg = ASHConfig(b=2, d=16, n_landmarks=8)
    model = AshIndex.build(kb, X, cfg, backend="flat").model
    indexes = {
        "flat": AshIndex.build(kb, X, cfg, backend="flat", model=model,
                               keep_raw=True),
        "ivf": AshIndex.build(kb, X, cfg, backend="ivf", model=model,
                              keep_raw=True),
        "sharded": AshIndex.build(kb, X, cfg, backend="sharded",
                                  model=model),
    }
    return X, Qm, indexes


def _engine(indexes, **kw):
    kw.setdefault("batch_buckets", (4, 16))
    kw.setdefault("k_buckets", (8,))
    kw.setdefault("max_wait_s", 60.0)  # flush explicitly in tests
    return QueryEngine(indexes, **kw)


@pytest.mark.parametrize("backend", BACKENDS)
def test_parity_bit_identical(setup, backend):
    """Batched+padded engine results == per-request direct search,
    bit-for-bit (scores AND ids), for new rows and repeated rows."""
    X, Qm, indexes = setup
    idx = indexes[backend]
    kw = {"nprobe": 4} if backend == "ivf" else {}
    eng = _engine({backend: idx})
    for round_ in range(2):  # round 2 repeats round 1's rows
        sizes = [1, 3, 2, 5, 1]
        offs = onp.cumsum([0] + sizes)
        tickets = [
            eng.submit(Qm[offs[i]:offs[i + 1]], k=7, index=backend, **kw)
            for i in range(len(sizes))
        ]
        eng.flush()
        for i, t in enumerate(tickets):
            s, ids = t.result()
            ds, di = idx.search(Qm[offs[i]:offs[i + 1]], k=7, **kw)
            assert jnp.array_equal(jnp.asarray(s), ds), (backend, round_, i)
            assert jnp.array_equal(jnp.asarray(ids), di), (backend, round_, i)


@pytest.mark.parametrize("backend", ("flat", "ivf"))
@pytest.mark.parametrize(
    "k,rerank,k_buckets",
    [
        (5, 30, (8,)),  # shortlist (rerank) beyond the k bucket
        (5, 6, (8,)),  # shortlist below the k bucket
        (20, 50, (10, 100)),  # k pads to 100 > rerank: the bucketized
        # k must not widen the shortlist past the direct path's
        # max(rerank, k) (regression: this returned different ids)
    ],
)
def test_parity_with_rerank(setup, backend, k, rerank, k_buckets):
    X, Qm, indexes = setup
    idx = indexes[backend]
    kw = {"rerank": rerank}
    if backend == "ivf":
        kw["nprobe"] = 4
    eng = _engine({backend: idx}, k_buckets=k_buckets)
    t1 = eng.submit(Qm[:1], k=k, index=backend, **kw)
    t2 = eng.submit(Qm[1:4], k=k, index=backend, **kw)
    eng.flush()
    assert eng.stats.batches == 1  # same shortlist: one fused call
    for t, sl in ((t1, slice(0, 1)), (t2, slice(1, 4))):
        s, ids = t.result()
        ds, di = idx.search(Qm[sl], k=k, **kw)
        assert jnp.array_equal(jnp.asarray(s), ds)
        assert jnp.array_equal(jnp.asarray(ids), di)


def test_rerank_mixed_k_groups_by_shortlist(setup):
    """rerank < k requests need a shortlist of exactly their k, so each
    distinct max(rerank, k) forms its own group/fused call — and every
    request still matches per-request search bit-for-bit."""
    X, Qm, indexes = setup
    idx = indexes["flat"]
    eng = _engine({"flat": idx})
    t1 = eng.submit(Qm[:2], k=4, index="flat", rerank=2)
    t2 = eng.submit(Qm[2:5], k=7, index="flat", rerank=2)
    eng.flush()
    assert eng.stats.batches == 2  # shortlists 4 and 7 cannot fuse
    for t, sl, k in ((t1, slice(0, 2), 4), (t2, slice(2, 5), 7)):
        s, ids = t.result()
        ds, di = idx.search(Qm[sl], k=k, rerank=2)
        assert jnp.array_equal(jnp.asarray(s), ds)
        assert jnp.array_equal(jnp.asarray(ids), di)


def test_mixed_k_share_one_bucket(setup):
    """Different requested k ride one bucket (k padded to a k-bucket,
    per-request prefix sliced) — one fused call, exact results."""
    X, Qm, indexes = setup
    eng = _engine({"flat": indexes["flat"]}, batch_buckets=(16,))
    t1 = eng.submit(Qm[:2], k=3, index="flat")
    t2 = eng.submit(Qm[2:5], k=8, index="flat")
    eng.flush()
    assert eng.stats.batches == 1
    assert t1.result()[0].shape == (2, 3)
    assert jnp.array_equal(
        jnp.asarray(t1.result()[1]), indexes["flat"].search(Qm[:2], k=3)[1]
    )
    assert jnp.array_equal(
        jnp.asarray(t2.result()[1]),
        indexes["flat"].search(Qm[2:5], k=8)[1],
    )


def test_k_larger_than_n():
    """k > index size clamps the fused call and pads results with the
    missing-candidate sentinel (score -inf, id -1)."""
    X = embedding_dataset(jax.random.PRNGKey(5), 30, 16)
    idx = AshIndex.build(
        jax.random.PRNGKey(0), X, ASHConfig(b=2, d=8, n_landmarks=2)
    )
    eng = _engine({"tiny": idx}, batch_buckets=(4,), k_buckets=(8,))
    s, ids = eng.search(X[:2], k=50, index="tiny")
    assert s.shape == (2, 50) and ids.shape == (2, 50)
    assert (ids[:, 30:] == -1).all()
    assert onp.isneginf(s[:, 30:]).all()
    ds, di = idx.search(X[:2], k=30)
    assert jnp.array_equal(jnp.asarray(ids[:, :30]), di)
    assert jnp.array_equal(jnp.asarray(s[:, :30]), ds)


def test_empty_flush_and_poll(setup):
    X, Qm, indexes = setup
    eng = _engine(indexes)
    assert eng.flush() == 0
    assert eng.poll() == 0
    assert eng.pending_requests == 0


def test_flush_on_size(setup):
    X, Qm, indexes = setup
    eng = _engine({"flat": indexes["flat"]}, batch_buckets=(4,))
    tickets = [eng.submit(Qm[i:i + 1], k=5, index="flat")
               for i in range(4)]
    # 4 rows == largest bucket: flushed inside the last submit
    assert all(t.done for t in tickets)
    assert tickets[0].stats.flush_reason == "size"
    assert tickets[0].stats.bucket_rows == 4


def test_flush_on_timeout(setup):
    X, Qm, indexes = setup
    eng = _engine({"flat": indexes["flat"]}, batch_buckets=(64,),
                  max_wait_s=0.0)
    t = eng.submit(Qm[:1], k=5, index="flat")
    eng.poll()
    assert t.done
    assert t.stats.flush_reason == "timeout"


def test_bounded_queue_applies_backpressure(setup):
    """Exceeding max_pending rows forces a serve — requests are never
    dropped and the queue never grows past the bound."""
    X, Qm, indexes = setup
    eng = _engine({"flat": indexes["flat"]}, batch_buckets=(64,),
                  max_pending=8)
    t1 = eng.submit(Qm[:4], k=5, index="flat")
    t2 = eng.submit(Qm[4:8], k=5, index="flat")
    assert not t1.done  # still queued: bound not exceeded yet
    t3 = eng.submit(Qm[8:12], k=5, index="flat")
    assert t1.done and t2.done  # backpressure flush served the backlog
    # queue-pressure flushes are their own telemetry bucket, distinct
    # from explicit flush() calls
    assert t1.stats.flush_reason == "pressure"
    assert eng.stats.flushes["pressure"] == 1
    eng.flush()
    assert t3.done
    assert t3.stats.flush_reason == "manual"


@pytest.mark.parametrize("n", (3, 20, 100))
@pytest.mark.parametrize("backend", ("flat", "ivf"))
def test_one_prepare_per_call(setup, monkeypatch, backend, n):
    """Each fused call prepares its padded bucket once, as one (bucket,
    D) block, and serves results bit-identical to direct search."""
    X, Qm, indexes = setup
    idx = indexes[backend]
    kw = {"nprobe": 4} if backend == "ivf" else {}
    buckets = (8, 32, 128)
    eng = _engine({backend: idx}, batch_buckets=buckets)
    shapes = []
    real_prepare = idx.prepare

    def prepare(queries):
        shapes.append(tuple(queries.shape))
        return real_prepare(queries)

    monkeypatch.setattr(idx, "prepare", prepare)
    Q = onp.random.default_rng(n).standard_normal(
        (n, X.shape[1])).astype(onp.float32)
    offs = list(range(0, n, 7)) + [n]
    tickets = [eng.submit(Q[a:b], k=7, index=backend, **kw)
               for a, b in zip(offs, offs[1:])]
    eng.flush()
    bucket = next(b for b in buckets if n <= b)
    assert eng.stats.batches == 1
    assert shapes == [(bucket, X.shape[1])]
    for (a, b), t in zip(zip(offs, offs[1:]), tickets):
        s, ids = t.result()
        ds, di = idx.search(Q[a:b], k=7, **kw)
        assert jnp.array_equal(jnp.asarray(s), ds), (backend, n, a)
        assert jnp.array_equal(jnp.asarray(ids), di), (backend, n, a)


def test_snapshot_counts_prepared_rows(setup):
    """``prep_misses`` counts the real rows prepared (pad rows not
    included) and ``prep_hits`` stays 0: no row is looked up in a
    cache, and the benchmark harness reads both keys."""
    X, Qm, indexes = setup
    eng = _engine({"flat": indexes["flat"]}, batch_buckets=(4,))
    eng.search(Qm[:2], k=5, index="flat")
    eng.search(Qm[:2], k=5, index="flat")  # the same rows again
    eng.search(Qm[2:5], k=5, index="flat")
    snap = eng.stats.snapshot()
    assert snap["rows"] == 7 and snap["batches"] == 3
    assert snap["prep_misses"] == 7
    assert snap["prep_hits"] == 0


def test_ivf_nprobe_clamps_before_grouping(setup):
    """nprobe values at/above nlist route identically, so they must
    share one group and one trace (nlist == 8 in this setup)."""
    X, Qm, indexes = setup
    idx = indexes["ivf"]
    eng = _engine({"ivf": idx}, batch_buckets=(16,))
    t1 = eng.submit(Qm[:2], k=5, index="ivf", nprobe=8)
    t2 = eng.submit(Qm[2:4], k=5, index="ivf", nprobe=1000)
    t3 = eng.submit(Qm[4:6], k=5, index="ivf")  # default, also clamped
    eng.flush()
    assert eng.stats.batches == 1
    assert len(eng.stats.compiled_buckets) == 1
    s, ids = t2.result()
    ds, di = idx.search(Qm[2:4], k=5, nprobe=1000)
    assert jnp.array_equal(jnp.asarray(s), ds)
    assert jnp.array_equal(jnp.asarray(ids), di)
    assert t1.done and t3.done


def test_submit_rejects_mismatched_query_dim(setup):
    """A query whose width differs from the index dim is rejected at
    submit — inside a group it would fail the whole fused call and take
    unrelated requests down with it."""
    X, Qm, indexes = setup
    eng = _engine({"flat": indexes["flat"]})
    with pytest.raises(ValueError, match="dim"):
        eng.submit(onp.zeros((1, 16), onp.float32), k=5, index="flat")
    assert eng.pending_requests == 0


def test_submit_survives_failing_flush(setup):
    """A flush triggered inside submit() must not swallow the caller's
    Ticket: the error is delivered by the failing request's result(),
    and unrelated requests keep working."""
    X, Qm, indexes = setup
    eng = _engine({"flat": indexes["flat"]}, max_wait_s=0.0)
    bad = eng.submit(Qm[:1], k=5, index="flat", bogus=True)
    assert bad.done  # timeout-flushed (and failed) inside submit
    good = eng.submit(Qm[:2], k=5, index="flat")
    eng.poll()
    s, ids = good.result()
    assert jnp.array_equal(
        jnp.asarray(ids), indexes["flat"].search(Qm[:2], k=5)[1]
    )
    with pytest.raises(RuntimeError, match="fused scoring call"):
        bad.result()


def test_trace_reuse_across_requests(setup):
    """Many requests of novel shapes ride ONE jit trace per bucket: the
    underlying compiled-call cache grows by at most the bucket count,
    not per request."""
    X, Qm, indexes = setup
    eng = _engine({"flat": indexes["flat"]}, batch_buckets=(8,))
    before = F._search_prepped._cache_size()
    for i in range(12):  # request shapes 1..4 rows, all pad to bucket 8
        eng.submit(Qm[i:i + 1 + (i % 4)], k=5, index="flat")
    eng.flush()
    after = F._search_prepped._cache_size()
    assert eng.stats.batches >= 3  # several fused calls actually ran
    assert after - before <= 1  # ... through at most ONE new trace
    assert len(eng.stats.compiled_buckets) == 1


def test_multi_index_routing(setup):
    """One engine fronts several tenant indexes; requests route by
    name and never cross-contaminate."""
    X, Qm, indexes = setup
    eng = _engine(indexes)
    tf = eng.submit(Qm[:2], k=5, index="flat")
    ti = eng.submit(Qm[:2], k=5, index="ivf", nprobe=4)
    ts = eng.submit(Qm[:2], k=5, index="sharded")
    eng.flush()
    assert jnp.array_equal(
        jnp.asarray(tf.result()[1]), indexes["flat"].search(Qm[:2], k=5)[1]
    )
    assert jnp.array_equal(
        jnp.asarray(ti.result()[1]),
        indexes["ivf"].search(Qm[:2], k=5, nprobe=4)[1],
    )
    assert jnp.array_equal(
        jnp.asarray(ts.result()[1]),
        indexes["sharded"].search(Qm[:2], k=5)[1],
    )
    with pytest.raises(KeyError, match="unknown index"):
        eng.submit(Qm[:1], k=5, index="nope")


def test_request_stats_populated(setup):
    X, Qm, indexes = setup
    eng = _engine({"flat": indexes["flat"]})
    t = eng.submit(Qm[:3], k=5, index="flat")
    eng.flush()
    st = t.stats
    assert st.queue_wait_s >= 0.0
    assert st.batch_rows == 3 and st.bucket_rows == 4
    assert st.scoring_us > 0.0
    assert st.flush_reason == "manual"


def test_oversized_request_rides_alone(setup):
    """A request larger than the largest bucket pads to a multiple of
    it (closed shape set) and still returns exact results."""
    X, Qm, indexes = setup
    idx = indexes["flat"]
    eng = _engine({"flat": idx}, batch_buckets=(8,))
    s, ids = eng.search(Qm[:20], k=5, index="flat")
    ds, di = idx.search(Qm[:20], k=5)
    assert jnp.array_equal(jnp.asarray(s), ds)
    assert jnp.array_equal(jnp.asarray(ids), di)
    assert t_bucket(eng) == 24
    assert eng.stats.padded_rows == 4


def t_bucket(eng):
    (entry,) = eng.stats.compiled_buckets
    return entry[2]


def test_sharded_rerank_without_raw_errors_at_result(setup):
    """Sharded rerank needs distributed raw shards; without keep_raw
    the backend error reaches the ticket instead of vanishing."""
    X, Qm, indexes = setup
    eng = _engine(indexes)
    t = eng.submit(Qm[:1], k=5, index="sharded", rerank=10)
    with pytest.raises(ValueError, match="keep_raw"):
        eng.flush()  # explicit flush re-raises at the flush site
    with pytest.raises(RuntimeError, match="fused scoring"):
        t.result()  # ... and the ticket carries it too


def test_sharded_rerank_through_engine_matches_direct(setup):
    """Engine-served sharded rerank == direct search bit-for-bit (the
    shard-local rerank path honors the shortlist grouping)."""
    X, Qm, indexes = setup
    model = indexes["sharded"].model
    cfg = model.config
    idx = AshIndex.build(
        jax.random.PRNGKey(0), X, cfg, backend="sharded", model=model,
        keep_raw=True,
    )
    eng = _engine({"sharded": idx})
    t1 = eng.submit(Qm[:3], k=5, index="sharded", rerank=20)
    t2 = eng.submit(Qm[3:4], k=5, index="sharded", rerank=20)
    eng.flush()
    ds, di = idx.search(Qm[:4], k=5, rerank=20)
    got_s = onp.concatenate([t1.result()[0], t2.result()[0]])
    got_i = onp.concatenate([t1.result()[1], t2.result()[1]])
    assert onp.array_equal(got_s, onp.asarray(ds))
    assert onp.array_equal(got_i, onp.asarray(di))


def test_engine_config_validation():
    with pytest.raises(ValueError, match="ascending"):
        EngineConfig(batch_buckets=(32, 8))
    with pytest.raises(ValueError, match="non-empty"):
        EngineConfig(batch_buckets=())
