"""ScanPlan: one scoring path for dense, gathered and sharded scans.

Covers: the masked-gather kernel family (packed-word gather)
against its rowwise oracle on ragged candidate lists with pad ids;
exact equality of fused gather selection vs materialize-then-``top_k``;
the dynamic ``n_valid`` row masking of the dense selection kernel;
cross-path parity — sharded l2/cos/dot vs the flat fused scan (values,
ids, tie order) across 1/2/4-shard meshes and the gather plan vs the
retained rowwise reference scorers; and shard-local exact rerank end to
end (build -> save -> load -> engine).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import ASHConfig, prepare_queries
from repro.core import quantization as Q
from repro.core import scoring as S
from repro.data.synthetic import embedding_dataset
from repro.index import AshIndex
from repro.index import common as C
from repro.index import distributed as DX
from repro.kernels import ops, ref
from repro.kernels.ash_score import (
    ProbedLists,
    ash_score_gather_pallas,
    ash_score_gather_topk_pallas,
    ash_score_pallas,
    ash_score_topk_pallas,
)
from repro.serving.engine import QueryEngine

METRICS = ("dot", "l2", "cos")


def _mk_inputs(key, b, d, n, m, C_):
    """Synthetic packed codes + epilogue operands (no trained model)."""
    ks = jax.random.split(key, 8)
    vals = Q.quant(jax.random.normal(ks[0], (n, d)), b)
    codes = Q.pack_codes(vals, b)
    d_pad = codes.shape[1] * Q.codes_per_word(b)
    q = jnp.pad(jax.random.normal(ks[1], (m, d)), ((0, 0), (0, d_pad - d)))
    scale = jax.random.uniform(ks[2], (n,), minval=0.5, maxval=2.0)
    offset = jax.random.normal(ks[3], (n,))
    cluster = jax.random.randint(ks[4], (n,), 0, C_)
    ipq = jax.random.normal(ks[5], (m, C_))
    qterm = jax.random.uniform(ks[6], (m,), minval=0.1, maxval=3.0)
    rowterm = jax.random.uniform(ks[7], (n,), minval=0.1, maxval=3.0)
    return codes, q, scale, offset, cluster, ipq, qterm, rowterm


def _mk_rows(key, m, R, n, pad_frac=0.3):
    """Ragged candidate lists: random rows with ~pad_frac -1 pads."""
    k1, k2 = jax.random.split(key)
    rows = jax.random.randint(k1, (m, R), 0, n)
    pads = jax.random.uniform(k2, (m, R)) < pad_frac
    return jnp.where(pads, -1, rows).astype(jnp.int32)


# b sweep x ragged m/R/d (never block multiples)
CASES = [
    (1, 96, 300, 3, 4, 21),
    (2, 130, 513, 5, 16, 37),
    (4, 48, 257, 1, 8, 130),
    (8, 36, 140, 4, 2, 9),
]


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("b,d,n,m,C_,R", CASES)
def test_gather_kernel_vs_rowwise_oracle(metric, b, d, n, m, C_, R):
    """The masked-gather kernel matches the rowwise oracle
    on ragged candidate lists; pad ids score exactly -inf."""
    codes, q, scale, offset, cluster, ipq, qterm, rowterm = _mk_inputs(
        jax.random.PRNGKey(b * 31 + d), b, d, n, m, C_
    )
    rows = _mk_rows(jax.random.PRNGKey(R), m, R, n)
    args = (codes, rows, q, scale, offset, cluster, ipq, qterm, rowterm)
    want = ref.ash_score_gather_ref(*args, b=b, metric=metric)
    got = ash_score_gather_pallas(
        *args, b=b, metric=metric, interpret=True,
        compute_dtype=jnp.float32, block_r=16, block_d=128,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-4
    )
    assert np.all(np.isneginf(np.asarray(got))[np.asarray(rows) < 0])
    assert np.all(np.isneginf(np.asarray(want))[np.asarray(rows) < 0])


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("b,d,n,m,C_,R", CASES)
def test_gather_fused_topk_exact_vs_materialize(metric, b, d, n, m, C_, R):
    """Fused gather selection == top_k over the gather kernel's scores
    EXACTLY (values, mapped rows, tie order) for k <= k̃."""
    codes, q, scale, offset, cluster, ipq, qterm, rowterm = _mk_inputs(
        jax.random.PRNGKey(b * 7 + n), b, d, n, m, C_
    )
    rows = _mk_rows(jax.random.PRNGKey(R + 1), m, R, n)
    args = (codes, rows, q, scale, offset, cluster, ipq, qterm, rowterm)
    blocks = dict(block_r=16, block_d=128)
    scores = ash_score_gather_pallas(
        *args, b=b, metric=metric, interpret=True,
        compute_dtype=jnp.float32, **blocks,
    )
    for k in (1, 7, min(R, 32)):
        ws, wp = jax.lax.top_k(scores, k)
        wrows = jnp.take_along_axis(rows, wp, axis=1)
        gs, gr = ash_score_gather_topk_pallas(
            *args, b=b, k=k, metric=metric, interpret=True,
            compute_dtype=jnp.float32, **blocks,
        )
        assert np.array_equal(np.asarray(gs), np.asarray(ws)), (metric, k)
        assert np.array_equal(np.asarray(gr), np.asarray(wrows)), (metric, k)


def test_gather_topk_all_pad_row_returns_sentinels():
    """A query whose whole candidate list is padding gets score -inf /
    row -1 in every slot."""
    b, d, n, m, C_ = 2, 64, 200, 3, 4
    codes, q, scale, offset, cluster, ipq, qterm, rowterm = _mk_inputs(
        jax.random.PRNGKey(3), b, d, n, m, C_
    )
    rows = _mk_rows(jax.random.PRNGKey(4), m, 20, n)
    rows = rows.at[1, :].set(-1)
    args = (codes, rows, q, scale, offset, cluster, ipq, qterm, rowterm)
    gs, gr = ash_score_gather_topk_pallas(
        *args, b=b, k=5, metric="l2", interpret=True,
        compute_dtype=jnp.float32, block_r=16, block_d=128,
    )
    assert np.all(np.isneginf(np.asarray(gs)[1]))
    assert np.all(np.asarray(gr)[1] == -1)


def test_dense_topk_dynamic_n_valid_masks_rows():
    """The dense selection kernel's runtime n_valid masks rows exactly
    like materialize + mask + top_k (the sharded pad-row fold)."""
    b, d, n, m, C_ = 2, 64, 300, 4, 4
    codes, q, scale, offset, cluster, ipq, qterm, rowterm = _mk_inputs(
        jax.random.PRNGKey(5), b, d, n, m, C_
    )
    args = (codes, q, scale, offset, cluster, ipq, qterm, rowterm)
    blocks = dict(block_m=8, block_n=128, block_d=128)
    scores = ash_score_pallas(
        *args, b=b, metric="l2", interpret=True,
        compute_dtype=jnp.float32, **blocks,
    )
    for nv in (10, 129, 300):
        masked = jnp.where(jnp.arange(n)[None, :] < nv, scores, -jnp.inf)
        ws, wi = jax.lax.top_k(masked, 9)
        gs, gi = ash_score_topk_pallas(
            *args, jnp.int32(nv), b=b, k=9, metric="l2", interpret=True,
            compute_dtype=jnp.float32, **blocks,
        )
        assert np.array_equal(np.asarray(gs), np.asarray(ws)), nv
        # masked rows surface only as -inf; where both sides are -inf
        # the id conventions differ (sentinel vs masked row id), which
        # the sharded merge maps to -1 either way
        finite = np.isfinite(np.asarray(ws))
        assert np.array_equal(
            np.asarray(gi)[finite], np.asarray(wi)[finite]
        ), nv


# ---------------------------------------------------------------------------
# List form: whole contiguous lists read in place by the gather kernel
# ---------------------------------------------------------------------------

# list lengths of a cluster-sorted payload: an empty list (3), one
# longer than a 128-row block (5), the rest ragged, so most starts are
# not block-aligned
LIST_LENS = (37, 0, 150, 0, 61, 300, 9, 128, 77, 1)


def _mk_lists(key, b, m, live_frac=None, d=64, block_rows=128):
    """A cluster-sorted synthetic payload, per-query probes (query 1
    probes the empty list and the long one; queries 1 and 2 probe the
    same lists) and the matching :class:`ProbedLists`."""
    lens = np.asarray(LIST_LENS)
    nlist, n = lens.size, int(lens.sum())
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    codes, q, scale, offset, _, ipq, qterm, rowterm = _mk_inputs(
        key, b, d, n, m, nlist
    )
    cluster = jnp.asarray(np.repeat(np.arange(nlist), lens), jnp.int32)
    rng = np.random.default_rng(b * 100 + m)
    probe = np.stack([rng.permutation(nlist)[:4] for _ in range(m)])
    if m > 2:
        probe[1] = (1, 5, 3, 0)
        probe[2] = probe[1]
    live = None
    if live_frac is not None:
        live = jnp.asarray(rng.random(n) > live_frac)
    lists = ProbedLists(
        starts=jnp.asarray(starts[probe], jnp.int32),
        lens=jnp.asarray(lens[probe], jnp.int32),
        clusters=jnp.asarray(probe, jnp.int32),
        live=live, stride=int(lens.max()),
    )
    return (codes, q, scale, offset, cluster, ipq, qterm, rowterm), lists


@pytest.mark.parametrize("m, live_frac", ((2, None), (11, 0.25)))
@pytest.mark.parametrize("b", (2, 4))
@pytest.mark.parametrize("metric", METRICS)
def test_list_form_bit_identical_to_rows_form(metric, b, m, live_frac):
    """The gather kernel reading whole lists in place returns exactly
    what it returns for the same candidates as a row table: values,
    rows and tie order — over an empty list, a list longer than a
    block, unaligned starts, two queries probing the same lists,
    tombstones, the served m=2 pad and a query block with pad rows."""
    (codes, q, *rest), lists = _mk_lists(
        jax.random.PRNGKey(b * 13 + m), b, m, live_frac
    )
    rows = lists.rows()
    assert (np.asarray(rows) >= 0).sum() > 0
    kw = dict(b=b, metric=metric, interpret=True,
              compute_dtype=jnp.float32, block_r=128)
    for k in (1, 10, 40):
        gs, gr = ash_score_gather_topk_pallas(codes, lists, q, *rest, k=k,
                                              **kw)
        ws, wr = ash_score_gather_topk_pallas(codes, rows, q, *rest, k=k,
                                              **kw)
        assert np.array_equal(np.asarray(gs), np.asarray(ws)), (metric, k)
        assert np.array_equal(np.asarray(gr), np.asarray(wr)), (metric, k)
    # the empty and tombstoned slots never surface
    got = np.asarray(gr)
    if live_frac is not None:
        alive = np.asarray(lists.live)
        assert alive[got[got >= 0]].all()


@pytest.mark.parametrize("block_r", (128, 256))
def test_list_form_counts_block_copies(block_r):
    """``count_blocks`` is the number of (query, list-block) copies:
    each real probe of a non-empty list reads the blocks its row range
    spans, and the five pad queries of the block read nothing."""
    (codes, q, *rest), lists = _mk_lists(jax.random.PRNGKey(7), 2, 3)
    _, _, blocks = ash_score_gather_topk_pallas(
        codes, lists, q, *rest, b=2, k=10, metric="l2", interpret=True,
        compute_dtype=jnp.float32, block_r=block_r, count_blocks=True,
    )
    starts, lens = np.asarray(lists.starts), np.asarray(lists.lens)
    want = np.where(lens > 0, -(-(starts % block_r + lens) // block_r), 0)
    assert int(blocks) == int(want.sum()) > 0


# ---------------------------------------------------------------------------
# Index-layer routing on a real encoded payload
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def index_setup():
    key = jax.random.PRNGKey(21)
    kx, kq, kb = jax.random.split(key, 3)
    X = embedding_dataset(kx, 3000, 32)
    Qm = embedding_dataset(kq, 16, 32)
    cfg = ASHConfig(b=2, d=16, n_landmarks=8)
    model = AshIndex.build(kb, X, cfg, backend="flat").model
    return X, Qm, cfg, model, kb


@pytest.mark.parametrize("metric", METRICS)
def test_gather_plan_vs_rowwise_reference_scorers(index_setup, metric):
    """The gather plan's scores track the retained rowwise reference
    scorers (``scoring.score_*`` over a gathered sub-payload) to float
    assoc-order error — the pre-ScanPlan IVF partial-probe path."""
    X, Qm, cfg, model, kb = index_setup
    idx = AshIndex.build(kb, X, cfg, backend="ivf", metric=metric,
                         model=model)
    state = idx._state
    prep = idx.prepare(Qm)
    rows = _mk_rows(jax.random.PRNGKey(0), Qm.shape[0], 64, idx.n)
    got = ops.ash_score_gather(
        model, prep, state.payload, rows, metric=metric,
        stats=state.stats, use_pallas=False,
    )

    def rowwise_one(prep_q, rows_q):
        sub = C.gather_payload(state.payload, rows_q)
        one = jax.tree_util.tree_map(
            lambda a: a[None] if hasattr(a, "ndim") else a, prep_q
        )
        if metric == "dot":
            sc = S.score_dot(model, one, sub, rowwise=True)
        elif metric == "l2":
            sc = -S.score_l2(model, one, sub, rowwise=True)
        else:
            sc = S.score_cosine(model, one, sub, rowwise=True)
        return jnp.where(rows_q >= 0, sc[0], C.NEG_INF)

    want = jax.vmap(rowwise_one)(prep, rows)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=2e-3
    )


@pytest.mark.parametrize("metric", METRICS)
def test_ivf_partial_probe_fused_equals_materialized(index_setup, metric):
    """IVF partial probes route through the gather plan: the fused
    search result == top_k over the gather scores of the probed lists
    (values, ids, tie order) — no score-matrix path left behind."""
    X, Qm, cfg, model, kb = index_setup
    idx = AshIndex.build(kb, X, cfg, backend="ivf", metric=metric,
                         model=model)
    state = idx._state
    k, nprobe = 10, 3
    s, ids = idx.search(Qm, k=k, nprobe=nprobe)

    @jax.jit
    def materialized(state, prep):
        coarse = (
            prep.ip_q_landmarks
            - 0.5 * model.landmark_sq_norms[None, :]
        )
        _, probe = jax.lax.top_k(coarse, nprobe)
        rows = state.invlists[probe].reshape(prep.q.shape[0], -1)
        sc = ops.ash_score_gather(
            model, prep, state.payload, rows, metric=metric,
            stats=state.stats,
        )
        ws, wp = jax.lax.top_k(sc, k)
        wrows = jnp.take_along_axis(rows, wp, axis=1)
        return ws, jnp.where(
            wrows < 0, -1, state.ids[jnp.maximum(wrows, 0)]
        )

    ws, wids = materialized(state, idx.prepare(Qm))
    assert np.array_equal(np.asarray(s), np.asarray(ws))
    assert np.array_equal(np.asarray(ids), np.asarray(wids))


def test_probed_lists_are_the_padded_lists(index_setup):
    """The row ranges a partial probe hands the kernel are the padded
    inverted lists: ``probed_lists(...).rows()`` equals
    ``invlists[probe]`` with tombstoned rows dropped, slot for slot,
    after an add and deletes."""
    from repro.index import ivf as IV

    X, Qm, cfg, model, kb = index_setup
    idx = AshIndex.build(kb, X[:2000], cfg, backend="ivf", model=model)
    idx.add(X[2000:2400])
    assert idx.delete(np.arange(0, 2400, 7)) > 0
    st = idx._state
    probe = IV._probe_lists(st, idx.prepare(Qm), 3)
    got = IV.probed_lists(st, probe).rows()
    want = st.invlists[probe].reshape(Qm.shape[0], -1)
    want = jnp.where(st.live[jnp.maximum(want, 0)], want, -1)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_ivf_partial_probe_single_row_matches_batch(index_setup):
    """Per-row bit-identity across batch shapes on the gather path —
    the invariant the serving engine's bucketing relies on."""
    X, Qm, cfg, model, kb = index_setup
    for metric in METRICS:
        idx = AshIndex.build(kb, X, cfg, backend="ivf", metric=metric,
                             model=model)
        sb, ib = idx.search(Qm, k=9, nprobe=3)
        s1, i1 = idx.search(Qm[5:6], k=9, nprobe=3)
        assert np.array_equal(np.asarray(s1), np.asarray(sb)[5:6]), metric
        assert np.array_equal(np.asarray(i1), np.asarray(ib)[5:6]), metric


# ---------------------------------------------------------------------------
# Cross-path parity: sharded vs flat over 1/2/4-shard meshes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("n_shards", (1, 2, 4))
def test_sharded_fused_matches_flat_exactly(index_setup, metric, n_shards):
    """Sharded search == flat fused search bit-for-bit — values, ids
    AND tie order — for every metric and mesh width (the local scans
    run the same fused epilogues + fused local top-k, the merge
    preserves the global tie convention)."""
    X, Qm, cfg, model, kb = index_setup
    if n_shards > jax.device_count():
        pytest.skip("needs more devices")
    fi = AshIndex.build(kb, X, cfg, metric=metric, model=model)
    fs, fids = fi.search(Qm, k=20)
    mesh = Mesh(np.array(jax.devices()[:n_shards]), ("data",))
    si = AshIndex.build(
        kb, X, cfg, backend="sharded", metric=metric, model=model,
        mesh=mesh, axes=("data",),
    )
    ss, sids = si.search(Qm, k=20)
    assert np.array_equal(np.asarray(ss), np.asarray(fs))
    assert np.array_equal(np.asarray(sids), np.asarray(fids))


def test_sharded_fused_matches_reference_searcher(index_setup):
    """The fused sharded route == the retained reference route
    (fused=False: reference scorers + materialize-then-top_k) on the
    same mesh — identical ids, scores to float assoc-order error."""
    X, Qm, cfg, model, kb = index_setup
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    si = AshIndex.build(
        kb, X, cfg, backend="sharded", metric="cos", model=model,
        mesh=mesh, axes=("data",),
    )
    state = si._state
    prep = si.prepare(Qm)
    fused = state.searcher(10)(
        state.sharded, prep, stats=state.sharded_stats
    )
    reference = DX.make_sharded_search_prepped(
        mesh, model, ("data",), 10, metric="cos", fused=False
    )(state.sharded, prep)
    assert np.array_equal(np.asarray(fused[1]), np.asarray(reference[1]))
    np.testing.assert_allclose(
        np.asarray(fused[0]), np.asarray(reference[0]),
        rtol=1e-4, atol=2e-3,
    )


def test_sharded_padded_mesh_parity(index_setup):
    """A row count that does NOT divide the mesh exercises the pad
    sentinel + derived n_valid mask: results still match flat."""
    X, Qm, cfg, model, kb = index_setup
    X_odd = X[:2999]  # 2999 rows over 4 shards -> 1 pad row
    fi = AshIndex.build(kb, X_odd, cfg, metric="l2", model=model)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    si = AshIndex.build(
        kb, X_odd, cfg, backend="sharded", metric="l2", model=model,
        mesh=mesh, axes=("data",),
    )
    fs, fids = fi.search(Qm, k=15)
    ss, sids = si.search(Qm, k=15)
    assert np.array_equal(np.asarray(ss), np.asarray(fs))
    assert np.array_equal(np.asarray(sids), np.asarray(fids))
    assert int(np.asarray(sids).max()) < 2999


# ---------------------------------------------------------------------------
# Shard-local rerank end to end
# ---------------------------------------------------------------------------


def test_sharded_rerank_build_save_load_engine(index_setup, tmp_path):
    """The acceptance path: sharded rerank works end-to-end (build ->
    save -> load -> engine) and the engine serves it bit-identically."""
    X, Qm, cfg, model, kb = index_setup
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    idx = AshIndex.build(
        kb, X, cfg, backend="sharded", metric="cos", model=model,
        keep_raw=True, mesh=mesh, axes=("data",),
    )
    s1, i1 = idx.search(Qm, k=10, rerank=60)
    assert np.all(np.asarray(i1) >= 0)
    idx.save(tmp_path / "sharded")
    idx2 = AshIndex.load(tmp_path / "sharded")
    s2, i2 = idx2.search(Qm, k=10, rerank=60)
    assert np.array_equal(np.asarray(s1), np.asarray(s2))
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    eng = QueryEngine(idx2, batch_buckets=(8, 16), k_buckets=(16,),
                      max_wait_s=60.0)
    t = eng.submit(np.asarray(Qm[:5]), k=10, rerank=60)
    eng.flush()
    es, ei = t.result()
    assert np.array_equal(es, np.asarray(s2)[:5])
    assert np.array_equal(ei, np.asarray(i2)[:5])


def test_sharded_add_keeps_raw_and_stats(index_setup):
    """add() re-places raw shards + stats; results match a fresh build
    over the concatenated rows."""
    X, Qm, cfg, model, kb = index_setup
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    kw = dict(backend="sharded", metric="l2", model=model,
              keep_raw=True, mesh=mesh, axes=("data",))
    a = AshIndex.build(kb, X[:2000], cfg, **kw)
    a.add(X[2000:])
    b = AshIndex.build(kb, X, cfg, **kw)
    sa, ia = a.search(Qm, k=10, rerank=50)
    sb, ib = b.search(Qm, k=10, rerank=50)
    assert np.array_equal(np.asarray(sa), np.asarray(sb))
    assert np.array_equal(np.asarray(ia), np.asarray(ib))


def test_pad_sentinel_never_reaches_list_assembly(index_setup):
    """The -1 pad sentinel is rejected where cluster ids feed gathers
    (IVF list assembly) — it would silently alias by wrapping."""
    X, Qm, cfg, model, kb = index_setup
    from repro.index import ivf as IV

    fi = AshIndex.build(kb, X[:100], cfg, metric="dot", model=model)
    padded = DX.pad_to_multiple(fi.payload, 64)
    assert int(np.asarray(padded.cluster)[-1]) == DX.PAD_CLUSTER
    ids = jnp.arange(padded.n, dtype=jnp.int32)
    with pytest.raises(ValueError, match="pad-sentinel"):
        IV._assemble("dot", model, padded, ids, None)


# ---------------------------------------------------------------------------
# ScanPlan validation + selection-cap fallback
# ---------------------------------------------------------------------------


def test_plan_rejects_unknown_coarse_mode(index_setup):
    X, Qm, cfg, model, kb = index_setup
    idx = AshIndex.build(kb, X, cfg, model=model)
    with pytest.raises(ValueError, match="unknown coarse mode"):
        idx.search(Qm, k=5, coarse="fp8")


def test_plan_rejects_shortlist_without_coarse(index_setup):
    X, Qm, cfg, model, kb = index_setup
    idx = AshIndex.build(kb, X, cfg, model=model)
    with pytest.raises(ValueError, match="requires"):
        idx.search(Qm, k=5, shortlist=64)


def test_plan_rejects_row_masks_on_gathered_plan(index_setup):
    """Gathered plans mask by pad id only: row_valid / n_valid are
    dense-plan concepts and must fail loudly, not no-op (a silently
    ignored tombstone bitmap would resurrect deleted rows)."""
    X, Qm, cfg, model, kb = index_setup
    idx = AshIndex.build(kb, X, cfg, model=model)
    st = idx._state
    prep = idx.prepare(Qm)
    rows = _mk_rows(jax.random.PRNGKey(1), Qm.shape[0], 32, idx.n)
    for bad in (
        {"row_valid": jnp.ones((idx.n,), bool)},
        {"n_valid": jnp.int32(10)},
    ):
        plan = C.ScanPlan(metric="dot", k=5, rows=rows, **bad)
        with pytest.raises(ValueError, match="dense plans only"):
            C.execute_plan(model, prep, st.payload, plan,
                           stats=st.stats)


def test_sharded_rerank_without_raw_raises(index_setup):
    """rerank > 0 without retained raw vectors is a loud error on the
    sharded backend — never a silent fall-back to ASH scores."""
    X, Qm, cfg, model, kb = index_setup
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    si = AshIndex.build(kb, X, cfg, backend="sharded", model=model,
                        mesh=mesh, axes=("data",))
    with pytest.raises(ValueError, match="keep_raw"):
        si.search(Qm, k=5, rerank=50)


def test_topk_beyond_fused_cap_falls_back(index_setup):
    """k above fused_topk_limit() routes to materialize-then-top_k and
    returns exactly top_k of the materialized scores — the routing
    boundary is invisible."""
    X, Qm, cfg, model, kb = index_setup
    idx = AshIndex.build(kb, X, cfg, model=model)
    k = C.fused_topk_limit() + 22
    s, ids = idx.search(Qm, k=k)
    st = idx._state
    want = jax.lax.top_k(
        C.approx_scores(model, idx.prepare(Qm), st.payload, "dot",
                        stats=st.stats),
        k,
    )
    assert np.array_equal(np.asarray(s), np.asarray(want[0]))
    assert np.array_equal(np.asarray(ids), np.asarray(want[1]))
