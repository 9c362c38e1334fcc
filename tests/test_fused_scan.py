"""Fused all-metric scoring + on-chip top-k selection (tentpole of the
metric/selection kernel family).

Covers: l2/cos epilogue parity vs the jnp oracles across bitrates and
ragged (non-block-multiple) shapes in interpret mode; exact equality of
the fused-selection kernel against the materialize-then-``top_k``
oracle (values, ids AND tie order) for every k <= k̃; NEG_INF /
padded-row masking; the k̃ < k recall mode; and the index-layer routing
(flat fused path, IVF full-probe full scan, stats save/load).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ASHConfig, encode, payload_stats, prepare_queries, train,
)
from repro.core import scoring as S
from repro.core import quantization as Q
from repro.data.synthetic import embedding_dataset
from repro.index import AshIndex
from repro.index import common as C
from repro.kernels import ops, ref
from repro.kernels.ash_score import ash_score_pallas, ash_score_topk_pallas

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


# b sweep x ragged m/n/d (never block multiples) per the brief
CASES = [
    (1, 96, 300, 3, 4),
    (2, 130, 513, 9, 16),
    (4, 48, 257, 1, 8),
    (8, 36, 140, 5, 2),
]


@pytest.mark.parametrize("metric", ("l2", "cos"))
@pytest.mark.parametrize("b,d,n,m,C_", CASES)
def test_metric_epilogue_kernel_vs_oracle(metric, b, d, n, m, C_):
    args = _mk_inputs(jax.random.PRNGKey(b * 31 + d), b, d, n, m, C_)
    want = ref.ash_score_metric_ref(*args, b=b, metric=metric)
    got = ash_score_pallas(
        *args, b=b, metric=metric, interpret=True,
        compute_dtype=jnp.float32,
        block_m=8, block_n=128, block_d=128,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-4
    )


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("b,d,n,m,C_", CASES)
def test_fused_topk_exact_vs_materialize(metric, b, d, n, m, C_):
    """Fused selection == materialize + lax.top_k EXACTLY (values, ids,
    tie order) for k <= k̃, on multi-tile ragged grids."""
    args = _mk_inputs(jax.random.PRNGKey(b * 7 + n), b, d, n, m, C_)
    blocks = dict(block_m=8, block_n=128, block_d=128)
    scores = ash_score_pallas(
        *args, b=b, metric=metric, interpret=True,
        compute_dtype=jnp.float32, **blocks,
    )
    for k in (1, 7, 128):
        k = min(k, n)
        ws, wi = jax.lax.top_k(scores, k)
        gs, gi = ash_score_topk_pallas(
            *args, b=b, k=k, metric=metric, interpret=True,
            compute_dtype=jnp.float32, **blocks,
        )
        assert np.array_equal(np.asarray(gs), np.asarray(ws)), (metric, k)
        assert np.array_equal(np.asarray(gi), np.asarray(wi)), (metric, k)


def test_fused_topk_neg_inf_rows_and_padding():
    """Rows carrying -inf scores keep lax.top_k's tie order (ascending
    id), block-padding columns never surface, and fully exhausted
    candidate strips pad with score -inf / id -1."""
    b, d, n, m, C_ = 2, 64, 200, 4, 4
    codes, q, scale, offset, cluster, ipq, qterm, rowterm = _mk_inputs(
        jax.random.PRNGKey(5), b, d, n, m, C_
    )
    # dot-metric sentinel convention: offset = -inf silences a row
    offset = offset.at[50:].set(-jnp.inf)  # 150 dead rows
    args = (codes, q, scale, offset, cluster, ipq, qterm, rowterm)
    blocks = dict(block_m=8, block_n=128, block_d=128)
    scores = ash_score_pallas(
        *args, b=b, metric="dot", interpret=True,
        compute_dtype=jnp.float32, **blocks,
    )
    k = 80  # deep enough that -inf rows enter the result
    ws, wi = jax.lax.top_k(scores, k)
    gs, gi = ash_score_topk_pallas(
        *args, b=b, k=k, metric="dot", interpret=True,
        compute_dtype=jnp.float32, **blocks,
    )
    assert np.array_equal(np.asarray(gs), np.asarray(ws))
    assert np.array_equal(np.asarray(gi), np.asarray(wi))
    assert int(np.asarray(gi).max()) < n  # padding cols never returned
    # k̃ smaller than the per-tile -inf population: tiles emit k̃ = 8
    # candidates each (2 tiles), so k = 16 is still exactly covered but
    # the sentinel -1 shows up when k exceeds what the strip holds
    gs2, gi2 = ash_score_topk_pallas(
        *args, b=b, k=16, k_tilde=8, metric="dot", interpret=True,
        compute_dtype=jnp.float32, **blocks,
    )
    assert np.asarray(gs2).shape == (m, 16)
    valid = np.asarray(gi2) >= 0
    assert valid[:, :8].all()  # k <= k̃ prefix is the exact top-8
    assert np.array_equal(np.asarray(gs2)[:, :8], np.asarray(ws)[:, :8])
    assert np.array_equal(np.asarray(gi2)[:, :8], np.asarray(wi)[:, :8])


def test_fused_topk_recall_mode_is_valid_subset():
    """k̃ < k trades exactness for VMEM: results must still be real
    (score, id) pairs without duplicates, drawn from the true scores."""
    b, d, n, m, C_ = 2, 64, 513, 3, 8
    args = _mk_inputs(jax.random.PRNGKey(9), b, d, n, m, C_)
    blocks = dict(block_m=8, block_n=128, block_d=128)
    scores = np.asarray(ash_score_pallas(
        *args, b=b, metric="dot", interpret=True,
        compute_dtype=jnp.float32, **blocks,
    ))
    gs, gi = ash_score_topk_pallas(
        *args, b=b, k=24, k_tilde=8, metric="dot", interpret=True,
        compute_dtype=jnp.float32, **blocks,
    )
    gs, gi = np.asarray(gs), np.asarray(gi)
    for r in range(m):
        ids = gi[r][gi[r] >= 0]
        assert len(set(ids.tolist())) == len(ids)  # no duplicates
        np.testing.assert_array_equal(gs[r][: len(ids)], scores[r][ids])


def _ordered(args, order):
    """Operands whose scores follow ``order`` along the row ids, the
    same order for every query: a dominant per-row ``offset`` for
    ascending / descending, every row a copy of row 0 for equal;
    ``random`` leaves the random operands as they are."""
    codes, q, scale, offset, cluster, ipq, qterm, rowterm = args
    n = codes.shape[0]
    ids = jnp.arange(n, dtype=jnp.float32)
    if order == "ascending":
        offset = ids * 1e3
    elif order == "descending":
        offset = (n - ids) * 1e3
    elif order == "equal":
        codes, scale, offset, cluster, rowterm = (
            jnp.broadcast_to(a[:1], a.shape)
            for a in (codes, scale, offset, cluster, rowterm)
        )
    return codes, q, scale, offset, cluster, ipq, qterm, rowterm


def _union_of_tile_topk(scores, valid, block_n, k_tilde, k):
    """numpy oracle of the k̃ < k mode: the top-k, by (score desc, id
    asc), of the union of every row tile's top-k̃ valid rows, padded
    with (-inf, -1)."""
    m, n = scores.shape
    vals = np.full((m, k), -np.inf, np.float32)
    ids = np.full((m, k), -1, np.int64)
    for r in range(m):
        union = []
        for c0 in range(0, n, block_n):
            tile = [i for i in range(c0, min(c0 + block_n, n)) if valid[i]]
            tile.sort(key=lambda i: (-scores[r, i], i))
            union += tile[:k_tilde]
        union.sort(key=lambda i: (-scores[r, i], i))
        top = union[:k]
        vals[r, : len(top)] = scores[r, top]
        ids[r, : len(top)] = top
    return vals, ids


# (metric, row order, row masking, k, k̃): every order that drives the
# running threshold to an extreme, each metric, both masks, recall mode
PRUNE_CASES = [
    (metric, order, None, 20, None)
    for metric in METRICS
    for order in ("ascending", "descending", "equal", "random")
] + [
    ("l2", "random", "row_valid", 20, None),
    ("l2", "descending", "row_valid", 20, None),
    ("dot", "descending", "n_valid", 20, None),
    ("dot", "random", None, 24, 8),
    ("cos", "ascending", None, 24, 8),
]


@pytest.mark.parametrize("metric,order,mask,k,k_tilde", PRUNE_CASES)
def test_fused_topk_pruned_selection_is_exact(metric, order, mask, k, k_tilde):
    """The running threshold skips only rows that cannot reach the
    merged top-k: bit-equal to materialize + lax.top_k for k <= k̃ and
    to the union of per-tile top-k̃ sets for k̃ < k, over 17 row tiles
    (the last ragged) and two query blocks (the second mostly padding)."""
    b, d, n, m, C_ = 2, 64, 2100, 9, 8
    args = _ordered(
        _mk_inputs(jax.random.PRNGKey(17), b, d, n, m, C_), order
    )
    blocks = dict(block_m=8, block_n=128, block_d=128)
    scores = ash_score_pallas(
        *args, b=b, metric=metric, interpret=True,
        compute_dtype=jnp.float32, **blocks,
    )
    valid = np.ones(n, bool)
    masks = (None, None)
    if mask == "row_valid":
        valid = np.asarray(
            jax.random.bernoulli(jax.random.PRNGKey(3), 0.7, (n,))
        )
        masks = (None, jnp.asarray(valid))
    elif mask == "n_valid":
        valid = np.arange(n) < 1000
        masks = (jnp.int32(1000), None)
    gs, gi = ash_score_topk_pallas(
        *args, *masks, b=b, k=k, k_tilde=k_tilde, metric=metric,
        interpret=True, compute_dtype=jnp.float32, **blocks,
    )
    if k_tilde is None:
        ws, wi = jax.lax.top_k(
            jnp.where(jnp.asarray(valid)[None, :], scores, -jnp.inf), k
        )
    else:
        ws, wi = _union_of_tile_topk(
            np.asarray(scores), valid, 128, k_tilde, k
        )
    assert np.array_equal(np.asarray(gs), np.asarray(ws))
    assert np.array_equal(np.asarray(gi), np.asarray(wi))


# (row order, n, m, k = k̃, expected sweeps per call): ascending runs
# every tile's k̃ sweeps (the ragged last tile only its 6 valid rows,
# and each of the two query blocks its own), descending only tile 0's
SWEEP_CASES = [
    ("ascending", 5 * 128 + 6, 8, 10, 5 * 10 + 6),
    ("ascending", 5 * 128 + 6, 9, 10, 2 * (5 * 10 + 6)),
    ("descending", 16 * 128, 8, 10, 10),
    ("descending", 5 * 128 + 6, 9, 12, 2 * 12),
]


@pytest.mark.parametrize("order,n,m,k,want", SWEEP_CASES)
def test_fused_topk_sweep_counter(order, n, m, k, want):
    """The counter reads the sweeps the selection ran, and asking for
    it leaves the top-k as it is."""
    b, d, C_ = 2, 64, 8
    args = _ordered(
        _mk_inputs(jax.random.PRNGKey(4), b, d, n, m, C_), order
    )
    kw = dict(b=b, k=k, metric="l2", interpret=True,
              compute_dtype=jnp.float32, block_m=8, block_n=128,
              block_d=128)
    gs, gi, sweeps = ash_score_topk_pallas(*args, count_sweeps=True, **kw)
    assert int(sweeps) == want
    ws, wi = ash_score_topk_pallas(*args, **kw)
    assert np.array_equal(np.asarray(gs), np.asarray(ws))
    assert np.array_equal(np.asarray(gi), np.asarray(wi))


def test_fused_topk_sweep_counter_prunes_random_rows():
    """Random scores, m = 8, 64 tiles of 128, k = 16: the threshold
    leaves under a quarter of the 1,024 sweeps the unpruned selection
    runs (a simulation of iid scores gives 164–195)."""
    b, d, n, m, C_ = 2, 64, 64 * 128, 8, 8
    args = _mk_inputs(jax.random.PRNGKey(6), b, d, n, m, C_)
    _, _, sweeps = ash_score_topk_pallas(
        *args, b=b, k=16, metric="l2", interpret=True,
        compute_dtype=jnp.float32, block_m=8, block_n=128, block_d=128,
        count_sweeps=True,
    )
    assert 16 <= int(sweeps) < 64 * 16 // 4


def test_topk_k_exceeding_candidate_strip_raises():
    b, d, n, m, C_ = 2, 64, 120, 2, 2
    args = _mk_inputs(jax.random.PRNGKey(2), b, d, n, m, C_)
    with pytest.raises(ValueError, match="candidate strip"):
        ash_score_topk_pallas(
            *args, b=b, k=64, k_tilde=8, metric="dot", interpret=True,
            block_m=8, block_n=128, block_d=128,
        )


# ---------------------------------------------------------------------------
# ops wrappers on a real encoded payload
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def payload_setup():
    key = jax.random.PRNGKey(11)
    X = embedding_dataset(key, 2000, 48)
    Qm = embedding_dataset(jax.random.PRNGKey(12), 7, 48)
    model, _ = train(key, X, ASHConfig(b=2, d=24, n_landmarks=8))
    pay = encode(model, X)
    prep = prepare_queries(model, Qm)
    stats = payload_stats(model, pay)
    return model, pay, prep, stats


@pytest.mark.parametrize("metric", METRICS)
def test_ops_metric_oracle_tracks_reference_scorers(payload_setup, metric):
    """The epilogue-form oracle approximates the reference scorers to
    float assoc-order error (same math, different grouping)."""
    model, pay, prep, stats = payload_setup
    ref_scores = {
        "dot": lambda: S.score_dot(model, prep, pay),
        "l2": lambda: -S.score_l2(model, prep, pay),
        "cos": lambda: S.score_cosine(model, prep, pay),
    }[metric]()
    got = ops.ash_score(
        model, prep, pay, metric=metric, stats=stats, use_pallas=False
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref_scores), rtol=1e-4, atol=2e-3
    )


@pytest.mark.parametrize("metric", METRICS)
def test_ops_topk_interpret_matches_oracle_routing(payload_setup, metric):
    """ops.ash_score_topk in interpret mode == top_k of the interpreted
    metric kernel (the acceptance-criterion oracle), k up to the cap."""
    model, pay, prep, stats = payload_setup
    scores = ops.ash_score(
        model, prep, pay, metric=metric, stats=stats,
        use_pallas=True, interpret=True,
    )
    for k in (1, 10, ops.FUSED_TOPK_MAX_K):
        ws, wi = jax.lax.top_k(scores, k)
        gs, gi = ops.ash_score_topk(
            model, prep, pay, k, metric=metric, stats=stats,
            use_pallas=True, interpret=True,
        )
        assert np.array_equal(np.asarray(gs), np.asarray(ws)), (metric, k)
        assert np.array_equal(np.asarray(gi), np.asarray(wi)), (metric, k)


def test_stats_on_the_fly_matches_prebuilt(payload_setup):
    """stats=None rebuilds ASHStats in-call — same scores bit-for-bit."""
    model, pay, prep, stats = payload_setup
    a = ops.ash_score(
        model, prep, pay, metric="cos", stats=stats, use_pallas=False
    )
    b = ops.ash_score(
        model, prep, pay, metric="cos", stats=None, use_pallas=False
    )
    assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Index-layer routing
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
def test_flat_fused_selection_equals_materialized_topk(index_setup, metric):
    """The flat fused-selection route == top_k over the fused scores
    (the routing boundary at k > FUSED_TOPK_MAX_K is invisible)."""
    X, Qm, cfg, model, kb = index_setup
    idx = AshIndex.build(kb, X, cfg, metric=metric, model=model)
    k = 20
    s, i = idx.search(Qm, k=k)

    # the oracle must be jitted as one program with the same argument
    # structure as _search_prepped (closure constants vs jit arguments
    # change XLA fusion, hence last-ulp score bits)
    @jax.jit
    def materialized(index, prep):
        scores = C.approx_scores(
            index.model, prep, index.payload, metric,
            use_pallas=None, stats=index.stats,
        )
        return jax.lax.top_k(scores, k)

    ws, wi = materialized(idx._state, idx.prepare(Qm))
    assert np.array_equal(np.asarray(s), np.asarray(ws))
    assert np.array_equal(np.asarray(i), np.asarray(wi))
    # beyond the fused-selection cap the materialize fallback serves
    # identical prefixes
    big_k = min(C.fused_topk_limit() + 50, idx.n)
    s2, i2 = idx.search(Qm, k=big_k)
    assert np.array_equal(np.asarray(s2)[:, :k], np.asarray(s))
    assert np.array_equal(np.asarray(i2)[:, :k], np.asarray(i))


@pytest.mark.parametrize("metric", METRICS)
def test_ivf_full_probe_routes_to_full_scan(index_setup, metric):
    """nprobe >= nlist runs the fused dense scan: same candidates as
    the flat backend (identical per-row scores, ids mapped back)."""
    X, Qm, cfg, model, kb = index_setup
    fi = AshIndex.build(kb, X, cfg, metric=metric, model=model)
    ii = AshIndex.build(kb, X, cfg, backend="ivf", metric=metric,
                        model=model)
    fs, fids = fi.search(Qm, k=15)
    is_, iids = ii.search(Qm, k=15, nprobe=cfg.n_landmarks)
    assert np.array_equal(np.sort(np.asarray(fids), 1),
                          np.sort(np.asarray(iids), 1))
    np.testing.assert_allclose(
        np.sort(np.asarray(fs), 1), np.sort(np.asarray(is_), 1),
        rtol=1e-5, atol=1e-5,
    )
    # over-large nprobe normalizes onto the same path/trace
    s2, i2 = ii.search(Qm, k=15, nprobe=10_000)
    assert np.array_equal(np.asarray(i2), np.asarray(iids))


def test_flat_single_row_matches_batch_rows(index_setup):
    """Per-row bit-identity across batch shapes on the fused path — the
    invariant the serving engine's bucketing relies on."""
    X, Qm, cfg, model, kb = index_setup
    for metric in METRICS:
        idx = AshIndex.build(kb, X, cfg, metric=metric, model=model)
        sb, ib = idx.search(Qm, k=9)
        s1, i1 = idx.search(Qm[3:4], k=9)
        assert np.array_equal(np.asarray(s1), np.asarray(sb)[3:4]), metric
        assert np.array_equal(np.asarray(i1), np.asarray(ib)[3:4]), metric


@pytest.mark.parametrize("backend", ("flat", "ivf"))
def test_stats_save_load_bit_identity(index_setup, backend, tmp_path):
    """ASHStats survives persistence bit-for-bit, and loading a
    pre-stats save (no stats.* arrays) rebuilds identical values."""
    X, Qm, cfg, model, kb = index_setup
    idx = AshIndex.build(kb, X, cfg, backend=backend, metric="cos",
                         model=model)
    assert idx.stats is not None and idx.stats.n == idx.n
    path = tmp_path / backend
    idx.save(path)
    idx2 = AshIndex.load(path)
    for f in ("res_norm", "ip_x_mu", "x_sq"):
        assert np.array_equal(
            np.asarray(getattr(idx.stats, f)),
            np.asarray(getattr(idx2.stats, f)),
        ), f
    s1, i1 = idx.search(Qm, k=10)
    s2, i2 = idx2.search(Qm, k=10)
    assert np.array_equal(np.asarray(s1), np.asarray(s2))
    assert np.array_equal(np.asarray(i1), np.asarray(i2))

    # simulate a pre-stats save: strip the stats arrays and reload
    import numpy as onp
    with onp.load(path / "arrays.npz") as npz:
        arrays = {k: npz[k] for k in npz.files if not k.startswith("stats.")}
    onp.savez(path / "arrays.npz", **arrays)
    import json
    meta = json.loads((path / "config.json").read_text())
    meta["dtypes"] = {
        k: v for k, v in meta["dtypes"].items()
        if not k.startswith("stats.")
    }
    # a genuine pre-stats save predates the checksum manifest too
    meta["checksums"] = {
        k: v for k, v in meta.get("checksums", {}).items()
        if not k.startswith("stats.")
    }
    (path / "config.json").write_text(json.dumps(meta))
    idx3 = AshIndex.load(path)
    assert idx3.stats is not None
    s3, i3 = idx3.search(Qm, k=10)
    assert np.array_equal(np.asarray(s1), np.asarray(s3))
    assert np.array_equal(np.asarray(i1), np.asarray(i3))


def test_flat_add_extends_stats(index_setup):
    """add() concatenates stats == a from-scratch build's stats."""
    X, Qm, cfg, model, kb = index_setup
    a = AshIndex.build(kb, X[:2000], cfg, metric="l2", model=model)
    a.add(X[2000:])
    b = AshIndex.build(kb, X, cfg, metric="l2", model=model)
    for f in ("res_norm", "ip_x_mu", "x_sq"):
        assert np.array_equal(
            np.asarray(getattr(a.stats, f)),
            np.asarray(getattr(b.stats, f)),
        ), f
