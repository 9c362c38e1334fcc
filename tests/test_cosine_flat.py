"""Cosine search over non-unit text-embedding rows (D=768, b=4, d=128).

The row norm cosine divides by is recovered from the headers as
||x - mu*||^2 + 2 <x, mu*> - ||mu*||^2, where <x, mu*> is the exact
inner product that OFFSET folds in at encode.  The paper's Eq. (A.5)
sees that cross term only through the quantized projection; each test
here would fail under it (readings at this file's sizes, CPU, in the
comments).  Also: chunked encode, and a load of an index saved with the
older ``x_sq``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from repro.core import ASHConfig
from repro.core import ash as A
from repro.core import quantization as Qz
from repro.core import scoring as S
from repro.data.synthetic import embedding_dataset
from repro.index import AshIndex
from repro.serving.engine import QueryEngine

N, M, D = 12_000, 200, 768
CFG = ASHConfig(b=4, d=128, n_landmarks=64)
K, RERANK = 10, 100


@pytest.fixture(scope="module")
def corpus():
    """Rows and queries from one draw of the distribution; a flat cos
    index with raw rows for rerank."""
    XA = embedding_dataset(jax.random.PRNGKey(15), N + M, D)
    X, Q = XA[:N], onp.asarray(XA[N:])
    index = AshIndex.build(
        jax.random.PRNGKey(1), X, CFG, backend="flat", metric="cos",
        keep_raw=True,
    )
    return X, Q, index


def _eq_a5_x_sq(model, payload):
    """The paper's Eq. (A.5) estimate of ||x||^2, for the controls."""
    V = Qz.unpack_codes(payload.codes, payload.d, payload.b).astype(
        jnp.float32)
    vnorm = jnp.linalg.norm(V, axis=-1)
    res = payload.scale.astype(jnp.float32) * vnorm
    ip_wmu_v = jnp.sum(model.W_landmarks[payload.cluster] * V, axis=-1)
    return (res * res + 2.0 * (res / vnorm) * ip_wmu_v
            + model.landmark_sq_norms[payload.cluster])


def _with_x_sq(index, x_sq):
    st = index._state
    return AshIndex(index.backend, index.metric, dataclasses.replace(
        st, stats=dataclasses.replace(st.stats, x_sq=x_sq)))


def _truth(X, Q):
    """Plain float32 brute-force cosine top-k at ``highest``."""
    Xn = X / jnp.linalg.norm(X, axis=-1, keepdims=True)
    Qn = Q / jnp.linalg.norm(Q, axis=-1, keepdims=True)
    s = jnp.matmul(Qn, Xn.T, precision="highest")
    return onp.asarray(jax.lax.top_k(s, K)[1])


def _recall(ids, truth):
    ids = onp.asarray(ids)[:, :K]
    return float(onp.mean(
        [len(set(a) & set(b)) / K for a, b in zip(ids, truth)]))


def _rel_err(x_sq, X):
    true = onp.sum(onp.asarray(X, onp.float64) ** 2, axis=-1)
    return onp.abs(onp.asarray(x_sq, onp.float64) / true - 1.0)


def test_recovered_norm_matches_exact(corpus):
    """``payload_stats(...).x_sq`` is ||x||^2 to 1e-3 relative; only
    the fp16 SCALE / OFFSET headers round it (max 3.9e-4, spread
    6.7e-5).  Eq. (A.5) misses it by up to 4.9 % (spread 0.9 %)."""
    X, _, index = corpus
    stats = S.payload_stats(index.model, index.payload)
    assert _rel_err(stats.x_sq, X).max() < 1e-3
    assert _rel_err(_eq_a5_x_sq(index.model, index.payload), X).max() > 1e-3


def _served(index, Q, rerank):
    engine = QueryEngine({"c": index})
    tickets = [engine.submit(Q[i:i + 8], k=K, rerank=rerank, index="c")
               for i in range(0, len(Q), 8)]
    engine.flush()
    scores = onp.concatenate([onp.asarray(t.result()[0]) for t in tickets])
    ids = onp.concatenate([onp.asarray(t.result()[1]) for t in tickets])
    return scores, ids


def test_served_recall_with_rerank(corpus):
    """QueryEngine -> flat index, rerank 100: reads 0.9985.  The rerank
    scores bf16 rows, so a near-tie with the float32 truth may swap;
    0.99 leaves room for that and nothing else."""
    X, Q, index = corpus
    _, ids = _served(index, Q, RERANK)
    assert _recall(ids, _truth(X, Q)) >= 0.99


def test_served_shortlist_recall_without_rerank(corpus):
    """The kernel's own shortlist (rerank 0) ranks by the recovered
    norm: recall@10 0.912 here, against 0.742 with Eq. (A.5)'s norm in
    its place.  The floor lies between the two."""
    X, Q, index = corpus
    truth = _truth(X, Q)
    _, ids = _served(index, Q, 0)
    assert _recall(ids, truth) >= 0.85
    old = _with_x_sq(index, _eq_a5_x_sq(index.model, index.payload))
    _, old_ids = _served(old, Q, 0)
    assert _recall(old_ids, truth) < 0.85


def test_served_scores_are_exact_cosines_of_stored_rows(corpus):
    """Every served score is the cosine of its stored bf16 row in
    float32 (widest gap 5.4e-7 here)."""
    _, Q, index = corpus
    scores, ids = _served(index, Q, RERANK)
    R = onp.asarray(index._state.raw.astype(jnp.float32))[ids]
    Q32 = Q.astype(onp.float32)
    exact = onp.einsum("md,mkd->mk", Q32, R) / (
        onp.linalg.norm(Q32, axis=-1)[:, None]
        * onp.linalg.norm(R, axis=-1))
    assert onp.abs(scores - exact).max() < 1e-4


@pytest.mark.parametrize("chunk_rows", (1024, 777))
def test_chunked_encode_equals_one_block(corpus, chunk_rows, monkeypatch):
    """Encode in row blocks (the last one overlapping the one before)
    gives the payload of one whole-corpus block, bit for bit."""
    X, _, index = corpus
    rows = X[:3000]
    assert A.encode_chunks(index.model, len(rows)) == 1
    one = A.encode(index.model, rows)
    monkeypatch.setattr(A, "ENCODE_WHOLE_BYTES", 0)
    monkeypatch.setattr(A, "ENCODE_CHUNK_ROWS", chunk_rows)
    chunked = A.encode(index.model, rows)
    assert A.encode_chunks(index.model, len(rows)) > 1
    assert (chunked.b, chunked.d) == (one.b, one.d)
    for a, b in zip(jax.tree_util.tree_leaves(one),
                    jax.tree_util.tree_leaves(chunked)):
        assert a.dtype == b.dtype
        assert onp.array_equal(onp.asarray(a), onp.asarray(b))


def test_load_recovers_norm_of_old_save(corpus, tmp_path):
    """An index saved with the Eq. (A.5) ``x_sq`` loads with the
    recovered one, from the stored ``res_norm`` and ``ip_x_mu``, and
    searches bit-identically to the fresh index."""
    _, Q, index = corpus
    path = tmp_path / "old"
    _with_x_sq(index, _eq_a5_x_sq(index.model, index.payload)).save(path)
    # the save is what an older build wrote: its x_sq is Eq. (A.5)'s
    with onp.load(path / "arrays.npz") as npz:
        arrays = {k: npz[k] for k in npz.files}
    assert not onp.array_equal(arrays["stats.x_sq"],
                               onp.asarray(index.stats.x_sq))
    loaded = AshIndex.load(path)
    for f in ("res_norm", "ip_x_mu", "x_sq"):
        assert onp.array_equal(onp.asarray(getattr(loaded.stats, f)),
                               onp.asarray(getattr(index.stats, f))), f
    for rerank in (0, RERANK):
        s0, i0 = index.search(jnp.asarray(Q), k=K, rerank=rerank)
        s1, i1 = loaded.search(jnp.asarray(Q), k=K, rerank=rerank)
        assert onp.array_equal(onp.asarray(s0), onp.asarray(s1))
        assert onp.array_equal(onp.asarray(i0), onp.asarray(i1))
