"""Similarity computations from ASH payloads.

Implements the asymmetric dot product (Eq. 20), the 1-bit masked-add
specialization (Eq. 22), Euclidean distance and cosine similarity
(Appendix A), and the symmetric case (Appendix B).  These are the pure-jnp
reference paths; the Pallas fused kernels in ``repro.kernels`` are bit-for
-bit validated against them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import quantization as Q
from repro.core.types import (
    ASHModel, ASHPayload, ASHStats, CoarseCodes, CoarseQueryPrep,
    QueryPrep,
)

_EPS = 1e-12


# ---------------------------------------------------------------------------
# Per-query precompute (QUERY-COMPUTE of Eq. 20)
# ---------------------------------------------------------------------------


@jax.jit
def prepare_queries(model: ASHModel, q: jax.Array) -> QueryPrep:
    """One-time per-query work: q_breve = W q, <q, mu_c>, ||q||^2."""
    with jax.named_scope("ash.prep"):
        q32 = q.astype(jnp.float32)
        return QueryPrep(
            q=q32,
            q_proj=q32 @ model.W.T,
            ip_q_landmarks=q32 @ model.landmarks.T,
            q_sq_norm=jnp.sum(q32 * q32, axis=-1),
        )


# ---------------------------------------------------------------------------
# Recoverable Table-1 quantities
# ---------------------------------------------------------------------------


def recovered_terms(model: ASHModel, payload: ASHPayload, V=None):
    """Recover (V float, ||v||, ||x-mu*||, <x, mu*>) from the payload.

    ``V`` optionally passes already-unpacked codes so callers that need
    both the recovered terms and the code matrix decompress the payload
    once instead of twice.
    """
    if V is None:
        V = Q.unpack_codes(payload.codes, payload.d, payload.b).astype(
            jnp.float32
        )
    vnorm = Q.code_norms(V)
    scale = payload.scale.astype(jnp.float32)
    offset = payload.offset.astype(jnp.float32)
    res_norm = scale * vnorm
    ip_Wmu_v = jnp.sum(model.W_landmarks[payload.cluster] * V, axis=-1)
    ip_x_mu = (
        offset + scale * ip_Wmu_v
        + model.landmark_sq_norms[payload.cluster]
    )
    return V, vnorm, res_norm, ip_x_mu


def _x_sq_estimate(model, cluster, res_norm, ip_x_mu):
    """||x||^2 recovered from the headers: ||x - mu*||^2 + 2 <x, mu*>
    - ||mu*||^2 — the single definition shared by :func:`payload_stats`
    (fused cos epilogue), :func:`restore_stats` (index load) and
    :func:`score_cosine` (reference scorer), and the quantity the l2
    epilogue recovers too.

    Departs from Eq. (A.5), which writes the cross term as
    2 (||x - mu*|| / ||v||) <W mu*, v>: that sees <x - mu*, mu*> only
    through the quantized d-dimensional projection (a 0.8-2.4 % spread
    in ||x||^2 at d=64-128, b=2-4, D=768).  The term is known exactly:
    OFFSET folds the exact <x, mu*> in at encode, so ``ip_x_mu``
    recovers it at no extra storage, and only the fp16 headers' rounding
    is left."""
    mu_sq = model.landmark_sq_norms[cluster]
    return res_norm * res_norm + 2.0 * ip_x_mu - mu_sq


@jax.jit
def payload_stats(model: ASHModel, payload: ASHPayload) -> ASHStats:
    """Build the :class:`ASHStats` row statistics for a payload.

    One decompression pass at encode/build time; afterwards the fused
    l2/cos kernels score straight off the packed codes + these vectors
    (see ``repro.kernels.ops``).  ``x_sq`` is the recovered squared
    norm used by cosine search — identical to the quantity
    :func:`score_cosine` derives on the fly.
    """
    _, _, res_norm, ip_x_mu = recovered_terms(model, payload)
    return restore_stats(
        model, payload.cluster, res_norm.astype(jnp.float32),
        ip_x_mu.astype(jnp.float32),
    )


@jax.jit
def restore_stats(
    model: ASHModel, cluster: jax.Array, res_norm: jax.Array,
    ip_x_mu: jax.Array,
) -> ASHStats:
    """:class:`ASHStats` from its two stored terms, with no unpack:
    ``x_sq`` is derived from them (an index saved with an older
    ``x_sq`` loads to the same statistics as a fresh build)."""
    return ASHStats(
        res_norm=res_norm,
        ip_x_mu=ip_x_mu,
        x_sq=_x_sq_estimate(model, cluster, res_norm, ip_x_mu),
    )


# ---------------------------------------------------------------------------
# Symmetric int8 coarse pass (query quantizer + dequantized-code cache)
# ---------------------------------------------------------------------------

# int8 query grid half-width; paired with the |code| <= 255 (b=8) bound
# this keeps every coarse partial sum under 2^24 for d_pad <= 512, so
# fp32 accumulation of the integer products is EXACT — the jnp coarse
# path (one BLAS matmul over CoarseCodes.values) is bitwise equal to
# the Pallas kernel's int32 MXU accumulation.
COARSE_QMAX = 127


def coarse_codes(payload: ASHPayload) -> CoarseCodes:
    """Build the :class:`CoarseCodes` cache for a payload.

    One decompression pass at build/add/compact/load time (like
    :func:`payload_stats`); afterwards the coarse jnp scan is a single
    fp32 BLAS matmul over exact-integer values — no per-call unpack.
    """
    d_pad = payload.codes.shape[1] * Q.codes_per_word(payload.b)
    V = Q.unpack_codes(payload.codes, d_pad, payload.b).astype(
        jnp.float32
    )
    scale = payload.scale.astype(jnp.float32)
    return CoarseCodes(
        values=V, mean=jnp.mean(scale[:, None] * V, axis=0)
    )


@jax.jit
def prepare_coarse_queries(
    prep: QueryPrep, mean: jax.Array
) -> CoarseQueryPrep:
    """Symmetric int8 quantization of the projected queries.

    Per-query scale ``s = max|q_proj| / 127`` (eps-guarded), codes
    ``round(q_proj / s)`` clipped to [-127, 127].  The correction term
    ``q_corr = <q_proj - s * q_int8, mean>`` (``mean`` from
    :func:`coarse_codes`) folds the average residual contribution into
    the Eq. (20) base score, making the coarse score an unbiased
    estimate of the asymmetric score against the corpus mean.
    """
    qp = prep.q_proj.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(qp), axis=-1), _EPS) / COARSE_QMAX
    qi = jnp.clip(
        jnp.round(qp / s[..., None]), -COARSE_QMAX, COARSE_QMAX
    )
    resid = qp - s[..., None] * qi
    # mean is (d_pad,) from the packed-code width; q_proj is (…, d) with
    # d <= d_pad.  A zero-padded residual column contributes nothing, so
    # slicing mean to the query width is exact.
    return CoarseQueryPrep(
        q_int8=qi.astype(jnp.int8),
        q_scale=s,
        q_corr=resid @ mean.astype(jnp.float32)[: qp.shape[-1]],
    )


# ---------------------------------------------------------------------------
# Asymmetric scoring (Eq. 20)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("rowwise",))
def score_dot(
    model: ASHModel, prep: QueryPrep, payload: ASHPayload,
    *, rowwise: bool = False,
) -> jax.Array:
    """<q, x_i> approximation, Eq. (20), for a batch of queries against
    all payload rows.  Returns (n_queries, n_db).

    rowwise=True swaps the dense matmul for a broadcast-multiply +
    last-axis reduce.  Same values up to reduction order — but the
    reduction order no longer depends on the query-batch size, so row i
    is bit-identical whether scored alone or inside any batch.  Used by
    the gathered (IVF) and shortlist paths, where XLA's batched-matmul
    lowering is batch-size dependent; the dense scan keeps the
    MXU-friendly matmul.
    """
    V = Q.unpack_codes(payload.codes, payload.d, payload.b).astype(
        jnp.float32
    )
    return _score_dot_from_V(prep, payload, V, rowwise)


def _score_dot_from_V(
    prep: QueryPrep, payload: ASHPayload, V: jax.Array, rowwise: bool
) -> jax.Array:
    """Eq. (20) from already-unpacked codes — lets the l2/cos reference
    scorers reuse one decompression instead of unpacking twice."""
    if rowwise:
        dot = jnp.sum(prep.q_proj[..., None, :] * V, axis=-1)
    else:
        dot = prep.q_proj @ V.T  # (m, n) — DOT-PROD term (MXU on TPU)
    scale = payload.scale.astype(jnp.float32)[None, :]
    offset = payload.offset.astype(jnp.float32)[None, :]
    query_compute = prep.ip_q_landmarks[..., payload.cluster]  # (m, n)
    return scale * dot + query_compute + offset


@jax.jit
def score_dot_1bit(
    model: ASHModel, prep: QueryPrep, payload: ASHPayload
) -> jax.Array:
    """1-bit masked-add formulation, Eq. (22). Numerically identical to
    score_dot for b == 1 (tested); mirrors the masked-load kernel."""
    assert payload.b == 1
    d = payload.d
    V = Q.unpack_codes(payload.codes, d, 1)
    Bmat = ((V + 1) // 2).astype(jnp.float32)  # bin() in {0,1}
    res_norm = payload.scale.astype(jnp.float32) * jnp.sqrt(
        jnp.float32(d)
    )  # ||v|| = sqrt(d) for b=1
    inv_sqrt_d = 1.0 / jnp.sqrt(jnp.float32(d))
    masked_add = prep.q_proj @ Bmat.T  # (m, n): sum of q_j where bit set
    sum_q = jnp.sum(prep.q_proj, axis=-1, keepdims=True)  # <q, 1>
    scale = 2.0 * inv_sqrt_d * res_norm[None, :]
    query_compute = (
        -inv_sqrt_d * res_norm[None, :] * sum_q
        + prep.ip_q_landmarks[..., payload.cluster]
    )
    ip_Wmu_2b1 = jnp.sum(
        model.W_landmarks[payload.cluster]
        * (2.0 * Bmat - 1.0),
        axis=-1,
    )
    offset_terms = (
        # <x, mu*> recovered
        payload.offset.astype(jnp.float32)
        + payload.scale.astype(jnp.float32)
        * jnp.sqrt(jnp.float32(d))
        * inv_sqrt_d
        * ip_Wmu_2b1
        + model.landmark_sq_norms[payload.cluster]
        # minus d^-1/2 ||x-mu|| <W mu, 2b-1> - ||mu||^2  (Eq. 22 OFFSET)
        - inv_sqrt_d * res_norm * ip_Wmu_2b1
        - model.landmark_sq_norms[payload.cluster]
    )
    return scale * masked_add + query_compute + offset_terms[None, :]


@functools.partial(jax.jit, static_argnames=("rowwise",))
def score_l2(
    model: ASHModel, prep: QueryPrep, payload: ASHPayload,
    *, rowwise: bool = False,
) -> jax.Array:
    """||q - x_i||^2 approximation (Appendix A), (m, n)."""
    V, _, res_norm, ip_x_mu = recovered_terms(model, payload)
    ip_qx = _score_dot_from_V(prep, payload, V, rowwise)
    mu_sq = model.landmark_sq_norms[payload.cluster]  # (n,)
    ip_q_mu = prep.ip_q_landmarks[..., payload.cluster]  # (m, n)
    q_sq_mu = (
        prep.q_sq_norm[..., None] - 2.0 * ip_q_mu + mu_sq[None, :]
    )  # ||q - mu*||^2
    return (
        q_sq_mu
        + (res_norm**2)[None, :]
        - 2.0 * (ip_qx - ip_x_mu[None, :] - ip_q_mu + mu_sq[None, :])
    )


@functools.partial(jax.jit, static_argnames=("rowwise",))
def score_cosine(
    model: ASHModel, prep: QueryPrep, payload: ASHPayload,
    *, rowwise: bool = False,
) -> jax.Array:
    """cosSim(q, x_i) with ||x_i|| recovered from the headers, (m, n)."""
    V, _, res_norm, ip_x_mu = recovered_terms(model, payload)
    ip_qx = _score_dot_from_V(prep, payload, V, rowwise)
    x_sq = _x_sq_estimate(model, payload.cluster, res_norm, ip_x_mu)
    x_norm = jnp.sqrt(jnp.maximum(x_sq, _EPS))
    q_norm = jnp.sqrt(jnp.maximum(prep.q_sq_norm, _EPS))
    return ip_qx / (q_norm[..., None] * x_norm[None, :])


# ---------------------------------------------------------------------------
# Symmetric scoring (Appendix B) — for graph-index construction
# ---------------------------------------------------------------------------


@jax.jit
def score_symmetric_dot(
    model: ASHModel, pa: ASHPayload, pb: ASHPayload
) -> jax.Array:
    """<x, y> for two encoded sets (C == 1 assumed per Appendix B).

    (n_a, n_b) matrix; Eq. (B.2) with cosSim(quant(Wx~), quant(Wy~))."""
    Va, va_n, ra_n, ip_a_mu = recovered_terms(model, pa)
    Vb, vb_n, rb_n, ip_b_mu = recovered_terms(model, pb)
    cos = (Va @ Vb.T) / jnp.maximum(
        va_n[:, None] * vb_n[None, :], _EPS
    )
    mu_sq = model.landmark_sq_norms[0]
    return (
        ra_n[:, None] * rb_n[None, :] * cos
        + ip_a_mu[:, None]
        + ip_b_mu[None, :]
        - mu_sq
    )


# ---------------------------------------------------------------------------
# Bias correction (Eq. 34)
# ---------------------------------------------------------------------------


def fit_bias(
    model: ASHModel,
    payload: ASHPayload,
    X: jax.Array,
    queries: jax.Array,
    sample: int = 100,
) -> ASHModel:
    """Least-squares (rho, beta) so that rho*<q,x> + beta ~ <q, x^>.

    Per the paper, a ~100-sample regression; the correction divides the
    estimate by rho (and subtracts beta) for L2-faithful scores.
    """
    qs = queries[:sample].astype(jnp.float32)
    xs = X[:sample].astype(jnp.float32)
    sub = jax.tree_util.tree_map(
        lambda a: a[:sample] if a.ndim >= 1 and a.shape[0] == payload.n else a,
        payload,
    )
    prep = prepare_queries(model, qs)
    est = score_dot(model, prep, sub).reshape(-1)
    true = (qs @ xs.T).reshape(-1)
    A = jnp.stack([true, jnp.ones_like(true)], axis=1)
    coef, *_ = jnp.linalg.lstsq(A, est, rcond=None)
    rho, beta = coef[0], coef[1]
    return ASHModel(
        config=model.config,
        W=model.W,
        landmarks=model.landmarks,
        W_landmarks=model.W_landmarks,
        landmark_sq_norms=model.landmark_sq_norms,
        bias_rho=rho,
        bias_beta=beta,
    )


def debias(model: ASHModel, scores: jax.Array) -> jax.Array:
    """Apply the inverse linear correction to estimated dot products."""
    return (scores - model.bias_beta) / jnp.maximum(model.bias_rho, _EPS)
