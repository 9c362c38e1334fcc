"""ASH encoder/decoder and end-to-end training (Sections 2-3).

Encoder  g(x):  c* = nearest landmark; x~ = (x-mu*)/||x-mu*||;
                v = quant_b(W x~);  payload = (codes, SCALE, OFFSET, c*).
Decoder  f(v):  x^ = ||x-mu*|| * ||v||^-1 W^T v + mu*.

The SCALE/OFFSET headers are exactly Eq. (20):
  SCALE  = ||v||^-1 ||x - mu*||
  OFFSET = <x, mu*> - SCALE * <W mu*, v> - ||mu*||^2
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import learning as L
from repro.core import quantization as Q
from repro.core.types import ASHConfig, ASHModel, ASHPayload

_EPS = 1e-12


# ---------------------------------------------------------------------------
# Training (Section 3)
# ---------------------------------------------------------------------------


def train(
    key: jax.Array,
    X: jax.Array,
    config: ASHConfig,
    *,
    train_sample: Optional[int] = None,
    landmark_sample: Optional[int] = None,
    max_iters: int = 25,
    use_newton_schulz: bool = False,
    kmeans_iters: int = 25,
) -> tuple[ASHModel, list[float]]:
    """Learn landmarks + W = R P from data.

    Follows the paper: W is learned on a subsample of ~10*D vectors
    (10x oversampling of the covariance), PCA init for P,
    random-rotation init for R, <= 25 alternation iterations with early
    stopping.  The landmark k-means runs on the full set by default
    (``landmark_sample`` caps it for very large corpora) — landmark
    quality bounds the residual norms every downstream bit quantizes,
    and Lloyd iterations are cheap relative to encoding.
    """
    n, D = X.shape
    d = config.d if config.d > 0 else D
    assert d <= D, f"target dim {d} exceeds input dim {D}"
    config = ASHConfig(
        b=config.b, d=d, n_landmarks=config.n_landmarks,
        store_fp16=config.store_fp16,
    )
    k_sub, k_lm, k_km, k_rot = jax.random.split(key, 4)

    # Subsample BEFORE casting so a capped run on a huge low-precision
    # corpus never materializes a full fp32 copy.
    X32 = None  # full fp32 view, created lazily
    if landmark_sample is not None and landmark_sample < n:
        idx_lm = jax.random.choice(
            k_lm, n, shape=(landmark_sample,), replace=False
        )
        X_lm = X[idx_lm].astype(jnp.float32)
    else:
        X32 = X.astype(jnp.float32)
        X_lm = X32
    centroids, _ = L.kmeans(
        k_km, X_lm, config.n_landmarks, iters=kmeans_iters
    )

    if train_sample is None:
        # 10x covariance oversampling per the paper, but never
        # subsample tiny corpora — the cap exists to bound training
        # cost, and below ~4k rows there is no cost to bound.
        train_sample = min(n, max(10 * D, 4096))
    if train_sample < n:
        idx = jax.random.choice(
            k_sub, n, shape=(train_sample,), replace=False
        )
        Xt = X[idx].astype(jnp.float32)
    else:
        Xt = X32 if X32 is not None else X.astype(jnp.float32)
    x_tilde, _, _ = L.normalized_residuals(Xt, centroids)
    P = L.pca_topd(x_tilde, d)  # (d, D)
    Z = x_tilde @ P.T  # (n_t, d)
    R, history = L.learn_rotation(
        k_rot, Z, config.b,
        max_iters=max_iters, use_newton_schulz=use_newton_schulz,
    )
    W = (R @ P).astype(jnp.float32)  # (d, D), row-orthonormal
    model = ASHModel(
        config=config,
        W=W,
        landmarks=centroids,
        W_landmarks=centroids @ W.T,
        landmark_sq_norms=jnp.sum(centroids * centroids, axis=-1),
        bias_rho=jnp.float32(1.0),
        bias_beta=jnp.float32(0.0),
    )
    return model, history


def random_model(
    key: jax.Array, D: int, config: ASHConfig, X_for_landmarks=None
) -> ASHModel:
    """Data-agnostic ASH: W = random row-orthonormal (JL baseline; also the
    RaBitQ regime when d == D and C == 1)."""
    d = config.d if config.d > 0 else D
    config = ASHConfig(
        b=config.b, d=d, n_landmarks=config.n_landmarks,
        store_fp16=config.store_fp16,
    )
    k_w, k_km = jax.random.split(key)
    g = jax.random.normal(k_w, (D, D), dtype=jnp.float32)
    qmat, _ = jnp.linalg.qr(g)
    W = qmat[:, :d].T  # (d, D) rows orthonormal
    if X_for_landmarks is not None and config.n_landmarks > 1:
        centroids, _ = L.kmeans(
            k_km, X_for_landmarks.astype(jnp.float32), config.n_landmarks
        )
    elif X_for_landmarks is not None:
        centroids = jnp.mean(
            X_for_landmarks.astype(jnp.float32), axis=0, keepdims=True
        )
    else:
        centroids = jnp.zeros((config.n_landmarks, D), jnp.float32)
    return ASHModel(
        config=config,
        W=W,
        landmarks=centroids,
        W_landmarks=centroids @ W.T,
        landmark_sq_norms=jnp.sum(centroids * centroids, axis=-1),
        bias_rho=jnp.float32(1.0),
        bias_beta=jnp.float32(0.0),
    )


# ---------------------------------------------------------------------------
# Encode / decode
# ---------------------------------------------------------------------------


def _encode_rows(model: ASHModel, X: jax.Array, exact: bool) -> ASHPayload:
    """Encode one block of rows (every row independently)."""
    cfg = model.config
    X32 = X.astype(jnp.float32)
    x_tilde, res_norm, assign = L.normalized_residuals(X32, model.landmarks)
    U = x_tilde @ model.W.T  # (n, d)
    V = Q.quant(U, cfg.b, exact=exact)  # (n, d) int32 grid values
    vnorm = jnp.maximum(Q.code_norms(V), _EPS)
    scale = res_norm / vnorm
    ip_x_mu = jnp.sum(X32 * model.landmarks[assign], axis=-1)
    ip_Wmu_v = jnp.sum(
        model.W_landmarks[assign] * V.astype(jnp.float32), axis=-1
    )
    offset = (
        ip_x_mu - scale * ip_Wmu_v - model.landmark_sq_norms[assign]
    )
    # IEEE fp16 (10-bit mantissa), matching Table 1's 16-bit header;
    # bf16 would cost ~3 bits of SCALE/OFFSET precision.  Clip into the
    # fp16-finite range so extreme-norm corpora degrade in precision
    # instead of overflowing to inf (which would poison every score of
    # the affected rows).
    hdr_dtype = jnp.float16 if cfg.store_fp16 else jnp.float32
    if cfg.store_fp16:
        lim = float(jnp.finfo(jnp.float16).max)
        scale = jnp.clip(scale, 0.0, lim)
        offset = jnp.clip(offset, -lim, lim)
    return ASHPayload(
        b=cfg.b,
        d=cfg.d,
        codes=Q.pack_codes(V, cfg.b),
        scale=scale.astype(hdr_dtype),
        offset=offset.astype(hdr_dtype),
        cluster=assign,
    )


# Above this many bytes of temporaries (``_encode_row_bytes`` a row) an
# encode runs in blocks of ``ENCODE_CHUNK_ROWS`` rows inside one device
# program.  A whole 1M x 768 corpus at b=4, d=128 would need 18.6 GB on
# v5e (more than the chip); a block of 32,768 rows needs 0.7 GB there.
# Below it the corpus is encoded whole: the blocked program's code is
# larger, and a loaded program's code stays in device memory.
ENCODE_WHOLE_BYTES = 8 << 30
ENCODE_CHUNK_ROWS = 32_768


def _encode_row_bytes(model: ASHModel) -> int:
    """Upper estimate of an encode's temporaries per row: four copies of
    the exact quantizer's d * (2^(b-1) - 1) sort keys, the landmark
    distances and two input-wide rows, 4 bytes each (compiled for v5e:
    2.3 KB a row at D=128, b=2, d=64; 18.6 KB at D=768, b=4, d=128)."""
    d, D = model.W.shape
    keys = d * (2 ** (model.config.b - 1) - 1)
    return 4 * (4 * keys + model.landmarks.shape[0] + 2 * D)


def encode_chunks(model: ASHModel, n: int) -> int:
    """Row blocks :func:`encode` runs for ``n`` rows."""
    if n * _encode_row_bytes(model) <= ENCODE_WHOLE_BYTES:
        return 1
    return -(-n // ENCODE_CHUNK_ROWS)


def encode(model: ASHModel, X: jax.Array, exact: bool = True) -> ASHPayload:
    """Encode database vectors into the ASH payload (Table 1).

    Rows are encoded independently; a corpus whose encode would exceed
    ``ENCODE_WHOLE_BYTES`` of temporaries runs in blocks of
    ``ENCODE_CHUNK_ROWS`` rows inside one device program, the last
    block being the final ``ENCODE_CHUNK_ROWS`` rows (it overlaps the
    one before and rewrites the same values).  The payload equals a
    single-block encode.
    """
    n = X.shape[0]
    chunk = n if encode_chunks(model, n) == 1 else ENCODE_CHUNK_ROWS
    return _encode_blocks(model, X, exact, chunk)


@functools.partial(jax.jit, static_argnames=("exact", "chunk"))
def _encode_blocks(model, X, exact, chunk) -> ASHPayload:
    n = X.shape[0]
    if n <= chunk:
        return _encode_rows(model, X, exact)

    def block(i, out):
        lo = jnp.minimum(i * chunk, n - chunk)
        part = _encode_rows(
            model, jax.lax.dynamic_slice_in_dim(X, lo, chunk), exact
        )
        return jax.tree_util.tree_map(
            lambda o, p: jax.lax.dynamic_update_slice_in_dim(o, p, lo, 0),
            out, part,
        )

    shapes = jax.eval_shape(
        functools.partial(_encode_rows, exact=exact), model, X[:chunk]
    )
    out = jax.tree_util.tree_map(
        lambda s: jnp.zeros((n,) + s.shape[1:], s.dtype), shapes
    )
    return jax.lax.fori_loop(0, -(-n // chunk), block, out)


@jax.jit
def decode(model: ASHModel, payload: ASHPayload) -> jax.Array:
    """Reconstruct x^ = ||x-mu*|| ||v||^-1 W^T v + mu* from the payload.

    ||x-mu*|| is recovered as SCALE * ||v||; this is the full (lossy)
    inverse of encode.
    """
    V = Q.unpack_codes(payload.codes, payload.d, payload.b).astype(
        jnp.float32
    )
    x_tilde_hat = (V / jnp.maximum(Q.code_norms(V), _EPS)[:, None]) @ model.W
    res_norm = payload.scale.astype(jnp.float32) * Q.code_norms(V)
    return res_norm[:, None] * x_tilde_hat + model.landmarks[payload.cluster]


def reconstruction_error(model: ASHModel, X: jax.Array) -> jax.Array:
    """Mean squared reconstruction error of the *normalized residuals*
    (Eq. 5/14) — the quantity the learning minimizes."""
    X32 = X.astype(jnp.float32)
    x_tilde, _, _ = L.normalized_residuals(X32, model.landmarks)
    U = x_tilde @ model.W.T
    V = Q.quant(U, model.config.b).astype(jnp.float32)
    vnorm = jnp.maximum(Q.code_norms(V), _EPS)
    x_hat = (V / vnorm[:, None]) @ model.W
    return jnp.mean(jnp.sum((x_tilde - x_hat) ** 2, axis=-1))
