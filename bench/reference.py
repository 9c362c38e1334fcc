"""The plain reference that decides ``correct``, and its control.

Straightforward float32 brute force over the corpus the benchmark
generated from the seed: it imports nothing of the program and reads
nothing the program made.  What it checks of a served answer:

* ``score_gap``: each served score must be the exact metric score of
  the served id for that request's own query, computed over the row as
  the configuration stores it for rerank (bfloat16) in float32
  arithmetic.  The gap is taken against the scale of the terms summed
  (||q||^2 + ||x||^2 for L2, 1 for cosine), and the widest over the
  sample is compared.  It covers the exact rerank and the engine's
  scatter of every batch back to its own requests.
* ``recall_loss``: 1 - recall@10 of the served ids against the float32
  brute-force top-10, held to the configuration's ``limits``, which
  lie between sound runs and the control and well inside the recall
  the configuration guarantees (``recall_at_10_min``).  It covers query
  prep, the scan or probe and gather kernels and the strip merge, which
  pick the shortlist.
* ``bad_rows``: served rows whose ids are out of range, repeated, or
  whose scores are not descending (exact: limit 0).

The control is this reference put in the program's place one precision
below the configuration's: every score computed from bfloat16 operands
(queries and rows), float32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_EPS = 1e-12


def _scores(Q, X, metric, precision):
    """(m, n) higher-is-better scores of float32 queries against rows."""
    ip = jnp.matmul(Q, X.T, precision=precision)
    if metric == "l2":
        return -(jnp.sum(Q * Q, -1)[:, None] - 2.0 * ip
                 + jnp.sum(X * X, -1)[None, :])
    if metric == "cos":
        qn = jnp.maximum(jnp.linalg.norm(Q, axis=-1), _EPS)
        xn = jnp.maximum(jnp.linalg.norm(X, axis=-1), _EPS)
        return ip / (qn[:, None] * xn[None, :])
    if metric == "dot":
        return ip
    raise ValueError(metric)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _topk_f32(Q, X, *, k, metric):
    return jax.lax.top_k(_scores(Q, X, metric, "highest"), k)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _topk_bf16(Q, X, *, k, metric):
    """The control: bfloat16 operands, float32 accumulation."""
    Qb = Q.astype(jnp.bfloat16).astype(jnp.float32)
    Xb = X.astype(jnp.bfloat16).astype(jnp.float32)
    ip = jnp.matmul(Q.astype(jnp.bfloat16), X.astype(jnp.bfloat16).T,
                    preferred_element_type=jnp.float32)
    if metric == "l2":
        s = -(jnp.sum(Qb * Qb, -1)[:, None] - 2.0 * ip
              + jnp.sum(Xb * Xb, -1)[None, :])
    elif metric == "cos":
        qn = jnp.maximum(jnp.linalg.norm(Qb, axis=-1), _EPS)
        xn = jnp.maximum(jnp.linalg.norm(Xb, axis=-1), _EPS)
        s = ip / (qn[:, None] * xn[None, :])
    else:
        s = ip
    return jax.lax.top_k(s, k)


def topk(Q: np.ndarray, X, k: int, metric: str, *, control=False,
         block: int = 256):
    """Exact top-k (scores, ids) of every query row, in blocks of
    ``block`` queries so the (block, n) score matrix fits."""
    fn = _topk_bf16 if control else _topk_f32
    m = len(Q)
    pad = (-m) % block
    Qp = np.concatenate([Q, np.repeat(Q[:1], pad, 0)]) if pad else Q
    out_s, out_i = [], []
    for lo in range(0, len(Qp), block):
        s, i = fn(jnp.asarray(Qp[lo:lo + block]), X, k=k, metric=metric)
        out_s.append(np.asarray(s))
        out_i.append(np.asarray(i))
    return np.concatenate(out_s)[:m], np.concatenate(out_i)[:m]


@functools.partial(jax.jit, static_argnames=("metric",))
def _pair_scores(Q, C, metric):
    """Exact scores of candidate rows ``C`` (m, k, D) for their queries
    ``Q`` (m, D), float32 arithmetic; returns (scores, scale)."""
    ip = jnp.sum(Q[:, None, :] * C, axis=-1)
    qq = jnp.sum(Q * Q, axis=-1)[:, None]
    cc = jnp.sum(C * C, axis=-1)
    if metric == "l2":
        return -(qq - 2.0 * ip + cc), qq + cc
    if metric == "cos":
        qn = jnp.maximum(jnp.sqrt(qq), _EPS)
        cn = jnp.maximum(jnp.sqrt(cc), _EPS)
        return ip / (qn * cn), jnp.ones_like(ip)
    if metric == "dot":
        return ip, jnp.sqrt(qq * cc)
    raise ValueError(metric)


def stored_rows(X):
    """The rows as the configuration stores them for rerank."""
    return X.astype(jnp.bfloat16)


def pair_scores(Q: np.ndarray, ids: np.ndarray, X_stored, metric: str,
                block: int = 1024):
    """Reference scores (and their scales) of served ``ids`` (m, k)."""
    n = X_stored.shape[0]
    out_s, out_c = [], []
    for lo in range(0, len(Q), block):
        sel = np.clip(ids[lo:lo + block], 0, n - 1)
        C = X_stored[jnp.asarray(sel)].astype(jnp.float32)
        s, c = _pair_scores(jnp.asarray(Q[lo:lo + block]), C, metric)
        out_s.append(np.asarray(s))
        out_c.append(np.asarray(c))
    return np.concatenate(out_s), np.concatenate(out_c)


def bad_rows(scores: np.ndarray, ids: np.ndarray, n: int, k: int) -> int:
    """Rows that are not k distinct valid ids with descending scores."""
    if scores.shape != ids.shape or ids.shape[1] != k:
        return int(len(ids))
    bad = (ids < 0) | (ids >= n)
    bad = bad.any(1)
    srt = np.sort(ids, axis=1)
    bad |= (np.diff(srt, axis=1) == 0).any(1)
    bad |= ~np.isfinite(scores).all(1)
    bad |= (np.diff(scores, axis=1) > 0).any(1)
    return int(bad.sum())


def compare(Q, served_scores, served_ids, truth_ids, X, metric, k):
    """The numbers ``correct`` is decided on, for served answers of the
    query rows ``Q``; ``truth_ids`` is the float32 top-k."""
    n = X.shape[0]
    ref_s, scale = pair_scores(Q, served_ids, stored_rows(X), metric)
    gap = np.abs(served_scores.astype(np.float64) - ref_s) / np.maximum(
        scale, _EPS)
    hits = (served_ids[:, :, None] == truth_ids[:, None, :k]).any(2)
    return dict(
        score_gap=float(np.nan_to_num(gap, nan=np.inf).max()),
        recall_loss=float(1.0 - hits.sum(1).mean() / k),
        bad_rows=bad_rows(served_scores, served_ids, n, k),
    )
