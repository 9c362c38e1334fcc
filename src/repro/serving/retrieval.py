"""ASH-compressed candidate retrieval — the paper's technique as a
first-class serving feature for the recsys architectures.

The item-embedding table (SASRec) or candidate set is encoded ONCE
offline; per request the user-state vector scores all candidates through
the fused asymmetric kernel (Pallas on TPU, oracle on CPU), followed by
top-k.  Payload is 32D/(bd)x smaller than the fp32 table, and the
scoring matmul reads packed codes only.

Requests route through the micro-batching :class:`QueryEngine`
(``repro.serving.engine``): one engine per index (cached here), so
request shapes collapse onto the engine's bucketed jit traces.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from repro.core import ASHConfig
from repro.index import AshIndex
from repro.serving.engine import QueryEngine


def build_index(
    key: jax.Array,
    embeddings: jax.Array,  # (n_items, e)
    *,
    bits: int = 4,
    reduce: int = 1,
    n_landmarks: int = 16,
    learned: bool = True,
    backend: str = "flat",
    metric: str = "dot",
) -> AshIndex:
    """Compress a candidate catalog into a searchable ``AshIndex``."""
    e = embeddings.shape[1]
    cfg = ASHConfig(b=bits, d=e // reduce, n_landmarks=n_landmarks)
    return AshIndex.build(
        key, embeddings, cfg, backend=backend, metric=metric,
        learned=learned,
    )


def engine_for(index: AshIndex, **overrides) -> QueryEngine:
    """The (cached) serving engine fronting ``index``.  Overrides only
    apply on first construction for a given index.

    Cached on the index instance itself so the engine (and its prep
    cache) lives exactly as long as the index it fronts.  The default
    bucket ladder is power-of-two dense: synchronous one-shot callers
    with power-of-two batch sizes (the common recsys request shapes)
    pad by at most 2x and usually not at all.
    """
    engine = getattr(index, "_serving_engine", None)
    if engine is None:
        overrides.setdefault("batch_buckets", (8, 16, 32, 64, 128))
        engine = QueryEngine(index, **overrides)
        index._serving_engine = engine
    return engine


def serve_topk(
    index: AshIndex,
    user_vecs: jax.Array,  # (B, e)
    k: int = 10,
    use_pallas: Optional[bool] = None,  # auto: kernel on TPU, oracle on CPU
    *,
    engine: Optional[QueryEngine] = None,
) -> "tuple[np.ndarray, np.ndarray]":
    """Top-k ASH MIPS through the engine's fused scoring path.
    Returns host-side (numpy) scores and ids, each (B, k)."""
    eng = engine if engine is not None else engine_for(index)
    return eng.search(user_vecs, k=k, use_pallas=use_pallas)


def sasrec_retrieve(
    params: dict,
    seq: jax.Array,
    index: AshIndex,
    cfg,
    k: int = 10,
    *,
    engine: Optional[QueryEngine] = None,
) -> "tuple[np.ndarray, np.ndarray]":
    """End-to-end SASRec next-item retrieval over the compressed
    catalog: user sequences -> user state -> engine-batched ASH MIPS."""
    from repro.models import sasrec as SR

    u = SR.user_state(params, seq, cfg)
    return serve_topk(index, u, k=k, engine=engine)
