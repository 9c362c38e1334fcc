"""Flat (exhaustive-scan) ASH index with optional exact re-ranking.

Entry point is ``repro.index.AshIndex`` with ``backend="flat"``; the
``_search_prepped`` path lets the serving engine reuse cached
``QueryPrep`` projections.  Metric dispatch and the rerank pipeline live
in ``repro.index.common`` (shared with the IVF and sharded backends).

Scan strategy: every metric routes through the fused kernel family by
default (``use_pallas=None`` → Pallas on TPU, the identical-semantics
jnp oracle on CPU; ``use_pallas=False`` forces the pure-jnp reference
scorers).  Whenever the requested top-k/shortlist fits the fused
selection budget (``common.fused_topk_limit()``), the scan and the
selection fuse — on TPU the (m, n) score matrix never reaches HBM.
The l2/cos epilogues read the encode-time ``ASHStats`` carried on the
index (built at build/add, persisted by save/load).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ash as A
from repro.core import scoring as S
from repro.core.types import (
    ASHConfig, ASHModel, ASHPayload, ASHStats, CoarseCodes, QueryPrep,
    pytree_dataclass,
)
from repro.index import common as C


@pytree_dataclass(meta_fields=("metric", "next_id"))
class FlatIndex:
    metric: str  # "dot" | "l2" | "cos"
    model: ASHModel
    payload: ASHPayload
    # Optional raw vectors for exact re-ranking of a shortlist (kept in
    # bf16 to bound memory; None for pure-compressed deployments).
    raw: Optional[jax.Array]
    # Encode-time row statistics consumed by the fused l2/cos epilogues
    # (None → rebuilt per scoring call, decompressing the database).
    stats: Optional[ASHStats] = None
    # User-facing id of each payload row; None = identity (row == id),
    # which holds until a compaction retires tombstoned ids.  Always
    # strictly increasing (appends continue past every retired id).
    ids: Optional[jax.Array] = None
    # Row-validity bitmap: False rows are tombstoned (deleted) and can
    # never surface in results (the ScanPlan threads this into the
    # kernels' runtime mask operand).  None = all rows live.
    live: Optional[jax.Array] = None
    # Meta: id the next added row receives (None = derived; see
    # ``common.effective_next_id``).  Only set once mutations happen.
    next_id: Optional[int] = None
    # Dequantized-code cache for the symmetric int8 coarse first pass
    # (``search(coarse="int8")``); derived from ``payload`` — rebuilt
    # at build/add/compact, never persisted (save/load reconstructs).
    # None → ``execute_plan`` rebuilds per call (decompressing).
    coarse: Optional[CoarseCodes] = None


def _build(
    key: jax.Array,
    X: jax.Array,
    config: ASHConfig,
    *,
    metric: str = "dot",
    learned: bool = True,
    keep_raw: bool = False,
    model: Optional[ASHModel] = None,
    **train_kw,
) -> FlatIndex:
    C.validate_metric(metric)
    model, payload, raw = C.train_and_encode(
        key, X, config, model=model, learned=learned, keep_raw=keep_raw,
        **train_kw,
    )
    return C.build_stats(lambda: FlatIndex(
        metric=metric, model=model, payload=payload, raw=raw,
        stats=S.payload_stats(model, payload),
        coarse=S.coarse_codes(payload),
    ))


@functools.partial(
    jax.jit,
    static_argnames=("k", "rerank", "use_pallas", "coarse", "shortlist"),
)
def _search_prepped(
    index: FlatIndex,
    prep: QueryPrep,
    k: int = 10,
    rerank: int = 0,
    use_pallas: Optional[bool] = None,
    coarse: Optional[str] = None,
    shortlist: Optional[int] = None,
) -> tuple[jax.Array, jax.Array]:
    """Top-k search from precomputed query projections.

    Returns (scores, indices), each (m, k).  rerank > 0: retrieve a
    shortlist of that size by ASH scores and re-rank it with exact
    (bf16) metric-aware scores (requires raw vectors).

    The shortlist/top-k selection fuses into the scan kernel whenever
    its size fits ``common.fused_topk_limit()``; the fallback
    materializes scores and runs ``lax.top_k`` — both return identical
    results, so the routing choice is invisible to callers (the ladder
    itself lives in ``common.execute_plan``, shared with the IVF and
    sharded backends).

    coarse="int8" runs the symmetric int8 first-pass scan over the
    persisted ``index.coarse`` value cache, keeping the top
    ``shortlist`` (default ``common.default_shortlist()``) rows per
    query for the asymmetric refine (then the usual exact rerank).
    """
    plan = C.ScanPlan(
        metric=index.metric, k=k, rerank=rerank, row_valid=index.live,
        ids=index.ids, use_pallas=use_pallas,
        coarse=coarse, shortlist=shortlist,
    )
    return C.execute_plan(
        index.model, prep, index.payload, plan,
        stats=index.stats, raw=index.raw, coarse_cache=index.coarse,
    )


def _search(
    index: FlatIndex,
    queries: jax.Array,
    k: int = 10,
    rerank: int = 0,
    use_pallas: Optional[bool] = None,
    coarse: Optional[str] = None,
    shortlist: Optional[int] = None,
) -> tuple[jax.Array, jax.Array]:
    """Top-k search; composition of ``prepare_queries`` and
    :func:`_search_prepped` so the batched engine path and the direct
    path share the exact same compiled arithmetic (bit-identical)."""
    prep = S.prepare_queries(index.model, queries)
    return _search_prepped(
        index, prep, k=k, rerank=rerank, use_pallas=use_pallas,
        coarse=coarse, shortlist=shortlist,
    )


def _add(index: FlatIndex, X_new: jax.Array) -> FlatIndex:
    """Encode new rows under the existing model and append them.  New
    rows get the next ``n_new`` user ids (see ``effective_next_id``)."""
    payload_new = A.encode(index.model, X_new)
    n_new = payload_new.n
    nid = C.effective_next_id(index.next_id, index.ids, index.payload.n)
    ids = index.ids
    if ids is not None:
        ids = jnp.concatenate(
            [ids, nid + jnp.arange(n_new, dtype=jnp.int32)]
        )
    live = index.live
    if live is not None:
        live = jnp.concatenate([live, jnp.ones((n_new,), bool)])
    raw = index.raw
    if raw is not None:
        raw = jnp.concatenate(
            [raw, X_new.astype(jnp.bfloat16)], axis=0
        )
    payload = C.concat_payloads(index.payload, payload_new)
    return FlatIndex(
        metric=index.metric,
        model=index.model,
        payload=payload,
        raw=raw,
        stats=C.concat_stats(
            index.stats, S.payload_stats(index.model, payload_new)
        ),
        ids=ids,
        live=live,
        next_id=None if index.next_id is None else nid + n_new,
        # full rebuild, not an incremental concat: CoarseCodes.mean
        # spans ALL rows, and an incremental mean update would drift
        # from a fresh build's (breaking add == rebuild bit-identity)
        coarse=None if index.coarse is None else S.coarse_codes(payload),
    )


def _delete(index: FlatIndex, del_ids) -> tuple[FlatIndex, int]:
    """Tombstone rows by user id: (index, rows newly removed).  Rows
    stay in the payload (scored ``-inf`` via the kernel mask operand)
    until :func:`_compact` evicts them."""
    new_live, removed = C.mark_deleted(
        index.ids, index.live, del_ids, index.payload.n
    )
    if removed == 0:
        return index, 0
    return dataclasses.replace(index, live=jnp.asarray(new_live)), removed


def _compact(index: FlatIndex) -> FlatIndex:
    """Rewrite codes/stats/raw/ids to evict tombstoned rows.  Search
    afterwards is bit-identical to a fresh build over the survivors
    (same model): encode/stats are row-independent and survivors keep
    their payload rows and relative order, so values and tie order
    match (survivor ids map monotonically onto the rebuild's rows)."""
    if index.live is None:
        return index
    live_np = np.asarray(index.live).astype(bool)
    if live_np.all():
        return dataclasses.replace(index, live=None)
    if not live_np.any():
        raise ValueError(
            "compact() would evict every row; an empty index cannot "
            "be searched — keep at least one live row or rebuild"
        )
    nid = C.effective_next_id(index.next_id, index.ids, index.payload.n)
    keep = jnp.asarray(np.nonzero(live_np)[0].astype(np.int32))
    ids = keep if index.ids is None else index.ids[keep]
    payload = C.gather_payload(index.payload, keep)
    return FlatIndex(
        metric=index.metric,
        model=index.model,
        payload=payload,
        raw=None if index.raw is None else index.raw[keep],
        stats=C.take_stats(index.stats, keep),
        ids=ids.astype(jnp.int32),
        live=None,
        next_id=nid,
        coarse=None if index.coarse is None else S.coarse_codes(payload),
    )
