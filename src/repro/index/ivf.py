"""Inverted-file (IVF) ASH index.

The ASH landmarks ARE the IVF centroids (Section 2 of the paper): the
coarse quantizer used for residual centering doubles as the routing
structure, so OFFSET/SCALE come for free per list.

Rows are sorted by cluster, so each inverted list is a contiguous row
range of the payload; ``invlists`` also stores them padded to the
longest list (static shapes).  A partial probe hands each query's
``nprobe`` lists to the gather kernel as row ranges: it reads their
blocks in place, scores them with the asymmetric estimator, masks what
lies outside a list, and top-k's.
Queries with fewer than k valid candidates pad results with score
``-inf`` / id ``-1`` (never aliased to row 0).

Entry point is ``repro.index.AshIndex`` with ``backend="ivf"``; the
``_search_prepped`` path lets the serving engine reuse cached
``QueryPrep`` projections.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import ash as A
from repro.core import scoring as S
from repro.core.types import (
    ASHConfig, ASHModel, ASHPayload, ASHStats, CoarseCodes, QueryPrep,
    pytree_dataclass,
)
from repro.index import common as C
from repro.kernels.ash_score import ProbedLists

NEG_INF = C.NEG_INF


@pytree_dataclass(meta_fields=("metric", "max_list_len", "next_id"))
class IVFIndex:
    metric: str
    max_list_len: int
    model: ASHModel  # landmarks == IVF centroids (nlist, D)
    payload: ASHPayload  # rows sorted by list
    ids: jax.Array  # (n,) original ids, sorted by list
    invlists: jax.Array  # (nlist, max_list_len) int32 row indices, -1 pad
    raw: Optional[jax.Array]  # optional bf16 vectors (sorted) for rerank
    # Encode-time row statistics for the fused l2/cos epilogues on the
    # full-probe (dense-scan) path; row-aligned with ``payload``.
    stats: Optional[ASHStats] = None
    # Row-validity bitmap, row-aligned with ``payload``: False rows are
    # tombstoned (deleted).  Dense full-probe scans mask them via the
    # kernel mask operand; partial probes mask them in the gather
    # kernel (the oracle drops them from the candidate rows).  None =
    # all live.
    live: Optional[jax.Array] = None
    # Meta: id the next added row receives (see effective_next_id).
    next_id: Optional[int] = None
    # Dequantized-code cache for the symmetric int8 coarse first pass,
    # row-aligned with the (list-sorted) ``payload``; derived, rebuilt
    # by ``_assemble`` on every mutation, never persisted.
    coarse: Optional[CoarseCodes] = None


def list_geometry(cluster, nlist: int):
    """Contiguous-list geometry of a (cluster-sorted or unsorted)
    cluster column: ``(counts, starts)``, each (nlist,) int64.  In the
    cluster-sorted row order list ``c`` occupies the contiguous global
    row range ``[starts[c], starts[c] + counts[c])`` — the invariant
    the padded inverted lists AND the host-tiered paged gather
    (``common.plan_paged_probe``) are built on."""
    import numpy as np

    counts = np.bincount(
        np.asarray(cluster), minlength=nlist
    ).astype(np.int64)
    starts = np.concatenate(
        [[0], np.cumsum(counts)[:-1]]
    ).astype(np.int64)
    return counts, starts


def build_invlists(counts, starts, max_len: int):
    """Padded inverted lists from the contiguous geometry: (nlist,
    max_len) int32 global rows, ``-1`` beyond each list's count."""
    import numpy as np

    t = np.arange(max_len, dtype=np.int64)
    rows = starts[:, None] + t[None, :]
    return np.where(
        t[None, :] < counts[:, None], rows, -1
    ).astype(np.int32)


def _assemble(
    metric: str,
    model: ASHModel,
    payload: ASHPayload,
    ids: jax.Array,
    raw: Optional[jax.Array],
    live: Optional[jax.Array] = None,
    next_id: Optional[int] = None,
) -> IVFIndex:
    """Sort rows by cluster and build the padded inverted lists.

    payload/ids/raw/live are row-aligned in any order; ``ids`` holds
    the original (user-facing) id of each row.  Used by build,
    incremental add and compaction — a stable sort keeps add() results
    identical to a from-scratch assembly over the concatenated rows.
    """
    import numpy as np

    cluster = np.asarray(payload.cluster)
    # the -1 pad sentinel (distributed.pad_to_multiple) must never be
    # gathered into inverted lists — it aliases under wrapped indexing
    if cluster.size and cluster.min() < 0:
        raise ValueError(
            "payload contains pad-sentinel cluster ids (-1); assemble "
            "inverted lists from an unpadded payload"
        )
    nlist = model.landmarks.shape[0]
    order = np.argsort(cluster, kind="stable")
    counts, starts = list_geometry(cluster, nlist)
    max_len = int(counts.max())
    invlists = build_invlists(counts, starts, max_len)

    perm = jnp.asarray(order)
    sorted_payload = C.permute_payload(payload, perm)
    return IVFIndex(
        metric=metric,
        max_list_len=max_len,
        model=model,
        payload=sorted_payload,
        ids=jnp.asarray(ids)[perm].astype(jnp.int32),
        invlists=jnp.asarray(invlists),
        raw=None if raw is None else raw[perm],
        stats=S.payload_stats(model, sorted_payload),
        live=None if live is None else jnp.asarray(live)[perm],
        next_id=next_id,
        coarse=S.coarse_codes(sorted_payload),
    )


def _build(
    key: jax.Array,
    X: jax.Array,
    config: ASHConfig,
    *,
    metric: str = "dot",
    keep_raw: bool = False,
    model: Optional[ASHModel] = None,
    **train_kw,
) -> IVFIndex:
    """nlist = config.n_landmarks."""
    C.validate_metric(metric)
    model, payload, raw = C.train_and_encode(
        key, X, config, model=model, keep_raw=keep_raw, **train_kw,
    )
    ids = jnp.arange(payload.n, dtype=jnp.int32)
    return C.build_stats(
        lambda: _assemble(metric, model, payload, ids, raw)
    )


def _add(index: IVFIndex, X_new: jax.Array) -> IVFIndex:
    """Encode new rows under the existing model and merge them into the
    inverted lists.  New rows get the next ``n_new`` user ids (past any
    retired ones; see ``effective_next_id``)."""
    payload_new = A.encode(index.model, X_new)
    n_new = payload_new.n
    nid = C.effective_next_id(index.next_id, index.ids, index.payload.n)
    ids = jnp.concatenate(
        [index.ids, nid + jnp.arange(n_new, dtype=jnp.int32)]
    )
    live = index.live
    if live is not None:
        live = jnp.concatenate([live, jnp.ones((n_new,), bool)])
    raw = index.raw
    if raw is not None:
        raw = jnp.concatenate(
            [raw, X_new.astype(jnp.bfloat16)], axis=0
        )
    return _assemble(
        index.metric,
        index.model,
        C.concat_payloads(index.payload, payload_new),
        ids,
        raw,
        live=live,
        next_id=None if index.next_id is None else nid + n_new,
    )


def _delete(index: IVFIndex, del_ids) -> tuple[IVFIndex, int]:
    """Tombstone rows by user id: (index, rows newly removed).  The
    inverted lists are untouched — tombstoned rows are dropped from
    gathered candidate lists at search time and masked in full scans —
    so delete never pays the re-sort; :func:`_compact` does."""
    import dataclasses

    new_live, removed = C.mark_deleted(
        index.ids, index.live, del_ids, index.payload.n
    )
    if removed == 0:
        return index, 0
    return dataclasses.replace(index, live=jnp.asarray(new_live)), removed


def _compact(index: IVFIndex) -> IVFIndex:
    """Evict tombstoned rows and rebuild the inverted lists.  Survivors
    keep their relative (stable cluster-sorted) order, so search after
    compaction is bit-identical to a fresh build over the surviving
    rows under the same model."""
    import dataclasses

    import numpy as np

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
    return _assemble(
        index.metric,
        index.model,
        C.gather_payload(index.payload, keep),
        index.ids[keep],
        None if index.raw is None else index.raw[keep],
        next_id=nid,
    )


@jax.jit
def _pad_single(prep: QueryPrep) -> QueryPrep:
    """m=1 -> m=2 by appending an all-zero query row.

    XLA lowers the degenerate single-query batch differently from
    every m >= 2 (last-ulp score drift), which would break the serving
    engine's bit-identity guarantee between per-request and bucketed
    calls.  The pad runs as its OWN jit program — never fused into the
    scoring trace — so the padded call dispatches the exact m=2
    executable real two-query batches use; padding inside the scoring
    trace would compile a third program ("pad then score") that XLA
    again fuses its own way.  (Concatenation is pure data movement, so
    a jitted pad emits bit-identical arrays to an eager one at a
    fraction of the dispatch cost.)"""
    return jax.tree_util.tree_map(
        lambda a: jnp.concatenate([a, jnp.zeros_like(a)], axis=0), prep
    )


def _search_prepped(
    index: IVFIndex,
    prep: QueryPrep,
    k: int = 10,
    nprobe: int = 8,
    rerank: int = 0,
    coarse: Optional[str] = None,
    shortlist: Optional[int] = None,
) -> tuple[jax.Array, jax.Array]:
    """Top-k from precomputed query projections: (scores, ids), (m,k).

    nprobe >= nlist probes every list — coarse routing degenerates to
    an exhaustive scan, so the query skips the gather entirely and runs
    the flat fused-kernel scan over the (list-sorted) payload, mapping
    rows back through ``index.ids``.  Partial probes lower to a
    gathered ``ScanPlan`` served by the masked-gather kernel family
    (batch-shape-invariant rowwise oracle on CPU).  ``coarse="int8"``
    inserts the symmetric int8 first pass on either route (see
    ``common.ScanPlan``)."""
    if nprobe >= index.invlists.shape[0]:
        return _full_scan(
            index, prep, k, rerank, coarse=coarse, shortlist=shortlist
        )
    if prep.q.shape[0] == 1:
        s, i = _score_gathered(
            index, _pad_single(prep), k, nprobe, rerank,
            coarse=coarse, shortlist=shortlist,
        )
        return s[:1], i[:1]
    return _score_gathered(
        index, prep, k, nprobe, rerank,
        coarse=coarse, shortlist=shortlist,
    )


@functools.partial(
    jax.jit,
    static_argnames=("k", "rerank", "use_pallas", "coarse", "shortlist"),
)
def _full_scan(
    index: IVFIndex,
    prep: QueryPrep,
    k: int,
    rerank: int,
    use_pallas: Optional[bool] = None,
    coarse: Optional[str] = None,
    shortlist: Optional[int] = None,
) -> tuple[jax.Array, jax.Array]:
    """Exhaustive fused-kernel scan (the nprobe == nlist case): the
    flat backend's routing ladder (a dense ``common.ScanPlan``) with
    payload rows mapped to user ids via ``index.ids``."""
    plan = C.ScanPlan(
        metric=index.metric, k=k, rerank=rerank, row_valid=index.live,
        ids=index.ids, use_pallas=use_pallas,
        coarse=coarse, shortlist=shortlist,
    )
    return C.execute_plan(
        index.model, prep, index.payload, plan,
        stats=index.stats, raw=index.raw, coarse_cache=index.coarse,
    )


def _probe_lists(
    index: IVFIndex, prep: QueryPrep, nprobe: int
) -> jax.Array:
    """Coarse assignment: the ``nprobe`` nearest centroids per query,
    best-first.  Nearest by L2 == max <q,mu> - ||mu||^2/2, computed
    from the prep's landmark inner products (already materialized for
    residual centering), so exposing it costs one top-k."""
    with jax.named_scope("ash.probe"):
        coarse = (
            prep.ip_q_landmarks
            - 0.5 * index.model.landmark_sq_norms[None, :]
        )
        return jax.lax.top_k(coarse, nprobe)[1]  # (m, nprobe)


@functools.partial(
    jax.jit,
    static_argnames=("k", "nprobe", "rerank", "coarse", "shortlist"),
)
def _score_gathered(
    index: IVFIndex,
    prep: QueryPrep,
    k: int,
    nprobe: int,
    rerank: int,
    coarse: Optional[str] = None,
    shortlist: Optional[int] = None,
) -> tuple[jax.Array, jax.Array]:
    """Partial probes: coarse-route, then score the probed lists."""
    probe = _probe_lists(index, prep, nprobe)
    return _score_probed_impl(
        index, prep, probe, k, rerank,
        coarse=coarse, shortlist=shortlist,
    )


def _search_probed(
    index: IVFIndex,
    prep: QueryPrep,
    probe: jax.Array,
    k: int = 10,
    rerank: int = 0,
    coarse: Optional[str] = None,
    shortlist: Optional[int] = None,
) -> tuple[jax.Array, jax.Array]:
    """Top-k over an explicit probed-list set (budgeted gather).

    ``probe`` is (m, nprobe) int32 list ids per query — callers that
    already hold the coarse assignment (the serving engine's
    candidate-row cost model computes it host-side to plan row
    budgets) skip the in-jit coarse top-k and land on the same
    gathered ``ScanPlan`` lowering as ``_search_prepped``.
    Bit-identical to it when ``probe`` equals the coarse assignment."""
    if prep.q.shape[0] == 1:
        # mirror _search_prepped's eager m=1 -> 2 padding (see
        # _pad_single); the pad row's probe must be the zero-query's
        # coarse assignment — not an arbitrary filler — for the padded
        # batch to match _search_prepped's bit-for-bit
        prep = _pad_single(prep)
        pad_probe = _probe_lists(index, prep, probe.shape[1])[1:]
        probe = jnp.concatenate([probe, pad_probe], axis=0)
        s, i = _score_probed(
            index, prep, probe, k, rerank,
            coarse=coarse, shortlist=shortlist,
        )
        return s[:1], i[:1]
    return _score_probed(
        index, prep, probe, k, rerank,
        coarse=coarse, shortlist=shortlist,
    )


@functools.partial(
    jax.jit, static_argnames=("k", "rerank", "coarse", "shortlist")
)
def _score_probed(
    index: IVFIndex,
    prep: QueryPrep,
    probe: jax.Array,
    k: int = 10,
    rerank: int = 0,
    coarse: Optional[str] = None,
    shortlist: Optional[int] = None,
) -> tuple[jax.Array, jax.Array]:
    """Jit entry over :func:`_score_probed_impl` for explicit probes."""
    return _score_probed_impl(
        index, prep, probe, k, rerank,
        coarse=coarse, shortlist=shortlist,
    )


def probed_lists(index: IVFIndex, probe: jax.Array) -> ProbedLists:
    """The (m, nprobe) probed lists as payload row ranges.  Lists are
    contiguous in the cluster-sorted payload, so a list's row of
    ``invlists`` reads ``start, start + 1, ...`` then ``-1`` padding:
    its first entry is the start and its non-pad entries count its
    rows."""
    inv = index.invlists
    lens = jnp.sum(inv >= 0, axis=1, dtype=jnp.int32)
    return ProbedLists(
        starts=jnp.maximum(inv[:, 0], 0)[probe],
        lens=lens[probe],
        clusters=probe.astype(jnp.int32),
        live=index.live,
        stride=inv.shape[1],
    )


def _score_probed_impl(
    index: IVFIndex,
    prep: QueryPrep,
    probe: jax.Array,
    k: int,
    rerank: int,
    coarse: Optional[str] = None,
    shortlist: Optional[int] = None,
) -> tuple[jax.Array, jax.Array]:
    """Hand each query's probed lists to a gathered ``ScanPlan`` as
    contiguous row ranges (``ProbedLists``): on TPU the gather kernel
    reads the lists' blocks from the resident payload, scores them off
    the packed codes (list padding and tombstones mask to ``-inf``) and
    fuses the selection; no per-slot gather and no (m, nprobe*L) score
    matrix reaches HBM."""
    with jax.named_scope("ash.candidates"):
        lists = probed_lists(index, probe)
    plan = C.ScanPlan(
        metric=index.metric, k=k, rerank=rerank, lists=lists,
        ids=index.ids, coarse=coarse, shortlist=shortlist,
    )
    return C.execute_plan(
        index.model, prep, index.payload, plan,
        stats=index.stats, raw=index.raw, coarse_cache=index.coarse,
    )


def _search(
    index: IVFIndex,
    queries: jax.Array,
    k: int = 10,
    nprobe: int = 8,
    rerank: int = 0,
    coarse: Optional[str] = None,
    shortlist: Optional[int] = None,
) -> tuple[jax.Array, jax.Array]:
    """Composition of ``prepare_queries`` and :func:`_search_prepped`,
    so engine (prepared) and direct paths share compiled arithmetic."""
    prep = S.prepare_queries(index.model, queries)
    return _search_prepped(
        index, prep, k=k, nprobe=nprobe, rerank=rerank,
        coarse=coarse, shortlist=shortlist,
    )
