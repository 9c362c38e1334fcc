"""Shared machinery for every index backend.

One metric dispatcher, one scan-plan executor and one exact-rerank
pipeline, used by the flat, IVF and sharded backends (and the serving
layer) instead of each re-implementing score selection and shortlist
rerank by hand.

Every backend lowers its search to a :class:`ScanPlan` — a declarative
description of WHAT to score (a dense row range, optionally truncated
by ``n_valid``, or per-query candidates: rows via ``rows``, whole
inverted lists via ``lists``) plus metric / top-k / rerank — and
:func:`execute_plan` picks the kernel: the fused dense scan family for
dense plans, the masked-gather family for gathered plans, with
materialize-then-``top_k`` fallbacks beyond the fused-selection budget.
The fused and fallback routes return identical results, so the routing
boundary is invisible to callers.

Score convention: **higher is better** for every metric — L2 scores are
negated squared distances.  Invalid candidates carry ``NEG_INF`` scores
and are reported with id ``-1`` (FAISS convention) rather than being
silently aliased to row 0.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import ash as A
from repro.core import scoring as S
from repro.core.types import (
    ASHConfig, ASHModel, ASHPayload, ASHStats, CoarseCodes, QueryPrep,
)

NEG_INF = -jnp.inf
METRICS = ("dot", "l2", "cos")
COARSE_MODES = ("int8",)
_EPS = 1e-12


def validate_metric(metric: str) -> str:
    if metric not in METRICS:
        raise ValueError(
            f"unknown metric {metric!r}; expected one of {METRICS}"
        )
    return metric


# ---------------------------------------------------------------------------
# Build stages — each waited for inside its host span (``ash.build.*``,
# listed in ``serving/engine.py``), so the span covers its device work
# ---------------------------------------------------------------------------

_span = jax.profiler.TraceAnnotation


def train_and_encode(
    key: jax.Array,
    X: jax.Array,
    config: ASHConfig,
    *,
    model: Optional[ASHModel] = None,
    learned: bool = True,
    keep_raw: bool = False,
    **train_kw,
) -> tuple[ASHModel, ASHPayload, Optional[jax.Array]]:
    """(model, payload, bf16 raw rows or None): train (or reuse
    ``model``) under ``ash.build.train``, then encode, and copy the raw
    rows for rerank, under ``ash.build.encode``."""
    with _span("ash.build.train"):
        if model is None and learned:
            model, _ = A.train(key, X, config, **train_kw)
        elif model is None:
            model = A.random_model(
                key, X.shape[1], config, X_for_landmarks=X
            )
        jax.block_until_ready(model)
    n = X.shape[0]
    with _span("ash.build.encode", rows=n,
               chunks=A.encode_chunks(model, n)):
        payload = A.encode(model, X)
        raw = X.astype(jnp.bfloat16) if keep_raw else None
        jax.block_until_ready((payload, raw))
    return model, payload, raw


def build_stats(build):
    """``build()`` (the statistics a backend derives from its payload,
    and any layout it assembles with them) under ``ash.build.stats``."""
    with _span("ash.build.stats"):
        return jax.block_until_ready(build())


# ---------------------------------------------------------------------------
# Approximate (payload) scoring — the single metric dispatcher
# ---------------------------------------------------------------------------


def approx_scores(
    model: ASHModel,
    prep: QueryPrep,
    payload: ASHPayload,
    metric: str,
    *,
    use_pallas: Optional[bool] = False,
    stats: Optional[ASHStats] = None,
) -> jax.Array:
    """ASH scores of all payload rows, (m, n), higher-is-better.

    use_pallas: ``False`` → the pure-jnp reference scorers (retained as
    oracles; ``scoring.score_*`` keep a ``rowwise`` mode for
    batch-invariance cross-checks); ``True`` / ``None`` → route EVERY
    metric through the fused kernel family (``None`` = auto: Pallas on
    TPU, the identical-semantics jnp oracle on CPU).  The l2/cos
    epilogues consume the encode-time ``stats``
    (``scoring.payload_stats``); when absent they are rebuilt on the
    fly, which unpacks the database once.
    """
    if use_pallas is False:
        if metric == "dot":
            return S.score_dot(model, prep, payload)
        if metric == "l2":
            return -S.score_l2(model, prep, payload)
        if metric == "cos":
            return S.score_cosine(model, prep, payload)
        raise ValueError(metric)
    validate_metric(metric)
    from repro.kernels import ops as K

    return K.ash_score(
        model, prep, payload, metric=metric, stats=stats,
        use_pallas=use_pallas,
    )


def approx_topk(
    model: ASHModel,
    prep: QueryPrep,
    payload: ASHPayload,
    metric: str,
    k: int,
    *,
    use_pallas: Optional[bool] = None,
    stats: Optional[ASHStats] = None,
    n_valid: Any = None,
    row_valid: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused-selection top-k over all payload rows: (scores, rows).

    Equal to ``top_k(approx_scores(..., use_pallas=use_pallas), k)`` —
    but on TPU the (m, n) score matrix never reaches HBM (each kernel
    tile emits a partial top-k̃; see ``kernels.ash_score``).  Callers
    must keep ``k <= fused_topk_limit()`` and ``k <= payload.n``.
    ``n_valid`` (int or traced scalar) masks rows at/beyond it inside
    the scan (sharded pad-row masking); ``row_valid`` ((n,) bool) masks
    tombstoned rows the same way.
    """
    validate_metric(metric)
    from repro.kernels import ops as K

    return K.ash_score_topk(
        model, prep, payload, k, metric=metric, stats=stats,
        use_pallas=use_pallas, n_valid=n_valid, row_valid=row_valid,
    )


def fused_topk_limit() -> int:
    """Largest k the fused-selection path serves (see kernels.ops)."""
    from repro.kernels import ops as K

    return K.FUSED_TOPK_MAX_K


def default_shortlist() -> int:
    """Default coarse-shortlist size L (see kernels.ops, picked by the
    kernel-bench recall sweep)."""
    from repro.kernels import ops as K

    return K.DEFAULT_SHORTLIST


# ---------------------------------------------------------------------------
# ScanPlan — the single scoring path every backend lowers to
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ScanPlan:
    """Declarative description of one top-k scan.

    WHAT to score:
      * dense (``rows is None``): every payload row, optionally
        truncated by ``n_valid`` (an int or traced scalar; rows
        at/beyond it are padding and score ``-inf`` — the sharded
        backend's per-shard pad masking) and/or filtered by
        ``row_valid`` (a (n,) bool validity bitmap; False rows are
        tombstones and score ``-inf`` — the mutation layer's deletes,
        folded into the same kernel mask operand so no plan variant
        recompiles).
      * gathered (``rows`` = (m, R) int32): query i scores its own
        candidate list ``rows[i]`` (the int8 refine's shortlists); pad
        entries carry id -1 and score ``-inf``.  Tombstones must be
        dropped from the candidate lists (mapped to -1) BEFORE planning
        (``row_valid`` on a gathered plan is an error, not a silent
        no-op).
      * probed lists (``lists`` = ``kernels.ash_score.ProbedLists``):
        query i scores whole contiguous inverted lists (IVF partial
        probes), candidate slots in ``lists.rows()`` order, tombstones
        dropped by its ``live`` bitmap.  The fused gather kernel reads
        the lists in place; every other route scores ``lists.rows()``.

    HOW to select: top-``k`` per query; ``rerank > 0`` retrieves a
    ``max(rerank, k)`` shortlist by ASH scores and re-ranks it with
    exact scores over the ``raw`` vectors handed to
    :func:`execute_plan`.  ``ids`` maps payload rows to user-facing ids
    (IVF stores rows sorted by list).  ``use_pallas``: None = auto
    (Pallas on TPU, the bit-identical-semantics jnp oracle on CPU),
    False = the retained pure-jnp reference scorers.

    FIRST PASS: ``coarse="int8"`` inserts a symmetric int8 coarse scan
    ahead of the asymmetric path — the bulk scan runs integer MXU
    products over per-query-quantized queries, only the top
    ``shortlist`` (L) coarse candidates are rescored asymmetrically
    (then optionally exact-reranked as usual).  ``shortlist=None``
    takes the benchmark-picked default.  Coarse search changes results
    BY DESIGN (the query side is quantized); exception: whenever L
    covers the whole candidate set (L >= n dense / L >= R gathered) the
    coarse stage is skipped outright and results are bit-identical to
    the pure asymmetric plan.  ``shortlist`` without ``coarse`` is an
    error, as is an unknown coarse mode.
    """

    metric: str
    k: int
    rerank: int = 0
    rows: Optional[jax.Array] = None
    lists: Any = None
    n_valid: Any = None
    row_valid: Optional[jax.Array] = None
    ids: Optional[jax.Array] = None
    use_pallas: Optional[bool] = None
    coarse: Optional[str] = None
    shortlist: Optional[int] = None


def _map_ids(rows: jax.Array, ids: Optional[jax.Array]) -> jax.Array:
    """Map payload rows to user-facing ids, preserving the -1
    missing-candidate sentinel (shared tail of every plan route)."""
    if ids is None:
        return rows
    return jnp.where(rows < 0, -1, ids[jnp.maximum(rows, 0)])


def execute_plan(
    model: ASHModel,
    prep: QueryPrep,
    payload: ASHPayload,
    plan: ScanPlan,
    *,
    stats: Optional[ASHStats] = None,
    raw: Optional[jax.Array] = None,
    coarse_cache: Optional[CoarseCodes] = None,
) -> tuple[jax.Array, jax.Array]:
    """Lower a :class:`ScanPlan` onto the fused kernel family.

    Returns (scores, ids), each (m, k).  The scan and the selection
    fuse whenever the requested top-k / rerank shortlist fits
    :func:`fused_topk_limit`, falling back to materialize +
    ``lax.top_k`` beyond it — the two return identical results, so the
    routing boundary is invisible to callers.

    ``coarse_cache`` is the backend's persisted :class:`CoarseCodes`
    for coarse plans; when absent it is rebuilt per call (one database
    unpack — backends should pass it, shard-local plans may not).
    """
    validate_metric(plan.metric)
    if plan.coarse is not None and plan.coarse not in COARSE_MODES:
        raise ValueError(
            f"unknown coarse mode {plan.coarse!r}; expected one of "
            f"{COARSE_MODES} (or None)"
        )
    if plan.shortlist is not None and plan.coarse is None:
        raise ValueError(
            "shortlist= sets the coarse first-pass size and requires "
            "coarse='int8'"
        )
    if plan.rows is None and plan.lists is None:
        return _execute_dense(
            model, prep, payload, plan, stats=stats, raw=raw,
            coarse_cache=coarse_cache,
        )
    if plan.n_valid is not None or plan.row_valid is not None:
        raise ValueError(
            "n_valid/row_valid apply to dense plans only; gathered "
            "plans mask by pad id (drop tombstoned rows to -1 in "
            "`rows` before planning)"
        )
    return _execute_gather(
        model, prep, payload, plan, stats=stats, raw=raw,
        coarse_cache=coarse_cache,
    )


def _execute_dense(model, prep, payload, plan, *, stats, raw,
                   coarse_cache=None):
    """Dense-scan lowering (flat, IVF full probe, sharded local scan)."""
    n = payload.n
    fused = plan.use_pallas is not False
    cap = fused_topk_limit()
    masked = plan.n_valid is not None or plan.row_valid is not None

    if plan.coarse is not None:
        from repro.kernels import ops as K

        want_rerank = bool(plan.rerank) and raw is not None
        refine_k = (
            min(max(plan.rerank, plan.k), n) if want_rerank else plan.k
        )
        L = max(plan.shortlist or default_shortlist(), refine_k)
        if L < n:
            ss, srows = K.coarse_refine_topk(
                model, prep, payload, refine_k, shortlist=L,
                metric=plan.metric, stats=stats, coarse=coarse_cache,
                n_valid=plan.n_valid, row_valid=plan.row_valid,
                use_pallas=plan.use_pallas,
            )
            if want_rerank:
                return exact_rerank(
                    prep, raw, ss, srows, plan.metric, plan.k,
                    ids=plan.ids,
                )
            ss, srows = ss[:, : plan.k], srows[:, : plan.k]
            srows = jnp.where(jnp.isneginf(ss), -1, srows)
            return ss, _map_ids(srows, plan.ids)
        # L >= n: the shortlist covers every row, so the coarse pass
        # cannot change the candidate set — run the pure asymmetric
        # path outright (bit-identical to coarse=None by construction)

    def materialized():
        s = approx_scores(
            model, prep, payload, plan.metric,
            use_pallas=plan.use_pallas, stats=stats,
        )
        if not masked:
            return s
        from repro.kernels import ops as K

        return K.mask_valid_rows(s, plan.n_valid, plan.row_valid)

    if plan.rerank and raw is not None:
        R = min(max(plan.rerank, plan.k), n)
        if fused and R <= cap:
            short_s, short_rows = approx_topk(
                model, prep, payload, plan.metric, R,
                use_pallas=plan.use_pallas, stats=stats,
                n_valid=plan.n_valid, row_valid=plan.row_valid,
            )
        else:
            short_s, short_rows = jax.lax.top_k(materialized(), R)
        return exact_rerank(
            prep, raw, short_s, short_rows, plan.metric, plan.k,
            ids=plan.ids,
        )
    if fused and plan.k <= min(cap, n):
        s, rows = approx_topk(
            model, prep, payload, plan.metric, plan.k,
            use_pallas=plan.use_pallas, stats=stats,
            n_valid=plan.n_valid, row_valid=plan.row_valid,
        )
    else:
        s, rows = jax.lax.top_k(materialized(), plan.k)
    if masked:
        # -inf slots carry route-dependent ids under row masking (the
        # fused kernel emits sentinels, lax.top_k the masked rows);
        # normalize both routes to the repo-wide -1 convention so the
        # routing boundary stays invisible
        rows = jnp.where(jnp.isneginf(s), -1, rows)
    return s, _map_ids(rows, plan.ids)


def _execute_gather(model, prep, payload, plan, *, stats, raw,
                    coarse_cache=None):
    """Gathered-candidate lowering (IVF partial probes, int8 refine).
    Probed lists feed the fused gather top-k as they are; the other
    routes take their candidate rows."""
    from repro.kernels import ops as K

    lists = plan.lists
    if lists is None:
        R = plan.rows.shape[1]
    else:
        R = lists.starts.shape[1] * lists.stride

    def rows():
        return plan.rows if lists is None else lists.rows()

    fused = plan.use_pallas is not False
    cap = fused_topk_limit()

    if plan.coarse is not None:
        want_rerank = bool(plan.rerank) and raw is not None
        refine_k = (
            min(max(plan.rerank, plan.k), R) if want_rerank else plan.k
        )
        L = max(plan.shortlist or default_shortlist(), refine_k)
        if L < R:
            ss, srows = K.coarse_refine_gather_topk(
                model, prep, payload, rows(), refine_k,
                shortlist=L, metric=plan.metric, stats=stats,
                coarse=coarse_cache, use_pallas=plan.use_pallas,
            )
            if want_rerank:
                return exact_rerank(
                    prep, raw, ss, srows, plan.metric, plan.k,
                    ids=plan.ids,
                )
            ss, srows = ss[:, : plan.k], srows[:, : plan.k]
            return ss, _map_ids(srows, plan.ids)
        # L >= R: shortlist covers the whole candidate list — pure
        # asymmetric gathered path, bit-identical to coarse=None

    def shortlist(size):
        if fused and size <= cap:
            return K.ash_score_gather_topk(
                model, prep, payload,
                plan.rows if lists is None else lists, size,
                metric=plan.metric, stats=stats,
                use_pallas=plan.use_pallas,
            )
        cand = rows()
        sc = K.ash_score_gather(
            model, prep, payload, cand, metric=plan.metric,
            stats=stats, use_pallas=plan.use_pallas,
        )
        s, pos = jax.lax.top_k(sc, size)
        return s, jnp.take_along_axis(cand, pos, axis=1)

    if plan.rerank and raw is not None:
        ss, srows = shortlist(min(max(plan.rerank, plan.k), R))
        return exact_rerank(
            prep, raw, ss, srows, plan.metric, plan.k, ids=plan.ids
        )
    s, rows_out = shortlist(plan.k)
    return s, _map_ids(rows_out, plan.ids)


# ---------------------------------------------------------------------------
# Paged scan planning — the host-tiered ScanPlan variant
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PagedScanPlan:
    """A gathered :class:`ScanPlan` whose candidate rows index a
    device-assembled UNION of probed inverted lists instead of the
    global payload (the host-tiered IVF backend, where codes live in
    host memory per list and only the probed lists are resident).

    Built host-side by :func:`plan_paged_probe` from the probe set and
    the index's contiguous-list geometry.  ``union_lists`` names the
    probed lists in ascending id order; concatenating their row blocks
    in that order reproduces the global (cluster-sorted) row order
    restricted to the union, so ``rows`` — global candidate rows
    remapped through a monotone shift into the union — preserves the
    candidate ORDER of the HBM-resident gathered plan exactly:
    per-candidate scoring arithmetic, top-k tie resolution and id
    mapping all come out bitwise identical.  ``n_pad - n_union``
    zero rows pad the union to a bounded set of trace shapes; they are
    never gathered (every ``rows`` entry is a real row or ``-1``).
    """

    metric: str
    k: int
    rerank: int
    coarse: Optional[str]
    shortlist: Optional[int]
    rows: Any  # (m, nprobe * max_list_len) int32 numpy, union-local
    union_lists: tuple  # ascending probed list ids
    n_union: int  # real rows in the union
    n_pad: int  # union rows after padding (multiple of pad_multiple)

    def to_scan_plan(self, rows, ids) -> ScanPlan:
        """Lower onto the gathered :class:`ScanPlan` executor; ``rows``
        is the device copy of ``self.rows``, ``ids`` the union's
        user-id column."""
        return ScanPlan(
            metric=self.metric, k=self.k, rerank=self.rerank,
            rows=rows, ids=ids, coarse=self.coarse,
            shortlist=self.shortlist,
        )


def plan_paged_probe(
    probe,
    counts,
    starts,
    live,
    max_list_len: int,
    *,
    metric: str,
    k: int,
    rerank: int = 0,
    coarse: Optional[str] = None,
    shortlist: Optional[int] = None,
    pad_multiple: int = 256,
) -> PagedScanPlan:
    """Plan a paged gathered scan over a probe set, host-side.

    ``probe`` is (m, nprobe) int32 probed list ids per query (any
    order, duplicates allowed); ``counts``/``starts`` the contiguous
    list geometry (:func:`repro.index.ivf.list_geometry`); ``live`` an
    optional (n,) row-validity bitmap — tombstoned rows are dropped to
    the ``-1`` pad id here, pre-DMA, exactly like the HBM gathered
    path.  The candidate layout matches ``invlists[probe]`` slot for
    slot (list-id probe order, each list's tail padded with ``-1``),
    with global rows shifted into the ascending-list union.
    """
    import numpy as np

    probe = np.asarray(probe)
    m = probe.shape[0]
    counts = np.asarray(counts, dtype=np.int64)
    starts = np.asarray(starts, dtype=np.int64)
    union = np.unique(probe.ravel())
    union = union[(union >= 0) & (union < counts.size)]
    c_u = counts[union]
    local_starts = np.concatenate(
        [[0], np.cumsum(c_u)[:-1]]
    ).astype(np.int64)
    n_union = int(c_u.sum())
    # per-list shift mapping a global row of list c into the union
    delta = np.zeros(counts.size, dtype=np.int64)
    delta[union] = local_starts - starts[union]
    t = np.arange(max_list_len, dtype=np.int64)
    g = starts[probe][:, :, None] + t[None, None, :]  # global rows
    valid = t[None, None, :] < counts[probe][:, :, None]
    if live is not None:
        live = np.asarray(live).astype(bool)
        valid &= live[np.minimum(g, max(live.size - 1, 0))]
    loc = g + delta[probe][:, :, None]
    cand = np.where(valid, loc, -1).reshape(m, -1).astype(np.int32)
    n_pad = max(
        pad_multiple, -(-n_union // pad_multiple) * pad_multiple
    )
    return PagedScanPlan(
        metric=metric, k=k, rerank=rerank, coarse=coarse,
        shortlist=shortlist, rows=cand,
        union_lists=tuple(int(c) for c in union),
        n_union=n_union, n_pad=n_pad,
    )


# ---------------------------------------------------------------------------
# Exact scoring + the shared rerank pipeline
# ---------------------------------------------------------------------------


def exact_scores(
    prep: QueryPrep, cand: jax.Array, metric: str
) -> jax.Array:
    """Metric-aware exact scores of raw candidates.

    cand: (m, R, D) candidate vectors per query.  Returns (m, R),
    higher-is-better (same convention as :func:`approx_scores`).

    The inner products use a broadcast-multiply + last-axis reduce
    rather than a batched matmul: XLA's batched-dot lowering varies
    with m, and rerank scores must be bit-identical whether a query is
    served alone or inside an engine bucket.
    """
    ip = jnp.sum(prep.q[:, None, :] * cand, axis=-1)
    if metric == "dot":
        return ip
    if metric == "l2":
        return -(
            prep.q_sq_norm[:, None]
            - 2.0 * ip
            + jnp.sum(cand * cand, axis=-1)
        )
    if metric == "cos":
        q_norm = jnp.sqrt(jnp.maximum(prep.q_sq_norm, _EPS))[:, None]
        c_norm = jnp.maximum(
            jnp.sqrt(jnp.sum(cand * cand, axis=-1)), _EPS
        )
        return ip / (q_norm * c_norm)
    raise ValueError(metric)


def exact_rerank(
    prep: QueryPrep,
    raw: jax.Array,
    shortlist_scores: jax.Array,
    shortlist_rows: jax.Array,
    metric: str,
    k: int,
    ids: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Re-rank a shortlist with exact scores on the raw vectors.

    shortlist_scores/rows: (m, R) approximate scores and row indices
    into ``raw``; invalid entries must carry ``NEG_INF`` scores (their
    rows may be ``-1``).  ``ids`` optionally maps raw rows to returned
    ids (IVF stores rows sorted by list).  Returns (scores, ids) each
    (m, k); entries without a valid candidate get score ``NEG_INF`` and
    id ``-1``.
    """
    with jax.named_scope("ash.rerank"):
        cand = raw[jnp.maximum(shortlist_rows, 0)].astype(jnp.float32)
        exact = exact_scores(prep, cand, metric)
        exact = jnp.where(jnp.isneginf(shortlist_scores), NEG_INF, exact)
        rs, ri = jax.lax.top_k(exact, k)
        rows_k = jnp.take_along_axis(shortlist_rows, ri, axis=1)
        out = rows_k if ids is None else ids[jnp.maximum(rows_k, 0)]
        return rs, jnp.where(jnp.isneginf(rs), -1, out)


# ---------------------------------------------------------------------------
# Payload manipulation shared by backends
# ---------------------------------------------------------------------------


def gather_payload(payload: ASHPayload, rows: jax.Array) -> ASHPayload:
    """Gather payload rows (any leading batch shape); -1 rows read row 0
    (callers mask them by score).  Serving no longer routes through
    payload gathers — gathered plans feed the masked-gather kernels —
    but the rowwise reference path (tests, benchmarks) still scores
    per-query sub-payloads built with this."""
    safe = jnp.maximum(rows, 0)
    return ASHPayload(
        b=payload.b,
        d=payload.d,
        codes=payload.codes[safe],
        scale=payload.scale[safe],
        offset=payload.offset[safe],
        cluster=payload.cluster[safe],
    )


def take_stats(
    stats: Optional[ASHStats], rows: jax.Array
) -> Optional[ASHStats]:
    """Gather stats rows (compaction: survivors keep their encode-time
    statistics bit-identically instead of being recomputed)."""
    if stats is None:
        return None
    return ASHStats(
        res_norm=stats.res_norm[rows],
        ip_x_mu=stats.ip_x_mu[rows],
        x_sq=stats.x_sq[rows],
    )


# ---------------------------------------------------------------------------
# Tombstone (delete) bookkeeping shared by backends
# ---------------------------------------------------------------------------


def effective_next_id(next_id, ids, n: int) -> int:
    """The user-facing id the next added row receives.

    ``next_id`` (persisted once mutations happen) wins; otherwise it is
    derived — identity-id states (``ids is None``) continue at ``n``,
    and explicit id arrays at ``max(ids) + 1`` (equal to ``n`` for any
    pre-mutation save, so old snapshots keep their add() semantics).
    Ids are never reused: a deleted-and-compacted id stays retired.
    """
    if next_id is not None:
        return int(next_id)
    if ids is None or n == 0:
        return int(n)
    import numpy as np

    return int(np.asarray(ids).max()) + 1


def mark_deleted(
    ids: Optional[jax.Array],
    live: Optional[jax.Array],
    del_ids,
    n: int,
) -> tuple[Any, int]:
    """Tombstone payload rows by user id: (new live bitmap (n,) bool
    numpy, rows newly removed).

    ``ids`` maps payload rows to user ids (None = identity); ``live``
    is the current bitmap (None = all live).  Ids that don't exist or
    are already tombstoned are ignored (FAISS ``remove_ids``
    semantics), so the removed count is the true live-row delta.
    """
    import numpy as np

    del_ids = np.unique(np.asarray(del_ids).reshape(-1).astype(np.int64))
    row_ids = (
        np.arange(n, dtype=np.int64) if ids is None
        else np.asarray(ids).astype(np.int64)
    )
    hit = np.isin(row_ids, del_ids)
    if live is not None:
        old = np.asarray(live).astype(bool)
        hit &= old  # only count rows that were still live
        new_live = old & ~hit
    else:
        new_live = ~hit
    return new_live, int(hit.sum())


def concat_stats(
    a: Optional[ASHStats], b: Optional[ASHStats]
) -> Optional[ASHStats]:
    """Row-concatenate two stats blocks (None if either side is
    missing — callers then rebuild via ``scoring.payload_stats``)."""
    if a is None or b is None:
        return None
    return ASHStats(
        res_norm=jnp.concatenate([a.res_norm, b.res_norm], axis=0),
        ip_x_mu=jnp.concatenate([a.ip_x_mu, b.ip_x_mu], axis=0),
        x_sq=jnp.concatenate([a.x_sq, b.x_sq], axis=0),
    )


def concat_payloads(a: ASHPayload, b: ASHPayload) -> ASHPayload:
    """Row-concatenate two payloads encoded under the same model."""
    if (a.b, a.d) != (b.b, b.d):
        raise ValueError(
            f"payload mismatch: (b={a.b}, d={a.d}) vs (b={b.b}, d={b.d})"
        )
    return ASHPayload(
        b=a.b,
        d=a.d,
        codes=jnp.concatenate([a.codes, b.codes], axis=0),
        scale=jnp.concatenate([a.scale, b.scale], axis=0),
        offset=jnp.concatenate([a.offset, b.offset], axis=0),
        cluster=jnp.concatenate([a.cluster, b.cluster], axis=0),
    )


def permute_payload(payload: ASHPayload, perm: jax.Array) -> ASHPayload:
    """Reorder payload rows by ``perm`` (a permutation of arange(n))."""
    return ASHPayload(
        b=payload.b,
        d=payload.d,
        codes=payload.codes[perm],
        scale=payload.scale[perm],
        offset=payload.offset[perm],
        cluster=payload.cluster[perm],
    )
