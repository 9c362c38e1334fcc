"""Public jit'd wrappers around the Pallas kernels.

``interpret`` defaults to auto: Python-interpret the kernel body on CPU
(this container), compile on TPU.  Both paths are validated against the
pure-jnp oracles in ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import quantization as Q
from repro.core import scoring as S
from repro.core.types import (
    ASHModel, ASHPayload, ASHStats, CoarseCodes, CoarseQueryPrep,
    QueryPrep,
)
from repro.kernels import ref
from repro.kernels.ash_score import (
    ProbedLists,
    ash_score_coarse_pallas,
    ash_score_coarse_topk_pallas,
    ash_score_gather_pallas,
    ash_score_gather_topk_pallas,
    ash_score_pallas,
    ash_score_topk_pallas,
)
from repro.kernels.ash_kv_attn import ash_kv_attn_pallas

_EPS = 1e-12

# Largest per-tile partial top-k the fused-selection path accepts: the
# selection epilogue is k̃ VPU sweeps per tile and 2·k̃·n_blocks VMEM
# candidate words per query row, so the index layers fall back to
# materialize-then-top_k beyond this (scores of the two kernels are
# identical per element, so the routing choice never changes results).
FUSED_TOPK_MAX_K = 128

# Default coarse shortlist size L for the coarse -> refine pipeline,
# picked by the recall-vs-shortlist sweep in benchmarks/kernel_bench.py
# (kernel/coarse_shortlist_sweep): the smallest power of two whose
# coarse-shortlist recall@10 against the pure asymmetric path clears
# 99% at the benchmark corpus shape.  Small L matters beyond recall:
# selection cost grows with L on every backend (k̃ VPU sweeps per tile
# fused, O(L) partial-selection work in XLA:CPU's TopK), so the sweep's
# floor is also the fast point — ``execute_plan`` raises L to the
# requested top-k/rerank depth when callers need more.
DEFAULT_SHORTLIST = 32


def _auto_interpret() -> bool:
    return jax.default_backend() == "cpu"


def _scan_scope(fn):
    """Run a scoring entry point, kernel or oracle, under the
    ``ash.scan`` scope.  It wraps the jitted kernel wrapper from
    outside: a scope inside the wrapper would rename the kernel's
    custom-call instruction.  (One ``named_scope`` object used as the
    decorator would keep its saved name stack on itself, shared by
    every thread and every nested call; each call opens its own.)"""

    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope("ash.scan"):
            return fn(*args, **kwargs)

    return scoped


def _metric_operands(
    model: ASHModel,
    prep: QueryPrep,
    payload: ASHPayload,
    stats: ASHStats | None,
    metric: str,
):
    """(qterm, rowterm) epilogue vectors for the fused kernel/oracle.

    dot needs none; l2/cos derive theirs from the encode-time
    ``ASHStats`` (built on the fly when ``stats`` is None — that
    fallback unpacks the database once and defeats the fused path's
    purpose, so index backends persist stats alongside the payload).
    """
    if metric == "dot":
        return None, None
    if stats is None:
        stats = S.payload_stats(model, payload)
    if metric == "l2":
        # -l2 = 2<q,x> - ||q||^2 - ||x||^2, ||x||^2 recovered (x_sq)
        return prep.q_sq_norm.astype(jnp.float32), stats.x_sq
    if metric == "cos":
        qterm = 1.0 / jnp.sqrt(jnp.maximum(prep.q_sq_norm, _EPS))
        rowterm = 1.0 / jnp.sqrt(jnp.maximum(stats.x_sq, _EPS))
        return qterm.astype(jnp.float32), rowterm.astype(jnp.float32)
    raise ValueError(metric)


def _score_args(prep: QueryPrep, payload: ASHPayload):
    d_pad = payload.codes.shape[1] * Q.codes_per_word(payload.b)
    q_proj = prep.q_proj
    if q_proj.shape[-1] < d_pad:
        q_proj = jnp.pad(q_proj, ((0, 0), (0, d_pad - q_proj.shape[-1])))
    return (
        payload.codes,
        q_proj,
        payload.scale.astype(jnp.float32),
        payload.offset.astype(jnp.float32),
        payload.cluster,
        prep.ip_q_landmarks,
    )


@_scan_scope
def ash_score(
    model: ASHModel,
    prep: QueryPrep,
    payload: ASHPayload,
    *,
    metric: str = "dot",
    stats: ASHStats | None = None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
    compute_dtype=jnp.float32,
) -> jax.Array:
    """Fused all-metric scoring: (m, n) fp32, higher-is-better.

    metric="dot" is a drop-in fused replacement for scoring.score_dot;
    "l2"/"cos" apply the stats-driven epilogues (negated squared
    distance / cosine over the recovered ||x||, ``ASHStats.x_sq``)
    without unpacking the database.

    use_pallas=None (auto): the fused kernel on TPU, the identical-
    semantics jnp oracle on CPU (interpret mode is for validation, far
    too slow for serving).
    """
    if use_pallas is None:
        use_pallas = not _auto_interpret()
    if interpret is None:
        interpret = _auto_interpret()
    args = _score_args(prep, payload)
    qterm, rowterm = _metric_operands(model, prep, payload, stats, metric)
    if not use_pallas:
        return ref.ash_score_metric_ref(
            *args, qterm, rowterm, b=payload.b, metric=metric
        )
    return ash_score_pallas(
        *args, qterm, rowterm, b=payload.b, metric=metric,
        interpret=interpret, compute_dtype=compute_dtype,
    )


def mask_valid_rows(
    scores: jax.Array, n_valid=None, row_valid=None
) -> jax.Array:
    """Force masked columns to ``-inf`` — the materialized-path
    equivalent of the fused kernel's runtime row-validity mask operand.
    ``n_valid`` (static int or traced scalar) masks columns at/beyond
    it; ``row_valid`` ((n,) bool) masks tombstoned rows."""
    return ref.mask_rows_ref(scores, n_valid, row_valid)


@_scan_scope
def ash_score_topk(
    model: ASHModel,
    prep: QueryPrep,
    payload: ASHPayload,
    k: int,
    *,
    metric: str = "dot",
    stats: ASHStats | None = None,
    k_tilde: int | None = None,
    n_valid=None,
    row_valid=None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
    compute_dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """Fused scan + on-chip selection: top-k (scores, row ids), (m, k).

    On TPU the (m, n) score matrix never reaches HBM — each output tile
    emits a partial top-k̃ merged by one small ``lax.top_k``.  Results
    equal ``lax.top_k(ash_score(...), k)`` exactly (values, ids, tie
    order) for ``k <= k̃`` (default ``k̃ = k``).  The CPU oracle
    materializes and calls ``lax.top_k`` — identical semantics.

    ``n_valid`` (int or traced scalar) masks rows at/beyond it to
    ``-inf`` inside the scan — the sharded backend's per-shard pad-row
    masking; ``row_valid`` ((n,) bool) additionally masks tombstoned
    rows.  Both fold into the kernel's single runtime mask operand, so
    deletes never trigger a recompile.
    """
    if use_pallas is None:
        use_pallas = not _auto_interpret()
    if interpret is None:
        interpret = _auto_interpret()
    args = _score_args(prep, payload)
    qterm, rowterm = _metric_operands(model, prep, payload, stats, metric)
    if not use_pallas:
        scores = ref.ash_score_metric_ref(
            *args, qterm, rowterm, b=payload.b, metric=metric
        )
        scores = mask_valid_rows(scores, n_valid, row_valid)
        with jax.named_scope("ash.merge"):
            return jax.lax.top_k(scores, k)
    return ash_score_topk_pallas(
        *args, qterm, rowterm, n_valid, row_valid, b=payload.b, k=k,
        k_tilde=k_tilde, metric=metric, interpret=interpret,
        compute_dtype=compute_dtype,
    )


@_scan_scope
def ash_score_gather(
    model: ASHModel,
    prep: QueryPrep,
    payload: ASHPayload,
    rows: jax.Array,
    *,
    metric: str = "dot",
    stats: ASHStats | None = None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
    compute_dtype=jnp.float32,
) -> jax.Array:
    """Fused masked-gather scoring: (m, R) fp32, higher-is-better.

    Query i scores its own candidate list ``rows[i]`` (payload row ids,
    -1 = padding → score ``-inf``) — the IVF partial-probe primitive.
    On TPU the kernel scores the probed rows' packed words in place;
    the CPU oracle (``ref.ash_score_gather_ref``) is rowwise and
    batch-shape-invariant, so engine bucketing stays bit-identical.
    """
    if use_pallas is None:
        use_pallas = not _auto_interpret()
    if interpret is None:
        interpret = _auto_interpret()
    codes, q_proj, scale, offset, cluster, ipq = _score_args(prep, payload)
    qterm, rowterm = _metric_operands(model, prep, payload, stats, metric)
    if not use_pallas:
        return ref.ash_score_gather_ref(
            codes, rows, q_proj, scale, offset, cluster, ipq,
            qterm, rowterm, b=payload.b, metric=metric,
        )
    return ash_score_gather_pallas(
        codes, rows, q_proj, scale, offset, cluster, ipq, qterm, rowterm,
        b=payload.b, metric=metric, interpret=interpret,
        compute_dtype=compute_dtype,
    )


@_scan_scope
def ash_score_gather_topk(
    model: ASHModel,
    prep: QueryPrep,
    payload: ASHPayload,
    rows: jax.Array | ProbedLists,
    k: int,
    *,
    metric: str = "dot",
    stats: ASHStats | None = None,
    k_tilde: int | None = None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
    compute_dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """Fused masked-gather scan + selection: (scores, payload rows),
    each (m, k); pad slots come back score ``-inf`` / row -1.

    Equal to ``top_k(ash_score_gather(...), k)`` with positions mapped
    back through ``rows`` — on TPU without the (m, R) score matrix ever
    reaching HBM.  Requires ``k <= R``.  ``rows`` may be
    :class:`ProbedLists` (whole contiguous lists): the kernel then
    reads the lists in place, and the oracle scores ``rows.rows()``.
    """
    if use_pallas is None:
        use_pallas = not _auto_interpret()
    if interpret is None:
        interpret = _auto_interpret()
    codes, q_proj, scale, offset, cluster, ipq = _score_args(prep, payload)
    qterm, rowterm = _metric_operands(model, prep, payload, stats, metric)
    if not use_pallas:
        if isinstance(rows, ProbedLists):
            rows = rows.rows()
        scores = ref.ash_score_gather_ref(
            codes, rows, q_proj, scale, offset, cluster, ipq,
            qterm, rowterm, b=payload.b, metric=metric,
        )
        with jax.named_scope("ash.merge"):
            s, pos = jax.lax.top_k(scores, k)
            return s, jnp.take_along_axis(rows, pos, axis=1)
    return ash_score_gather_topk_pallas(
        codes, rows, q_proj, scale, offset, cluster, ipq, qterm, rowterm,
        b=payload.b, k=k, k_tilde=k_tilde, metric=metric,
        interpret=interpret, compute_dtype=compute_dtype,
    )


def _coarse_inputs(
    prep: QueryPrep,
    payload: ASHPayload,
    coarse: CoarseCodes | None,
    cprep: CoarseQueryPrep | None,
):
    """Resolve the coarse cache + per-query quantization, building each
    on the fly when absent (the cache fallback unpacks the database once
    per call — index backends persist ``CoarseCodes`` alongside
    ``ASHStats`` to avoid exactly that)."""
    if coarse is None:
        coarse = S.coarse_codes(payload)
    if cprep is None:
        cprep = S.prepare_coarse_queries(prep, coarse.mean)
    return coarse, cprep


def _coarse_score_args(
    prep: QueryPrep, cprep: CoarseQueryPrep, payload: ASHPayload
):
    """Kernel/oracle operand tuple; zero-pads q_int8 to the packed-code
    width (zero int8 columns add nothing to the accumulation)."""
    d_pad = payload.codes.shape[1] * Q.codes_per_word(payload.b)
    qi = cprep.q_int8
    if qi.shape[-1] < d_pad:
        qi = jnp.pad(qi, ((0, 0), (0, d_pad - qi.shape[-1])))
    return (
        payload.codes,
        qi,
        cprep.q_scale.astype(jnp.float32),
        cprep.q_corr.astype(jnp.float32),
        payload.scale.astype(jnp.float32),
        payload.offset.astype(jnp.float32),
        payload.cluster,
        prep.ip_q_landmarks,
    )


# The coarse oracle branches are jitted at module level: the coarse
# bitwise contract (kernel == oracle, exact-integer accumulation + an
# identical float epilogue) holds when both sides compile as fused XLA
# programs — eager op-by-op dispatch blocks the FMA contraction XLA
# applies inside fusions, shifting the epilogue by an ulp.  Index
# backends already call these inside their own jit (nested jit inlines);
# the module-level jit makes standalone calls identical.
@functools.partial(jax.jit, static_argnames=("b", "metric"))
def _coarse_ref_scores(
    codes, qi, qs, qc, scale, offset, cluster, ipq, qterm, rowterm,
    values, *, b, metric,
):
    return ref.ash_score_coarse_ref(
        codes, qi, qs, qc, scale, offset, cluster, ipq, qterm, rowterm,
        b=b, metric=metric, values=values,
    )


@functools.partial(jax.jit, static_argnames=("b", "metric", "k"))
def _coarse_ref_topk(
    codes, qi, qs, qc, scale, offset, cluster, ipq, qterm, rowterm,
    values, n_valid, row_valid, *, b, metric, k,
):
    scores = ref.ash_score_coarse_ref(
        codes, qi, qs, qc, scale, offset, cluster, ipq, qterm, rowterm,
        b=b, metric=metric, values=values,
    )
    scores = ref.mask_rows_ref(scores, n_valid, row_valid)
    return jax.lax.top_k(scores, k)


@functools.partial(jax.jit, static_argnames=("b", "metric"))
def _coarse_gather_ref_scores(
    codes, rows, qi, qs, qc, scale, offset, cluster, ipq, qterm,
    rowterm, values, *, b, metric,
):
    return ref.ash_score_coarse_gather_ref(
        codes, rows, qi, qs, qc, scale, offset, cluster, ipq, qterm,
        rowterm, b=b, metric=metric, values=values,
    )


@_scan_scope
def ash_score_coarse(
    model: ASHModel,
    prep: QueryPrep,
    payload: ASHPayload,
    *,
    metric: str = "dot",
    stats: ASHStats | None = None,
    coarse: CoarseCodes | None = None,
    cprep: CoarseQueryPrep | None = None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Symmetric int8 coarse scores: (m, n) fp32, higher-is-better.

    The first-pass estimator of the coarse -> refine pipeline: queries
    are int8-quantized per query (``core.prepare_coarse_queries``) and
    the scan accumulates integer products — int8 MXU throughput on TPU,
    one cached-values BLAS matmul (no per-call unpack) on CPU.  Oracle
    and kernel are BITWISE equal (exact-integer accumulation), so the
    routing choice never changes results; both differ from the
    asymmetric score by design (quantization of the query side).
    """
    if use_pallas is None:
        use_pallas = not _auto_interpret()
    if interpret is None:
        interpret = _auto_interpret()
    coarse, cprep = _coarse_inputs(prep, payload, coarse, cprep)
    args = _coarse_score_args(prep, cprep, payload)
    qterm, rowterm = _metric_operands(model, prep, payload, stats, metric)
    if not use_pallas:
        return _coarse_ref_scores(
            *args, qterm, rowterm, coarse.values, b=payload.b,
            metric=metric,
        )
    return ash_score_coarse_pallas(
        *args, qterm, rowterm, b=payload.b, metric=metric,
        interpret=interpret,
    )


@_scan_scope
def ash_score_coarse_topk(
    model: ASHModel,
    prep: QueryPrep,
    payload: ASHPayload,
    k: int,
    *,
    metric: str = "dot",
    stats: ASHStats | None = None,
    coarse: CoarseCodes | None = None,
    cprep: CoarseQueryPrep | None = None,
    k_tilde: int | None = None,
    n_valid=None,
    row_valid=None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused coarse scan + shortlist selection: top-k (scores, ids).

    ``k`` is the SHORTLIST size L of the coarse -> refine pipeline, so
    unlike :func:`ash_score_topk` this wrapper routes its own
    ``FUSED_TOPK_MAX_K`` fallback (shortlists routinely exceed the
    fused-selection cap): beyond it the materializing coarse kernel +
    ``lax.top_k`` runs instead, with identical per-element scores.
    Masking semantics (``n_valid``/``row_valid``) match
    :func:`ash_score_topk`.
    """
    if use_pallas is None:
        use_pallas = not _auto_interpret()
    if interpret is None:
        interpret = _auto_interpret()
    coarse, cprep = _coarse_inputs(prep, payload, coarse, cprep)
    args = _coarse_score_args(prep, cprep, payload)
    qterm, rowterm = _metric_operands(model, prep, payload, stats, metric)
    if not use_pallas:
        return _coarse_ref_topk(
            *args, qterm, rowterm, coarse.values, n_valid, row_valid,
            b=payload.b, metric=metric, k=k,
        )
    if k > FUSED_TOPK_MAX_K:
        scores = ash_score_coarse_pallas(
            *args, qterm, rowterm, b=payload.b, metric=metric,
            interpret=interpret,
        )
        scores = mask_valid_rows(scores, n_valid, row_valid)
        with jax.named_scope("ash.merge"):
            return jax.lax.top_k(scores, k)
    return ash_score_coarse_topk_pallas(
        *args, qterm, rowterm, n_valid, row_valid, b=payload.b, k=k,
        k_tilde=k_tilde, metric=metric, interpret=interpret,
    )


def ash_score_coarse_gather(
    model: ASHModel,
    prep: QueryPrep,
    payload: ASHPayload,
    rows: jax.Array,
    *,
    metric: str = "dot",
    stats: ASHStats | None = None,
    coarse: CoarseCodes | None = None,
    cprep: CoarseQueryPrep | None = None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Coarse scores over per-query candidate lists: (m, R) fp32, pad
    ids (-1) come back ``-inf`` — the IVF partial-probe coarse pass.

    Runs the jnp oracle on every backend for now: candidate lists are
    small relative to dense scans, so the integer-matmul win is marginal
    and a DMA-gather coarse kernel is future work (the refine stage
    still uses the fused asymmetric gather kernel).
    """
    del use_pallas, interpret  # oracle-only (see docstring)
    coarse, cprep = _coarse_inputs(prep, payload, coarse, cprep)
    codes, qi, qs, qc, scale, offset, cluster, ipq = _coarse_score_args(
        prep, cprep, payload
    )
    qterm, rowterm = _metric_operands(model, prep, payload, stats, metric)
    return _coarse_gather_ref_scores(
        codes, rows, qi, qs, qc, scale, offset, cluster, ipq,
        qterm, rowterm, coarse.values, b=payload.b, metric=metric,
    )


def sort_candidate_rows(rows: jax.Array) -> jax.Array:
    """Ascending-id sort of a (m, R) candidate-row matrix with -1 pads
    pushed to the end.

    The gather kernels break score ties by candidate POSITION, so
    feeding the refine stage an ascending-id list makes its tie order
    the ``lax.top_k`` convention (lowest id first) — required for the
    shortlist pipeline to match dense scans whenever the shortlist
    covers every survivor.
    """
    big = jnp.iinfo(jnp.int32).max
    s = jnp.sort(jnp.where(rows < 0, big, rows.astype(jnp.int32)), axis=1)
    return jnp.where(s == big, -1, s)


def _refine_topk(
    model, prep, payload, rows, k, *, metric, stats, use_pallas,
    interpret,
):
    """Asymmetric refine stage shared by both pipelines, honouring the
    FUSED_TOPK_MAX_K routing contract for large refine shortlists."""
    if use_pallas is None:
        use_pallas = not _auto_interpret()
    if use_pallas and k > FUSED_TOPK_MAX_K:
        sc = ash_score_gather(
            model, prep, payload, rows, metric=metric, stats=stats,
            use_pallas=use_pallas, interpret=interpret,
        )
        s, pos = jax.lax.top_k(sc, k)
        return s, jnp.take_along_axis(rows, pos, axis=1)
    return ash_score_gather_topk(
        model, prep, payload, rows, k, metric=metric, stats=stats,
        use_pallas=use_pallas, interpret=interpret,
    )


def coarse_refine_topk(
    model: ASHModel,
    prep: QueryPrep,
    payload: ASHPayload,
    k: int,
    *,
    shortlist: int,
    metric: str = "dot",
    stats: ASHStats | None = None,
    coarse: CoarseCodes | None = None,
    cprep: CoarseQueryPrep | None = None,
    n_valid=None,
    row_valid=None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Dense two-stage scan: int8 coarse shortlist (size L) refined by
    the fused asymmetric gather — top-k (scores, row ids), (m, k).

    Stage 1 selects the L highest COARSE scores (masked rows never
    survive: slots whose coarse score is ``-inf`` are dropped to pad id
    -1 so the refine cannot resurrect them).  Stage 2 rescores the
    shortlist with the full asymmetric Eq. (20) path, ids ascending so
    ties land in ``lax.top_k`` order.  Requires ``k <= shortlist``;
    callers that also exact-rerank pass ``k = refine_k``.
    """
    L = min(shortlist, payload.n)
    if k > L:
        raise ValueError(f"k={k} exceeds shortlist={L}")
    svals, ids = ash_score_coarse_topk(
        model, prep, payload, L, metric=metric, stats=stats,
        coarse=coarse, cprep=cprep, n_valid=n_valid, row_valid=row_valid,
        use_pallas=use_pallas, interpret=interpret,
    )
    rows = sort_candidate_rows(jnp.where(jnp.isneginf(svals), -1, ids))
    return _refine_topk(
        model, prep, payload, rows, k, metric=metric, stats=stats,
        use_pallas=use_pallas, interpret=interpret,
    )


def coarse_refine_gather_topk(
    model: ASHModel,
    prep: QueryPrep,
    payload: ASHPayload,
    rows: jax.Array,
    k: int,
    *,
    shortlist: int,
    metric: str = "dot",
    stats: ASHStats | None = None,
    coarse: CoarseCodes | None = None,
    cprep: CoarseQueryPrep | None = None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Gathered two-stage scan (IVF partial probes): coarse-score the
    (m, R) candidate lists, keep the top-L rows per query, refine those
    asymmetrically — top-k (scores, payload rows), (m, k)."""
    R = rows.shape[1]
    L = min(shortlist, R)
    if k > L:
        raise ValueError(f"k={k} exceeds shortlist={L}")
    scores = ash_score_coarse_gather(
        model, prep, payload, rows, metric=metric, stats=stats,
        coarse=coarse, cprep=cprep, use_pallas=use_pallas,
        interpret=interpret,
    )
    svals, pos = jax.lax.top_k(scores, L)
    cand = jnp.take_along_axis(rows, pos, axis=1)
    cand = sort_candidate_rows(jnp.where(jnp.isneginf(svals), -1, cand))
    return _refine_topk(
        model, prep, payload, cand, k, metric=metric, stats=stats,
        use_pallas=use_pallas, interpret=interpret,
    )


def ash_kv_attention(
    q_k: jax.Array,  # (..., dk) projected queries (W_k q)
    k_codes: jax.Array,  # (..., S, Wk)
    k_scale: jax.Array,  # (..., S)
    k_bias: jax.Array,  # (..., S)
    v_codes: jax.Array,  # (..., S, Wv)
    v_scale: jax.Array,  # (..., S)
    mask: jax.Array,  # (..., S)
    *,
    b_k: int,
    b_v: int,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Batched (vmapped over leading dims) ASH-KV decode attention.

    Returns the reduced-space accumulation (..., dv); caller decodes with
    W_v^T and adds mu_v.
    """
    if interpret is None:
        interpret = _auto_interpret()

    if not use_pallas:
        def one(qk, kc, ks, kb, vc, vs, mk):
            acc, _ = ref.ash_kv_attn_ref(
                qk, kc, ks, kb, vc, vs, b_k, b_v, mask=mk
            )
            return acc
    else:
        def one(qk, kc, ks, kb, vc, vs, mk):
            return ash_kv_attn_pallas(
                qk, kc, ks, kb, vc, vs, mk,
                b_k=b_k, b_v=b_v, interpret=interpret,
            )

    fn = one
    batch_dims = q_k.ndim - 1
    for _ in range(batch_dims):
        fn = jax.vmap(fn)
    return fn(q_k, k_codes, k_scale, k_bias, v_codes, v_scale, mask)
