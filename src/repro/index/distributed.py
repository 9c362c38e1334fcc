"""Distributed (sharded) ASH search over a device mesh.

The database payload is sharded row-wise across every mesh axis; queries
are replicated.  Each shard lowers its local scan to a dense
``common.ScanPlan`` — the same fused metric epilogues and fused local
top-k (or shard-local exact rerank) as the flat backend, with the
per-shard pad-row mask folded into the kernel's id masking — converts
local row ids to global ids, all-gathers the k-per-shard candidates,
and re-top-k's: the classic scatter-gather ANN serving pattern,
expressed with shard_map + jax.lax collectives so XLA can overlap the
local scan with the gather.

The encode-time ``ASHStats`` (fused l2/cos epilogue inputs) and an
optional bf16 raw-vector copy (shard-local exact rerank) are sharded
row-aligned with the payload and threaded through the shard_map
alongside it.

This module is mesh-shape agnostic: it works on the single-host CPU test
mesh and on the (pod, data, model) = (2, 16, 16) production mesh of
launch/mesh.py.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import scoring as S
from repro.core.types import ASHModel, ASHPayload, ASHStats
from repro.index import common as C

PAD_CLUSTER = -1  # cluster id of pad rows; never a valid landmark


def shard_rows(mesh: Mesh, tree, axes: tuple[str, ...]):
    """Place every array leaf of ``tree`` row-sharded over the given
    mesh axes (remaining dims replicated).  Leaf row counts must divide
    the product of axis sizes."""
    sharding = NamedSharding(mesh, P(axes))
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, sharding), tree
    )


def shard_payload(
    mesh: Mesh, payload: ASHPayload, axes: tuple[str, ...]
) -> ASHPayload:
    """Row-shard a payload (see :func:`shard_rows`)."""
    return shard_rows(mesh, payload, axes)


def pad_to_multiple(payload: ASHPayload, multiple: int) -> ASHPayload:
    """Pad rows with sentinel entries so sharding divides evenly.

    Pad rows carry ``scale=0, offset=-inf`` (they never win a top-k)
    and ``cluster=PAD_CLUSTER`` (-1) — a sentinel no real row uses, so
    search paths can derive the valid-row count from the payload itself
    and list assembly can assert the sentinel never reaches a gather
    (``ivf._assemble``; under jit, negative ids would silently alias by
    wrapping).  Scores of pad rows are additionally masked by the
    per-shard ``n_valid`` row mask before any aliased landmark lookup
    can surface.
    """
    n = payload.n
    pad = (-n) % multiple
    if pad == 0:
        return payload
    return ASHPayload(
        b=payload.b,
        d=payload.d,
        codes=jnp.pad(payload.codes, ((0, pad), (0, 0))),
        scale=jnp.pad(payload.scale, (0, pad)),
        offset=jnp.pad(
            payload.offset, (0, pad), constant_values=jnp.finfo(
                payload.offset.dtype
            ).min
        ),
        cluster=jnp.pad(
            payload.cluster, (0, pad), constant_values=PAD_CLUSTER
        ),
    )


def pad_stats(stats: Optional[ASHStats], pad: int) -> Optional[ASHStats]:
    """Zero-pad stats rows to match a padded payload (pad rows are
    masked before their garbage epilogue terms can surface)."""
    if stats is None or pad == 0:
        return stats
    return ASHStats(
        res_norm=jnp.pad(stats.res_norm, (0, pad)),
        ip_x_mu=jnp.pad(stats.ip_x_mu, (0, pad)),
        x_sq=jnp.pad(stats.x_sq, (0, pad)),
    )


def _make_searcher(
    mesh: Mesh,
    model: ASHModel,
    axes: tuple[str, ...],
    k: int,
    *,
    metric: str,
    n_real: int | None,
    from_prep: bool,
    rerank: int = 0,
    fused: bool | None = None,
    coarse: str | None = None,
    shortlist: int | None = None,
):
    C.validate_metric(metric)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]

    def local_then_merge(payload: ASHPayload, stats, raw, valid, queries):
        # ---- local scan (per shard): one dense ScanPlan ----
        prep = (
            queries if from_prep
            else S.prepare_queries(model, queries)
        )
        n_local = payload.codes.shape[0]
        # global row ids: shard linear index * n_local + local id
        shard_lin = jnp.int32(0)
        mul = 1
        for a in reversed(axes):
            shard_lin = shard_lin + jax.lax.axis_index(a) * mul
            mul *= mesh.shape[a]
        if n_real is None:
            # rows padded by pad_to_multiple carry the -1 cluster
            # sentinel (always contiguous at the end of the last
            # shards), so the valid-row count is derivable per shard —
            # l2/cos callers can no longer forget the mask
            n_valid = jnp.sum(
                (payload.cluster != PAD_CLUSTER).astype(jnp.int32)
            )
        else:
            n_valid = jnp.clip(
                n_real - shard_lin * n_local, 0, n_local
            )
        # a shard can hold fewer rows than k (small indexes, deep
        # meshes): clamp the LOCAL top-k to the shard size — the
        # all-gather still collects n_shards * k_loc >= min(k, n_p)
        # candidates, so the global top-k below is unaffected
        k_loc = min(k, n_local)
        # coarse="int8": the int8 first pass + shortlist runs PER
        # SHARD (each shard keeps its own top-L before refining), so
        # the merged result equals the flat backend's only when every
        # shard's shortlist covers its true top-k_loc.  The value
        # cache is rebuilt inside the shard_map trace (CoarseCodes is
        # derived data; no persisted row-sharded copy yet).
        plan = C.ScanPlan(
            metric=metric, k=k_loc, rerank=rerank, n_valid=n_valid,
            row_valid=valid, use_pallas=fused,
            coarse=coarse, shortlist=shortlist,
        )
        ls, li = C.execute_plan(
            model, prep, payload, plan, stats=stats, raw=raw
        )  # (m, k) fused local top-k (exact scores under rerank)
        gi = li + shard_lin * n_local
        # ---- merge: gather k-per-shard along every sharded axis ----
        for a in axes:
            ls = jax.lax.all_gather(ls, a, axis=1, tiled=True)
            gi = jax.lax.all_gather(gi, a, axis=1, tiled=True)
        fs, fi = jax.lax.top_k(ls, k)
        gids = jnp.take_along_axis(gi, fi, axis=1)
        return fs, jnp.where(jnp.isneginf(fs), -1, gids)

    # pytree prefixes: payload/stats/raw/valid leaves row-sharded,
    # queries replicated (stats/raw/valid may be None — empty pytrees,
    # spec unused)
    specs = dict(
        in_specs=(P(axes), P(axes), P(axes), P(axes), P()),
        out_specs=(P(), P()),
    )
    fn = jax.shard_map(local_then_merge, mesh=mesh, check_vma=False, **specs)
    jitted = jax.jit(fn)

    def search(payload, queries, stats=None, raw=None, valid=None):
        if rerank and raw is None:
            # loud, not a silent fall-back to un-reranked ASH scores
            raise ValueError(
                "this searcher was built with rerank > 0; pass raw= "
                "(row-sharded bf16 vectors aligned with the payload)"
            )
        return jitted(payload, stats, raw, valid, queries)

    return search


def make_sharded_search(
    mesh: Mesh,
    model: ASHModel,
    axes: tuple[str, ...],
    k: int = 10,
    *,
    metric: str = "dot",
    n_real: int | None = None,
    rerank: int = 0,
    fused: bool | None = None,
    coarse: str | None = None,
    shortlist: int | None = None,
):
    """Build a jitted (payload, queries) -> (scores, global_ids) searcher.

    ``axes``: mesh axes the database rows are sharded over (e.g.
    ("pod", "data", "model") shards over all 512 devices).

    The searcher also accepts ``stats=`` (row-sharded ``ASHStats``, so
    the fused l2/cos epilogues skip the per-call stats rebuild),
    ``raw=`` (row-sharded bf16 vectors enabling shard-local exact
    rerank when ``rerank > 0``) and ``valid=`` (a row-sharded bool
    validity bitmap — tombstoned rows score ``-inf`` / id -1 via the
    kernels' runtime mask operand, no recompile per mutation), all
    aligned with the padded payload.

    ``n_real``: rows beyond this global index are padding (from
    :func:`pad_to_multiple`) and are masked to score ``-inf`` / id -1.
    Optional override — by default the mask is derived per shard from
    the pad rows' ``cluster == -1`` sentinel, for every metric.

    ``fused``: None = auto (Pallas kernels on TPU, the
    identical-semantics jnp oracle on CPU); False = the retained
    pure-jnp reference scorers + materialize-then-``top_k`` (the
    bit-identity oracle for the fused local scan).

    ``coarse``/``shortlist``: opt into the symmetric int8 first pass
    on each shard's local scan (see ``common.ScanPlan``); the
    shortlist is per shard.
    """
    return _make_searcher(
        mesh, model, axes, k, metric=metric, n_real=n_real,
        from_prep=False, rerank=rerank, fused=fused,
        coarse=coarse, shortlist=shortlist,
    )


def make_sharded_search_prepped(
    mesh: Mesh,
    model: ASHModel,
    axes: tuple[str, ...],
    k: int = 10,
    *,
    metric: str = "dot",
    n_real: int | None = None,
    rerank: int = 0,
    fused: bool | None = None,
    coarse: str | None = None,
    shortlist: int | None = None,
):
    """Like :func:`make_sharded_search` but takes a precomputed
    ``QueryPrep`` (replicated) instead of raw queries, so the
    QUERY-COMPUTE projections run once on the host instead of
    redundantly on every shard — and so the serving engine's prepared
    buckets can feed this backend too."""
    return _make_searcher(
        mesh, model, axes, k, metric=metric, n_real=n_real,
        from_prep=True, rerank=rerank, fused=fused,
        coarse=coarse, shortlist=shortlist,
    )
