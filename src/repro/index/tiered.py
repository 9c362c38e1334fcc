"""Host-memory tiered IVF backend: beyond-HBM indexes.

Every other backend keeps the whole payload device-resident, so index
size — not code size — caps the corpus.  ``TieredIVFBackend`` keeps
only the model (landmarks == IVF centroids) and a byte-bounded hot set
of inverted lists on the device; packed codes, the ``ASHStats``
columns, the ``CoarseCodes`` values and the raw rerank rows live
per-list in host memory, sliced along the contiguous-list row order
``ivf._assemble`` produces.

A search lowers through ``common.plan_paged_probe``: resolve the probe
set (the same coarse top-k expression the HBM backend jits, run as its
own tiny jit so the probed lists are host-visible), look each probed
list up in the device-resident block cache (a byte-bounded
:class:`ByteLRU`), batch all misses into ONE
host→device transfer, and concatenate the resident blocks into an
ascending-list union ``IVFIndex`` whose inverted lists are rebased to
union-local rows.  Scoring then calls the SAME jitted entry points the
HBM backend compiles — ``ivf._score_probed`` for partial probes,
``ivf._full_scan`` for covering ones — so the traced graph is
identical and only the gather-source length differs.  That is the
load-bearing choice for bit-identity: the union preserves the global
row order restricted to the probed lists (ascending contiguous slices
→ a monotone index shift), so the probed lists' row ranges
(``ivf.probed_lists``) give slot-for-slot the same candidates, and
reusing the HBM backend's own jit (rather than a lookalike graph)
keeps XLA's fusion and rounding decisions aligned — a separately-jitted
clone of the same math has been observed to drift by one ulp under
some XLA host configurations.  Results are bit-identical to ``backend="ivf"`` at
equal probe sets for every option combination (rerank,
``coarse="int8"``, m=1 padding, covering nprobe, tombstones).

``nprobe >= nlist`` mirrors the HBM backend's dense full scan: the
union of ALL lists reproduces the global payload exactly (no pad
rows), scored under a dense plan with the tombstone bitmap as the
kernel mask operand.

Mutations delegate to the HBM IVF implementation: add/compact
materialize the host mirrors into an ``IVFIndex``, run ``IV._add`` /
``IV._compact`` (literally the same code, hence bitwise-identical
assembly), and re-host.  Deletes are host-side bitmap updates — cached
device blocks stay valid because tombstones live in a separate bitmap
(sliced per-union from a lazily refreshed device copy), masked
in-graph exactly like the HBM backend masks them.
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Any, Dict, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scoring as S
from repro.core.types import (
    ASHPayload, ASHStats, CoarseCodes,
)
from repro.index import common as C
from repro.index import ivf as IV
from repro.index.api import (
    IVFBackend, _model_arrays, register_backend,
)

DEFAULT_HOT_BYTES = 64 << 20

# host mirror columns, in block order; "raw" rides last when present
_FIELDS = (
    "codes", "scale", "offset", "cluster",
    "res_norm", "ip_x_mu", "x_sq", "cvalues", "ids",
)


def _default_nbytes(value: Any) -> int:
    """Byte size of a cached value: a single array-like, or any
    tuple/list/dict of array-likes (anything exposing ``.nbytes``)."""
    if hasattr(value, "nbytes"):
        return int(value.nbytes)
    if isinstance(value, dict):
        value = value.values()
    return sum(_default_nbytes(v) for v in value)


class ByteLRU:
    """LRU mapping hashable keys to values under a byte budget: the
    device-resident hot set of inverted-list blocks.

    ``max_bytes`` bounds the summed size of cached values (summed
    ``.nbytes`` over each value's arrays).  A value larger than the
    whole budget is admitted and immediately evicted — ``put`` never
    raises, a zero-byte budget simply caches nothing (every lookup
    misses, which is exactly the cold-cache semantics the paging tests
    rely on).

    ``hits`` / ``misses`` / ``evictions`` count ``get`` outcomes and
    evicted entries for ``tier_stats``.  Not internally locked: the
    tiered backend serializes access through the per-index mutation
    barrier.
    """

    def __init__(self, max_bytes: int):
        self.max_bytes = int(max_bytes)
        self._data: "OrderedDict[Any, Any]" = OrderedDict()
        self._sizes: Dict[Any, int] = {}
        self.nbytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._data)

    def keys(self) -> Iterator:
        return iter(self._data.keys())

    def get(self, key, default=None):
        """Look up ``key``; a hit refreshes its recency."""
        entry = self._data.get(key)
        if entry is None:
            self.misses += 1
            return default
        self._data.move_to_end(key)
        self.hits += 1
        return entry

    def put(self, key, value) -> None:
        """Insert or replace ``key``, then evict LRU-first until the
        budget holds."""
        old = self._data.pop(key, None)
        if old is not None:
            self.nbytes -= self._sizes.pop(key)
        size = _default_nbytes(value)
        self._data[key] = value
        self._sizes[key] = size
        self.nbytes += size
        while self._data and self.nbytes > self.max_bytes:
            key, _ = self._data.popitem(last=False)
            self.nbytes -= self._sizes.pop(key)
            self.evictions += 1

    def clear(self) -> None:
        """Drop every entry (counters keep their lifetime values)."""
        self._data.clear()
        self._sizes.clear()
        self.nbytes = 0

    def stats(self) -> Dict[str, int]:
        """Gauge snapshot (counters are lifetime, not interval)."""
        total = self.hits + self.misses
        return {
            "entries": len(self._data),
            "nbytes": self.nbytes,
            "max_bytes": self.max_bytes,
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": self.hits / total if total else 0.0,
            "evictions": self.evictions,
        }


class TieredState:
    """Host mirrors + device hot set of one tiered IVF index.

    NOT a pytree: the host arrays never enter a jit trace — per-list
    blocks are device_put on demand and cached in ``cache`` (list id →
    tuple of device arrays in ``_FIELDS`` order, + raw).  ``counts`` /
    ``starts`` give each list's contiguous global row range;
    ``invlists`` / ``live`` are exposed host-side so the serving
    engine's IVF cost model (probe sets, live list sizes, nprobe
    clamping) works on this state unchanged.
    """

    def __init__(self):  # populated by from_ivf
        raise TypeError("use TieredState.from_ivf()")

    @classmethod
    def from_ivf(
        cls, index: IV.IVFIndex, hot_bytes: int, carry=None
    ) -> "TieredState":
        """Host an ``IVFIndex``.  ``carry`` threads the lifetime cache
        and paging counters through a mutation re-host so gauges stay
        monotonic (the block cache itself is dropped: a re-sort moves
        rows between lists)."""
        st = object.__new__(cls)
        st.metric = index.metric
        st.max_list_len = int(index.max_list_len)
        st.next_id = index.next_id
        st.hot_bytes = int(hot_bytes)
        st.model = index.model  # device-resident, with the landmarks
        st.coarse_mean = index.coarse.mean  # GLOBAL corpus mean
        st.b = index.payload.b
        st.d = index.payload.d
        st.nlist = int(index.model.landmarks.shape[0])
        st.codes = np.asarray(index.payload.codes)
        st.scale = np.asarray(index.payload.scale)
        st.offset = np.asarray(index.payload.offset)
        st.cluster = np.asarray(index.payload.cluster)
        st.res_norm = np.asarray(index.stats.res_norm)
        st.ip_x_mu = np.asarray(index.stats.ip_x_mu)
        st.x_sq = np.asarray(index.stats.x_sq)
        st.cvalues = np.asarray(index.coarse.values)
        st.ids = np.asarray(index.ids)
        st.raw = None if index.raw is None else np.asarray(index.raw)
        st.live = (
            None if index.live is None
            else np.asarray(index.live).astype(bool)
        )
        st.counts, st.starts = IV.list_geometry(st.cluster, st.nlist)
        st._invlists = None
        st._invlists_dev = None
        st._live_dev = None
        st.cache = ByteLRU(st.hot_bytes)
        st.paged_rows = 0
        st.paged_bytes = 0
        st.transfers = 0
        st.total_bytes = sum(
            int(getattr(st, f).nbytes) for f in _FIELDS
        ) + (0 if st.raw is None else int(st.raw.nbytes))
        if carry is not None:
            st.cache.hits = carry.cache.hits
            st.cache.misses = carry.cache.misses
            st.cache.evictions = carry.cache.evictions
            st.paged_rows = carry.paged_rows
            st.paged_bytes = carry.paged_bytes
            st.transfers = carry.transfers
        return st

    @property
    def n(self) -> int:
        return self.codes.shape[0]

    @property
    def invlists(self) -> np.ndarray:
        """Padded inverted lists, host numpy — derived lazily from the
        contiguous geometry for engine compatibility (probe clamping,
        live list sizes); searches never touch it."""
        if self._invlists is None:
            self._invlists = IV.build_invlists(
                self.counts, self.starts, self.max_list_len
            )
        return self._invlists

    @property
    def live_dev(self):
        """Device copy of the tombstone bitmap (the in-graph mask
        operand), rebuilt lazily after each delete."""
        if self.live is None:
            return None
        if self._live_dev is None:
            self._live_dev = jnp.asarray(self.live)
        return self._live_dev

    @property
    def invlists_dev(self):
        """Device copy of the padded inverted lists (global rows) —
        the operand union searches rebase per probe set."""
        if self._invlists_dev is None:
            self._invlists_dev = jnp.asarray(self.invlists)
        return self._invlists_dev

    def materialize(self) -> IV.IVFIndex:
        """Device-resident ``IVFIndex`` with identical contents — the
        mutation path runs the HBM implementation on it and re-hosts,
        so assembly stays bitwise-equal to the HBM backend's."""
        return IV.IVFIndex(
            metric=self.metric,
            max_list_len=self.max_list_len,
            model=self.model,
            payload=ASHPayload(
                b=self.b, d=self.d,
                codes=jnp.asarray(self.codes),
                scale=jnp.asarray(self.scale),
                offset=jnp.asarray(self.offset),
                cluster=jnp.asarray(self.cluster),
            ),
            ids=jnp.asarray(self.ids),
            invlists=jnp.asarray(self.invlists),
            raw=None if self.raw is None else jnp.asarray(self.raw),
            stats=ASHStats(
                res_norm=jnp.asarray(self.res_norm),
                ip_x_mu=jnp.asarray(self.ip_x_mu),
                x_sq=jnp.asarray(self.x_sq),
            ),
            live=(
                None if self.live is None else jnp.asarray(self.live)
            ),
            next_id=self.next_id,
            coarse=CoarseCodes(
                values=jnp.asarray(self.cvalues), mean=self.coarse_mean
            ),
        )

    # -- the paging core ----------------------------------------------

    def _host_block(self, c: int) -> tuple:
        s = int(self.starts[c])
        e = s + int(self.counts[c])
        blk = tuple(getattr(self, f)[s:e] for f in _FIELDS)
        if self.raw is not None:
            blk += (self.raw[s:e],)
        return blk

    def fetch_blocks(self, lists) -> dict:
        """Resolve every list in ``lists`` to its device block: cache
        hits first, then ONE batched ``device_put`` for all misses.
        Blocks larger than the whole budget still serve this call —
        the cache just evicts them immediately (paging, not OOM)."""
        out = {}
        miss = []
        for c in lists:
            blk = self.cache.get(c)
            if blk is None:
                miss.append(c)
            else:
                out[c] = blk
        if miss:
            dev = jax.device_put([self._host_block(c) for c in miss])
            for c, blk in zip(miss, dev):
                blk = tuple(blk)
                out[c] = blk
                self.cache.put(c, blk)
                self.paged_rows += int(self.counts[c])
                self.paged_bytes += sum(int(a.nbytes) for a in blk)
            self.transfers += 1
        return out

    def union_index(self, lists, pad_rows: int) -> IV.IVFIndex:
        """Device-resident ``IVFIndex`` over the union of ``lists``
        (ascending ids) plus ``pad_rows`` zero rows.

        Ascending-list concatenation of contiguous slices reproduces
        the global row order restricted to the union, so the union's
        inverted lists are the global ones shifted by a per-list
        constant (rebased on device; non-union lists keep their global
        rows, which is fine — a probe set is always a subset of the
        union built from it).  Pad rows are never gathered (candidate
        entries are real union rows or -1), so they cannot perturb
        results.  The tombstone bitmap is sliced per-union from the
        device copy — NOT stored in the cached blocks — so deletes
        never invalidate the hot set."""
        blocks = self.fetch_blocks(lists)
        names = _FIELDS + (("raw",) if self.raw is not None else ())
        parts = {
            f: [blocks[c][i] for c in lists]
            for i, f in enumerate(names)
        }
        live = self.live_dev
        live_parts = None
        if live is not None:
            live_parts = [
                live[int(self.starts[c]):
                     int(self.starts[c]) + int(self.counts[c])]
                for c in lists
            ]
        if pad_rows:
            for f in names:
                host = getattr(self, f)
                fill = -1 if f == "ids" else 0
                parts[f].append(jnp.full(
                    (pad_rows,) + host.shape[1:], fill,
                    dtype=host.dtype,
                ))
            if live_parts is not None:
                live_parts.append(jnp.zeros(pad_rows, dtype=bool))
        u = {f: jnp.concatenate(parts[f], axis=0) for f in names}
        c_u = self.counts[np.asarray(lists, dtype=np.int64)]
        local_starts = np.concatenate(
            [[0], np.cumsum(c_u)[:-1]]
        ).astype(np.int64)
        if len(lists) == self.nlist:
            # all-lists union: local rows ARE global rows
            inv = self.invlists_dev
        else:
            delta = np.zeros(self.nlist, dtype=np.int32)
            delta[np.asarray(lists, dtype=np.int64)] = (
                local_starts - self.starts[np.asarray(lists)]
            ).astype(np.int32)
            inv = self.invlists_dev
            inv = jnp.where(
                inv >= 0, inv + jnp.asarray(delta)[:, None], -1
            )
        return IV.IVFIndex(
            metric=self.metric,
            max_list_len=self.max_list_len,
            model=self.model,
            payload=ASHPayload(
                b=self.b, d=self.d, codes=u["codes"],
                scale=u["scale"], offset=u["offset"],
                cluster=u["cluster"],
            ),
            ids=u["ids"],
            invlists=inv,
            raw=u.get("raw"),
            stats=ASHStats(
                res_norm=u["res_norm"], ip_x_mu=u["ip_x_mu"],
                x_sq=u["x_sq"],
            ),
            live=(
                None if live_parts is None
                else jnp.concatenate(live_parts, axis=0)
            ),
            next_id=None,
            coarse=CoarseCodes(
                values=u["cvalues"], mean=self.coarse_mean
            ),
        )


@functools.partial(jax.jit, static_argnames=("nprobe",))
def _probe_paged(ip_q_landmarks, landmark_sq_norms, nprobe: int):
    """Coarse assignment — the exact ``ivf._probe_lists`` expression
    (0.5 * ||mu||^2 is a power-of-two scale, so this is FMA-stable:
    fused and unfused lowerings round identically, and the
    host-visible probe set equals the one the HBM backend computes
    in-jit)."""
    coarse = ip_q_landmarks - 0.5 * landmark_sq_norms[None, :]
    return jax.lax.top_k(coarse, nprobe)[1]


@register_backend
class TieredIVFBackend:
    """Host-memory tiered inverted-file backend (see module doc)."""

    name = "tiered_ivf"
    default_nprobe = IVFBackend.default_nprobe

    @staticmethod
    def build(key, X, config, *, metric,
              hot_bytes: int = DEFAULT_HOT_BYTES, **opts):
        return TieredState.from_ivf(
            IV._build(key, X, config, metric=metric, **opts),
            hot_bytes,
        )

    @staticmethod
    def from_parts(model, payload, *, metric, raw=None,
                   hot_bytes: int = DEFAULT_HOT_BYTES):
        return TieredState.from_ivf(
            IVFBackend.from_parts(model, payload, metric=metric,
                                  raw=raw),
            hot_bytes,
        )

    @staticmethod
    def resolve_nprobe(state, nprobe):
        """Same normalization as the HBM backend (shared default, so
        requests group identically across the two)."""
        if nprobe is None:
            nprobe = TieredIVFBackend.default_nprobe
        return min(nprobe, state.nlist)

    # -- search -------------------------------------------------------

    @staticmethod
    def search(state, queries, *, k, nprobe=None, rerank=0, **opts):
        prep = S.prepare_queries(state.model, queries)
        return TieredIVFBackend.search_prepped(
            state, prep, k=k, nprobe=nprobe, rerank=rerank, **opts
        )

    @staticmethod
    def search_prepped(state, prep, *, k, nprobe=None, rerank=0,
                       coarse=None, shortlist=None):
        nprobe = TieredIVFBackend.resolve_nprobe(state, nprobe)
        if nprobe >= state.nlist:
            return TieredIVFBackend._full_scan(
                state, prep, k, rerank, coarse, shortlist
            )
        if prep.q.shape[0] == 1:
            # the HBM backend's m=1 -> 2 zero-row pad (bit-identity
            # between per-request and bucketed engine calls); the pad
            # row's probed lists join the union exactly like they join
            # the HBM gather
            s, i = TieredIVFBackend._gathered(
                state, IV._pad_single(prep), k, nprobe, rerank,
                coarse, shortlist,
            )
            return s[:1], i[:1]
        return TieredIVFBackend._gathered(
            state, prep, k, nprobe, rerank, coarse, shortlist
        )

    @staticmethod
    def _gathered(state, prep, k, nprobe, rerank, coarse, shortlist):
        probe = np.asarray(_probe_paged(
            prep.ip_q_landmarks, state.model.landmark_sq_norms, nprobe
        ))
        return TieredIVFBackend._execute_probe(
            state, prep, probe, k, rerank, coarse, shortlist
        )

    @staticmethod
    def _execute_probe(state, prep, probe, k, rerank, coarse,
                       shortlist):
        # plan the union on the host (which lists, padded length) ...
        pp = C.plan_paged_probe(
            probe, state.counts, state.starts, None,
            state.max_list_len, metric=state.metric, k=k,
            rerank=rerank, coarse=coarse, shortlist=shortlist,
        )
        uidx = state.union_index(
            pp.union_lists, pp.n_pad - pp.n_union
        )
        # ... then execute through the HBM backend's OWN jitted
        # gather (probed row ranges + tombstone mask): same traced
        # graph, so same fusion/rounding — see module doc
        return IV._score_probed(
            uidx, prep, jnp.asarray(probe, dtype=jnp.int32), k,
            rerank, coarse=coarse, shortlist=shortlist,
        )

    @staticmethod
    def _full_scan(state, prep, k, rerank, coarse, shortlist):
        # the all-lists union IS the global cluster-sorted payload
        # (contiguous lists, ascending, no pad); ivf._full_scan's
        # dense plan then matches the HBM route bit for bit
        uidx = state.union_index(tuple(range(state.nlist)), 0)
        return IV._full_scan(
            uidx, prep, k, rerank, coarse=coarse, shortlist=shortlist
        )

    @staticmethod
    def probe_sets(state, prep, nprobe=None):
        """Host-visible coarse assignment (the engine cost model's
        contract; see ``IVFBackend.probe_sets``)."""
        nprobe = TieredIVFBackend.resolve_nprobe(state, nprobe)
        return np.asarray(_probe_paged(
            prep.ip_q_landmarks, state.model.landmark_sq_norms, nprobe
        ))

    @staticmethod
    def search_probed(state, prep, probe, *, k, rerank=0, coarse=None,
                      shortlist=None):
        """Top-k over an explicit probed-list set; mirrors
        ``IVFBackend.search_probed`` including the m=1 pad-row probe."""
        probe = np.asarray(probe)
        if prep.q.shape[0] == 1:
            prep = IV._pad_single(prep)
            pad_probe = np.asarray(_probe_paged(
                prep.ip_q_landmarks, state.model.landmark_sq_norms,
                probe.shape[1],
            ))[1:]
            probe = np.concatenate([probe, pad_probe], axis=0)
            s, i = TieredIVFBackend._execute_probe(
                state, prep, probe, k, rerank, coarse, shortlist
            )
            return s[:1], i[:1]
        return TieredIVFBackend._execute_probe(
            state, prep, probe, k, rerank, coarse, shortlist
        )

    @staticmethod
    def list_sizes(state):
        """Live rows per list, host numpy (nlist,) — the engine's
        probe-cost bill.  Segment sums over the contiguous geometry
        (equivalent to ``IVFBackend.list_sizes`` on the padded
        invlists, without materializing them)."""
        if state.live is None:
            return state.counts.astype(np.int64)
        csum = np.concatenate(
            [[0], np.cumsum(state.live.astype(np.int64))]
        )
        ends = state.starts + state.counts
        return (csum[ends] - csum[state.starts]).astype(np.int64)

    # -- mutations (delegated to the HBM implementation) ---------------

    @staticmethod
    def add(state, X_new):
        return TieredState.from_ivf(
            IV._add(state.materialize(), X_new),
            state.hot_bytes, carry=state,
        )

    @staticmethod
    def delete(state, del_ids):
        # host-side bitmap update; cached device blocks stay valid —
        # tombstones are dropped to -1 in the candidate rows pre-DMA
        # (plan_paged_probe), never read out of the blocks
        new_live, removed = C.mark_deleted(
            state.ids, state.live, del_ids, state.n
        )
        if removed == 0:
            return state, 0
        state.live = np.asarray(new_live).astype(bool)
        state._live_dev = None
        return state, removed

    @staticmethod
    def compact(state):
        if state.live is None:
            return state
        return TieredState.from_ivf(
            IV._compact(state.materialize()),
            state.hot_bytes, carry=state,
        )

    # -- introspection / persistence ----------------------------------

    @staticmethod
    def model_of(state):
        return state.model

    @staticmethod
    def payload_of(state):
        return ASHPayload(
            b=state.b, d=state.d, codes=state.codes,
            scale=state.scale, offset=state.offset,
            cluster=state.cluster,
        )

    @staticmethod
    def stats_of(state):
        return ASHStats(
            res_norm=state.res_norm, ip_x_mu=state.ip_x_mu,
            x_sq=state.x_sq,
        )

    @staticmethod
    def live_of(state):
        return state.live

    @staticmethod
    def ids_of(state):
        return state.ids

    @staticmethod
    def next_id_of(state):
        return C.effective_next_id(
            state.next_id, state.ids, state.n
        )

    @staticmethod
    def resident_mask(state) -> np.ndarray:
        """(nlist,) bool: which lists are device-resident right now —
        the engine bills non-resident lists at the paging surcharge."""
        mask = np.zeros(state.nlist, dtype=bool)
        keys = list(state.cache.keys())
        if keys:
            mask[np.asarray(keys, dtype=np.int64)] = True
        return mask

    @staticmethod
    def tier_stats(state) -> dict:
        """Gauge snapshot for ``snapshot()["tier"]`` (lifetime
        counters, carried across mutation re-hosts)."""
        cs = state.cache.stats()
        return {
            "hits": cs["hits"],
            "misses": cs["misses"],
            "hit_rate": round(cs["hit_rate"], 4),
            "evictions": cs["evictions"],
            "resident_lists": cs["entries"],
            "nlist": state.nlist,
            "resident_bytes": cs["nbytes"],
            "hot_bytes": state.hot_bytes,
            "total_bytes": state.total_bytes,
            "paged_rows": state.paged_rows,
            "paged_bytes": state.paged_bytes,
            "transfers": state.transfers,
        }

    @staticmethod
    def to_arrays(state):
        # identical layout to IVFBackend.to_arrays (the host mirrors
        # ARE the arrays), plus the hot-set budget in the meta so a
        # load reconstructs the same tier shape
        arrays = {
            **_model_arrays(state.model),
            "payload.codes": state.codes,
            "payload.scale": state.scale,
            "payload.offset": state.offset,
            "payload.cluster": state.cluster,
            "stats.res_norm": state.res_norm,
            "stats.ip_x_mu": state.ip_x_mu,
            "stats.x_sq": state.x_sq,
            "ids": state.ids,
            "invlists": state.invlists,
        }
        if state.raw is not None:
            arrays["raw"] = state.raw
        if state.live is not None:
            arrays["live"] = state.live
        meta = {
            "max_list_len": state.max_list_len,
            "hot_bytes": state.hot_bytes,
        }
        if state.next_id is not None:
            meta["next_id"] = int(state.next_id)
        return arrays, meta

    @staticmethod
    def from_arrays(arrays, meta, config, metric, *, hot_bytes=None,
                    **opts):
        ivf = IVFBackend.from_arrays(
            arrays, meta, config, metric, **opts
        )
        if hot_bytes is None:
            hot_bytes = meta.get("hot_bytes", DEFAULT_HOT_BYTES)
        return TieredState.from_ivf(ivf, hot_bytes)
