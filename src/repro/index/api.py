"""Unified ``AshIndex`` facade: one build/search/persist surface over
the flat, IVF and sharded backends.

The paper's value proposition is a single encoder-decoder payload
(Table 1) serving dot/L2/cosine search at every scale; this module is
the single entry point over it::

    index = AshIndex.build(key, X, ASHConfig(b=2, d=64, n_landmarks=64),
                           backend="ivf", metric="l2", keep_raw=True)
    scores, ids = index.search(queries, k=10, nprobe=16, rerank=100)
    index.add(X_new)                    # incremental ingestion
    index.delete([3, 17])               # tombstone rows by user id
    index.compact(max_dead_fraction=0.2)  # evict tombstones past 20%
    ids = index.stage_add(X_more)       # buffer for batched ingestion
    index.apply_pending()               # one re-sort for the batch
    index.save("/tmp/idx")              # npz arrays + JSON config
    index = AshIndex.load("/tmp/idx")   # bit-identical search results

Backends are pluggable via :func:`register_backend`; all share the
metric dispatcher and exact-rerank pipeline of ``repro.index.common``,
so every backend returns higher-is-better scores and id ``-1`` for
missing candidates.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import pathlib
import shutil
import zlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ash as A
from repro.core import scoring as S
from repro.core.types import (
    ASHConfig, ASHModel, ASHPayload, ASHStats, QueryPrep,
)
from repro.index import common as C
from repro.index import distributed as DX
from repro.index import flat as F
from repro.index import ivf as IV
from repro.testing import faults

FORMAT_VERSION = 1


class CorruptIndexError(ValueError):
    """A saved index failed an integrity check on load.

    Subclasses ``ValueError`` so pre-existing ``except ValueError``
    callers keep working; carries *where* and *which check* so an
    operator can tell a half-written save from bit rot."""

    def __init__(self, path, check: str):
        self.path = str(path)
        self.check = check
        super().__init__(f"corrupt index at {self.path}: {check}")


_BACKENDS: dict[str, type] = {}


def register_backend(cls):
    """Class decorator: register an index backend under ``cls.name``."""
    _BACKENDS[cls.name] = cls
    return cls


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


def _get_backend(name: str):
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; available: {available_backends()}"
        ) from None


# ---------------------------------------------------------------------------
# Array (de)serialization — numpy .npz with bf16 stored as uint16 views
# ---------------------------------------------------------------------------

_MODEL_FIELDS = (
    "W", "landmarks", "W_landmarks", "landmark_sq_norms",
    "bias_rho", "bias_beta",
)
_PAYLOAD_FIELDS = ("codes", "scale", "offset", "cluster")
_STATS_FIELDS = ("res_norm", "ip_x_mu", "x_sq")


_BF16 = np.dtype(jnp.bfloat16)


def _encode_array(a) -> tuple[np.ndarray, str]:
    """jax/numpy array -> (savez-safe numpy array, dtype tag).

    numpy can't serialize the ml_dtypes bfloat16 descr, so bf16 arrays
    are stored as uint16 bit patterns and tagged for exact restore."""
    a = np.asarray(a)
    if a.dtype == _BF16:
        return a.view(np.uint16), "bfloat16"
    return a, str(a.dtype)


def _decode_array(a: np.ndarray, tag: str) -> jax.Array:
    if tag == "bfloat16":
        return jnp.asarray(a.view(_BF16))
    return jnp.asarray(a)


# -- crash-safe on-disk layout ----------------------------------------
#
# A saved index is two files under one directory: arrays.npz and
# config.json (the manifest).  The manifest carries a crc32 per npz
# entry, computed over the encoded bytes, so load() can refuse bit rot
# before deserializing garbage.  Writes are atomic at every boundary:
#
#   fresh target   — write into a dot-prefixed temp dir next to it,
#                    fsync files + dirs, one os.replace of the dir;
#   existing target — write arrays.new.npz + config.new.json, fsync,
#                    then os.replace each (arrays first).  A crash
#                    between the two renames leaves new arrays under
#                    the old manifest; load() detects the checksum
#                    mismatch and rolls FORWARD from config.new.json
#                    (both .new files were durable before any rename).

_FAULT_SAVE_REPLACE = faults.point("save.replace")
_FAULT_SAVE_BETWEEN = faults.point("save.between_replace")


def _fsync_file(path: pathlib.Path) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(path: pathlib.Path) -> None:
    try:
        fd = os.open(path, os.O_RDONLY | getattr(os, "O_DIRECTORY", 0))
    except OSError:
        return  # platform without directory fsync
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _write_npz(path: pathlib.Path, encoded: dict[str, np.ndarray]) -> None:
    with open(path, "wb") as f:
        np.savez(f, **encoded)  # file object: no .npz suffix games
        f.flush()
        os.fsync(f.fileno())


def _write_manifest(path: pathlib.Path, meta: dict[str, Any]) -> None:
    with open(path, "w") as f:
        f.write(json.dumps(meta, indent=2))
        f.flush()
        os.fsync(f.fileno())


def _save_fresh(p: pathlib.Path, encoded, meta) -> None:
    p.parent.mkdir(parents=True, exist_ok=True)
    tmp = p.parent / f".{p.name}.tmp-{os.getpid()}"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir()
    _write_npz(tmp / "arrays.npz", encoded)
    _write_manifest(tmp / "config.json", meta)
    _fsync_dir(tmp)
    faults.fire(_FAULT_SAVE_REPLACE)
    os.replace(tmp, p)
    _fsync_dir(p.parent)


def _save_over(p: pathlib.Path, encoded, meta) -> None:
    _write_npz(p / "arrays.new.npz", encoded)
    _write_manifest(p / "config.new.json", meta)
    _fsync_dir(p)
    os.replace(p / "arrays.new.npz", p / "arrays.npz")
    faults.fire(_FAULT_SAVE_BETWEEN)
    os.replace(p / "config.new.json", p / "config.json")
    _fsync_dir(p)


def _read_index_files(
    p: pathlib.Path, manifest: str = "config.json"
) -> tuple[dict[str, Any], dict[str, np.ndarray]]:
    """Read + integrity-check one (manifest, arrays.npz) pair; returns
    (meta, still-encoded arrays).  Every failure mode — missing file,
    bad JSON, unreadable zip, missing entries, checksum mismatch —
    raises :class:`CorruptIndexError` naming the failed check."""
    mpath = p / manifest
    if not mpath.is_file():
        raise CorruptIndexError(p, f"{manifest} missing")
    try:
        meta = json.loads(mpath.read_text())
    except (ValueError, OSError) as e:
        raise CorruptIndexError(p, f"{manifest} unreadable: {e}") from e
    if not isinstance(meta, dict) or "format_version" not in meta:
        raise CorruptIndexError(p, f"{manifest} is not an index manifest")
    if meta["format_version"] != FORMAT_VERSION:
        raise CorruptIndexError(
            p,
            f"format_version {meta['format_version']} != {FORMAT_VERSION}",
        )
    apath = p / "arrays.npz"
    if not apath.is_file():
        raise CorruptIndexError(p, "arrays.npz missing")
    try:
        with np.load(apath) as npz:
            encoded = {name: np.asarray(npz[name]) for name in npz.files}
    except CorruptIndexError:
        raise
    except Exception as e:  # BadZipFile / ValueError / zlib / EOF / OS
        raise CorruptIndexError(p, f"arrays.npz unreadable: {e}") from e
    for name in encoded:
        if name not in meta.get("dtypes", {}):
            raise CorruptIndexError(
                p, f"arrays.npz entry {name!r} missing from manifest dtypes"
            )
    checksums = meta.get("checksums")
    if checksums is not None:  # pre-manifest saves have none
        missing = set(checksums) - set(encoded)
        if missing:
            raise CorruptIndexError(
                p, f"arrays.npz missing entries {sorted(missing)}"
            )
        extra = set(encoded) - set(checksums)
        if extra:
            raise CorruptIndexError(
                p, f"arrays.npz has unmanifested entries {sorted(extra)}"
            )
        for name, want in checksums.items():
            got = zlib.crc32(np.ascontiguousarray(encoded[name]).tobytes())
            if got != want:
                raise CorruptIndexError(
                    p,
                    f"checksum mismatch for {name!r}: "
                    f"crc32 {got:#010x} != manifest {want:#010x}",
                )
    return meta, encoded


def _read_index_dir(
    p: pathlib.Path,
) -> tuple[dict[str, Any], dict[str, np.ndarray]]:
    """:func:`_read_index_files` + roll-forward: if the live pair is
    inconsistent but a durable ``config.new.json`` matches the arrays
    (crash between an over-save's two renames), finish that save and
    load it; otherwise re-raise the original corruption error."""
    try:
        return _read_index_files(p)
    except CorruptIndexError as err:
        if not (p / "config.new.json").is_file():
            raise
        try:
            meta, encoded = _read_index_files(p, "config.new.json")
        except CorruptIndexError:
            raise err from None
        os.replace(p / "config.new.json", p / "config.json")
        (p / "arrays.new.npz").unlink(missing_ok=True)
        _fsync_dir(p)
        return meta, encoded


def _model_arrays(model: ASHModel) -> dict[str, Any]:
    return {f"model.{f}": getattr(model, f) for f in _MODEL_FIELDS}


def _model_from_arrays(
    arrays: dict[str, jax.Array], config: ASHConfig
) -> ASHModel:
    return ASHModel(
        config=config,
        **{f: arrays[f"model.{f}"] for f in _MODEL_FIELDS},
    )


def _payload_arrays(payload: ASHPayload) -> dict[str, Any]:
    return {f"payload.{f}": getattr(payload, f) for f in _PAYLOAD_FIELDS}


def _payload_from_arrays(
    arrays: dict[str, jax.Array], config: ASHConfig
) -> ASHPayload:
    return ASHPayload(
        b=config.b,
        d=config.d,
        **{f: arrays[f"payload.{f}"] for f in _PAYLOAD_FIELDS},
    )


def _stats_arrays(stats: Optional[ASHStats]) -> dict[str, Any]:
    if stats is None:
        return {}
    return {f"stats.{f}": getattr(stats, f) for f in _STATS_FIELDS}


def _stats_from_arrays(
    arrays: dict[str, jax.Array], model: ASHModel, payload: ASHPayload
) -> ASHStats:
    """Restore persisted stats; rebuild from the payload when loading
    a pre-stats save.  ``x_sq`` is derived again from the stored
    ``res_norm`` and ``ip_x_mu`` (no unpack), so a save that holds an
    older estimate of it loads to the statistics of a fresh build."""
    if all(f"stats.{f}" in arrays for f in _STATS_FIELDS):
        return S.restore_stats(
            model, payload.cluster, arrays["stats.res_norm"],
            arrays["stats.ip_x_mu"],
        )
    return S.payload_stats(model, payload)


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------


@register_backend
class FlatBackend:
    """Exhaustive scan over the whole payload."""

    name = "flat"

    @staticmethod
    def build(key, X, config, *, metric, **opts):
        return F._build(key, X, config, metric=metric, **opts)

    @staticmethod
    def from_parts(model, payload, *, metric, raw=None):
        return F.FlatIndex(
            metric=metric, model=model, payload=payload, raw=raw,
            stats=S.payload_stats(model, payload),
            coarse=S.coarse_codes(payload),
        )

    @staticmethod
    def search(state, queries, *, k, nprobe=None, rerank=0, **opts):
        del nprobe  # no coarse routing in a flat scan
        return F._search(state, queries, k=k, rerank=rerank, **opts)

    @staticmethod
    def search_prepped(state, prep, *, k, nprobe=None, rerank=0, **opts):
        del nprobe
        return F._search_prepped(state, prep, k=k, rerank=rerank, **opts)

    @staticmethod
    def add(state, X_new):
        return F._add(state, X_new)

    @staticmethod
    def delete(state, ids):
        return F._delete(state, ids)

    @staticmethod
    def compact(state):
        return F._compact(state)

    @staticmethod
    def model_of(state):
        return state.model

    @staticmethod
    def payload_of(state):
        return state.payload

    @staticmethod
    def stats_of(state):
        return state.stats

    @staticmethod
    def live_of(state):
        return state.live

    @staticmethod
    def ids_of(state):
        return state.ids

    @staticmethod
    def next_id_of(state):
        return C.effective_next_id(
            state.next_id, state.ids, state.payload.n
        )

    @staticmethod
    def to_arrays(state):
        arrays = {
            **_model_arrays(state.model),
            **_payload_arrays(state.payload),
            **_stats_arrays(state.stats),
        }
        if state.raw is not None:
            arrays["raw"] = state.raw
        if state.ids is not None:
            arrays["ids"] = state.ids
        if state.live is not None:
            arrays["live"] = state.live
        meta = {}
        if state.next_id is not None:
            meta["next_id"] = int(state.next_id)
        return arrays, meta

    @staticmethod
    def from_arrays(arrays, meta, config, metric, **opts):
        model = _model_from_arrays(arrays, config)
        payload = _payload_from_arrays(arrays, config)
        return F.FlatIndex(
            metric=metric,
            model=model,
            payload=payload,
            raw=arrays.get("raw"),
            stats=_stats_from_arrays(arrays, model, payload),
            ids=arrays.get("ids"),
            live=arrays.get("live"),
            next_id=meta.get("next_id"),
            # derived from the payload deterministically, so rebuild
            # on load (== the saved index's cache) instead of persisting
            coarse=S.coarse_codes(payload),
        )


@register_backend
class IVFBackend:
    """Inverted-file routing over the landmark coarse quantizer."""

    name = "ivf"
    default_nprobe = 8

    @staticmethod
    def build(key, X, config, *, metric, **opts):
        return IV._build(key, X, config, metric=metric, **opts)

    @staticmethod
    def from_parts(model, payload, *, metric, raw=None):
        ids = jnp.arange(payload.n, dtype=jnp.int32)
        return IV._assemble(metric, model, payload, ids, raw)

    @staticmethod
    def resolve_nprobe(state, nprobe):
        """Effective nprobe: default applied, clamped to the invlist
        count.  Public so the serving engine can normalize request
        nprobe before grouping (distinct values above nlist route
        identically and must share one group/trace)."""
        if nprobe is None:
            nprobe = IVFBackend.default_nprobe
        return min(nprobe, state.invlists.shape[0])

    @staticmethod
    def search(state, queries, *, k, nprobe=None, rerank=0, **opts):
        nprobe = IVFBackend.resolve_nprobe(state, nprobe)
        return IV._search(
            state, queries, k=k, nprobe=nprobe, rerank=rerank, **opts
        )

    @staticmethod
    def search_prepped(state, prep, *, k, nprobe=None, rerank=0, **opts):
        nprobe = IVFBackend.resolve_nprobe(state, nprobe)
        return IV._search_prepped(
            state, prep, k=k, nprobe=nprobe, rerank=rerank, **opts
        )

    @staticmethod
    def probe_sets(state, prep, nprobe=None):
        """Host-visible coarse assignment: (m, nprobe) int32 probed
        list ids per query, best-first — exactly the lists the
        gathered search scans at that nprobe (a smaller nprobe's set
        is a column prefix).  The serving engine's candidate-row cost
        model consumes these to dedup lists shared across a batch
        group before splitting it against a row budget."""
        nprobe = IVFBackend.resolve_nprobe(state, nprobe)
        return np.asarray(IV._probe_lists(state, prep, nprobe))

    @staticmethod
    def search_probed(state, prep, probe, *, k, rerank=0, **opts):
        """Top-k over an explicit probed-list set (budgeted gather
        entry point); ``probe`` as returned by :meth:`probe_sets`."""
        return IV._search_probed(
            state, prep, jnp.asarray(probe, dtype=jnp.int32),
            k=k, rerank=rerank, **opts,
        )

    @staticmethod
    def list_sizes(state):
        """Live row count per inverted list, host numpy (nlist,):
        what probing a list costs the gathered scan.  Tombstoned rows
        are dropped pre-DMA, so they bill as zero."""
        inv = np.asarray(state.invlists)
        valid = inv >= 0
        if state.live is not None:
            valid &= np.asarray(state.live)[np.maximum(inv, 0)]
        return valid.sum(axis=1).astype(np.int64)

    @staticmethod
    def add(state, X_new):
        return IV._add(state, X_new)

    @staticmethod
    def delete(state, ids):
        return IV._delete(state, ids)

    @staticmethod
    def compact(state):
        return IV._compact(state)

    @staticmethod
    def model_of(state):
        return state.model

    @staticmethod
    def payload_of(state):
        return state.payload

    @staticmethod
    def stats_of(state):
        return state.stats

    @staticmethod
    def live_of(state):
        return state.live

    @staticmethod
    def ids_of(state):
        return state.ids

    @staticmethod
    def next_id_of(state):
        return C.effective_next_id(
            state.next_id, state.ids, state.payload.n
        )

    @staticmethod
    def to_arrays(state):
        arrays = {
            **_model_arrays(state.model),
            **_payload_arrays(state.payload),
            **_stats_arrays(state.stats),
            "ids": state.ids,
            "invlists": state.invlists,
        }
        if state.raw is not None:
            arrays["raw"] = state.raw
        if state.live is not None:
            arrays["live"] = state.live
        meta = {"max_list_len": state.max_list_len}
        if state.next_id is not None:
            meta["next_id"] = int(state.next_id)
        return arrays, meta

    @staticmethod
    def from_arrays(arrays, meta, config, metric, **opts):
        model = _model_from_arrays(arrays, config)
        payload = _payload_from_arrays(arrays, config)
        return IV.IVFIndex(
            metric=metric,
            max_list_len=int(meta["max_list_len"]),
            model=model,
            payload=payload,
            ids=arrays["ids"],
            invlists=arrays["invlists"],
            raw=arrays.get("raw"),
            stats=_stats_from_arrays(arrays, model, payload),
            live=arrays.get("live"),
            next_id=meta.get("next_id"),
            coarse=S.coarse_codes(payload),  # derived; never persisted
        )


@dataclasses.dataclass
class ShardedState:
    """Host copy of the payload + its device-sharded placement.

    The host copies (unpadded) are kept for add()/delete()/save(); the
    padded, row-sharded copies are what searches scan: the payload, its
    encode-time ``ASHStats`` (fused l2/cos epilogue inputs), — when
    built with ``keep_raw`` — a bf16 raw-vector shard enabling
    shard-local exact rerank, and — once rows are deleted — a validity
    bitmap shard feeding the kernels' runtime mask operand.  Compiled
    searchers are cached per (k, rerank) and invalidated when the
    placement changes; deletes only re-shard the (tiny) bitmap.
    """

    metric: str
    model: ASHModel
    payload: ASHPayload  # unpadded, host-side source of truth
    mesh: Any
    axes: tuple[str, ...]
    raw: Optional[jax.Array] = None  # unpadded bf16 rows (rerank)
    stats: Optional[ASHStats] = None  # unpadded; built when missing
    ids: Optional[jax.Array] = None  # user ids; None = identity
    live: Optional[jax.Array] = None  # validity bitmap; None = all live
    next_id: Optional[int] = None  # id of the next added row
    sharded: ASHPayload = dataclasses.field(init=False)
    sharded_stats: ASHStats = dataclasses.field(init=False)
    sharded_raw: Optional[jax.Array] = dataclasses.field(init=False)
    sharded_valid: Optional[jax.Array] = dataclasses.field(init=False)
    searchers: dict = dataclasses.field(init=False, default_factory=dict)

    def __post_init__(self):
        # the unpadded payload is the gather-safe source of truth: the
        # pad sentinel (cluster == -1) must only ever exist on the
        # device-side padded copy, where row masking precedes use.
        # Validated once here — add() only appends encode() output,
        # whose cluster assignments are always valid
        cluster = np.asarray(self.payload.cluster)
        if cluster.size and int(cluster.min()) < 0:
            raise ValueError(
                "pad-sentinel cluster ids in the host payload; "
                "construct ShardedState from an unpadded payload"
            )
        if self.stats is None:
            self.stats = S.payload_stats(self.model, self.payload)
        self.place()

    def _pad(self) -> int:
        mult = math.prod(self.mesh.shape[a] for a in self.axes)
        return (-self.payload.n) % mult

    def place(self):
        mult = math.prod(self.mesh.shape[a] for a in self.axes)
        padded = DX.pad_to_multiple(self.payload, mult)
        pad = padded.n - self.payload.n
        self.sharded = DX.shard_rows(self.mesh, padded, self.axes)
        self.sharded_stats = DX.shard_rows(
            self.mesh, DX.pad_stats(self.stats, pad), self.axes
        )
        self.sharded_raw = None if self.raw is None else DX.shard_rows(
            self.mesh,
            jnp.pad(self.raw, ((0, pad), (0, 0))),
            self.axes,
        )
        self.place_valid()
        self.searchers = {}

    def place_valid(self):
        """(Re-)shard just the validity bitmap — the only placement a
        delete touches (payload/stats/raw shards and cached searcher
        traces survive; the mask is a runtime kernel operand)."""
        if self.live is None:
            self.sharded_valid = None
            return
        self.sharded_valid = DX.shard_rows(
            self.mesh,
            jnp.pad(jnp.asarray(self.live).astype(bool),
                    (0, self._pad())),
            self.axes,
        )

    def searcher(self, k: int, rerank: int = 0,
                 coarse: Optional[str] = None,
                 shortlist: Optional[int] = None):
        """(payload, QueryPrep) -> (scores, ids) searcher, cached per
        (k, rerank shortlist, coarse mode, coarse shortlist).

        Prep-based so the direct and engine paths share one compiled
        function (queries are prepped outside the shard_map, once,
        instead of redundantly on every shard)."""
        key = (k, rerank, coarse, shortlist)
        if key not in self.searchers:
            self.searchers[key] = DX.make_sharded_search_prepped(
                self.mesh, self.model, self.axes, k,
                metric=self.metric, n_real=self.payload.n,
                rerank=rerank, coarse=coarse, shortlist=shortlist,
            )
        return self.searchers[key]


def _default_mesh(axes: tuple[str, ...]):
    from jax.sharding import Mesh

    devs = np.array(jax.devices())
    shape = (len(devs),) + (1,) * (len(axes) - 1)
    return Mesh(devs.reshape(shape), axes)


@register_backend
class ShardedBackend:
    """Scatter-gather search over a device mesh (wraps
    ``distributed.make_sharded_search`` behind the common signature)."""

    name = "sharded"

    @staticmethod
    def _resolve_mesh(mesh, axes):
        axes = tuple(axes) if axes is not None else ("data",)
        if mesh is None:
            mesh = _default_mesh(axes)
        return mesh, axes

    @staticmethod
    def build(key, X, config, *, metric, mesh=None, axes=None,
              model=None, learned=True, keep_raw=False, **train_kw):
        mesh, axes = ShardedBackend._resolve_mesh(mesh, axes)
        model, payload, raw = C.train_and_encode(
            key, X, config, model=model, learned=learned,
            keep_raw=keep_raw, **train_kw,
        )
        return C.build_stats(lambda: ShardedState(
            metric=metric, model=model, payload=payload, mesh=mesh,
            axes=axes, raw=raw,
        ))

    @staticmethod
    def from_parts(model, payload, *, metric, raw=None, mesh=None,
                   axes=None):
        mesh, axes = ShardedBackend._resolve_mesh(mesh, axes)
        return ShardedState(
            metric=metric, model=model, payload=payload,
            mesh=mesh, axes=axes, raw=raw,
        )

    @staticmethod
    def search(state, queries, *, k, nprobe=None, rerank=0,
               coarse=None, shortlist=None):
        prep = S.prepare_queries(state.model, queries)
        return ShardedBackend.search_prepped(
            state, prep, k=k, nprobe=nprobe, rerank=rerank,
            coarse=coarse, shortlist=shortlist,
        )

    @staticmethod
    def search_prepped(state, prep, *, k, nprobe=None, rerank=0,
                       coarse=None, shortlist=None):
        del nprobe  # no list routing in the scatter-gather scan
        if rerank and state.raw is None:
            raise ValueError(
                "rerank on the sharded backend requires keep_raw=True "
                "(bf16 raw shards are distributed with the payload)"
            )
        s, rows = state.searcher(k, rerank, coarse, shortlist)(
            state.sharded, prep,
            stats=state.sharded_stats, raw=state.sharded_raw,
            valid=state.sharded_valid,
        )
        if state.ids is None:
            return s, rows
        # map global payload rows to user ids after the merge (a (m, k)
        # gather; monotonic ids keep the merge's tie order intact)
        return s, jnp.where(
            rows < 0, -1, state.ids[jnp.maximum(rows, 0)]
        )

    @staticmethod
    def add(state, X_new):
        # mirror build: encode, then recompute stats AND raw for the
        # appended rows before any re-placement — a partial update
        # (e.g. raw missing for the tail) would silently break
        # shard-local rerank after the next place()
        payload_new = A.encode(state.model, X_new)
        n_new = payload_new.n
        nid = C.effective_next_id(
            state.next_id, state.ids, state.payload.n
        )
        state.payload = C.concat_payloads(state.payload, payload_new)
        # __post_init__ guarantees stats is never None, so the concat
        # always yields the full stats block
        state.stats = C.concat_stats(
            state.stats, S.payload_stats(state.model, payload_new)
        )
        if state.raw is not None:
            state.raw = jnp.concatenate(
                [state.raw, X_new.astype(jnp.bfloat16)], axis=0
            )
        if state.ids is not None:
            state.ids = jnp.concatenate(
                [state.ids, nid + jnp.arange(n_new, dtype=jnp.int32)]
            )
        if state.live is not None:
            state.live = jnp.concatenate(
                [state.live, jnp.ones((n_new,), bool)]
            )
        if state.next_id is not None:
            state.next_id = nid + n_new
        state.place()
        return state

    @staticmethod
    def delete(state, ids):
        new_live, removed = C.mark_deleted(
            state.ids, state.live, ids, state.payload.n
        )
        if removed:
            state.live = jnp.asarray(new_live)
            state.place_valid()  # payload/raw/stats shards untouched
        return state, removed

    @staticmethod
    def compact(state):
        if state.live is None:
            return state
        live_np = np.asarray(state.live).astype(bool)
        if live_np.all():
            state.live = None
            state.place_valid()
            return state
        if not live_np.any():
            raise ValueError(
                "compact() would evict every row; an empty index "
                "cannot be searched — keep at least one live row or "
                "rebuild"
            )
        nid = C.effective_next_id(
            state.next_id, state.ids, state.payload.n
        )
        keep = jnp.asarray(np.nonzero(live_np)[0].astype(np.int32))
        state.ids = (
            keep if state.ids is None else state.ids[keep]
        ).astype(jnp.int32)
        state.next_id = nid
        state.payload = C.gather_payload(state.payload, keep)
        state.stats = C.take_stats(state.stats, keep)
        if state.raw is not None:
            state.raw = state.raw[keep]
        state.live = None
        state.place()
        return state

    @staticmethod
    def model_of(state):
        return state.model

    @staticmethod
    def payload_of(state):
        return state.payload

    @staticmethod
    def stats_of(state):
        return state.stats

    @staticmethod
    def live_of(state):
        return state.live

    @staticmethod
    def ids_of(state):
        return state.ids

    @staticmethod
    def next_id_of(state):
        return C.effective_next_id(
            state.next_id, state.ids, state.payload.n
        )

    @staticmethod
    def to_arrays(state):
        arrays = {
            **_model_arrays(state.model),
            **_payload_arrays(state.payload),
            **_stats_arrays(state.stats),
        }
        if state.raw is not None:
            arrays["raw"] = state.raw
        if state.ids is not None:
            arrays["ids"] = state.ids
        if state.live is not None:
            arrays["live"] = state.live
        meta = {"axes": list(state.axes)}
        if state.next_id is not None:
            meta["next_id"] = int(state.next_id)
        return arrays, meta

    @staticmethod
    def from_arrays(arrays, meta, config, metric, *, mesh=None,
                    axes=None):
        axes = tuple(axes or meta.get("axes") or ("data",))
        mesh, axes = ShardedBackend._resolve_mesh(mesh, axes)
        model = _model_from_arrays(arrays, config)
        payload = _payload_from_arrays(arrays, config)
        return ShardedState(
            metric=metric,
            model=model,
            payload=payload,
            mesh=mesh,
            axes=axes,
            raw=arrays.get("raw"),
            stats=_stats_from_arrays(arrays, model, payload),
            ids=arrays.get("ids"),
            live=arrays.get("live"),
            next_id=meta.get("next_id"),
        )


# ---------------------------------------------------------------------------
# The facade
# ---------------------------------------------------------------------------


class AshIndex:
    """One lifecycle — build / search / add / delete / compact / save /
    load — over every backend.  See the module docstring for the
    canonical usage.

    Mutation model: :meth:`delete` tombstones rows in place (a validity
    bitmap threaded into the scan kernels' runtime mask operand — no
    recompilation, deleted ids can never surface); :meth:`compact`
    rewrites codes/stats/raw to evict tombstones past a dead-fraction
    threshold; :meth:`stage_add` buffers rows host-side (ids assigned
    immediately) until :meth:`apply_pending` ingests them in ONE
    backend add — the serving engine's batched-mutation path, which
    amortizes the IVF re-sort / sharded re-placement across a batch.
    Tombstones and the pending-add buffer both survive save/load.
    """

    def __init__(self, backend: str, metric: str, state):
        self._backend = _get_backend(backend)
        self._backend_name = backend
        self._metric = C.validate_metric(metric)
        self._state = state
        self._pending_add: list[np.ndarray] = []
        # bumped on every state rewrite (add / delete that removed
        # rows / apply_pending that ingested rows / compact); the
        # background compactor compares epochs to detect mutations
        # landing between its snapshot and its atomic swap
        self._mutation_epoch = 0

    # -- construction -------------------------------------------------

    @classmethod
    def build(
        cls,
        key: jax.Array,
        X: jax.Array,
        config: ASHConfig,
        *,
        backend: str = "flat",
        metric: str = "dot",
        **opts,
    ) -> "AshIndex":
        """Train (or reuse ``model=``), encode ``X`` and assemble the
        backend structure.  Backend-specific ``opts``: ``keep_raw``,
        ``learned``, ``model``, ``train_sample``, ``mesh``, ``axes``
        and any ``repro.core.ash.train`` keyword."""
        impl = _get_backend(backend)
        C.validate_metric(metric)
        with jax.profiler.TraceAnnotation(
            "ash.build", backend=backend, rows=X.shape[0]
        ):
            state = impl.build(key, X, config, metric=metric, **opts)
        return cls(backend, metric, state)

    @classmethod
    def from_parts(
        cls,
        model: ASHModel,
        payload: ASHPayload,
        *,
        backend: str = "flat",
        metric: str = "dot",
        raw: Optional[jax.Array] = None,
        **opts,
    ) -> "AshIndex":
        """Wrap an already-encoded (model, payload) pair."""
        impl = _get_backend(backend)
        C.validate_metric(metric)
        state = impl.from_parts(
            model, payload, metric=metric, raw=raw, **opts
        )
        return cls(backend, metric, state)

    # -- lifecycle ----------------------------------------------------

    def search(
        self,
        queries: jax.Array,
        k: int = 10,
        *,
        nprobe: Optional[int] = None,
        rerank: int = 0,
        **opts,
    ) -> tuple[jax.Array, jax.Array]:
        """Top-k search: (scores, ids), each (m, k), higher-is-better
        scores for every metric; id -1 marks a missing candidate.

        ``coarse="int8"`` (every backend) runs the symmetric int8
        first-pass scan and asymmetrically rescores only the top
        ``shortlist`` candidates per query — faster on big scans, and
        exact (bit-identical to ``coarse=None``) whenever the
        shortlist covers the scanned rows."""
        return self._backend.search(
            self._state, queries, k=k, nprobe=nprobe, rerank=rerank,
            **opts,
        )

    def prepare(self, queries: jax.Array) -> QueryPrep:
        """Precompute the QUERY-COMPUTE projections (Eq. 20) for
        ``queries``; feed to :meth:`search_prepped`.  Row i of the prep
        depends only on row i of ``queries``, so prep rows are batchable
        across requests (the serving engine prepares each padded bucket
        once)."""
        return S.prepare_queries(self.model, queries)

    def search_prepped(
        self,
        prep: QueryPrep,
        k: int = 10,
        *,
        nprobe: Optional[int] = None,
        rerank: int = 0,
        **opts,
    ) -> tuple[jax.Array, jax.Array]:
        """:meth:`search` from precomputed projections — bit-identical
        to ``search(queries, ...)`` for the same query rows."""
        return self._backend.search_prepped(
            self._state, prep, k=k, nprobe=nprobe, rerank=rerank,
            **opts,
        )

    def add(self, X_new: jax.Array) -> "AshIndex":
        """Encode new vectors under the existing model and ingest them
        immediately (ids continue past every id ever assigned,
        including retired ones).  Flushes any staged rows first so id
        assignment stays in submission order.  Returns self."""
        self.apply_pending()
        self._state = self._backend.add(self._state, X_new)
        self._mutation_epoch += 1
        return self

    # -- mutations ----------------------------------------------------

    def stage_add(self, X_new) -> np.ndarray:
        """Buffer rows for a later batched ingestion; returns the user
        ids they WILL carry (assigned now, in submission order).

        Staged rows are invisible to search until
        :meth:`apply_pending` ingests the whole buffer in one backend
        ``add`` — one IVF re-sort / sharded re-placement per batch
        instead of per call (the serving engine's
        ``submit_add`` path).  The buffer persists through
        :meth:`save`/:meth:`load`.
        """
        X = np.ascontiguousarray(np.asarray(X_new), dtype=np.float32)
        if X.ndim == 1:
            X = X[None, :]
        dim = self.model.landmarks.shape[1]
        if X.ndim != 2 or X.shape[1] != dim:
            raise ValueError(
                f"stage_add rows must be (n, {dim}): got {X.shape}"
            )
        start = self.next_id + sum(
            p.shape[0] for p in self._pending_add
        )
        if X.shape[0] == 0:  # nothing to stage; no empty buffer entry
            return np.arange(start, start, dtype=np.int64)
        self._pending_add.append(X)
        return np.arange(start, start + X.shape[0], dtype=np.int64)

    def apply_pending(self) -> int:
        """Ingest every staged row in one backend add; returns the row
        count applied (0 = nothing staged)."""
        if not self._pending_add:
            return 0
        rows = np.concatenate(self._pending_add, axis=0)
        self._pending_add = []
        self._state = self._backend.add(self._state, jnp.asarray(rows))
        self._mutation_epoch += 1
        return rows.shape[0]

    def delete(self, ids) -> int:
        """Tombstone rows by user id; returns the number of rows newly
        removed (unknown / already-deleted ids are ignored — FAISS
        ``remove_ids`` semantics).  Deleted ids can never surface in
        results: the validity bitmap feeds the scan kernels' runtime
        mask operand (dense paths) and drops candidates pre-DMA
        (gathered paths).  Applies staged adds first, so deleting a
        just-staged id works."""
        self.apply_pending()
        self._state, removed = self._backend.delete(self._state, ids)
        if removed:
            self._mutation_epoch += 1
        return removed

    def compact(self, max_dead_fraction: float = 0.0) -> "AshIndex":
        """Evict tombstoned rows by rewriting codes/stats/raw when the
        dead fraction exceeds ``max_dead_fraction`` (default: any
        tombstone triggers a rewrite).  Search afterwards is
        bit-identical to a fresh build over the surviving rows (same
        model); user ids are stable across compaction and never
        reused.  No-op below the threshold.  Returns self."""
        self.apply_pending()
        if self.dead_fraction > max_dead_fraction:
            self._state = self._backend.compact(self._state)
            self._mutation_epoch += 1
        return self

    # -- persistence --------------------------------------------------

    def save(self, path, *, extra_meta: Optional[dict] = None) -> None:
        """Write ``arrays.npz`` + ``config.json`` under ``path/``
        atomically: a crash at any instant leaves either the previous
        save or the new one, never a torn mix (fresh targets go
        through a temp dir + one ``os.replace``; existing targets
        through durable ``.new`` files that :meth:`load` can roll
        forward).  The manifest carries a crc32 per array that
        :meth:`load` verifies.  ``extra_meta`` entries are merged into
        the manifest (the durability layer stores its WAL high-water
        mark this way)."""
        p = pathlib.Path(path)
        arrays, backend_meta = self._backend.to_arrays(self._state)
        if self._pending_add:
            # staged-but-unapplied rows ride along so a batched
            # ingestion in flight is never lost to a save/load cycle
            arrays = dict(arrays)
            arrays["pending_add"] = np.concatenate(
                self._pending_add, axis=0
            )
        encoded, dtypes, checksums = {}, {}, {}
        for name, a in arrays.items():
            encoded[name], dtypes[name] = _encode_array(a)
            checksums[name] = zlib.crc32(
                np.ascontiguousarray(encoded[name]).tobytes()
            )
        cfg = self.config
        meta = {
            "format_version": FORMAT_VERSION,
            "backend": self._backend_name,
            "metric": self._metric,
            "config": {
                "b": cfg.b,
                "d": cfg.d,
                "n_landmarks": cfg.n_landmarks,
                "store_fp16": cfg.store_fp16,
            },
            "dtypes": dtypes,
            "backend_meta": backend_meta,
            "checksums": checksums,
        }
        if extra_meta:
            meta.update(extra_meta)
        if p.exists():
            _save_over(p, encoded, meta)
        else:
            _save_fresh(p, encoded, meta)

    @classmethod
    def load(cls, path, **opts) -> "AshIndex":
        """Inverse of :meth:`save`; search results are bit-identical to
        the saved index.  ``opts`` (e.g. ``mesh=``/``axes=`` for the
        sharded backend) override the backend placement.  Every
        integrity failure — missing files, truncated or bit-flipped
        ``arrays.npz``, checksum mismatch — raises
        :class:`CorruptIndexError` naming the failed check."""
        p = pathlib.Path(path)
        meta, encoded = _read_index_dir(p)
        try:
            arrays = {
                name: _decode_array(a, meta["dtypes"][name])
                for name, a in encoded.items()
            }
        except Exception as e:
            raise CorruptIndexError(p, f"array decode failed: {e}") from e
        pending = arrays.pop("pending_add", None)
        config = ASHConfig(**meta["config"])
        impl = _get_backend(meta["backend"])
        state = impl.from_arrays(
            arrays, meta["backend_meta"], config, meta["metric"], **opts
        )
        index = cls(meta["backend"], meta["metric"], state)
        if pending is not None:
            index._pending_add = [
                np.asarray(pending, dtype=np.float32)
            ]
        return index

    # -- introspection ------------------------------------------------

    @property
    def backend(self) -> str:
        return self._backend_name

    @property
    def metric(self) -> str:
        return self._metric

    @property
    def model(self) -> ASHModel:
        return self._backend.model_of(self._state)

    @property
    def payload(self) -> ASHPayload:
        return self._backend.payload_of(self._state)

    @property
    def stats(self) -> Optional[ASHStats]:
        """Encode-time row statistics (fused l2/cos epilogue inputs);
        carried by every built-in backend, None only for custom
        backends without a ``stats_of``."""
        stats_of = getattr(self._backend, "stats_of", None)
        return None if stats_of is None else stats_of(self._state)

    @property
    def config(self) -> ASHConfig:
        return self.model.config

    @property
    def n(self) -> int:
        """Payload rows, INCLUDING tombstones (excluding staged adds)."""
        return self.payload.n

    @property
    def n_dead(self) -> int:
        """Tombstoned rows awaiting compaction."""
        live = getattr(self._backend, "live_of", lambda s: None)(
            self._state
        )
        if live is None:
            return 0
        return self.n - int(np.asarray(live).sum())

    @property
    def n_live(self) -> int:
        """Searchable rows (``n`` minus tombstones)."""
        return self.n - self.n_dead

    @property
    def dead_fraction(self) -> float:
        """Tombstoned fraction of the payload — compare against
        ``compact(max_dead_fraction=...)``."""
        return self.n_dead / max(1, self.n)

    @property
    def pending_rows(self) -> int:
        """Rows staged by :meth:`stage_add`, not yet ingested."""
        return sum(p.shape[0] for p in self._pending_add)

    @property
    def mutation_epoch(self) -> int:
        """Monotonic counter of state rewrites (adds applied, deletes
        that removed rows, compactions).  Equal epochs guarantee the
        searchable state is unchanged — the background compactor's
        swap-if-unchanged check."""
        return self._mutation_epoch

    @property
    def next_id(self) -> int:
        """User id the next added row receives (monotonic; retired ids
        are never reused).  Staged rows already hold theirs."""
        next_id_of = getattr(self._backend, "next_id_of", None)
        return self.n if next_id_of is None else next_id_of(self._state)

    def __len__(self) -> int:
        return self.n

    def __repr__(self) -> str:
        cfg = self.config
        mut = ""
        if self.n_dead or self.pending_rows:
            mut = (
                f", dead={self.n_dead}, pending={self.pending_rows}"
            )
        return (
            f"AshIndex(backend={self._backend_name!r}, "
            f"metric={self._metric!r}, n={self.n}{mut}, b={cfg.b}, "
            f"d={cfg.d}, C={cfg.n_landmarks}, "
            f"payload={cfg.payload_bits()} bits/vec)"
        )
