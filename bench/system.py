"""The system under test, as the benchmark drives it.

The only module of the benchmark that imports the program: it builds
the index a configuration describes, puts a ``QueryEngine`` behind a
``ServingFrontend``, warms the shapes a traffic mix can hit, and reads
the program's own counters (``RequestStats`` per ticket,
``EngineStats.snapshot()``).  Everything else the benchmark does —
data, load, reference, trace reduction — is its own.
"""
from __future__ import annotations

import numpy as np

from repro.core import ASHConfig
from repro.index import AshIndex
from repro.serving.engine import QueryEngine
from repro.serving.frontend import ServingFrontend

INDEX_NAME = "bench"


def build_index(cfg: dict, X, build_key) -> AshIndex:
    ix = cfg["index"]
    return AshIndex.build(
        build_key, X,
        ASHConfig(b=ix["b"], d=ix["d"], n_landmarks=ix["n_landmarks"]),
        backend=ix["backend"], metric=cfg["metric"],
        keep_raw=ix["keep_raw"], landmark_sample=ix["landmark_sample"],
    )


def search_kwargs(cfg: dict, traffic: dict) -> dict:
    """Per-request arguments of ``ServingFrontend.submit``."""
    kw = dict(k=int(traffic["k"]), index=INDEX_NAME)
    if cfg["search"].get("rerank"):
        kw["rerank"] = int(cfg["search"]["rerank"])
    if cfg["search"].get("nprobe") is not None:
        kw["nprobe"] = int(cfg["search"]["nprobe"])
    return kw


class Served:
    """An index behind an engine and a running frontend."""

    def __init__(self, cfg: dict, index: AshIndex):
        self.index = index
        self.engine = QueryEngine({INDEX_NAME: index}, **cfg["engine"])
        self.frontend = ServingFrontend(self.engine).start()

    @property
    def batch_buckets(self) -> tuple:
        return tuple(self.engine.config.batch_buckets)

    def submit(self, rows: np.ndarray, kw: dict):
        return self.frontend.submit(rows, **kw)

    def submit_fn(self, kw: dict):
        """``submit(rows)`` with the cell's search arguments bound."""
        return lambda rows: self.frontend.submit(rows, **kw)

    def snapshot(self) -> dict:
        return self.engine.stats.snapshot()

    def close(self) -> None:
        """Stop serving and drop every reference to the program's
        device state, so that the reference can use the memory."""
        self.frontend.stop(drain=True)
        self.frontend = self.engine = self.index = None


def warm_buckets(buckets: tuple, rows_per_request: int, clients) -> list:
    """The batch buckets a traffic mix can fill: every sum of whole
    requests (up to ``clients`` of them in flight, any number for an
    open loop) is padded to the smallest bucket that holds it, and a
    group above the largest bucket runs in chunks of it."""
    big = buckets[-1]
    most = big if clients is None else min(big, clients * rows_per_request)
    out = set()
    for rows in range(rows_per_request, most + 1, rows_per_request):
        out.add(next(b for b in buckets if rows <= b))
    if clients is not None and clients * rows_per_request > big:
        out.add(big)
    return sorted(out)


def request_stats(ticket) -> dict:
    st = ticket.stats
    return dict(
        queue_wait_s=st.queue_wait_s, scoring_us=st.scoring_us,
        batch_rows=st.batch_rows, bucket_rows=st.bucket_rows,
    )


def index_shapes(index: AshIndex) -> dict:
    """Sizes the kernels' ops/bytes functions need, read from the
    built index (the candidate width of a gathered probe depends on the
    longest inverted list, which the data decides)."""
    st = index._state
    codes = st.payload.codes
    out = dict(
        n=int(codes.shape[0]), words=int(codes.shape[1]),
        b=int(st.payload.b), d=int(st.payload.d),
        n_landmarks=int(st.model.landmarks.shape[0]),
        dim=int(st.model.landmarks.shape[1]), metric=st.metric,
    )
    if hasattr(st, "invlists"):
        out["max_list_len"] = int(st.max_list_len)
    return out
