"""Reductions shared by the per-layer metric readers in ``metrics/``.

Each reader file names its metric's source and pattern and calls one of
these; each returns ``None`` when the run holds nothing to read, and the
harness then leaves the metric out.
"""
from __future__ import annotations

import numpy as np

from bench import roofline


def quantile_ms(seconds, q: float):
    x = np.asarray(list(seconds), dtype=np.float64)
    x = x[np.isfinite(x)]
    if x.size == 0:
        return None
    return 1e3 * float(np.quantile(x, q))


def generator_lag_ms(ctx, q: float):
    """Send time minus due time of every request (open loop)."""
    return quantile_ms((r.sent - r.due for r in ctx.requests if r.sent), q)


def queue_wait_ms(ctx, q: float):
    """The program's ``RequestStats.queue_wait_s``: submit to the start
    of the fused call that served the request."""
    return quantile_ms(
        (r.stats["queue_wait_s"] for r in ctx.requests if r.stats), q
    )


def fused_call_ms(ctx, q: float):
    """The program's ``RequestStats.scoring_us``, once per fused call."""
    return quantile_ms((c[1] for c in ctx.calls()), q)


def rows_per_call(ctx):
    """Real query rows per fused call over the window (EngineStats)."""
    d = ctx.engine_delta
    return d["rows"] / d["batches"] if d.get("batches") else None


def idle_share(ctx):
    """1 - device busy / traced window, in %."""
    tr = ctx.trace
    if tr is None or not tr.ops or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def kernel_roofline(ctx, pattern: str, call_cost):
    """Roofline share (%) of the kernel whose trace operations match
    ``pattern``.  ``call_cost(bucket_rows) -> (ops, bytes)`` counts
    one call; the mean over the fused calls that ended in the traced
    stretch stands for each matching event that lies wholly inside it,
    and their least time at the peaks is held against the summed device
    time of those events."""
    tr = ctx.trace
    if tr is None or not ctx.peaks:
        return None
    evs = [e for e in tr.matching(pattern) if e.whole]
    calls = ctx.calls(within=ctx.trace_host)
    if not evs or not calls:
        return None
    costs = np.array([call_cost(c[3]) for c in calls], dtype=np.float64)
    ops, nbytes = len(evs) * costs.mean(axis=0)
    seconds = sum(e.end - e.start for e in evs)
    return roofline.share(ops, nbytes, seconds, ctx.peaks)[0]


def shortlist(ctx) -> int:
    """The shortlist the fused top-k keeps per query: the rerank depth
    when the cell reranks, else k."""
    return max(int(ctx.search.get("rerank", 0)), int(ctx.search["k"]))
