"""Load generators: an open loop and a closed loop.

Both take a ``submit(rows) -> ticket`` callable (the ticket has
``result(timeout)`` and ``add_done_callback(fn)``) and a host pool of
query rows; the program sees nothing but those rows.  Each records, per
request, when it was due (open loop) or sent, when its answer came back
and what the answer was, so that latencies, rates and the correctness
sample are all computed from one record.

* Open loop: independent users.  Arrival times are a fixed set of
  Poisson gaps for the rate and window (the same set for every seed, in
  an order drawn from the seed), so every seed offers the same work.
  Each request is timed from when it was DUE, which counts the wait a
  stall imposes on later requests; the generator's own lag (send time
  minus due time) is recorded beside it.
* Closed loop: ``clients`` callers, each sending its next request only
  once its last one is answered.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import Optional

import numpy as np

# how long after the window closes an answer is still waited for
GRACE_S = 60.0


@dataclasses.dataclass
class Request:
    row: int  # first pool row of the request
    n_rows: int
    due: float  # perf_counter time it was due (closed loop: sent)
    sent: float = 0.0
    done: float = float("nan")  # answer arrived (nan: never)
    scores: Optional[np.ndarray] = None
    ids: Optional[np.ndarray] = None
    error: Optional[str] = None
    stats: Optional[dict] = None  # the program's RequestStats

    @property
    def ok(self) -> bool:
        return self.error is None and self.ids is not None


def poisson_gaps(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Inter-arrival gaps of a Poisson process of ``rate`` over
    ``seconds``: the exponential distribution's quantiles at the
    midpoints of ``round(rate * seconds)`` equal bins, shuffled by the
    seed.  Their sum is the window (to within one gap)."""
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    return np.random.default_rng(seed).permutation(gaps)


def _collect(reqs, tickets, stats_of, deadline):
    for r, t in zip(reqs, tickets):
        if t is None:
            continue
        try:
            s, i = t.result(timeout=max(0.0, deadline - time.perf_counter()))
        except Exception as e:  # a failed or unanswered request
            r.error = repr(e)
            continue
        r.scores, r.ids = np.asarray(s), np.asarray(i)
        if stats_of is not None:
            r.stats = stats_of(t)


def open_loop(submit, pool: np.ndarray, gaps: np.ndarray, t0: float,
              stats_of=None) -> list:
    """Send one row per request at ``t0 + cumsum`` of the gaps (the
    first at ``t0``); returns the requests once every answer is in or
    ``GRACE_S`` past the last due time has gone by."""
    due = t0 + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    reqs = [Request(row=i, n_rows=1, due=float(d)) for i, d in enumerate(due)]
    tickets = [None] * len(reqs)
    for i, r in enumerate(reqs):
        wait = r.due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        r.sent = time.perf_counter()
        try:
            t = submit(pool[i:i + 1])
        except Exception as e:
            r.error = repr(e)
            continue
        tickets[i] = t
        t.add_done_callback(
            lambda _t, r=r: setattr(r, "done", time.perf_counter())
        )
    _collect(reqs, tickets, stats_of, float(due[-1]) + GRACE_S)
    return reqs


def closed_loop(submit, pool: np.ndarray, rows_per_request: int,
                clients: int, t0: float, t_end: float,
                stats_of=None) -> list:
    """``clients`` threads send ``rows_per_request``-row requests from
    ``t0`` and stop sending at ``t_end``; each waits for its answer
    before the next.  Rows are taken from the pool in order and wrap
    round it when it runs out."""
    r = rows_per_request
    slots = len(pool) // r
    counter = itertools.count()
    lock = threading.Lock()
    per_client = [[] for _ in range(clients)]

    def client(c):
        wait = t0 - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        out = per_client[c]
        while time.perf_counter() < t_end:
            with lock:
                j = next(counter)
            lo = (j % slots) * r
            req = Request(row=lo, n_rows=r, due=time.perf_counter())
            req.sent = req.due
            try:
                t = submit(pool[lo:lo + r])
                _collect([req], [t], stats_of, t_end + GRACE_S)
            except Exception as e:
                req.error = repr(e)
            if req.ok:
                req.done = time.perf_counter()
            out.append(req)

    threads = [
        threading.Thread(target=client, args=(c,), name=f"bench-client-{c}")
        for c in range(clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=max(1.0, t_end + 2 * GRACE_S - time.perf_counter()))
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a closed-loop client did not finish")
    return sorted(
        itertools.chain.from_iterable(per_client), key=lambda q: q.due
    )
