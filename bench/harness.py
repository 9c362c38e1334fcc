"""One run of one cell: set-up, the measured window, the reference.

    set-up   corpus from the seed on the device -> index build -> corpus
             freed -> engine + frontend -> warm-up of exactly the batch
             buckets the traffic can fill   (all of it is ``setup_s``)
    window   the traffic mix for ``--seconds``; with ``--trace 1`` a
             steady stretch inside it is profiled
    after    peak memory read, the program's state freed, corpus
             regenerated, answers compared with the plain reference

The last line of standard output is the result; everything else goes
to standard error, the compared numbers and their limits last.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import json
import os
import sys
import tempfile
import threading
import time
from typing import Optional

import numpy as np

# a trace of a few seconds of a steady stretch, starting this long
# after the window opens
TRACE_LEAD_S = 1.0
TRACE_S = 3.0
# at most this many answered query rows are compared with the
# reference (each dataset publishes 10,000 queries)
SAMPLE_ROWS = 10_000
# warm-up passes over each batch bucket
WARM_PASSES = 2


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileWatch:
    """Counts traces, backend compiles and persistent-cache hits (one
    per process: JAX's listeners cannot be taken back)."""

    def __init__(self):
        import jax

        self.n = {"trace": 0, "compile": 0, "cache_hit": 0}
        self.compile_s = 0.0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n["compile"] += 1
                self.compile_s += duration
            elif event == "/jax/core/compile/jaxpr_trace_duration":
                self.n["trace"] += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.n["cache_hit"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def counts(self) -> dict:
        return dict(self.n)


@functools.lru_cache(maxsize=None)
def compile_watch() -> CompileWatch:
    return CompileWatch()


@dataclasses.dataclass
class Context:
    """What a per-layer metric's ``read(ctx)`` may read."""

    loop: str  # "open" | "closed"
    requests: list  # loadgen.Request of the window
    window: tuple  # (t0, t_end), perf_counter seconds
    engine_delta: dict  # EngineStats counters over the window
    shapes: dict  # index sizes (system.index_shapes)
    search: dict  # per-request search arguments
    peaks: dict  # the chip's entry of peaks.json
    trace: Optional[object] = None  # trace.Trace of the traced stretch
    trace_host: Optional[tuple] = None  # its (start, end), perf_counter

    def calls(self, within=None) -> list:
        """Distinct fused calls, from the program's per-ticket stats:
        every ticket of one call carries the same numbers.  Each is
        (end time, scoring seconds, real rows, bucket rows); ``within``
        keeps calls that ended inside a (start, end) host interval."""
        seen = {}
        for r in self.requests:
            if r.stats is None or not np.isfinite(r.done):
                continue
            st = r.stats
            key = (st["scoring_us"], st["batch_rows"], st["bucket_rows"])
            seen[key] = min(seen.get(key, r.done), r.done)
        out = [(t, us * 1e-6, b, bk) for (us, b, bk), t in seen.items()]
        if within is not None:
            out = [c for c in out if within[0] <= c[0] <= within[1]]
        return sorted(out)


def _engine_delta(a: dict, b: dict) -> dict:
    keys = ("requests", "batches", "rows", "prep_hits", "prep_misses")
    return {k: b[k] - a[k] for k in keys}


def _quantile(x, q) -> float:
    return float(np.quantile(np.asarray(x, dtype=np.float64), q))


def _start_tracer(t_start: float, box: dict):
    """Profile [t_start, t_start + TRACE_S] from a helper thread."""
    from bench import trace as T

    def work():
        wait = t_start - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        box["dir"] = tempfile.mkdtemp(prefix="bench-trace-")
        box["t0"] = time.perf_counter()
        rec = T.Recorder(box["dir"])
        time.sleep(TRACE_S)
        box["t1"] = time.perf_counter()
        box["path"] = rec.stop()

    th = threading.Thread(target=work, name="bench-tracer")
    th.start()
    return th


@dataclasses.dataclass
class Prepared:
    """A cell after set-up: the system serving, its pool of queries."""

    cell: object  # spec.Cell
    cfg: dict
    traffic: dict
    seed: int
    dev: object
    n_devices: int
    peaks: dict
    watch: CompileWatch
    served: object  # system.Served
    shapes: dict
    kw: dict
    pool: np.ndarray
    setup_s: float
    in_use: int


@dataclasses.dataclass
class Window:
    requests: list
    t0: float
    t_end: float
    seconds: float
    compiles: dict
    engine_delta: dict
    peak: int
    box: dict


def prepare(args, *, t_process: float, require_tpu: bool = True,
            overrides: Optional[dict] = None,
            n_window: Optional[int] = None) -> Prepared:
    """Set-up: everything before the window opens (``setup_s``).
    Raises ``SystemExit(3)`` without a TPU or with too few chips.
    ``n_window`` sizes the query pool (default: what one window of
    the cell's traffic uses)."""
    from bench import spec as S

    cell = S.load_cell(args.workload)
    cfg, traffic = cell.config, cell.traffic
    if overrides:
        cfg = _merge(cfg, overrides.get("config", {}))
        traffic = _merge(traffic, overrides.get("traffic", {}))

    import jax

    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        log(f"needs a TPU; JAX found {dev.platform} ({dev.device_kind})")
        raise SystemExit(3)
    if len(devs) < cell.chips:
        log(f"needs {cell.chips} chips; JAX found {len(devs)}")
        raise SystemExit(3)
    from bench import data as D
    from bench import roofline as RF
    from bench import system as Y

    pk = RF.peaks(dev.device_kind) if require_tpu else {}
    watch = compile_watch()
    log(f"device {dev.platform} {dev.device_kind} x{len(devs)}; "
        f"cell {cell.name}; seed {args.seed}; seconds {args.seconds}")

    X = D.corpus(cfg, args.seed).block_until_ready()
    log(f"corpus {X.shape} at {time.perf_counter() - t_process:.3f} s")
    index = Y.build_index(cfg, X, D.dataset_keys(cfg)["build"])
    del X
    shapes = Y.index_shapes(index)
    log(f"index built at {time.perf_counter() - t_process:.3f} s: {shapes}")
    kw = Y.search_kwargs(cfg, traffic)
    closed = traffic["loop"] == "closed"
    served = Y.Served(cfg, index)
    del index
    buckets = Y.warm_buckets(
        served.batch_buckets, int(traffic["rows_per_request"]),
        int(traffic["clients"]) if closed else None,
    )
    n_warm = WARM_PASSES * sum(buckets)
    if n_window is None:
        n_window = (
            int(traffic["pool_rows"]) if closed
            else int(round(float(traffic["rate_rps"]) * args.seconds))
        )
    pool = D.queries(cfg, args.seed, n_warm + n_window)
    warm, pool = pool[:n_warm], pool[n_warm:]
    lo = 0
    for _ in range(WARM_PASSES):
        for b in buckets:
            served.submit(warm[lo:lo + b], kw).result(timeout=1200)
            lo += b
    in_use = int((dev.memory_stats() or {}).get("bytes_in_use", 0))
    setup_s = time.perf_counter() - t_process
    log(f"warmed buckets {buckets}; set-up {setup_s:.3f} s; compiles "
        f"{watch.counts()} ({watch.compile_s:.3f} s compiling); "
        f"bytes in use {in_use}")
    return Prepared(
        cell=cell, cfg=cfg, traffic=traffic, seed=args.seed, dev=dev,
        n_devices=len(devs), peaks=pk, watch=watch, served=served,
        shapes=shapes, kw=kw, pool=pool, setup_s=setup_s, in_use=in_use,
    )


def measure(p: Prepared, seconds: float, *, trace: bool = False,
            rate: Optional[float] = None, pool_from: int = 0) -> Window:
    """The measured window: the cell's traffic for ``seconds`` (an open
    loop at ``rate`` if given, else the traffic file's), rows taken
    from the pool at ``pool_from``."""
    from bench import loadgen as G
    from bench import system as Y

    traffic = p.traffic
    pool = p.pool[pool_from:]
    before = p.watch.counts()
    snap0 = p.served.snapshot()
    tracer, box = None, {}
    t0 = time.perf_counter() + 0.05
    t_end = t0 + seconds
    if trace:
        tracer = _start_tracer(t0 + TRACE_LEAD_S, box)
    submit = p.served.submit_fn(p.kw)
    if traffic["loop"] == "open":
        gaps = G.poisson_gaps(
            float(rate or traffic["rate_rps"]), seconds, p.seed
        )
        reqs = G.open_loop(submit, pool, gaps, t0, stats_of=Y.request_stats)
    else:
        reqs = G.closed_loop(
            submit, pool, int(traffic["rows_per_request"]),
            int(traffic["clients"]), t0, t_end, stats_of=Y.request_stats,
        )
    for r in reqs:
        r.row += pool_from
    if tracer is not None:
        tracer.join()
    after = p.watch.counts()
    snap1 = p.served.snapshot()
    w = Window(
        requests=reqs, t0=t0, t_end=t_end, seconds=seconds,
        compiles={k: after[k] - before[k] for k in after},
        engine_delta=_engine_delta(snap0, snap1),
        peak=int((p.dev.memory_stats() or {}).get("peak_bytes_in_use", 0)),
        box=box,
    )
    log(f"window closed; compiles in window {w.compiles}; engine "
        f"{w.engine_delta}; peak bytes in use {w.peak}")
    return w


def end_to_end(p: Prepared, w: Window) -> dict:
    from bench import loadgen as G

    ok = [r for r in w.requests if r.ok]
    rows_done = sum(r.n_rows for r in ok if r.done <= w.t_end)
    values = {
        "qps": rows_done / w.seconds,
        "setup_s": p.setup_s,
        "hbm_bytes_per_row": p.in_use / p.shapes["n"],
    }
    if p.traffic["loop"] == "open":
        give_up = w.t_end + G.GRACE_S
        lat = [(r.done if r.ok else give_up) - r.due for r in w.requests]
        lag = [r.sent - r.due for r in w.requests if r.sent]
        values["latency_p50_ms"] = 1e3 * _quantile(lat, 0.5)
        values["latency_p95_ms"] = 1e3 * _quantile(lat, 0.95)
        log(f"open loop: {len(w.requests)} requests, latency p50 "
            f"{values['latency_p50_ms']:.3f} ms p95 "
            f"{values['latency_p95_ms']:.3f} ms p99 "
            f"{1e3 * _quantile(lat, 0.99):.3f} ms; generator lag p99 "
            f"{1e3 * _quantile(lag, 0.99):.3f} ms; sends 50 ms or more "
            f"late: {sum(1 for x in lag if x >= 0.05)}")
    return values


def finish(p: Prepared, w: Window, *, trace: bool,
           keep_trace: Optional[str] = None, with_control: bool = False):
    """Free the program's state, compare with the reference, read the
    per-layer metrics of a traced run; the result dict."""
    from bench import spec as S

    p.served.close()
    gc.collect()
    values = end_to_end(p, w)
    ok = [r for r in w.requests if r.ok]
    failed = len(w.requests) - len(ok)
    t_ref = time.perf_counter()
    checks, n_sample, control = _check(
        p.cfg, p.seed, p.pool, ok, int(p.traffic["k"]), with_control
    )
    if "recall_loss" in checks:
        values["recall_at_10"] = 1.0 - checks["recall_loss"]["value"]
    checks["unanswered"] = {"value": failed, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    log(f"reference compared {n_sample} rows in "
        f"{time.perf_counter() - t_ref:.3f} s")

    device = {
        "platform": p.dev.platform, "kind": p.dev.device_kind,
        "count": p.n_devices, "memory_peak_bytes": w.peak,
    }
    result = {"correct": bool(correct), "attempted": len(w.requests),
              "failed": failed}
    if trace:
        tr = _reduce(w.box, keep_trace)
        ctx = Context(
            loop=p.traffic["loop"], requests=w.requests,
            window=(w.t0, w.t_end), engine_delta=w.engine_delta,
            shapes=p.shapes, search=p.kw, peaks=p.peaks, trace=tr,
            trace_host=(w.box.get("t0"), w.box.get("t1")),
        )
        metrics = {}
        for m in p.cell.per_layer:
            v = S.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in tr.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in tr.idle_gaps(10)],
        }
    else:
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in p.cell.end_to_end
        }
        result["device"] = device
    log("end-to-end " + json.dumps(values))
    if control is not None:
        result["control"] = control
    result["checks"] = checks
    for name, c in checks.items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return result


def run(args, *, t_process: float, require_tpu: bool = True,
        overrides: Optional[dict] = None, keep_trace: Optional[str] = None,
        with_control: bool = False):
    """One run of one cell; the result dict."""
    p = prepare(args, t_process=t_process, require_tpu=require_tpu,
                overrides=overrides)
    w = measure(p, args.seconds, trace=bool(args.trace))
    return finish(p, w, trace=bool(args.trace), keep_trace=keep_trace,
                  with_control=with_control)


def _check(cfg, seed, pool, ok, k, with_control=False):
    """Compare a seeded sample of the answered rows with the plain
    reference over the corpus, regenerated from the seed.  Returns
    (checks, rows compared, the control's numbers or None); with no
    answer at all, the check ``answered`` fails."""
    from bench import data as D
    from bench import reference as R

    rows, scores, ids = [], [], []
    for r in ok:
        rows.append(np.arange(r.row, r.row + r.n_rows))
        scores.append(r.scores[:, :k])
        ids.append(r.ids[:, :k])
    if not rows:
        return {"answered": {"value": 1, "limit": 0}}, 0, None
    rows = np.concatenate(rows)
    scores = np.concatenate(scores)
    ids = np.concatenate(ids)
    pick = np.random.default_rng(seed).permutation(len(rows))[:SAMPLE_ROWS]
    pick.sort()
    Q = pool[rows[pick]]
    X = D.corpus(cfg, seed)
    metric = cfg["metric"]
    _, truth = R.topk(Q, X, k, metric)
    got = R.compare(Q, scores[pick], ids[pick], truth, X, metric, k)
    control = None
    if with_control:
        cs, ci = R.topk(Q, X, k, metric, control=True)
        control = R.compare(Q, cs, ci, truth, X, metric, k)
    del X
    return {
        "score_gap": {"value": got["score_gap"],
                      "limit": cfg["limits"]["score_gap"]},
        "recall_loss": {"value": got["recall_loss"],
                        "limit": cfg["limits"]["recall_loss"]},
        "bad_rows": {"value": got["bad_rows"], "limit": 0},
    }, int(len(pick)), control


def _reduce(box: dict, keep: Optional[str]):
    import shutil

    from bench import trace as T

    path = box["path"]
    if keep:
        shutil.copyfile(path, keep)
    tr = T.load(path)
    shutil.rmtree(box["dir"], ignore_errors=True)
    return tr


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, v in over.items():
        out[key] = _merge(base.get(key, {}), v) if isinstance(v, dict) else v
    return out


def env_for_cache(root) -> None:
    """The compile cache lives in the checkout, at a fixed path (the
    path is part of the cache key); every program is cached, however
    fast it compiled."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
