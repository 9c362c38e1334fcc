"""Serving-path benchmarks: engine throughput/latency vs batch-bucket
config (``bench/serving``).

Streams single-query and small-batch requests through the
micro-batching ``QueryEngine`` and reports QPS + p50/p99 request
latency per bucket configuration, against the direct per-request
``AshIndex.search`` baseline — the measurement loop behind the paper's
"batched scoring stays a dense matmul" serving claim.
"""
from __future__ import annotations

import threading
import time

import jax
import numpy as np

from benchmarks.common import D, QUICK, dataset, recall10, row
from repro.core import ASHConfig
from repro.index import AshIndex
from repro.index.common import default_shortlist
from repro.serving.engine import QueryEngine


def _request_stream(Qm, seed=0):
    """(start, size) request slices with a serving-like size mix."""
    rng = np.random.RandomState(seed)
    out, i = [], 0
    while i < Qm.shape[0]:
        m = min(int(rng.choice([1, 1, 2, 4])), Qm.shape[0] - i)
        out.append((i, m))
        i += m
    return out


def _stream_through(engine, Qm, reqs, k, nprobe):
    tickets = [
        engine.submit(Qm[i:i + m], k=k, nprobe=nprobe)
        for i, m in reqs
    ]
    engine.flush()
    return tickets


def serving_engine():
    X, Qm, gt = dataset()
    cfg = ASHConfig(b=2, d=D // 2, n_landmarks=16)
    key = jax.random.PRNGKey(0)
    index = AshIndex.build(key, X, cfg, backend="flat")
    # the IVF rows get a serving-shaped partition: nprobe 8 of 32
    # lists scans ~1/4 of the corpus per query, so per-query probe
    # sets genuinely differ and the union bill has somewhere to go.
    # (nprobe 8 of 16 probes half the corpus: every union saturates
    # near n and neither the budget nor batching can matter.)
    ivf = AshIndex.build(key, X,
                         ASHConfig(b=2, d=D // 2, n_landmarks=32),
                         backend="ivf")
    Qm = np.asarray(Qm)  # host-side slicing in the request loop
    X_np = np.asarray(X)
    reqs = _request_stream(Qm)
    n_rows = Qm.shape[0]
    rows = []

    # flat baseline: direct per-request search, sequential burst
    # (fresh trace per novel shape)
    for i, m in reqs:  # warmup: compile every request shape
        index.search(Qm[i:i + m], k=10)
    t0 = time.perf_counter()
    lats = []
    for i, m in reqs:
        t1 = time.perf_counter()
        jax.block_until_ready(index.search(Qm[i:i + m], k=10))
        lats.append(time.perf_counter() - t1)
    dt = time.perf_counter() - t0
    p50, p99 = np.percentile(lats, [50, 99])
    rows.append(row(
        "serving/direct_flat", 1e6 * dt / len(reqs),
        f"qps={n_rows / dt:.0f};"
        f"p50_ms={1e3 * p50:.2f};p99_ms={1e3 * p99:.2f}",
    ))

    # flat engine rows: one fused call per bucket, traces shared
    # across requests, measured on the same sequential burst
    for buckets in ((8,), (8, 32), (32,)):
        tag = "-".join(map(str, buckets))
        engine = QueryEngine(index, batch_buckets=buckets,
                             max_wait_s=0.005)
        _stream_through(engine, Qm, reqs, 10, None)  # warmup
        engine = QueryEngine(index, batch_buckets=buckets,
                             max_wait_s=0.005)
        t0 = time.perf_counter()
        tickets = _stream_through(engine, Qm, reqs, 10, None)
        dt = time.perf_counter() - t0
        lats = [t.stats.latency_s for t in tickets]
        p50, p99 = np.percentile(lats, [50, 99])
        st = engine.stats.snapshot()
        rows.append(row(
            f"serving/engine_flat_b{tag}", 1e6 * dt / len(reqs),
            f"qps={n_rows / dt:.0f};"
            f"p50_ms={1e3 * p50:.2f};p99_ms={1e3 * p99:.2f};"
            f"batches={st['batches']};fill={st['bucket_fill']};"
            f"traces={st['unique_buckets']}",
        ))

    # coarse first pass through the engine: the same request mix and
    # bucket as engine_flat_b8, with ``coarse="int8"`` riding the opts
    # into the group key (coarse and asymmetric requests never share a
    # fused call).  Both modes are measured here back to back so the
    # row is self-contained: check_bench gates it (full size only) at
    # qps >= 1.5x qps_asym with recall@10 within 1 point — the
    # serving-side win of the symmetric first pass.  The throughput
    # half only arms on accelerator rows (see the platform stamp):
    # XLA:CPU fuses the code unpack into the asymmetric scan and runs
    # both passes as the same-size f32 BLAS GEMM, so parity (~1.0x) is
    # the expected CPU result and only the recall half gates there.
    qps_by, rec_by, dt_by = {}, {}, {}
    for mode in ("asym", "coarse"):
        kw = {} if mode == "asym" else {"coarse": "int8"}
        engine = QueryEngine(index, batch_buckets=(8,), max_wait_s=0.005)
        for i, m in reqs:  # warmup: compile the mode's trace family
            engine.submit(Qm[i:i + m], k=10, **kw)
        engine.flush()
        engine = QueryEngine(index, batch_buckets=(8,), max_wait_s=0.005)
        t0 = time.perf_counter()
        tickets = [
            engine.submit(Qm[i:i + m], k=10, **kw) for i, m in reqs
        ]
        engine.flush()
        dt = time.perf_counter() - t0
        ids = np.concatenate(
            [np.asarray(t.result()[1]) for t in tickets]
        )
        qps_by[mode] = n_rows / dt
        rec_by[mode] = recall10(ids, gt)
        dt_by[mode] = dt
    rows.append(row(
        "serving/coarse_flat",
        1e6 * dt_by["coarse"] / len(reqs),
        f"qps={qps_by['coarse']:.0f};qps_asym={qps_by['asym']:.0f};"
        f"speedup={qps_by['coarse'] / max(qps_by['asym'], 1e-9):.2f}x;"
        f"recall_at_10={rec_by['coarse']:.4f};"
        f"recall_at_10_asym={rec_by['asym']:.4f};"
        f"shortlist={default_shortlist()};"
        f"platform={jax.default_backend()}",
    ))

    # IVF rows measure serving under CONCURRENT load, where the tail
    # actually lives: closed-loop clients each submit a 1-row request
    # and block on it.  The direct baseline pays per-request dispatch,
    # prep and its own gather, and the callers serialize; the frontend
    # driver batches concurrent arrivals into fused calls.  Clients
    # draw from the Qm pool — a hot set of 256 queries, the
    # query-repetition shape real traffic has.  Hot-set probes share lists heavily, so the union
    # bill grows slowly with group size: row_budget at 0.5n sits
    # above the hot-pool union at both costed rungs (~0.39n for an
    # 8-group, ~0.44n for a 16-group) — correlated traffic rides the
    # whole ladder and the bucket floor guarantees no chop below the
    # 8-bucket it pads up to — and far below a diverse (uncorrelated)
    # 16-group's union (~0.98n), which would budget-flush early and
    # split instead of serializing one monster gather.  The costed
    # ladder tops out at 16 — the fused call turns superlinear past
    # ~16 rows on this geometry, so a bigger top bucket only buys
    # tail.  The costed rows also arm the full tentpole config:
    # nprobe_min = nprobe/2 lets the pressure ladder halve probe
    # depth when the queue backs up — a recall-for-tail trade the
    # direct path cannot make, surfaced per row as degraded_batches
    # (and per engine in snapshot()["ivf_cost"]).  The
    # single-big-bucket 32 config stays uncosted as the contrast row
    # — the tail regression the cost model exists to kill.
    # check_bench gates every costed row (marked by the row_budget
    # field) at p99 <= direct_ivf* p99 and qps >= 2x direct_ivf*.
    nprobe = 8
    c = 32
    reqs_each = 6 if QUICK else 25
    jax.block_until_ready(ivf.search(X_np[:1], k=10, nprobe=nprobe))
    warm = QueryEngine(ivf, batch_buckets=(8, 16, 32), max_wait_s=0.002)
    for b in (8, 16, 32):
        warm.search(X_np[:b], k=10, nprobe=nprobe)
        # the costed rows' pressure ladder halves nprobe once (8 -> 4)
        # under load; warm that trace family too so no row compiles
        # mid-measurement
        warm.search(X_np[:b], k=10, nprobe=nprobe // 2)

    lat_d, dt_d = _closed_loop_direct(ivf, c, reqs_each, Qm, nprobe)
    p50, p99 = np.percentile(lat_d, [50, 99])
    rows.append(row(
        f"serving/direct_ivf_c{c}", 1e6 * dt_d / lat_d.size,
        f"qps={lat_d.size / dt_d:.0f};"
        f"p50_ms={1e3 * p50:.2f};p99_ms={1e3 * p99:.2f};"
        f"clients={c}",
    ))

    for tag, buckets in (("8", (8,)), ("8-16", (8, 16)),
                         ("32", (32,))):
        kw = {}
        if buckets != (32,):
            kw["row_budget"] = int(0.5 * ivf.n)
            kw["nprobe_min"] = nprobe // 2
        lats, dt, engine = _closed_loop(
            ivf, c, reqs_each, Qm, nprobe=nprobe, buckets=buckets,
            engine_kw=kw, warm_pool=Qm,
        )
        p50, p99 = np.percentile(lats, [50, 99])
        st = engine.stats.snapshot()
        extra = ""
        if kw:
            ic = st["ivf_cost"]
            extra = (
                f";row_budget={kw['row_budget']};"
                f"rows_per_q={ic['rows_per_query']};"
                f"splits={ic['splits']};"
                f"budget_flushes={st['flushes']['budget']};"
                f"nprobe_min={kw['nprobe_min']};"
                f"degraded_batches={ic['degraded']}"
            )
        rows.append(row(
            f"serving/engine_ivf_c{c}_b{tag}", 1e6 * dt / lats.size,
            f"qps={lats.size / dt:.0f};"
            f"p50_ms={1e3 * p50:.2f};p99_ms={1e3 * p99:.2f};"
            f"batches={st['batches']};fill={st['bucket_fill']};"
            f"clients={c}" + extra,
        ))

    return rows


def _mutation_stream(engine, X_np, Qm, reqs, nprobe, mutate_every):
    """Serve the request mix with every ``mutate_every``-th slot also
    carrying a mutation (alternating batched add / tombstone delete).
    Returns (query tickets, mutation tickets, wall seconds)."""
    rng = np.random.RandomState(7)
    tickets, muts = [], []
    t0 = time.perf_counter()
    for j, (i, m) in enumerate(reqs):
        if j % mutate_every == mutate_every - 1:
            if (j // mutate_every) % 2 == 0:
                rows_ = X_np[rng.randint(0, X_np.shape[0], 4)]
                muts.append(engine.submit_add(rows_))
            else:
                victims = rng.randint(0, X_np.shape[0], 4)
                muts.append(engine.submit_delete(victims))
        tickets.append(engine.submit(Qm[i:i + m], k=10, nprobe=nprobe))
    engine.flush()
    for t in muts:
        t.result()
    return tickets, muts, time.perf_counter() - t0


def serving_mutation():
    """Engine throughput under ~10% mutation traffic: adds/sec,
    deletes/sec and the search p99 while batched adds and tombstone
    deletes ride the same bucket/flush loop (the live-index serving
    scenario; compaction amortized via auto_compact)."""
    X, Qm, gt = dataset()
    X_np = np.asarray(X)
    cfg = ASHConfig(b=2, d=D // 2, n_landmarks=16)
    key = jax.random.PRNGKey(0)
    base = AshIndex.build(key, X, cfg, backend="flat")
    rows = []
    Qm = np.asarray(Qm)
    reqs = _request_stream(Qm)
    n_rows = Qm.shape[0]
    for nm, backend, nprobe in (("flat", "flat", None),
                                ("ivf", "ivf", 8)):
        # warmup engine+index compile every shape the stream hits,
        # including post-mutation payload shapes
        for pass_ in ("warm", "timed"):
            idx = AshIndex.build(
                key, X, cfg, backend=backend, model=base.model
            )
            engine = QueryEngine(
                idx, batch_buckets=(8, 32), max_wait_s=0.005,
                auto_compact=0.3,
            )
            # jit traces are warm after the first pass, but each pass
            # rebuilds the index and engine: the fresh build's device
            # arrays materialize lazily and the first flush would
            # otherwise block on them, charging ~100x p50 to whichever
            # tickets land in it (the old p99 outlier).  Block on the
            # index and serve one throwaway flush per bucket first,
            # the way launch/serve.py warms query buckets.
            jax.block_until_ready(jax.tree_util.tree_leaves(idx._state))
            for b in (8, 32):
                engine.submit(Qm[:b], k=10, nprobe=nprobe)
                engine.flush()
            tickets, muts, dt = _mutation_stream(
                engine, X_np, Qm, reqs, nprobe, mutate_every=10
            )
        added = sum(t.n_rows for t in muts if t.kind == "add")
        deleted = sum(t.result() for t in muts if t.kind == "delete")
        worst_apply = max((t.apply_s for t in muts), default=0.0)
        lats = [t.stats.latency_s for t in tickets]
        p50, p99 = np.percentile(lats, [50, 99])
        st = engine.stats.snapshot()
        rows.append(row(
            f"serving/mutation_{nm}_10pct", 1e6 * dt / len(reqs),
            f"qps={n_rows / dt:.0f};"
            f"adds_per_s={added / max(dt, 1e-9):.0f};"
            f"deletes_per_s={deleted / max(dt, 1e-9):.0f};"
            f"p50_ms={1e3 * p50:.2f};p99_ms={1e3 * p99:.2f};"
            f"mut_batches={st['mutation_batches']};"
            f"compactions={st['compactions']};"
            f"worst_apply_ms={1e3 * worst_apply:.1f}",
        ))
    return rows


def serving_durability():
    """Durability cost on the serving path: the same ~25% mutation
    request mix served with no WAL and with the WAL attached at each
    fsync policy.  Reports acked mutations/sec and the search p99 per
    mode — the number behind the fsync trade-off table in the README.
    check_bench gates interval_muts_per_s >= 0.8x nowal_muts_per_s
    (the default policy must not cost the serving path more than 20%
    of its mutation throughput)."""
    import gc
    import shutil
    import tempfile

    from repro.serving.wal import DurableIndex

    # settle the allocator before the first (nowal baseline) mode: this
    # stage runs last, and collecting the preceding stages' engine/
    # ticket graphs mid-measurement shows up directly in its p99
    gc.collect()

    X, Qm, gt = dataset()
    X_np = np.asarray(X)
    Qm = np.asarray(Qm)
    cfg = ASHConfig(b=2, d=D // 2, n_landmarks=16)
    key = jax.random.PRNGKey(0)
    base = AshIndex.build(key, X, cfg, backend="flat")
    reqs = _request_stream(Qm)
    per_mode = {}
    wal_note = ""
    us_interval = 0.0
    for mode in ("nowal", "always", "interval", "off"):
        for pass_ in ("warm", "timed"):
            idx = AshIndex.build(
                key, X, cfg, backend="flat", model=base.model
            )
            engine = QueryEngine(
                idx, batch_buckets=(8, 32), max_wait_s=0.005,
                auto_compact=0.3,
            )
            durable = None
            tmp = None
            if mode != "nowal":
                tmp = tempfile.mkdtemp(prefix=f"ash-bench-wal-{mode}-")
                durable = DurableIndex.create(
                    idx, tmp, fsync=mode
                )
                engine.attach_durability(durable)
            jax.block_until_ready(
                jax.tree_util.tree_leaves(idx._state)
            )
            for b in (8, 32):
                engine.submit(Qm[:b], k=10)
                engine.flush()
            tickets, muts, dt = _mutation_stream(
                engine, X_np, Qm, reqs, None, mutate_every=4
            )
            if durable is not None:
                if pass_ == "timed" and mode == "interval":
                    st = durable.stats()
                    wal_note = (
                        f";wal_appends={st['appends']};"
                        f"wal_bytes={st['appended_bytes']};"
                        f"wal_fsyncs={st['fsyncs']}"
                    )
                durable.close()
                shutil.rmtree(tmp, ignore_errors=True)
        p99 = np.percentile([t.stats.latency_s for t in tickets], 99)
        per_mode[mode] = (len(muts) / dt, 1e3 * p99)
        if mode == "interval":
            us_interval = 1e6 * dt / len(reqs)
    derived = ";".join(
        f"{m}_muts_per_s={r:.1f};p99_{m}_ms={p:.2f}"
        for m, (r, p) in per_mode.items()
    )
    # free the per-mode engines/ticket graphs before the next stage —
    # eight index builds of residue otherwise skews later timings
    del engine, idx, tickets, muts
    gc.collect()
    return [row("serving/durability_flat", us_interval,
                derived + wal_note)]


def _closed_loop_direct(index, n_clients, reqs_each, pool, nprobe):
    """The no-engine baseline for the closed-loop rows: each client
    thread calls ``index.search`` per request and blocks on the device
    result — every request pays its own dispatch and its own gather,
    and concurrent callers serialize instead of sharing a fused call.
    Returns (per-request latencies, wall seconds)."""
    lats = [[] for _ in range(n_clients)]
    errors = []

    def client(cid):
        rng = np.random.RandomState(1000 + cid)
        try:
            for _ in range(reqs_each):
                q = pool[rng.randint(0, pool.shape[0])][None, :]
                t1 = time.perf_counter()
                jax.block_until_ready(
                    index.search(q, k=10, nprobe=nprobe)
                )
                lats[cid].append(time.perf_counter() - t1)
        except Exception as e:
            errors.append(e)

    threads = [
        threading.Thread(target=client, args=(c,), daemon=True)
        for c in range(n_clients)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return np.concatenate([np.asarray(x) for x in lats]), dt


def _closed_loop(index, n_clients, reqs_each, Qm, *, nprobe=None,
                 mutator=None, auto_compact=None, background=False,
                 engine_kw=None, buckets=(8, 32), warm_pool=None):
    """Closed-loop clients through a ServingFrontend: each thread
    submits a 1-row request, blocks on its ticket, repeats.  Returns
    (per-request latencies, wall seconds, engine).  ``mutator(fe,
    stop)`` runs on its own thread for the duration when given;
    ``background`` attaches a BackgroundCompactor so ``auto_compact``
    leaves the serving path; ``engine_kw`` adds EngineConfig overrides
    (the adaptive-probing row arms row_budget/nprobe_min here);
    ``buckets`` picks the engine's batch-bucket ladder.  ``warm_pool``
    streams those rows through the engine before the clock starts so
    a hot-pool run measures the steady state (prep/probe caches warm,
    like the jit warmup both paths already get) rather than the
    one-time cold fill."""
    from repro.serving.compactor import BackgroundCompactor
    from repro.serving.frontend import ServingFrontend

    engine = QueryEngine(index, batch_buckets=buckets,
                         max_wait_s=0.002, auto_compact=auto_compact,
                         **(engine_kw or {}))
    if warm_pool is not None:
        wb = max(buckets)
        for s in range(0, warm_pool.shape[0], wb):
            engine.search(warm_pool[s:s + wb], k=10, nprobe=nprobe)
    compactor = (
        BackgroundCompactor(engine).start() if background else None
    )
    lats = [[] for _ in range(n_clients)]
    errors = []
    stop = threading.Event()
    t0 = time.perf_counter()
    with ServingFrontend(engine) as fe:
        def client(cid):
            rng = np.random.RandomState(1000 + cid)
            try:
                for _ in range(reqs_each):
                    q = Qm[rng.randint(0, Qm.shape[0])][None, :]
                    t1 = time.perf_counter()
                    fe.search(q, k=10, nprobe=nprobe, timeout=120.0)
                    lats[cid].append(time.perf_counter() - t1)
            except Exception as e:
                errors.append(e)

        threads = [
            threading.Thread(target=client, args=(c,), daemon=True)
            for c in range(n_clients)
        ]
        mut_thread = None
        if mutator is not None:
            mut_thread = threading.Thread(
                target=mutator, args=(fe, stop), daemon=True
            )
            mut_thread.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        if mut_thread is not None:
            mut_thread.join(timeout=120.0)
    dt = time.perf_counter() - t0
    if compactor is not None:
        compactor.wait_idle(60.0)
        compactor.stop()
    if errors:
        raise errors[0]
    return np.concatenate([np.asarray(x) for x in lats]), dt, engine


def serving_concurrent():
    """The concurrent-serving row: closed-loop multi-client QPS/p99
    through the ServingFrontend driver vs the same loop single-caller
    (concurrent clients share buckets a single caller underfills), and
    search p99 while compaction runs in the background vs synchronous
    auto-compaction stalling the serving path.  check_bench enforces
    qps >= qps_single and p99_bg_compact_ms < p99_sync_compact_ms."""
    X, Qm, gt = dataset()
    X_np = np.asarray(X)
    Qm = np.asarray(Qm)
    cfg = ASHConfig(b=2, d=D // 2, n_landmarks=16)
    key = jax.random.PRNGKey(0)
    index = AshIndex.build(key, X, cfg, backend="flat")
    reqs_each = 16 if QUICK else 40

    # warm every bucket the closed loops can hit (driver-batched 1-row
    # requests land in bucket 8; backlog spills into 32)
    warm = QueryEngine(index, batch_buckets=(8, 32), max_wait_s=0.002)
    for b in (8, 32):
        warm.search(Qm[:b] if Qm.shape[0] >= b else Qm, k=10)

    lat1, dt1, _ = _closed_loop(index, 1, 8 * reqs_each, Qm)
    qps_single = lat1.size / dt1
    lat8, dt8, engine = _closed_loop(index, 8, reqs_each, Qm)
    qps = lat8.size / dt8
    p50, p99 = np.percentile(lat8, [50, 99])
    st = engine.stats.snapshot()

    # compaction-active p99: cycles of (add B rows, delete them) push
    # the dead fraction over auto_compact every cycle; the index
    # returns to warmed shapes each cycle so the runs compare the
    # compaction path itself, not stray recompiles.  Synchronous
    # auto-compaction rebuilds survivors inline under the index
    # barrier (searches queue behind it); the background compactor
    # rebuilds off-thread and only swaps under the barrier.
    B = 128
    cycles = 4 if QUICK else 8

    def mutator(fe, stop):
        for _ in range(cycles):
            if stop.is_set():
                return
            ids = fe.submit_add(
                X_np[np.random.RandomState(5).randint(0, X_np.shape[0],
                                                      B)]
            ).result(120.0)
            fe.submit_delete(ids).result(120.0)
            # let compaction land before the next cycle so both runs
            # walk the same (warmed) payload-shape sequence
            t_wait = time.perf_counter()
            while (fe.engine.index().n_dead
                   and time.perf_counter() - t_wait < 30.0):
                time.sleep(0.001)

    def compaction_run(background):
        idx = AshIndex.build(key, X, cfg, backend="flat",
                             model=index.model)
        warm2 = QueryEngine(idx, batch_buckets=(8, 32),
                            max_wait_s=0.002)
        warm2.search(Qm[:8], k=10)
        warm_ids = warm2.submit_add(X_np[:B]).result()  # warm n0+B
        warm2.search(Qm[:8], k=10)  # trace at the grown payload shape
        warm2.submit_delete(warm_ids).result()
        idx.compact()  # back to n0; compact internals warmed
        warm2.search(Qm[:8], k=10)
        lats, _, eng = _closed_loop(
            idx, 4, reqs_each, Qm, mutator=mutator,
            auto_compact=0.001, background=background,
        )
        return float(np.percentile(lats, 99)), eng

    p99_sync, _ = compaction_run(background=False)
    p99_bg, eng_bg = compaction_run(background=True)
    comp = eng_bg.stats.snapshot()["compaction"]

    return [row(
        "serving/concurrent_flat_c8", 1e6 * dt8 / lat8.size,
        f"qps={qps:.0f};qps_single={qps_single:.0f};"
        f"p50_ms={1e3 * p50:.2f};p99_ms={1e3 * p99:.2f};"
        f"p99_sync_compact_ms={1e3 * p99_sync:.2f};"
        f"p99_bg_compact_ms={1e3 * p99_bg:.2f};"
        f"bg_runs={comp['runs']};bg_retries={comp['retries']};"
        f"fill={st['bucket_fill']}",
    )]


def serving_adaptive():
    """Load-adaptive probing under genuine queue pressure: 8
    closed-loop clients hammer an IVF index through the frontend
    driver with ``row_budget`` + ``nprobe_min`` armed and a tight
    pressure horizon.  While fused gathers hold the driver, waiting
    groups age past the horizon and flushes walk the nprobe ladder
    down; when the queue drains, fidelity recovers.  The row surfaces
    the recall-trade telemetry (degraded flush count, effective-nprobe
    floor, deduped rows per query) next to the latency it buys."""
    X, Qm, gt = dataset()
    X_np = np.asarray(X)
    cfg = ASHConfig(b=2, d=D // 2, n_landmarks=32)
    key = jax.random.PRNGKey(0)
    ivf = AshIndex.build(key, X, cfg, backend="ivf")
    reqs_each = 16 if QUICK else 40

    warm = QueryEngine(ivf, batch_buckets=(8, 32), max_wait_s=0.002)
    for b in (8, 32):
        warm.search(X_np[:b], k=10, nprobe=8)

    engine_kw = dict(
        row_budget=int(0.5 * ivf.n), nprobe_min=2,
        pressure_age_s=0.02,
    )
    # warm the degraded rungs of the ladder too (8 -> 4 -> 2), so the
    # timed loop never charges a rung's first trace to a ticket
    for np_w in (4, 2):
        warm.search(X_np[:8], k=10, nprobe=np_w)
    lats, dt, engine = _closed_loop(
        ivf, 8, reqs_each, X_np, nprobe=8, engine_kw=engine_kw
    )
    p50, p99 = np.percentile(lats, [50, 99])
    st = engine.stats.snapshot()
    ic = st["ivf_cost"]
    eff = {int(k): v for k, v in ic["effective_nprobe"].items()}
    return [row(
        "serving/adaptive_ivf_c8", 1e6 * dt / lats.size,
        f"qps={lats.size / dt:.0f};"
        f"p50_ms={1e3 * p50:.2f};p99_ms={1e3 * p99:.2f};"
        f"degraded={ic['degraded']};"
        f"min_eff_nprobe={min(eff) if eff else 8};"
        f"rows_per_q={ic['rows_per_query']};"
        f"budget_flushes={st['flushes']['budget']}",
    )]


def _tiered_pass(index, Qm, reqs, nprobe):
    """One sequential pass of the request mix through ``index.search``;
    returns (latencies, wall seconds, scores list, ids list)."""
    lats, scores, ids = [], [], []
    t0 = time.perf_counter()
    for i, m in reqs:
        t1 = time.perf_counter()
        s, out_ids = jax.block_until_ready(
            index.search(Qm[i:i + m], k=10, nprobe=nprobe)
        )
        lats.append(time.perf_counter() - t1)
        scores.append(np.asarray(s))
        ids.append(np.asarray(out_ids))
    return np.asarray(lats), time.perf_counter() - t0, scores, ids


def serving_tiered():
    """Tiered IVF (host-resident lists + device hot set) vs the
    HBM-resident IVF it pages for: the same request mix is served by
    the HBM index, by a tiered index whose hot-set budget covers a
    quarter of the payload (cold cache, then steady state), and by a
    covering-budget tiered index whose results must be bit-identical
    to HBM at equal probe sets.  The row carries the cache gauges
    (hit rates, paged rows, resident vs total bytes) the structural
    gate in tools/check_bench.py holds."""
    import tempfile

    from repro.index.tiered import TieredIVFBackend

    X, Qm, gt = dataset()
    cfg = ASHConfig(b=2, d=D // 2, n_landmarks=32)
    key = jax.random.PRNGKey(0)
    nprobe = 8
    hbm = AshIndex.build(key, X, cfg, backend="ivf")
    # same key/config/build path => same model, landmarks and probe
    # sets as the HBM index, so covering-budget results are bitwise
    # comparable request by request
    cover = AshIndex.build(key, X, cfg, backend="tiered_ivf",
                           hot_bytes=1 << 30)
    total = TieredIVFBackend.tier_stats(cover._state)["total_bytes"]
    hot = max(1, total // 4)
    with tempfile.TemporaryDirectory() as tmp:
        cover.save(f"{tmp}/tiered")  # reuse the build, resize the set
        paged = AshIndex.load(f"{tmp}/tiered", hot_bytes=hot)
    Qm = np.asarray(Qm)
    reqs = _request_stream(Qm)
    n_req = len(reqs)

    lat_h = dt_h = None
    for _ in range(2):  # pass 1 compiles the request shapes
        lat_h, dt_h, s_h, i_h = _tiered_pass(hbm, Qm, reqs, nprobe)

    # paged tiered: compile pass, then drop the hot set for a true
    # cold-cache pass, then the steady-state pass over the same mix
    _tiered_pass(paged, Qm, reqs, nprobe)
    paged._state.cache.clear()
    t0 = TieredIVFBackend.tier_stats(paged._state)
    lat_c, dt_c, _, _ = _tiered_pass(paged, Qm, reqs, nprobe)
    t1 = TieredIVFBackend.tier_stats(paged._state)
    lat_w, dt_w, _, _ = _tiered_pass(paged, Qm, reqs, nprobe)
    t2 = TieredIVFBackend.tier_stats(paged._state)
    paged_rows_cold = t1["paged_rows"] - t0["paged_rows"]
    warm_lookups = (t2["hits"] - t1["hits"]) + (t2["misses"] - t1["misses"])
    hit_warm = (t2["hits"] - t1["hits"]) / max(1, warm_lookups)

    # covering budget: one fill pass, then every probe hits the cache
    _tiered_pass(cover, Qm, reqs, nprobe)
    c1 = TieredIVFBackend.tier_stats(cover._state)
    lat_v, dt_v, s_v, i_v = _tiered_pass(cover, Qm, reqs, nprobe)
    c2 = TieredIVFBackend.tier_stats(cover._state)
    cover_lookups = (c2["hits"] - c1["hits"]) + (c2["misses"] - c1["misses"])
    hit_cover = (c2["hits"] - c1["hits"]) / max(1, cover_lookups)
    bitwise = int(all(
        np.array_equal(a, b) and np.array_equal(c, d)
        for (a, b), (c, d) in zip(zip(s_h, s_v), zip(i_h, i_v))
    ))
    rec = recall10(np.concatenate(i_v, axis=0), gt)

    p99_h, p99_c, p99_w, p99_v = (
        float(np.percentile(x, 99)) for x in (lat_h, lat_c, lat_w, lat_v)
    )
    return [row(
        "serving/tiered_ivf", 1e6 * dt_w / n_req,
        f"qps={n_req / dt_w:.0f};qps_hbm={n_req / dt_h:.0f};"
        f"qps_cold={n_req / dt_c:.0f};qps_cover={n_req / dt_v:.0f};"
        f"p99_hbm_ms={1e3 * p99_h:.2f};p99_cold_ms={1e3 * p99_c:.2f};"
        f"p99_warm_ms={1e3 * p99_w:.2f};p99_cover_ms={1e3 * p99_v:.2f};"
        f"hit_rate_warm={hit_warm:.4f};hit_rate_cover={hit_cover:.4f};"
        f"hot_bytes={hot};total_bytes={total};"
        f"paged_rows_cold={paged_rows_cold};"
        f"bitwise_cover={bitwise};recall_at_10={rec:.4f}",
    )]


# serving_durability runs LAST: its four per-mode engine builds leave
# enough allocator/jit-cache residue to visibly inflate the
# sync-vs-background compaction p99 comparison in serving_concurrent
# when it runs earlier in the process
ALL = [serving_engine, serving_mutation, serving_concurrent,
       serving_adaptive, serving_tiered, serving_durability]
