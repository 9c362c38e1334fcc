"""End-to-end driver (the paper's kind): serve a mixed stream of ANN
requests from an ASH-compressed IVF index through the micro-batching
QueryEngine, with exact-rerank and latency stats.

  PYTHONPATH=src python examples/ann_serving.py
"""
import time

import jax
import numpy as np

from repro.core import ASHConfig
from repro.data.synthetic import embedding_dataset, isotropy_diagnostics
from repro.index import AshIndex, metrics
from repro.serving import QueryEngine


def main():
    key = jax.random.PRNGKey(7)
    kx, kq, kb = jax.random.split(key, 3)
    D, n = 128, 50_000
    X = embedding_dataset(kx, n, D)
    print("dataset diagnostics (paper Table 4 regime):",
          isotropy_diagnostics(X))

    cfg = ASHConfig(b=2, d=64, n_landmarks=128)  # nlist = 128
    t0 = time.time()
    index = AshIndex.build(kb, X, cfg, backend="ivf", keep_raw=True)
    print(f"index built in {time.time() - t0:.1f}s ({index!r})")

    # mixed request stream: single queries and small batches, the shape
    # traffic actually arrives in — the engine buckets them so only a
    # handful of jit traces serve everything
    rng = np.random.RandomState(0)
    sizes = rng.choice([1, 2, 4, 8], size=64, p=[0.4, 0.3, 0.2, 0.1])
    queries = [embedding_dataset(jax.random.fold_in(kq, i), int(m), D)
               for i, m in enumerate(sizes)]
    gt = [metrics.exact_topk(q, X, k=10)[1] for q in queries]

    for nprobe in (4, 16, 64):
        # untimed warmup pass compiles every bucket trace this stream
        # will hit (throwaway engine so the timed pass starts with
        # fresh stats)
        warm = QueryEngine(index, batch_buckets=(8, 32),
                           max_wait_s=0.002)
        for q in queries:
            warm.submit(q, k=10, nprobe=nprobe, rerank=50)
        warm.flush()
        engine = QueryEngine(index, batch_buckets=(8, 32),
                             max_wait_s=0.002)
        t0 = time.time()
        tickets = [engine.submit(q, k=10, nprobe=nprobe, rerank=50)
                   for q in queries]
        engine.flush()
        dt = time.time() - t0
        rec = [float(metrics.recall_at(np.asarray(t.result()[1]), g))
               for t, g in zip(tickets, gt)]
        lat = sorted(t.stats.latency_s * 1e3 for t in tickets)
        st = engine.stats.snapshot()
        print(f"nprobe={nprobe:3d}: 10-recall@10="
              f"{sum(rec) / len(rec):.4f}  "
              f"p50={lat[len(lat) // 2]:.1f}ms  p99~={lat[-1]:.1f}ms  "
              f"({int(sizes.sum()) / dt:.0f} QPS, "
              f"{st['batches']} fused calls for {st['requests']} reqs, "
              f"fill={st['bucket_fill']:.2f})")


if __name__ == "__main__":
    main()
