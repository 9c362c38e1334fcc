"""Find the highest rate an open-loop cell sustains, in one process.

    python3 bench/sweep.py --workload <open cell> --seed <n> \\
        --seconds <s> --rates 500,1000,2000

Sets the cell up once, then offers each rate in turn for ``--seconds``
and prints one JSON line per rate: offered and completed rows/s, the
latency median, 95th and 99th percentiles from the due time, the generator's
lag, and whether a backlog grew (the median latency of the window's
last quarter against its first).  The rate a cell runs at is written
into its traffic file by hand, from such a sweep.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

from run import ROOT, enable_cache, setup_paths  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    args = p.parse_args(argv)
    args.trace = 0
    rates = [float(r) for r in args.rates.split(",")]
    setup_paths()
    from bench import harness as H

    H.env_for_cache(ROOT)
    enable_cache()
    need = sum(int(round(r * args.seconds)) for r in rates)
    try:
        pr = H.prepare(args, t_process=T_PROCESS, n_window=need)
    except SystemExit as e:
        return int(e.code or 1)
    at = 0
    for rate in rates:
        w = H.measure(pr, args.seconds, rate=rate, pool_from=at)
        at += len(w.requests)
        reqs = w.requests
        lat = np.array([r.done - r.due if r.ok else np.inf for r in reqs])
        lag = np.array([r.sent - r.due for r in reqs])
        q = max(1, len(reqs) // 4)
        done = sum(1 for r in reqs if r.ok and r.done <= w.t_end)
        print(json.dumps({
            "rate_offered": rate,
            "rate_completed": done / args.seconds,
            "failed": int(sum(not r.ok for r in reqs)),
            "latency_p50_ms": 1e3 * float(np.quantile(lat, 0.5)),
            "latency_p95_ms": 1e3 * float(np.quantile(lat, 0.95)),
            "latency_p99_ms": 1e3 * float(np.quantile(lat, 0.99)),
            "gen_lag_p99_ms": 1e3 * float(np.quantile(lag, 0.99)),
            "latency_p50_first_quarter_ms":
                1e3 * float(np.median(lat[:q])),
            "latency_p50_last_quarter_ms":
                1e3 * float(np.median(lat[-q:])),
            "engine": w.engine_delta,
            "compiles_in_window": w.compiles,
        }), flush=True)
    pr.served.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
