"""Correctness readings of a cell over many seeds, in one process.

    python3 bench/seeds.py --workload <cell> --seeds 1,2,3 --seconds <s>

Runs the cell once per seed, as ``run.py --trace 0`` would, and beside
each run's compared numbers prints the control's on the same sample:
the plain reference put in the program's place one precision below the
configuration's (bfloat16 operands).  The program's readings set the
lower end of each limit and the control's the upper end (see PERF.md).
One JSON line per seed.  The window may be shorter than a run's, as
long as it answers as many rows as a run compares.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, enable_cache, setup_paths  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    setup_paths()
    from bench import harness as H

    H.env_for_cache(ROOT)
    enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        one = argparse.Namespace(workload=args.workload, seed=seed,
                                 seconds=args.seconds, trace=0)
        t = time.perf_counter()
        try:
            res = H.run(one, t_process=t, with_control=True)
        except SystemExit as e:
            return int(e.code or 1)
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "checks": {k: v["value"] for k, v in res["checks"].items()},
            "control": res["control"],
            # set-up and memory are a fresh process's numbers only: a
            # later seed here finds its programs compiled and the
            # earlier seeds' device buffers still held
            "metrics": {k: v["value"] for k, v in res["metrics"].items()
                        if k not in ("setup_s", "hbm_bytes_per_row")},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
