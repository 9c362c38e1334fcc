"""Benchmark harness — one function per paper table/figure plus the
serving-engine suite.

Prints ``name,us_per_call,derived`` CSV rows (plus section markers);
``--json`` additionally emits the machine-readable perf trajectory:

  PYTHONPATH=src python -m benchmarks.run                 # full suite
  PYTHONPATH=src python -m benchmarks.run fig9            # substring filter
  PYTHONPATH=src python -m benchmarks.run --json          # + BENCH_*.json
  PYTHONPATH=src python -m benchmarks.run --json out.json # + combined file
  PYTHONPATH=src python -m benchmarks.run --quick --json  # CI smoke size

With ``--json``, one ``BENCH_<group>.json`` file per benchmark group
(figures / kernels / serving) is written to the working directory so CI
artifacts and committed snapshots can track regressions over PRs;
``tools/check_bench.py`` gates on their contents.
"""
import argparse
import json
import os
import sys
import time

SCHEMA_VERSION = 1


def _parse_derived(derived: str) -> dict:
    """'k1=v1;k2=v2' -> dict, values floated where possible."""
    out = {}
    for part in str(derived).split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v)
        except ValueError:
            out[k] = v
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("filter", nargs="?", default="",
                   help="substring filter on benchmark function names")
    p.add_argument("--json", nargs="?", const="", default=None,
                   metavar="OUT",
                   help="write BENCH_<group>.json files (and a combined "
                        "file at OUT, if given)")
    p.add_argument("--quick", action="store_true",
                   help="reduced problem size (CI smoke)")
    args = p.parse_args(argv)

    if args.json and not args.json.endswith(".json"):
        # nargs="?" would otherwise swallow a positional filter, e.g.
        # `benchmarks.run --json fig9` silently running the full suite
        p.error(f"--json OUT must end in .json (got {args.json!r}); "
                f"put the filter before --json")
    if args.quick:
        os.environ["ASH_BENCH_QUICK"] = "1"
    sys.path.insert(0, "src")
    from repro import compile_cache

    compile_cache.enable()
    from benchmarks import kernel_bench, paper_figures, serving_bench

    groups = (
        ("figures", paper_figures.ALL),
        ("kernels", kernel_bench.ALL),
        ("serving", serving_bench.ALL),
    )
    print("name,us_per_call,derived")
    results = {g: [] for g, _ in groups}
    t0 = time.time()
    for group, fns in groups:
        for fn in fns:
            if args.filter and args.filter not in fn.__name__:
                continue
            print(f"# --- {fn.__name__} ---", flush=True)
            try:
                for r in fn():
                    print(r, flush=True)
                    name, us, derived = str(r).split(",", 2)
                    results[group].append({
                        "name": name,
                        "us_per_call": float(us),
                        "derived": _parse_derived(derived),
                        "error": None,
                    })
            except Exception as e:  # keep the harness running
                print(f"{fn.__name__},0,ERROR:{e!r}", flush=True)
                results[group].append({
                    "name": fn.__name__,
                    "us_per_call": 0.0,
                    "derived": {},
                    "error": repr(e),
                })
    total_s = time.time() - t0
    print(f"# total {total_s:.1f}s")

    if args.json is not None:
        combined = {
            "schema_version": SCHEMA_VERSION,
            "quick": args.quick,
            "filter": args.filter,
            "total_s": round(total_s, 1),
            "groups": {g: rows for g, rows in results.items() if rows},
        }
        # The BENCH_<group>.json snapshots track the full-size perf
        # trajectory across PRs — never clobber them with quick-size or
        # filtered partial rows (those go to the combined OUT only).
        if not args.quick and not args.filter:
            for group, _ in groups:
                rows = results[group]
                if not rows:
                    continue
                payload = {
                    "schema_version": SCHEMA_VERSION,
                    "group": group,
                    "quick": args.quick,
                    "rows": rows,
                }
                path = f"BENCH_{group}.json"
                with open(path, "w") as f:
                    json.dump(payload, f, indent=1)
                print(f"# wrote {path} ({len(rows)} rows)")
        if args.json:
            with open(args.json, "w") as f:
                json.dump(combined, f, indent=1)
            print(f"# wrote {args.json}")
        elif args.quick or args.filter:
            print("# quick/filtered run: snapshot files skipped "
                  "(pass --json OUT for a combined file)")
    errored = [r["name"] for rows in results.values() for r in rows
               if r["error"] is not None]
    if errored:
        print(f"# {len(errored)} benchmark(s) errored: "
              f"{', '.join(errored)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
