"""Smoke run of the ASH serving path on a TPU, in one process.

    python chip_smoke.py [--seed 0]          # one chip
    python chip_smoke.py --chips 4           # sharded backend, 4 chips

Default mode drives the path a user would: ``AshIndex.build`` ->
``QueryEngine`` -> ``ServingFrontend``, at the shape of ann-benchmarks
``sift-128-euclidean`` (1,000,000 x 128 base rows, L2).  Nothing can be
downloaded, so the corpus and queries come from
``repro.data.synthetic.embedding_dataset`` under ``--seed``.  One model
(``ASHConfig(b=2, d=64, n_landmarks=1024)``) is trained and shared by a
``flat`` and an ``ivf`` index, both with ``keep_raw=True``.  The
frontend then serves a few hundred 8-row requests: k=10 and k=100 on
both backends (IVF at nprobe=16), a rerank=100 batch, a few
``coarse="int8"`` requests, and one add + delete batch followed by
searches that run the row-validity mask.

Checks (any failure exits non-zero; nothing is caught):

* JAX's first device is a TPU — there is no CPU fallback;
* every served search program holds a compiled Pallas kernel
  (``tpu_custom_call``), so neither interpret mode nor the jnp oracle
  serves;
* every result has the expected shape, finite scores and in-range ids,
  and deleted ids never come back;
* the Pallas kernels agree with their jnp oracles (``use_pallas=False``)
  on the same chip: identical top-10 id sets on at least
  ``MIN_ORACLE_AGREEMENT`` of the query rows (see there);
* recall@10 against an f32 brute-force reference is printed per
  backend.

``--chips 4`` runs only the sharded backend over a 4-device mesh and
``flat`` on the mesh's first chip, on the same corpus, and compares the
two.  Wall, compile and memory figures printed here are smoke timings
of one cold run, not benchmark numbers.  The last line of standard
output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

N_BASE = 1_000_000  # sift-128-euclidean: 1M base rows
DIM = 128
N_QUERIES = 512
REQ_ROWS = 8
NPROBE = 16
KERNEL_MARK = "tpu_custom_call"
# The kernels and their oracles compute the same f32 estimator and
# differ only in summation order (MXU tiles vs XLA's reduction), which
# can swap two candidates whose estimates tie to within ~1e-6 at the
# 10th place.  Such ties are rare in a 1M-row corpus, so nearly every
# row must agree exactly; 0.95 leaves room for the ties and nothing
# else (a wrong tile, mask or merge disagrees on most rows).
MIN_ORACLE_AGREEMENT = 0.95

_compile_s = [0.0]
_cache_hits = [0]


def _watch_compiles():
    import jax

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _compile_s[0] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _cache_hits[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


@contextlib.contextmanager
def phase(name):
    c0, t0 = _compile_s[0], time.perf_counter()
    yield
    print(f"[smoke-timing] phase={name} "
          f"wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={_compile_s[0] - c0:.3f}", flush=True)


def require_tpu():
    """The first device must be a TPU; returns the device list."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{devs[0].platform!r} ({devs[0].device_kind})")
    return devs


def assert_kernel(fn, *args, where):
    """``fn(*args)`` lowered for the chip must contain a Pallas call."""
    import jax

    text = jax.jit(fn).lower(*args).as_text()
    n = text.count(KERNEL_MARK)
    assert n > 0, f"{where}: no compiled Pallas kernel in the program"
    print(f"[kernel] {where}: {n} {KERNEL_MARK}", flush=True)


def check_result(name, scores, ids, m, k, n_ids):
    import numpy as np

    s, i = np.asarray(scores), np.asarray(ids)
    assert s.shape == (m, k) and i.shape == (m, k), (name, s.shape)
    assert np.isfinite(s).all(), f"{name}: non-finite scores"
    assert ((i >= 0) & (i < n_ids)).all(), f"{name}: id out of range"
    assert (np.diff(s, axis=1) <= 0).all(), f"{name}: scores unsorted"


def recall10(ids, gt):
    import numpy as np

    hit = (np.asarray(ids)[:, :10, None] == gt[:, None, :10]).any(1)
    return float(hit.sum(1).mean() / 10)


def set_agreement(a, b, k=10):
    import numpy as np

    a, b = np.sort(np.asarray(a)[:, :k], 1), np.sort(np.asarray(b)[:, :k], 1)
    return float((a == b).all(1).mean())


def make_data(seed, n_extra):
    """Base rows, query rows and rows to add later, from one draw of the
    generator (one distribution)."""
    import jax
    import numpy as np

    from repro.data.synthetic import embedding_dataset

    n = N_BASE
    allv = embedding_dataset(
        jax.random.PRNGKey(seed), n + N_QUERIES + n_extra, DIM
    )
    X = allv[:n].block_until_ready()
    Q = np.asarray(allv[n:n + N_QUERIES])
    extra = np.asarray(allv[n + N_QUERIES:])
    return X, Q, extra


def ground_truth(X, Q, k=100):
    """f32 brute-force L2 top-k, full-precision matmuls, in chunks."""
    import jax
    import numpy as np

    from repro.index.metrics import exact_topk

    out = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(Q), 64):
            out.append(np.asarray(
                exact_topk(Q[lo:lo + 64], X, k=k, metric="l2")[1]
            ))
    return np.concatenate(out)


def build_model_index(seed, X):
    import jax

    from repro.core import ASHConfig
    from repro.index import AshIndex

    cfg = ASHConfig(b=2, d=64, n_landmarks=1024)
    # k-means over 256 rows per list (faiss's training guideline); the
    # full corpus is still encoded
    return AshIndex.build(
        jax.random.PRNGKey(seed + 1), X, cfg, backend="flat",
        metric="l2", keep_raw=True, landmark_sample=256 * 1024,
    )


def serve(fe, cases):
    """Submit every case's 8-row requests, then collect (scores, ids)."""
    import numpy as np

    tickets = {
        name: [fe.submit(Q[lo:lo + REQ_ROWS], **kw)
               for lo in range(0, len(Q), REQ_ROWS)]
        for name, (Q, kw) in cases.items()
    }
    out = {}
    for name, ts in tickets.items():
        res = [t.result(timeout=900) for t in ts]
        out[name] = (np.concatenate([r[0] for r in res]),
                     np.concatenate([r[1] for r in res]))
    return out


def run_one_chip(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.index import AshIndex
    from repro.serving.engine import QueryEngine
    from repro.serving.frontend import ServingFrontend

    with phase("data"):
        X, Q, extra = make_data(args.seed, REQ_ROWS)
    with phase("build_flat"):
        flat = build_model_index(args.seed, X)
    with phase("build_ivf"):
        ivf = AshIndex.build(
            None, X, flat.config, backend="ivf", metric="l2",
            keep_raw=True, model=flat.model,
        )
    with phase("ground_truth"):
        gt = ground_truth(X, Q)
    del X
    print(f"[index] n={flat.n} lists={ivf._state.invlists.shape[0]} "
          f"max_list_len={ivf._state.max_list_len} "
          f"ivf_candidates_per_query={NPROBE * ivf._state.max_list_len}",
          flush=True)

    engine = QueryEngine({"flat": flat, "ivf": ivf})
    half = Q[:64]
    cases = {
        "flat_k10": (Q, dict(k=10, index="flat")),
        "flat_k100": (Q, dict(k=100, index="flat")),
        "ivf_k10": (Q, dict(k=10, index="ivf", nprobe=NPROBE)),
        "ivf_k100": (Q, dict(k=100, index="ivf", nprobe=NPROBE)),
        "flat_rerank100": (half, dict(k=10, index="flat", rerank=100)),
        "flat_coarse_int8": (half, dict(k=10, index="flat",
                                        coarse="int8")),
    }
    with ServingFrontend(engine) as fe:
        with phase("serve"):
            served = serve(fe, cases)
        dead = np.unique(served["flat_k10"][1][:REQ_ROWS, 0])
        with phase("mutate"):
            fe.submit_add(extra, index="flat").result(timeout=900)
            fe.submit_delete(dead, index="flat").result(timeout=900)
        with phase("serve_after_delete"):
            after = serve(fe, {"flat_masked_k10": (
                half, dict(k=10, index="flat"))})
    n_reqs = sum(len(q) // REQ_ROWS for q, _ in cases.values())
    n_reqs += len(half) // REQ_ROWS
    print(f"[serve] {n_reqs} requests of {REQ_ROWS} rows served",
          flush=True)

    for name, (s, i) in {**served, **after}.items():
        q, kw = (cases.get(name) or (half, dict(k=10)))
        check_result(name, s, i, len(q), kw["k"], flat.n)
    assert not np.isin(after["flat_masked_k10"][1], dead).any(), \
        "a deleted id came back"
    recalls = {
        name: recall10(i, gt[:len(i)]) for name, (s, i) in served.items()
    }
    for name, r in recalls.items():
        print(f"[recall] {name} recall@10={r:.4f}", flush=True)

    with phase("kernel_check"):
        prep8 = flat.prepare(jnp.asarray(Q[:REQ_ROWS]))
        for name, (idx, kw) in {
            "flat_k10": (flat, dict(k=10)),
            "flat_k100": (flat, dict(k=100)),
            "ivf_k10_nprobe16": (ivf, dict(k=10, nprobe=NPROBE)),
            "ivf_k100_nprobe16": (ivf, dict(k=100, nprobe=NPROBE)),
            "flat_rerank100": (flat, dict(k=10, rerank=100)),
            "flat_coarse_int8": (flat, dict(k=10, coarse="int8")),
            "flat_masked_after_delete": (flat, dict(k=10)),
        }.items():
            assert_kernel(
                lambda st, p, idx=idx, kw=kw:
                    idx._backend.search_prepped(st, p, **kw),
                idx._state, prep8, where=name,
            )
        assert flat._state.live is not None  # the masked variant ran

    with phase("oracle_agreement"):
        agree = oracle_agreement(flat, ivf, Q)
    for name, a in agree.items():
        print(f"[oracle] {name} top10_set_agreement={a:.4f} "
              f"(min {MIN_ORACLE_AGREEMENT})", flush=True)
        assert a >= MIN_ORACLE_AGREEMENT, (name, a)


def oracle_agreement(flat, ivf, Q):
    """Kernel vs jnp oracle on the same prep and the same chip: dense
    fused top-k (with the row mask), IVF gather top-k, and the int8
    coarse -> refine pipeline, REQ_ROWS queries at a time (the gather
    oracle materializes every candidate's unpacked codes).  Oracle
    matmuls run at full f32 precision, like the kernels' f32 passes."""
    import jax
    import numpy as np

    from repro.index import common as C
    from repro.kernels import ops

    st, ist = flat._state, ivf._state
    got = {}
    want = {}
    for lo in range(0, len(Q), REQ_ROWS):
        prep = flat.prepare(Q[lo:lo + REQ_ROWS])
        probe = ivf._backend.probe_sets(ist, prep, NPROBE)
        rows = ist.invlists[probe].reshape(len(probe), -1)
        runs = {
            "flat_fused_topk": lambda up: ops.ash_score_topk(
                st.model, prep, st.payload, 10, metric="l2",
                stats=st.stats, row_valid=st.live, use_pallas=up),
            "ivf_gather_topk": lambda up: ops.ash_score_gather_topk(
                ist.model, prep, ist.payload, rows, 10, metric="l2",
                stats=ist.stats, use_pallas=up),
            "flat_coarse_refine": lambda up: ops.coarse_refine_topk(
                st.model, prep, st.payload, 10,
                shortlist=C.default_shortlist(), metric="l2",
                stats=st.stats, coarse=st.coarse, row_valid=st.live,
                use_pallas=up),
        }
        for name, run in runs.items():
            got.setdefault(name, []).append(np.asarray(run(True)[1]))
            with jax.default_matmul_precision("highest"):
                want.setdefault(name, []).append(
                    np.asarray(run(False)[1]))
    return {
        name: set_agreement(np.concatenate(got[name]),
                            np.concatenate(want[name]))
        for name in got
    }


def run_four_chips(args, devs):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.index import AshIndex
    from repro.serving.engine import QueryEngine
    from repro.serving.frontend import ServingFrontend

    assert len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}"
    with phase("data"):
        X, Q, _ = make_data(args.seed, 0)
    with phase("build_flat_chip0"):
        flat = build_model_index(args.seed, X)
    with phase("build_sharded"):
        mesh = Mesh(np.array(devs), ("data",))
        sharded = AshIndex.build(
            None, X, flat.config, backend="sharded", metric="l2",
            model=flat.model, mesh=mesh,
        )
    with phase("ground_truth"):
        gt = ground_truth(X, Q)
    del X
    st = sharded._state
    placed = {d.id for a in jax.tree_util.tree_leaves(st.sharded)
              for d in a.devices()}
    print(f"[mesh] payload shards on devices {sorted(placed)}; "
          f"flat on {sorted(d.id for d in flat.payload.codes.devices())}",
          flush=True)
    assert placed == {d.id for d in devs}, placed

    engine = QueryEngine({"flat": flat, "sharded": sharded})
    with ServingFrontend(engine) as fe, phase("serve"):
        served = serve(fe, {
            f"{b}_k{k}": (Q, dict(k=k, index=b))
            for b in ("sharded", "flat") for k in (10, 100)
        })
    for name, (s, i) in served.items():
        check_result(name, s, i, len(Q), int(name.split("_k")[1]), flat.n)

    with phase("kernel_check"):
        prep8 = flat.prepare(Q[:REQ_ROWS])
        assert_kernel(
            lambda pay, stats, p: st.searcher(10)(pay, p, stats=stats),
            st.sharded, st.sharded_stats, prep8, where="sharded_k10",
        )

    agree = {}
    for k in (10, 100):
        si, fi = served[f"sharded_k{k}"][1], served[f"flat_k{k}"][1]
        agree[f"k{k}_ordered"] = float((si == fi).all(1).mean())
        agree[f"k{k}_set"] = set_agreement(si, fi, k)
        ds = np.abs(served[f"sharded_k{k}"][0] - served[f"flat_k{k}"][0])
        print(f"[sharded-vs-flat] k={k} ordered_id_agreement="
              f"{agree[f'k{k}_ordered']:.4f} set_agreement="
              f"{agree[f'k{k}_set']:.4f} max_abs_score_diff="
              f"{float(ds.max()):.3g}", flush=True)
        print(f"[recall] sharded_k{k} recall@10="
              f"{recall10(si, gt):.4f} flat_k{k} recall@10="
              f"{recall10(fi, gt):.4f}", flush=True)
    s0, i0 = served["sharded_k10"]
    print(f"[merge] query 0 sharded top-10 ids={i0[0].tolist()} "
          f"scores={np.round(s0[0], 3).tolist()}", flush=True)
    print(f"[merge] query 0 flat    top-10 ids="
          f"{served['flat_k10'][1][0].tolist()}", flush=True)
    assert agree["k10_set"] >= MIN_ORACLE_AGREEMENT, agree


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the sharded backend over a "
                        "4-chip mesh against flat on one of its chips")
    args = p.parse_args(argv)

    from repro import compile_cache

    cache_dir = compile_cache.enable()
    devs = require_tpu()
    import jax

    _watch_compiles()
    dev = devs[0]
    print(f"[device] {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"compile cache {cache_dir}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(args, devs)
    else:
        run_one_chip(args)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"[smoke-timing] total wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={_compile_s[0]:.3f} "
          f"persistent_cache_hits={_cache_hits[0]} "
          f"peak_bytes_in_use={peak}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
