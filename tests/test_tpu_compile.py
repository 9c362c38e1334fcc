"""Compile the served Pallas kernels for a TPU v5e, without the chip.

The TPU compiler ships with the installed libtpu and compiles for a
described, unattached chip.  That catches what interpret mode cannot:
block shapes the chip refuses, VMEM overruns, and HBM blow-ups from
layouts XLA must copy into.  Shapes are the served ones: the
sift-128-euclidean smoke corpus (1M rows, d=64, b=2, 1024 landmarks,
L2) and a 10M-row scan for the memory bound.  Nothing runs; no result
is checked here (the interpret-mode oracle tests do that).  The served
flat and IVF search programs are also compiled whole, at small shapes,
to see the named scopes reach the compiled operations while the
kernels' instruction names stay as they are.

The topology is described inside a module fixture, never at import:
only one process may load libtpu at a time, and under several pytest
workers only the worker given this file may touch it.
"""
import importlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ASHConfig
from repro.core import scoring as S
from repro.data.synthetic import embedding_dataset
from repro.index import AshIndex
from repro.kernels.ash_score import (
    DEFAULT_BLOCK_N,
    ProbedLists,
    ash_score_coarse_topk_pallas,
    ash_score_gather_topk_pallas,
    ash_score_topk_pallas,
)

N, D, B, C = 1_000_000, 64, 2, 1024
W = D * B // 32  # packed uint32 words per row
# the cohere-768-cosine-flat corpus: d=128 at b=4, 16 words a row, cos
COS_D, COS_B = 128, 4
# IVF candidates per query at nprobe=16: 16 lists padded to 4096 rows,
# above the smoke corpus's longest list (1,670 rows at seed 0)
SMOKE_IVF_R = 16 * 4096
# the glove-100-angular IVF corpus: 1,183,514 rows in 1,024 lists, the
# longest 2,876 rows, 16 probed per query, d=64 at b=2
GLOVE_N, GLOVE_L, GLOVE_NPROBE = 1_183_514, 2_876, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory on one described chip, with JAX's
    persistent cache off (a chip compile written there cannot be read
    back without the chip)."""
    from jax.experimental.compilation_cache import compilation_cache

    chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _dense(spec, n, m, d=D, b=B):
    """codes, q_proj, scale, offset, cluster, ipq, qterm, rowterm."""
    f32 = jnp.float32
    return [
        spec((n, d * b // 32), jnp.uint32), spec((m, d), f32),
        spec((n,), f32),
        spec((n,), f32), spec((n,), jnp.int32), spec((m, C), f32),
        spec((m,), f32), spec((n,), f32),
    ]


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _kernels(compiled):
    """Instruction names of the compiled program's Pallas kernels."""
    return re.findall(
        r'%(\S+) = .*custom_call_target="tpu_custom_call"',
        compiled.as_text(),
    )


def _dense_topk(spec, m, k, masked, d=D, b=B, metric="l2", **kw):
    """The served dense top-k at 1M rows, optionally row-masked."""
    args = _dense(spec, N, m, d, b) + (
        [spec((N,), jnp.bool_)] if masked else [])
    compiled = _compile(
        lambda *a: ash_score_topk_pallas(
            *a[:8], None, *a[8:], b=b, k=k, metric=metric,
            compute_dtype=jnp.float32, **kw,
        ),
        *args,
    )
    names = _kernels(compiled)
    assert len(names) == 1 and re.fullmatch(
        r"ash_score_topk_pallas(\.\d+)?", names[0]), names
    return compiled


@pytest.mark.parametrize("k", (10, 100))
def test_dense_fused_topk_compiles(spec, k):
    _dense_topk(spec, 8, k, masked=False)


def test_dense_fused_topk_row_mask_compiles(spec):
    """The tombstone / shard-padding variant: a (1, n) mask operand."""
    _dense_topk(spec, 8, 10, masked=True)


@pytest.mark.parametrize("count_sweeps", (False, True))
@pytest.mark.parametrize("masked", (False, True))
@pytest.mark.parametrize("k", (10, 100))
def test_dense_pruned_topk_compiles_at_full_bucket(spec, k, masked,
                                                   count_sweeps):
    """The running-threshold selection at the 128-row bucket: its
    data-dependent sweep count and carried best-k buffer, with and
    without the sweep counter's output, under the kernel's own name."""
    _dense_topk(spec, 128, k, masked, count_sweeps=count_sweeps)


@pytest.mark.parametrize("masked", (False, True))
@pytest.mark.parametrize("m, k", ((8, 10), (32, 100), (128, 100)))
def test_dense_fused_topk_compiles_at_b4_cos(spec, m, k, masked):
    """The cosine corpus's scan: the 4-bit unpack of 16 words a row and
    the cos row and query terms, at each engine bucket, under the
    kernel's own name."""
    _dense_topk(spec, m, k, masked, d=COS_D, b=COS_B, metric="cos")


def test_coarse_fused_topk_compiles(spec):
    f32 = jnp.float32
    args = [
        spec((N, W), jnp.uint32), spec((8, D), jnp.int8),
        spec((8,), f32), spec((8,), f32), spec((N,), f32),
        spec((N,), f32), spec((N,), jnp.int32), spec((8, C), f32),
        spec((8,), f32), spec((N,), f32),
    ]
    _compile(
        lambda *a: ash_score_coarse_topk_pallas(
            *a, b=B, k=32, metric="l2"
        ),
        *args,
    )


def test_gather_fused_topk_compiles_at_ivf_width(spec):
    codes, q, scale, offset, cluster, ipq, qterm, rowterm = _dense(
        spec, N, 8
    )
    rows = spec((8, SMOKE_IVF_R), jnp.int32)
    _compile(
        lambda *a: ash_score_gather_topk_pallas(
            *a, b=B, k=10, metric="l2", compute_dtype=jnp.float32
        ),
        codes, rows, q, scale, offset, cluster, ipq, qterm, rowterm,
    )


@pytest.mark.parametrize("tombstones", (False, True))
@pytest.mark.parametrize("m", (8, 128))
def test_gather_list_form_compiles_at_glove_geometry(spec, m, tombstones):
    """The list form at the served glove shapes: eight blocks of each
    payload operand a step, their index maps reading the prefetched
    list geometry, within the chip's scalar and vector memory, under
    the kernel's own name."""
    f32, i32 = jnp.float32, jnp.int32
    n = GLOVE_N
    args = [
        spec((n, W), jnp.uint32), spec((m, D), f32), spec((n,), f32),
        spec((n,), f32), spec((n,), i32), spec((m, C), f32),
        spec((m,), f32), spec((n,), f32),
        spec((m, GLOVE_NPROBE), i32), spec((m, GLOVE_NPROBE), i32),
        spec((m, GLOVE_NPROBE), i32),
    ] + ([spec((n,), jnp.bool_)] if tombstones else [])

    def lists_topk(codes, q, scale, offset, cluster, ipq, qterm, rowterm,
                   starts, lens, clusters, live=None):
        lists = ProbedLists(starts=starts, lens=lens, clusters=clusters,
                            live=live, stride=GLOVE_L)
        return ash_score_gather_topk_pallas(
            codes, lists, q, scale, offset, cluster, ipq, qterm, rowterm,
            b=B, k=100, metric="l2", compute_dtype=f32,
        )

    names = _kernels(_compile(lists_topk, *args))
    assert len(names) == 1 and re.fullmatch(
        r"ash_score_gather_topk_pallas(\.\d+)?", names[0]), names


@pytest.mark.parametrize("m", (8, 128))
def test_dense_fused_topk_memory_at_10m_rows(spec, m):
    """Temporaries stay within twice the codes plus the logical
    candidate strip: no lane-padded copy of the narrow codes array
    (once 32x the codes) and no per-call padding of it."""
    n, k = 10_000_000, 10
    compiled = _compile(
        lambda *a: ash_score_topk_pallas(
            *a[:6], b=B, k=k, metric="dot", compute_dtype=jnp.float32
        ),
        *_dense(spec, n, m)[:6],
    )
    codes_bytes = n * W * 4
    strip_bytes = m * -(-n // DEFAULT_BLOCK_N) * k * 8
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 2 * (codes_bytes + strip_bytes), (temp, codes_bytes)


SCOPES = {
    "ash.prep", "ash.probe", "ash.candidates", "ash.gather", "ash.scan",
    "ash.merge", "ash.rerank",
}


@pytest.fixture(scope="module")
def served_programs(spec):
    """The served flat and IVF search programs and the query prep,
    compiled for the chip at small shapes: {name: compiled text}.  The
    index layers pick the Pallas kernels only where the default backend
    is not the CPU, so that choice is forced here, and jit's caches are
    dropped on both sides so no CPU test ever sees a chip trace."""
    ops = importlib.import_module("repro.kernels.ops")
    flat = importlib.import_module("repro.index.flat")
    ivf = importlib.import_module("repro.index.ivf")
    kx, kb, kq = jax.random.split(jax.random.PRNGKey(11), 3)
    X = embedding_dataset(kx, 4096, 128)
    cfg = ASHConfig(b=2, d=64, n_landmarks=16)
    fi = AshIndex.build(kb, X, cfg, backend="flat", metric="l2",
                        keep_raw=True)
    ii = AshIndex.build(kb, X, cfg, backend="ivf", metric="l2",
                        keep_raw=True, model=fi.model)
    q = embedding_dataset(kq, 8, 128)

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), tree)

    prep = on_chip(S.prepare_queries(fi.model, q))
    auto = ops._auto_interpret
    jax.clear_caches()
    ops._auto_interpret = lambda: False
    try:
        lowered = {
            "prepare": jax.jit(S.prepare_queries).lower(
                on_chip(fi.model), on_chip(q)),
            "flat": flat._search_prepped.lower(
                on_chip(fi._state), prep, k=10, rerank=100),
            "ivf": ivf._score_gathered.lower(
                on_chip(ii._state), prep, 10, 4, 100),
        }
        yield {name: lo.compile().as_text() for name, lo in lowered.items()}
    finally:
        ops._auto_interpret = auto
        jax.clear_caches()


def _op_scopes(text):
    names = " ".join(re.findall(r'op_name="([^"]*)"', text))
    return set(re.findall(r"ash\.[a-z]+", names))


def test_served_programs_keep_kernel_names_and_carry_scopes(served_programs):
    """Named scopes are metadata: the kernels' custom calls keep the
    names the benchmark's roofline readers match, and every scope of
    the served path reaches the compiled operations' ``op_name``."""
    kernels = {
        name: re.findall(
            r'%(\S+) = .*custom_call_target="tpu_custom_call"', text)
        for name, text in served_programs.items()
    }
    assert kernels["prepare"] == []
    assert kernels["flat"] and all(
        k.startswith("ash_score_topk_pallas") for k in kernels["flat"])
    assert kernels["ivf"] and all(
        k.startswith("ash_score_gather_topk_pallas") for k in kernels["ivf"])
    seen = set().union(*(_op_scopes(t) for t in served_programs.values()))
    assert seen == SCOPES
    assert "ash.gather" in _op_scopes(served_programs["ivf"])


def test_served_ivf_program_gathers_no_payload_rows(served_programs):
    """The IVF program reads the probed lists in the kernel: no XLA
    gather takes the (n, W) codes, and every gather over an (n,) row
    column — the ids, for the top-k's rows — yields at most a shortlist
    per query, never a row per candidate slot."""
    text = served_programs["ivf"]
    shapes = {
        name: (dt, tuple(int(x) for x in dims.split(",") if x))
        for name, dt, dims in re.findall(
            r"%(\S+) = (\w+)\[([\d,]*)\]", text)
    }
    n, W_ivf = 4096, 4  # the fixture's corpus, d=64 at b=2
    shortlist = 8 * 100  # m x rerank
    gathers = re.findall(
        r"= \w+\[([\d,]*)\]\S* gather\(%([^,\s)]+)", text)
    assert gathers
    for out_dims, operand in gathers:
        dt, dims = shapes[operand]
        out = 1
        for x in (int(v) for v in out_dims.split(",") if v):
            out *= x
        assert dims != (n, W_ivf), (operand, dt, dims)
        if dims == (n,):
            assert out <= shortlist, (operand, dt, dims, out_dims)
