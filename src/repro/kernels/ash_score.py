"""Fused ASH asymmetric-scoring Pallas TPU kernel family.

The TPU adaptation of the paper's AVX-512 Code 1 (see DESIGN.md §2):
batched scoring of m queries against n packed ASH codes is a dense
matmul, so every dense kernel in this file

  1. streams packed uint32 code words HBM -> VMEM one (w_blk, n_blk)
     tile at a time (codes never exist unpacked in HBM: 32/b codes per
     word, a 16x-32x traffic reduction vs fp32 vectors);
  2. unpacks in-register (shift/mask -> odd-integer grid values);
  3. feeds the MXU: acc += q_tile (m_blk, d_blk) @ values (d_blk, n_blk);
  4. on the last reduction step applies a metric epilogue over the
     accumulated Eq. (20) base score, entirely in VMEM.

Metric epilogues (``metric=``) — all emit HIGHER-IS-BETTER scores:

  dot   base = acc * SCALE + one_hot(cluster) lookup of <q, mu_c>
             + OFFSET            (Eq. 20; the landmark lookup is itself
             an MXU one-hot matmul)
  l2    2 * base - ||q||^2 - L2CONST_i          == -||q - x_i||^2
  cos   base * (1/||q||) * (1/||x_i||)          (||x_i||^2 recovered as
             ||x_i - mu*||^2 + 2 <x_i, mu*> - ||mu*||^2, the l2 row term)

The l2/cos row constants are query-independent and recovered once at
encode/build time into an ``ASHStats`` structure (``repro.core.types``),
so neither metric ever unpacks the database in HBM.

Chip layout rules every kernel here follows (the TPU compiler refuses
a block whose last two dimensions are neither (8, 128)-aligned nor
equal to the array's, and lays every array out in (8, 128) tiles):

  * Codes are read LANE-DENSE, as the (W, n) transpose of the stored
    (n, W) array.  XLA keeps a narrow (n, W) array column-major, so the
    transpose is a free view; a row-major (n, W) kernel operand would
    instead force a copy padded to 128 lanes (512 B/row for W=4, 32x the
    codes).  Blocks are (w_blk, n_blk), n_blk a multiple of 128; the
    last n block may be ragged (its columns are masked or dropped), so
    the codes are never padded or copied per call.
  * Per-row vectors ride as (1, n) rows, per-query vectors as (m, 1)
    columns.
  * Fused selection writes its candidate strip TRANSPOSED: candidates
    on sublanes, queries on lanes, one (k̃₈, m_blk) block per tile
    (k̃₈ = k̃ rounded up to 8) — a (m_blk, k̃) block with k̃ = 10 would
    be refused, and padding k̃ to 128 lanes would cost 12.8x the
    candidate bytes.

Fused selection (:func:`ash_score_topk_pallas`): instead of writing the
(m, n) score matrix back to HBM and running a separate ``top_k`` pass,
each (m_blk, n_blk) output tile keeps only its partial top-k̃ of
(score, global id) pairs — VPU max/argmax sweeps in VMEM — and one
small ``lax.top_k`` merges the strip.  HBM traffic for selection drops
from O(m·n) fp32 to O(m · n/block_n · k̃).

  * Running threshold: the row tiles of one query block run in order
    (grid below), and a VMEM buffer carries each query's best k scores
    emitted so far.  A tile admits only the rows strictly above the
    buffer's minimum (every valid row until k have been emitted) and
    runs as many sweeps as the block's busiest real query has admitted
    rows, at most min(k̃, k) — not k̃ every tile.  A row at or below the
    threshold has k emitted rows scoring at least as much with lower
    ids, so it cannot reach the merged top-k; a row above it keeps its
    in-tile rank, so it is emitted exactly when the unpruned sweep
    would emit it.  The strip's shape does not change; skipped slots
    hold sentinels.  ``count_sweeps=True`` also returns the call's
    total sweeps (a measurement hook; the served path never asks).
  * Results are EXACTLY the materialized ``lax.top_k`` (values and
    indices, including tie order) whenever k <= k̃, because a row's
    global rank-r element ranks <= r inside its own tile.  k̃ < k trades
    exactness for a smaller strip (recall-style; routed index paths
    never do this): the top-k of the union of per-tile top-k̃ sets.
  * Ties follow the ``lax.top_k`` convention (lowest id first): tiles
    select by (score desc, id asc) and the merge keeps the lowest strip
    position, which is the lowest id.
  * Invalid rows (ragged block tail, masked rows) never surface;
    exhausted and padding slots emit int32-max sentinel ids which the
    merge maps to -1.

Masked-gather variants (:func:`ash_score_gather_pallas`,
:func:`ash_score_gather_topk_pallas`): the same epilogues and fused
selection over PER-QUERY candidates, scored eight queries per grid step
on the VPU (each query has its own candidates, so the product is a
batched mat-vec).  Neither the unpacked codes nor the (m, R) score
matrix reach HBM.  Two forms share the kernel body and differ only in
how the operands arrive and how slots are numbered:

  * list form (IVF partial probes, :class:`ProbedLists`): each query's
    probed lists are contiguous row ranges of the cluster-sorted
    payload, so the kernel reads them in place.  The list geometry is
    scalar-prefetched; eight BlockSpecs per payload operand (one per
    query of the step) index the resident ``codes.T`` and (1, n) rows
    at block ``start // r_blk + l`` of the step's list, clamped to its
    last block, so a list costs the blocks it spans and an empty list
    or a step past its end issues no copy.  One landmark bias per
    (query, list) replaces the per-row cluster lookup.  Rows outside
    the list, and tombstoned rows, are masked; slot ``p * stride + t``
    is the same slot the row table of the same lists would give.
  * rows form (arbitrary row sets: the int8 refine's shortlists, and
    the materializing route): XLA gathers the rows' PACKED words (W
    words per candidate) and header values into per-query, lane-dense
    (m, ·, R) operands.  Pad entries (row id -1) are masked to
    ``-inf``.

Row-validity masking: a runtime (1, n) int32 mask operand folds the
sharded backend's ``n_real`` pad truncation AND the tombstone bitmap
into the dense selection kernel's id masking, so one compiled program
serves every shard and every mutation state.

Grids: dense (m_blocks, n_blocks, d_blocks), query blocks parallel and
row tiles in order for the running threshold; gather rows form (m/8,
r_blocks, d_blocks) and list form (m/8, nprobe, list blocks, d_blocks),
whose per-tile selections keep k̃ sweeps; d innermost for accumulation
in a VMEM scratch tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quantization as Q
from repro.core.types import pytree_dataclass

DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 2048
DEFAULT_BLOCK_D = 512
DEFAULT_BLOCK_R = 512
_GATHER_BLOCK_Q = 8  # queries per gather grid step (one f32 sublane tile)

METRICS = ("dot", "l2", "cos")
_ID_SENTINEL = jnp.iinfo(jnp.int32).max
# quiet NaN with the sign bit set (0xFFC00000); numpy, so it is an HLO
# literal and never a device array captured by the kernels' traces
_NEG_NAN = np.array(-0x400000, np.int32).view(np.float32)


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _unpack_lanes(words: jax.Array, b: int, compute_dtype) -> jax.Array:
    """(..., w_blk, n_blk) uint32 -> (..., w_blk * 32//b, n_blk) grid
    values, lane-dense: value row ``w * 32//b + s`` is code ``s`` of
    word ``w`` (the ``Q.unpack_codes`` order, transposed)."""
    k = 32 // b
    shifts = (
        jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0) * b
    ).astype(jnp.uint32)
    grouped = (words[..., None, :] >> shifts) & jnp.uint32(2**b - 1)
    levels = grouped.reshape(
        words.shape[:-2] + (words.shape[-2] * k, words.shape[-1])
    )
    return (
        2 * levels.astype(jnp.int32) - (2**b - 1)
    ).astype(compute_dtype)


def _mxu(a: jax.Array, b: jax.Array) -> jax.Array:
    """a (M, K) @ b (K, N) -> f32.  f32 operands run at full precision
    (the chip's default rounds them to bf16 first)."""
    precise = a.dtype == jnp.float32 and b.dtype == jnp.float32
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if precise else None,
        preferred_element_type=jnp.float32,
    )


def _bias_lookup(cluster_ref, ipq_ref):
    """(m_blk, n_blk) landmark bias <q, mu_{c*_i}> via a one-hot matmul
    (exactly one non-zero term per column, so it is bitwise equal to the
    oracle's gather)."""
    C = ipq_ref.shape[1]
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) == cluster_ref[...]
    ).astype(jnp.float32)  # (C, n_blk)
    return _mxu(ipq_ref[...].astype(jnp.float32), onehot)


def _epilogue(dot, scale, bias, offset, qcol, rrow, metric):
    """Eq. (20) base score + metric tail, broadcasting (m_blk, 1)
    query columns against (1, n_blk) or (m_blk, n_blk) row terms.  The
    op order is mirrored by the oracles in ``ref.py``."""
    base = dot * scale + bias + offset
    if metric == "dot":
        return base
    if metric == "l2":
        return (2.0 * base - qcol) - rrow  # == -||q - x||^2
    if metric == "cos":
        return (base * qcol) * rrow
    raise ValueError(metric)


def _select_topk(scores, avail, col0, trips, vals_ref, ids_ref, top=None):
    """Per-tile partial top-k̃ of ``scores`` (m_blk, n_blk) into the
    transposed (1, k̃₈, m_blk) strip blocks ``vals_ref``/``ids_ref``.

    ``trips`` (a static int, or a traced bound) VPU max sweeps over the
    columns ``avail`` admits, ties to the LOWEST id (the lax.top_k
    convention) via a min over the argmax candidate set.  ``avail``
    (not a -inf re-mask) tracks taken columns so rows whose scores are
    genuinely -inf are still emitted once each, in ascending-id order;
    other columns never surface.  Emitted ids are ``col0 + column``;
    exhausted sweeps, slots past ``trips`` and the k̃ -> k̃₈ padding
    slots emit -inf / the int32-max sentinel.

    ``top`` (m_blk, L), when given, is the running best-k buffer of the
    dense scan: each emitted score replaces the buffer's minimum when it
    beats it, and the updated buffer is returned.
    """
    m_blk, n_blk = scores.shape
    kt_pad = vals_ref.shape[1]
    slots = _round_up(kt_pad, 128)  # lane-dense in-register accumulator
    local = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (m_blk, slots), 1)
    neg_inf = jnp.float32(-jnp.inf)

    def sweep(t, carry):
        avail, out_v, out_i, *run = carry
        ok = avail != 0
        masked = jnp.where(ok, scores, neg_inf)
        best = jnp.max(masked, axis=1, keepdims=True)  # (m_blk, 1)
        cand = jnp.where(ok & (masked == best), local, n_blk)
        bid = jnp.min(cand, axis=1, keepdims=True)  # n_blk == exhausted
        has = bid < n_blk
        here = slot == t
        out_v = jnp.where(here, jnp.where(has, best, neg_inf), out_v)
        out_i = jnp.where(
            here, jnp.where(has, bid + col0, _ID_SENTINEL), out_i
        )
        if run:  # an exhausted sweep's best is -inf and enters nowhere
            (buf,) = run
            lane = jax.lax.broadcasted_iota(jnp.int32, buf.shape, 1)
            low = jnp.min(buf, axis=1, keepdims=True)
            first = jnp.min(
                jnp.where(buf == low, lane, buf.shape[1]), axis=1,
                keepdims=True,
            )
            run = [jnp.where((lane == first) & (best > low), best, buf)]
        return jnp.where(local == bid, 0, avail), out_v, out_i, *run

    init = (
        avail.astype(jnp.int32),
        jnp.full((m_blk, slots), neg_inf, jnp.float32),
        jnp.full((m_blk, slots), _ID_SENTINEL, jnp.int32),
    ) + (() if top is None else (top,))
    _, out_v, out_i, *run = jax.lax.fori_loop(0, trips, sweep, init)
    vals_ref[0] = out_v.T[:kt_pad]
    ids_ref[0] = out_i.T[:kt_pad]
    return run[0] if run else None


def _merge_strip(vals, ids, m: int, k: int):
    """Merge a (m_blocks, N, m_blk) transposed candidate strip into the
    top-k (scores, ids), each (m, k), in (score desc, id asc) order —
    bit-equal to ``lax.top_k`` over the materialized row.

    One ``lax.top_k``: along the strip, tiles ascend in id and each
    tile emits equal scores in ascending id, so top_k's lowest-position
    tie rule IS the lowest-id rule.  Sentinel slots are keyed -NaN,
    which top_k's total float order puts below every score, ``-inf``
    included, so they never displace a real row.  (A two-key sort says
    the same but takes ~30 s to compile for the chip, and an int32 key
    ~15 s; float top_k compiles in well under one.)"""
    mb, N, bm = vals.shape
    with jax.named_scope("ash.merge"):
        vals = vals.transpose(0, 2, 1).reshape(mb * bm, N)[:m]
        ids = ids.transpose(0, 2, 1).reshape(mb * bm, N)[:m]
        _, pos = jax.lax.top_k(
            jnp.where(ids == _ID_SENTINEL, _NEG_NAN, vals), k
        )
        return (
            jnp.take_along_axis(vals, pos, axis=1),
            jnp.take_along_axis(ids, pos, axis=1),
        )


def _strip_outputs(k, k_tilde, tile, n_tiles, q_blocks, q_blk, index_map):
    """(k̃, out_specs, out_shape) of a fused-selection call: one
    (1, k̃₈, q_blk) vals/ids block per tile of ``tile`` candidates."""
    kt = min(k if k_tilde is None else k_tilde, tile)
    if k > n_tiles * kt:
        raise ValueError(
            f"k={k} exceeds the {n_tiles} x k_tilde={kt} candidate "
            f"strip; raise k_tilde or use the materializing kernel"
        )
    kt_pad = _round_up(kt, 8)
    spec = pl.BlockSpec((1, kt_pad, q_blk), index_map)
    shape = (q_blocks, n_tiles * kt_pad, q_blk)
    return kt, [spec, spec], [
        jax.ShapeDtypeStruct(shape, jnp.float32),
        jax.ShapeDtypeStruct(shape, jnp.int32),
    ]


# ---------------------------------------------------------------------------
# Dense kernels (asymmetric and symmetric-int8 coarse, score and select)
# ---------------------------------------------------------------------------


def _dense_kernel(
    *refs,
    b: int,
    n_d_blocks: int,
    compute_dtype,
    metric: str,
    coarse: bool,
    k_tilde: int | None,
    k: int | None,
    block_n: int,
    n_real: int,
    m_real: int,
    use_mask: bool,
    count_sweeps: bool,
):
    """One body for the four dense kernels.  Refs, in order:
    q (m_blk, d_blk), codes (w_blk, n_blk), scale/offset/cluster
    (1, n_blk), ipq (m_blk, C); [qterm (m_blk, 1), rowterm (1, n_blk)]
    unless dot; [qscale, qcorr (m_blk, 1)] if coarse; [mask (1, n_blk)]
    if use_mask; outputs — scores (m_blk, n_blk), or the vals/ids strip
    blocks when ``k_tilde`` is set, then the (1, 8, 128) sweep count if
    ``count_sweeps``; scratch — the accumulator, then the running best-k
    buffer (m_blk, round_up(k, 128)) when ``k_tilde`` is set."""
    it = iter(refs)
    q_ref, codes_ref, scale_ref, offset_ref, cluster_ref, ipq_ref = (
        next(it) for _ in range(6)
    )
    qterm_ref = rowterm_ref = None
    if metric != "dot":
        qterm_ref, rowterm_ref = next(it), next(it)
    if coarse:
        qscale_ref, qcorr_ref = next(it), next(it)
    mask_ref = next(it) if use_mask else None
    outs = [next(it), next(it)] if k_tilde else [next(it)]
    sweeps_ref = next(it) if count_sweeps else None
    acc_ref = next(it)
    top_ref = next(it) if k_tilde else None

    kd = pl.program_id(2)
    # program_id must be read outside the pl.when body (interpret mode
    # lowers the body through lax.cond, where the primitive is absent)
    tile = pl.program_id(1)
    col0 = tile * block_n
    row0 = pl.program_id(0) * acc_ref.shape[0]

    @pl.when(kd == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if coarse:
        # int8 (b <= 4) or int32 (b = 8: grid values reach +-255)
        # operands, exact int32 accumulation
        dt = jnp.int8 if b <= 4 else jnp.int32
        acc_ref[...] += jax.lax.dot_general(
            q_ref[...].astype(dt), _unpack_lanes(codes_ref[...], b, dt),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32,
        )
    else:
        acc_ref[...] += _mxu(
            q_ref[...].astype(compute_dtype),
            _unpack_lanes(codes_ref[...], b, compute_dtype),
        )

    @pl.when(kd == n_d_blocks - 1)
    def _finish():
        bias = _bias_lookup(cluster_ref, ipq_ref)
        dot = acc_ref[...]
        if coarse:
            dot = dot.astype(jnp.float32) * qscale_ref[...]
            bias = bias + qcorr_ref[...]
        scores = _epilogue(
            dot, scale_ref[...], bias, offset_ref[...],
            None if qterm_ref is None else qterm_ref[...],
            None if rowterm_ref is None else rowterm_ref[...],
            metric,
        )  # (m_blk, n_blk) fp32
        if not k_tilde:
            outs[0][...] = scores
            return
        local = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        # the ragged tail of the last block holds unspecified data
        valid = (local + col0) < n_real
        if use_mask:
            # runtime row validity: the sharded backend's per-shard
            # n_real truncation and tombstoned rows, one operand for
            # every shard and every mutation state
            valid = valid & (mask_ref[...] != 0)
        # running threshold: the k-th best score this query block has
        # emitted in earlier row tiles (lanes past k hold +inf, so never
        # the minimum).  -inf — fewer than k emitted, or -inf among the
        # best k — admits every valid row.  A row at or below it has k
        # emitted rows at least as good and of lower id, so it cannot
        # reach the merged top-k; the rows above it keep their in-tile
        # ranks.
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, top_ref.shape[1]), 1)
        top = jnp.where(
            tile == 0, jnp.where(lane < k, -jnp.inf, jnp.inf), top_ref[...]
        )
        thr = jnp.min(top, axis=1, keepdims=True)  # (m_blk, 1)
        above = valid & ((scores > thr) | (thr == -jnp.inf))
        # as many sweeps as the block's busiest real query needs (no
        # tile rank past k reaches the top-k either)
        real = jax.lax.broadcasted_iota(jnp.int32, thr.shape, 0) + row0
        need = jnp.sum(above.astype(jnp.int32), axis=1, keepdims=True)
        trips = jnp.minimum(
            jnp.max(jnp.where(real < m_real, need, 0)), min(k_tilde, k)
        )
        top_ref[...] = _select_topk(scores, above, col0, trips, *outs, top)
        if count_sweeps:
            sweeps_ref[...] = jnp.where(
                tile == 0, 0, sweeps_ref[...]
            ) + trips


def _dense_call(
    codes, q, scale, offset, cluster, ipq, qterm, rowterm, *,
    b, metric, block_m, block_n, block_d, interpret, compute_dtype,
    coarse_q=None, n_valid=None, row_valid=None, k=None, k_tilde=None,
    count_sweeps=False,
):
    """Shared wrapper of the dense kernels: materialized (m, n) scores,
    or — with ``k`` — the merged fused top-k (scores, ids), plus the
    call's total selection sweeps (int32 scalar) if ``count_sweeps``."""
    assert metric in METRICS, metric
    n, W = codes.shape
    m, d_pad = q.shape
    kpw = Q.codes_per_word(b)
    assert W * kpw == d_pad, (W, kpw, d_pad)

    bm = min(block_m, _round_up(m, 8))
    bn = min(block_n, _round_up(n, 128))
    bd = min(block_d, d_pad)
    assert bd % kpw == 0
    bw = bd // kpw
    m_p = _round_up(m, bm)
    d_p = _round_up(d_pad, bd)
    # query blocks outermost: the row tiles of one block run in order,
    # carrying its running selection threshold
    grid = (m_p // bm, pl.cdiv(n, bn), d_p // bd)

    def col(v):  # per-query (m,) -> (m_p, 1) f32
        return jnp.pad(v.astype(jnp.float32), (0, m_p - m)).reshape(m_p, 1)

    def row(v, dt=jnp.float32):  # per-row (n,) -> (1, n), never padded
        return v.astype(dt).reshape(1, n)

    row_spec = pl.BlockSpec((1, bn), lambda j, i, kd: (0, i))
    col_spec = pl.BlockSpec((bm, 1), lambda j, i, kd: (j, 0))
    operands = [
        jnp.pad(q, ((0, m_p - m), (0, d_p - d_pad))),
        codes.T,  # (W, n): a free view of the column-major codes
        row(scale), row(offset), row(cluster, jnp.int32),
        jnp.pad(ipq, ((0, m_p - m), (0, 0))),
    ]
    in_specs = [
        pl.BlockSpec((bm, bd), lambda j, i, kd: (j, kd)),
        pl.BlockSpec((bw, bn), lambda j, i, kd: (kd, i)),
        row_spec, row_spec, row_spec,
        pl.BlockSpec((bm, ipq.shape[1]), lambda j, i, kd: (j, 0)),
    ]
    if metric != "dot":
        operands += [col(qterm), row(rowterm)]
        in_specs += [col_spec, row_spec]
    coarse = coarse_q is not None
    if coarse:
        operands += [col(coarse_q[0]), col(coarse_q[1])]
        in_specs += [col_spec, col_spec]
    use_mask = n_valid is not None or row_valid is not None
    if use_mask:
        mask = (
            jnp.ones((n,), jnp.int32) if row_valid is None
            else row_valid.astype(jnp.int32)
        )
        if n_valid is not None:
            mask = mask * (
                jnp.arange(n, dtype=jnp.int32)
                < jnp.asarray(n_valid, jnp.int32)
            ).astype(jnp.int32)
        operands.append(row(mask, jnp.int32))
        in_specs.append(row_spec)

    kt = None
    scratch = [pltpu.VMEM((bm, bn), jnp.int32 if coarse else jnp.float32)]
    if k is None:
        out_specs = pl.BlockSpec((bm, bn), lambda j, i, kd: (j, i))
        out_shape = jax.ShapeDtypeStruct((m_p, n), jnp.float32)
    else:
        kt, out_specs, out_shape = _strip_outputs(
            k, k_tilde, bn, grid[1], grid[0], bm,
            lambda j, i, kd: (j, i, 0),
        )
        scratch.append(pltpu.VMEM((bm, _round_up(k, 128)), jnp.float32))
        if count_sweeps:
            out_specs.append(
                pl.BlockSpec((1, 8, 128), lambda j, i, kd: (j, 0, 0)))
            out_shape.append(
                jax.ShapeDtypeStruct((grid[0], 8, 128), jnp.int32))
    out = pl.pallas_call(
        functools.partial(
            _dense_kernel, b=b, n_d_blocks=grid[2],
            compute_dtype=compute_dtype, metric=metric, coarse=coarse,
            k_tilde=kt, k=k, block_n=bn, n_real=n, m_real=m,
            use_mask=use_mask, count_sweeps=count_sweeps,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(*operands)
    if k is None:
        return out[:m]
    s, i = _merge_strip(*out[:2], m, k)
    i = jnp.where(i == _ID_SENTINEL, -1, i)
    return (s, i, jnp.sum(out[2][:, 0, 0])) if count_sweeps else (s, i)


@functools.partial(
    jax.jit,
    static_argnames=(
        "b", "metric", "block_m", "block_n", "block_d", "interpret",
        "compute_dtype",
    ),
)
def ash_score_pallas(
    codes: jax.Array,  # (n, Wd) uint32
    q_proj: jax.Array,  # (m, d_pad)
    scale: jax.Array,  # (n,)
    offset: jax.Array,  # (n,)
    cluster: jax.Array,  # (n,)
    ip_q_landmarks: jax.Array,  # (m, C)
    qterm: jax.Array | None = None,  # (m,) metric query term
    rowterm: jax.Array | None = None,  # (n,) metric row term
    *,
    b: int,
    metric: str = "dot",
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    """(m, n) fp32 scores, higher-is-better for every metric.

    ``metric="dot"`` matches ``ref.ash_score_ref``; ``"l2"``/``"cos"``
    additionally need the per-row/per-query epilogue terms (see
    ``repro.kernels.ops._metric_operands``) and match
    ``ref.ash_score_metric_ref``.
    """
    return _dense_call(
        codes, q_proj, scale, offset, cluster, ip_q_landmarks,
        qterm, rowterm, b=b, metric=metric, block_m=block_m,
        block_n=block_n, block_d=block_d, interpret=interpret,
        compute_dtype=compute_dtype,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "b", "k", "k_tilde", "metric", "block_m", "block_n", "block_d",
        "interpret", "compute_dtype", "count_sweeps",
    ),
)
def ash_score_topk_pallas(
    codes: jax.Array,  # (n, Wd) uint32
    q_proj: jax.Array,  # (m, d_pad)
    scale: jax.Array,  # (n,)
    offset: jax.Array,  # (n,)
    cluster: jax.Array,  # (n,)
    ip_q_landmarks: jax.Array,  # (m, C)
    qterm: jax.Array | None = None,
    rowterm: jax.Array | None = None,
    n_valid: jax.Array | None = None,  # scalar: rows >= this are masked
    row_valid: jax.Array | None = None,  # (n,) bool/int: 0 = masked row
    *,
    b: int,
    k: int,
    k_tilde: int | None = None,
    metric: str = "dot",
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
    compute_dtype=jnp.bfloat16,
    count_sweeps: bool = False,
) -> tuple[jax.Array, ...]:
    """Fused scan + selection: top-k (scores, ids), each (m, k).

    The (m, n) score matrix never exists — each output tile emits its
    partial top-k̃ and one ``lax.top_k`` merges the candidate strip.
    Exactly equal to ``top_k(ash_score_pallas(...))`` (values, ids and
    tie order) for ``k <= k̃``; ``k̃`` defaults to ``k``.  Ids of
    exhausted slots come back as -1 (only reachable when
    ``k > min(n, k̃)``).

    Row-validity masking: when either ``n_valid`` or ``row_valid`` is
    given, they fold into ONE runtime (1, n) int32 mask operand — no
    recompilation between mutation states or shard shapes.  Without
    them (the common unmutated, unsharded scan) no mask operand exists.

    * ``n_valid`` (scalar): rows at or beyond it score ``-inf`` — the
      sharded backend's per-shard ``n_real`` pad-row truncation.
    * ``row_valid`` ((n,) bool): rows whose entry is 0 are excluded
      from selection — the index layers' tombstone bitmap.

    ``count_sweeps=True`` also returns the call's total selection
    sweeps (int32 scalar; module docstring, "Running threshold").
    """
    return _dense_call(
        codes, q_proj, scale, offset, cluster, ip_q_landmarks,
        qterm, rowterm, b=b, metric=metric, block_m=block_m,
        block_n=block_n, block_d=block_d, interpret=interpret,
        compute_dtype=compute_dtype, n_valid=n_valid,
        row_valid=row_valid, k=k, k_tilde=k_tilde,
        count_sweeps=count_sweeps,
    )


# ---------------------------------------------------------------------------
# Masked-gather kernels (IVF partial probes: per-query candidate lists)
# ---------------------------------------------------------------------------


@pytree_dataclass(meta_fields=("stride",))
class ProbedLists:
    """Each query's probed inverted lists, as the contiguous payload row
    ranges of a cluster-sorted payload: list ``clusters[i, p]`` holds the
    rows ``[starts[i, p], starts[i, p] + lens[i, p])``, all of that
    cluster.  Candidate slot ``p * stride + t`` of query i is row
    ``starts[i, p] + t`` — the slot order of ``invlists[probe]``, whose
    padded width is ``stride``.  ``live`` ((n,) bool, or None) drops
    tombstoned rows."""

    starts: jax.Array  # (m, nprobe) int32
    lens: jax.Array  # (m, nprobe) int32
    clusters: jax.Array  # (m, nprobe) int32 probed list ids
    live: jax.Array | None
    stride: int

    def rows(self) -> jax.Array:
        """The (m, nprobe * stride) candidate-row table, -1 past each
        list's end and for tombstoned rows: ``invlists[probe]`` with
        the tombstone drop, for the callers that take arbitrary rows."""
        m = self.starts.shape[0]
        t = jnp.arange(self.stride, dtype=jnp.int32)
        rows = jnp.where(
            t < self.lens[..., None], self.starts[..., None] + t, -1
        ).reshape(m, -1)
        if self.live is not None:
            rows = jnp.where(self.live[jnp.maximum(rows, 0)], rows, -1)
        return rows


def _gather_accumulate(acc_ref, kd, q, codes, b, compute_dtype):
    """Per-query mat-vec on the VPU: (8, d_blk, 1) x (8, d_blk, r_blk),
    exact f32 products of the (compute_dtype-rounded) query and the
    small-integer grid values, reduced over d into the accumulator."""

    @pl.when(kd == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    vals = _unpack_lanes(codes, b, jnp.float32)
    q = q.astype(compute_dtype).astype(jnp.float32)
    acc_ref[...] += jnp.sum(q * vals, axis=1)


def _gather_emit(acc, scale, bias, offset, qterm, rowterm, metric, valid,
                 col0, k_tilde, outs):
    """Epilogue of one gather tile, then its partial top-k̃ (slot ids
    ``col0 + column``) or its -inf-masked scores."""
    scores = _epilogue(acc, scale, bias, offset, qterm, rowterm, metric)
    if k_tilde:
        _select_topk(scores, valid, col0, k_tilde, *outs)
    else:
        outs[0][...] = jnp.where(valid, scores, jnp.float32(-jnp.inf))


def _gather_kernel(
    *refs,
    b: int,
    n_d_blocks: int,
    compute_dtype,
    metric: str,
    k_tilde: int | None,
    block_r: int,
):
    """Rows form.  Refs: q (8, d_blk, 1), codes (8, w_blk, r_blk),
    scale/bias/offset/rows (8, r_blk); [qterm (8, 1), rowterm (8,
    r_blk)] unless dot; outputs (scores, or the vals/ids strip blocks);
    accumulator."""
    q_ref, codes_ref, scale_ref, bias_ref, offset_ref, rows_ref = refs[:6]
    rest = refs[6:]
    qterm_ref = rowterm_ref = None
    if metric != "dot":
        qterm_ref, rowterm_ref, *rest = rest
    *outs, acc_ref = rest
    kd = pl.program_id(2)
    r0 = pl.program_id(1) * block_r

    _gather_accumulate(
        acc_ref, kd, q_ref[...], codes_ref[...], b, compute_dtype
    )

    @pl.when(kd == n_d_blocks - 1)
    def _finish():
        # pad-id masking IS the validity mask: list padding, dropped
        # (tombstoned) rows and R-padding all carry id -1
        _gather_emit(
            acc_ref[...], scale_ref[...], bias_ref[...], offset_ref[...],
            None if qterm_ref is None else qterm_ref[...],
            None if rowterm_ref is None else rowterm_ref[...],
            metric, rows_ref[...] >= 0, r0, k_tilde, outs,
        )


def _list_kernel(
    geo_ref,
    *refs,
    b: int,
    n_d_blocks: int,
    compute_dtype,
    metric: str,
    k_tilde: int,
    block_r: int,
    nprobe: int,
    stride: int,
    use_live: bool,
    count_blocks: bool,
):
    """List form.  ``geo_ref`` (SMEM): (start, len) of each (query,
    probe), flattened.  Refs: q (8, d_blk, 1), bias (1, 8, 1); [qterm
    (8, 1)] unless dot; then per query of the block its own blocks of
    the resident payload — codes (w_blk, r_blk), scale, offset,
    [rowterm] unless dot, [live] if ``use_live``, each (1, r_blk);
    outputs: the vals/ids strip blocks, [the (1, 8, 128) block count];
    accumulator.  Step (qb, p, l) scores block ``start // r_blk + l`` of
    each query's p-th list; a step where no query has a block left
    writes sentinels and computes nothing."""
    q_ref, bias_ref, *rest = refs
    qterm_ref = rest.pop(0) if metric != "dot" else None
    fields = 3 + (metric != "dot") + use_live
    bq = _GATHER_BLOCK_Q
    per_q = [rest[j * fields:(j + 1) * fields] for j in range(bq)]
    rest = rest[bq * fields:]
    outs = rest[:2]
    blocks_ref = rest[2] if count_blocks else None
    acc_ref = rest[-1]
    # program_id must be read outside the pl.when bodies (interpret
    # mode lowers them through lax.cond, where the primitive is absent)
    qb, p, blk, kd = (pl.program_id(a) for a in range(4))

    # each query's list: its row range and the step's first row, as
    # (8, 1) columns, and how many of the eight still have a block
    lane = jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    lo = hi = row0 = jnp.zeros((bq, 1), jnp.int32)
    busy = jnp.int32(0)
    for j in range(bq):
        e = ((qb * bq + j) * nprobe + p) * 2
        start, n = geo_ref[e], geo_ref[e + 1]
        first = start // block_r
        n_blocks = (start - first * block_r + n + block_r - 1) // block_r
        busy = busy + ((n > 0) & (blk < n_blocks)).astype(jnp.int32)
        lo = jnp.where(lane == j, start, lo)
        hi = jnp.where(lane == j, start + n, hi)
        row0 = jnp.where(lane == j, (first + blk) * block_r, row0)

    if count_blocks:
        @pl.when(kd == 0)
        def _count():
            fresh = (p == 0) & (blk == 0)
            blocks_ref[...] = jnp.where(fresh, 0, blocks_ref[...]) + busy

    def rows_of(f):  # the eight (1, r_blk) blocks of field f: (8, r_blk)
        return jnp.concatenate([q_refs[f][...] for q_refs in per_q])

    @pl.when(busy > 0)
    def _score():
        codes = jnp.concatenate([q_refs[0][...][None] for q_refs in per_q])
        _gather_accumulate(
            acc_ref, kd, q_ref[...], codes, b, compute_dtype
        )

        @pl.when(kd == n_d_blocks - 1)
        def _finish():
            row = row0 + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_r), 1
            )
            valid = (row >= lo) & (row < hi)
            if use_live:
                valid = valid & (rows_of(fields - 1) != 0)
            _gather_emit(
                acc_ref[...], rows_of(1), bias_ref[0], rows_of(2),
                None if qterm_ref is None else qterm_ref[...],
                None if metric == "dot" else rows_of(3),
                metric, valid, row0 - lo + p * stride, k_tilde, outs,
            )

    @pl.when((busy == 0) & (kd == n_d_blocks - 1))
    def _idle():
        outs[0][...] = jnp.full(outs[0].shape, -jnp.inf, jnp.float32)
        outs[1][...] = jnp.full(outs[1].shape, _ID_SENTINEL, jnp.int32)


def _gather_call(
    codes, rows, q, scale, offset, cluster, ipq, qterm, rowterm, *,
    b, metric, block_r, block_d, interpret, compute_dtype,
    k=None, k_tilde=None,
):
    """Shared wrapper of the gather kernels: (m, R) scores, or — with
    ``k`` — the fused top-k (scores, candidate POSITIONS) plus the
    -1-padded (m, R_p) row table."""
    assert metric in METRICS, metric
    n, W = codes.shape
    m, d_pad = q.shape
    R = rows.shape[1]
    kpw = Q.codes_per_word(b)
    assert W * kpw == d_pad, (W, kpw, d_pad)

    bq = _GATHER_BLOCK_Q
    br = min(block_r, _round_up(R, 128))
    bd = min(block_d, d_pad)
    assert bd % kpw == 0
    bw = bd // kpw
    m_p = _round_up(m, bq)
    R_p = _round_up(R, br)
    d_p = _round_up(d_pad, bd)
    grid = (m_p // bq, R_p // br, d_p // bd)

    rows_p = jnp.pad(
        rows.astype(jnp.int32), ((0, m_p - m), (0, R_p - R)),
        constant_values=-1,
    )
    safe = jnp.maximum(rows_p, 0)
    q3 = jnp.pad(q, ((0, m_p - m), (0, d_p - d_pad)))[:, :, None]
    with jax.named_scope("ash.gather"):
        # the probed rows' packed words, per query and lane-dense:
        # (m_p, w_p, R_p) — only W words per candidate move, never
        # unpacked
        codes_g = jnp.pad(
            jnp.swapaxes(codes[safe], 1, 2),
            ((0, 0), (0, d_p // kpw - W), (0, 0)),
        )
        bias_g = jnp.take_along_axis(
            jnp.pad(ipq.astype(jnp.float32), ((0, m_p - m), (0, 0))),
            cluster[safe].astype(jnp.int32), axis=1,
        )
        operands = [
            q3, codes_g, scale.astype(jnp.float32)[safe], bias_g,
            offset.astype(jnp.float32)[safe], rows_p,
        ]
        if metric != "dot":
            operands += [
                jnp.pad(qterm.astype(jnp.float32), (0, m_p - m))[:, None],
                rowterm.astype(jnp.float32)[safe],
            ]
    tile = pl.BlockSpec((bq, br), lambda i, j, kd: (i, j))
    in_specs = [
        pl.BlockSpec((bq, bd, 1), lambda i, j, kd: (i, kd, 0)),
        pl.BlockSpec((bq, bw, br), lambda i, j, kd: (i, kd, j)),
        tile, tile, tile, tile,
    ]
    if metric != "dot":
        in_specs += [pl.BlockSpec((bq, 1), lambda i, j, kd: (i, 0)), tile]

    kt = None
    if k is None:
        out_specs = tile
        out_shape = jax.ShapeDtypeStruct((m_p, R_p), jnp.float32)
    else:
        kt, out_specs, out_shape = _strip_outputs(
            k, k_tilde, br, grid[1], grid[0], bq,
            lambda i, j, kd: (i, j, 0),
        )
    out = pl.pallas_call(
        functools.partial(
            _gather_kernel, b=b, n_d_blocks=grid[2],
            compute_dtype=compute_dtype, metric=metric, k_tilde=kt,
            block_r=br,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bq, br), jnp.float32)],
        interpret=interpret,
    )(*operands)
    if k is None:
        return out[:m, :R]
    return _merge_strip(*out, m, k), rows_p[:m]


def _list_call(
    codes, lists, q, scale, offset, ipq, qterm, rowterm, *,
    b, metric, block_r, block_d, interpret, compute_dtype, k, k_tilde,
    count_blocks,
):
    """List form of the fused gather top-k: (scores, slot positions),
    each (m, k), [and the call's (query, list-block) copies].  The
    kernel reads each probed list's blocks straight from the resident
    payload (``codes.T`` and (1, n) rows), eight BlockSpecs per operand
    whose index maps read the scalar-prefetched list geometry."""
    assert metric in METRICS, metric
    n, W = codes.shape
    m, d_pad = q.shape
    nprobe, L = lists.starts.shape[1], lists.stride
    kpw = Q.codes_per_word(b)
    assert W * kpw == d_pad, (W, kpw, d_pad)

    bq = _GATHER_BLOCK_Q
    br = min(block_r, _round_up(nprobe * L, 128), _round_up(n, 128))
    bd = min(block_d, d_pad)
    assert bd % kpw == 0
    bw = bd // kpw
    m_p = _round_up(m, bq)
    d_p = _round_up(d_pad, bd)
    n_blk = pl.cdiv(L + br - 1, br)  # blocks an unaligned list can span
    grid = (m_p // bq, nprobe, n_blk, d_p // bd)

    def pad_q(a):  # per-(query, probe) (m, nprobe) -> (m_p, nprobe)
        return jnp.pad(a.astype(jnp.int32), ((0, m_p - m), (0, 0)))

    def lanes(a):  # (m_p, nprobe) -> (8, m_p / 8 * nprobe), grid order
        a = a.reshape(m_p // bq, bq, nprobe).transpose(1, 0, 2)
        return a.reshape(bq, -1)

    def unlanes(a):
        a = a.reshape(bq, m_p // bq, nprobe).transpose(1, 0, 2)
        return a.reshape(m_p, nprobe)

    with jax.named_scope("ash.gather"):
        start, lens = pad_q(lists.starts), pad_q(lists.lens)
        # an empty list (and every pad query's) points at the block its
        # query lane read last, in grid order, so it costs no copy: its
        # start moves to that block, its length stays 0
        last = lanes((start + jnp.maximum(lens, 1) - 1) // br)
        seq = jnp.arange(last.shape[1], dtype=jnp.int32)
        src = jax.lax.cummax(jnp.where(lanes(lens) > 0, seq, -1), axis=1)
        held = jnp.take_along_axis(last, jnp.maximum(src, 0), axis=1)
        held = unlanes(jnp.where(src >= 0, held, 0))
        start = jnp.where(lens > 0, start, held * br)
        geo = jnp.stack([start, lens], axis=-1).reshape(-1)
        # every row of list c has cluster c: one landmark bias per list
        bias = jnp.take_along_axis(
            jnp.pad(ipq.astype(jnp.float32), ((0, m_p - m), (0, 0))),
            pad_q(lists.clusters), axis=1,
        ).T[:, :, None]  # (nprobe, m_p, 1)
        q3 = jnp.pad(q, ((0, m_p - m), (0, d_p - d_pad)))[:, :, None]
        fields = [scale, offset]
        if metric != "dot":
            fields.append(rowterm)
        fields = [f.astype(jnp.float32).reshape(1, n) for f in fields]
        if lists.live is not None:
            fields.append(lists.live.astype(jnp.int32).reshape(1, n))

    def block(j):  # query lane j's block of its p-th list at step l
        def index(qb, p, l, geo):
            # lax ops (not jnp's): each jnp call here is one more trace
            # per BlockSpec, bucket and process; s, ln >= 0
            e = ((qb * bq + j) * nprobe + p) * 2
            s, ln = geo[e], geo[e + 1]
            end = jax.lax.div(s + jax.lax.max(ln, 1) - 1, br)
            return jax.lax.min(jax.lax.div(s, br) + l, end)
        return index

    operands = [q3, bias]
    in_specs = [
        pl.BlockSpec((bq, bd, 1), lambda qb, p, l, kd, geo: (qb, kd, 0)),
        pl.BlockSpec((1, bq, 1), lambda qb, p, l, kd, geo: (p, qb, 0)),
    ]
    if metric != "dot":
        operands.append(
            jnp.pad(qterm.astype(jnp.float32), (0, m_p - m))[:, None])
        in_specs.append(
            pl.BlockSpec((bq, 1), lambda qb, p, l, kd, geo: (qb, 0)))
    for j in range(bq):
        at = block(j)
        operands += [codes.T, *fields]
        in_specs.append(pl.BlockSpec(
            (bw, br),
            lambda qb, p, l, kd, geo, at=at: (kd, at(qb, p, l, geo)),
        ))
        in_specs += [pl.BlockSpec(
            (1, br),
            lambda qb, p, l, kd, geo, at=at: (0, at(qb, p, l, geo)),
        )] * len(fields)

    kt, out_specs, out_shape = _strip_outputs(
        k, k_tilde, br, nprobe * n_blk, grid[0], bq,
        lambda qb, p, l, kd, geo: (qb, p * n_blk + l, 0),
    )
    if count_blocks:
        out_specs.append(
            pl.BlockSpec((1, 8, 128), lambda qb, p, l, kd, geo: (qb, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((grid[0], 8, 128), jnp.int32))
    out = pl.pallas_call(
        functools.partial(
            _list_kernel, b=b, n_d_blocks=grid[3],
            compute_dtype=compute_dtype, metric=metric, k_tilde=kt,
            block_r=br, nprobe=nprobe, stride=L,
            use_live=lists.live is not None, count_blocks=count_blocks,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((bq, br), jnp.float32)],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "arbitrary", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(geo, *operands)
    merged = _merge_strip(*out[:2], m, k)
    return merged + ((jnp.sum(out[2][:, 0, 0]),) if count_blocks else ())


@functools.partial(
    jax.jit,
    static_argnames=(
        "b", "metric", "block_r", "block_d", "interpret", "compute_dtype",
    ),
)
def ash_score_gather_pallas(
    codes: jax.Array,  # (n, Wd) uint32
    rows: jax.Array,  # (m, R) int32 candidate rows, -1 = padding
    q_proj: jax.Array,  # (m, d_pad)
    scale: jax.Array,  # (n,)
    offset: jax.Array,  # (n,)
    cluster: jax.Array,  # (n,)
    ip_q_landmarks: jax.Array,  # (m, C)
    qterm: jax.Array | None = None,
    rowterm: jax.Array | None = None,
    *,
    b: int,
    metric: str = "dot",
    block_r: int = DEFAULT_BLOCK_R,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    """Masked-gather scores: (m, R) fp32, higher-is-better; pad entries
    (row id -1) come back ``-inf``.  Matches
    ``ref.ash_score_gather_ref``.

    Query i is scored against its own candidate list ``rows[i]`` (IVF
    partial probes), straight off the probed rows' packed words.
    """
    return _gather_call(
        codes, rows, q_proj, scale, offset, cluster, ip_q_landmarks,
        qterm, rowterm, b=b, metric=metric, block_r=block_r,
        block_d=block_d, interpret=interpret, compute_dtype=compute_dtype,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "b", "k", "k_tilde", "metric", "block_r", "block_d", "interpret",
        "compute_dtype", "count_blocks",
    ),
)
def ash_score_gather_topk_pallas(
    codes: jax.Array,
    rows: jax.Array | ProbedLists,  # (m, R) int32 rows, -1 = padding
    q_proj: jax.Array,
    scale: jax.Array,
    offset: jax.Array,
    cluster: jax.Array,
    ip_q_landmarks: jax.Array,
    qterm: jax.Array | None = None,
    rowterm: jax.Array | None = None,
    *,
    b: int,
    k: int,
    k_tilde: int | None = None,
    metric: str = "dot",
    block_r: int = DEFAULT_BLOCK_R,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
    compute_dtype=jnp.bfloat16,
    count_blocks: bool = False,
) -> tuple[jax.Array, ...]:
    """Fused masked-gather scan + selection: (scores, payload rows),
    each (m, k).

    Equal to ``top_k(ash_score_gather_pallas(...), k)`` with positions
    mapped back through ``rows`` (values, ids and tie order — ties
    break to the lowest candidate POSITION, the ``lax.top_k``
    convention) for ``k <= k̃``.  Slots without a candidate (pad ids,
    or k beyond the emitted strip) come back score ``-inf`` / row -1.

    ``rows`` is either an arbitrary (m, R) row table (rows form: XLA
    gathers the candidates' words and headers first) or
    :class:`ProbedLists`, whole contiguous lists (list form: the kernel
    reads each list's blocks from the resident payload, and the cluster
    column is not read).  Both give the same results bit for bit for
    the same candidates, ``ProbedLists.rows()``.  ``count_blocks=True``
    (list form) also returns the call's (query, list-block) copies, a
    measurement hook the served path never asks for.
    """
    if isinstance(rows, ProbedLists):
        s, pos, *blocks = _list_call(
            codes, rows, q_proj, scale, offset, ip_q_landmarks, qterm,
            rowterm, b=b, metric=metric, block_r=block_r, block_d=block_d,
            interpret=interpret, compute_dtype=compute_dtype, k=k,
            k_tilde=k_tilde, count_blocks=count_blocks,
        )
        # slot p * stride + t of query i is row starts[i, p] + t
        L, nprobe = rows.stride, rows.starts.shape[1]
        start = jnp.take_along_axis(
            rows.starts.astype(jnp.int32),
            jnp.clip(pos // L, 0, nprobe - 1), axis=1,
        )
        out = (s, jnp.where(pos == _ID_SENTINEL, -1, start + pos % L))
        return out + tuple(blocks)
    if count_blocks:
        raise ValueError("count_blocks counts the list form's copies")
    (out_s, out_p), rows_p = _gather_call(
        codes, rows, q_proj, scale, offset, cluster, ip_q_landmarks,
        qterm, rowterm, b=b, metric=metric, block_r=block_r,
        block_d=block_d, interpret=interpret, compute_dtype=compute_dtype,
        k=k, k_tilde=k_tilde,
    )
    out_rows = jnp.take_along_axis(
        rows_p, jnp.clip(out_p, 0, rows_p.shape[1] - 1), axis=1
    )
    return out_s, jnp.where(out_p == _ID_SENTINEL, -1, out_rows)


# ---------------------------------------------------------------------------
# Symmetric int8 coarse-scan kernels (first pass of coarse -> refine)
# ---------------------------------------------------------------------------
#
# Same tile structure as the asymmetric family, but the query side is the
# per-query int8 quantization of q_proj (``core.prepare_coarse_queries``),
# so the matmul accumulates INTEGER products with
# ``preferred_element_type=jnp.int32`` — int8 x int8 native MXU throughput
# instead of fp32/bf16 for the bulk scan.  The epilogue rescales the
# integer accumulation (``acc * q_scale``), folds the per-query residual
# correction ``q_corr`` into the landmark bias, then applies the exact
# Eq. (20) base + metric op order of the asymmetric epilogue.  Bitwise
# contract: both operands are exact small integers (|q| <= 127,
# |v| <= 2^b - 1 <= 255), so every partial sum stays below
# 127 * 255 * 512 < 2^24 for d_pad <= 512 — the int32 accumulation here,
# the oracle's fp32 matmul over the same integers, and the CoarseCodes
# fp32 value-cache path all produce identical scores bit for bit.  No
# ``compute_dtype`` knob: the operand dtype is fixed by the bitrate
# (int8 for b <= 4, int32 for b=8) and accumulation is always int32.


@functools.partial(
    jax.jit,
    static_argnames=(
        "b", "metric", "block_m", "block_n", "block_d", "interpret",
    ),
)
def ash_score_coarse_pallas(
    codes: jax.Array,  # (n, Wd) uint32
    q_int8: jax.Array,  # (m, d_pad) int8 quantized query projections
    q_scale: jax.Array,  # (m,) per-query symmetric scale
    q_corr: jax.Array,  # (m,) residual correction term
    scale: jax.Array,  # (n,)
    offset: jax.Array,  # (n,)
    cluster: jax.Array,  # (n,)
    ip_q_landmarks: jax.Array,  # (m, C)
    qterm: jax.Array | None = None,
    rowterm: jax.Array | None = None,
    *,
    b: int,
    metric: str = "dot",
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
) -> jax.Array:
    """Materializing coarse scan: (m, n) fp32 symmetric int8 scores,
    higher-is-better.  Matches ``ref.ash_score_coarse_ref`` bitwise."""
    return _dense_call(
        codes, q_int8, scale, offset, cluster, ip_q_landmarks,
        qterm, rowterm, b=b, metric=metric, block_m=block_m,
        block_n=block_n, block_d=block_d, interpret=interpret,
        compute_dtype=None, coarse_q=(q_scale, q_corr),
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "b", "k", "k_tilde", "metric", "block_m", "block_n", "block_d",
        "interpret", "count_sweeps",
    ),
)
def ash_score_coarse_topk_pallas(
    codes: jax.Array,  # (n, Wd) uint32
    q_int8: jax.Array,  # (m, d_pad) int8
    q_scale: jax.Array,  # (m,)
    q_corr: jax.Array,  # (m,)
    scale: jax.Array,  # (n,)
    offset: jax.Array,  # (n,)
    cluster: jax.Array,  # (n,)
    ip_q_landmarks: jax.Array,  # (m, C)
    qterm: jax.Array | None = None,
    rowterm: jax.Array | None = None,
    n_valid: jax.Array | None = None,  # scalar: rows >= this are masked
    row_valid: jax.Array | None = None,  # (n,) bool/int: 0 = masked row
    *,
    b: int,
    k: int,
    k_tilde: int | None = None,
    metric: str = "dot",
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
    count_sweeps: bool = False,
) -> tuple[jax.Array, ...]:
    """Fused coarse scan + shortlist selection: top-k (scores, ids),
    each (m, k) — the FIRST PASS of the coarse -> refine pipeline, so
    ``k`` here is the shortlist size L, not the final k.

    Same selection machinery, mask folding, and ``lax.top_k`` tie
    contract as :func:`ash_score_topk_pallas`, over the integer-domain
    coarse scores: exactly ``top_k(ash_score_coarse_pallas(...), k)``
    for ``k <= k̃``.  The emitted ids feed
    ``ash_score_gather_topk_pallas`` for the asymmetric refine.
    ``count_sweeps`` as in :func:`ash_score_topk_pallas`.
    """
    return _dense_call(
        codes, q_int8, scale, offset, cluster, ip_q_landmarks,
        qterm, rowterm, b=b, metric=metric, block_m=block_m,
        block_n=block_n, block_d=block_d, interpret=interpret,
        compute_dtype=None, coarse_q=(q_scale, q_corr),
        n_valid=n_valid, row_valid=row_valid, k=k, k_tilde=k_tilde,
        count_sweeps=count_sweeps,
    )
