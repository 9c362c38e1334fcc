"""Pure-jnp oracles for the Pallas kernels.

These define bit-exact semantics; the kernels are validated against them
(interpret mode on CPU, compiled on TPU) across shape/dtype/bit sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quantization as Q


def ash_score_ref(
    codes: jax.Array,  # (n, Wd) uint32 packed
    q_proj: jax.Array,  # (m, d_pad) query projections (zero-padded cols)
    scale: jax.Array,  # (n,)
    offset: jax.Array,  # (n,)
    cluster: jax.Array,  # (n,) int32
    ip_q_landmarks: jax.Array,  # (m, C)
    b: int,
) -> jax.Array:
    """Asymmetric ASH scores (Eq. 20): (m, n) fp32.

    d is implied by the packed width: d_pad = Wd * (32 // b); q_proj must
    be zero-padded to d_pad so padding lanes contribute nothing.
    """
    d_pad = codes.shape[1] * Q.codes_per_word(b)
    V = Q.unpack_codes(codes, d_pad, b).astype(jnp.float32)
    dot = q_proj.astype(jnp.float32) @ V.T  # (m, n)
    bias = ip_q_landmarks.astype(jnp.float32)[:, cluster]  # (m, n)
    return (
        dot * scale.astype(jnp.float32)[None, :]
        + bias
        + offset.astype(jnp.float32)[None, :]
    )


def ash_score_metric_ref(
    codes: jax.Array,  # (n, Wd) uint32 packed
    q_proj: jax.Array,  # (m, d_pad)
    scale: jax.Array,  # (n,)
    offset: jax.Array,  # (n,)
    cluster: jax.Array,  # (n,) int32
    ip_q_landmarks: jax.Array,  # (m, C)
    qterm: jax.Array | None,  # (m,) metric query term (None for dot)
    rowterm: jax.Array | None,  # (n,) metric row term (None for dot)
    b: int,
    metric: str = "dot",
) -> jax.Array:
    """Metric-epilogue scores, higher-is-better: the oracle for the
    fused kernel family.

    Applies the same epilogue op order as the kernel's
    ``_epilogue`` over the Eq. (20) base score:
      dot: base;  l2: 2*base - qterm - rowterm (== -||q - x||^2);
      cos: base * qterm * rowterm.
    ``qterm``/``rowterm`` come from ``ops._metric_operands``.
    """
    base = ash_score_ref(
        codes, q_proj, scale, offset, cluster, ip_q_landmarks, b
    )
    if metric == "dot":
        return base
    qcol = qterm.astype(jnp.float32)[:, None]
    rrow = rowterm.astype(jnp.float32)[None, :]
    if metric == "l2":
        return (2.0 * base - qcol) - rrow
    if metric == "cos":
        return (base * qcol) * rrow
    raise ValueError(metric)


def mask_rows_ref(
    scores: jax.Array,  # (m, n)
    n_valid: jax.Array | None = None,  # scalar; cols >= it are masked
    row_valid: jax.Array | None = None,  # (n,) bool/int; 0 = masked
) -> jax.Array:
    """Oracle for the dense kernel's row-validity mask operand.

    Forces masked columns to ``-inf``: columns at/beyond ``n_valid``
    (the sharded backend's per-shard pad truncation) and columns whose
    ``row_valid`` entry is falsy (the index layers' tombstone bitmap).
    The fused selection kernel folds the same combined mask into its id
    masking, so materialize-then-``top_k`` and fused selection agree.
    """
    if n_valid is None and row_valid is None:
        return scores
    ok = jnp.ones((scores.shape[-1],), bool)
    if row_valid is not None:
        ok = ok & row_valid.astype(bool)
    if n_valid is not None:
        cols = jnp.arange(scores.shape[-1])
        ok = ok & (cols < n_valid)
    return jnp.where(ok[None, :], scores, -jnp.inf)


def ash_score_gather_ref(
    codes: jax.Array,  # (n, Wd) uint32 packed
    rows: jax.Array,  # (m, R) int32 candidate row ids, -1 = padding
    q_proj: jax.Array,  # (m, d_pad)
    scale: jax.Array,  # (n,)
    offset: jax.Array,  # (n,)
    cluster: jax.Array,  # (n,) int32
    ip_q_landmarks: jax.Array,  # (m, C)
    qterm: jax.Array | None,  # (m,) metric query term (None for dot)
    rowterm: jax.Array | None,  # (n,) metric row term (None for dot)
    b: int,
    metric: str = "dot",
) -> jax.Array:
    """Masked-gather metric scores: (m, R) fp32, higher-is-better — the
    oracle for the masked-gather kernel.

    Query i is scored against its own candidate list ``rows[i]``; pad
    entries (id -1) come back ``-inf``.  The DOT-PROD term is a
    broadcast-multiply + last-axis reduce (not a batched matmul), so row
    i's scores are identical whatever the query-batch size — the
    bit-identity invariant the serving engine's bucketing relies on.
    The epilogue applies the same op order as the dense kernel's
    ``_epilogue`` (the landmark bias has a single non-zero
    one-hot term, so gather and one-hot matmul agree bitwise).
    """
    m, R = rows.shape
    d_pad = codes.shape[1] * Q.codes_per_word(b)
    safe = jnp.maximum(rows, 0)
    V = Q.unpack_codes(
        codes[safe.reshape(-1)], d_pad, b
    ).astype(jnp.float32).reshape(m, R, d_pad)
    dot = jnp.sum(q_proj.astype(jnp.float32)[:, None, :] * V, axis=-1)
    cl = cluster[safe]  # (m, R)
    bias = jnp.take_along_axis(
        ip_q_landmarks.astype(jnp.float32), cl, axis=1
    )
    base = (
        dot * scale.astype(jnp.float32)[safe]
        + bias
        + offset.astype(jnp.float32)[safe]
    )
    if metric == "dot":
        out = base
    elif metric == "l2":
        qcol = qterm.astype(jnp.float32)[:, None]
        out = (2.0 * base - qcol) - rowterm.astype(jnp.float32)[safe]
    elif metric == "cos":
        qcol = qterm.astype(jnp.float32)[:, None]
        out = (base * qcol) * rowterm.astype(jnp.float32)[safe]
    else:
        raise ValueError(metric)
    return jnp.where(rows >= 0, out, -jnp.inf)


def _coarse_base(dot_int, q_scale, q_corr, scale, offset, bias):
    """Shared Eq. (20) base for the coarse oracles — the exact op order
    the coarse kernel's epilogue mirrors.  ``dot_int`` is the integer
    int8 x code accumulation (exact in fp32: every partial sum of the
    integer products stays below 2^24 for d_pad <= 512)."""
    dotc = dot_int.astype(jnp.float32) * q_scale.astype(jnp.float32)[
        ..., None
    ]
    biasq = bias + q_corr.astype(jnp.float32)[..., None]
    return (
        dotc * scale.astype(jnp.float32)
        + biasq
        + offset.astype(jnp.float32)
    )


def ash_score_coarse_ref(
    codes: jax.Array,  # (n, Wd) uint32 packed
    q_int8: jax.Array,  # (m, d_pad) int8 quantized query projections
    q_scale: jax.Array,  # (m,) per-query symmetric scale
    q_corr: jax.Array,  # (m,) residual correction term
    scale: jax.Array,  # (n,)
    offset: jax.Array,  # (n,)
    cluster: jax.Array,  # (n,) int32
    ip_q_landmarks: jax.Array,  # (m, C)
    qterm: jax.Array | None,  # (m,) metric query term (None for dot)
    rowterm: jax.Array | None,  # (n,) metric row term (None for dot)
    b: int,
    metric: str = "dot",
    values: jax.Array | None = None,  # (n, d_pad) pre-dequantized codes
) -> jax.Array:
    """Symmetric int8 coarse scores: (m, n) fp32, higher-is-better —
    the oracle for ``ash_score_coarse[_topk]_pallas``.

    The DOT-PROD term is the integer accumulation
    ``<q_int8, v>`` scaled back by the per-query ``q_scale``; the
    correction ``q_corr`` rides the bias so the coarse score is an
    unbiased (corpus-mean) estimate of the asymmetric Eq. (20) score.
    Integer accumulation is order-invariant and exact in fp32 below
    2^24, so this matmul is BITWISE equal to the kernel's int32 MXU
    accumulation — and to the ``values``-cache fast path (pass
    ``CoarseCodes.values`` to skip the unpack).  The metric epilogues
    apply the same op order as ``ash_score_metric_ref`` over the coarse
    base.
    """
    if values is None:
        d_pad = codes.shape[1] * Q.codes_per_word(b)
        values = Q.unpack_codes(codes, d_pad, b).astype(jnp.float32)
    dot = q_int8.astype(jnp.float32) @ values.T  # (m, n) exact ints
    bias = ip_q_landmarks.astype(jnp.float32)[:, cluster]
    base = _coarse_base(
        dot, q_scale, q_corr, scale[None, :], offset[None, :], bias
    )
    if metric == "dot":
        return base
    qcol = qterm.astype(jnp.float32)[:, None]
    rrow = rowterm.astype(jnp.float32)[None, :]
    if metric == "l2":
        return (2.0 * base - qcol) - rrow
    if metric == "cos":
        return (base * qcol) * rrow
    raise ValueError(metric)


def ash_score_coarse_gather_ref(
    codes: jax.Array,  # (n, Wd) uint32 packed
    rows: jax.Array,  # (m, R) int32 candidate row ids, -1 = padding
    q_int8: jax.Array,  # (m, d_pad) int8
    q_scale: jax.Array,  # (m,)
    q_corr: jax.Array,  # (m,)
    scale: jax.Array,  # (n,)
    offset: jax.Array,  # (n,)
    cluster: jax.Array,  # (n,) int32
    ip_q_landmarks: jax.Array,  # (m, C)
    qterm: jax.Array | None,
    rowterm: jax.Array | None,
    b: int,
    metric: str = "dot",
    values: jax.Array | None = None,  # (n, d_pad) pre-dequantized codes
) -> jax.Array:
    """Coarse scores over per-query candidate lists: (m, R) fp32; pad
    entries (id -1) come back ``-inf``.  The gathered counterpart of
    :func:`ash_score_coarse_ref` (IVF partial probes): rowwise reduce
    over exact integers — order-invariant, so gathered and dense coarse
    scores agree bitwise on shared rows.
    """
    m, R = rows.shape
    safe = jnp.maximum(rows, 0)
    if values is None:
        d_pad = codes.shape[1] * Q.codes_per_word(b)
        V = Q.unpack_codes(
            codes[safe.reshape(-1)], d_pad, b
        ).astype(jnp.float32).reshape(m, R, -1)
    else:
        V = values[safe]
    dot = jnp.sum(
        q_int8.astype(jnp.float32)[:, None, :] * V, axis=-1
    )
    cl = cluster[safe]  # (m, R)
    bias = jnp.take_along_axis(
        ip_q_landmarks.astype(jnp.float32), cl, axis=1
    )
    base = _coarse_base(
        dot, q_scale, q_corr, scale.astype(jnp.float32)[safe],
        offset.astype(jnp.float32)[safe], bias,
    )
    if metric == "dot":
        out = base
    elif metric == "l2":
        qcol = qterm.astype(jnp.float32)[:, None]
        out = (2.0 * base - qcol) - rowterm.astype(jnp.float32)[safe]
    elif metric == "cos":
        qcol = qterm.astype(jnp.float32)[:, None]
        out = (base * qcol) * rowterm.astype(jnp.float32)[safe]
    else:
        raise ValueError(metric)
    return jnp.where(rows >= 0, out, -jnp.inf)


def ash_kv_attn_ref(
    q_k: jax.Array,  # (dk,) query projected into K-code space (W_k q)
    k_codes: jax.Array,  # (S, Wk) packed K codes
    k_scale: jax.Array,  # (S,)
    k_bias: jax.Array,  # (S,) per-position logit bias:
    #   <q, mu_k> + offset_k  (QUERY-COMPUTE + OFFSET folded outside)
    v_codes: jax.Array,  # (S, Wv) packed V codes
    v_scale: jax.Array,  # (S,) SCALE of the V encoder
    b_k: int,
    b_v: int,
    mask: jax.Array | None = None,  # (S,) bool; False = ignore
) -> tuple[jax.Array, jax.Array]:
    """Single-query decode attention over an ASH-compressed KV cache.

    logits_i = k_scale_i * <q_k, unpack(k_codes_i)> + k_bias_i
    p = softmax(logits)
    returns (acc (dv,), none_placeholder) where
      acc = sum_i p_i * v_scale_i * unpack(v_codes_i)
    The caller completes the output as W_v^T acc + mu_v (linear decode).
    """
    dk = k_codes.shape[1] * Q.codes_per_word(b_k)
    dv = v_codes.shape[1] * Q.codes_per_word(b_v)
    K = Q.unpack_codes(k_codes, dk, b_k).astype(jnp.float32)
    V = Q.unpack_codes(v_codes, dv, b_v).astype(jnp.float32)
    logits = (
        K @ q_k.astype(jnp.float32)
    ) * k_scale.astype(jnp.float32) + k_bias.astype(jnp.float32)
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits)
    acc = (p * v_scale.astype(jnp.float32)) @ V  # (dv,)
    return acc, p
