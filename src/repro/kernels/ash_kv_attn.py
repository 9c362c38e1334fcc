"""Decode attention over an ASH-compressed KV cache (Pallas TPU kernel).

Beyond-paper application of Eq. (20): the "database" is the KV cache.
K vectors are ASH-encoded (per-head projection W_k, codes packed b_k
bits/dim); V likewise.  For one new token:

  logits_i = k_scale_i * <W_k q, unpack(k_codes_i)> + k_bias_i
  p        = softmax(logits)                       (online, blockwise)
  acc      = sum_i p_i * v_scale_i * unpack(v_codes_i)   (reduced space!)

The linear ASH decoder means the V de-projection W_v^T is applied ONCE
per query *after* the reduction (outside the kernel) instead of once per
cached token — exactly the paper's "simple linear decoder" argument
(Section 2.2) transplanted to attention.  HBM traffic per step drops by
32/b_k vs a bf16 cache.

Kernel = flash-decoding-style online softmax over KV-length blocks with
in-register code unpacking; grid (S_blocks,), scratch: running (max,
denom, acc).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quantization as Q

DEFAULT_BLOCK_S = 512
_NEG_INF = -1e30


def _unpack_block(words: jax.Array, b: int, compute_dtype) -> jax.Array:
    """(s_blk, w_blk) uint32 -> (s_blk, w_blk * 32//b) grid values."""
    k = 32 // b
    shifts = (jnp.arange(k, dtype=jnp.uint32) * b).astype(jnp.uint32)
    mask = jnp.uint32(2**b - 1)
    grouped = (words[:, :, None] >> shifts[None, None, :]) & mask
    levels = grouped.reshape(words.shape[0], -1)
    return (
        2 * levels.astype(jnp.int32) - (2**b - 1)
    ).astype(compute_dtype)


def _kernel(
    qk_ref,  # (1, dk)
    k_codes_ref,  # (s_blk, wk)
    k_scale_ref,  # (1, s_blk)
    k_bias_ref,  # (1, s_blk)
    v_codes_ref,  # (s_blk, wv)
    v_scale_ref,  # (1, s_blk)
    mask_ref,  # (1, s_blk) int32 (1 = valid)
    acc_ref,  # out (1, dv) fp32
    denom_ref,  # out (1, 1) fp32
    m_scr,  # scratch (1, 1) running max
    d_scr,  # scratch (1, 1) running denom
    a_scr,  # scratch (1, dv) running acc
    *,
    b_k: int,
    b_v: int,
    n_s_blocks: int,
):
    s_idx = pl.program_id(0)

    @pl.when(s_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        d_scr[...] = jnp.zeros_like(d_scr)
        a_scr[...] = jnp.zeros_like(a_scr)

    K = _unpack_block(k_codes_ref[...], b_k, jnp.float32)  # (s_blk, dk)
    q = qk_ref[...].astype(jnp.float32)  # (1, dk)
    logits = jax.lax.dot_general(
        q, K, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (1, s_blk)
    logits = logits * k_scale_ref[...].astype(jnp.float32) + k_bias_ref[
        ...
    ].astype(jnp.float32)
    logits = jnp.where(mask_ref[...] > 0, logits, _NEG_INF)

    m_prev = m_scr[0, 0]
    m_new = jnp.maximum(m_prev, jnp.max(logits))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)  # (1, s_blk)
    d_scr[0, 0] = d_scr[0, 0] * corr + jnp.sum(p)
    V = _unpack_block(v_codes_ref[...], b_v, jnp.float32)  # (s_blk, dv)
    pv = p * v_scale_ref[...].astype(jnp.float32)  # (1, s_blk)
    a_scr[...] = a_scr[...] * corr + jax.lax.dot_general(
        pv, V, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[0, 0] = m_new

    @pl.when(s_idx == n_s_blocks - 1)
    def _final():
        acc_ref[...] = a_scr[...]
        denom_ref[...] = d_scr[...]


@functools.partial(
    jax.jit, static_argnames=("b_k", "b_v", "block_s", "interpret")
)
def ash_kv_attn_pallas(
    q_k: jax.Array,  # (dk,)
    k_codes: jax.Array,  # (S, Wk)
    k_scale: jax.Array,  # (S,)
    k_bias: jax.Array,  # (S,)
    v_codes: jax.Array,  # (S, Wv)
    v_scale: jax.Array,  # (S,)
    mask: jax.Array,  # (S,) bool
    *,
    b_k: int,
    b_v: int,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool = False,
) -> jax.Array:
    """Returns acc (dv,) = sum_i softmax(logits)_i v_scale_i unpack(v_i).

    Caller applies the V decode: out = W_v^T acc + mu_v.
    Semantics == ref.ash_kv_attn_ref (first output).
    """
    S, Wk = k_codes.shape
    Wv = v_codes.shape[1]
    dk = Wk * Q.codes_per_word(b_k)
    dv = Wv * Q.codes_per_word(b_v)
    assert q_k.shape == (dk,)

    block_s = min(block_s, _round_up(S, 128))
    S_p = _round_up(S, block_s)
    pad = S_p - S
    k_codes = jnp.pad(k_codes, ((0, pad), (0, 0)))
    v_codes = jnp.pad(v_codes, ((0, pad), (0, 0)))
    k_scale2 = jnp.pad(k_scale, (0, pad)).reshape(1, S_p)
    k_bias2 = jnp.pad(k_bias, (0, pad)).reshape(1, S_p)
    v_scale2 = jnp.pad(v_scale, (0, pad)).reshape(1, S_p)
    mask2 = jnp.pad(mask.astype(jnp.int32), (0, pad)).reshape(1, S_p)
    qk2 = q_k.reshape(1, dk)

    grid = (S_p // block_s,)
    acc, denom = pl.pallas_call(
        functools.partial(
            _kernel, b_k=b_k, b_v=b_v, n_s_blocks=grid[0]
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, dk), lambda i: (0, 0)),
            pl.BlockSpec((block_s, Wk), lambda i: (i, 0)),
            pl.BlockSpec((1, block_s), lambda i: (0, i)),
            pl.BlockSpec((1, block_s), lambda i: (0, i)),
            pl.BlockSpec((block_s, Wv), lambda i: (i, 0)),
            pl.BlockSpec((1, block_s), lambda i: (0, i)),
            pl.BlockSpec((1, block_s), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, dv), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, dv), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qk2, k_codes, k_scale2, k_bias2, v_codes, v_scale2, mask2)
    return (acc / jnp.maximum(denom, 1e-30))[0]


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
