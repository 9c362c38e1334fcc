"""Core datatypes for the ASH library.

Everything is a registered JAX pytree so models/payloads flow through
``jax.jit`` / ``shard_map`` / checkpointing without special casing.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp


def pytree_dataclass(cls=None, *, meta_fields: tuple = ()):
    """Dataclass registered as a JAX pytree. ``meta_fields`` are static."""

    def wrap(c):
        c = dataclasses.dataclass(frozen=True)(c)
        data_fields = tuple(
            f.name for f in dataclasses.fields(c) if f.name not in meta_fields
        )
        jax.tree_util.register_dataclass(
            c, data_fields=list(data_fields), meta_fields=list(meta_fields)
        )
        return c

    if cls is None:
        return wrap
    return wrap(cls)


@pytree_dataclass(meta_fields=("b", "d", "n_landmarks", "store_fp16"))
class ASHConfig:
    """Static configuration of an ASH quantizer.

    Attributes:
      b: bitrate per dimension (1, 2, 4, 8).
      d: target (reduced) dimensionality, d <= D.
      n_landmarks: number of landmark (coarse-quantizer) vectors C.
      store_fp16: downcast per-vector headers (SCALE/OFFSET) to IEEE
        fp16, matching the paper's 16-bit header payload (Table 1).
    """

    b: int = 2
    d: int = 0  # 0 == "same as input D" (resolved at train time)
    n_landmarks: int = 1
    store_fp16: bool = True

    @property
    def grid_max(self) -> int:
        return 2**self.b - 1

    def payload_bits(self, with_log2c: bool = True) -> int:
        """Total bits per encoded vector, per Table 1 of the paper."""
        import math

        header = 2 * 16
        if with_log2c and self.n_landmarks > 1:
            header += math.ceil(math.log2(self.n_landmarks))
        return header + self.b * self.d


@pytree_dataclass(meta_fields=("config",))
class ASHModel:
    """Learned global parameters of an ASH quantizer.

    W = R @ P with P the top-d PCA basis (d, D) and R in SO(d); the
    landmarks are the coarse quantizer centroids (C, D).
    """

    config: ASHConfig
    W: jax.Array  # (d, D) row-orthonormal projection
    landmarks: jax.Array  # (C, D)
    # Pre-computed W @ mu_c for all landmarks (C, d): used by OFFSET and
    # the symmetric path; tiny, stored with the model.
    W_landmarks: jax.Array  # (C, d)
    landmark_sq_norms: jax.Array  # (C,)
    # Optional linear-bias correction (rho, beta) from Eq. (34); identity
    # by default. Only affects L2 search ordering, not MIPS.
    bias_rho: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.float32(1.0)
    )
    bias_beta: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.float32(0.0)
    )

    @property
    def D(self) -> int:
        return self.W.shape[1]

    @property
    def d(self) -> int:
        return self.W.shape[0]


@pytree_dataclass(meta_fields=("b", "d"))
class ASHPayload:
    """Encoded database vectors (the per-vector payload of Table 1).

    codes are bit-packed little-endian into uint32 words,
    ``32 // b`` codes per word. scale/offset are the SCALE / OFFSET
    terms of Eq. (20); cluster is c*_i. The extra fields of Table 1
    (residual norm, <x, mu*>) are *recoverable* from scale/offset:
      ||x - mu*||   = scale * ||v||          (||v|| from codes)
      <x, mu*>      = offset + scale * <W mu*, v> + ||mu*||^2
    so dot/L2/cosine search all run off this payload.
    """

    b: int
    d: int
    codes: jax.Array  # (n, n_words) uint32 bit-packed
    scale: jax.Array  # (n,) fp32 or bf16
    offset: jax.Array  # (n,) fp32 or bf16
    cluster: jax.Array  # (n,) int32

    @property
    def n(self) -> int:
        return self.codes.shape[0]


@pytree_dataclass
class ASHStats:
    """Query-independent per-row payload statistics (Table 1 recoveries).

    Everything the l2/cos scoring epilogues need beyond the payload
    itself, recovered ONCE at encode/build time (from ``<W mu*, v>``)
    instead of re-unpacking the whole database per search call:

      res_norm = ||x - mu*||         = SCALE * ||v||
      ip_x_mu  = <x, mu*>            = OFFSET + SCALE <W mu*, v> + ||mu*||^2
      x_sq     = ||x||^2             = res_norm^2 + 2 ip_x_mu - ||mu*||^2

    Rows are aligned with the owning :class:`ASHPayload`; build with
    ``scoring.payload_stats``.  Persisted with the index (``x_sq`` is
    derived again on load, ``scoring.restore_stats``) so the fused
    kernels never touch unpacked codes.
    """

    res_norm: jax.Array  # (n,) fp32
    ip_x_mu: jax.Array  # (n,) fp32
    x_sq: jax.Array  # (n,) fp32

    @property
    def n(self) -> int:
        return self.res_norm.shape[0]


@pytree_dataclass
class QueryPrep:
    """Per-query precomputed terms (QUERY-COMPUTE of Eq. (20)).

    Computed once per query; thousands of per-vector scores reuse it.
    """

    q: jax.Array  # (..., D) original query
    q_proj: jax.Array  # (..., d)  q-breve = W q
    ip_q_landmarks: jax.Array  # (..., C) <q, mu_c>
    q_sq_norm: jax.Array  # (...,) ||q||^2  (for L2)


@pytree_dataclass
class CoarseCodes:
    """Pre-dequantized code matrix for the symmetric int8 coarse scan.

    ``values`` holds the payload's grid values as EXACT small integers
    in fp32 (``2*level - (2^b - 1)``, at most +-255) so the coarse jnp
    path runs one BLAS matmul per call with no per-call ``unpack_codes``
    pass — the unpack the asymmetric jnp scan pays every search.  All
    partial sums stay below 2^24, so fp32 accumulation of these integer
    products is exact and bitwise equal to the Pallas kernel's int32
    MXU accumulation.

    ``mean`` is the scale-weighted corpus mean of the dequantized rows,
    ``mean_j(SCALE_j * v_j)`` (d_pad,) — the correction operand that
    makes coarse scores corpus-mean-unbiased estimates of the
    asymmetric score (see ``scoring.prepare_coarse_queries``).

    Derived from the payload (never persisted): rebuilt at build / add
    / compact / load alongside ``ASHStats``.
    """

    values: jax.Array  # (n, d_pad) fp32 exact grid values
    mean: jax.Array  # (d_pad,) fp32 mean_j(scale_j * v_j)

    @property
    def n(self) -> int:
        return self.values.shape[0]


@pytree_dataclass
class CoarseQueryPrep:
    """Per-query int8 symmetric quantization of ``QueryPrep.q_proj``.

    q_int8 = round(q_proj / q_scale) with a per-query symmetric scale
    q_scale = max|q_proj| / 127, so the coarse first pass accumulates
    int8 x int8 dot products on the MXU.  ``q_corr`` is the
    ``ASHStats``-style correction ``<q_proj - q_scale * q_int8,
    mean_j(scale_j * v_j)>`` folded into the Eq. (20) base score so the
    coarse estimate is unbiased against the corpus mean (it cancels the
    average quantization-residual contribution).
    """

    q_int8: jax.Array  # (m, d_pad) int8
    q_scale: jax.Array  # (m,) fp32 per-query symmetric scale
    q_corr: jax.Array  # (m,) fp32 residual correction term
