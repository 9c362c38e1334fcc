"""Peaks of the chip and the operations and bytes of each kernel call.

The peak table (``peaks.json``) is keyed by JAX's ``device_kind`` and
names its source; a device that is not in it is an error, never a
default.  The shape functions count what one call of a kernel has to
do at the shapes the engine gave it: the estimator's multiply-adds
(two operations each) and every operand the kernel reads or writes
once.  A roofline share is the least time those need at the chip's
peaks, over the kernel's measured device time; it names the bound
(``compute`` or ``memory``) that sets that least time.  Operations are
held against the MXU's bfloat16 peak, the highest the chip publishes,
so a share can only read low, never high.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"

# the serving kernels' tiling (repro.kernels.ash_score defaults)
BLOCK_M = 128
BLOCK_N = 2048
BLOCK_R = 512
GATHER_Q = 8
F32 = 4


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


def _up(x: int, m: int) -> int:
    return -(-x // m) * m


def codes_per_word(b: int) -> int:
    return 32 // b


def dense_topk_call(s: dict, m: int, k_tilde: int) -> tuple:
    """(ops, bytes) of one fused dense scan + top-k over all ``n`` rows
    for a bucket of ``m`` queries."""
    n, W, C = s["n"], s["words"], s["n_landmarks"]
    d_pad = W * codes_per_word(s["b"])
    bm = min(BLOCK_M, _up(m, 8))
    m_p = _up(m, bm)
    ops = 2 * m_p * n * d_pad
    rows = n * (W + 3 + (0 if s["metric"] == "dot" else 1))
    queries = m_p * (d_pad + C + (0 if s["metric"] == "dot" else 1))
    n_tiles = -(-n // min(BLOCK_N, _up(n, 128)))
    strip = 2 * n_tiles * _up(k_tilde, 8) * m_p
    return ops, F32 * (rows + queries + strip)


def gather_topk_call(s: dict, m: int, nprobe: int, k_tilde: int) -> tuple:
    """(ops, bytes) of one masked-gather scan + top-k over each query's
    ``nprobe`` padded inverted lists, for a bucket of ``m`` queries: the
    kernel's operands as the XLA gather laid them out (per candidate
    its packed words, scale, bias, offset, row id and, unless ``dot``,
    row term), the queries, and the candidate strip it writes."""
    W, L = s["words"], s["max_list_len"]
    d_pad = W * codes_per_word(s["b"])
    R = nprobe * L
    R_p = _up(R, min(BLOCK_R, _up(R, 128)))
    m_p = _up(m, GATHER_Q)
    per_cand = W + 4 + (0 if s["metric"] == "dot" else 1)
    ops = 2 * m_p * R_p * d_pad
    cand = m_p * R_p * per_cand
    strip = 2 * (R_p // min(BLOCK_R, R_p)) * _up(k_tilde, 8) * m_p
    return ops, F32 * (cand + m_p * d_pad + strip)


def share(ops: float, nbytes: float, seconds: float, pk: dict):
    """(roofline share in %, bound) of work done in ``seconds``."""
    t_ops = ops / pk["bf16_flops_per_s"]
    t_mem = nbytes / pk["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
