"""Operations and bytes the served model's work needs, from its shapes.

These count what the algorithm requires, whatever implements it:

* a BitLinear projection of ``rows`` tokens, ``K`` inputs and ``N``
  outputs is ``2 * rows * K * N`` operations of the +-1 product, and
  moves its weights at 1 bit each plus its activations in and out at
  bfloat16 (2 bytes);
* attention of one query over ``c`` positions is ``4 * heads *
  head_dim * c`` operations (scores and the value product);
* the head is ``2 * d * vocab`` operations per token it is read for.

The kernel's roofline time is, call by call, the larger of operations
over the int8 peak (no implementation of a +-1 product beats it) and
bytes over the memory bandwidth.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device missing from the
    table is an error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}")
    return table[device_kind]


def bitlinear_shapes(dims) -> list[tuple[int, int]]:
    """(K, N) of each BitLinear call of one layer, as the packed path
    issues them: q, k and v fused into one call, then o, w1, w3, w2."""
    d, hd = dims.d, dims.head_dim
    qkv = (dims.heads + 2 * dims.kv_heads) * hd
    return [(d, qkv), (dims.heads * hd, d), (d, dims.d_ff), (d, dims.d_ff), (dims.d_ff, d)]


def bitlinear_ops(rows: int, k: int, n: int) -> float:
    return 2.0 * rows * k * n


def bitlinear_bytes(rows: int, k: int, n: int) -> float:
    return k * n / 8 + 2.0 * rows * (k + n)


def bitlinear_roofline(dims, row_counts, peak: dict) -> tuple[float, float]:
    """(seconds, share of them bound by bytes) for the kernel calls of
    ``row_counts``: one entry per forward pass (a prefill's prompt
    length, a decode tick's live rows), each running every layer."""
    total = by_bytes = 0.0
    for rows in row_counts:
        for k, n in bitlinear_shapes(dims):
            t_ops = bitlinear_ops(rows, k, n) / peak["int8_ops_per_s"]
            t_bytes = bitlinear_bytes(rows, k, n) / peak["hbm_bytes_per_s"]
            total += dims.layers * max(t_ops, t_bytes)
            by_bytes += dims.layers * t_bytes if t_bytes >= t_ops else 0.0
    return total, (by_bytes / total if total else 0.0)


def projection_ops_per_token(dims) -> float:
    return dims.layers * sum(bitlinear_ops(1, k, n) for k, n in bitlinear_shapes(dims))


def attention_ops(dims, context: int) -> float:
    """One query attending over ``context`` positions, every layer."""
    return dims.layers * 4.0 * dims.heads * dims.head_dim * context


def head_ops(dims) -> float:
    return 2.0 * dims.d * dims.vocab


def prefill_ops(dims, prompt_len: int) -> float:
    """A causal prefill of ``prompt_len`` tokens, and the head once."""
    causal = prompt_len * (prompt_len + 1) / 2
    return (
        prompt_len * projection_ops_per_token(dims)
        + dims.layers * 4.0 * dims.heads * dims.head_dim * causal
        + head_ops(dims)
    )


def decode_ops(dims, context: int) -> float:
    """One generated token whose query attends over ``context`` positions."""
    return projection_ops_per_token(dims) + attention_ops(dims, context) + head_ops(dims)
