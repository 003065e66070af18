"""Fused flash attention (forward) — Pallas TPU kernel.

Why: the roofline table (EXPERIMENTS.md §Roofline) shows the memory
term dominating nearly every cell, and the per-computation byte
attribution puts the bulk of it in attention score/probability
materialization — the jnp flash path writes (B, H, Sq, C) fp32 score
blocks to HBM on every KV chunk (~2 GB per chunk-step on qwen2-72b
train). This kernel keeps the entire online-softmax state (scores,
probabilities, m/l accumulators) in VMEM: HBM traffic drops to the
q/k/v/o tensors themselves.

Geometry
--------
grid = (B, H, Sq/bq, Skv/bk) — the KV dimension is the innermost
(sequential) axis; (m, l, acc) live in VMEM scratch across its steps.
GQA costs nothing: the K/V BlockSpec index_map divides the head index
by the group size, so grouped heads read the same KV block without any
materialized repeat.

Causal masking positions each block with absolute offsets; blocks
entirely above the diagonal still run (simplicity > skip logic here —
the scheduler-level win of skipping is an optimization documented in
EXPERIMENTS.md §Perf).

Validated in interpret mode against ``ref.attention_ref`` across shape/
dtype/GQA sweeps (tests/test_flash_attention.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels._compat import resolve_interpret

Array = jax.Array

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, bq: int, bk: int, n_k: int,
                  diag_offset: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (bq, bk)

    if causal:
        # queries are the LAST sq positions when Skv > Sq (decode-style)
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + diag_offset
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    if causal:  # fully-masked rows: keep p exactly zero
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
    interpret: bool | None = None,
) -> Array:
    """q (B, H, Sq, D); k/v (B, KV, Skv, D) with KV | H. -> (B, H, Sq, D).

    Scores/probabilities never leave VMEM. Sq/Skv are padded to block
    multiples internally (padded keys are masked by position).
    """
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    g = h // kvh
    scale = 1.0 / math.sqrt(d)
    interpret = resolve_interpret(interpret)

    bq_ = min(bq, max(sq, 8))
    bk_ = min(bk, max(skv, 8))
    pad_q = (-sq) % bq_
    pad_k = (-skv) % bk_
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    sq_p, skv_p = q.shape[2], k.shape[2]
    n_k = skv_p // bk_

    # padded keys must never win the softmax: causal masking handles the
    # tail when causal; for non-causal, mask via an explicit bias would
    # be needed — callers pad KV themselves in that case (asserted):
    if not causal and pad_k:
        raise ValueError("non-causal flash_attention requires Skv % bk == 0")

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal or bool(pad_k),
        bq=bq_, bk=bk_, n_k=n_k, diag_offset=(skv - sq) if causal else 0,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, h, sq_p // bq_, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq_, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, bk_, d), lambda b_, h_, qi, ki, g_=g: (b_, h_ // g_, ki, 0)),
            pl.BlockSpec((1, 1, bk_, d), lambda b_, h_, qi, ki, g_=g: (b_, h_ // g_, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq_, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq, :]
