"""Fused BitLinear kernel: binarize -> ±1 matmul -> per-column rescale.

This is the deployable form of the paper's technique inside an LM layer
(DESIGN.md §4): activations are sign-binarized *inside* the kernel (no
fp activation round-trip to HBM), multiplied against pre-binarized ±1
weights on the MXU, and rescaled by the per-output-channel fp scale in
the same VMEM residency. One kernel = binarize + XNOR-popcount-matmul +
dequant, the fusion a crossbar gets for free.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels._compat import resolve_interpret

Array = jax.Array

DEFAULT_BB = 128
DEFAULT_BN = 128
DEFAULT_BM = 512


def _bitlinear_kernel(x_ref, w_ref, alpha_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    xs = jnp.where(x >= 0, 1.0, -1.0).astype(jnp.bfloat16)  # in-kernel binarize
    o_ref[...] += jnp.dot(xs, w_ref[...], preferred_element_type=jnp.float32)

    # rescale once, after the last contraction step
    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _scale():
        o_ref[...] *= alpha_ref[...]


def bitlinear(
    x: Array,
    w_signs: Array,
    alpha: Array,
    *,
    bb: int = DEFAULT_BB,
    bn: int = DEFAULT_BN,
    bm: int = DEFAULT_BM,
    interpret: bool | None = None,
) -> Array:
    """(B, M) fp x (M, N) ±1 x (N,) scale -> (B, N) fp32.

    Operands pre-padded to block multiples; pad columns of ``x`` must be
    >= 0 or exactly 0 — they binarize to +1 and hit zero pad *rows* of
    ``w`` (the ops wrapper pads w with zeros), contributing 0.
    """
    interpret = resolve_interpret(interpret)
    B, M = x.shape
    M2, N = w_signs.shape
    if M != M2:
        raise ValueError(f"contraction mismatch: x has {M} cols, w {M2} rows")
    if B % bb or N % bn or M % bm:
        raise ValueError(
            f"operands must be pre-padded to block multiples: shape "
            f"({B}, {M}) x ({M}, {N}) vs blocks bb={bb}, bn={bn}, bm={bm}"
        )
    grid = (B // bb, N // bn, M // bm)
    return pl.pallas_call(
        _bitlinear_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bm), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w_signs.astype(jnp.bfloat16), alpha.reshape(1, -1).astype(jnp.float32))
