"""WDM MMM kernel — EinsteinBarrier's K-wavelength step on the MXU.

The paper's WDM turns a VMM into an MMM: K input vectors share one pass
through the crossbar (Fig. 5-(b)). The MXU analogue: the K wavelengths
are the *sublane-batched rows* of a (K, m) @ (m, n) matmul — one systolic
pass serves all K rows, exactly the "same weights, K simultaneous
inputs" structure. ±1 values are carried in bf16 (exactly representable;
fp32 accumulation keeps integer exactness for m < 2^24).

Kernel geometry: grid (B/bb, N/bn, M/bm) over a (B, m) lhs where
B = G*K flattened wavelength groups; fp32 (bb, bn) accumulator block in
VMEM; contraction dimension marked "arbitrary".
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels._compat import resolve_interpret

Array = jax.Array

DEFAULT_BB = 128   # wavelength-batch rows per block (G*K flattened)
DEFAULT_BN = 128
DEFAULT_BM = 512   # contraction slice


def _mmm_kernel(a_ref, w_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        a_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )


def mmm(
    lhs: Array,
    rhs: Array,
    *,
    bb: int = DEFAULT_BB,
    bn: int = DEFAULT_BN,
    bm: int = DEFAULT_BM,
    interpret: bool | None = None,
) -> Array:
    """(B, M) x (M, N) -> (B, N) fp32, MXU-blocked.

    Operands must be pre-padded to block multiples (ops wrapper).
    """
    interpret = resolve_interpret(interpret)
    B, M = lhs.shape
    M2, N = rhs.shape
    if M != M2:
        raise ValueError(f"contraction mismatch: lhs has {M} cols, rhs {M2} rows")
    if B % bb or N % bn or M % bm:
        raise ValueError(
            f"operands must be pre-padded to block multiples: shape "
            f"({B}, {M}) x ({M}, {N}) vs blocks bb={bb}, bn={bn}, bm={bm}"
        )
    grid = (B // bb, N // bn, M // bm)
    return pl.pallas_call(
        _mmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bm), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lhs, rhs)
