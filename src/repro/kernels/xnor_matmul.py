"""Packed XNOR+popcount matmul — the TPU-native TacitMap crossbar step.

The paper stores 1 bit per oPCM cell; the TPU translation of that
density is *bit-packing*: 32 binary weights/activations per int32 lane,
XOR + population_count on the VPU, int32 accumulation. HBM traffic
drops 32x vs fp32 (16x vs bf16) — the memory-roofline equivalent of the
crossbar's "weights live where the compute is".

Identity (Eq. 1 of the paper, word-packed): for ±1 vectors encoded as
{0,1} bits packed into words,

    dot±1(a, w) = m - 2 * Σ_words popcount(a_word XOR w_word)

The kernel computes the Hamming term; the `ops.py` wrapper applies the
affine correction. Pad bits are ZERO in both operands, so they XOR to
zero and drop out of the sum (tests cover ragged m).

Kernel geometry
---------------
grid = (M/bm, N/bn, KW/bkw); each step loads an int32 block of packed
activations (bm, bkw) and packed weights (bkw, bn) into VMEM and
accumulates the (bm, bn) int32 Hamming block with an unrolled
outer-product loop over the bkw word columns (static unroll — TPU VPU
friendly, no dynamic vreg indexing). The contraction grid dimension is
marked "arbitrary" so XLA keeps the accumulation in VMEM across steps.

Compiled (Mosaic) blocks must tile the TPU's (8, 128) vreg: words are
the activation block's LANE dim, so ``bkw`` is either the whole padded
word axis or a multiple of 128, and the weight block's sublane dim
``bkw`` is a multiple of 8. ``ops._word_block`` picks the whole
8-aligned word axis up to 128 words (one contraction step at the
qwen1.5-0.5b widths: 32 / 88 words), else 128-word steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels._compat import resolve_interpret

Array = jax.Array

# Block sizes: (bm, bn) int32 accumulator = 128*128*4 B = 64 KiB in VMEM;
# packed operand blocks are at most 64 KiB. Comfortably under ~16 MiB VMEM.
DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BKW = 128  # cap: 128 words = 4096 bits of contraction per step


def _hamming_kernel(a_ref, w_ref, o_ref, *, bkw: int):
    """o += Σ_k popcount(a[:, k] ^ w[k, :]) — one grid step."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...]  # (bm, bkw) int32
    w = w_ref[...]  # (bkw, bn) int32
    acc = jnp.zeros(o_ref.shape, jnp.int32)
    for k in range(bkw):  # static unroll: VPU outer products
        x = jax.lax.bitwise_xor(a[:, k : k + 1], w[k : k + 1, :])
        acc = acc + jax.lax.population_count(x)
    o_ref[...] += acc


def hamming_matmul_packed(
    a_packed: Array,
    w_packed: Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bkw: int = DEFAULT_BKW,
    interpret: bool | None = None,
) -> Array:
    """(B, KW) int32 x (KW, N) int32 -> (B, N) int32 Hamming sums.

    Operands must be pre-padded to multiples of the block sizes (the
    ``ops`` wrapper does this; zero pad-words are harmless).
    """
    interpret = resolve_interpret(interpret)
    B, KW = a_packed.shape
    KW2, N = w_packed.shape
    # Named errors, not asserts: asserts vanish under ``python -O`` and a
    # mismatched word count would silently corrupt the Hamming sums.
    if KW != KW2:
        raise ValueError(
            f"packed word-count mismatch: activations carry {KW} int32 words "
            f"but weights carry {KW2}"
        )
    if B % bm or N % bn or KW % bkw:
        raise ValueError(
            f"operands must be pre-padded to block multiples: shape "
            f"({B}, {KW}) x ({KW}, {N}) vs blocks bm={bm}, bn={bn}, bkw={bkw}"
        )

    grid = (B // bm, N // bn, KW // bkw)
    kernel = functools.partial(_hamming_kernel, bkw=bkw)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bkw), lambda i, j, k: (i, k)),
            pl.BlockSpec((bkw, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a_packed, w_packed)
