"""Fused BNN decode-tick kernel: binarize + pack + XNOR + scale in one pass.

The paper's speed claim is that the crossbar collapses a whole
matrix–matrix step into one in-memory activation: weights never move and
activations stream through exactly once (PAPER.md, Eq. 1). The unfused
TPU translation leaves that on the table — every decode tick runs
binarize -> ``pack_bits`` -> ``hamming_matmul_packed`` -> affine
correction -> per-token rescale as *separate* XLA ops, with the raw
activation block crossing HBM between each. This kernel is the fused
read path: raw fp activations in, scaled BitLinear output out, one
``pallas_call``.

Per grid step the kernel

1. loads a raw fp32 activation block (bm, bkw*32) into VMEM,
2. binarizes in-register (``x >= 0`` -> bit 1, matching
   ``bnn.binarize_ste`` — zero maps to +1) and bit-packs 32 lanes per
   int32 word exactly like ``ops.pack_bits`` (per word: a static
   32-lane slice, a shift by lane index and an int32 lane sum),
3. XORs against the prepared weight words and accumulates popcounts
   straight into the live fp32 output block (same unrolled
   outer-product loop as ``xnor_matmul.py``; the block index map drops
   the contraction dim, so the block stays resident across k steps —
   popcount partials are small integers, exactly representable in fp32),
4. on the last contraction step rewrites the block in place with the
   Eq. 1 affine correction ``dot = m - 2 * hamming`` and the BitLinear
   rescale ``out = dot * (alpha * beta)`` — ``alpha * beta`` is
   multiplied FIRST, reproducing ``models.layers.dense``'s f32
   association so the fused path stays bit-exact against the reference
   engine.

No VMEM scratch is used: the Hamming count lives in the output block
itself and activation words are re-packed per output-column block.  The
re-pack is a handful of VPU ops against a block already in VMEM, while a
scratch accumulator forces the interpreter (CPU CI) to thread carried
state through every grid step — measured ~3x slower per launch.

Grid = (B/bm, N/bn, KW/bkw) where B is all leading dims flattened — the
serving engine's stacked (G, K, m) grouped activations run as one launch
with B = G*K. Pad discipline: the ops wrapper pads activation FEATURES
with -1.0 (binarizes to bit 0) and weights with zero words, so pad bits
XOR to zero and drop out of the Hamming sum; ``m`` carries the true
contraction length for the affine correction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels._compat import resolve_interpret

Array = jax.Array

WORD = 32

# Same budget as xnor_matmul: the fp32 activation block dominates at
# (bm, bkw*32) * 4 B <= 2 MiB (bm=128, bkw=128), double-buffered.
DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BKW = 128


def _fused_kernel(x_ref, w_ref, alpha_ref, beta_ref, o_ref, *, bkw: int, m: int):
    """One grid step of the fused binarize-pack-popcount-scale pass."""
    kblk = pl.program_id(2)

    @pl.when(kblk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # binarize + bit-pack in VMEM, one word column at a time: word k is
    # lanes [32k, 32k+32) of the fp32 block, bit i = lane 32k+i
    # (ops.pack_bits layout). Bits are disjoint, so the signed int32
    # lane sum equals their OR, bit 31 included (two's complement) —
    # Mosaic has no unsigned reductions and no lane-splitting reshape,
    # so the pack is a static lane slice + shift + lane sum per word.
    x = x_ref[...]
    shifts = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], WORD), 1)
    w = w_ref[...]  # (bkw, bn) int32 prepared weight words
    acc = jnp.zeros(o_ref.shape, jnp.int32)
    for k in range(bkw):  # static unroll: VPU outer products
        bits = (x[:, k * WORD : (k + 1) * WORD] >= 0).astype(jnp.int32)
        word = jnp.sum(bits << shifts, axis=1, keepdims=True)  # (bm, 1)
        xw = jax.lax.bitwise_xor(word, w[k : k + 1, :])
        acc = acc + jax.lax.population_count(xw)
    # Hamming partials are integers < bkw*32 * bm-blocks <= m << 2^24,
    # so the fp32 running sum in the output block is exact.
    o_ref[...] += acc.astype(jnp.float32)

    # last contraction step: affine correction + BitLinear rescale,
    # rewriting the accumulated Hamming count in place.
    @pl.when(kblk == pl.num_programs(2) - 1)
    def _finish():
        dot = m - 2.0 * o_ref[...]  # exact: integer-valued fp32
        # (alpha * beta) FIRST — same f32 association as layers.dense.
        o_ref[...] = dot * (alpha_ref[...] * beta_ref[...])


def fused_bnn_matmul_kernel(
    x: Array,
    w_packed: Array,
    alpha: Array,
    beta: Array,
    *,
    m: int,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bkw: int = DEFAULT_BKW,
    interpret: bool | None = None,
) -> Array:
    """(B, KW*32) fp32 x (KW, N) words x (1, N) x (B, 1) -> (B, N) fp32.

    Operands must be pre-padded to block multiples (the ``ops`` wrapper
    does this; activation pad columns must binarize to bit 0, i.e. be
    negative). ``m`` is the true contraction length for Eq. 1.
    """
    interpret = resolve_interpret(interpret)
    B, MP = x.shape
    KW, N = w_packed.shape
    if MP != KW * WORD:
        raise ValueError(
            f"activation block carries {MP} features but weights carry "
            f"{KW} words = {KW * WORD} bits"
        )
    if alpha.shape != (1, N) or beta.shape != (B, 1):
        raise ValueError(
            f"scale shapes must be alpha (1, {N}) / beta ({B}, 1), got "
            f"{alpha.shape} / {beta.shape}"
        )
    if B % bm or N % bn or KW % bkw:
        raise ValueError(
            f"operands must be pre-padded to block multiples: shape "
            f"({B}, {MP}) x ({KW}, {N}) vs blocks bm={bm}, bn={bn}, bkw={bkw}"
        )

    grid = (B // bm, N // bn, KW // bkw)
    kernel = functools.partial(_fused_kernel, bkw=bkw, m=m)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bkw * WORD), lambda i, j, k: (i, k)),
            pl.BlockSpec((bkw, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w_packed, alpha, beta)
