"""Public jit'd wrappers around the Pallas kernels.

Handles bit-packing, padding to block multiples, the Eq. 1 affine
correction, and shape restoration — callers pass ordinary arrays.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core import bnn
from repro.kernels import _compat
from repro.kernels import bitlinear as _bitlinear_kernel
from repro.kernels import fused_decode as _fused_kernel
from repro.kernels import wdm_mmm as _wdm_kernel
from repro.kernels import xnor_matmul as _xnor_kernel

Array = jax.Array

WORD = 32


# ---------------------------------------------------------------------------
# Bit packing
# ---------------------------------------------------------------------------


def pack_bits(bits: Array, axis: int = -1) -> Array:
    """Pack {0,1} values into int32 words along ``axis`` (zero-padded).

    (..., m) -> (..., ceil(m/32)); bit i of word j is element 32*j + i.
    """
    bits = jnp.moveaxis(bits, axis, -1)
    m = bits.shape[-1]
    kw = math.ceil(m / WORD)
    pad = kw * WORD - m
    b = jnp.pad(bits.astype(jnp.uint32), [(0, 0)] * (bits.ndim - 1) + [(0, pad)])
    b = b.reshape(*b.shape[:-1], kw, WORD)
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    words = jnp.sum(b << shifts, axis=-1, dtype=jnp.uint32)  # disjoint bits: sum == or
    return jnp.moveaxis(jax.lax.bitcast_convert_type(words, jnp.int32), -1, axis)


def pack_signs(signs: Array, axis: int = -1) -> Array:
    """Pack ±1 values (bit = 1 for +1) into int32 words."""
    return pack_bits((signs > 0).astype(jnp.uint32), axis)


def pack_weights(w_signs: Array) -> Array:
    """Program ±1 weights for the packed kernel: (m, n) -> (ceil(m/32), n).

    int32 words packed along the contraction axis (bit = 1 for +1, zero
    pad bits). This is the packed backend's one-time "crossbar
    programming" step — callers that hold weights resident (the
    prepared-weights path, ``Engine.prepare``) pay it once and then
    stream only activations through :func:`xnor_matmul_packed_weights`.
    """
    return pack_bits((w_signs > 0).astype(jnp.uint32), axis=0)


def pad_packed_weights(
    w_packed: Array,
    *,
    bkw: int = _xnor_kernel.DEFAULT_BKW,
    bn: int = _xnor_kernel.DEFAULT_BN,
) -> Array:
    """Pre-pad packed weight words to kernel block multiples at *program*
    time: (KW, n) -> (ceil(KW/b)*b, ceil(n/bn)*bn) with
    ``b = _word_block(KW, bkw)``, zero pad words.

    The execute-phase wrappers re-pad every call; ``_pad_to`` is a no-op
    on already-aligned operands, so paying the padding once here removes
    the per-tick ``jnp.pad`` of the (large) weight side from the decode
    graph. Zero pad words XOR to zero against zero activation pad bits
    and drop out of the Hamming sum, and the wrappers slice with the
    *logical* ``m``/``n``, so results are bit-identical either way.
    """
    bkw = _word_block(w_packed.shape[0], bkw)
    return _pad_to(_pad_to(w_packed, bkw, 0), bn, 1)


def _pad_to(x: Array, mult: int, axis: int) -> Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _row_block(requested: int, size: int, unit: int = 8) -> int:
    """Shrink a row-block size for operands smaller than one block.

    The kernels require dims to be block multiples, so small/ragged
    operands (the model zoo's odd layer widths, single-vector batches)
    are padded UP — but padding a 6-row batch to a 128-row block wastes
    ~20x the kernel work. Rows are the TPU sublane dim, so any multiple
    of the sublane tile (8 for int32/fp32 operands, 16 for bf16) is a
    legal block: clamp to the operand size rounded up to ``unit``.
    Lane-dim blocks (n, packed words) stay as requested — sub-128 lane
    tiles are where Mosaic layouts get inefficient.
    """
    return min(requested, max(unit, -(-size // unit) * unit))


def _word_block(kw: int, requested: int) -> int:
    """Contraction words per grid step of the packed kernels.

    Packed words are the LANE dim of the xnor kernel's activation block
    and the SUBLANE dim of both kernels' weight blocks, so a compiled
    block is the whole word axis padded to a multiple of 8 or a
    multiple of 128 words: clamp the requested cap (128 by default) to
    the 8-aligned word count. At qwen1.5-0.5b widths (32 and 88 words)
    that is one contraction step covering the whole axis.
    """
    return min(requested, max(8, -(-kw // 8) * 8))


# ---------------------------------------------------------------------------
# XNOR matmul (packed popcount path)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("m", "n", "bm", "bn", "bkw", "interpret"))
def xnor_matmul_packed_weights(
    a_signs: Array,
    w_packed: Array,
    *,
    m: int,
    n: int,
    bm: int = _xnor_kernel.DEFAULT_BM,
    bn: int = _xnor_kernel.DEFAULT_BN,
    bkw: int = _xnor_kernel.DEFAULT_BKW,
    interpret: bool | None = None,
) -> Array:
    """±1 binary matmul against pre-packed weights (:func:`pack_weights`).

    (..., m) x (ceil(m/32), n) words -> (..., n) int32. ``m``/``n`` are
    the *logical* weight dims (static): the word padding carries zero
    bits, and the Eq. 1 affine correction ``dot = m - 2 * hamming``
    needs the true contraction length. Only the activation side packs
    per call — this is the execute phase of the two-phase contract.
    """
    lead = a_signs.shape[:-1]
    a2 = a_signs.reshape(-1, m)
    ap = pack_bits((a2 > 0).astype(jnp.uint32))
    bm_eff = _row_block(bm, a2.shape[0])
    bkw = _word_block(w_packed.shape[0], bkw)
    ap = _pad_to(_pad_to(ap, bm_eff, 0), bkw, 1)
    wp = _pad_to(_pad_to(w_packed, bkw, 0), bn, 1)
    ham = _xnor_kernel.hamming_matmul_packed(ap, wp, bm=bm_eff, bn=bn, bkw=bkw, interpret=interpret)
    out = m - 2 * ham[: a2.shape[0], :n]
    return out.reshape(*lead, n)


def xnor_matmul(
    a_signs: Array,
    w_signs: Array,
    *,
    bm: int = _xnor_kernel.DEFAULT_BM,
    bn: int = _xnor_kernel.DEFAULT_BN,
    bkw: int = _xnor_kernel.DEFAULT_BKW,
    interpret: bool | None = None,
) -> Array:
    """±1 binary matmul via the packed XNOR+popcount Pallas kernel.

    (..., m) x (m, n) -> (..., n) int32. Bit-exact vs the ±1 matmul:
    dot = m - 2 * hamming. Packs the weights then delegates to
    :func:`xnor_matmul_packed_weights` — one execution path, so the raw
    and prepared-weight routes are bit-identical by construction.
    """
    return xnor_matmul_packed_weights(
        a_signs,
        pack_weights(w_signs),
        m=int(a_signs.shape[-1]),
        n=int(w_signs.shape[1]),
        bm=bm,
        bn=bn,
        bkw=bkw,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Fused BNN decode tick (binarize + pack + XNOR + popcount + scale)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("m", "n", "bm", "bn", "bkw", "interpret"))
def fused_bnn_matmul(
    x: Array,
    w_packed: Array,
    alpha: Array,
    *,
    m: int,
    n: int,
    bm: int = _fused_kernel.DEFAULT_BM,
    bn: int = _fused_kernel.DEFAULT_BN,
    bkw: int = _fused_kernel.DEFAULT_BKW,
    interpret: bool | None = None,
) -> Array:
    """Whole fused BitLinear against prepared weights, one kernel launch.

    (..., m) raw fp x (ceil(m/32), n) words x alpha -> (..., n) fp32 of
    ``(binarize(x) @ w±1) * (alpha * beta)`` with ``beta = mean|x|`` per
    row — the full ``models.layers.dense`` BNN seam fused into a single
    ``pallas_call`` (binarize, bit-pack, XNOR+popcount, Eq. 1 affine
    correction and rescale all happen in VMEM; the raw activation block
    crosses HBM exactly once). Leading dims flatten, so the serving
    engine's stacked (G, K, m) grouped activations are one launch.

    ``alpha`` is a scalar (one per-tensor scale) or an (n,) vector (the
    concatenated [q|k|v] fused projection). ``beta`` is computed here by
    ``bnn.activation_scale``, the function ``dense`` uses, so the fused
    path is bit-exact vs the unfused reference. Activation feature padding uses
    -1.0: pad columns binarize to bit 0 and drop out of the Hamming sum
    against the zero weight pad words.
    """
    lead = x.shape[:-1]
    beta = bnn.activation_scale(x)
    x2 = x.reshape(-1, m).astype(jnp.float32)
    rows = x2.shape[0]
    beta2 = beta.reshape(rows, 1)
    alpha2 = jnp.broadcast_to(
        jnp.asarray(alpha, jnp.float32).reshape(-1), (n,)
    ).reshape(1, n)

    kw = math.ceil(m / WORD)
    # ``w_packed`` may arrive pre-padded to block multiples (the packed
    # engine's ``prepad`` programming layout) — treat its stored word
    # rows as the contraction extent; extra rows are zero pad words the
    # -1.0 activation pads cancel against.
    kw_w = w_packed.shape[0]
    if kw_w < kw:
        raise ValueError(
            f"prepared weights carry {kw_w} words but m={m} needs {kw}"
        )
    # Block-size policy. Blocking exists for VMEM locality; the CPU
    # interpreter (CI) has no VMEM and instead pays a large fixed cost
    # PER GRID STEP, so there rows and columns take one block each.
    # Compiled TPU keeps (8, 128)-tiled row and column blocks. Both
    # share the contraction block of ``_word_block``: the whole
    # 8-aligned word axis up to 128 words, which also bounds the
    # statically unrolled per-word loop.
    bkw = _word_block(kw_w, bkw)
    if _compat.resolve_interpret(interpret):
        bm_eff, bn = rows, w_packed.shape[1]
    else:
        bm_eff = _row_block(bm, rows)
    kw_pad = -(-kw_w // bkw) * bkw
    # feature pads binarize to bit 0 (negative); pad rows are sliced away
    x2 = jnp.pad(
        x2, [(0, (-rows) % bm_eff), (0, kw_pad * WORD - m)], constant_values=-1.0
    )
    wp = _pad_to(_pad_to(w_packed, bkw, 0), bn, 1)
    out = _fused_kernel.fused_bnn_matmul_kernel(
        x2,
        wp,
        _pad_to(alpha2, bn, 1),
        _pad_to(beta2, bm_eff, 0),
        m=m,
        bm=bm_eff,
        bn=bn,
        bkw=bkw,
        interpret=interpret,
    )
    return out[:rows, :n].reshape(*lead, n)


# ---------------------------------------------------------------------------
# WDM MMM
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("bb", "bn", "bm", "interpret"))
def wdm_mmm(
    groups: Array,
    w: Array,
    *,
    bb: int = _wdm_kernel.DEFAULT_BB,
    bn: int = _wdm_kernel.DEFAULT_BN,
    bm: int = _wdm_kernel.DEFAULT_BM,
    interpret: bool | None = None,
) -> Array:
    """(G, K, m) x (m, n) -> (G, K, n): K wavelengths per systolic pass."""
    g, k, m = groups.shape
    lhs = groups.reshape(g * k, m).astype(jnp.bfloat16)
    bb = _row_block(bb, g * k, unit=16)  # bf16 sublane tile
    lhs = _pad_to(_pad_to(lhs, bb, 0), bm, 1)
    rhs = _pad_to(_pad_to(w.astype(jnp.bfloat16), bm, 0), bn, 1)
    out = _wdm_kernel.mmm(lhs, rhs, bb=bb, bn=bn, bm=bm, interpret=interpret)
    return out[: g * k, : w.shape[1]].reshape(g, k, w.shape[1])


# ---------------------------------------------------------------------------
# BitLinear (fused binarize + matmul + rescale)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("bb", "bn", "bm", "interpret"))
def bitlinear(
    x: Array,
    w_signs: Array,
    alpha: Array,
    *,
    bb: int = _bitlinear_kernel.DEFAULT_BB,
    bn: int = _bitlinear_kernel.DEFAULT_BN,
    bm: int = _bitlinear_kernel.DEFAULT_BM,
    interpret: bool | None = None,
) -> Array:
    """(..., m) fp x (m, n) ±1 x (n,) -> (..., n) fp32 fused BitLinear."""
    m = x.shape[-1]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, m)
    bb = _row_block(bb, x2.shape[0], unit=16 if x.dtype == jnp.bfloat16 else 8)
    x2 = _pad_to(_pad_to(x2, bb, 0), bm, 1)
    # pad weight ROWS with zeros: pad x columns binarize to +1 and hit
    # zero rows -> contribute nothing (see kernel docstring)
    wp = _pad_to(_pad_to(w_signs, bm, 0), bn, 1)
    ap = _pad_to(alpha, bn, 0)
    out = _bitlinear_kernel.bitlinear(x2, wp, ap, bb=bb, bn=bn, bm=bm, interpret=interpret)
    n = w_signs.shape[1]
    rows = math.prod(lead) if lead else 1
    return out[:rows, :n].reshape(*lead, n)
