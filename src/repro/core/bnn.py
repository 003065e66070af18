"""Binary-neural-network arithmetic: Eq. 1 of the paper, binarization, STE.

The paper's Eq. 1 (for equally-sized binary vectors)::

    In (*) W = 2 * Popcount(In' XNOR W') - VectorLength

where ``In', W'`` are the {0,1} encodings of the ±1 vectors ``In, W``.
Everything in this module is pure jnp and differentiable where it needs
to be (straight-through estimators for training).

Conventions
-----------
* ``bits``    — arrays with values in {0, 1} (any integer/float dtype).
* ``signs``   — arrays with values in {-1, +1}.
* ``latent``  — real-valued master weights (training time).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# ---------------------------------------------------------------------------
# Encodings
# ---------------------------------------------------------------------------


def signs_to_bits(x: Array) -> Array:
    """Map {-1,+1} -> {0,1} (``-1 -> 0``, ``+1 -> 1``)."""
    return ((x + 1) // 2).astype(x.dtype) if jnp.issubdtype(x.dtype, jnp.integer) else (x + 1.0) * 0.5


def bits_to_signs(b: Array) -> Array:
    """Map {0,1} -> {-1,+1}."""
    return 2 * b - 1


def binarize_ste(x: Array) -> Array:
    """Sign-binarize with a straight-through estimator.

    Forward: ``sign(x)`` in {-1, +1} (zero maps to +1).
    Backward: identity within the clip region |x| <= 1 (hard-tanh STE,
    the standard BNN estimator from Courbariaux et al.).
    """
    binary = jnp.where(x >= 0, 1.0, -1.0).astype(x.dtype)
    # straight-through: forward uses `binary`, gradient flows through the
    # clipped identity.
    clipped = jnp.clip(x, -1.0, 1.0)
    return clipped + jax.lax.stop_gradient(binary - clipped)


def weight_scale(w: Array) -> Array:
    """BitLinear's per-tensor weight scale ``alpha = mean|w|`` (f32)."""
    return jnp.mean(jnp.abs(w)).astype(jnp.float32)


_SCALE_LEVELS = 3  # exact partial sums per activation_scale


@jax.custom_jvp
def activation_scale(x: Array) -> Array:
    """BitLinear's per-row activation scale ``beta = mean|x|`` over the
    feature axis: (..., m) -> (..., 1) f32, the same bits in every
    program.

    XLA picks a reduction's summation order per program (layout, fusion,
    which dims it folds) and may turn ``/ m`` into ``* (1/m)``, so a
    plain f32 mean can differ in the last bit between two programs that
    compute it from the same activations. On a TPU v5e the reference
    path and the packed kernel path disagreed this way on the 2816-wide
    FFN activations, and one ulp of beta flips binarized activations
    downstream. Here every partial sum is exact, so no order matters:
    ``|x|`` is cut into three parts on power-of-two grids, each grid
    ``2**(24 - log2 m)`` times finer than the last and the first sized
    so that ``m * max|x|`` is at most ``2**24`` grid steps; each part
    sums exactly in f32, and what the finest grid drops is below
    ``2**-24 * max|x|`` in all. The derivative is that of ``mean|x|``.
    """
    a = jnp.abs(x).astype(jnp.float32)
    m = a.shape[-1]
    bits = max(1, (m - 1).bit_length())  # m <= 2**bits
    # max|x| < 2**e, read off the f32 exponent field; the clamp keeps
    # every grid a normal f32 number
    big = jax.lax.bitcast_convert_type(jnp.max(a, axis=-1, keepdims=True), jnp.int32)
    e = ((big >> 23) & 0xFF) - 126
    k = jnp.clip(e, 24 * _SCALE_LEVELS - 125 - _SCALE_LEVELS * bits, 100)
    total = jnp.zeros(a.shape[:-1] + (1,), jnp.float32)
    for _ in range(_SCALE_LEVELS):
        k = k + bits - 24  # this level's grid is 2**k
        part = jnp.floor(a * _pow2(-k)) * _pow2(k)
        a = a - part  # exact: the bits of a below the grid
        total = total + jnp.sum(part, axis=-1, keepdims=True)
    return total * np.float32(1 / m)


def _pow2(k: Array) -> Array:
    """``2.0 ** k`` in f32, exactly, for int32 ``k`` in [-126, 127]
    (``jnp.ldexp`` goes through a float power, which the TPU computes
    approximately)."""
    return jax.lax.bitcast_convert_type((k + 127) << 23, jnp.float32)


@activation_scale.defjvp
def _activation_scale_jvp(primals, tangents):
    (x,), (t,) = primals, tangents
    dx = jnp.sign(x).astype(jnp.float32) * t.astype(jnp.float32)
    return activation_scale(x), jnp.mean(dx, axis=-1, keepdims=True)


def binarize_ste_bits(x: Array) -> Array:
    """STE binarization straight to the {0,1} encoding."""
    return signs_to_bits(binarize_ste(x))


# ---------------------------------------------------------------------------
# Eq. 1: XNOR + Popcount
# ---------------------------------------------------------------------------


def xnor(a_bits: Array, w_bits: Array) -> Array:
    """Element-wise XNOR on {0,1} arrays (dtype-preserving, no bitwise ops
    so it also works on float carriers)."""
    return 1 - (a_bits + w_bits - 2 * a_bits * w_bits)


def popcount(bits: Array, axis: int = -1) -> Array:
    """Population count (number of set bits) along ``axis``."""
    return jnp.sum(bits, axis=axis)


def xnor_popcount(a_bits: Array, w_bits: Array) -> Array:
    """``popcount(xnor(a, w))`` along the last axis — the BNN MAC."""
    return popcount(xnor(a_bits, w_bits))


def binary_dot_eq1(a_bits: Array, w_bits: Array) -> Array:
    """Eq. 1: the ±1-domain dot product recovered from XNOR+popcount."""
    m = a_bits.shape[-1]
    return 2 * xnor_popcount(a_bits, w_bits) - m


def binary_matmul_signs(a_signs: Array, w_signs: Array) -> Array:
    """Reference ±1 binary matmul: ``a @ w`` for sign-valued arrays.

    ``a_signs``: (..., m), ``w_signs``: (m, n) -> (..., n).
    This is the ground truth every mapping/kernel must reproduce.
    """
    return jnp.matmul(a_signs, w_signs)


# ---------------------------------------------------------------------------
# The TacitMap algebraic core: complement concatenation
# ---------------------------------------------------------------------------


def concat_complement_input(a_bits: Array) -> Array:
    """TacitMap input prep: ``[a ; ā]`` along the last axis (length 2m)."""
    return jnp.concatenate([a_bits, 1 - a_bits], axis=-1)


def stack_complement_weights(w_bits: Array) -> Array:
    """TacitMap weight prep: ``[w ; w̄]`` stacked along the row axis.

    ``w_bits``: (m, n) -> (2m, n): weight column then its complement
    directly below it (Fig. 2-(b) of the paper).
    """
    return jnp.concatenate([w_bits, 1 - w_bits], axis=0)


def tacitmap_vmm(a_bits: Array, w_bits: Array) -> Array:
    """One-step XNOR+Popcount via a single VMM (the TacitMap identity).

    ``a_bits``: (..., m) in {0,1}; ``w_bits``: (m, n) in {0,1}.
    Returns popcount(XNOR) of shape (..., n), computed as
    ``[a ; ā] @ [w ; w̄]`` — exactly what the crossbar's analog MAC does.
    """
    return jnp.matmul(concat_complement_input(a_bits), stack_complement_weights(w_bits))


def tacitmap_binary_matmul(a_signs: Array, w_signs: Array) -> Array:
    """±1 binary matmul routed through the TacitMap VMM identity."""
    m = a_signs.shape[-1]
    pc = tacitmap_vmm(signs_to_bits(a_signs), signs_to_bits(w_signs))
    return 2 * pc - m
