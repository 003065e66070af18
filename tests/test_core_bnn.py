"""Eq. 1 arithmetic + mapping identities: the algebraic heart of the paper."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bnn

import proptest as pt


def _rand_signs(rng, shape):
    return rng.choice(np.array([-1.0, 1.0], dtype=np.float32), size=shape)


class TestEncodings:
    def test_roundtrip(self):
        s = jnp.array([-1.0, 1.0, 1.0, -1.0])
        assert jnp.array_equal(bnn.bits_to_signs(bnn.signs_to_bits(s)), s)

    @pt.given(m=pt.integers(1, 300))
    def test_roundtrip_random(self, m):
        rng = np.random.default_rng(m)
        s = jnp.asarray(_rand_signs(rng, (m,)))
        assert jnp.array_equal(bnn.bits_to_signs(bnn.signs_to_bits(s)), s)


class TestEq1:
    """In (*) W = 2*Popcount(In' XNOR W') - VectorLength."""

    @pt.given(m=pt.integers(1, 513), n=pt.integers(1, 65), b=pt.integers(1, 5))
    def test_eq1_equals_pm1_dot(self, m, n, b):
        rng = np.random.default_rng(m * 1000 + n)
        a = jnp.asarray(_rand_signs(rng, (b, m)))
        w = jnp.asarray(_rand_signs(rng, (m, n)))
        ref = bnn.binary_matmul_signs(a, w)
        via_eq1 = 2 * bnn.xnor_popcount(
            bnn.signs_to_bits(a)[:, None, :], bnn.signs_to_bits(w).T[None, :, :]
        ) - m
        np.testing.assert_array_equal(np.asarray(via_eq1), np.asarray(ref))

    def test_xnor_truth_table(self):
        a = jnp.array([0, 0, 1, 1])
        w = jnp.array([0, 1, 0, 1])
        assert jnp.array_equal(bnn.xnor(a, w), jnp.array([1, 0, 0, 1]))

    def test_popcount(self):
        assert bnn.popcount(jnp.array([1, 0, 1, 1, 0])) == 3


class TestTacitMapIdentity:
    """[a ; ā] @ [w ; w̄] == popcount(XNOR(a, w)) — the 1-step claim."""

    @pt.given(m=pt.integers(1, 700), n=pt.integers(1, 40))
    def test_complement_vmm_is_xnor_popcount(self, m, n):
        rng = np.random.default_rng(m + n)
        a_bits = jnp.asarray(rng.integers(0, 2, size=(3, m)), jnp.float32)
        w_bits = jnp.asarray(rng.integers(0, 2, size=(m, n)), jnp.float32)
        vmm = bnn.tacitmap_vmm(a_bits, w_bits)
        direct = bnn.xnor_popcount(a_bits[:, None, :], w_bits.T[None, :, :])
        np.testing.assert_array_equal(np.asarray(vmm), np.asarray(direct))

    @pt.given(m=pt.integers(1, 700), n=pt.integers(1, 40))
    def test_tacitmap_binary_matmul(self, m, n):
        rng = np.random.default_rng(m * 7 + n)
        a = jnp.asarray(_rand_signs(rng, (2, m)))
        w = jnp.asarray(_rand_signs(rng, (m, n)))
        np.testing.assert_array_equal(
            np.asarray(bnn.tacitmap_binary_matmul(a, w)),
            np.asarray(bnn.binary_matmul_signs(a, w)),
        )


class TestSTE:
    def test_forward_is_sign(self):
        x = jnp.array([-2.0, -0.3, 0.0, 0.7, 3.0])
        assert jnp.array_equal(bnn.binarize_ste(x), jnp.array([-1.0, -1.0, 1.0, 1.0, 1.0]))

    def test_gradient_is_clipped_identity(self):
        g = jax.grad(lambda x: bnn.binarize_ste(x).sum())(jnp.array([-2.0, -0.5, 0.5, 2.0]))
        np.testing.assert_allclose(np.asarray(g), [0.0, 1.0, 1.0, 0.0])

    def test_training_signal_flows(self):
        # a tiny STE regression must reduce loss
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (8, 4)) * 0.5
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
        target = jnp.sign(x @ jnp.sign(jax.random.normal(jax.random.PRNGKey(2), (8, 4))))

        def loss(w):
            return jnp.mean((bnn.binary_matmul_signs(bnn.binarize_ste(x), bnn.binarize_ste(w)) / 8.0 - target) ** 2)

        l0 = loss(w)
        for _ in range(60):
            w = w - 0.1 * jax.grad(loss)(w)
        assert loss(w) < l0


class TestActivationScale:
    """beta = mean|x| with exact partial sums: the same bits whatever
    order or shape the reduction takes."""

    @pytest.mark.parametrize("m", [1, 64, 1024, 2816])
    def test_order_and_shape_free(self, m):
        rng = np.random.default_rng(m)
        x = rng.standard_normal((16, m)) * np.exp(rng.uniform(-8, 8, (16, m)))
        x = jnp.asarray(x, jnp.bfloat16)
        beta = np.asarray(bnn.activation_scale(x))
        perm = rng.permutation(m)
        np.testing.assert_array_equal(np.asarray(bnn.activation_scale(x[:, perm])), beta)
        np.testing.assert_array_equal(
            np.asarray(bnn.activation_scale(x[None]))[0], beta
        )
        np.testing.assert_array_equal(
            np.asarray(jax.jit(bnn.activation_scale)(x[::-1]))[::-1], beta
        )
        exact = np.abs(np.asarray(x, np.float64)).mean(axis=-1, keepdims=True)
        np.testing.assert_allclose(beta, exact, rtol=3e-7)

    def test_zero_rows_and_tiny_values(self):
        x = jnp.asarray([[0.0] * 8, [1e-30] * 8, [3.0] + [0.0] * 7], jnp.float32)
        np.testing.assert_allclose(
            np.asarray(bnn.activation_scale(x))[:, 0], [0.0, 1e-30, 0.375], rtol=1e-6
        )

    def test_gradient_is_that_of_the_mean(self):
        x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 96)), jnp.float32)
        f = lambda v: jnp.sum(bnn.activation_scale(v) * jnp.arange(1.0, 5.0)[:, None])
        g = lambda v: jnp.sum(jnp.mean(jnp.abs(v), -1, keepdims=True) * jnp.arange(1.0, 5.0)[:, None])
        np.testing.assert_allclose(np.asarray(jax.grad(f)(x)), np.asarray(jax.grad(g)(x)), rtol=1e-6)
