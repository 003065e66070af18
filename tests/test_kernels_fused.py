"""Fused decode-tick kernel (PR 6) vs the unfused BitLinear chain.

The contract under test: ``ops.fused_bnn_matmul`` — binarize + bit-pack
+ XNOR + popcount + Eq. 1 affine + α/β rescale in ONE ``pallas_call`` —
is bit-exact against the ``models.layers.dense`` reference math for any
operand shape (ragged m, B=1, stacked G·K group leading dims), through
the engine surface (``fused_dense``, GroupedEngine pass-through, the
``prepad`` programming layout), the shared-activation QKV fusion, and
the donated-cache decode step. The unfused path stays selectable
(``fused=False``) as the benchmark baseline.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import bnn
from repro.core import engine as engine_lib
from repro.kernels import ops
from repro.kernels.fused_decode import fused_bnn_matmul_kernel
from repro.models import layers, lm as lm_lib

import proptest as pt


def _reference(x, w, alpha):
    """layers.dense BNN math, no kernels: the bit-exactness oracle."""
    beta = bnn.activation_scale(x)
    xb = bnn.binarize_ste(x.astype(jnp.float32))
    dot = jnp.einsum("...k,kn->...n", xb, bnn.binarize_ste(w))
    return dot.astype(jnp.float32) * (alpha * beta)


def _operands(lead, m, n, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(*lead, m)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    alpha = jnp.asarray(rng.uniform(0.5, 2.0, size=(n,)), jnp.float32)
    return x, w, alpha


class TestFusedBnnMatmul:
    @pt.given(b=pt.integers(1, 16), m=pt.integers(1, 300), n=pt.integers(1, 64))
    def test_property_sweep_bit_exact(self, b, m, n):
        """Ragged everything incl. non-multiple-of-32 m and B=1."""
        x, w, alpha = _operands((b,), m, n, b + m * 13 + n)
        wp = ops.pack_weights(bnn.binarize_ste(w))
        got = ops.fused_bnn_matmul(x, wp, alpha, m=m, n=n, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(_reference(x, w, alpha))
        )

    @pytest.mark.parametrize(
        "lead",
        [(1,), (4, 2), (2, 3, 5)],  # B=1 / (G, K) / (G, K, b) stacks
    )
    def test_grouped_leading_dims_one_launch(self, lead):
        """The serving engine's stacked (G, K, m) groups flatten into
        one launch and match the per-row reference exactly."""
        x, w, alpha = _operands(lead, 100, 48, sum(lead))
        wp = ops.pack_weights(bnn.binarize_ste(w))
        got = ops.fused_bnn_matmul(x, wp, alpha, m=100, n=48, interpret=True)
        assert got.shape == (*lead, 48)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(_reference(x, w, alpha))
        )

    def test_scalar_alpha(self):
        x, w, _ = _operands((3,), 70, 20, 7)
        wp = ops.pack_weights(bnn.binarize_ste(w))
        alpha = jnp.float32(0.37)
        got = ops.fused_bnn_matmul(x, wp, alpha, m=70, n=20, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(_reference(x, w, alpha))
        )

    def test_zero_activations_binarize_to_plus_one(self):
        """binarize_ste maps 0 -> +1; the in-kernel ``x >= 0`` must
        agree (and beta = 0 zeroes the row either way only via scale)."""
        x = jnp.zeros((2, 64), jnp.bfloat16).at[0, :5].set(1.0)
        _, w, alpha = _operands((2,), 64, 24, 11)
        wp = ops.pack_weights(bnn.binarize_ste(w))
        got = ops.fused_bnn_matmul(x, wp, alpha, m=64, n=24, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(_reference(x, w, alpha))
        )

    def test_blocked_grid_path_bit_exact(self):
        """Force the compiled-style multi-block grid (interpret=True but
        explicit small blocks) — same results as the single-step grid."""
        x, w, alpha = _operands((9,), 520, 130, 21)
        wp = ops.pack_weights(bnn.binarize_ste(w))
        ref = _reference(x, w, alpha)
        beta = bnn.activation_scale(x)
        x2 = jnp.pad(
            x.astype(jnp.float32), [(0, 7), (0, 17 * 32 - 520)],
            constant_values=-1.0,
        )
        got = fused_bnn_matmul_kernel(
            jnp.pad(x2, [(0, 0), (0, 32)], constant_values=-1.0)[:16, :18 * 32],
            ops.pad_packed_weights(wp, bkw=6, bn=64)[:18],
            jnp.pad(alpha.reshape(1, -1), [(0, 0), (0, 62)]),
            jnp.pad(beta, [(0, 7), (0, 0)]),
            m=520, bm=8, bn=64, bkw=6, interpret=True,
        )
        np.testing.assert_array_equal(
            np.asarray(got[:9, :130]), np.asarray(ref)
        )

    def test_word_count_mismatch_named_error(self):
        x = jnp.zeros((8, 64), jnp.float32)
        w = jnp.zeros((4, 128), jnp.int32)
        alpha = jnp.zeros((1, 128), jnp.float32)
        beta = jnp.zeros((8, 1), jnp.float32)
        with pytest.raises(ValueError, match="words"):
            fused_bnn_matmul_kernel(
                x, w, alpha, beta, m=64, bm=8, bn=128, bkw=4, interpret=True
            )

    def test_block_divisibility_named_error(self):
        x = jnp.zeros((8, 128), jnp.float32)
        w = jnp.zeros((4, 100), jnp.int32)
        alpha = jnp.zeros((1, 100), jnp.float32)
        beta = jnp.zeros((8, 1), jnp.float32)
        with pytest.raises(ValueError, match="pre-padded to block multiples"):
            fused_bnn_matmul_kernel(
                x, w, alpha, beta, m=128, bm=8, bn=64, bkw=4, interpret=True
            )

    def test_short_weights_named_error(self):
        x = jnp.zeros((2, 128), jnp.bfloat16)
        wp = jnp.zeros((2, 16), jnp.int32)  # 2 words < ceil(128/32)
        with pytest.raises(ValueError, match="carry 2 words"):
            ops.fused_bnn_matmul(x, wp, 1.0, m=128, n=16, interpret=True)


class TestPrepadLayout:
    @pytest.mark.parametrize("m,n", [(64, 96), (100, 40), (512, 768)])
    def test_prepad_round_trip_bit_identical(self, m, n):
        """prepad=True programs block-aligned words; fused AND unfused
        execution match the unpadded artifact exactly."""
        x, w, alpha = _operands((5,), m, n, m + n)
        ws = bnn.binarize_ste(w)
        xb = bnn.binarize_ste(x.astype(jnp.float32))
        outs = {}
        for prepad in (False, True):
            eng = engine_lib.PackedEngine(interpret=True, prepad=prepad)
            pw = eng.prepare(ws)
            outs[prepad] = (
                np.asarray(eng.fused_dense(x, pw, alpha)),
                np.asarray(eng.binary_vmm(xb, pw)),
            )
        np.testing.assert_array_equal(outs[False][0], outs[True][0])
        np.testing.assert_array_equal(outs[False][1], outs[True][1])

    def test_prepad_emits_block_aligned_words(self):
        eng = engine_lib.PackedEngine(interpret=True, prepad=True)
        pw = eng.prepare(bnn.binarize_ste(jnp.ones((100, 40))))
        kw, n = pw.data.shape
        # 4 words -> one 8-aligned contraction block (ops._word_block)
        assert kw == 8 and n % 128 == 0
        assert (pw.m, pw.n) == (100, 40)  # logical dims preserved

    def test_with_spec_preserves_flags(self):
        from repro.core.crossbar import CrossbarSpec

        eng = engine_lib.PackedEngine(interpret=True, fused=False, prepad=True)
        clone = eng.with_spec(CrossbarSpec(rows=64, cols=64))
        assert clone.fused is False and clone.prepad is True


class TestEngineSurface:
    def test_unfused_flag_disables_capability(self):
        assert engine_lib.PackedEngine(fused=True).supports_fused_dense
        assert not engine_lib.PackedEngine(fused=False).supports_fused_dense

    def test_grouped_engine_delegates(self):
        base = engine_lib.PackedEngine(interpret=True)
        grouped = engine_lib.GroupedEngine(base, 2)
        assert grouped.supports_fused_dense == base.supports_fused_dense
        x, w, alpha = _operands((4,), 96, 32, 5)
        pw = base.prepare(bnn.binarize_ste(w))
        np.testing.assert_array_equal(
            np.asarray(grouped.fused_dense(x, pw, alpha)),
            np.asarray(base.fused_dense(x, pw, alpha)),
        )

    def test_non_fused_engines_lack_capability(self):
        for name in engine_lib.list_engines():
            eng = engine_lib.get_engine(name)
            if not isinstance(eng, engine_lib.PackedEngine):
                assert not getattr(eng, "supports_fused_dense", False)


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(get_smoke_config("tinyllama-1.1b"), quant="bnn")
    params = lm_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


class TestQkvFusion:
    def _programmed_attn(self, cfg, params, engine):
        programmed, _ = lm_lib.program_weights(params, cfg, engine)
        attn = programmed["blocks"]["slot0"]["attn"]
        # slice repeat 0 off every stacked artifact, as the layer scan does
        return jax.tree.map(lambda a: a[0], attn)

    def test_artifact_attached_for_fused_engines_only(self, model):
        cfg, params = model
        fused_attn = self._programmed_attn(
            cfg, params, engine_lib.PackedEngine(interpret=True)
        )
        assert "qkv" in fused_attn
        unfused_attn = self._programmed_attn(
            cfg, params, engine_lib.PackedEngine(interpret=True, fused=False)
        )
        assert "qkv" not in unfused_attn

    def test_concat_split_matches_three_dense_calls(self, model):
        """One launch over [q|k|v] splits bit-identically to three
        separate projections (packing is column-independent)."""
        cfg, params = model
        eng = engine_lib.PackedEngine(interpret=True)
        attn = self._programmed_attn(cfg, params, eng)
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(2, 3, cfg.d_model)), jnp.bfloat16)
        fused = layers.fused_qkv_dense(attn, x, cfg, "bnn", eng)
        assert fused is not None
        unfused_eng = engine_lib.PackedEngine(interpret=True, fused=False)
        for got, name in zip(fused, ("q", "k", "v")):
            want = layers.dense(attn[name], x, "bnn", engine=unfused_eng)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_returns_none_without_capability(self, model):
        cfg, params = model
        eng = engine_lib.PackedEngine(interpret=True)
        attn = self._programmed_attn(cfg, params, eng)
        unfused = engine_lib.PackedEngine(interpret=True, fused=False)
        assert layers.fused_qkv_dense(attn, jnp.zeros((1, cfg.d_model)),
                                      cfg, "bnn", unfused) is None
        assert layers.fused_qkv_dense(attn, jnp.zeros((1, cfg.d_model)),
                                      cfg, "none", eng) is None


class TestTargetAndDonation:
    def test_fused_false_requires_packed(self):
        from repro.compiler import HardwareTarget
        from repro.compiler.target import TargetError

        HardwareTarget(engine="packed", fused=False).validate()  # baseline knob
        with pytest.raises(TargetError, match="fused=False"):
            HardwareTarget(engine="wdm", fused=False).validate()

    def test_describe_reports_fused_knob(self):
        from repro.compiler import HardwareTarget

        assert "fused=False" in HardwareTarget(
            engine="packed", fused=False
        ).describe()

    def test_decode_step_donates_cache_buffers(self, model):
        """The KV-cache pytree is donated: tick N's caches update in
        place of tick N-1's buffers instead of doubling resident size."""
        from repro import compiler as compiler_lib
        from repro.compiler import HardwareTarget

        cfg, params = model
        cm = compiler_lib.compile(cfg, params, HardwareTarget(engine="packed"))
        tokens = jnp.asarray(np.arange(1, 6, dtype=np.int32))[None, :]
        logits, pre = cm.prefill(tokens)
        caches = cm.init_cache(1, 12)

        def graft(dst, src):
            if dst.ndim == 5 and dst.shape[2] >= src.shape[2]:
                return dst.at[:, :, : src.shape[2]].set(src.astype(dst.dtype))
            return src.astype(dst.dtype)

        caches = jax.tree.map(graft, caches, pre)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        old_leaf = jax.tree.leaves(caches)[0]
        _, caches = cm.decode_step(tok, jnp.asarray(5, jnp.int32), caches)
        assert old_leaf.is_deleted()
        # and the decode loop still runs on the donated-output caches
        _, caches = cm.decode_step(tok, jnp.asarray(6, jnp.int32), caches)
