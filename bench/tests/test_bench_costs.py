"""Operations and bytes at both configurations' shapes, against counts
worked out by hand from the published sizes."""

import json
from pathlib import Path

import pytest

import costs
import reference

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def dims(name):
    return reference.Dims.from_config(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_qwen15_shapes_and_token_ops():
    d = dims("qwen1.5-0.5b")
    assert costs.bitlinear_shapes(d) == [
        (1024, 3072), (1024, 1024), (1024, 2816), (1024, 2816), (2816, 1024),
    ]
    per_layer = 2 * 1024 * (3072 + 1024 + 2816 * 3)
    assert costs.projection_ops_per_token(d) == 24 * per_layer
    assert costs.head_ops(d) == 2 * 1024 * 151936
    # 100 positions of context: scores and values, 16 heads of 64
    assert costs.attention_ops(d, 100) == 24 * 4 * 16 * 64 * 100
    assert costs.decode_ops(d, 100) == 24 * per_layer + 24 * 4 * 1024 * 100 + 2 * 1024 * 151936


def test_qwen2_72b_stage_shapes_and_prefill_ops():
    d = dims("qwen2-72b-l2")
    qkv = (64 + 2 * 8) * 128
    assert costs.bitlinear_shapes(d) == [
        (8192, qkv), (8192, 8192), (8192, 29568), (8192, 29568), (29568, 8192),
    ]
    params = 8192 * qkv + 8192 * 8192 + 3 * 8192 * 29568
    assert params == 877_658_112  # one layer's binarized weights
    p = 1024
    want = p * 2 * 2 * params + 2 * 4 * 64 * 128 * p * (p + 1) / 2 + 2 * 8192 * 19008
    assert costs.prefill_ops(d, p) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("rows,k,n", [(1, 1024, 3072), (16, 2816, 1024), (4096, 8192, 29568)])
def test_bitlinear_ops_and_bytes(rows, k, n):
    assert costs.bitlinear_ops(rows, k, n) == 2 * rows * k * n
    assert costs.bitlinear_bytes(rows, k, n) == k * n / 8 + 2 * rows * k + 2 * rows * n


def test_roofline_bound_switches_from_bytes_to_ops():
    d = dims("qwen2-72b-l2")
    peak = costs.peaks("TPU v5 lite")
    t_decode, bytes_share = costs.bitlinear_roofline(d, [16], peak)
    assert bytes_share == 1.0  # 16 rows: the 1-bit weights bound it
    layer_bits = sum(k * n for k, n in costs.bitlinear_shapes(d))
    assert t_decode == pytest.approx(
        2 * (layer_bits / 8 + 2 * 16 * sum(k + n for k, n in costs.bitlinear_shapes(d)))
        / 819e9
    )
    _, share = costs.bitlinear_roofline(d, [4096], peak)
    assert share == 0.0  # 4096 rows: the int8 peak bounds it


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        costs.peaks("TPU v9 imaginary")
