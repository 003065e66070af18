"""The reduction from a profiler trace to busy time, kernel time and
idle gaps: on hand-made intervals, and on a small trace recorded on a
TPU v5e (``data/trace_small.json``, a few steps of the chat cell in
the plain form ``devtrace.from_xplane`` makes)."""

import json
from pathlib import Path

import pytest

import devtrace

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_and_keeps_gaps():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert devtrace.union([]) == []


def _trace():
    return {
        "window": [100, 200],
        "device": {"/device:TPU:0": [
            ["fusion.1", 90, 20, ""],                 # clipped to 100..110
            ["_fused_kernel", 120, 10, ""],
            ["custom-call.3", 125, 10, "pallas fused_bnn"],  # overlaps: union
            ["copy.2", 190, 30, ""],                  # clipped to 190..200
        ]},
        "host": [
            ["bench.window", 100, 100],
            ["bench.step", 105, 40],
            ["bench.wait", 150, 35],
        ],
    }


def test_reduce_counts_busy_union_kernel_time_and_names_gaps():
    out = devtrace.reduce(_trace(), {"bitlinear": ("_fused_kernel", "fused_bnn")})
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx((10 + 15 + 10) * 1e-9)
    assert out["kernel_s"]["bitlinear"] == pytest.approx(20e-9)
    # gaps: 110..120 (step), 135..190 (wait covers the middle, 162)
    assert out["idle_gaps"][0] == ["bench.wait", pytest.approx(55e-9)]
    assert out["idle_gaps"][1] == ["bench.step", pytest.approx(10e-9)]
    assert out["device_ops"][0][0] in ("fusion.1", "_fused_kernel", "custom-call.3", "copy.2")


def test_busy_is_averaged_over_chips():
    t = _trace()
    t["device"]["/device:TPU:1"] = []
    out = devtrace.reduce(t, {})
    assert out["busy_s"] == pytest.approx(35e-9 / 2)


def test_recorded_chip_trace():
    plain = json.loads((DATA / "trace_small.json").read_text())
    expect = plain.pop("expect")
    out = devtrace.reduce(plain, {"bitlinear": tuple(expect["kernel_needles"])})
    assert out["busy_s"] == pytest.approx(expect["busy_s"], abs=2e-9)
    assert out["kernel_s"]["bitlinear"] == pytest.approx(expect["kernel_s"], abs=2e-9)
    assert 0 < out["kernel_s"]["bitlinear"] < out["busy_s"] < out["window_s"]
    # the longest gap and its host event; gaps of equal length may swap
    assert out["idle_gaps"][0][0] == expect["gap_names"][0]
    host_names = {h[0] for h in plain["host"]}
    assert all(g[0] in host_names for g in out["idle_gaps"])
    assert sum(g[1] for g in out["idle_gaps"]) <= out["window_s"] - out["busy_s"] + 1e-9
