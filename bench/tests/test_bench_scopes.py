"""Device time by program scope (``scopes.by_scope``) on a hand-made
trace whose device ops carry their op_name, and the new metrics' readers
on runs that hold nothing for them to read."""

import importlib.util
import types
from pathlib import Path

import pytest

import scopes

BENCH = Path(__file__).resolve().parents[1]


def _trace():
    d = "jit(_decode)/decode"
    return {
        "window": [0, 1000],
        "device": {"/device:TPU:0": [
            ["fusion.1", 0, 100, "", f"{d}/pool.gather/gather"],
            ["while.3", 100, 460, "", f"{d}/layers/while"],
            ["copy.5", 100, 50, "", f"{d}/layers/while/body/dynamic_slice"],
            ["fusion.7", 150, 100, "", f"{d}/layers/while/body/closed_call/attn.core/dot_general"],
            ["custom-call.2", 250, 150, "", f"{d}/layers/while/body/closed_call/ffn/bitlinear/pallas_call"],
            ["fusion.8", 400, 100, "", f"{d}/layers/while/body/closed_call/attn.kv_write/scatter"],
            ["copy.6", 560, 40, "", ""],            # a relayout for the scatter
            ["fusion.10", 600, 50, "", f"{d}/pool.scatter/scatter"],
            ["fusion.9", 650, 50, "", f"{d}/head/bd,dv->bv/dot_general"],
            ["copy.11", 700, 40, "", ""],           # followed by an eager op
            ["fusion.12", 760, 40, "", "jit(_where)/select_n"],
            ["fusion.13", 800, 100, "", "jit(prefill)/prefill/layers/while/body/attn.core/exp"],
            ["fusion.14", 950, 100, "", f"{d}/embed/gather"],  # clipped to 50
        ]},
        "host": [
            ["bench.window", 0, 1000],
            ["bench.step", 0, 600],
            ["decode_tick", 0, 720],
            ["decode.read", 705, 50],
            ["sched.admit", 755, 200],
            ["decode_tick", 990, 200],
            ["decode_tick", 1200, 100],   # starts after the window
        ],
        "modules": {"/device:TPU:0": [   # the program runs
            ["jit__decode(1)", 0, 740],
            ["jit__where(2)", 760, 40],
            ["jit_prefill(3)", 800, 100],
            ["jit__decode(1)", 950, 100],
        ]},
    }


def test_scope_of_takes_the_innermost_scope_under_the_root():
    assert scopes.scope_of("jit(f)/decode/layers/while/body/attn.o/bitlinear/xor") == (
        "decode", "bitlinear")
    assert scopes.scope_of("jit(f)/decode/layers/while/body/dynamic_slice") == (
        "decode", "layers.carry")
    assert scopes.scope_of("jit(f)/prefill/iota") == ("prefill", "prefill")
    assert scopes.scope_of("jit(_where)/select_n") is None
    assert scopes.scope_of("") is None


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _msg(*fields):
    """A protobuf message from (field, value) pairs: ints as varints,
    bytes and str as length-delimited fields."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def test_op_names_read_the_event_metadata_of_device_planes(tmp_path):
    """On a v5e the op_name of an ``XLA Ops`` event sits in its event
    metadata as stat ``tf_op`` (a string, or a reference to an interned
    one), keyed by the op's HLO text."""
    stat_names = [(5, _msg((1, k), (2, _msg((1, k), (2, name)))))
                  for k, name in ((1, "tf_op"), (2, "program_id"), (3, "jit(g)/decode/embed/gather"))]
    events = [
        (7, "%fusion.3 = f32[2] fusion(%p)", _msg((1, 1), (5, "jit(f)/decode/head/mul"))),
        (8, "%copy.1 = f32[2] copy(%p)", _msg((1, 2), (3, 99))),       # no op_name
        (9, "%gather.2 = f32[2] gather(%p)", _msg((1, 1), (7, 3))),     # interned
    ]
    event_meta = [(4, _msg((1, k), (2, _msg((1, k), (2, text), (5, stat)))))
                  for k, text, stat in events]
    device = _msg((1, 1), (2, "/device:TPU:0"), *event_meta, *stat_names)
    host = _msg((1, 2), (2, "/host:CPU"), event_meta[0], *stat_names)
    (tmp_path / "t.xplane.pb").write_bytes(_msg((1, host), (1, device)))
    assert scopes.op_names(str(tmp_path)) == {
        "%fusion.3 = f32[2] fusion(%p)": "jit(f)/decode/head/mul",
        "%gather.2 = f32[2] gather(%p)": "jit(g)/decode/embed/gather",
    }


def test_by_scope_sums_per_scope_and_keeps_the_remainder():
    out = scopes.by_scope(_trace())
    ns = 1e-9
    assert out["scope_s"] == pytest.approx({
        "decode/pool.gather": 100 * ns,
        "decode/layers.carry": 50 * ns,
        "decode/attn.core": 100 * ns,
        "decode/bitlinear": 150 * ns,
        "decode/attn.kv_write": 100 * ns,
        "decode/pool.scatter": (40 + 50) * ns,   # the copy goes with its consumer
        "decode/head": 50 * ns,
        "prefill/attn.core": 100 * ns,
        "decode/embed": 50 * ns,
    })
    # busy: 0..740, 760..900, 950..1000
    assert out["busy_s"] == pytest.approx(930 * ns)
    # the eager op, the copy before it, and the loop's 60 ns outside its body's ops
    assert out["unscoped_s"] == pytest.approx((40 + 40 + 60) * ns)
    assert sum(out["scope_s"].values()) + out["unscoped_s"] == pytest.approx(out["busy_s"])
    assert [op for op, _ in out["unscoped_ops"]] == ["copy.11", "fusion.12"]
    assert out["inferred_s"] == pytest.approx({"decode/pool.scatter": 40 * ns})
    assert out["inferred_ops"] == [["copy.6", "decode/pool.scatter", pytest.approx(40 * ns)]]
    assert out["ticks"] == 2
    assert scopes.shares(out) == pytest.approx({"unscoped": 140 / 930, "unnamed": 180 / 930})


def test_an_unnamed_op_never_takes_the_scope_of_another_program_run():
    """The tail copy of one decode stays unscoped although the next op,
    in the next decode run, carries a scope."""
    t = _trace()
    t["device"]["/device:TPU:0"][10] = ["fusion.12", 760, 40, "", "jit(_decode)/decode/pool.gather/gather"]
    t["modules"]["/device:TPU:0"][1] = ["jit__decode(1)", 760, 40]
    out = scopes.by_scope(t)
    assert out["scope_s"]["decode/pool.gather"] == pytest.approx(140e-9)
    assert [op for op, _ in out["unscoped_ops"]] == ["copy.11"]
    assert out["inferred_s"] == pytest.approx({"decode/pool.scatter": 40e-9})


def test_without_program_runs_unnamed_ops_stay_unscoped():
    t = _trace()
    del t["modules"]
    out = scopes.by_scope(t)
    assert out["inferred_s"] == {} and out["inferred_ops"] == []
    assert "decode/pool.scatter" in out["scope_s"]
    assert out["scope_s"]["decode/pool.scatter"] == pytest.approx(50e-9)
    assert [op for op, _ in out["unscoped_ops"]] == ["copy.11", "copy.6", "fusion.12"]
    assert scopes.shares(out)["unscoped"] == pytest.approx(180 / 930)


def test_shares_of_an_empty_stretch():
    assert scopes.shares({"busy_s": 0.0, "unscoped_s": 0.0, "inferred_s": {}}) == {
        "unscoped": None, "unnamed": None}


def test_idle_spans_name_the_program_span_over_each_gap():
    # gaps: 740..760 (middle 750: decode.read), 900..950 (sched.admit)
    assert scopes.idle_spans(_trace()) == [
        ["sched.admit", pytest.approx(50e-9)], ["decode.read", pytest.approx(20e-9)],
    ]


def test_readers_on_a_profiled_stretch():
    run = types.SimpleNamespace(scopes=scopes.by_scope(_trace()))
    assert scopes.pool_copy_ms_per_tick(run) == pytest.approx(190e-9 / 2 * 1e3)
    assert scopes.layer_carry_ms_per_tick(run) == pytest.approx(50e-9 / 2 * 1e3)
    assert scopes.decode_attention_ms_per_tick(run) == pytest.approx(200e-9 / 2 * 1e3)
    assert scopes.head_ms_per_tick(run) == pytest.approx(50e-9 / 2 * 1e3)
    assert scopes.prefill_attention_share(run) == pytest.approx(100 * 100 / 930)


def _no_ticks():
    t = _trace()
    t["host"] = [h for h in t["host"] if h[0] != "decode_tick"]
    return scopes.by_scope(t)


def _no_scopes():
    t = _trace()
    t["device"] = {p: [ev[:4] for ev in evs] for p, evs in t["device"].items()}
    return scopes.by_scope(t)


@pytest.mark.parametrize("name", sorted(scopes.READERS))
@pytest.mark.parametrize("run", [
    types.SimpleNamespace(),                       # the harness hands no scopes
    types.SimpleNamespace(scopes=None),            # an untraced run
    types.SimpleNamespace(scopes=_no_scopes()),    # a program without scopes
], ids=["absent", "none", "unscoped"])
def test_readers_find_nothing_to_read(name, run):
    assert scopes.READERS[name](run) is None


def test_per_tick_readers_need_ticks():
    run = types.SimpleNamespace(scopes=_no_ticks())
    assert scopes.pool_copy_ms_per_tick(run) is None
    assert scopes.prefill_attention_share(run) is not None


def _gathered_share():
    path = BENCH / "metrics" / "gathered_tick_share.serve.py"
    spec = importlib.util.spec_from_file_location("gathered_tick_share", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_gathered_tick_share_reads_the_counters():
    read = _gathered_share()
    s = types.SimpleNamespace
    run = s(stats0=s(ticks=10, gathered_ticks=4), stats1=s(ticks=30, gathered_ticks=19))
    assert read(run) == pytest.approx(75.0)


@pytest.mark.parametrize("stats", [
    # a program without the counter, and a window without ticks
    (types.SimpleNamespace(ticks=3), types.SimpleNamespace(ticks=9)),
    (types.SimpleNamespace(ticks=3, gathered_ticks=1), types.SimpleNamespace(ticks=3, gathered_ticks=1)),
], ids=["no_counter", "no_ticks"])
def test_gathered_tick_share_finds_nothing_to_read(stats):
    run = types.SimpleNamespace(stats0=stats[0], stats1=stats[1])
    assert _gathered_share()(run) is None
