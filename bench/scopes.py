"""Device time by program scope, and the metrics that read it.

The serving programs name their parts with ``jax.named_scope``, which
only sets each HLO op's ``op_name`` metadata: a root scope per dispatch
(``decode`` for both decode dispatches, ``prefill`` for the prompt and
its continuation), and inside it ``embed``, ``layers``, ``head``, the
slot pool's ``pool.gather`` and ``pool.scatter``, and in every layer
``norm``, ``attn.qkv``, ``attn.kv_write``, ``attn.core``, ``attn.o``,
``ffn`` and, innermost, ``bitlinear``.

``by_scope`` puts each device op of a profiled stretch down to the
innermost scope in its name stack and sums the time per (root, scope).
An op under ``layers`` but under no scope inside it is the layer scan's
own slicing and stacking of the cache: ``layers.carry``. A fusion takes
the op_name of its root instruction, so its whole time goes to the scope
of that one instruction. An op the compiler made without any op_name (a
relayout copy, a chunk of an expanded gather) goes with the next op of
the same program run (the device's ``XLA Modules`` line) that has one:
XLA schedules such an op right before its consumer. That guess is kept
apart as ``inferred_s``; an unnamed op with no named op after it in its
run stays unscoped. What no root covers (eager ops such as the slot
graft and the argmax read), and a loop's time outside its body's ops, is
``unscoped_s``: ``sum(scope_s) + unscoped_s == busy_s``.

Run one traced run of a cell and print its scope sums and metrics:

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

Delete ``main`` once the harness hands ``by_scope`` of its trace to each
run as ``Run.scopes`` (PERF.md, section 7): the readers below then serve
as the metrics' own. It exits 1 when no op of the stretch carries a
scope or when scopes leave more than ``MAX_UNSCOPED`` of busy time.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import sys
import time
from collections import defaultdict
from pathlib import Path

import devtrace

HERE = Path(__file__).resolve().parent
ROOTS = ("decode", "prefill")
SCOPES = (
    "embed", "layers", "head", "pool.gather", "pool.scatter", "norm",
    "attn.qkv", "attn.kv_write", "attn.core", "attn.o", "ffn", "bitlinear",
)
CARRY = "layers.carry"
TICK = "decode_tick"   # the program's span around one decode tick
OP_NAME_STATS = ("tf_op",)   # the stat a device op's metadata keeps its op_name in
MODULES_LINE = "XLA Modules"  # a device line with one event per program run
MAX_UNSCOPED = 0.10           # the largest share of busy time no scope may hold
TOP_OPS, TOP_SPANS = 10, 5    # the longest unscoped/inferred ops and idle spans listed


def scope_of(stack: str) -> tuple[str, str] | None:
    """(root, innermost scope) of an op_name such as
    ``jit(_decode)/decode/layers/while/body/attn.core/dot_general``;
    ``(root, root)`` for an op under a root and no scope, ``None`` for an
    op under no root."""
    parts = stack.split("/")
    root = next((i for i, p in enumerate(parts) if p in ROOTS), None)
    if root is None:
        return None
    inner = [p for p in parts[root + 1:] if p in SCOPES]
    scope = inner[-1] if inner else parts[root]
    return parts[root], CARRY if scope == "layers" else scope


def _opcode(text: str) -> tuple[str, str]:
    """(instruction, opcode) of an op event named by its HLO text or, as
    on a v5e, by its instruction name alone (``while.3`` -> "while")."""
    name, opcode = devtrace.op_name(text)
    if " = " not in text:
        head, _, tail = name.rpartition(".")
        opcode = head if head and tail.isdigit() else name
    return name, opcode


def op_names(directory: str) -> dict[str, str]:
    """HLO op text -> op_name of the device ops in the newest trace under
    ``directory``. On a v5e an ``XLA Ops`` event is named by its HLO text
    and keeps the op_name (stat ``tf_op``) on its event metadata, which
    ``jax.profiler.ProfileData`` does not expose: this reads the XSpace
    protobuf itself (planes ``/device:*``: field 4 event metadata, 5 stat
    metadata; a stat's value is a string (5) or a reference (7) to a
    stat-metadata name)."""
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    out: dict[str, str] = {}
    for _, plane in _fields(Path(paths[-1]).read_bytes(), want=1):
        events, names = [], {}
        is_device = False
        for f, v in _fields(plane):
            if f == 2:
                is_device = v.decode(errors="replace").startswith("/device:")
            elif f == 4:
                events.append(_map_value(v))
            elif f == 5:
                meta = dict(_fields(_map_value(v)))
                names[_int(meta.get(1, 0))] = meta.get(2, b"").decode(errors="replace")
        if not is_device:
            continue
        key = next((k for k, n in names.items() if n in OP_NAME_STATS), None)
        for event in events:
            meta = list(_fields(event))
            text = next((v.decode(errors="replace") for f, v in meta if f == 2), "")
            for stat in (dict(_fields(v)) for f, v in meta if f == 5):
                if _int(stat.get(1, 0)) == key:
                    value = stat[5].decode() if 5 in stat else names.get(_int(stat.get(7, 0)), "")
                    out.setdefault(text, value)
    return out


def _fields(buf: bytes, want: int | None = None):
    """(field number, value) of a protobuf message: varints as ints,
    length-delimited fields as bytes (fixed-width ones skipped)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:
            i += 8 if wire == 1 else 4
            continue
        if want is None or field == want:
            yield field, value


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _int(value) -> int:
    return value if isinstance(value, int) else 0


def _map_value(entry: bytes) -> bytes:
    """The value (field 2) of a protobuf map entry."""
    return next((v for f, v in _fields(entry) if f == 2), b"")


def with_stacks(trace: dict, directory: str) -> dict:
    """``trace`` (``devtrace.from_xplane`` of ``directory``) with each
    device event's op_name appended as a fifth field ("" for none), and
    each device plane's program runs as ``modules``: ``{plane: [[name,
    start_ns, dur_ns], ...]}``."""
    import jax

    names = op_names(directory)
    device = {
        plane: [list(ev[:4]) + [names.get(ev[0], "")] for ev in events]
        for plane, events in trace["device"].items()
    }
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    modules = {
        plane.name: [[e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events]
        for plane in jax.profiler.ProfileData.from_file(paths[-1]).planes
        if plane.name in device
        for line in plane.lines if line.name == MODULES_LINE
    }
    return dict(trace, device=device, modules=modules)


def _run_of(runs: list[tuple[int, int]], t: int) -> int | None:
    """Index of the program run (sorted ``(start, end)``) that holds time
    ``t``, or ``None``."""
    i = bisect.bisect_right(runs, (t, float("inf"))) - 1
    return i if i >= 0 and t < runs[i][1] else None


def by_scope(trace: dict) -> dict:
    """Device seconds of the window per ``"root/scope"``, averaged over the
    device planes like ``devtrace.reduce``; device events carry their
    op_name as a fifth field. ``inferred_s`` is the part of each scope's
    time that ops without an op_name bring (the next named op of their
    program run, ``trace["modules"]``), ``inferred_ops`` the longest of
    them; ``unscoped_s`` is busy time that no scope holds,
    ``unscoped_ops`` its longest ops, and ``ticks`` the program's
    ``decode_tick`` spans that start in the window (host plane)."""
    lo, hi = trace["window"]
    planes = trace["device"]
    scoped: dict[str, int] = defaultdict(int)
    inferred: dict[str, int] = defaultdict(int)
    inferred_ops: dict[tuple[str, str], int] = defaultdict(int)
    loose: dict[str, int] = defaultdict(int)
    busy = 0
    for plane, events in planes.items():
        runs = sorted((s, s + d) for _, s, d in trace.get("modules", {}).get(plane, ()))
        ops = []
        for ev, s, e in sorted(devtrace._clip(events, lo, hi), key=lambda c: c[1]):
            name, opcode = _opcode(ev[0])
            if not opcode.endswith("-start"):
                own = ev[4] if len(ev) > 4 else ""
                ops.append((name, opcode, s, e, own, _run_of(runs, ev[1])))
        busy += sum(e - s for s, e in devtrace.union([op[2:4] for op in ops]))
        stack, stack_run = "", None   # the next op_name of the stream, and its run
        for name, opcode, s, e, own, run in reversed(ops):
            if own:
                stack, stack_run = own, run
            if opcode in devtrace.CONTAINERS:
                continue
            guess = not own and run is not None and run == stack_run
            key = scope_of(own or (stack if guess else ""))
            if key is None:
                loose[name] += e - s
                continue
            scope = "/".join(key)
            scoped[scope] += e - s
            if guess:
                inferred[scope] += e - s
                inferred_ops[(name, scope)] += e - s
    n = max(1, len(planes))
    scope_s = {k: v * 1e-9 / n for k, v in sorted(scoped.items())}
    busy_s = busy * 1e-9 / n

    def longest(ns: dict) -> list:
        return sorted(ns.items(), key=lambda kv: (-kv[1], kv[0]))[:TOP_OPS]

    return {
        "busy_s": busy_s,
        "scope_s": scope_s,
        "inferred_s": {k: v * 1e-9 / n for k, v in sorted(inferred.items())},
        "unscoped_s": busy_s - sum(scope_s.values()),
        "inferred_ops": [[op, scope, v * 1e-9 / n] for (op, scope), v in longest(inferred_ops)],
        "unscoped_ops": [[k, v * 1e-9 / n] for k, v in longest(loose)],
        "ticks": sum(1 for name, s, _ in trace["host"] if name == TICK and lo <= s < hi),
    }


def shares(summary: dict) -> dict:
    """The shares of busy time that no scope holds: ``unscoped`` after
    the unnamed ops went with their next named op, ``unnamed`` before
    (what the op_names alone leave)."""
    busy = summary["busy_s"]
    if busy <= 0:
        return {"unscoped": None, "unnamed": None}
    unnamed = summary["unscoped_s"] + sum(summary["inferred_s"].values())
    return {"unscoped": summary["unscoped_s"] / busy, "unnamed": unnamed / busy}


def idle_spans(trace: dict) -> list:
    """The host events that cover the most device-idle time of the window:
    each idle gap goes to the innermost host event covering its middle,
    as ``devtrace.reduce`` names gaps, summed per name."""
    lo, hi = trace["window"]
    host = sorted(trace["host"], key=lambda ev: ev[1])
    idle: dict[str, int] = defaultdict(int)
    planes = trace["device"]
    for events in planes.values():
        spans = [(s, e) for ev, s, e in devtrace._clip(events, lo, hi)
                 if not _opcode(ev[0])[1].endswith("-start")]
        edges = [lo] + [x for iv in devtrace.union(spans) for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                idle[devtrace._host_at(host, (s + e) // 2)] += e - s
    n = max(1, len(planes))
    return [[k, v * 1e-9 / n] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])[:TOP_SPANS]]


# ---------------------------------------------------------------------------
# the metrics' readers: ``read(run) -> float | None``, reading ``run.scopes``
# (``by_scope`` of the profiled stretch); ``None`` when there is none
# ---------------------------------------------------------------------------


def _per_tick_ms(run, *names: str) -> float | None:
    scopes = getattr(run, "scopes", None)
    if not scopes or not scopes["ticks"]:
        return None
    s = [scopes["scope_s"][n] for n in names if n in scopes["scope_s"]]
    return sum(s) / scopes["ticks"] * 1e3 if s else None


def pool_copy_ms_per_tick(run):
    """Slot-pool executor: device time of the gathered decode's whole-pool
    gather and scatter per decode tick of the stretch."""
    return _per_tick_ms(run, "decode/pool.gather", "decode/pool.scatter")


def layer_carry_ms_per_tick(run):
    """Model: device time of the decode layer scan's own cache slicing and
    stacking (under ``layers``, outside every layer part) per tick."""
    return _per_tick_ms(run, f"decode/{CARRY}")


def decode_attention_ms_per_tick(run):
    """Model: decode attention and the new K/V rows' write per tick."""
    return _per_tick_ms(run, "decode/attn.core", "decode/attn.kv_write")


def head_ms_per_tick(run):
    """Model: the decode head (final norm, f32 logits, vocab mask) per tick."""
    return _per_tick_ms(run, "decode/head")


def prefill_attention_share(run):
    """Model: prefill attention's share of the stretch's device busy time."""
    scopes = getattr(run, "scopes", None)
    if not scopes or scopes["busy_s"] <= 0 or "prefill/attn.core" not in scopes["scope_s"]:
        return None
    return 100.0 * scopes["scope_s"]["prefill/attn.core"] / scopes["busy_s"]


READERS = {
    "pool_copy_ms_per_tick.serve": pool_copy_ms_per_tick,
    "layer_carry_ms_per_tick.serve": layer_carry_ms_per_tick,
    "decode_attention_ms_per_tick.serve": decode_attention_ms_per_tick,
    "head_ms_per_tick.serve": head_ms_per_tick,
    "prefill_attention_share.batch": prefill_attention_share,
}


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE.parent / "src"))
    import types

    import harness

    # the harness reduces and deletes its trace: keep the plain form with
    # each op's name stack on the way; and report the cell's end-to-end
    # metrics beside its per-layer ones, to show what tracing costs
    kept = []
    plain, metrics_of = devtrace.from_xplane, harness.Cell.metrics

    def keep(directory):
        kept.append(with_stacks(plain(directory), directory))
        return kept[-1]

    devtrace.from_xplane = keep
    harness.Cell.metrics = lambda cell, trace: metrics_of(cell, False) + metrics_of(cell, trace)
    try:
        result = harness.run(args.workload, args.seed, args.seconds, True, t_start=t_start)
    finally:
        devtrace.from_xplane, harness.Cell.metrics = plain, metrics_of
    print(json.dumps(result), flush=True)
    summary = by_scope(kept[-1])
    share = shares(summary)
    run = types.SimpleNamespace(scopes=summary)
    metrics = {name: read(run) for name, read in READERS.items()}
    print(json.dumps({
        "scopes": summary,
        "unscoped_share": share["unscoped"],
        "unnamed_share": share["unnamed"],
        "idle_spans": idle_spans(kept[-1]),
        "metrics": {k: v for k, v in metrics.items() if v is not None},
    }), flush=True)
    if not summary["scope_s"]:
        print("scopes: no device op of the stretch carries a program scope", file=sys.stderr)
        return 1
    for which in ("unnamed", "unscoped"):
        if share[which] > MAX_UNSCOPED:
            print(f"scopes: {which} share {share[which]:.4f} of busy time is over "
                  f"{MAX_UNSCOPED}", file=sys.stderr)
    return 1 if share["unscoped"] > MAX_UNSCOPED else 0


if __name__ == "__main__":
    raise SystemExit(main())
