"""Reduction of a profiler trace to device busy time, kernel time and
idle gaps.

``from_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into a small plain form, which the recorded test trace also uses::

    {"window": [start_ns, end_ns],
     "device": {plane: [[op, start_ns, dur_ns, detail], ...]},
     "host": [[name, start_ns, dur_ns], ...]}

``window`` is the harness's ``bench.window`` annotation, ``device``
the ops of each accelerator's ``XLA Ops`` line and ``host`` the events
of the thread that drove the window. ``reduce`` turns that into what
the per-layer metrics read.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
DETAIL_STATS = ("long_name", "hlo_op", "tf_op", "name")


def from_xplane(directory: str) -> dict:
    """The plain form of the newest trace under ``directory``."""
    import jax

    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    device: dict[str, list] = {}
    host_lines: list[list] = []
    window = None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        [e.name, int(e.start_ns), int(e.duration_ns), _detail(e)]
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [[e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events]
                for name, start, dur in events:
                    if name == WINDOW:
                        window = [start, start + dur]
                        host_lines.append(events)
    if window is None:
        raise ValueError(f"trace under {directory} holds no {WINDOW!r} annotation")
    return {"window": window, "device": device, "host": host_lines[0]}


def _detail(event) -> str:
    stats = dict(event.stats) if hasattr(event, "stats") else {}
    return " ".join(str(stats[k]) for k in DETAIL_STATS if k in stats)


def _clip(events, lo: int, hi: int):
    for ev in events:
        s, e = max(ev[1], lo), min(ev[1] + ev[2], hi)
        if e > s:
            yield ev, s, e


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_name(text: str) -> tuple[str, str]:
    """(instruction, opcode) of an op event named by its HLO text, e.g.
    ``%copy.32 = bf16[...] copy(...)`` -> ("copy.32", "copy")."""
    if " = " not in text:
        return text, text
    name, rest = text.split(" = ", 1)
    m = _OPCODE.search(rest)
    return name.lstrip("%"), m.group(1) if m else "?"


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
# ops whose event spans the ops it runs (listed by their children)
CONTAINERS = ("while", "conditional", "call")


def reduce(trace: dict, kernels: dict[str, tuple[str, ...]], top: int = 10) -> dict:
    """Busy and kernel seconds of the window, averaged over the device
    planes, with the longest ops and idle gaps.

    An async op's start event spans the whole transfer and is left out;
    a loop's event counts as busy but is not listed among the ops (its
    body's ops are). ``kernels`` maps a kernel's name to substrings of
    its op text. Idle gaps are named by the innermost host event that
    covers the gap's middle."""
    lo, hi = trace["window"]
    planes = trace["device"]
    busy = 0
    kernel_ns: dict[str, int] = {k: 0 for k in kernels}
    op_ns: dict[str, int] = defaultdict(int)
    gaps: list[tuple[int, int]] = []
    for events in planes.values():
        spans = []
        for ev, s, e in _clip(events, lo, hi):
            name, opcode = op_name(ev[0])
            if opcode.endswith("-start"):
                continue
            spans.append((s, e))
            if opcode not in CONTAINERS:
                op_ns[name] += e - s
            text = f"{ev[0]} {ev[3] if len(ev) > 3 else ''}"
            for k, needles in kernels.items():
                if any(n in text for n in needles):
                    kernel_ns[k] += e - s
        merged = union(spans)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(e - s, (s + e) // 2) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    n = max(1, len(planes))
    gaps.sort(key=lambda g: -g[0])
    host = sorted(trace["host"], key=lambda ev: ev[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * 1e-9 / n,
        "kernel_s": {k: v * 1e-9 / n for k, v in kernel_ns.items()},
        "device_ops": [[k, v * 1e-9 / n] for k, v in sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_host_at(host, mid), ns * 1e-9] for ns, mid in gaps[:top]],
    }


def _host_at(host, t: int) -> str:
    """The shortest host event covering time ``t`` (the innermost)."""
    best = None
    for name, s, dur in host:
        if s > t:
            break
        if t < s + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else "idle host"
