"""Post-compile HLO analysis: trip-count-aware FLOPs, HBM bytes,
collective bytes, and the three-term roofline.

Why not just ``compiled.cost_analysis()``: XLA's HloCostAnalysis counts
a ``while`` body ONCE, ignoring the trip count — under scan-over-layers
(and scan-over-KV-chunks, scan-over-loss-chunks) that undercounts a
94-layer model by ~100x. The optimized HLO text annotates every while
with ``backend_config={"known_trip_count":{"n":...}}``, so this module
re-derives the counts with proper loop multipliers:

* **flops** — 2 * prod(output dims) * prod(contracting dims) for every
  ``dot``; recursion into called computations (fusions, while bodies,
  conditionals) carries the trip-count multiplier. Dots are >95% of
  model FLOPs; elementwise/transcendental ops are excluded (they are
  not MXU work).
* **bytes** — two estimates. ``bytes_raw``: operand+output bytes for
  every non-free instruction (CPU-fusion granularity — an upper bound:
  the CPU backend leaves hundreds of elementwise ops unfused that TPU
  XLA would fuse). ``bytes`` (used for the roofline memory term):
  TPU-fusion-aware — only *materialization points* count (dot operands/
  outputs, reduces, scatters, gathers, transposes/copies, dynamic
  (update-)slices, concats, collectives); elementwise / broadcast /
  convert / select chains and kLoop fusions wrapping only such ops are
  treated as fused epilogues with no incremental HBM traffic. This
  mirrors how TPU XLA actually schedules these graphs; both numbers are
  recorded so the bound is visible.
* **collectives** — operand bytes per all-gather / all-reduce /
  reduce-scatter / all-to-all / collective-permute, trip-multiplied;
  ring-model wire bytes (all-reduce counts 2x) reported alongside.

All sizes are PER-DEVICE (post-SPMD shapes are shard shapes). Roofline
terms (TPU v5e, per the brief):

    compute    = FLOPs_per_dev  / 197e12 FLOP/s
    memory     = bytes_per_dev  / 819e9  B/s
    collective = coll_bytes_per_dev / 50e9 B/s (per-ICI-link)

(equivalently global_quantity / (chips * rate)).
"""

from __future__ import annotations

import json
import re
from typing import Any

# v5e per-chip constants (per the brief)
PEAK_FLOPS = 197e12      # bf16 FLOP/s
HBM_BW = 819e9           # B/s
ICI_BW = 50e9            # B/s per link

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "token": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# ops that move no HBM bytes of their own
_FREE_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota", "while",
    "conditional", "call", "custom-call", "domain", "opt-barrier",
}

# ops TPU XLA reliably fuses into producers/consumers (no extra HBM trip)
_FUSIBLE_OPS = _FREE_OPS | {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "exponential-minus-one", "log", "log-plus-one",
    "tanh", "logistic", "sqrt", "rsqrt", "power", "sign", "floor", "ceil",
    "round-nearest-afz", "round-nearest-even", "is-finite", "not", "and",
    "or", "xor", "compare", "select", "clamp", "convert", "broadcast",
    "reshape", "slice", "reduce-precision", "erf", "atan2", "cbrt",
    "cosine", "sine", "tan", "expm1", "log1p", "shift-left",
    "shift-right-logical", "shift-right-arithmetic", "popcnt",
    "count-leading-zeros", "rng-bit-generator", "rng-get-and-update-state",
    "stochastic-convert", "real", "imag", "complex", "map",
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*")
_ARRAY_TYPE_RE = re.compile(r"[a-z][\w]*\[[0-9,]*\](?:\{[^}]*\})?")
_OP_RE = re.compile(r"\s*([\w\-]+)\(")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*{\s*$")


def _split_instr(line: str):
    """Robustly split ``%name = <type> op(...rest`` — tuple types may
    contain ``/*index=N*/`` comments and layout braces, so the type part
    is consumed with a matching-paren scan rather than a regex."""
    m = _NAME_RE.match(line)
    if not m:
        return None
    name, rest = m.group(1), line[m.end():]
    if rest.startswith("("):
        depth = 0
        idx = 0
        for idx, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        type_str, rem = rest[: idx + 1], rest[idx + 1:]
    else:
        m2 = _ARRAY_TYPE_RE.match(rest)
        if not m2:
            return None
        type_str, rem = m2.group(0), rest[m2.end():]
    m3 = _OP_RE.match(rem)
    if not m3:
        return None
    return name, type_str, m3.group(1), rem[m3.end():]


def _shape_list_bytes(type_str: str) -> int:
    return sum(
        _DTYPE_BYTES.get(dt, 4) * _dims_prod(dims)
        for dt, dims in _SHAPE_RE.findall(type_str)
    )


def _dims_prod(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


class _Instr:
    __slots__ = ("name", "type_str", "op", "rest", "out_bytes")

    def __init__(self, name: str, type_str: str, op: str, rest: str):
        self.name = name
        self.type_str = type_str
        self.op = op
        self.rest = rest
        self.out_bytes = _shape_list_bytes(type_str)


def _parse_computations(hlo: str) -> dict[str, list[_Instr]]:
    comps: dict[str, list[_Instr]] = {}
    entry_alias: str | None = None
    current: list[_Instr] | None = None
    for line in hlo.splitlines():
        if current is None:
            m = _COMP_RE.match(line.strip()) if "{" in line and "->" in line else None
            if m:
                name = m.group(1)
                current = comps.setdefault(name, [])
                if line.strip().startswith("ENTRY"):
                    entry_alias = name
            continue
        s = line.strip()
        if s == "}":
            current = None
            continue
        parts = _split_instr(line)
        if parts:
            current.append(_Instr(*parts))
    if entry_alias:
        comps["__entry__"] = comps[entry_alias]
    return comps


def _operand_names(rest: str) -> list[str]:
    """Names inside the top-level call parens of an instruction line."""
    depth, args = 1, ""
    for ch in rest:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                break
        args += ch
    return re.findall(r"%([\w.\-]+)", args)


def _trip_count(rest: str) -> int:
    m = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', rest)
    return int(m.group(1)) if m else 1


def _called_comps(rest: str) -> list[str]:
    names: list[str] = []
    for key in ("body=", "calls=", "true_computation=", "false_computation=",
                "branch_computations="):
        for m in re.finditer(re.escape(key) + r"\{?%?([\w.\-]+(?:, ?%[\w.\-]+)*)", rest):
            names += re.findall(r"[\w.\-]+", m.group(1).replace("%", ""))
    return names


def _dot_flops(instr: _Instr, sizes: dict[str, int], shapes: dict[str, str]) -> float:
    """2 * prod(out dims) * prod(lhs contracting dims)."""
    out_elems = sum(_dims_prod(d) for _, d in _SHAPE_RE.findall(instr.type_str))
    mc = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", instr.rest)
    ops = _operand_names(instr.rest)
    if not mc or not ops:
        return 0.0
    lhs_shape_str = shapes.get(ops[0], "")
    mm = _SHAPE_RE.search(lhs_shape_str)
    if not mm:
        return 0.0
    lhs_dims = [int(d) for d in mm.group(2).split(",") if d]
    contract = 1
    for idx in mc.group(1).split(","):
        if idx and int(idx) < len(lhs_dims):
            contract *= lhs_dims[int(idx)]
    return 2.0 * out_elems * contract


def analyze_hlo_text(hlo: str) -> dict[str, Any]:
    comps = _parse_computations(hlo)
    # per-computation symbol tables (name -> bytes / type string)
    tables: dict[str, tuple[dict[str, int], dict[str, str]]] = {}
    for cname, instrs in comps.items():
        sizes = {i.name: i.out_bytes for i in instrs}
        shapes = {i.name: i.type_str for i in instrs}
        tables[cname] = (sizes, shapes)

    memo: dict[str, dict[str, float]] = {}
    per_kind: dict[str, dict[str, float]] = {
        k: {"count": 0.0, "operand_bytes": 0.0, "wire_bytes": 0.0} for k in _COLLECTIVES
    }

    elementwise_fusion: dict[str, bool] = {}
    _SLICE_OPS = ("dynamic-slice", "slice", "gather")

    def _is_elementwise_fusion(cname: str) -> bool:
        if cname not in elementwise_fusion:
            elementwise_fusion[cname] = cname in comps and all(
                i.op in _FUSIBLE_OPS or i.op in _SLICE_OPS for i in comps[cname]
            )
        return elementwise_fusion[cname]

    fusion_in_traffic: dict[str, int] = {}

    def _fusion_input_traffic(cname: str) -> int:
        """Input HBM bytes of a fused kernel: parameters consumed only
        through slice-like ops are charged at the SLICE size (a
        dynamic-slice of a stacked per-layer buffer reads one layer's
        slice, not the whole stack — charging the full operand was a
        ~5x over-count on scanned models)."""
        if cname in fusion_in_traffic:
            return fusion_in_traffic[cname]
        il = comps.get(cname, [])
        uses: dict[str, list[_Instr]] = {}
        for u in il:
            for n in _operand_names(u.rest):
                uses.setdefault(n, []).append(u)
        t = 0
        for x in il:
            if x.op != "parameter":
                continue
            users = uses.get(x.name, [])
            if users and all(u.op in _SLICE_OPS for u in users):
                t += sum(u.out_bytes for u in users)
            else:
                t += x.out_bytes
        fusion_in_traffic[cname] = t
        return t

    def _instr_traffic(i: _Instr, sizes: dict[str, int]) -> int:
        """Slice-aware HBM traffic of one instruction."""
        if i.op in _SLICE_OPS:
            return 2 * i.out_bytes  # read slice + write result
        if i.op == "dynamic-update-slice":
            ops = _operand_names(i.rest)
            upd = sizes.get(ops[1], 0) if len(ops) > 1 else 0
            return 2 * upd  # in-place: read update + write window
        if i.op == "fusion":
            called = _called_comps(i.rest)
            inp = _fusion_input_traffic(called[0]) if called else 0
            return inp + i.out_bytes
        return i.out_bytes + sum(sizes.get(n, 0) for n in _operand_names(i.rest))

    def walk(cname: str, mult: float) -> dict[str, float]:
        # flops/bytes are multiplier-independent per computation; collect
        # collectives with the live multiplier (can't memo those), so:
        # memo stores per-execution totals and a (kind, bytes) coll list.
        if cname not in comps:
            return {"flops": 0.0, "raw": 0.0, "fused": 0.0}
        if cname in memo:
            acc = memo[cname]
        else:
            sizes, shapes = tables[cname]
            acc = {"flops": 0.0, "raw": 0.0, "fused": 0.0, "colls": [], "children": []}
            for i in comps[cname]:
                base = i.op.replace("-start", "")
                io_bytes = _instr_traffic(i, sizes)
                if base in _COLLECTIVES:
                    ob = sum(sizes.get(n, 0) for n in _operand_names(i.rest))
                    acc["colls"].append((base, float(ob)))
                if i.op == "while":
                    tc = _trip_count(i.rest)
                    for child in _called_comps(i.rest):
                        if "cond" not in child:
                            acc["children"].append((child, float(tc), "full"))
                elif i.op == "fusion":
                    acc["raw"] += io_bytes
                    if not _is_elementwise_fusion(_called_comps(i.rest)[0]
                                                  if _called_comps(i.rest) else ""):
                        acc["fused"] += io_bytes
                    for child in _called_comps(i.rest):
                        acc["children"].append((child, 1.0, "flops_only"))
                elif i.op in ("call", "conditional"):
                    for child in _called_comps(i.rest):
                        acc["children"].append((child, 1.0, "full"))
                elif i.op == "dot":
                    acc["flops"] += _dot_flops(i, sizes, shapes)
                    acc["raw"] += io_bytes
                    acc["fused"] += io_bytes
                elif i.op in _FUSIBLE_OPS:
                    if i.op not in _FREE_OPS:
                        acc["raw"] += io_bytes
                else:
                    # materialization points: reduce, scatter, copy,
                    # transpose, concatenate, (dynamic-)slice/DUS,
                    # sort, convolution, pad, ...
                    acc["raw"] += io_bytes
                    acc["fused"] += io_bytes
            memo[cname] = acc

        total = {"flops": acc["flops"], "raw": acc["raw"], "fused": acc["fused"]}
        for kind, ob in acc["colls"]:
            wire = ob * (2.0 if kind == "all-reduce" else 1.0)
            per_kind[kind]["count"] += mult
            per_kind[kind]["operand_bytes"] += ob * mult
            per_kind[kind]["wire_bytes"] += wire * mult
        for child, cm, mode in acc["children"]:
            if mode == "flops_only":
                total["flops"] += walk_flops_only(child, mult * cm)
            else:
                sub = walk(child, mult * cm)
                total["flops"] += sub["flops"] * cm
                total["raw"] += sub["raw"] * cm
                total["fused"] += sub["fused"] * cm
        return total

    flops_memo: dict[str, float] = {}

    def walk_flops_only(cname: str, mult: float) -> float:
        if cname not in comps:
            return 0.0
        if cname in flops_memo:
            return flops_memo[cname]
        sizes, shapes = tables[cname]
        f = 0.0
        for i in comps[cname]:
            if i.op == "dot":
                f += _dot_flops(i, sizes, shapes)
            elif i.op in ("fusion", "call", "while", "conditional"):
                tc = _trip_count(i.rest) if i.op == "while" else 1
                for child in _called_comps(i.rest):
                    if i.op == "while" and "cond" in child:
                        continue
                    f += walk_flops_only(child, 1.0) * tc
        flops_memo[cname] = f
        return f

    top = walk("__entry__", 1.0)
    total_ob = sum(v["operand_bytes"] for v in per_kind.values())
    total_wire = sum(v["wire_bytes"] for v in per_kind.values())
    return {
        "flops_per_dev": top["flops"],
        "bytes_per_dev": top["fused"],
        "bytes_raw_per_dev": top["raw"],
        "coll_per_kind": per_kind,
        "coll_operand_bytes_per_dev": total_ob,
        "coll_wire_bytes_per_dev": total_wire,
    }


def roofline_terms(
    flops_per_dev: float, bytes_per_dev: float, coll_bytes_per_dev: float
) -> dict[str, Any]:
    compute = flops_per_dev / PEAK_FLOPS
    memory = bytes_per_dev / HBM_BW
    collective = coll_bytes_per_dev / ICI_BW
    dominant = max(
        ("compute", compute), ("memory", memory), ("collective", collective),
        key=lambda kv: kv[1],
    )[0]
    return {
        "compute_s": compute,
        "memory_s": memory,
        "collective_s": collective,
        "dominant": dominant,
    }


def analyze_compiled(compiled, n_chips: int) -> dict[str, Any]:
    """Full per-cell record: trip-aware cost, memory, collectives, roofline."""
    xla_cost = compiled.cost_analysis()
    text = compiled.as_text()
    acc = analyze_hlo_text(text)
    mem = compiled.memory_analysis()
    terms = roofline_terms(
        acc["flops_per_dev"], acc["bytes_per_dev"], acc["coll_operand_bytes_per_dev"]
    )
    return {
        # global quantities (= per-dev * chips; shapes in HLO are shards)
        "flops": acc["flops_per_dev"] * n_chips,
        "bytes_accessed": acc["bytes_per_dev"] * n_chips,
        "flops_per_dev": acc["flops_per_dev"],
        "bytes_per_dev": acc["bytes_per_dev"],
        "bytes_raw_per_dev": acc["bytes_raw_per_dev"],
        "xla_cost_flops_tripblind": float(xla_cost.get("flops", 0.0)),
        "collectives": {
            "per_kind": acc["coll_per_kind"],
            "operand_bytes": acc["coll_operand_bytes_per_dev"],
            "wire_bytes": acc["coll_wire_bytes_per_dev"],
        },
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "peak_per_device_bytes": int(
                mem.argument_size_in_bytes
                + mem.output_size_in_bytes
                + mem.temp_size_in_bytes
                - mem.alias_size_in_bytes
            ),
        },
        "roofline": terms,
    }
