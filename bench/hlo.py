"""Trip-count-aware dot FLOPs and collectives of a compiled HLO module.

A copy, kept with the benchmark, of the program's
``launch/hlo_analysis.py``: it counts what the compiled program does, so the
benchmark's ``step_flops_ratio`` cannot move with a change to the program's
own copy.  Added to the copy: the TPU compiler rewrites most dots as
``convolution`` ops (batch dimensions folded into windows, strides and
dilations), and those are counted by their valid multiply-adds
(``_conv_flops``).  XLA's ``compiled.cost_analysis()`` counts each while-loop body
once; this parses ``compiled.as_text()`` and weights every computation by
the ``known_trip_count`` of the loops that call it:

  * dot_flops          — 2·(result elements)·K per dot, and 2·(valid
                         multiply-adds) per convolution
  * bytes_estimate     — Σ result-buffer bytes per instruction
  * collective_bytes   — per-kind result bytes of all-gather / all-reduce /
                         reduce-scatter / all-to-all / collective-permute

All numbers are per device (the HLO is the SPMD per-device module).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(
    r"(pred|s4|u4|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64|c64|c128|"
    r"f8e4m3fn|f8e5m2)\[([\d,]*)\]")

_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(.+?)\s+"
                     r"([\w\-]+)\(")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shapes_in(text: str) -> List[Tuple[str, List[int]]]:
    out = []
    for m in _SHAPE_RE.finditer(text):
        dims = [int(d) for d in m.group(2).split(",")] if m.group(2) else []
        out.append((m.group(1), dims))
    return out


def _numel_bytes(text: str) -> int:
    total = 0
    for dt, dims in _shapes_in(text):
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class Instr:
    name: str
    shape_str: str          # result type text (may be a tuple)
    op: str
    line: str


@dataclass
class Computation:
    name: str
    instrs: List[Instr] = field(default_factory=list)
    # symbol table: %name -> result shape text
    shapes: Dict[str, str] = field(default_factory=dict)


_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?(%?[\w.\-]+)\s*\(")


def parse_hlo(text: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry: Optional[str] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        s = line.strip()
        if cur is None:
            if s.endswith("{") and "->" in s and \
                    (s.startswith("%") or s.startswith("ENTRY")):
                m = _COMP_HDR.match(s)
                if m:
                    name = m.group(1).lstrip("%")
                    cur = Computation(name)
                    if s.startswith("ENTRY"):
                        entry = name
            continue
        if s == "}" or s.startswith("}"):
            comps[cur.name] = cur
            cur = None
            continue
        m = _DEF_RE.match(s)
        if m:
            name, shape_str, op = m.group(1), m.group(2), m.group(3)
            cur.instrs.append(Instr(name, shape_str, op, s))
            cur.shapes[name] = shape_str
        elif "parameter(" in s:
            m2 = re.match(r"^\s*(%[\w.\-]+)\s*=\s*(.+?)\s+parameter\(", s)
            if m2:
                cur.instrs.append(Instr(m2.group(1), m2.group(2),
                                        "parameter", s))
                cur.shapes[m2.group(1)] = m2.group(2)
    return comps, entry


_CALLEE_RE = {
    "while": re.compile(r"body=(%?[\w.\-]+)"),
    "cond": re.compile(r"condition=(%?[\w.\-]+)"),
    "fusion": re.compile(r"calls=(%?[\w.\-]+)"),
    "call": re.compile(r"to_apply=(%?[\w.\-]+)"),
    "conditional": re.compile(r"(?:true_computation|branch_computations)="
                              r"[{(]?(%?[\w.\-]+)"),
    "sort": re.compile(r"to_apply=(%?[\w.\-]+)"),
    "reduce": re.compile(r"to_apply=(%?[\w.\-]+)"),
    "scatter": re.compile(r"to_apply=(%?[\w.\-]+)"),
}

_TRIP_RE = re.compile(r'known_trip_count[":{ ]+n["\':]+\s*"?(\d+)')


def _dot_flops(instr: Instr, comp: Computation) -> float:
    """2 · numel(result) · K  (K = product of lhs contracting dim sizes)."""
    shapes = _shapes_in(instr.shape_str)
    if not shapes:
        return 0.0
    _, rdims = shapes[0]
    numel = 1
    for d in rdims:
        numel *= d
    m = re.search(r"dot\((%[\w.\-]+)", instr.line)
    mc = re.search(r"lhs_contracting_dims={([\d,]*)}", instr.line)
    if not m or not mc:
        return 2.0 * numel          # fallback: treat as elementwise-ish
    lhs_shape_str = comp.shapes.get(m.group(1), "")
    lsh = _shapes_in(lhs_shape_str)
    if not lsh:
        return 2.0 * numel
    _, ldims = lsh[0]
    k = 1
    for ci in mc.group(1).split(","):
        if ci != "" and int(ci) < len(ldims):
            k *= ldims[int(ci)]
    return 2.0 * numel * k


_WIN_RE = re.compile(r"window=\{([^}]*)\}")
_LABELS_RE = re.compile(r"dim_labels=([0-9a-z]+)_([0-9a-z]+)->([0-9a-z]+)")
_OPERANDS_RE = re.compile(r"convolution\((%[\w.\-]+),\s*(%[\w.\-]+)")


def _window(text: str) -> Dict[str, List[str]]:
    out: Dict[str, List[str]] = {}
    for part in text.split():
        if "=" in part:
            k, v = part.split("=", 1)
            out[k] = v.split("x")
    return out


def _pairs(o: int, i: int, k: int, stride: int, lo: int, lhs_dil: int,
           rhs_dil: int) -> int:
    """(output position, kernel tap) pairs that read a real input element
    along one spatial dimension."""
    top = (i - 1) * lhs_dil
    n = 0
    for kk in range(k):
        off = kk * rhs_dil - lo
        for oo in range(o):
            x = oo * stride + off
            if 0 <= x <= top and x % lhs_dil == 0:
                n += 1
    return n


def _conv_flops(instr: Instr, comp: Computation) -> float:
    """2 · Σ valid multiply-adds of one HLO convolution."""
    shapes = _shapes_in(instr.shape_str)
    ml = _LABELS_RE.search(instr.line)
    mo = _OPERANDS_RE.search(instr.line)
    if not shapes or not ml or not mo:
        return 0.0
    lhs = _shapes_in(comp.shapes.get(mo.group(1), ""))
    rhs = _shapes_in(comp.shapes.get(mo.group(2), ""))
    if not lhs or not rhs:
        return 0.0
    ldims, rdims, odims = lhs[0][1], rhs[0][1], shapes[0][1]
    l_lab, r_lab, o_lab = ml.groups()
    mw = _WIN_RE.search(instr.line)
    win = _window(mw.group(1)) if mw else {}
    numel = 1
    for d in odims:
        numel *= d
    macs = float(numel) * rdims[r_lab.index("i")]
    spatial = sorted(c for c in o_lab if c.isdigit())
    for j, c in enumerate(spatial):
        o, i = odims[o_lab.index(c)], ldims[l_lab.index(c)]
        k = rdims[r_lab.index(c)]

        def field(name, default, j=j):
            v = win.get(name)
            return v[j] if v and j < len(v) else default
        lo = int(field("pad", "0_0").split("_")[0])
        macs *= _pairs(o, i, k, int(field("stride", 1)), lo,
                       int(field("lhs_dilate", 1)),
                       int(field("rhs_dilate", 1))) / o
    return 2.0 * macs


_CONST_RE = re.compile(r"s32\[\][^ ]*\s+constant\((\d+)\)")


def _cond_bound(comps: Dict[str, Computation], name: str) -> float:
    """Trip count of a counted loop without ``known_trip_count`` (the TPU
    compiler drops it): the one s32 constant its condition compares the
    counter with."""
    comp = comps.get(name.lstrip("%"))
    if comp is None:
        return 1.0
    consts = [int(m.group(1)) for ins in comp.instrs
              for m in [_CONST_RE.search(ins.line)] if m]
    return float(consts[0]) if len(consts) == 1 else 1.0


_SKIP_BYTES_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
                   "bitcast", "copy-start", "copy-done", "after-all"}


def analyze_hlo(text: str) -> Dict[str, object]:
    comps, entry = parse_hlo(text)
    if entry is None:
        for name in comps:
            if name.startswith("main") or "entry" in name.lower():
                entry = name
                break
        if entry is None and comps:
            entry = next(iter(comps))

    memo: Dict[str, Dict[str, object]] = {}

    def visit(name: str) -> Dict[str, object]:
        name = name.lstrip("%")
        if name in memo:
            return memo[name]
        comp = comps.get(name)
        acc = {"dot_flops": 0.0, "bytes": 0.0,
               "coll": {k: 0.0 for k in _COLLECTIVES},
               "coll_count": {k: 0 for k in _COLLECTIVES}}
        memo[name] = acc             # break cycles defensively
        if comp is None:
            return acc
        for ins in comp.instrs:
            base_op = ins.op.replace("-start", "").replace("-done", "")
            if base_op == "dot":
                acc["dot_flops"] += _dot_flops(ins, comp)
            elif base_op == "convolution":
                acc["dot_flops"] += _conv_flops(ins, comp)
            if ins.op not in _SKIP_BYTES_OPS and not ins.op.endswith("-done"):
                acc["bytes"] += _numel_bytes(ins.shape_str)
            if base_op in _COLLECTIVES and not ins.op.endswith("-done"):
                acc["coll"][base_op] += _numel_bytes(ins.shape_str)
                acc["coll_count"][base_op] += 1
            # recurse into callees
            mult = 1.0
            callees: List[str] = []
            if ins.op == "while":
                mb = _CALLEE_RE["while"].search(ins.line)
                mt = _TRIP_RE.search(ins.line)
                mc = _CALLEE_RE["cond"].search(ins.line)
                mult = float(mt.group(1)) if mt else \
                    _cond_bound(comps, mc.group(1) if mc else "")
                if mb:
                    callees.append(mb.group(1))
                mc = _CALLEE_RE["cond"].search(ins.line)
                if mc:
                    callees.append(mc.group(1))
            elif ins.op == "fusion":
                mb = _CALLEE_RE["fusion"].search(ins.line)
                if mb:
                    callees.append(mb.group(1))
            elif ins.op in ("call", "custom-call", "sort", "reduce",
                            "reduce-window", "scatter", "select-and-scatter",
                            "map", "conditional", "async-start"):
                for pat_key in ("call", "conditional"):
                    mb = _CALLEE_RE[pat_key].search(ins.line)
                    if mb:
                        callees.append(mb.group(1))
                        break
            for callee in callees:
                sub = visit(callee)
                acc["dot_flops"] += mult * sub["dot_flops"]
                acc["bytes"] += mult * sub["bytes"]
                for k in _COLLECTIVES:
                    acc["coll"][k] += mult * sub["coll"][k]
                    acc["coll_count"][k] += int(mult) * sub["coll_count"][k]
        return acc

    acc = visit(entry) if entry else {"dot_flops": 0.0, "bytes": 0.0,
                                      "coll": {}, "coll_count": {}}
    return {
        "dot_flops_tc": acc["dot_flops"],
        "bytes_estimate_tc": acc["bytes"],
        "collective_bytes_tc": dict(acc["coll"]),
        "collective_count_tc": dict(acc["coll_count"]),
        "collective_total_tc": sum(acc["coll"].values()),
        "n_computations": len(comps),
    }
