"""The step's device time and dot FLOPs by the program's named scopes.

The program names its parts with ``jax.named_scope``; XLA writes the name
stack into every HLO instruction's ``op_name`` metadata, and the profiler
reports the same instructions on the device's ``XLA Ops`` line.  So the
compiled step's text maps each operation of a trace to a path such as
``jit(step_fn)/perfed.hvp/jvp(transpose(jvp()))/while/body/.../attn.core/mul``.

* **Phase**: the first of ``PHASES`` on the path, else ``unscoped``.  Each
  operation falls in exactly one phase, so the phases sum to the busy time.
  A component may be wrapped in transforms (``transpose(jvp(perfed.hvp))``):
  the scope is matched as a whole component inside its wrappers.
* **Parts**, counted in any phase: ``remat`` (JAX's ``rematted_computation``
  on the path: work ``jax.checkpoint`` recomputes) and the model's own
  scopes: the part is the first name on the path that holds a ``.`` and is
  not a phase.  Python names hold no dot, so only ``jax.named_scope`` names
  (``attn.core``, ``moe.experts``) qualify; of nested ones the outer
  counts.

An operation goes by its own instruction's metadata: a fusion by the
fusion's, a loop's own time (its overhead) by the ``while`` instruction's.
Where that names no phase (the TPU compiler writes none on the copies,
slices and clones it makes), it goes by the instruction that calls its
computation: the fusion around it, the loop whose body holds it, up to the
entry.  An event whose name is not an instruction of the step counts as
``unscoped``.  Dot FLOPs are counted per operation as ``bench/hlo.py``
counts them (loop trip counts applied), so a phase's FLOPs and its time
come from the same operations.

The compiled module comes from the trace itself: the profiler writes the
HLO of every program it saw run into its ``.xplane.pb`` (the
``/host:metadata`` plane), so the names are those of the executable that
ran, whatever compile cache it came from.
"""
from __future__ import annotations

import functools
import json
import re
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

from bench import hlo
from bench import trace as btrace

PHASES = ("perfed.adapt", "perfed.outer", "perfed.hvp", "perfed.loss",
          "train.update", "semi_sync.eq8")
UNSCOPED = "unscoped"
REMAT = "rematted_computation"
# control flow: the computations these call run as operations of their own
CONTROL = ("while", "call", "conditional")
# where run.py keeps the trace of a traced window until its readers are done
BENCH_OUT = Path(__file__).resolve().parent.parent / ".bench_out"
MODULES_LINE = "XLA Modules"

_OP_NAME_RE = re.compile(r'metadata=\{[^{}]*?op_name="([^"]*)"')
_WRAP_RE = re.compile(r"^[\w\-]+\((.*)\)$")


def components(path: str) -> List[str]:
    """The scope names of an ``op_name`` path, transforms unwrapped:
    ``jit(f)/perfed.hvp/jvp(transpose(jvp(attn.core)))/mul`` gives
    ``["f", "perfed.hvp", "attn.core", "mul"]`` (an empty wrapper, ``jvp()``,
    gives ``""``)."""
    parts, depth, cur = [], 0, []
    for ch in path:
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    parts.append("".join(cur))
    out: List[str] = []
    for p in parts:
        m = _WRAP_RE.match(p)
        while m:
            p = m.group(1)
            m = _WRAP_RE.match(p)
        out.extend(components(p) if "/" in p else [p])
    return out


@dataclass(frozen=True)
class Scope:
    phase: str                # one of PHASES, or UNSCOPED
    part: Optional[str]       # the first model scope, if any
    remat: bool               # recomputed under jax.checkpoint


NONE = Scope(UNSCOPED, None, False)


def classify(path: str) -> Scope:
    names = components(path)
    phase = next((n for n in names if n in PHASES), UNSCOPED)
    part = next((n for n in names if "." in n and n not in PHASES), None)
    return Scope(phase, part, REMAT in names)


def calls(ins: hlo.Instr, comps: Dict[str, hlo.Computation]
          ) -> Tuple[float, List[str]]:
    """(times each runs, computations) that one instruction calls, as
    ``hlo.analyze_hlo`` walks them: a loop's body and condition run its trip
    count times."""
    if ins.op == "while":
        mb = hlo._CALLEE_RE["while"].search(ins.line)
        mc = hlo._CALLEE_RE["cond"].search(ins.line)
        mt = hlo._TRIP_RE.search(ins.line)
        mult = float(mt.group(1)) if mt else \
            hlo._cond_bound(comps, mc.group(1) if mc else "")
        return mult, [m.group(1) for m in (mb, mc) if m]
    if ins.op == "fusion":
        mb = hlo._CALLEE_RE["fusion"].search(ins.line)
        return 1.0, [mb.group(1)] if mb else []
    if ins.op in ("call", "custom-call", "sort", "reduce", "reduce-window",
                  "scatter", "select-and-scatter", "map", "conditional",
                  "async-start"):
        for key in ("call", "conditional"):
            mb = hlo._CALLEE_RE[key].search(ins.line)
            if mb:
                return 1.0, [mb.group(1)]
    return 1.0, []


def instr_dot_flops(ins: hlo.Instr, comp: hlo.Computation) -> float:
    """Dot FLOPs of one instruction alone, as ``hlo.analyze_hlo`` counts."""
    base_op = ins.op.replace("-start", "").replace("-done", "")
    if base_op == "dot":
        return hlo._dot_flops(ins, comp)
    if base_op == "convolution":
        return hlo._conv_flops(ins, comp)
    return 0.0


def op_paths(text: str) -> Dict[str, str]:
    """Instruction name (``fusion.12``) → the ``op_name`` path it runs
    under, for every instruction of an HLO module's text: its own where
    that names a phase, else that of the nearest instruction up the chain
    of callers (fusion, loop) that names one, else its own (``""`` if it
    has none)."""
    comps, _ = hlo.parse_hlo(text)
    own: Dict[str, str] = {}
    home: Dict[str, str] = {}        # instruction → its computation
    caller: Dict[str, str] = {}      # computation → the instruction calling it
    for comp in comps.values():
        for ins in comp.instrs:
            name = ins.name.lstrip("%")
            m = _OP_NAME_RE.search(ins.line)
            own[name] = m.group(1) if m else ""
            home[name] = comp.name
            for c in calls(ins, comps)[1]:
                caller[c.lstrip("%")] = name
    phased = {p: classify(p).phase != UNSCOPED for p in set(own.values())}
    out = {}
    for name, path in own.items():
        up, seen = name, set()
        while not phased[own[up]] and home[up] in caller and up not in seen:
            seen.add(up)
            up = caller[home[up]]
        out[name] = own[up] if phased[own[up]] else path
    return out


def operations(text: str) -> Iterator[Tuple[str, float]]:
    """(instruction name, dot FLOPs per step) of every instruction that runs
    as one device operation: those of the entry computation and of the
    computations that control flow calls, each with the FLOPs of the
    computations it calls (a fusion's), times the trip counts of the loops
    around it.  The FLOPs sum to ``analyze_hlo``'s ``dot_flops_tc``."""
    comps, entry = hlo.parse_hlo(text)

    @functools.lru_cache(maxsize=None)
    def inside(name: str) -> float:
        comp = comps.get(name.lstrip("%"))
        if comp is None:
            return 0.0
        return sum(own(ins, comp) for ins in comp.instrs)

    def own(ins, comp) -> float:
        mult, callees = calls(ins, comps)
        return instr_dot_flops(ins, comp) + mult * sum(
            inside(c) for c in callees)

    def walk(name: str, mult: float) -> Iterator[Tuple[str, float]]:
        comp = comps.get(name.lstrip("%"))
        if comp is None:
            return
        for ins in comp.instrs:
            if ins.op in CONTROL:
                times, callees = calls(ins, comps)
                yield ins.name.lstrip("%"), 0.0
                for c in callees:
                    yield from walk(c, mult * times)
            else:
                yield ins.name.lstrip("%"), mult * own(ins, comp)

    if entry is not None:
        yield from walk(entry, 1.0)


class Buckets:
    """Sums of one quantity by phase and by part."""

    def __init__(self, paths: Dict[str, str]):
        self.paths = paths
        self.by_path = {p: classify(p) for p in set(paths.values())}
        self.phases = {p: 0.0 for p in PHASES + (UNSCOPED,)}
        self.parts: Dict[str, float] = {"remat": 0.0}

    def scope(self, name: str) -> Scope:
        path = self.paths.get(name)
        return NONE if path is None else self.by_path[path]

    def add(self, name: str, v: float) -> None:
        s = self.scope(name)
        self.phases[s.phase] += v
        if s.remat:
            self.parts["remat"] += v
        if s.part:
            self.parts[s.part] = self.parts.get(s.part, 0.0) + v


def device_ms(trace: btrace.Trace, device: str, paths: Dict[str, str],
              steps: int) -> Tuple[Buckets, Dict[str, float]]:
    """Own device time of the window's operations in ms per step: by phase
    and part, and by operation."""
    b = Buckets(paths)
    by_op: Dict[str, float] = {}
    lo, hi = trace.window()
    for ev, ns in btrace.self_times(trace.ops(device), lo, hi):
        name = btrace.op_name(ev)
        ms = ns * 1e-6 / steps
        by_op[name] = by_op.get(name, 0.0) + ms
        b.add(name, ms)
    return b, by_op


def reduce(text: str, trace: btrace.Trace, device: str, steps: int,
           top: int = 10) -> Optional[dict]:
    """Device ms per step and dot FLOPs per step by phase and by part, from
    a traced window of ``steps`` whole steps of the program whose compiled
    module ``text`` is, with the ``top`` operations by own time.  ``None``
    if the program names no phase scope."""
    paths = op_paths(text)
    if all(classify(p).phase == UNSCOPED for p in set(paths.values())):
        return None
    ms, by_op = device_ms(trace, device, paths, steps)
    flops = Buckets(paths)
    for name, f in operations(text):
        flops.add(name, f)

    def table(t: Dict[str, float], f: Dict[str, float]) -> dict:
        keys = list(t) + [k for k in f if k not in t]
        return {k: {"ms": t.get(k, 0.0), "dot_flops": f.get(k, 0.0)}
                for k in keys}

    heavy = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "steps": steps,
        "busy_ms": sum(by_op.values()),
        "phases": table(ms.phases, flops.phases),
        "parts": table(ms.parts, flops.parts),
        "top": [[n, t, ms.scope(n).phase, ms.scope(n).part]
                for n, t in heavy],
    }


# ---------------------------------------------------------------------------
# the compiled module, from the trace
# ---------------------------------------------------------------------------

def _varint(b, i: int) -> Tuple[int, int]:
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return r, i


def _fields(b) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message's wire bytes; a
    length-delimited value is a view into ``b``."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield field, v


def _message(b) -> Dict[int, list]:
    out: Dict[int, list] = {}
    for f, v in _fields(b):
        out.setdefault(f, []).append(v)
    return out


HLO_PROTO_STAT = "Hlo Proto"


def hlo_modules(xplane: Path) -> Dict[str, bytes]:
    """Program name (``jit_step_fn(5)``) → its ``HloModuleProto``, for every
    program whose HLO the profiler wrote into an ``.xplane.pb``: the
    event metadata of a plane carrying an ``Hlo Proto`` stat (``XSpace`` →
    ``XPlane`` → ``XEventMetadata`` → ``XStat`` → ``HloProto``)."""
    data = memoryview(Path(xplane).read_bytes())
    out = {}
    for f, plane in _fields(data):
        if f != 1:                                  # XSpace.planes
            continue
        pl = _message(plane)
        stat_ids = set()
        for entry in pl.get(5, []):                 # stat_metadata map
            kv = _message(entry)
            meta = _message(kv.get(2, [b""])[0])
            if bytes(meta.get(2, [b""])[0]) == HLO_PROTO_STAT.encode():
                stat_ids.add(kv.get(1, [0])[0])
        if not stat_ids:
            continue
        for entry in pl.get(4, []):                 # event_metadata map
            em = _message(_message(entry).get(2, [b""])[0])
            name = bytes(em.get(2, [b""])[0]).decode()
            for st in em.get(5, []):                # XEventMetadata.stats
                stat = _message(st)
                if stat.get(1, [0])[0] in stat_ids and 6 in stat:
                    module = _message(stat[6][0]).get(1)  # HloProto.hlo_module
                    if module:
                        out[name] = bytes(module[0])
    return out


def module_text(proto: bytes) -> str:
    """An ``HloModuleProto`` as the text ``compiled.as_text()`` prints."""
    from jax._src.lib import xla_client

    opts = xla_client._xla.HloPrintOptions()
    opts.print_metadata = True
    opts.print_operand_shape = False
    opts.print_large_constants = False
    return xla_client.XlaComputation(proto).get_hlo_module().to_string(opts)


def step_module(modules: Dict[str, bytes], trace: btrace.Trace,
                device: str) -> Optional[bytes]:
    """The module of the program that held ``device`` longest in the
    window: by its name on the ``XLA Modules`` line, else by the name
    before its id (the largest such module)."""
    lo, hi = trace.window()
    held: Dict[str, int] = {}
    for n, s, d in trace.planes[device].get(MODULES_LINE, []):
        held[n] = held.get(n, 0) + max(0, min(s + d, hi) - max(s, lo))
    if not held:
        return None
    name = max(held, key=held.get)
    if name in modules:
        return modules[name]
    base = name.split("(", 1)[0]
    same = [p for n, p in modules.items() if n.split("(", 1)[0] == base]
    return max(same, key=len) if same else None


def newest_xplane(out_dir: Path) -> Optional[Path]:
    files = sorted(Path(out_dir).glob("*/trace/**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def from_art(art: dict) -> Optional[dict]:
    """The scope reduction of a traced training window, computed once per
    artifact and printed as ``bench: scopes {...}``: ``art["trace"]``
    timed by the module the profiler wrote into the window's
    ``.xplane.pb`` (under ``BENCH_OUT``, where ``run.py`` keeps it until
    its readers are done).  ``None`` where the program names no phase scope
    or the trace holds no module of the step."""
    if art.get("kind") != "train":
        return None
    if "scopes" not in art:
        tr = art["trace"]
        dev = tr.devices[0]
        xplane = newest_xplane(BENCH_OUT)
        proto = None if xplane is None else step_module(
            hlo_modules(xplane), tr, dev)
        art["scopes"] = None if proto is None else reduce(
            module_text(proto), tr, dev, art["steps"])
        print(f"bench: scopes {json.dumps(art['scopes'])}", file=sys.stderr,
              flush=True)
    return art["scopes"]


def phase_ms(art: dict, phase: str) -> Optional[float]:
    """A phase's device ms per step of a traced training window."""
    sc = from_art(art)
    return None if sc is None else sc["phases"][phase]["ms"]


def part_ms(art: dict, part: str) -> Optional[float]:
    """A part's device ms per step of a traced training window (0 where
    the program names its phases but no operation of the part ran)."""
    sc = from_art(art)
    if sc is None:
        return None
    return sc["parts"].get(part, {"ms": 0.0})["ms"]
