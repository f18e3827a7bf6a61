"""From a profiler trace to numbers: the one reduction every cell uses.

A trace is held in a neutral form (``Trace``): planes, their lines, and
events ``(name, start_ns, duration_ns)``.  ``load`` reads the
``.xplane.pb`` that ``jax.profiler`` writes; ``Trace.from_json`` reads the
same form from a small recorded file (the tests' input).

On a TPU the device planes are ``/device:TPU:<i>``; their ``XLA Ops``
line holds one event per operation executed.  Host planes carry the
``TraceAnnotation`` spans of the benchmark and of the program.

All readings are clipped to the window: the ``bench.window`` span on a
host plane where there is one, else the extent of the device events.
"""
from __future__ import annotations

import contextlib
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, duration_ns)

DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
# the opcode of an HLO instruction's text: the first word after the shape
# that opens a parenthesis
OPCODE_RE = re.compile(r"\s([a-z][\w\-]*)\(")


def op_name(text: str) -> str:
    """``fusion.12`` of ``%fusion.12 = bf16[..] fusion(...)``."""
    return text.split(" = ", 1)[0].lstrip("%")


def opcode(text: str) -> str:
    """``fusion`` of ``%fusion.12 = bf16[..] fusion(...)``; without an
    instruction's text, the name up to its first dot."""
    if " = " in text:
        m = OPCODE_RE.search(" " + text.split(" = ", 1)[1])
        if m:
            return m.group(1)
    return op_name(text).split(".", 1)[0]


@contextlib.contextmanager
def span(name: str):
    """A host span on the profiler's clock (a no-op when not tracing)."""
    import jax.profiler

    with jax.profiler.TraceAnnotation(name):
        yield


def start(trace_dir: str) -> None:
    """Start the profiler with Python's own function tracing off: it would
    slow the host loop it is meant to observe."""
    import jax.profiler

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge [start, end) intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Iterable[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Tuple[int, int]], b: List[Tuple[int, int]]
             ) -> List[Tuple[int, int]]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: Iterable[Event], lo: int, hi: int
               ) -> List[Tuple[str, int]]:
    """(name, own time) of each event clipped to [lo, hi): its time less
    that of the events nested in it on the same line (a loop's time is
    its body's)."""
    evs = sorted(((n, max(s, lo), min(s + d, hi)) for n, s, d in events
                  if min(s + d, hi) > max(s, lo)),
                 key=lambda e: (e[1], -e[2]))
    own = [e[2] - e[1] for e in evs]
    stack: List[int] = []
    for i, (_, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, evs[stack[-1]][2]) - s
        stack.append(i)
    return [(evs[i][0], own[i]) for i in range(len(evs))]


@dataclass
class Trace:
    # plane name → line name → events
    planes: Dict[str, Dict[str, List[Event]]] = field(default_factory=dict)

    # ---- input -------------------------------------------------------------
    @classmethod
    def from_json(cls, path) -> "Trace":
        with open(path) as f:
            raw = json.load(f)
        return cls({p: {ln: [tuple(e) for e in evs]
                        for ln, evs in lines.items()}
                    for p, lines in raw["planes"].items()})

    def to_json(self, path, *, max_events: Optional[int] = None) -> None:
        def cut(evs):
            return evs if max_events is None else evs[:max_events]
        with open(path, "w") as f:
            json.dump({"planes": {p: {ln: [list(e) for e in cut(evs)]
                                      for ln, evs in lines.items()}
                                  for p, lines in self.planes.items()}}, f)

    # ---- what is in it -----------------------------------------------------
    @property
    def devices(self) -> List[str]:
        devs = [p for p in self.planes if DEVICE_RE.match(p)]
        return sorted(devs, key=lambda p: int(DEVICE_RE.match(p).group(1)))

    def host_events(self) -> List[Event]:
        return [e for p, lines in self.planes.items()
                if not DEVICE_RE.match(p)
                for evs in lines.values() for e in evs]

    def window(self) -> Tuple[int, int]:
        spans = [(s, s + d) for n, s, d in self.host_events()
                 if n == WINDOW_SPAN]
        if spans:
            return min(s for s, _ in spans), max(e for _, e in spans)
        evs = [e for d in self.devices
               for e in self.planes[d].get(OPS_LINE, [])]
        if not evs:
            raise ValueError("trace holds no device operation")
        return (min(s for _, s, _ in evs),
                max(s + d for _, s, d in evs))

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) * 1e-9

    def ops(self, device: str) -> List[Event]:
        lo, hi = self.window()
        return [e for e in self.planes[device].get(OPS_LINE, [])
                if e[1] < hi and e[1] + e[2] > lo]

    # ---- readings ----------------------------------------------------------
    def busy(self, device: str) -> List[Tuple[int, int]]:
        lo, hi = self.window()
        return clip(union((s, s + d) for _, s, d in self.ops(device)), lo, hi)

    def busy_s(self, device: str) -> float:
        return total(self.busy(device)) * 1e-9

    def idle_pct(self, device: str) -> float:
        return 100.0 * (1.0 - self.busy_s(device) / self.window_s())

    def label_at(self, t: int, labels: Optional[Sequence[str]] = None
                 ) -> str:
        """The innermost host span around time ``t`` (``idle`` if none)."""
        best = None
        for n, s, d in self.host_events():
            if n == WINDOW_SPAN or not (s <= t < s + d):
                continue
            if labels is not None and n not in labels:
                continue
            if best is None or d < best[1]:
                best = (n, d)
        return best[0] if best else "idle"

    def breakdown(self, device: str, labels: Optional[Sequence[str]] = None,
                  top: int = 10) -> dict:
        """The device operations that took most time (their own time,
        without the operations nested in them), and the longest idle gaps,
        each gap named by the host span that encloses it."""
        by_op: Dict[str, int] = {}
        lo, hi = self.window()
        for n, t in self_times(self.ops(device), lo, hi):
            key = f"{op_name(n)} ({opcode(n)})"
            by_op[key] = by_op.get(key, 0) + t
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        busy = self.busy(device)
        gaps = subtract([(lo, hi)], busy)
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, v * 1e-9] for n, v in ops],
                "idle_gaps": [[self.label_at((s + e) // 2, labels),
                               (e - s) * 1e-9] for s, e in gaps]}


def load(trace_dir, chips: int = 1) -> Trace:
    """Read the ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in pd.planes:
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            evs = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                   for ev in line.events]
            if evs:
                lines.setdefault(line.name, []).extend(evs)
        if lines:
            planes[plane.name] = lines
    return Trace(planes)
