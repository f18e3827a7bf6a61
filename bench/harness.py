"""What every cell shares: finding files by name, the device, the result.

The benchmark is driven by data.  ``BENCHMARK.json`` names the cells and
metrics; each is found here by its name alone:

* ``workloads/<cell>.json``  — the cell: its config, driver kind, chips,
  traffic parameters and correctness limits;
* ``configs/<config>.json``  — the sizes as run, the ``family`` of the
  program's models they belong to, and ``tiny``: the sizes a CPU test run
  holds (``{"config": {...}, "workload": {...}}``, each laid over its file);
* ``configs/<config>_ref.py`` — the plain reference of the model;
* ``families/<family>.py``   — the family's side of the program;
* ``drivers/<kind>.py``      — ``run(ctx) -> Outcome``;
* ``metrics/<metric>.py``    — ``read(art) -> float | None``.

A family module gives

* ``program_config(cfg) -> ModelConfig``: the program's configuration at
  the sizes ``cfg`` states;
* ``forward_per_token(cfg, seq_len) -> float``: the model FLOPs of one
  forward pass per token of a ``seq_len``-token sequence, of this chip's
  share of the model (matrix products and the like, two FLOPs a
  multiply-add; nothing elementwise).

A reference module imports nothing of the program and gives

* ``layout(cfg) -> {path: (shape, dtype)}``: every parameter the step is
  given, paths joined by ``/``;
* ``init(key, cfg)``: random weights in those dtypes, as a nested dict,
  made on the device in one jitted call;
* ``loss(params, tokens, targets, cfg, q=None, prec=HIGHEST, act=None)``:
  the mean next-token cross-entropy of float32 ``params``, every product
  at precision ``prec``, with ``q`` applied to the inputs of every product
  and ``act`` to the residual stream after the embedding and each layer
  (``bench/eq7_ref.py`` passes its roundings).

A later configuration, cell or metric is new files; nothing here changes
for it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


# ---------------------------------------------------------------------------
# lookup by name
# ---------------------------------------------------------------------------

def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def spec(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def load_module(path: Path, name: Optional[str] = None) -> ModuleType:
    """Import a file by path (metric files carry dots in their names)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_name = name or "bench_dyn_" + path.stem.replace(".", "_").replace(
        "-", "_")
    sp = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(sp)
    sys.modules[mod_name] = mod
    sp.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict            # workloads/<name>.json
    config: dict              # configs/<config>.json
    reference: ModuleType     # configs/<config>_ref.py
    family: ModuleType        # families/<family>.py
    driver: ModuleType        # drivers/<kind>.py
    e2e: List[dict]           # BENCHMARK.json end_to_end entries it reports
    per_layer: List[dict]     # BENCHMARK.json per_layer entries it reports


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(cell: str, bench: Path = BENCH, spec_: Optional[dict] = None
            ) -> Cell:
    """Everything one cell needs, found by name under ``bench``."""
    sp = spec_ if spec_ is not None else spec(bench.parent)
    entry = next((w for w in sp["workloads"] if w["name"] == cell), None)
    if entry is None:
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json")
    wl = load_json(bench / "workloads" / f"{cell}.json")
    if wl["config"] != entry["config"] or wl["chips"] != entry["chips"]:
        raise ValueError(f"workloads/{cell}.json disagrees with "
                         "BENCHMARK.json on config or chips")
    cfg_name = entry["config"]
    cfg_file = bench / "configs" / f"{cfg_name}.json"
    cfg = load_json(cfg_file)
    fam_file = bench / "families" / f"{cfg.get('family')}.py"
    if not fam_file.is_file():
        raise FileNotFoundError(f"{cfg_file.name} names family "
                                f"{cfg.get('family')!r}: no {fam_file}")
    ref = load_module(bench / "configs" / f"{cfg_name}_ref.py")
    fam = load_module(fam_file)
    drv = load_module(bench / "drivers" / f"{wl['driver']}.py")
    e2e = [m for m in sp["end_to_end"] if _reports(m, cell)]
    per_layer = [m for m in sp["per_layer"] if _reports(m, cell)]
    return Cell(cell, wl, cfg, ref, fam, drv, e2e, per_layer)


def metric_reader(name: str, bench: Path = BENCH) -> Callable:
    return load_module(bench / "metrics" / f"{name}.py").read


# ---------------------------------------------------------------------------
# what a driver hands back
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    e2e: Dict[str, float]                 # end-to-end values by name
    t_window: float                       # perf_counter at the window start
    setup_split: Dict[str, float]         # seconds by set-up phase
    compiles_in_window: int
    attempted: int
    failed: int
    checks: List[Check]
    art: Dict[str, Any] = dataclasses.field(default_factory=dict)
    memory_peak_bytes: Optional[int] = None
    busy_s: Optional[float] = None        # traced runs only
    window_s: Optional[float] = None      # traced runs only
    breakdown: Optional[dict] = None      # traced runs only


@dataclasses.dataclass
class Ctx:
    """What a driver is given: the cell, its seed and window, a clock."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t0: float                             # perf_counter at process start
    outdir: Path                          # traces and dumps of this run
    devices: Any = None                   # the chips of this cell
    peaks: Optional[dict] = None          # their published peaks
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)
    _last: float = 0.0

    def phase(self, name: str) -> None:
        """Close the set-up phase ``name`` at now."""
        now = time.perf_counter()
        start = self._last or self.t0
        self.phases[name] = self.phases.get(name, 0.0) + now - start
        self._last = now


# ---------------------------------------------------------------------------
# compilations, devices, memory
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts executables built or loaded from the persistent cache.

    JAX records ``/jax/core/compile/backend_compile_duration`` once per
    executable it compiles or loads; a warm in-memory cache records none.
    """
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon
        self.n = 0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.n += 1


def device_info(devices) -> dict:
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> Optional[int]:
    """Peak bytes in use on the fullest of ``devices``, as JAX reports."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def peaks_for(kind: str, bench: Path = BENCH) -> dict:
    """Published peaks of one chip of ``kind``; an unknown kind is an error."""
    table = load_json(bench / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device_kind {kind!r}; "
                       f"known: {sorted(table)}")
    return table[kind]


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

def result_line(cell: Cell, out: Outcome, *, setup_s: float, trace: bool,
                device: dict, per_layer: Dict[str, float]) -> Tuple[str, str]:
    """(stderr tail, stdout JSON line) of one run."""
    if trace:
        metrics = {m["name"]: {"value": per_layer[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.per_layer if m["name"] in per_layer}
    else:
        vals = dict(out.e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in cell.e2e}
    dev = dict(device, memory_peak_bytes=out.memory_peak_bytes)
    if trace:
        dev["busy_s"] = out.busy_s
        dev["window_s"] = out.window_s
    correct = bool(out.checks) and all(c.ok for c in out.checks) \
        and out.failed == 0
    line: Dict[str, Any] = {"correct": correct, "attempted": out.attempted,
                            "failed": out.failed, "metrics": metrics,
                            "device": dev}
    if trace and out.breakdown:
        line["breakdown"] = out.breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    tail = "\n".join(f"check {c.name} {c.value!r} limit {c.limit!r} "
                     f"{'ok' if c.ok else 'FAIL'}" for c in out.checks)
    return tail, json.dumps(line)
