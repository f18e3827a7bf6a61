"""Cells of BENCHMARK.json at sizes a CPU test run can hold."""
import json
import shutil
import time
from pathlib import Path
from typing import Iterable, Optional, Sequence

import jax

from bench import harness

DATA = Path(__file__).resolve().parent / "data"


def train_cells() -> list:
    """The cells of BENCHMARK.json whose driver is ``train_step``."""
    return [w["name"] for w in harness.spec()["workloads"]
            if harness.load_json(harness.BENCH / "workloads"
                                 / f"{w['name']}.json")["driver"]
            == "train_step"]


def tiny(name: str, bench: Path = harness.BENCH, **workload
         ) -> harness.Cell:
    """The cell at the sizes its configuration file's ``tiny`` block
    gives, with ``workload`` laid over its workload file."""
    cell = harness.resolve(name, bench=bench)
    small = cell.config["tiny"]
    cell.config = dict(cell.config, **small["config"])
    cell.workload = dict(cell.workload, **small["workload"], **workload)
    return cell


def add_cell(root: Path, workload: dict, *, traffic: str, why: str,
             reports: Sequence[str], metrics: Iterable[dict] = (),
             files: Iterable[str] = (), config: Optional[dict] = None
             ) -> Path:
    """A copy of the benchmark under ``root`` with one more cell, added as
    files and entries alone: its workload file, its entry in
    BENCHMARK.json, its name in the ``workloads`` of the metrics it
    ``reports``, and the per-layer ``metrics`` it adds; ``files`` (paths
    under ``bench/tests/data``) go to the same paths under the copy's
    ``bench``, and ``config`` is a new entry of its ``configs``.  No file
    of the copy is overwritten.  Returns the copy's ``bench`` directory."""
    bench = root / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for rel in files:
        dst = bench / rel
        if dst.exists():
            raise FileExistsError(dst)
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copyfile(DATA / rel, dst)
    name = workload["name"]
    wl_file = bench / "workloads" / f"{name}.json"
    if wl_file.exists():
        raise FileExistsError(wl_file)
    wl_file.write_text(json.dumps(workload))
    spec = harness.spec()
    if config is not None:
        spec["configs"].append(config)
    spec["workloads"].append({"name": name, "config": workload["config"],
                              "traffic": traffic,
                              "chips": workload["chips"], "why": why})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in reports and "workloads" in m:
            m["workloads"].append(name)
    spec["per_layer"].extend(metrics)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return bench


def ctx(cell: harness.Cell, tmp_path: Path, seed: int = 2**33 + 17,
        seconds: float = 0.5) -> harness.Ctx:
    """What ``run.py`` hands a driver, minus the look for a chip."""
    return harness.Ctx(cell=cell, seed=seed, seconds=seconds, trace=False,
                       t0=time.perf_counter(), outdir=tmp_path,
                       devices=jax.devices()[:1],
                       peaks=harness.peaks_for("TPU v5 lite"))


def correct(out: harness.Outcome) -> bool:
    return all(c.ok for c in out.checks) and out.failed == 0
