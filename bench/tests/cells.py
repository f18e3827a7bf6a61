"""Cells of BENCHMARK.json at sizes a CPU test run can hold."""
import json
import shutil
import time
from pathlib import Path
from typing import Iterable, Sequence

import jax

from bench import harness

TINY = {
    "mamba2_370m": (
        dict(d_model=128, n_layer=2, vocab_size=512, d_state=32,
             headdim=32, chunk_size=32),
        dict(batch=2, seq_len=64, pool=4, ref_block_rows=1)),
}


def tiny(name: str, bench: Path = harness.BENCH, **workload
         ) -> harness.Cell:
    cell = harness.resolve(name, bench=bench)
    cfg_kw, wl_kw = TINY[cell.config["name"]]
    cell.config = dict(cell.config, **cfg_kw)
    cell.workload = dict(cell.workload, **wl_kw, **workload)
    return cell


def add_cell(root: Path, workload: dict, *, traffic: str, why: str,
             reports: Sequence[str], metrics: Iterable[dict] = ()
             ) -> Path:
    """A copy of the benchmark under ``root`` with one more cell, added as
    files and entries alone: its workload file, its entry in
    BENCHMARK.json, its name in the ``workloads`` of the metrics it
    ``reports``, and the per-layer ``metrics`` it adds.  Returns the copy's
    ``bench`` directory."""
    bench = root / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    name = workload["name"]
    (bench / "workloads" / f"{name}.json").write_text(json.dumps(workload))
    spec = harness.spec()
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
