#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control 3 \
        --fault 3 [--diag 3] [--base-seed N] [--seed N ...]

One process builds the cell's program once and, for each of ``--seeds``
seeds, reads the gaps between what the timed path produced and the plain
reference (the lower readings).  On the first ``--control`` seeds it also
reads the control (the reference one precision lower, put in the
program's place), on the first ``--fault`` seeds the cell's planted
faults (the reference put in the program's place with the fault in it),
and on the first ``--diag`` seeds a reading that looks for the cause of
the gaps.  Each seed's readings are one JSON line on standard output.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--fault", type=int, default=3)
    ap.add_argument("--diag", type=int, default=0)
    ap.add_argument("--base-seed", type=int, default=3_000_000_011)
    ap.add_argument("--seed", type=int, action="append", default=[],
                    help="a seed read first, before the --seeds drawn from "
                    "--base-seed (repeatable)")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.resolve(args.workload)
    devices = jax.devices()[:int(cell.workload["chips"])]
    ctx = harness.Ctx(cell=cell, seed=args.base_seed, seconds=0.0,
                      trace=False, t0=time.perf_counter(),
                      outdir=ROOT / ".bench_out" / "calibrate",
                      devices=devices,
                      peaks=harness.peaks_for(devices[0].device_kind))
    seeds = args.seed + [args.base_seed + 7919 * i
                         for i in range(args.seeds)]
    for rec in cell.driver.calibrate(ctx, seeds, args.control, args.fault,
                                     args.diag):
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
