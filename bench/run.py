#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (import, weights and data from the seed, compile or cache load,
warm-up) is timed as ``setup_s``; then the cell's driver measures for
``--seconds`` seconds and checks what the timed path produced against the
plain reference.  ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the window.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, then ``checks``: each
number compared beside its limit); the last lines of standard error repeat
the checks.  Without a TPU, or with fewer chips than the cell asks for, the
run prints no result and exits 2.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

# traces and dumps of the current run; fixed, inside the checkout
OUT_DIR = ROOT / ".bench_out"


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"bench: {msg}; no result", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    args = parse(argv)
    cell = harness.resolve(args.workload)
    chips = int(cell.workload["chips"])

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        return fail(f"cell {cell.name} needs {chips} chips, JAX sees "
                    f"{len(devices)}")
    devices = devices[:chips]
    device = harness.device_info(devices)
    peaks = harness.peaks_for(device["kind"])

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    # every program, however small, goes to the cache: a run after the
    # first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"bench: {cell.name} seed={args.seed} device={device} "
          f"cache={cache}", file=sys.stderr, flush=True)

    outdir = OUT_DIR / cell.name
    shutil.rmtree(outdir, ignore_errors=True)
    outdir.mkdir(parents=True)
    ctx = harness.Ctx(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t0=T0, outdir=outdir,
                      devices=devices, peaks=peaks)
    out = cell.driver.run(ctx)
    setup_s = out.t_window - T0

    per_layer = {}
    if args.trace:
        for m in cell.per_layer:
            v = harness.metric_reader(m["name"])(out.art)
            if v is not None:
                per_layer[m["name"]] = v
    shutil.rmtree(outdir, ignore_errors=True)

    split = " ".join(f"{k}={v:.3f}" for k, v in out.setup_split.items())
    print(f"bench: setup_s={setup_s:.3f} ({split}); compiles in window: "
          f"{out.compiles_in_window}", file=sys.stderr)
    tail, line = harness.result_line(cell, out, setup_s=setup_s,
                                     trace=bool(args.trace), device=device,
                                     per_layer=per_layer)
    print(tail, file=sys.stderr, flush=True)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter teardown: nothing may print after the result
    os._exit(code)
